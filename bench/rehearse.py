"""Compile each cell's decode and prefill steps for a described TPU v5e,
with no chip, and print what the compiler says of their memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

The steps are the engine's: ``lm.decode_step`` over every slot and
``lm.prefill_into_slot`` for one chunk, on the paged cache at the cell's
slots, ``max_seq`` and page size, with the configuration's weights
(W8A8 after quantization, or bfloat16).  A four-chip cell compiles the
distributed engine's ``lm.sharded_decode_step`` and
``lm.sharded_prefill_into_slot`` over a ``("shard",)`` mesh of the
described chips instead, the weights replicated and one pool per chip;
the bytes it prints are per chip.  Only shapes are passed, so nothing is
allocated.  A compile that passes is not a chip run: it says nothing of
times.
"""
from __future__ import annotations

import os
import pathlib
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from harness import cell, spec  # noqa: E402
from harness import trace as tracing  # noqa: E402


def shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


#: an HLO instruction's opcode: the first word before a ``(`` after its
#: ``=`` (its shape holds none)
OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def collectives(hlo: str) -> list:
    """The collective opcodes in a program's HLO text, matched by the
    pattern the trace reduction uses (``trace.COLLECTIVES``)."""
    kinds = set()
    for line in hlo.splitlines():
        if " = " not in line:
            continue
        op = OPCODE.search(" " + line.split(" = ", 1)[1])
        if op and tracing.COLLECTIVES.match("%" + op.group(1)):
            kinds.add(op.group(1))
    return sorted(kinds)


def rehearse(bench: spec.Bench, name: str, topo) -> None:
    from repro.kernels import ops
    from repro.models import lm
    from repro.serving.quantize import quantize_model_params

    w = bench.workload(name)
    conf = bench.config(w["config"])
    m = cell.model(bench, conf)
    cfg = m.cfg
    e = conf["engine"]
    p = jax.eval_shape(lambda: m.weights(0))
    act = jnp.bfloat16
    if e["precision"] == "w8a8":
        p = jax.eval_shape(lambda q: quantize_model_params(q, cfg, None), p)
        act = jnp.float32
    n_pg = e["max_seq"] // e["page_size"]
    n_pages = 1 + e["slots"] * n_pg
    cache = lm.init_cache_abstract(cfg, n_pages, e["page_size"],
                                   layout="paged")
    B, C, i32 = e["slots"], e["chunk_size"], jnp.int32
    ops._on_tpu = lambda: True  # the kernels, as on the chip
    if w["chips"] == 1:
        one_chip = SingleDeviceSharding(topo.devices[0])

        def s(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        W, K = shapes(p, one_chip), shapes(cache, one_chip)
        decode = jax.jit(lambda p, tok, c, ln, bt, a: lm.decode_step(
            p, cfg, tok, c, ln, active=a, block_table=bt, dtype=act))
        prefill = jax.jit(lambda p, toks, c, slot, bt, off, n:
                          lm.prefill_into_slot(p, cfg, toks, c, slot, off,
                                               valid=n, block_table=bt,
                                               dtype=act))
        steps = (
            ("decode", decode, (W, s((B, 1), i32), K, s((B,), i32),
                                s((B, n_pg), i32), s((B,), jnp.bool_))),
            ("prefill", prefill, (W, s((C,), i32), K, s((), i32),
                                  s((n_pg,), i32), s((), i32), s((), i32))))
    else:
        D = w["chips"]
        mesh = Mesh(topo.devices[:D], ("shard",))
        shard = NamedSharding(mesh, P("shard"))

        def s(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=shard)

        W = shapes(p, NamedSharding(mesh, P()))
        K = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((D,) + a.shape, a.dtype,
                                           sharding=shard), cache)
        decode = jax.jit(lambda p, tok, c, ln, bt, a: lm.sharded_decode_step(
            p, cfg, mesh, tok, c, ln, actives=a, block_tables=bt,
            dtype=act))
        prefill = jax.jit(
            lambda p, toks, c, slots, offs, n, a, bts:
            lm.sharded_prefill_into_slot(p, cfg, mesh, toks, c, slots, offs,
                                         n, a, block_tables=bts, dtype=act))
        steps = (
            ("decode", decode, (W, s((D, B, 1), i32), K, s((D, B), i32),
                                s((D, B, n_pg), i32),
                                s((D, B), jnp.bool_))),
            ("prefill", prefill, (W, s((D, C), i32), K, s((D,), i32),
                                  s((D,), i32), s((D,), i32),
                                  s((D,), jnp.bool_), s((D, n_pg), i32))))
    for label, fn, args in steps:
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        kernels = text.count("tpu_custom_call")
        colls = collectives(text)
        print(f"[rehearse] {name} {label}: compiled in "
              f"{time.perf_counter() - t0:.1f} s; {kernels} "
              f"tpu_custom_call sites; collectives {colls}; arguments "
              f"{ma.argument_size_in_bytes}, outputs "
              f"{ma.output_size_in_bytes}, temporaries "
              f"{ma.temp_size_in_bytes}, aliased {ma.alias_size_in_bytes} "
              "bytes", flush=True)


def main(argv) -> int:
    bench = spec.Bench()
    names = argv or [w["name"] for w in bench.spec["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    for name in names:
        rehearse(bench, name, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
