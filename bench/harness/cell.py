"""One run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (timed as ``setup_s``, from process start to the first due
request): the device check, weights from the seed in one jitted call,
the engine the configuration names on the cell's chips (one chip:
``ServeEngine``; four: ``DistributedServeEngine`` over a ``("shard",)``
mesh of them, the configuration's slots on each), and one warm-up
request through a full and a partial prefill chunk and two decode
steps, which compiles every shape the window uses.  Then the open loop
runs for the traffic file's lead-in and ``--seconds`` more; the window
is those seconds.  After each tick the loop waits for the pool the tick
left (one chip) or the tick before it left (four chips, whose tick is
pipelined; ``harness/drive.py``).

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the records and from a
profiler trace of the window.  Every run then frees the engine, makes
the weights again from the seed and compares a sample of the served
requests with the plain float32 reference (``harness/check.py``).

The last stdout line is the result; the last stderr lines are each
compared number beside its limit.  With no TPU, too few chips or a
device kind missing from ``bench/peaks.json``, the run exits 2 and
prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from types import ModuleType
from typing import Any, Callable, Dict, List, NamedTuple, Optional

from harness import check, device, drive, e2e, params, spec, traffic
from harness import trace as tracing

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write the trace's planes, lines and sample "
                         "events to this JSON file")
    return ap


class CompileCounter:
    """Backend compilations (all, and while ``armed``) and persistent
    cache misses."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self.total = 0
        self.seconds = 0.0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.total += 1
            self.seconds += duration
            self.count += self.armed

    def _on_event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self.misses += 1


#: program-config fields checked against the file's sizes, unless the
#: family's reference module names its own (``PROGRAM_KEYS``)
PROGRAM_KEYS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                "vocab_size")
#: fields always taken from the file's sizes (``PROGRAM_CUT``)
PROGRAM_CUT = ("n_layers",)


def program_config(conf: Dict, dims: Dict, ref: ModuleType):
    """The program's model config, cut as the file says, checked against
    the file's sizes.  ``"resize": true`` (test files only) takes every
    checked size from the file instead."""
    from repro.configs import get_config

    prog = conf["program"]
    keys = getattr(ref, "PROGRAM_KEYS", PROGRAM_KEYS)
    cut = getattr(ref, "PROGRAM_CUT", PROGRAM_CUT)
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **{k: dims[k] for k in cut})
    if prog.get("resize"):
        cfg = dataclasses.replace(cfg, **{k: dims[k] for k in keys})
    for key in keys:
        if getattr(cfg, key) != dims[key]:
            raise ValueError(f"{conf['name']}: the program's {key} is "
                             f"{getattr(cfg, key)}, the file says "
                             f"{dims[key]}")
    return cfg


class Model(NamedTuple):
    """A configuration as the harness serves it: the family's reference,
    the sizes, the program's config and the weights' dtype."""
    ref: ModuleType
    dims: Dict
    cfg: Any
    dtype: Any

    def weights(self, seed: int) -> Dict:
        return params.build(self.ref, self.dims, seed, self.dtype)


def model(bench: spec.Bench, conf: Dict) -> Model:
    import jax.numpy as jnp

    ref = bench.reference(conf["family"])
    dims = ref.dims(conf)
    return Model(ref, dims, program_config(conf, dims, ref),
                 getattr(jnp, conf["weights"]["dtype"]))


def build_engine(conf: Dict, cfg, weights, seed: int, telemetry,
                 chips: int):
    """The configuration's engine on the cell's ``chips``: one chip
    serves through ``ServeEngine`` with the configuration's slots; more
    through ``DistributedServeEngine`` with that many slots and one page
    pool on each chip, and the engine's own decode waves and placement."""
    import jax.numpy as jnp
    import numpy as np

    eng_conf = conf["engine"]
    quantized = eng_conf["precision"] == "w8a8"
    calib = None
    if quantized:
        rows, cols = eng_conf["calibration"]
        rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 11])
        calib = [jnp.asarray(rng.integers(1, cfg.vocab_size, (rows, cols)),
                             jnp.int32)]
    common = dict(max_seq=eng_conf["max_seq"], eos_id=-1,
                  quantized=quantized, calibration_batches=calib,
                  chunk_size=eng_conf["chunk_size"],
                  page_size=eng_conf["page_size"], telemetry=telemetry)
    if chips == 1:
        from repro.serving.engine import ServeEngine

        return ServeEngine(cfg, weights, batch_slots=eng_conf["slots"],
                           **common)
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.distributed import DistributedServeEngine

    return DistributedServeEngine(
        cfg, weights, mesh=make_serving_mesh(chips),
        slots_per_shard=eng_conf["slots"], **common)


def step_programs() -> Dict[str, str]:
    """The module names of the engines' step programs, by kind of call:
    ``jax.jit`` names a program after its function, and both engines
    name their steps as ``serving.engine.PROGRAMS`` says."""
    from repro.serving.engine import PROGRAMS

    return {kind: "jit_" + PROGRAMS[kind] for kind in ("prefill", "decode")}


def warm_up(eng, chips: int) -> None:
    """Compile every shape the window uses: a prompt one past a chunk
    (a full and a partial chunk, the first token) and two decodes."""
    eng.submit(list(range(1, eng.chunk_size + 2)), max_new=3)
    eng.run()
    drive.view(eng, chips).drain()
    eng.finished.clear()
    eng.tel.registry.reset()


def per_layer(bench: spec.Bench, cell: str, rec: Dict) -> Dict:
    out = {}
    for m in bench.per_layer(cell):
        v = m["reader"].read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def end_to_end(bench: spec.Bench, cell: str, rec: Dict,
               setup_s: float) -> Dict:
    vals = dict(e2e.metrics(rec), setup_s=setup_s)
    out = {}
    for m in bench.end_to_end(cell):
        v = vals.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(argv: Optional[List[str]] = None, *, t_start: float,
        root=spec.ROOT, require_tpu: bool = True,
        fault: Optional[Callable] = None) -> int:
    args = parser().parse_args(argv)
    bench = spec.Bench(root)
    cell = bench.workload(args.workload)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    peaks = bench.peaks()

    import jax

    try:
        dev = device.check(cell["chips"], peaks, require_tpu)
    except device.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    from repro.serving.telemetry import Telemetry

    m = model(bench, conf)
    tel = Telemetry(trace=bool(args.trace), annotate=bool(args.trace))
    eng = build_engine(conf, m.cfg, m.weights(args.seed), args.seed, tel,
                       cell["chips"])
    if fault is not None:
        fault(eng)
    warm_up(eng, cell["chips"])
    reqs = traffic.generate(mix, args.seed, args.seconds, m.cfg.vocab_size)
    gc.collect()

    annotate = drive._null
    hooks: Dict = {}
    logdir = None
    if args.trace:
        from jax.profiler import TraceAnnotation

        annotate = TraceAnnotation
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        hooks = {"before_window": lambda: jax.profiler.start_trace(logdir),
                 "pre_s": 3.0}
    opened: Dict = {}

    def on_open(t):
        opened["t"] = t
        compiles.armed = True

    def on_close(t):
        compiles.armed = False
        if args.trace:
            jax.profiler.stop_trace()

    setup_s = time.monotonic() - t_start
    setup_compiles = (compiles.total, compiles.seconds, compiles.misses)
    rec = drive.drive(eng, reqs, float(mix["lead_in_s"]), args.seconds,
                      chips=cell["chips"], annotate=annotate, on_open=on_open, on_close=on_close,
                      **hooks)
    mem = device.peak_memory(dev["devices"])

    # the served requests to compare, then the program's state goes
    served = check.served(rec["requests"])
    shards = check.shards(rec["requests"])
    flow = e2e.flow(rec)
    for r in rec["requests"]:
        r["req"] = None
    del eng
    gc.collect()

    rec.update(conf=conf, family=conf["family"], chips=cell["chips"],
               dims=m.dims, memory=mem,
               cost=bench.cost, peak=peaks["devices"].get(dev["kind"]),
               engine=conf["engine"], compiles_in_window=compiles.count,
               trace=None)
    result: Dict = {}
    if args.trace:
        tr = tracing.load(logdir)
        if args.dump_trace:
            with open(args.dump_trace, "w") as f:
                json.dump({"describe": tracing.describe(logdir),
                           "loaded": tr, "calls": rec["calls"],
                           "opened": opened.get("t")}, f)
        shutil.rmtree(logdir, ignore_errors=True)
        busy = {}
        if tr["devices"]:  # none off the chip
            offset = tracing.clock_offset(tr["host"], rec["calls"],
                                          opened["t"])
            w0, w1 = rec["window"]
            calls = [(c["kind"], c["t"] * 1e9 + offset)
                     for c in rec["calls"]]
            t = rec["trace"] = tracing.reduce(
                tr, w0 * 1e9 + offset, w1 * 1e9 + offset, cell["chips"],
                calls, step_programs())
            result["breakdown"] = t["breakdown"]
            busy = {"busy_s": t["busy_s"], "window_s": t["window_s"]}
        metrics = per_layer(bench, args.workload, rec)
    else:
        metrics = end_to_end(bench, args.workload, rec, setup_s)
        busy = {}

    # correctness: the sample against the plain reference
    chk = conf["check"]
    chosen = check.sample(served, args.seed, chk["requests"], shards)
    weights = m.weights(args.seed)
    cmp = check.compare(m.ref, weights, conf, chosen)
    del weights
    limit = chk["max_logit_gap"]
    correct = check.correct(cmp, chk, rec["failed"])

    result = {
        "correct": correct,
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": metrics,
        "device": dict({"platform": dev["platform"], "kind": dev["kind"],
                        "count": dev["count"],
                        "memory_peak_bytes": mem["peak"]}, **busy),
        **result,
        "check": {
            "max_logit_gap": {"value": cmp["max_logit_gap"],
                              "limit": limit},
            "tokens_compared": {"value": cmp["tokens_compared"],
                                "limit": chk["min_tokens"]},
        },
    }
    print(f"[bench] set-up compiled {setup_compiles[0]} programs in "
          f"{setup_compiles[1]!r} s, {setup_compiles[2]} persistent cache "
          "misses", file=sys.stderr)
    print(f"[bench] {args.workload} seed {args.seed}: setup {setup_s!r} s; "
          f"{rec['attempted']} requests offered, generator late by at most "
          f"{rec['late_max_s']!r} s; {len(rec['ticks'])} ticks; "
          f"{e2e.first_tokens_in_window(rec)} first tokens and "
          f"{e2e.tokens_in_window(rec)} tokens in the window; "
          f"{flow['due']} requests due and {flow['done']} done in the "
          f"window; without a first token {flow['waiting'][0]} at its "
          f"open and {flow['waiting'][1]} at its close, decoding "
          f"{flow['decoding'][0]} and {flow['decoding'][1]}; "
          f"{rec['compiles_in_window']} compilations in the window; "
          f"memory peak {mem['peak']} of {mem['limit']}", file=sys.stderr)
    print(f"check: max_logit_gap {cmp['max_logit_gap']!r} limit {limit!r}",
          file=sys.stderr)
    print(f"check: tokens_compared {cmp['tokens_compared']} limit "
          f"{chk['min_tokens']} (at least; {cmp['requests']} requests)",
          file=sys.stderr)
    print(json.dumps(result))
    return 0
