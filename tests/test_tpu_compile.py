"""Compile the serving path's Pallas kernels for a TPU v5e chip.

No chip is attached: the TPU compiler compiles for a *described* v5e
topology, so these tests catch what interpret mode cannot (block shapes
Mosaic refuses, scalar stores into VMEM, VMEM over-use) at gpt2-345m
widths: 16 heads of 64, page 16, max_seq 1024, 8 slots, chunk 32, and
d_model 1024 / d_ff 4096 for the matmuls.  The paged attention kernel
also compiles at the benchmark's widths: gpt2-345m decode over 32 slots
and a one-row 32-token prefill chunk, and minitron-4b (24 query heads
over 8 KV heads of 128, max_seq 4096, 8 slots).  Each compiled program
must call its kernel (``tpu_custom_call``), and the paged kernel's HLO
instruction must carry the name the benchmark's trace reader matches.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and it keeps it until it
exits, so under several test workers only the worker given this file
loads it.  Keep these compiles in this one file.
"""
import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H, D, PAGE, MAX_SEQ, SLOTS, CHUNK = 16, 64, 16, 1024, 8, 32
D_MODEL, D_FF = 1024, 4096
N_PG = MAX_SEQ // PAGE
POOL = 1 + SLOTS * N_PG
#: the benchmark's slots for gpt2-345m, and minitron-4b's widths
GPT2_SLOTS = 32
MT_H, MT_HKV, MT_D, MT_NPG, MT_SLOTS = 24, 8, 128, 4096 // PAGE, 8


def _paged_attn_match() -> str:
    """The pattern ``bench/costs/paged_attn.py`` finds the kernel by."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "costs"
            / "paged_attn.py")
    spec = importlib.util.spec_from_file_location("paged_attn_costs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.MATCH


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise write its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        # entries compiled for a described chip cannot be read back here
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            from jax.experimental import topologies

            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _cases(sds):
    bf, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
    pages = sds((POOL, H, PAGE, D), bf)
    return {
        "paged_decode": (
            lambda q, k, v, ln, bt: ops.paged_mha_decode(
                q, k, v, ln, bt, backend="pallas"),
            [sds((SLOTS, H, D), bf), pages, pages, sds((SLOTS,), i32),
             sds((SLOTS, N_PG), i32)]),
        "paged_verify_linear": (
            lambda q, k, v, b, bt: ops.paged_verify(
                q, k, v, b, bt, backend="pallas"),
            [sds((SLOTS, CHUNK, H, D), bf), pages, pages,
             sds((SLOTS,), i32), sds((SLOTS, N_PG), i32)]),
        "paged_verify_tree": (
            lambda q, k, v, b, bt, anc: ops.paged_verify(
                q, k, v, b, bt, anc=anc, backend="pallas"),
            [sds((SLOTS, CHUNK, H, D), bf), pages, pages,
             sds((SLOTS,), i32), sds((SLOTS, N_PG), i32),
             sds((SLOTS, CHUNK, CHUNK), jnp.bool_)]),
        "paged_decode_gpt2_32_slots": (
            lambda q, k, v, ln, bt, a: ops.paged_mha_decode(
                q, k, v, ln, bt, active=a, backend="pallas"),
            [sds((GPT2_SLOTS, H, D), jnp.float32),
             sds((1 + GPT2_SLOTS * N_PG, H, PAGE, D), bf),
             sds((1 + GPT2_SLOTS * N_PG, H, PAGE, D), bf),
             sds((GPT2_SLOTS,), i32), sds((GPT2_SLOTS, N_PG), i32),
             sds((GPT2_SLOTS,), jnp.bool_)]),
        "paged_prefill_gpt2": (
            lambda q, k, v, b, bt: ops.paged_verify(
                q, k, v, b, bt, backend="pallas"),
            [sds((1, CHUNK, H, D), jnp.float32),
             sds((1 + GPT2_SLOTS * N_PG, H, PAGE, D), bf),
             sds((1 + GPT2_SLOTS * N_PG, H, PAGE, D), bf),
             sds((1,), i32), sds((1, N_PG), i32)]),
        "paged_decode_minitron": (
            lambda q, k, v, ln, bt, a: ops.paged_mha_decode(
                q, k, v, ln, bt, active=a, backend="pallas"),
            [sds((MT_SLOTS, MT_H, MT_D), bf),
             sds((1 + MT_SLOTS * MT_NPG, MT_HKV, PAGE, MT_D), bf),
             sds((1 + MT_SLOTS * MT_NPG, MT_HKV, PAGE, MT_D), bf),
             sds((MT_SLOTS,), i32), sds((MT_SLOTS, MT_NPG), i32),
             sds((MT_SLOTS,), jnp.bool_)]),
        "paged_prefill_minitron": (
            lambda q, k, v, b, bt: ops.paged_verify(
                q, k, v, b, bt, backend="pallas"),
            [sds((1, CHUNK, MT_H, MT_D), bf),
             sds((1 + MT_SLOTS * MT_NPG, MT_HKV, PAGE, MT_D), bf),
             sds((1 + MT_SLOTS * MT_NPG, MT_HKV, PAGE, MT_D), bf),
             sds((1,), i32), sds((1, MT_NPG), i32)]),
        "mha_decode_stacked": (
            lambda q, k, v, ln: ops.mha_decode(q, k, v, ln, backend="pallas"),
            [sds((SLOTS, H, D), bf), sds((SLOTS, H, MAX_SEQ, D), bf),
             sds((SLOTS, H, MAX_SEQ, D), bf), sds((SLOTS,), i32)]),
        "quant_matmul_decode": (
            lambda x, w, xs, ws: ops.quant_matmul(
                x, w, xs, ws, backend="pallas"),
            [sds((SLOTS, D_MODEL), i8), sds((D_MODEL, D_FF), i8),
             sds((SLOTS, 1), f32), sds((1, D_FF), f32)]),
        "quant_matmul_prefill": (
            lambda x, w, xs, ws: ops.quant_matmul(
                x, w, xs, ws, backend="pallas"),
            [sds((CHUNK, D_MODEL), i8), sds((D_MODEL, D_FF), i8),
             sds((CHUNK, 1), f32), sds((1, D_FF), f32)]),
        "ln_res": (
            lambda x, r, w, b: ops.ln_res(x, r, w, b, backend="pallas"),
            [sds((SLOTS, D_MODEL), bf), sds((SLOTS, D_MODEL), f32),
             sds((D_MODEL,), f32), sds((D_MODEL,), f32)]),
    }


@pytest.mark.parametrize("name", [
    "paged_decode", "paged_verify_linear", "paged_verify_tree",
    "mha_decode_stacked", "quant_matmul_decode", "quant_matmul_prefill",
    "ln_res", "paged_decode_gpt2_32_slots", "paged_prefill_gpt2",
    "paged_decode_minitron", "paged_prefill_minitron"])
def test_kernel_compiles_for_v5e(one_chip, name):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _cases(sds)[name]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    if name.startswith("paged"):
        kernels = re.findall(r"^\s*(?:ROOT )?(%\S+) = .*"
                             r'custom_call_target="tpu_custom_call"', hlo,
                             re.MULTILINE)
        assert any(re.match(_paged_attn_match(), k) for k in kernels), \
            kernels
