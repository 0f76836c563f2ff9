"""Fused Matrix-Processing (MP) Pallas kernel — LoopLynx's Fused MP MDK on TPU.

The paper's Fused MP kernel (Fig 6a) chains DMA -> MAC array -> quantization
unit -> router through FIFOs so one large kernel serves *every* linear layer.
The TPU-native equivalent below fuses the whole chain into one Pallas kernel:

  HBM->VMEM block DMA (BlockSpec pipeline)      <- paper's burst DMA engines
  int8 x int8 -> int32 MXU matmul               <- paper's MAC slices
  dequant (per-token x per-channel) + bias      <- paper's quantization unit
  epilogue writes bf16 activations               (router is the ring layer,
                                                  see core/ring.py)

Grid is (M/bm, N/bn, K/bk), K innermost; an int32 VMEM scratch accumulates
across K blocks so the MXU never leaves int8 x int8 -> int32.  Block shapes
default to 128 — MXU systolic alignment — and the ``ops.py`` wrapper pads
ragged edges.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mp_kernel(
    x_ref,  # (bm, bk) int8
    w_ref,  # (bk, bn) int8
    xs_ref,  # (bm, 1) f32
    ws_ref,  # (1, bn) f32
    b_ref,  # (1, bn) f32
    o_ref,  # (bm, bn) out_dtype
    acc_ref,  # (bm, bn) int32 VMEM scratch
    *,
    n_k: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32)
        y = y * xs_ref[...] * ws_ref[...]  # dequant: per-token x per-channel
        y = y + b_ref[...]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def mp_matmul(
    x_q: jax.Array,  # (M, K) int8
    w_q: jax.Array,  # (K, N) int8
    x_scale: jax.Array,  # (M, 1) f32
    w_scale: jax.Array,  # (1, N) f32
    bias: jax.Array,  # (N,) f32
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """Fused W8A8 matmul; shapes must be multiples of the block shape."""
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0, (
        (M, K, N),
        (bm, bn, bk),
    )
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_mp_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_q, w_q, x_scale, w_scale, bias[None, :])
