"""Fused LayerNorm & Residual Pallas kernel — LoopLynx's Fused LN&Res MDK.

The paper fuses the critical-path operators between matmuls — residual add
and layer normalization — into one overlapped kernel (Fig 4a, -11 % latency).
On TPU the same economics hold as HBM traffic: an unfused chain reads/writes
the (B, D) activation three times; this kernel does residual-add, norm,
scale/shift *and* the SmoothQuant per-token int8 activation quantization for
the next linear layer in a single HBM pass, emitting:

  y      bf16  — normalized output (for unquantized consumers)
  r      bf16  — updated residual stream
  y_q    int8  — quantized activations for the next Fused MP kernel
  scale  f32   — per-token dequant scales

so a transformer block's norm->linear edge costs one read and one write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ln_res_kernel(
    x_ref,  # (bb, D)
    r_ref,  # (bb, D)
    w_ref,  # (1, D)
    b_ref,  # (1, D)
    y_ref,  # (bb, D) bf16
    rn_ref,  # (bb, D) residual dtype
    q_ref,  # (bb, D) int8
    s_ref,  # (bb, 1) f32
    *,
    kind: str,
    eps: float,
):
    r = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(r, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(r - mu), axis=-1, keepdims=True)
        y = (r - mu) * jax.lax.rsqrt(var + eps)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(r), axis=-1, keepdims=True)
        y = r * jax.lax.rsqrt(ms + eps)
    y = y * w_ref[...] + b_ref[...]
    amax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(y / scale), -127, 127)

    y_ref[...] = y.astype(y_ref.dtype)
    rn_ref[...] = r.astype(rn_ref.dtype)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(
    jax.jit, static_argnames=("kind", "eps", "bb", "interpret")
)
def ln_res(
    x: jax.Array,  # (B, D)
    res: jax.Array,  # (B, D)
    weight: jax.Array,  # (D,)
    bias: jax.Array,  # (D,)  (zeros for rmsnorm)
    *,
    kind: str = "layernorm",
    eps: float = 1e-5,
    bb: int = 128,
    interpret: bool = False,
):
    B, D = x.shape
    bb = min(bb, B)
    assert B % bb == 0, (B, bb)
    grid = (B // bb,)
    kernel = functools.partial(_ln_res_kernel, kind=kind, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, D), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, D), res.dtype),
            jax.ShapeDtypeStruct((B, D), jnp.int8),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(
        x,
        res,
        weight.astype(jnp.float32)[None, :],
        bias.astype(jnp.float32)[None, :],
    )
