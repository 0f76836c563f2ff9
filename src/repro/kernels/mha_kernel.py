"""Fused MHA decode Pallas kernel — LoopLynx's Fused MHA MDK on TPU.

The paper's Fused MHA kernel (Fig 6b) is a head-wise task-level pipeline:
score MAC -> mask -> softmax -> token-mixing MAC, with softmax of head i-1
hidden under the attention compute of head i (Fig 4b).  On TPU we adapt this
to the strictly-stronger single-pass form: the grid iterates (batch, head,
kv-block) and an *online softmax* (running max/denominator in VMEM scratch)
eliminates the two-phase softmax barrier the paper pipelines around, while
independent head rows of the grid give the same head-level overlap for free.

GQA is expressed in the BlockSpec index map (query head h reads KV head
h // group), so grouped heads re-read the same KV block from VMEM —
mirroring the paper's head-wise KV-cache partitioning.  A sliding-window
mask (recurrentgemma local attention) reuses the same kernel.

Queries and outputs travel head-major, ``(B, H, C, D)``, so every block's
last two dims are whole array dims (Mosaic tiles the last two dims of a
block by (8, 128) unless they span the array).  The paged verify kernel
shares :func:`online_softmax_update`, the fold of one K/V block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def online_softmax_update(q, k, v, valid, acc_ref, m_ref, l_ref):
    """Fold one K/V block into the running softmax state of ``C`` rows.

    ``q`` is ``(C, D)``, ``k``/``v`` ``(bs, D)``, all float32; ``valid``
    is the ``(C, bs)`` valid-key mask.  ``acc_ref`` ``(C, D)`` and the
    running max and denominator ``m_ref``/``l_ref`` ``(C, 1)`` are VMEM
    refs written as whole vectors (Mosaic cannot store a scalar into
    VMEM).
    """
    d = q.shape[-1]
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (1.0 / (d**0.5))  # (C, bs)
    scores = jnp.where(valid, scores, _NEG_INF)

    m_prev = m_ref[...]  # (C, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)  # (C, 1)
    p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)  # (C, bs)

    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new


def softmax_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def softmax_result(acc_ref, l_ref):
    """The attention output of the folded blocks; a row with no valid key
    finalizes to zero through the denominator clamp."""
    l = l_ref[...]  # (C, 1)
    return acc_ref[...] / jnp.where(l > 0.0, l, 1.0)


def online_softmax_block(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                         *, step, n_steps: int, mask):
    """One K/V block of single-pass attention for ``C`` query rows.

    ``q_ref``/``o_ref`` are ``(1, 1, C, D)`` blocks, ``k_ref``/``v_ref``
    ``(1, 1, bs, D)``; ``mask(shape)`` returns the ``(C, bs)`` valid-key
    mask.
    """

    @pl.when(step == 0)
    def _init():
        softmax_init(acc_ref, m_ref, l_ref)

    k = k_ref[0, 0].astype(jnp.float32)  # (bs, D)
    online_softmax_update(
        q_ref[0, 0].astype(jnp.float32), k, v_ref[0, 0].astype(jnp.float32),
        mask((q_ref.shape[2], k.shape[0])), acc_ref, m_ref, l_ref)

    @pl.when(step == n_steps - 1)
    def _final():
        o_ref[0, 0] = softmax_result(acc_ref, l_ref).astype(o_ref.dtype)


def softmax_scratch(c: int, d: int):
    """VMEM scratch of :func:`online_softmax_block`: acc, max, denom."""
    return [
        pltpu.VMEM((c, d), jnp.float32),
        pltpu.VMEM((c, 1), jnp.float32),
        pltpu.VMEM((c, 1), jnp.float32),
    ]


def _mha_kernel(
    len_ref,  # (B,) i32 scalar-prefetch
    q_ref,  # (1, 1, 1, D)
    k_ref,  # (1, 1, bs, D)
    v_ref,  # (1, 1, bs, D)
    o_ref,  # (1, 1, 1, D)
    acc_ref,  # (1, D) f32 scratch
    m_ref,  # (1, 1) f32 scratch
    l_ref,  # (1, 1) f32 scratch
    *,
    n_s: int,
    bs: int,
    window: int,
):
    b = pl.program_id(0)
    s = pl.program_id(2)

    def mask(shape):
        length = len_ref[b]
        pos = s * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        valid = pos < length
        if window:
            valid = jnp.logical_and(valid, pos >= length - window)
        return valid

    online_softmax_block(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                         step=s, n_steps=n_s, mask=mask)


@functools.partial(
    jax.jit, static_argnames=("bs", "window", "interpret")
)
def mha_decode(
    q: jax.Array,  # (B, H, D)
    k_cache: jax.Array,  # (B, Hkv, S, D)
    v_cache: jax.Array,  # (B, Hkv, S, D)
    lengths: jax.Array,  # (B,) i32
    *,
    bs: int = 128,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    assert H % Hkv == 0 and S % bs == 0, (q.shape, k_cache.shape, bs)
    group = H // Hkv
    n_s = S // bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lengths feed the mask from SMEM
        grid=(B, H, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, s, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, s, ln: (b, h // group, s, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, s, ln: (b, h // group, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, s, ln: (b, h, 0, 0)),
        scratch_shapes=softmax_scratch(1, D),
    )
    out = pl.pallas_call(
        functools.partial(_mha_kernel, n_s=n_s, bs=bs, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.reshape(B, H, 1, D), k_cache, v_cache)
    return out.reshape(B, H, D)
