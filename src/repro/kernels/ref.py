"""Pure-jnp oracles for every Pallas kernel.

These are the semantic ground truth: each ``kernels/<name>.py`` Pallas
implementation must match its oracle here (asserted by the per-kernel
allclose sweeps in ``tests/test_kernels*.py``), and they are also the
CPU/dry-run execution path selected by ``ops.py`` when not on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Fused MP kernel oracle: W8A8 matmul + dequant + bias (LoopLynx Fused MP)
# ---------------------------------------------------------------------------


def quant_matmul_ref(
    x_q: jax.Array,  # int8 (M, K)
    w_q: jax.Array,  # int8 (K, N)
    x_scale: jax.Array,  # f32 (M, 1) per-token
    w_scale: jax.Array,  # f32 (1, N) per-channel
    bias: jax.Array | None = None,  # f32 (N,)
    out_dtype=jnp.bfloat16,
) -> jax.Array:
    """Y = (x_q @ w_q) * x_scale * w_scale + bias, int32 accumulation."""
    acc = jax.lax.dot_general(
        x_q,
        w_q,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    y = acc.astype(jnp.float32) * x_scale.astype(jnp.float32) * w_scale.astype(
        jnp.float32
    )
    if bias is not None:
        y = y + bias.astype(jnp.float32)[None, :]
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# Fused MHA decode oracle: one query token vs KV cache, GQA, optional window
# ---------------------------------------------------------------------------


def mha_decode_ref(
    q: jax.Array,  # (B, H, D) bf16/f32
    k_cache: jax.Array,  # (B, Hkv, S, D)
    v_cache: jax.Array,  # (B, Hkv, S, D)
    lengths: jax.Array,  # (B,) i32 — number of valid cache entries
    window: int = 0,  # 0 => full causal cache; else sliding window
) -> jax.Array:
    """Single-token attention with online-softmax semantics (exact softmax).

    GQA is computed as a grouped einsum — the KV cache is contracted
    directly at its stored width/dtype (no ``jnp.repeat`` materialization,
    no f32 copy of the cache), so HBM traffic is one cache read.
    """
    B, H, D = q.shape
    Hkv = k_cache.shape[1]
    group = H // Hkv
    S = k_cache.shape[2]
    qg = q.reshape(B, Hkv, group, D)
    scores = jnp.einsum(
        "bhgd,bhsd->bhgs", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) / jnp.sqrt(float(D))  # (B, Hkv, g, S) f32
    pos = jnp.arange(S)[None, None, None, :]
    valid = pos < lengths[:, None, None, None]
    if window:
        valid = valid & (pos >= (lengths[:, None, None, None] - window))
    scores = jnp.where(valid, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgs,bhsd->bhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged MHA decode oracle: block-table gather + single-token attention
# ---------------------------------------------------------------------------


def paged_gather_ref(
    pages: jax.Array,  # (P, Hkv, ps, D) global page pool
    block_table: jax.Array,  # (B, n_pg) i32 page ids per sequence
) -> jax.Array:
    """Gather each sequence's pages into a contiguous (B, Hkv, n_pg*ps, D)
    view.  Unallocated block-table entries point at the reserved null page
    (id 0); its contents land above every sequence's length and are masked
    by the attention length/causality accounting, exactly like stale slot
    content in the contiguous layout."""
    g = pages[block_table]  # (B, n_pg, Hkv, ps, D)
    B, n_pg, Hkv, ps, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, n_pg * ps, D)


def paged_mha_decode_ref(
    q: jax.Array,  # (B, H, D)
    k_pages: jax.Array,  # (P, Hkv, ps, D)
    v_pages: jax.Array,  # (P, Hkv, ps, D)
    lengths: jax.Array,  # (B,) i32 valid tokens per sequence
    block_table: jax.Array,  # (B, n_pg) i32
    window: int = 0,
) -> jax.Array:
    """Single-token attention over a paged KV cache.

    Semantically the contiguous :func:`mha_decode_ref` applied to the
    block-table gather of the page pool — the gathered view is bit-identical
    to the contiguous cache at every position below ``lengths`` (pages hold
    the same K/V values, written at the same rope'd positions), and masked
    positions contribute exactly zero either way, so paged decode is
    bit-exact against the contiguous layout.
    """
    k = paged_gather_ref(k_pages, block_table)
    v = paged_gather_ref(v_pages, block_table)
    return mha_decode_ref(q, k, v, lengths, window=window)


# ---------------------------------------------------------------------------
# Paged verify oracle: k+1 query positions vs block-table-addressed pages
# ---------------------------------------------------------------------------


def paged_verify_ref(
    q: jax.Array,  # (B, C, H, D) — C = k+1 chunk positions per row
    k_pages: jax.Array,  # (P, Hkv, ps, D)
    v_pages: jax.Array,  # (P, Hkv, ps, D)
    base: jax.Array,  # (B,) i32 — row's first query position (its length)
    block_table: jax.Array,  # (B, n_pg) i32
    window: int = 0,
    anc: jax.Array | None = None,  # (B, C, C) ancestor bitmask (tree mode)
) -> jax.Array:
    """Chunked causal attention over a paged KV cache.

    Query position ``j`` of row ``b`` sits at logical position
    ``base[b] + j`` and attends every cached position ``<=`` itself — the
    chunk's own K/V are assumed already written into the pages (the
    in-place verify/prefill write), so the mask is pure causality plus
    the optional sliding window.  Rows parked at ``base >= n_pg * ps``
    attend only positions the caller's length accounting masks out — the
    caller never reads their output; a row the window leaves with no
    valid key at all yields the zero vector (NaN-free), mirroring the
    kernel's zero-denominator clamp.

    With ``anc`` the implicit causal in-chunk mask is replaced by a token
    tree's ancestor bitmask: query position ``i`` attends every cached
    position ``< base[b]`` (the committed prefix) plus exactly the chunk
    positions ``j`` with ``anc[b, i, j]`` — its own root path.  A causal
    (lower-triangular) ``anc`` reproduces the linear mask bit-exactly.
    ``window`` and ``anc`` are mutually exclusive.
    """
    B, C, H, D = q.shape
    Hkv = k_pages.shape[1]
    group = H // Hkv
    k = paged_gather_ref(k_pages, block_table)  # (B, Hkv, S, D)
    v = paged_gather_ref(v_pages, block_table)
    S = k.shape[2]
    qg = q.reshape(B, C, Hkv, group, D)
    scores = jnp.einsum(
        "bchgd,bhsd->bhgcs", qg, k,
        preferred_element_type=jnp.float32,
    ) / jnp.sqrt(float(D))  # (B, Hkv, g, C, S)
    pos = jnp.arange(S)[None, None, None, None, :]
    qpos = (base[:, None] + jnp.arange(C)[None, :])[:, None, None, :, None]
    if anc is not None:
        if window:
            raise ValueError("window and anc are mutually exclusive")
        rel = jnp.arange(S)[None, :] - base[:, None]  # (B, S) chunk-relative
        in_chunk = (rel >= 0) & (rel < C)
        bits = jnp.take_along_axis(
            anc.astype(bool),
            jnp.clip(rel, 0, C - 1)[:, None, :],
            axis=2,
        )  # (B, C, S)
        prefix = (jnp.arange(S)[None, :] < base[:, None])[:, None, :]
        valid = (prefix | (in_chunk[:, None, :] & bits))[:, None, None, :, :]
    else:
        valid = pos <= qpos
        if window:
            valid = valid & (pos > qpos - window)
    scores = jnp.where(valid, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(valid.any(axis=-1, keepdims=True), p, 0.0)
    # p is rounded to the cache dtype, then both operands are widened:
    # their products are exact in f32, so this equals a cache-dtype dot
    # with f32 accumulation, which XLA's CPU backend does not implement
    # for this contraction.
    out = jnp.einsum(
        "bhgcs,bhsd->bchgd",
        p.astype(v.dtype).astype(jnp.float32), v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, C, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused LN&Res oracle: residual add + norm (+ per-token int8 quant epilogue)
# ---------------------------------------------------------------------------


def ln_res_ref(
    x: jax.Array,  # (B, D) block output
    res: jax.Array,  # (B, D) running residual
    weight: jax.Array,  # (D,)
    bias: jax.Array | None,  # (D,) or None (rmsnorm)
    *,
    kind: str = "layernorm",  # layernorm | rmsnorm
    eps: float = 1e-5,
):
    """Returns (normed bf16, new_residual, normed_int8, inv127_scale)."""
    r = x.astype(jnp.float32) + res.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(r, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(r - mu), axis=-1, keepdims=True)
        y = (r - mu) * jax.lax.rsqrt(var + eps)
    elif kind == "rmsnorm":
        ms = jnp.mean(jnp.square(r), axis=-1, keepdims=True)
        y = r * jax.lax.rsqrt(ms + eps)
    else:
        raise ValueError(kind)
    y = y * weight.astype(jnp.float32)[None, :]
    if bias is not None:
        y = y + bias.astype(jnp.float32)[None, :]
    # dynamic per-token symmetric int8 quantization (SmoothQuant W8A8 act path)
    amax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    y_q = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
    return (
        y.astype(jnp.bfloat16),
        r.astype(res.dtype),
        y_q,
        scale.astype(jnp.float32),
    )
