"""GQA attention: full-sequence (train/prefill) and cached decode paths.

Decode routes through the Fused MHA MDK (``ops.mha_decode``) with head-wise
online-softmax pipelining; train/prefill use a standard causal (optionally
sliding-window) softmax attention in jnp, sharded head-wise under TP.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.layers import linear, linear_init, rope

_NEG_INF = -1e30


def attn_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    """Separate q/k/v projections (not fused): under 16-way tensor
    parallelism the fused qkv column split is shard-misaligned for GQA, and
    k/v must be *replicable* independently of q when n_kv_heads < model
    axis (the MaxText kv-replication pattern).  The serving scheduler still
    issues them as one Fused-MP activation (concatenated column blocks)."""
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "q": linear_init(k1, cfg.d_model, cfg.q_dim, dtype),
        "k": linear_init(k2, cfg.d_model, cfg.kv_dim, dtype),
        "v": linear_init(k3, cfg.d_model, cfg.kv_dim, dtype),
        "out": linear_init(k4, cfg.q_dim, cfg.d_model, dtype),
    }


def _project_qkv(p, cfg: ModelConfig, x: jax.Array, name: str):
    B, S = x.shape[:2]
    q = linear(p["q"], x, name + ".q").reshape(
        B, S, cfg.n_heads, cfg.head_dim)
    k = linear(p["k"], x, name + ".k").reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim)
    v = linear(p["v"], x, name + ".v").reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def full_attention(
    p: Dict,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # (B, S)
    window: int = 0,
    causal: bool = True,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    name: str = "",
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (out (B,S,D), (k,v) for cache fill). ``cross_kv`` bypasses
    self-attention K/V (whisper cross-attention)."""
    q, k, v = _project_qkv(p, cfg, x, name)
    if cfg.pos == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if cross_kv is not None:
        k, v = cross_kv
    group = cfg.n_heads // cfg.n_kv_heads
    B, Sq = q.shape[:2]
    # grouped-query einsum: contract K/V at stored width & dtype (no
    # jnp.repeat materialization, no f32 cache copy)
    qg = q.reshape(B, Sq, cfg.n_kv_heads, group, cfg.head_dim)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) / (cfg.head_dim**0.5)
    Sk = scores.shape[-1]
    if causal and cross_kv is None:
        iq = jnp.arange(Sq)[:, None]
        ik = jnp.arange(Sk)[None, :]
        mask = ik <= iq
        if window:
            mask = mask & (ik > iq - window)
        scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    out = out.astype(x.dtype).reshape(B, Sq, cfg.q_dim)
    return linear(p["out"], out, name + ".out"), (k, v)


def chunk_attention(
    p: Dict,
    x: jax.Array,  # (B, C, D) chunk of prompt tokens
    cfg: ModelConfig,
    k_cache: jax.Array,  # (B, Hkv, S, hd) slot-view KV cache
    v_cache: jax.Array,
    positions: jax.Array,  # (B, C) absolute positions of the chunk tokens
    *,
    anc: Optional[jax.Array] = None,  # (B, C, C) tree ancestor bitmask
    rope_positions: Optional[jax.Array] = None,  # (B, C) logical positions
    name: str = "",
):
    """Multi-token cached attention for chunked prefill.

    The chunk's K/V are written into the cache at their absolute
    ``positions`` first, then the chunk queries attend over the *whole*
    cache under the causal mask ``key_pos <= query_pos`` — earlier chunks
    of the same prompt are live cache content below the chunk; stale
    entries above it are masked out by causality.  ``positions`` are
    per-row: prefill passes one broadcast row, speculative verification
    passes each slot's own offset.  Returns (out (B,C,D), k_cache,
    v_cache).

    Tree verification (``anc``): the in-chunk causal mask is replaced by
    the token tree's ancestor bitmask — position ``i`` attends every key
    below the chunk base plus exactly the chunk positions ``anc[b, i]``
    names (its root path).  ``rope_positions`` then carries each node's
    *logical* position (``base + depth``) for the rotary phase, while
    ``positions`` keeps the flat chunk slot the K/V scatter targets — so
    a node's K/V depend only on its root path and survive the accepted
    path's later compaction to contiguous offsets.  A causal
    (lower-triangular) ``anc`` with ``rope_positions == positions``
    reduces bit-exactly to the linear mask.
    """
    B, C = x.shape[:2]
    q, k, v = _project_qkv(p, cfg, x, name)  # (B,C,H,hd) / (B,C,Hkv,hd)
    if cfg.pos == "rope":
        rpos = positions if rope_positions is None else rope_positions
        q = rope(q, rpos, cfg.rope_theta)
        k = rope(k, rpos, cfg.rope_theta)
    # per-row per-position scatter with mode="drop": positions past the
    # cache end — the last prefill chunk's fixed-size window hanging past
    # max_seq, or a verify row flagged inactive by an out-of-range offset
    # — are dropped instead of (as dynamic_update_slice would) clamping
    # backwards over already-written prompt K/V
    b_idx = jnp.arange(B)[:, None]  # advanced dims lead: value is (B,C,..)
    k_cache = k_cache.at[b_idx, :, positions].set(
        k.astype(k_cache.dtype), mode="drop")
    v_cache = v_cache.at[b_idx, :, positions].set(
        v.astype(v_cache.dtype), mode="drop")
    group = cfg.n_heads // cfg.n_kv_heads
    S = k_cache.shape[2]
    qg = q.reshape(B, C, cfg.n_kv_heads, group, cfg.head_dim)
    scores = jnp.einsum(
        "bqhgd,bhkd->bhgqk", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) / (cfg.head_dim**0.5)
    key_pos = jnp.arange(S)[None, None, None, None, :]
    if anc is not None:
        base = positions[:, :1]  # (B, 1)
        rel = jnp.arange(S)[None] - base  # (B, S) chunk-relative key pos
        in_chunk = (rel >= 0) & (rel < C)
        bits = jnp.take_along_axis(
            anc.astype(bool), jnp.clip(rel, 0, C - 1)[:, None, :], axis=2)
        m = ((jnp.arange(S)[None] < base)[:, None, :]
             | (in_chunk[:, None, :] & bits))  # (B, C, S)
        mask = m[:, None, None, :, :]
    else:
        mask = key_pos <= positions[:, None, None, :, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgqk,bhkd->bqhgd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    out = out.astype(x.dtype).reshape(B, C, cfg.q_dim)
    return linear(p["out"], out, name + ".out"), k_cache, v_cache


def chunk_attention_rotating(
    p: Dict,
    x: jax.Array,  # (B, C, D) chunk of prompt / draft tokens
    cfg: ModelConfig,
    k_cache: jax.Array,  # (B, Hkv, W, hd) rotating-window (ring) cache
    v_cache: jax.Array,
    positions: jax.Array,  # (B, C) absolute positions of the chunk tokens
    limits: jax.Array,  # (B,) absolute position bound: >= limit writes drop
    *,
    name: str = "",
):
    """Multi-token cached attention for rotating-window (sliding) layers.

    The ring cache (W slots; slot = pos % W) cannot hold both a chunk's
    new K/V and the predecessor positions they evict, so unlike
    :func:`chunk_attention` the chunk queries attend over the
    *concatenation* of the pre-write ring (ring slot ``s`` holds the
    latest position below the row's chunk start congruent to ``s``) and
    the chunk's own K/V, under the sliding-window causal mask
    ``query_pos - W < key_pos <= query_pos``.  Writes then land at
    ``pos % W`` with last-write-wins semantics: only positions in
    ``[limit - W, limit)`` — the final window — are written, so an
    over-window chunk leaves exactly the ring a sequential replay would.
    ``limits`` bounds each row's real tokens: positions at or past it
    (prompt padding, parked verify rows) write nothing — a ring write
    wraps instead of dropping, so unlike the absolute-offset path the
    bound must be explicit.  Returns (out (B,C,D), k_cache, v_cache).
    """
    B, C = x.shape[:2]
    W = k_cache.shape[2]
    q, k, v = _project_qkv(p, cfg, x, name)  # (B,C,H,hd) / (B,C,Hkv,hd)
    if cfg.pos == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # read (and later write) chunk K/V at cache precision, like the
    # write-then-read absolute-offset chunk path does
    k = k.astype(k_cache.dtype)
    v = v.astype(v_cache.dtype)
    group = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, C, cfg.n_kv_heads, group, cfg.head_dim)
    # ring slot s holds the latest position below the row's chunk start
    # congruent to s mod W; prefill is contiguous, so written <=> pos >= 0
    off = positions[:, :1]  # (B, 1) — first chunk position per row
    s_idx = jnp.arange(W)[None]  # (1, W)
    cache_pos = off - 1 - jnp.mod(off - 1 - s_idx, W)  # (B, W)
    scale = cfg.head_dim**0.5
    sc_cache = jnp.einsum(
        "bqhgd,bhkd->bhgqk", qg, k_cache,
        preferred_element_type=jnp.float32) / scale
    sc_self = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k,
        preferred_element_type=jnp.float32) / scale
    qpos = positions[:, None, None, :, None]  # (B,1,1,C,1)
    cpos = cache_pos[:, None, None, None, :]  # (B,1,1,1,W)
    mask_cache = (cpos >= 0) & (cpos > qpos - W)  # cpos <= qpos always
    kpos = positions[:, None, None, None, :]  # (B,1,1,1,C)
    mask_self = (kpos <= qpos) & (kpos > qpos - W)
    scores = jnp.concatenate(
        [jnp.where(mask_cache, sc_cache, _NEG_INF),
         jnp.where(mask_self, sc_self, _NEG_INF)], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    vals = jnp.concatenate(
        [v_cache, v.transpose(0, 2, 1, 3)], axis=2)  # (B,Hkv,W+C,hd)
    out = jnp.einsum(
        "bhgqk,bhkd->bqhgd", probs.astype(vals.dtype), vals,
        preferred_element_type=jnp.float32,
    )
    out = out.astype(x.dtype).reshape(B, C, cfg.q_dim)
    # last-write-wins ring update, bounded to each row's real tokens;
    # kept positions span at most one window, so their slots are distinct
    wvalid = (positions < limits[:, None]) & (positions >= limits[:, None]
                                              - W)
    slots = jnp.where(wvalid, jnp.mod(positions, W), W)  # W => dropped
    b_idx = jnp.arange(B)[:, None]
    k_cache = k_cache.at[b_idx, :, slots].set(k, mode="drop")
    v_cache = v_cache.at[b_idx, :, slots].set(v, mode="drop")
    return linear(p["out"], out, name + ".out"), k_cache, v_cache


def decode_attention(
    p: Dict,
    x: jax.Array,  # (B, 1, D) current token
    cfg: ModelConfig,
    k_cache: jax.Array,  # (B, Hkv, S, hd)
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,) tokens already in cache (position of new one)
    *,
    window: int = 0,
    cross: bool = False,
    name: str = "",
):
    """One-token cached attention through the Fused MHA MDK.

    Returns (out (B,1,D), new_k_cache, new_v_cache).  With ``cross=True``
    the cache is static (whisper encoder K/V) and is not written.
    """
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x, name)  # (B,1,H,hd) / (B,1,Hkv,hd)
    if cfg.pos == "rope":
        pos = lengths[:, None]  # (B, 1) — position of the new token
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    if not cross:
        k_cache = _write_cache(k_cache, k[:, 0], lengths)
        v_cache = _write_cache(v_cache, v[:, 0], lengths)
        attn_len = lengths + 1  # the new token attends to itself
    else:
        attn_len = lengths
    qh = q[:, 0]  # (B, H, hd)
    out = ops.mha_decode(
        qh, k_cache, v_cache, attn_len, window=window
    )  # (B, H, hd)
    out = out.reshape(B, 1, cfg.q_dim)
    return linear(p["out"], out, name + ".out"), k_cache, v_cache


def _write_cache(cache: jax.Array, new: jax.Array, lengths: jax.Array):
    """cache (B, Hkv, S, hd); new (B, Hkv, hd) written at slot lengths[b]."""
    B = cache.shape[0]
    return cache.at[jnp.arange(B), :, lengths].set(new.astype(cache.dtype))


def paged_decode_attention(
    p: Dict,
    x: jax.Array,  # (B, 1, D) current token
    cfg: ModelConfig,
    k_pages: jax.Array,  # (P, Hkv, ps, hd) global page pool
    v_pages: jax.Array,
    lengths: jax.Array,  # (B,) tokens already cached (position of new one)
    block_table: jax.Array,  # (B, n_pg) i32 page ids per sequence
    *,
    active: Optional[jax.Array] = None,  # (B,) bool — rows really decoding
    name: str = "",
):
    """One-token cached attention against a paged KV cache.

    The new token's K/V are written into the page the block table names for
    logical position ``lengths[b]`` (decode tail pages are uniquely owned —
    prefix sharing only ever shares *full, immutable* prompt pages — so the
    batched scatter cannot collide between live requests; idle rows all
    target the reserved null page 0, where any write order is acceptable
    because its content is never unmasked).  Rows the ``active`` mask
    declares as tag-alongs (mid-prefill or empty slots riding the batched
    engine step) park their write past the block table, which resolves to
    the null page — NOT at ``lengths[b]``: with per-kind layouts a
    prefilling sharer's length points INTO its linked prefix pages, and an
    unparked tag-along write there would corrupt the prefix owner's live
    K/V.  Attention then runs through the paged Fused-MHA MDK
    (``ops.paged_mha_decode``), which is bit-exact against
    :func:`decode_attention` on the same logical cache content.

    Returns (out (B,1,D), new_k_pages, new_v_pages).
    """
    B = x.shape[0]
    ps = k_pages.shape[2]
    n_pg = block_table.shape[1]
    q, k, v = _project_qkv(p, cfg, x, name)  # (B,1,H,hd) / (B,1,Hkv,hd)
    if cfg.pos == "rope":
        pos = lengths[:, None]  # (B, 1) — position of the new token
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    wpos = (lengths if active is None
            else jnp.where(active, lengths, n_pg * ps))
    blk = wpos // ps
    page = jnp.where(
        blk < n_pg,
        block_table[jnp.arange(B), jnp.minimum(blk, n_pg - 1)], 0)
    off = wpos % ps
    k_pages = k_pages.at[page, :, off].set(k[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[page, :, off].set(v[:, 0].astype(v_pages.dtype))
    out = ops.paged_mha_decode(
        q[:, 0], k_pages, v_pages, lengths + 1, block_table, active=active
    )  # (B, H, hd)
    out = out.reshape(B, 1, cfg.q_dim)
    return linear(p["out"], out, name + ".out"), k_pages, v_pages


def paged_chunk_attention(
    p: Dict,
    x: jax.Array,  # (B, C, D) chunk of prompt / draft tokens
    cfg: ModelConfig,
    k_pages: jax.Array,  # (P, Hkv, ps, hd) global page pool
    v_pages: jax.Array,
    positions: jax.Array,  # (B, C) absolute positions (contiguous per row)
    block_tables: jax.Array,  # (B, n_pg) i32 page ids per sequence
    *,
    anc: Optional[jax.Array] = None,  # (B, C, C) tree ancestor bitmask
    rope_positions: Optional[jax.Array] = None,  # (B, C) logical positions
    name: str = "",
):
    """Multi-token cached attention **in place** over a paged KV cache.

    The chunked analogue of :func:`paged_decode_attention`, serving both
    chunked prefill (``positions = offset + arange(C)``) and speculative
    verify (``positions = lengths + arange(C)``): the chunk's K/V are
    scattered directly into the pages the block table names for each
    position, then the chunk queries attend through the paged verify MDK
    (``ops.paged_verify``) with ``base = positions[:, 0]`` — no gathered
    ``max_seq`` view exists at any point, so copy traffic is the chunk
    write plus the live pages the kernel streams.

    Write-collision safety is the decode-path invariant: positions at or
    past a row's committed length live in decode-tail/prompt pages that
    row uniquely owns (prefix sharing only links *full, immutable* prompt
    pages below the rewind floor, and prefill resumes at the first
    unshared page boundary), so the batched scatter cannot touch another
    row's live content.  Positions whose logical block is out of range
    (parked verify rows at ``max_seq``, a last chunk hanging past the
    pool) or whose table entry is unallocated resolve to the null page 0,
    whose content is never unmasked.  The block gather is masked
    **explicitly**: jnp clamps out-of-range gather indices, which would
    silently redirect a parked row's write into the table's *last* entry
    — a real page — instead of the null page.

    Returns (out (B,C,D), new_k_pages, new_v_pages).
    """
    B, C = x.shape[:2]
    ps = k_pages.shape[2]
    n_pg = block_tables.shape[1]
    q, k, v = _project_qkv(p, cfg, x, name)  # (B,C,H,hd) / (B,C,Hkv,hd)
    if cfg.pos == "rope":
        # tree verify: rotary phase follows logical (base + depth)
        # positions; the scatter below keeps the flat chunk slot, so a
        # node's K/V depend only on its root path and survive the
        # accepted path's compaction to contiguous offsets
        rpos = positions if rope_positions is None else rope_positions
        q = rope(q, rpos, cfg.rope_theta)
        k = rope(k, rpos, cfg.rope_theta)
    blk = positions // ps  # (B, C)
    page = jnp.where(
        blk < n_pg,
        jnp.take_along_axis(block_tables, jnp.clip(blk, 0, n_pg - 1),
                            axis=1),
        0)  # (B, C)
    off = positions % ps
    k_pages = k_pages.at[page, :, off].set(k.astype(k_pages.dtype))
    v_pages = v_pages.at[page, :, off].set(v.astype(v_pages.dtype))
    out = ops.paged_verify(
        q, k_pages, v_pages, positions[:, 0], block_tables, anc=anc
    )  # (B, C, H, hd)
    out = out.reshape(B, C, cfg.q_dim)
    return linear(p["out"], out, name + ".out"), k_pages, v_pages
