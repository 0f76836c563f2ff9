"""Paged attention Pallas kernel — C query positions over paged K/V.

The Fused MHA MDK (``mha_kernel.py``) over a paged cache, for decode
(``C = 1``), speculative verify (``C = k+1``) and chunked prefill: a chunk
of ``C`` query positions attends **in place** against the row's
block-table-addressed pages.  The chunk's own K/V have already been
written into the pages (the in-place write), so the kernel is pure causal
attention with a per-row offset: query ``j`` of row ``b`` sits at logical
position ``base[b] + j`` and sees every cached position ``<= base[b] + j``.
Decode is the ``C = 1`` case with ``base = length - 1``.

The KV cache lives in a global *page pool* ``(P, Hkv, page_size, D)``.  The
block table and base offsets are **scalar-prefetch** operands
(``PrefetchScalarGridSpec``): the K/V BlockSpec index maps read
``bt[b, s]`` before the body runs, so grid step ``(b, h, s)`` DMAs
exactly the page sequence ``b`` owns at logical block ``s``.  Traffic is
therefore proportional to the *live pages* named by the table, where the
jnp oracle materializes each row's full ``max_seq`` view per call.  GQA
stays in the index map (query head ``h`` reads KV head ``h // group``).

Online softmax runs per query row (:func:`online_softmax_block`).  Rows
the window leaves with no valid key finalize through the zero-denominator
clamp (NaN-free); rows parked past the pool (``base >= n_pg * ps``)
produce output the caller's length accounting never reads.  Null pages
(block-table entries 0 for unallocated blocks) sit above every row's
positions and are masked like stale cache content.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mha_kernel import online_softmax_block, softmax_scratch


def _paged_verify_kernel(
    bt_ref,  # (B, n_pg) i32 scalar-prefetch (consumed by index maps)
    base_ref,  # (B,) i32 scalar-prefetch — per-row first query position
    q_ref,  # (1, 1, C, D)
    k_ref,  # (1, 1, ps, D) — the page named by bt[b, s]
    v_ref,  # (1, 1, ps, D)
    o_ref,  # (1, 1, C, D)
    acc_ref,  # (C, D) f32 scratch
    m_ref,  # (C, 1) f32 scratch
    l_ref,  # (C, 1) f32 scratch
    *,
    n_pg: int,
    ps: int,
    window: int,
):
    b = pl.program_id(0)
    s = pl.program_id(2)

    def mask(shape):
        pos = s * ps + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qpos = base_ref[b] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        valid = pos <= qpos
        if window:
            valid = jnp.logical_and(valid, pos > qpos - window)
        return valid

    online_softmax_block(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                         step=s, n_steps=n_pg, mask=mask)


def _paged_verify_tree_kernel(
    bt_ref,  # (B, n_pg) i32 scalar-prefetch (consumed by index maps)
    base_ref,  # (B,) i32 scalar-prefetch — per-row first query position
    q_ref,  # (1, 1, C, D)
    k_ref,  # (1, 1, ps, D) — the page named by bt[b, s]
    v_ref,  # (1, 1, ps, D)
    anc_ref,  # (1, C, C) i32 — per-row ancestor bitmask over chunk positions
    o_ref,  # (1, 1, C, D)
    acc_ref,  # (C, D) f32 scratch
    m_ref,  # (C, 1) f32 scratch
    l_ref,  # (C, 1) f32 scratch
    *,
    n_pg: int,
    ps: int,
):
    """Ancestor-masked variant: query ``i`` attends the committed prefix
    (``pos < base``) plus exactly the in-chunk positions ``j`` with
    ``anc[i, j]`` set — its root path through the token tree.  The
    in-chunk bits are resolved with a one-hot matmul (MXU) instead of a
    per-key gather: ``onehot[j, key] = (key's chunk-relative position
    == j)``, so ``anc @ onehot`` lands each query row's ancestor bits on
    this page's keys."""
    b = pl.program_id(0)
    s = pl.program_id(2)

    def mask(shape):
        base = base_ref[b]
        pos = s * ps + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        rel = pos - base  # key's chunk-relative position (rows identical)
        jrow = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        onehot = (rel == jrow).astype(jnp.float32)  # (C, ps)
        anc = anc_ref[0].astype(jnp.float32)  # (C, C)
        in_chunk = jax.lax.dot_general(
            anc, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) > 0.5  # (C, ps)
        return jnp.logical_or(pos < base, in_chunk)

    online_softmax_block(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                         step=s, n_steps=n_pg, mask=mask)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify(
    q: jax.Array,  # (B, C, H, D)
    k_pages: jax.Array,  # (P, Hkv, ps, D) page pool
    v_pages: jax.Array,  # (P, Hkv, ps, D)
    base: jax.Array,  # (B,) i32 — first query position per row
    block_table: jax.Array,  # (B, n_pg) i32 page ids
    anc: jax.Array | None = None,  # (B, C, C) ancestor bitmask (tree mode)
    *,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    B, C, H, D = q.shape
    _, Hkv, ps, _ = k_pages.shape
    n_pg = block_table.shape[1]
    assert H % Hkv == 0, (q.shape, k_pages.shape)
    if anc is not None and window:
        raise ValueError("window and anc are mutually exclusive")
    group = H // Hkv
    in_specs = [
        pl.BlockSpec((1, 1, C, D), lambda b, h, s, bt, bs: (b, h, 0, 0)),
        pl.BlockSpec(
            (1, 1, ps, D),
            lambda b, h, s, bt, bs: (bt[b, s], h // group, 0, 0),
        ),
        pl.BlockSpec(
            (1, 1, ps, D),
            lambda b, h, s, bt, bs: (bt[b, s], h // group, 0, 0),
        ),
    ]
    operands = [
        block_table.astype(jnp.int32),
        base.astype(jnp.int32),
        q.transpose(0, 2, 1, 3),  # head-major (B, H, C, D)
        k_pages,
        v_pages,
    ]
    if anc is not None:
        in_specs.append(
            pl.BlockSpec((1, C, C), lambda b, h, s, bt, bs: (b, 0, 0)))
        operands.append(anc.astype(jnp.int32))
        body = functools.partial(_paged_verify_tree_kernel, n_pg=n_pg, ps=ps)
    else:
        body = functools.partial(
            _paged_verify_kernel, n_pg=n_pg, ps=ps, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + bases feed the index maps
        grid=(B, H, n_pg),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, C, D), lambda b, h, s, bt, bs: (b, h, 0, 0)),
        scratch_shapes=softmax_scratch(C, D),
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out.transpose(0, 2, 1, 3)
