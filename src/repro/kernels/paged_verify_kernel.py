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
kernel visits only each row's **live pages**: the grid is a flat schedule
(:func:`_schedule`) of each row's live blocks of ``ppb`` pages (about 128
keys), its length the number of such blocks, so capacity a row does not
use costs no step.  Each page of a block is one ``(1, Hkv, ps, D)`` block
of its own pipelined input, so one step reads every head of its pages,
and every KV head's ``group`` query heads and ``C`` positions form the M
dimension of that head's product: a page is read once for all heads.  A
row with no live page (length 0, or a tag-along the caller marks
inactive) takes one step that repeats the pages before it, which the
pipeline does not copy again, and writes zeros.  (Copies issued by hand
from the pool in HBM would need a page's minor dim to fill the 128 lanes;
gpt2's heads of 64 do not, so the pipeline does the copies.)

Online softmax runs per query row (:func:`online_softmax_update`).  Rows
the window leaves with no valid key finalize through the zero-denominator
clamp (NaN-free); rows parked past the pool (``base >= n_pg * ps``)
produce output the caller's length accounting never reads.  Slots of a
block outside the live pages hold a copy of an edge page and are masked,
as are null pages (block-table entry 0) above a row's positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mha_kernel import (online_softmax_update, softmax_init,
                                      softmax_result)

#: keys per grid step: the pages one step reads and computes on
_BLOCK_KEYS = 128


def _paged_verify_kernel(
    pages_ref,  # (T * ppb,) i32 scalar-prefetch — page ids of each step
    row_ref,  # (T,) i32 scalar-prefetch — the row each step serves
    blk_ref,  # (T,) i32 scalar-prefetch — its block, -1 for no page
    base_ref,  # (B,) i32 scalar-prefetch — per-row first query position
    q_ref,  # (1, Hkv, M, D), M = group * C, rows (query head, position)
    *refs,
    n_pg: int,
    ps: int,
    ppb: int,
    chunk: int,
    window: int,
    tree: bool,
):
    if tree:
        anc_ref, *refs = refs  # (1, M, C) f32 ancestor bits per row
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]  # (1, Hkv, ps, D) pages
    o_ref, acc_ref, m_ref, l_ref = refs[2 * ppb:]  # (1, Hkv, M, D) out,
    # (Hkv, M, D) and (Hkv, M, 1) f32 scratch
    t, n = pl.program_id(0), pl.num_programs(0)
    row, blk = row_ref[t], blk_ref[t]
    n_kv, m_rows = acc_ref.shape[:2]
    keys = ppb * ps
    base = base_ref[row]

    def mask():
        shape = (m_rows, keys)
        pos = blk * keys + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if tree:
            # in-chunk ancestor bits resolved with a one-hot matmul (MXU)
            # instead of a per-key gather: onehot[c, key] = (key's
            # chunk-relative position == c)
            rel = (blk * keys - base
                   + jax.lax.broadcasted_iota(jnp.int32, (chunk, keys), 1))
            onehot = rel == jax.lax.broadcasted_iota(
                jnp.int32, (chunk, keys), 0)
            in_chunk = jax.lax.dot_general(
                anc_ref[0], onehot.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) > 0.5  # (M, keys)
            valid = jnp.logical_or(pos < base, in_chunk)
        else:
            qpos = base
            if chunk > 1:
                r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                qpos = base + (r % chunk if m_rows > chunk else r)
            valid = pos <= qpos
            if window:
                valid = jnp.logical_and(valid, pos > qpos - window)
        if n_pg % ppb:  # the last block overhangs the table
            valid = jnp.logical_and(valid, pos < n_pg * ps)
        return valid

    # a row's steps are consecutive: its first inits, its last finalizes
    @pl.when(jnp.logical_or(t == 0, row_ref[jnp.maximum(t - 1, 0)] != row))
    def _init():
        for h in range(n_kv):
            softmax_init(acc_ref.at[h], m_ref.at[h], l_ref.at[h])

    @pl.when(blk >= 0)
    def _fold():
        valid = mask()
        for h in range(n_kv):
            k = jnp.concatenate(
                [r[0, h].astype(jnp.float32) for r in k_refs], axis=0)
            v = jnp.concatenate(
                [r[0, h].astype(jnp.float32) for r in v_refs], axis=0)
            online_softmax_update(q_ref[0, h].astype(jnp.float32), k, v,
                                  valid, acc_ref.at[h], m_ref.at[h],
                                  l_ref.at[h])

    @pl.when(jnp.logical_or(
        t == n - 1, row_ref[jnp.minimum(t + 1, row_ref.shape[0] - 1)] != row))
    def _final():
        for h in range(n_kv):
            o_ref[0, h] = softmax_result(
                acc_ref.at[h], l_ref.at[h]).astype(o_ref.dtype)


def _schedule(block_table, lo, hi, ppb: int):
    """The grid's steps: each row's live blocks of ``ppb`` pages in order,
    one step with no page for a row with none (it writes the row's zero
    output).  Returns the steps' page ids ``(T * ppb,)``, rows and blocks
    ``(T,)`` (block -1: no page) for the static bound ``T = B * nb``, and
    the number of steps taken.  Slots outside the live pages ``[lo, hi)``
    name an edge page, and a step with no page repeats the pages of the
    step before, so the pipeline, which skips a copy whose block did not
    change, issues no DMA for it."""
    B, n_pg = block_table.shape
    T = B * (-(-n_pg // ppb))
    live_row = hi > lo
    first = lo // ppb
    count = jnp.where(live_row, (hi + ppb - 1) // ppb - first, 1)
    ends = jnp.cumsum(count)
    t = jnp.arange(T)
    row = jnp.minimum(jnp.sum(ends <= t[:, None], axis=1), B - 1)
    blk = first[row] + t - (ends - count)[row]
    live = live_row[row] & (t < ends[-1])
    pg = jnp.clip(blk[:, None] * ppb + jnp.arange(ppb), lo[row][:, None],
                  jnp.maximum(hi - 1, lo)[row][:, None])
    pages = block_table[row[:, None], jnp.minimum(pg, n_pg - 1)]
    prev = jax.lax.cummax(jnp.where(live, t, -1))
    pages = jnp.where(live[:, None], pages, pages[jnp.maximum(prev, 0)])
    return (pages.reshape(-1), row.astype(jnp.int32),
            jnp.where(live, blk, -1).astype(jnp.int32), ends[-1])


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify(
    q: jax.Array,  # (B, C, H, D)
    k_pages: jax.Array,  # (P, Hkv, ps, D) page pool
    v_pages: jax.Array,  # (P, Hkv, ps, D)
    base: jax.Array,  # (B,) i32 — first query position per row
    block_table: jax.Array,  # (B, n_pg) i32 page ids
    anc: jax.Array | None = None,  # (B, C, C) ancestor bitmask (tree mode)
    active: jax.Array | None = None,  # (B,) bool — rows whose output is read
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
    M = group * C
    ppb = max(1, min(n_pg, _BLOCK_KEYS // ps))
    base = base.astype(jnp.int32)
    # live pages [lo, hi): up to the chunk's last position, from the first
    # position the window leaves visible to the chunk's first query
    hi = jnp.clip((base + C + ps - 1) // ps, 0, n_pg)
    if active is not None:
        hi = jnp.where(active, hi, 0)
    lo = (jnp.clip((base - window + 1) // ps, 0, hi) if window
          else jnp.zeros_like(hi))
    pages, rows, blocks, n_steps = _schedule(
        block_table.astype(jnp.int32), lo, hi, ppb)
    # (B, C, H, D) -> (B, Hkv, group * C, D): row g * C + j is query head
    # (kv head, g) at chunk position j
    qm = q.reshape(B, C, Hkv, group, D).transpose(0, 2, 3, 1, 4)
    row_spec = pl.BlockSpec((1, Hkv, M, D),
                            lambda t, pg, row, *_: (row[t], 0, 0, 0))
    operands = [pages, rows, blocks, base, qm.reshape(B, Hkv, M, D)]
    in_specs = [row_spec]
    if anc is not None:
        operands.append(jnp.tile(anc.astype(jnp.float32), (1, group, 1)))
        in_specs.append(pl.BlockSpec(
            (1, M, C), lambda t, pg, row, *_: (row[t], 0, 0)))
    # the pool once per page slot of a block: slot i of step t is the
    # page pages[t, i], all heads, double-buffered by the pipeline
    page_specs = [
        pl.BlockSpec((1, Hkv, ps, D),
                     lambda t, pg, *_, i=i: (pg[t * ppb + i], 0, 0, 0))
        for i in range(ppb)]
    operands += [k_pages] * ppb + [v_pages] * ppb
    in_specs += page_specs * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # step pages, rows and blocks; bases
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, M, D), jnp.float32),
            pltpu.VMEM((Hkv, M, 1), jnp.float32),
            pltpu.VMEM((Hkv, M, 1), jnp.float32),
        ],
    )
    body = functools.partial(
        _paged_verify_kernel, n_pg=n_pg, ps=ps, ppb=ppb, chunk=C,
        window=window, tree=anc is not None)
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, M, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, Hkv, group, C, D).transpose(0, 3, 1, 2, 4).reshape(
        B, C, H, D)
