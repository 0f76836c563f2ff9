"""Public jit'd wrappers for the Pallas MDK kernels.

Dispatch policy (``backend`` argument, default ``"auto"``):
  * ``"pallas"``     — compiled Pallas (TPU target).
  * ``"interpret"``  — Pallas interpreter (CPU correctness tests).
  * ``"jnp"``        — pure-jnp oracle from :mod:`repro.kernels.ref`
                       (CPU execution + dry-run lowering path).
  * ``"auto"``       — pallas on TPU, jnp elsewhere.

Wrappers also pad ragged shapes up to kernel block multiples and slice the
result back, so callers never deal with MXU alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ln_res_kernel import ln_res as _ln_res_pallas
from repro.kernels.mha_kernel import mha_decode as _mha_pallas
from repro.kernels.mp_kernel import mp_matmul as _mp_pallas
from repro.kernels.paged_verify_kernel import \
    paged_verify as _paged_verify_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(backend: str) -> bool:
    if backend == "auto":
        return _on_tpu()
    return backend in ("pallas", "interpret")


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------


def quant_matmul(
    x_q,
    w_q,
    x_scale,
    w_scale,
    bias=None,
    *,
    out_dtype=jnp.bfloat16,
    backend: str = "auto",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
):
    """Fused W8A8 matmul (LoopLynx Fused MP kernel)."""
    M, K = x_q.shape
    _, N = w_q.shape
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if not _use_pallas(backend):
        return ref.quant_matmul_ref(
            x_q, w_q, x_scale, w_scale, bias, out_dtype=out_dtype
        )
    bm = min(bm, max(8, M))
    xp = _pad_to(_pad_to(x_q, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w_q, 0, bk), 1, bn)
    xsp = _pad_to(x_scale, 0, bm)
    wsp = _pad_to(w_scale, 1, bn)
    bp = _pad_to(bias, 0, bn)
    out = _mp_pallas(
        xp,
        wp,
        xsp,
        wsp,
        bp,
        bm=bm,
        bn=bn,
        bk=bk,
        out_dtype=out_dtype,
        interpret=(backend == "interpret"),
    )
    return out[:M, :N]


def mha_decode(
    q,
    k_cache,
    v_cache,
    lengths,
    *,
    window: int = 0,
    backend: str = "auto",
    bs: int = 128,
):
    """Fused decode attention (LoopLynx Fused MHA kernel)."""
    if not _use_pallas(backend):
        return ref.mha_decode_ref(q, k_cache, v_cache, lengths, window=window)
    S = k_cache.shape[2]
    kp = _pad_to(k_cache, 2, bs)
    vp = _pad_to(v_cache, 2, bs)
    return _mha_pallas(
        q,
        kp,
        vp,
        lengths,
        bs=bs,
        window=window,
        interpret=(backend == "interpret"),
    )


def paged_mha_decode(
    q,
    k_pages,
    v_pages,
    lengths,
    block_table,
    *,
    window: int = 0,
    active=None,
    backend: str = "auto",
):
    """Fused decode attention over a paged KV cache (block-table gather).

    ``k_pages``/``v_pages`` are the global page pool ``(P, Hkv, ps, D)``;
    ``block_table`` ``(B, n_pg)`` names each sequence's pages.  The jnp
    path gathers the pool into a contiguous view and reuses the contiguous
    oracle, so it is bit-exact against :func:`mha_decode` on the same
    logical cache content; the Pallas path is the paged verify kernel at
    one query position (``base = length - 1``), which copies only each
    row's live pages (no materialized gather).  Rows ``active`` marks
    False visit no page; their output is zero on that path and is not to
    be read.
    """
    if not _use_pallas(backend):
        return ref.paged_mha_decode_ref(
            q, k_pages, v_pages, lengths, block_table, window=window)
    return _paged_verify_pallas(
        q[:, None],
        k_pages,
        v_pages,
        lengths - 1,
        block_table,
        active=active,
        window=window,
        interpret=(backend == "interpret"),
    )[:, 0]


def paged_verify(
    q,
    k_pages,
    v_pages,
    base,
    block_table,
    *,
    window: int = 0,
    anc=None,
    backend: str = "auto",
):
    """Chunked causal attention over a paged KV cache (verify/prefill).

    ``q`` is ``(B, C, H, D)`` — C query positions per row, position ``j``
    of row ``b`` at logical position ``base[b] + j`` — attending pages
    the row's ``block_table`` names, whose contents already include the
    chunk's own K/V (the in-place write).  The Pallas path copies only
    each row's live pages, all heads of a page at once; the jnp oracle
    gathers a contiguous view first and is the semantic ground truth.

    ``anc`` (``(B, C, C)`` bool/int) replaces the implicit causal
    in-chunk mask with a token tree's ancestor bitmask: position ``i``
    attends the committed prefix plus exactly the chunk positions its
    row of ``anc`` names.  Mutually exclusive with ``window``; a causal
    lower-triangular ``anc`` is bit-identical to the linear mask.
    """
    if anc is not None and window:
        raise ValueError("window and anc are mutually exclusive")
    if not _use_pallas(backend):
        return ref.paged_verify_ref(
            q, k_pages, v_pages, base, block_table, window=window, anc=anc)
    return _paged_verify_pallas(
        q,
        k_pages,
        v_pages,
        base,
        block_table,
        anc,
        window=window,
        interpret=(backend == "interpret"),
    )


def ln_res(
    x,
    res,
    weight,
    bias=None,
    *,
    kind: str = "layernorm",
    eps: float = 1e-5,
    backend: str = "auto",
    bb: int = 128,
):
    """Fused residual-add + norm + per-token int8 quant epilogue."""
    D = x.shape[-1]
    if bias is None:
        bias = jnp.zeros((D,), jnp.float32)
    if not _use_pallas(backend):
        return ref.ln_res_ref(x, res, weight, bias, kind=kind, eps=eps)
    B = x.shape[0]
    bb = min(bb, B)
    xp = _pad_to(x, 0, bb)
    rp = _pad_to(res, 0, bb)
    outs = _ln_res_pallas(
        xp,
        rp,
        weight,
        bias,
        kind=kind,
        eps=eps,
        bb=bb,
        interpret=(backend == "interpret"),
    )
    return tuple(o[:B] for o in outs)
