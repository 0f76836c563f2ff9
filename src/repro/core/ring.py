"""Ring-overlap tensor parallelism — LoopLynx's router + transmission hiding.

The paper interconnects accelerator nodes in a ring and hides the
synchronization of block *k-1* inside the block-matmul of block *k*
(Fig 4c / Fig 6c).  The TPU-native form is the *collective matmul*: the
all-gather / reduce-scatter around a Megatron linear is decomposed into
``n`` ``jax.lax.ppermute`` hops interleaved with per-chunk partial matmuls,
so each ICI transfer overlaps the next chunk's MXU work — the identical
dependency structure to the paper's "sync of previous block hidden within
computation of current block".

All functions here are *per-device* bodies meant to run under
``jax.shard_map``; ``tests/test_ring.py`` checks them against the dense
matmul on 8 virtual devices.  Naive (exposed-collective) variants are kept
for the §Perf before/after comparison.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compat


def _axis_size_index(axis_name):
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    return n, idx


# ---------------------------------------------------------------------------
# All-gather collective matmul (column-parallel consumer)
# ---------------------------------------------------------------------------


def ring_ag_matmul(x_local: jax.Array, w_local: jax.Array, axis_name: str):
    """Y_local = X_full @ W_local with the X all-gather hidden in the ring.

    x_local: (M, Kl) — this device's feature shard of X (K = n * Kl)
    w_local: (K, Nl) — full-K rows of this device's output-column shard
    returns: (M, Nl)

    Step t multiplies the chunk that originated at device ``idx + t`` while
    simultaneously forwarding it around the ring; communication of chunk
    t+1 overlaps the matmul of chunk t (paper Fig 4c).
    """
    n, idx = _axis_size_index(axis_name)
    M, Kl = x_local.shape
    Nl = w_local.shape[1]
    perm = [(i, (i - 1) % n) for i in range(n)]  # receive from successor

    def body(t, carry):
        acc, chunk = carry
        src = (idx + t) % n
        w_rows = jax.lax.dynamic_slice_in_dim(w_local, src * Kl, Kl, axis=0)
        nxt = jax.lax.ppermute(chunk, axis_name, perm)  # overlaps the dot
        acc = acc + jnp.dot(
            chunk, w_rows, preferred_element_type=jnp.float32
        )
        return acc, nxt

    acc = jnp.zeros((M, Nl), jnp.float32)
    acc, _ = jax.lax.fori_loop(0, n, body, (acc, x_local), unroll=True)
    return acc.astype(x_local.dtype)


def naive_ag_matmul(x_local: jax.Array, w_local: jax.Array, axis_name: str):
    """Exposed-collective baseline: all-gather X, then one matmul."""
    x_full = jax.lax.all_gather(x_local, axis_name, axis=1, tiled=True)
    return jnp.dot(x_full, w_local, preferred_element_type=jnp.float32).astype(
        x_local.dtype
    )


# ---------------------------------------------------------------------------
# Reduce-scatter collective matmul (row-parallel producer)
# ---------------------------------------------------------------------------


def ring_rs_matmul(x_local: jax.Array, w_local: jax.Array, axis_name: str):
    """Y_local = reduce_scatter(X_local @ W_local) with the RS in the ring.

    x_local: (M, Kl) — feature shard of X
    w_local: (Kl, N) — this device's row shard of W (full N)
    returns: (M, Nl) — output block ``idx`` of the summed product

    A travelling accumulator picks up each device's partial contribution and
    lands at its home device after n-1 hops; each hop overlaps the next
    partial matmul.
    """
    n, idx = _axis_size_index(axis_name)
    M = x_local.shape[0]
    N = w_local.shape[1]
    Nl = N // n
    perm = [(i, (i + 1) % n) for i in range(n)]  # send forward

    def wblock(b):
        return jax.lax.dynamic_slice_in_dim(w_local, b * Nl, Nl, axis=1)

    # The accumulator hops d-1 -> d each step, so the block device d works
    # on at step t is (d - t - 1) mod n; after n-1 hops block d lands home.
    acc = jnp.dot(
        x_local, wblock((idx - 1) % n), preferred_element_type=jnp.float32
    )

    def body(t, acc):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        b = (idx - t - 1) % n
        return acc + jnp.dot(
            x_local, wblock(b), preferred_element_type=jnp.float32
        )

    acc = jax.lax.fori_loop(1, n, body, acc, unroll=True)
    return acc.astype(x_local.dtype)


def naive_rs_matmul(x_local: jax.Array, w_local: jax.Array, axis_name: str):
    """Exposed-collective baseline: matmul then psum_scatter."""
    y = jnp.dot(x_local, w_local, preferred_element_type=jnp.float32)
    y = jax.lax.psum_scatter(y, axis_name, scatter_dimension=1, tiled=True)
    return y.astype(x_local.dtype)


# ---------------------------------------------------------------------------
# jit-level wrapper
# ---------------------------------------------------------------------------

_STRATEGIES: dict[str, Callable] = {
    "ring_ag": ring_ag_matmul,
    "naive_ag": naive_ag_matmul,
    "ring_rs": ring_rs_matmul,
    "naive_rs": naive_rs_matmul,
}


def tp_matmul(
    x: jax.Array,
    w: jax.Array,
    mesh: jax.sharding.Mesh,
    axis: str = "model",
    strategy: str = "ring_ag",
) -> jax.Array:
    """Distributed matmul over mesh axis ``axis`` with the given schedule.

    For ``*_ag``:  x is sharded (M, K/n), w replicated-rows (K, N/n) shards
    concatenated on N; result (M, N) sharded on N.
    For ``*_rs``:  x sharded (M, K/n), w sharded rows (K/n, N); result
    (M, N) sharded on N (reduce-scattered).
    """
    fn = _STRATEGIES[strategy]
    if strategy.endswith("_ag"):
        in_specs = (P(None, axis), P(None, axis))
        # per-device w must be (K, Nl): shard columns only
        body = lambda xl, wl: fn(xl, wl, axis)
    else:
        in_specs = (P(None, axis), P(axis, None))
        body = lambda xl, wl: fn(xl, wl, axis)
    out_specs = P(None, axis)
    return compat.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )(x, w)


def tp_quant_matmul(
    x_q: jax.Array,  # int8 (M, K) quantized activations (replicated)
    w_q: jax.Array,  # int8 (K, N) quantized weights (sharded on N)
    x_scale: jax.Array,  # f32 (M, 1) per-token scales (replicated)
    w_scale: jax.Array,  # f32 (1, N) per-channel scales (sharded on N)
    bias=None,  # f32 (N,) or None
    *,
    mesh: jax.sharding.Mesh,
    axis: str = "model",
    out_dtype=jnp.bfloat16,
    backend: str = "auto",
) -> jax.Array:
    """W8A8 matmul sharded over output columns (Megatron column-parallel).

    Each device runs the Fused MP kernel (:func:`repro.kernels.ops.
    quant_matmul`) on its (K, N/n) weight shard with the full activations;
    outputs concatenate on N.  Because weight scales are per-output-channel
    and activation scales per-token, every output column is computed by
    exactly the math the unsharded kernel uses — the sharded result is
    *bit-identical*, so routing the quantized engine through ``mesh=`` can
    never change the served stream (asserted in
    ``tests/subscripts/ring_check.py``).
    """
    from repro.kernels import ops

    N = w_q.shape[1]
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)

    def body(xq, wq, xs, ws, b):
        return ops.quant_matmul(
            xq, wq, xs, ws, b, out_dtype=out_dtype, backend=backend)

    return compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, axis), P(), P(None, axis), P(axis)),
        out_specs=P(None, axis),
    )(x_q, w_q, x_scale, w_scale, bias)
