"""Token samplers for the serving engine.

Two layers:

  * pure single-policy functions (:func:`greedy`, :func:`temperature`,
    :func:`top_k`) — kept for tests and offline use;
  * :class:`SamplingParams` + :func:`sample_batch` — the engine path.  Each
    request carries its own (temperature, top_k, top_p); the engine packs
    them into per-slot arrays and one jitted ``sample_batch`` call samples
    the whole batch, so heterogeneous requests share a single decode tick.

Convention: ``temperature <= 0`` means greedy (argmax); ``top_k <= 0``
disables the top-k filter; ``top_p >= 1`` disables the nucleus filter.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30


def greedy(logits: jax.Array, rng=None) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature(logits: jax.Array, rng: jax.Array, temp: float = 0.8):
    return jax.random.categorical(rng, logits / max(temp, 1e-4)).astype(
        jnp.int32
    )


def top_k(logits: jax.Array, rng: jax.Array, k: int = 40, temp: float = 0.8):
    vals, idx = jax.lax.top_k(logits, k)
    choice = jax.random.categorical(rng, vals / max(temp, 1e-4))
    return jnp.take_along_axis(idx, choice[..., None], axis=-1)[..., 0].astype(
        jnp.int32
    )


# ---------------------------------------------------------------------------
# per-request sampling (engine path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding policy. Defaults reproduce greedy decoding.

    ``priority`` and ``deadline_s`` feed admission, not sampling: higher
    priority admits (and preempts) first, and an absolute monotonic
    deadline orders the queue within a priority class.  The defaults
    (priority 0, no deadline) reproduce exact FIFO admission.
    """

    temperature: float = 0.0  # <= 0 -> greedy
    top_k: int = 0  # <= 0 -> no top-k filter
    top_p: float = 1.0  # >= 1 -> no nucleus filter
    priority: int = 0  # higher admits first, preempts lower
    deadline_s: float | None = None  # absolute time.monotonic() SLO


GREEDY = SamplingParams()


def _filter_logits(
    lg: jax.Array,  # (B, V) f32
    temp: jax.Array,  # (B,) f32
    topk: jax.Array,  # (B,) i32
    topp: jax.Array,  # (B,) f32
) -> jax.Array:
    """Temperature + per-row top-k + nucleus filtering, shared by
    :func:`sample_batch` and :func:`spec_accept_batch`.

    Greedy rows (temp <= 0) are sanitized to temperature 1.0 before the
    divide — their argmax is taken separately by the callers, and pushing
    real logits through the 1e-4 floor overflows them to inf, which turns
    the softmax row into NaNs (crashes under ``jax_debug_nans`` even
    though a final ``where`` discards the row).

    The nucleus cut keeps tokens by *rank* in the descending-probability
    order, not by comparing against the cutoff value: a value comparison
    readmits every token tied with the last kept one, exceeding mass p.
    """
    V = lg.shape[-1]
    safe_temp = jnp.where(temp <= 0.0, 1.0, jnp.maximum(temp, 1e-4))
    x = lg / safe_temp[:, None]
    # per-row top-k: threshold at the k-th largest value (k<=0 -> keep all)
    sorted_desc = -jnp.sort(-x, axis=-1)  # (B, V) descending
    k = jnp.clip(jnp.where(topk <= 0, V, topk), 1, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    x = jnp.where(x >= kth, x, _NEG_INF)
    # per-row top-p on the filtered distribution: keep the smallest prefix
    # of descending probs whose cumulative mass reaches p, by rank
    probs = jax.nn.softmax(x, axis=-1)
    order = jnp.argsort(-probs, axis=-1)  # descending, index-stable
    sp = jnp.take_along_axis(probs, order, axis=-1)
    keep = (jnp.cumsum(sp, axis=-1) - sp) < topp[:, None]
    keep = keep.at[:, 0].set(True)  # top_p <= 0 still keeps the top token
    n_keep = jnp.sum(keep.astype(jnp.int32), axis=-1, keepdims=True)
    ranks = jnp.argsort(order, axis=-1)  # token id -> its descending rank
    return jnp.where(ranks < n_keep, x, _NEG_INF)


def all_greedy(temp: np.ndarray) -> bool:
    """Whether every row of a batch is greedy: the host's copy of the
    predicate :func:`sample_batch` branches on, for counting which
    branch a call took."""
    return bool(np.all(temp <= 0.0))


def _sample_full(lg, rng, temp, topk, topp):
    greedy_tok = jnp.argmax(lg, axis=-1)
    x = _filter_logits(lg, temp, topk, topp)
    tok = jax.random.categorical(rng, x, axis=-1)
    return jnp.where(temp <= 0.0, greedy_tok, tok).astype(jnp.int32)


def _sample_greedy(lg, *_):
    return jnp.argmax(lg, axis=-1).astype(jnp.int32)


def sample_batch(
    logits: jax.Array,  # (B, V)
    rng: jax.Array,
    temp: jax.Array,  # (B,) f32
    topk: jax.Array,  # (B,) i32
    topp: jax.Array,  # (B,) f32
) -> jax.Array:
    """Sample one token per row under that row's sampling params.

    Fully vectorized: rows with temp<=0 take the argmax; the rest apply
    temperature, then a per-row top-k cut (mask below the k-th largest
    logit), then a per-row nucleus (top-p) cut, then categorical sampling.
    A batch whose rows are all greedy takes only the argmax: the filter's
    vocabulary sorts and the draw run only when some row needs them, and
    every row's token is the same either way.  Returns (B,) i32.
    """
    lg = logits.astype(jnp.float32)
    return jax.lax.cond(jnp.all(temp <= 0.0), _sample_greedy, _sample_full,
                        lg, rng, temp, topk, topp)


# ---------------------------------------------------------------------------
# speculative decoding: accept/reject against verified logits
# ---------------------------------------------------------------------------


def spec_accept_batch(
    logits: jax.Array,  # (B, C, V) verify logits, C >= k + 1
    draft: jax.Array,  # (B, k) i32 proposed tokens
    n_draft: jax.Array,  # (B,) i32 valid draft count per row
    rng: jax.Array,
    temp: jax.Array,  # (B,) f32
    topk: jax.Array,  # (B,) i32
    topp: jax.Array,  # (B,) f32
) -> Tuple[jax.Array, jax.Array]:
    """Accept/reject deterministically-drafted tokens against the target
    distribution, preserving it exactly.

    ``logits[b, i]`` is the target's next-token distribution after the
    row's context plus ``draft[b, :i]`` (position 0 follows the current
    token) — the per-position logits one ``lm.verify_chunk`` call returns.
    Draft tokens are a *point-mass* proposal (n-gram lookup, or a greedy
    draft model), so the Leviathan accept rule reduces to: accept token i
    with probability ``p_i(d_i)`` under the row's (temperature / top-k /
    top-p filtered) target distribution ``p_i``; at the first rejection
    resample from the leftover ``(p - q)^+ / Z`` — which for a point mass
    is ``p_i`` with ``d_i`` struck out and renormalized.  Marginally every
    emitted token is distributed exactly as plain per-token sampling.

    Greedy rows (temp <= 0) reduce to the longest draft prefix matching
    the argmax chain plus the argmax at the first divergence (or the bonus
    argmax after a full match) — token-for-token the plain greedy stream.

    Returns ``(n_accept (B,) i32, next_tok (B,) i32)``: row b emits
    ``draft[b, :n_accept[b]]`` followed by ``next_tok[b]`` — 1..k+1 tokens.
    """
    B, C, V = logits.shape
    k = draft.shape[1]
    lg = logits.astype(jnp.float32)
    flat = _filter_logits(
        lg.reshape(B * C, V),
        jnp.repeat(temp, C), jnp.repeat(topk, C), jnp.repeat(topp, C),
    ).reshape(B, C, V)
    probs = jax.nn.softmax(flat, axis=-1)
    gtok = jnp.argmax(lg, axis=-1)  # (B, C) the greedy chain

    p_draft = jnp.take_along_axis(
        probs[:, :k], draft[..., None], axis=-1)[..., 0]  # (B, k)
    r1, r2 = jax.random.split(rng)
    u = jax.random.uniform(r1, (B, k))
    greedy_row = (temp <= 0.0)[:, None]
    ok = jnp.where(greedy_row, draft == gtok[:, :k], u < p_draft)
    ok = ok & (jnp.arange(k)[None] < n_draft[:, None])
    n_accept = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=-1), axis=-1)

    # bonus (all accepted) / corrective (first rejection) token: in both
    # cases the right distribution sits at chunk position n_accept
    row = jnp.arange(B)
    p_next = probs[row, n_accept]  # (B, V)
    rejected = n_accept < n_draft
    d_rej = draft[row, jnp.minimum(n_accept, k - 1)]
    strike = rejected[:, None] & (jnp.arange(V)[None] == d_rej[:, None])
    p_next = jnp.where(strike, 0.0, p_next)
    p_next = p_next / jnp.maximum(
        jnp.sum(p_next, axis=-1, keepdims=True), 1e-30)
    sampled = jax.random.categorical(
        r2, jnp.log(jnp.maximum(p_next, 1e-30)), axis=-1)
    next_tok = jnp.where(temp <= 0.0, gtok[row, n_accept], sampled)
    return n_accept.astype(jnp.int32), next_tok.astype(jnp.int32)


def spec_accept_tree(
    logits: jax.Array,  # (B, C, V) verify logits over the tree chunk
    tokens: jax.Array,  # (B, k) i32 tree node tokens, DFS order
    parents: jax.Array,  # (B, k) i32 parent *chunk position* per node
    n_nodes: jax.Array,  # (B,) i32 valid node count per row
    rng: jax.Array,
    temp: jax.Array,  # (B,) f32
    topk: jax.Array,  # (B,) i32
    topp: jax.Array,  # (B,) f32
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Accept/reject a token *tree* against the target distribution.

    Node ``j`` (1-based chunk position; node index ``j - 1``) carries
    token ``tokens[b, j-1]`` and hangs off chunk position
    ``parents[b, j-1]`` (0 = the root / current token).  ``logits[b, i]``
    is the target distribution after the row's context plus position
    ``i``'s root path — what the ancestor-masked verify returns.  Nodes
    are walked in DFS order (parents strictly before children): a node is
    *tryable* iff its parent was accepted and no earlier sibling already
    won that parent.  Each tryable candidate takes the Leviathan
    point-mass decision against the parent's *residual* distribution —
    previously rejected siblings struck out and the mass renormalized
    (sampling without replacement), which preserves the target
    distribution exactly for stochastic rows.  Greedy rows accept a child
    iff its token equals the parent's argmax, reducing to the longest
    root-to-leaf prefix of the greedy chain.

    The corrective/bonus token samples the final accepted position's
    residual (its rejected children struck, renormalized); greedy rows
    take its argmax.  On a chain-shaped tree (node ``j``'s parent is
    ``j - 1``) every step reduces *bit-exactly* to
    :func:`spec_accept_batch`: the first trial at each parent divides by
    a residual mass of exactly ``1.0``, the same per-trial uniforms line
    up, and the finale applies the identical strike/renorm/categorical
    ops.

    Returns ``(n_accept (B,) i32, accepted (B, C) bool, next_tok (B,)
    i32)``: the accepted chunk positions (position 0 always set) form a
    root-to-leaf path whose ascending order is depth order; row b emits
    the accepted nodes' tokens followed by ``next_tok[b]``.
    """
    B, C, V = logits.shape
    k = tokens.shape[1]
    assert k + 1 <= C, (tokens.shape, logits.shape)
    lg = logits.astype(jnp.float32)
    flat = _filter_logits(
        lg.reshape(B * C, V),
        jnp.repeat(temp, C), jnp.repeat(topk, C), jnp.repeat(topp, C),
    ).reshape(B, C, V)
    probs = jax.nn.softmax(flat, axis=-1)
    gtok = jnp.argmax(lg, axis=-1)  # (B, C) greedy token at each position

    r1, r2 = jax.random.split(rng)
    u = jax.random.uniform(r1, (B, k))  # one uniform per node trial
    greedy_row = temp <= 0.0  # (B,)
    row = jnp.arange(B)

    accepted = jnp.zeros((B, C), bool).at[:, 0].set(True)
    child_done = jnp.zeros((B, C), bool)  # parent already has a winner
    struck = jnp.zeros((B, C, V), bool)  # rejected tokens per position
    struck_mass = jnp.zeros((B, C), jnp.float32)

    for j in range(1, k + 1):
        par = parents[:, j - 1]  # (B,) parent chunk position
        tok = tokens[:, j - 1]  # (B,)
        tryable = (
            ((j - 1) < n_nodes)
            & accepted[row, par]
            & ~child_done[row, par]
        )
        p_tok = probs[row, par, tok]
        was_struck = struck[row, par, tok]
        denom = jnp.maximum(1.0 - struck_mass[row, par], 1e-30)
        p_try = jnp.where(was_struck, 0.0, p_tok) / denom
        ok = jnp.where(greedy_row, tok == gtok[row, par], u[:, j - 1] < p_try)
        ok = ok & tryable
        rej = tryable & ~ok
        accepted = accepted.at[:, j].set(ok)
        child_done = child_done.at[row, par].set(child_done[row, par] | ok)
        struck = struck.at[row, par, tok].set(struck[row, par, tok] | rej)
        struck_mass = struck_mass.at[row, par].add(
            jnp.where(rej & ~was_struck, p_tok, 0.0))

    # deepest accepted position = max accepted index (DFS: parent < child)
    fin = jnp.max(
        jnp.where(accepted, jnp.arange(C)[None], 0), axis=1)  # (B,)
    n_accept = jnp.sum(accepted[:, 1:].astype(jnp.int32), axis=1)

    p_next = probs[row, fin]  # (B, V)
    p_next = jnp.where(struck[row, fin], 0.0, p_next)
    p_next = p_next / jnp.maximum(
        jnp.sum(p_next, axis=-1, keepdims=True), 1e-30)
    sampled = jax.random.categorical(
        r2, jnp.log(jnp.maximum(p_next, 1e-30)), axis=-1)
    next_tok = jnp.where(greedy_row, gtok[row, fin], sampled)
    return (
        n_accept.astype(jnp.int32),
        accepted,
        next_tok.astype(jnp.int32),
    )
