"""Plain float32 sparse-expert decoder of the program's ``moe`` family
(the block of OLMoE, arXiv:2409.02060, without its QK-norm), written
from the description; a test family that enters the benchmark through
its own files alone.

Pre-norm blocks: ``x += attn(RMS1(x))``, ``x += moe(RMS2(x))``; multi-head
attention with rotary embeddings on every channel (``rotate_half`` form,
inverse frequencies ``rope_theta ** (-2i / head_dim)``), scaled by
``1/sqrt(head_dim)``; RMSNorm ``x / sqrt(mean(x^2) + eps) * w``; untied
LM head.  The expert layer: router logits ``h W_r`` (float32), softmax
over all ``num_experts``, exact top ``num_experts_per_tok`` (no
capacity, no token dropped), gates renormalised to sum to one where
``norm_topk_prob``; each chosen expert computes ``W_down (silu(W_gate
h) * W_up h)`` and the layer returns the gate-weighted sum.  Every
expert is computed for every token and the unchosen ones are weighted
by zero.  Every matrix product runs at ``HIGHEST`` precision.

``quant`` (``{"weight_bits": b, "act_bits": a}``) turns the reference
into a control: every projection, the router, each expert's matrices
and the LM head see weights rounded per output channel and inputs
rounded per token.

Published OLMoE also normalises queries and keys (QK-norm) and does not
renormalise the top-k gates; the program does neither, and this file
describes the program's block.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

#: program-config fields the file's sizes fix
PROGRAM_KEYS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                "vocab_size", "n_experts", "experts_per_token",
                "rope_theta", "activation", "norm")


def dims(conf: Dict) -> Dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "n_layers": conf["num_hidden_layers"], "d_model": d,
        "n_heads": H, "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": d // H, "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "n_experts": conf["num_experts"],
        "experts_per_token": conf["num_experts_per_tok"],
        "rope_theta": float(conf["rope_theta"]),
        "activation": "swiglu", "norm": "rmsnorm",
    }


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def make(dims: Dict, key: jax.Array, dtype) -> Dict:
    """The program's MoE layout, layers stacked on a leading axis; the
    router stays float32, as the program keeps it."""
    L, d = dims["n_layers"], dims["d_model"]
    qd = dims["n_heads"] * dims["head_dim"]
    kvd = dims["n_kv_heads"] * dims["head_dim"]
    ff, E, V = dims["d_ff"], dims["n_experts"], dims["vocab_size"]
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in, dt=dtype):
        return _normal(next(ks), shape, fan_in ** -0.5, dt)

    def norm(lead):
        return {"w": (1.0 + _normal(next(ks), lead + (d,), 0.05,
                                    jnp.float32)).astype(dtype)}

    layer = {
        "ln1": norm((L,)),
        "attn": {"q": {"w": w((L, d, qd), d)}, "k": {"w": w((L, d, kvd), d)},
                 "v": {"w": w((L, d, kvd), d)},
                 "out": {"w": w((L, qd, d), qd)}},
        "ln2": norm((L,)),
        "moe": {"router": {"w": w((L, d, E), d, jnp.float32)},
                "w_up": w((L, E, d, ff), d), "w_gate": w((L, E, d, ff), d),
                "w_down": w((L, E, ff, d), ff)},
    }
    return {
        "embed": {"table": _normal(next(ks), (V, d), 0.02, dtype)},
        "periods": (layer,),
        "rest": [],
        "final_ln": norm(()),
        "lm_head": {"w": w((d, V), d)},
    }


def _fake_quant(x, bits, axis):
    top = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-8) / top
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _lin(x, w, quant):
    """``x (S, K) @ w (K, N)``, or ``(E, K, N)`` for every expert."""
    w = w.astype(jnp.float32)
    if quant:
        w = _fake_quant(w, quant[0], axis=-2)
        x = _fake_quant(x, quant[1], axis=-1)
    if w.ndim == 3:
        return jnp.einsum("sk,ekn->sen", x, w, precision=HI)
    return jnp.dot(x, w, precision=HI)


def _rms(x, p, eps):
    ms = jnp.square(x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * p["w"].astype(jnp.float32)


def _rope(x, pos, theta):
    """``x`` (S, heads, hd): rotate every channel."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, hd/2)
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = hd // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _moe(mw, h, k, norm_topk, quant):
    probs = jax.nn.softmax(_lin(h, mw["router"]["w"], quant), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)  # (S, k)
    if norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    weight = jnp.zeros_like(probs).at[rows, idx].set(gates)  # (S, E)
    up = _lin(h, mw["w_up"], quant)  # (S, E, ff)
    act = jax.nn.silu(_lin(h, mw["w_gate"], quant)) * up
    down = mw["w_down"].astype(jnp.float32)
    if quant:
        down = _fake_quant(down, quant[0], axis=-2)
        act = _fake_quant(act, quant[1], axis=-1)
    y = jnp.einsum("sef,efd->sed", act, down, precision=HI)
    return jnp.einsum("se,sed->sd", weight, y, precision=HI)


@functools.partial(jax.jit, static_argnums=(2,))
def _block(lw, x, geo):
    n_heads, n_kv, hd, theta, eps, k, norm_topk, quant = geo
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1"], eps)
    q = _lin(h, lw["attn"]["q"]["w"], quant).reshape(S, n_heads, hd)
    kk = _lin(h, lw["attn"]["k"]["w"], quant).reshape(S, n_kv, hd)
    v = _lin(h, lw["attn"]["v"]["w"], quant).reshape(S, n_kv, hd)
    q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
    g = n_heads // n_kv
    kk, v = jnp.repeat(kk, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kk, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    x = x + _lin(o.reshape(S, n_heads * hd), lw["attn"]["out"]["w"], quant)
    return x + _moe(lw["moe"], _rms(x, lw["ln2"], eps), k, norm_topk, quant)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(ln, w, x, want, eps, quant):
    return _lin(_rms(x[want], ln, eps), w, quant)


def logits(params: Dict, conf: Dict, tokens: Sequence[int],
           want: np.ndarray, quant: Optional[Dict] = None) -> np.ndarray:
    """Logits ``(len(want), vocab)`` at sequence positions ``want``."""
    eps = float(conf["rms_norm_eps"])
    qt = (quant["weight_bits"], quant["act_bits"]) if quant else None
    H = conf["num_attention_heads"]
    geo = (H, conf["num_key_value_heads"], conf["hidden_size"] // H,
           float(conf["rope_theta"]), eps, conf["num_experts_per_tok"],
           bool(conf["norm_topk_prob"]), qt)
    S = len(tokens)
    pad = -(-S // 128) * 128
    toks = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, pad - S)))
    x = params["embed"]["table"][toks].astype(jnp.float32)
    lay = params["periods"][0]
    for i in range(conf["num_hidden_layers"]):
        lw = jax.tree_util.tree_map(lambda a: a[i], lay)
        x = _block(lw, x, geo)
    n = len(want)
    want = np.pad(np.asarray(want, np.int32), (0, -(-n // 128) * 128 - n),
                  mode="edge")
    out = _head(params["final_ln"], params["lm_head"]["w"], x,
                jnp.asarray(want), eps, qt)
    return np.asarray(out)[:n]
