"""Plain float32 Nemotron / Minitron decoder (arXiv:2407.14679; the
``nemotron`` config of Hugging Face transformers), written from the
published description.

Pre-norm blocks: ``x += attn(LN1(x))``, ``x += mlp(LN2(x))``; grouped
query attention (``num_key_value_heads`` K/V heads, each shared by
``num_attention_heads / num_key_value_heads`` query heads) with rotary
embeddings on the first ``partial_rotary_factor * head_dim`` channels of
every head (``rotate_half`` form, inverse frequencies
``rope_theta ** (-2i / rot_dim)``), scaled by ``1/sqrt(head_dim)``;
ungated MLP ``W_down relu(W_up h) ** 2``; final LayerNorm; untied LM
head.  The published LayerNorm1P computes ``(1 + w)`` as its gain; the
seed draws the gain itself, which is the same function.  Every matrix
product runs at ``HIGHEST`` precision.

``quant`` (``{"weight_bits": b, "act_bits": a}``) turns the reference
into a control, as in ``gpt2.py``: every projection and the LM head see
weights rounded per output channel and inputs rounded per token.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def dims(conf: Dict) -> Dict:
    return {
        "n_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"],
        "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "learned_pos": 0, "tied": bool(conf["tie_word_embeddings"]),
        "norm": "layernorm", "mlp_matrices": 2,
    }


def _fake_quant(x, bits, axis):
    top = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-8) / top
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _lin(x, w, quant):
    w = w.astype(jnp.float32)
    if quant:
        w = _fake_quant(w, quant[0], axis=0)
        x = _fake_quant(x, quant[1], axis=-1)
    return jnp.dot(x, w, precision=HI)


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * p["w"].astype(jnp.float32)
            + p["b"].astype(jnp.float32))


def _rope(x, pos, theta, rot):
    """``x`` (S, heads, hd): rotate the first ``rot`` channels."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, rot/2)
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate(
        [xr * jnp.cos(emb) + rotated * jnp.sin(emb), xp], -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _block(lw, x, geo):
    n_heads, n_kv, hd, rot, theta, eps, quant = geo
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _ln(x, lw["ln1"], eps)
    q = _lin(h, lw["attn"]["q"]["w"], quant).reshape(S, n_heads, hd)
    k = _lin(h, lw["attn"]["k"]["w"], quant).reshape(S, n_kv, hd)
    v = _lin(h, lw["attn"]["v"]["w"], quant).reshape(S, n_kv, hd)
    q, k = _rope(q, pos, theta, rot), _rope(k, pos, theta, rot)
    g = n_heads // n_kv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    x = x + _lin(o.reshape(S, n_heads * hd), lw["attn"]["out"]["w"], quant)
    h = _ln(x, lw["ln2"], eps)
    h = jnp.square(jax.nn.relu(_lin(h, lw["mlp"]["up"]["w"], quant)))
    return x + _lin(h, lw["mlp"]["down"]["w"], quant)


@functools.partial(jax.jit, static_argnums=(3,))
def _final(ln, x, want, eps):
    return _ln(x[want], ln, eps)


@functools.partial(jax.jit, static_argnums=(2,))
def _head_block(x, w, quant):
    return _lin(x, w, quant)


def logits(params: Dict, conf: Dict, tokens: Sequence[int],
           want: np.ndarray, quant: Optional[Dict] = None) -> np.ndarray:
    """Logits ``(len(want), vocab)`` at sequence positions ``want``."""
    eps = float(conf["norm_eps"])
    qt = (quant["weight_bits"], quant["act_bits"]) if quant else None
    hd = conf["head_dim"]
    geo = (conf["num_attention_heads"], conf["num_key_value_heads"], hd,
           int(hd * conf["partial_rotary_factor"]),
           float(conf["rope_theta"]), eps, qt)
    S = len(tokens)
    pad = -(-S // 512) * 512
    toks = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, pad - S)))
    x = params["embed"]["table"][toks].astype(jnp.float32)
    lay = params["periods"][0]
    for i in range(conf["num_hidden_layers"]):
        lw = jax.tree_util.tree_map(lambda a: a[i], lay)
        x = _block(lw, x, geo)
    n = len(want)
    want = np.pad(np.asarray(want, np.int32), (0, -(-n // 128) * 128 - n),
                  mode="edge")
    x = _final(params["final_ln"], x, jnp.asarray(want), eps)
    # the head in column blocks: a float32 copy of the whole head would
    # not fit beside the weights (quantization is per column either way)
    w = params["lm_head"]["w"]
    step = -(-w.shape[1] // 8)
    out = [np.asarray(_head_block(x, w[:, j:j + step], qt))
           for j in range(0, w.shape[1], step)]
    return np.concatenate(out, axis=1)[:n]
