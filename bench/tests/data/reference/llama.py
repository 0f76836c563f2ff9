"""Plain float32 Llama decoder (Touvron et al., 2023; the ``llama``
config of Hugging Face transformers, as TinyLlama, arXiv:2401.02385,
publishes it), written from the published description; a test family
that enters the benchmark through its own files alone.

Pre-norm blocks: ``x += attn(RMS1(x))``, ``x += mlp(RMS2(x))``; grouped
query attention (``num_key_value_heads`` K/V heads, each shared by
``num_attention_heads / num_key_value_heads`` query heads) with rotary
embeddings on every channel (``rotate_half`` form, inverse frequencies
``rope_theta ** (-2i / head_dim)``), scaled by ``1/sqrt(head_dim)``;
RMSNorm ``x / sqrt(mean(x^2) + eps) * w``; gated MLP ``W_down (silu(W_gate
h) * W_up h)``; untied LM head.  Every matrix product runs at
``HIGHEST`` precision.

``quant`` (``{"weight_bits": b, "act_bits": a}``) turns the reference
into a control: every projection and the LM head see weights rounded
per output channel and inputs rounded per token.
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
                "vocab_size", "rope_theta", "activation", "norm")


def dims(conf: Dict) -> Dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "n_layers": conf["num_hidden_layers"], "d_model": d,
        "n_heads": H, "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": d // H, "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "activation": "swiglu", "norm": "rmsnorm",
    }


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def make(dims: Dict, key: jax.Array, dtype) -> Dict:
    """The program's layout for a gated MLP, layers stacked on a leading
    axis."""
    L, d = dims["n_layers"], dims["d_model"]
    qd = dims["n_heads"] * dims["head_dim"]
    kvd = dims["n_kv_heads"] * dims["head_dim"]
    ff, V = dims["d_ff"], dims["vocab_size"]
    ks = iter(jax.random.split(key, 16))

    def lin(k_in, k_out, lead=(L,)):
        return {"w": _normal(next(ks), lead + (k_in, k_out), k_in ** -0.5,
                             dtype)}

    def norm(lead):
        return {"w": (1.0 + _normal(next(ks), lead + (d,), 0.05,
                                    jnp.float32)).astype(dtype)}

    layer = {
        "ln1": norm((L,)),
        "attn": {"q": lin(d, qd), "k": lin(d, kvd), "v": lin(d, kvd),
                 "out": lin(qd, d)},
        "ln2": norm((L,)),
        "mlp": {"up": lin(d, ff), "gate": lin(d, ff), "down": lin(ff, d)},
    }
    return {
        "embed": {"table": _normal(next(ks), (V, d), 0.02, dtype)},
        "periods": (layer,),
        "rest": [],
        "final_ln": norm(()),
        "lm_head": lin(d, V, ()),
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


@functools.partial(jax.jit, static_argnums=(2,))
def _block(lw, x, geo):
    n_heads, n_kv, hd, theta, eps, quant = geo
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1"], eps)
    q = _lin(h, lw["attn"]["q"]["w"], quant).reshape(S, n_heads, hd)
    k = _lin(h, lw["attn"]["k"]["w"], quant).reshape(S, n_kv, hd)
    v = _lin(h, lw["attn"]["v"]["w"], quant).reshape(S, n_kv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = n_heads // n_kv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    x = x + _lin(o.reshape(S, n_heads * hd), lw["attn"]["out"]["w"], quant)
    h = _rms(x, lw["ln2"], eps)
    m = lw["mlp"]
    h = (jax.nn.silu(_lin(h, m["gate"]["w"], quant))
         * _lin(h, m["up"]["w"], quant))
    return x + _lin(h, m["down"]["w"], quant)


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
           float(conf["rope_theta"]), eps, qt)
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
