"""Plain float32 GPT-2 (Radford et al., 2019; the ``gpt2`` config of
Hugging Face transformers), written from the published description.

Pre-norm blocks: ``x += attn(LN1(x))``, ``x += mlp(LN2(x))``; learned
absolute positions added to the token embedding; causal multi-head
attention scaled by ``1/sqrt(head_dim)``; MLP ``W_down gelu_tanh(W_up
h)``; final LayerNorm; logits through the tied embedding.  The weights
carry no biases on the projections (the seed draws them as zero, the
published initialisation).  Every matrix product runs at ``HIGHEST``
precision, so on a TPU it is float32 and not bfloat16 passes.

``quant`` (``{"weight_bits": b, "act_bits": a}``) turns the reference
into a control: each projection's weight is rounded per output channel
and its input per token to symmetric ``b``/``a``-bit integers.
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
    """The sizes the program and the weight maker need."""
    return {
        "n_layers": conf["n_layer"], "d_model": conf["n_embd"],
        "n_heads": conf["n_head"], "n_kv_heads": conf["n_head"],
        "head_dim": conf["n_embd"] // conf["n_head"],
        "d_ff": conf["n_inner"], "vocab_size": conf["vocab_size"],
        "learned_pos": conf["n_positions"], "tied": True,
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


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block(lw, x, n_heads, eps, quant):
    S, d = x.shape
    hd = d // n_heads
    h = _ln(x, lw["ln1"], eps)
    q = _lin(h, lw["attn"]["q"]["w"], quant).reshape(S, n_heads, hd)
    k = _lin(h, lw["attn"]["k"]["w"], quant).reshape(S, n_heads, hd)
    v = _lin(h, lw["attn"]["v"]["w"], quant).reshape(S, n_heads, hd)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, d)
    x = x + _lin(o, lw["attn"]["out"]["w"], quant)
    h = _ln(x, lw["ln2"], eps)
    h = _gelu_tanh(_lin(h, lw["mlp"]["up"]["w"], quant))
    return x + _lin(h, lw["mlp"]["down"]["w"], quant)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(params, x, want, eps):
    x = _ln(x[want], params["final_ln"], eps)
    table = params["embed"]["table"].astype(jnp.float32)
    return jnp.dot(x, table.T, precision=HI)


def logits(params: Dict, conf: Dict, tokens: Sequence[int],
           want: np.ndarray, quant: Optional[Dict] = None) -> np.ndarray:
    """Logits ``(len(want), vocab)`` at sequence positions ``want``."""
    eps = float(conf["layer_norm_epsilon"])
    qt = (quant["weight_bits"], quant["act_bits"]) if quant else None
    S = len(tokens)
    pad = min(-(-S // 256) * 256, conf["n_positions"])
    toks = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, pad - S)))
    x = (params["embed"]["table"].astype(jnp.float32)[toks]
         + params["pos_embed"].astype(jnp.float32)[:pad])
    lay = params["periods"][0]
    for i in range(conf["n_layer"]):
        lw = jax.tree_util.tree_map(lambda a: a[i], lay)
        x = _block(lw, x, conf["n_head"], eps, qt)
    n = len(want)
    want = np.pad(np.asarray(want, np.int32), (0, -(-n // 128) * 128 - n),
                  mode="edge")
    return np.asarray(_head(params, x, jnp.asarray(want), eps))[:n]
