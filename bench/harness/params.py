"""Random weights from the seed, in the serving program's parameter layout.

The benchmark makes the weights; the program serves them and the plain
reference reads the same arrays.  All leaves come from one jitted call,
on the device, in the dtype they are served in (:func:`build`).  A
family whose reference module defines ``make(dims, key, dtype)`` gets
its weights from that; every other family gets the dense GPT-style
stack of :func:`make`.  Layers are stacked on a leading axis (the
program scans over them).

:func:`make`'s ``dims`` hold ``n_layers, d_model, n_heads, n_kv_heads,
head_dim, d_ff, vocab_size`` and the layout flags ``learned_pos`` (table
length, or 0), ``tied`` and ``norm`` (``layernorm``).
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict

import jax
import jax.numpy as jnp


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _norm(key, lead, d, dtype):
    kw, kb = jax.random.split(key)
    return {"w": (1.0 + _normal(kw, lead + (d,), 0.05, jnp.float32)
                  ).astype(dtype),
            "b": _normal(kb, lead + (d,), 0.02, dtype)}


def make(dims: Dict, key: jax.Array, dtype) -> Dict:
    """The parameter pytree; call under ``jax.jit`` (see :func:`build`)."""
    L, d = dims["n_layers"], dims["d_model"]
    qd = dims["n_heads"] * dims["head_dim"]
    kvd = dims["n_kv_heads"] * dims["head_dim"]
    ff, V = dims["d_ff"], dims["vocab_size"]
    ks = iter(jax.random.split(key, 16))

    def lin(k_in, k_out):
        return {"w": _normal(next(ks), (L, k_in, k_out), k_in ** -0.5,
                             dtype)}

    layer = {
        "ln1": _norm(next(ks), (L,), d, dtype),
        "attn": {"q": lin(d, qd), "k": lin(d, kvd), "v": lin(d, kvd),
                 "out": lin(qd, d)},
        "ln2": _norm(next(ks), (L,), d, dtype),
        "mlp": {"up": lin(d, ff), "down": lin(ff, d)},
    }
    params = {
        "embed": {"table": _normal(next(ks), (V, d), 0.02, dtype)},
        "periods": (layer,),
        "rest": [],
        "final_ln": _norm(next(ks), (), d, dtype),
    }
    if dims["learned_pos"]:
        params["pos_embed"] = _normal(
            next(ks), (dims["learned_pos"], d), 0.01, dtype)
    if not dims["tied"]:
        params["lm_head"] = {"w": _normal(next(ks), (d, V), d ** -0.5,
                                          dtype)}
    return params


def build(ref: ModuleType, dims: Dict, seed: int, dtype) -> Dict:
    """Every weight of the model, made on the device in one call by the
    family's maker (``ref.make``, else :func:`make`)."""
    maker = getattr(ref, "make", make)
    fn = jax.jit(lambda k: maker(dims, k, dtype))
    return fn(key_from_seed(seed))
