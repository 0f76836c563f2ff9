"""Model FLOPs of one call of a serving step of a Llama decoder
(``reference/llama.py``'s block).

As ``costs/model_step.py``, with the gated MLP's three matrices (up,
gate, down) counted for every token.
"""
from __future__ import annotations

from typing import Dict


def layer_params(dims: Dict) -> int:
    """Weights one token multiplies in one layer."""
    d, ff = dims["d_model"], dims["d_ff"]
    qd = dims["n_heads"] * dims["head_dim"]
    kvd = dims["n_kv_heads"] * dims["head_dim"]
    return d * qd + 2 * d * kvd + qd * d + 3 * d * ff


def attn_flops_per_key(dims: Dict) -> int:
    """All layers: QK^T and PV for one query against one key."""
    return dims["n_layers"] * 4 * dims["n_heads"] * dims["head_dim"]


def flops(call: Dict, dims: Dict) -> float:
    mm = 2 * dims["n_layers"] * layer_params(dims)
    head = 2 * dims["d_model"] * dims["vocab_size"]
    per_key = attn_flops_per_key(dims)
    if call["kind"] == "decode":
        return float(sum(mm + head + per_key * (L + 1)
                         for L in call["lens"]))
    s, n = call["start"], call["n"]
    keys = n * s + n * (n + 1) // 2  # query j sees s + j + 1 keys
    return float(mm * n + head + per_key * keys)
