"""Model FLOPs of one call of a serving step.

Counted from the tokens the call computes and the keys each attends:
every projection and MLP matrix once per token (2 FLOPs per
multiply-add), attention scores and values over the live keys (4 FLOPs
per query head, key and head channel), and the LM head for each row
whose logits the call returns.  Rows that only ride along in the batch,
padding and pages past the live length are not counted.

A call is ``{"kind": "decode", "lens": [cached positions of each
decoding row]}`` or ``{"kind": "prefill", "start": s, "n": n}``.
"""
from __future__ import annotations

from typing import Dict


def layer_params(dims: Dict) -> int:
    d, ff = dims["d_model"], dims["d_ff"]
    qd = dims["n_heads"] * dims["head_dim"]
    kvd = dims["n_kv_heads"] * dims["head_dim"]
    return d * qd + 2 * d * kvd + qd * d + dims["mlp_matrices"] * d * ff


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
