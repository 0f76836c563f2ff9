"""Operations and bytes of the paged attention kernel
(``kernels/paged_verify_kernel.py``) for one serving call, all layers.

The work the live K/V needs: every query head against every live key of
its row (4 FLOPs per query head, key and channel), each live key's K and
V read once per K/V head, and the queries read and outputs written once.
Pages past the live length, padding rows and re-reads of a page by the
query heads that share it are not work, so they are not counted.
"""
from __future__ import annotations

from typing import Dict

#: how the kernel's ops are named in the device trace
MATCH = r"^%paged_verify\b"

KV_BYTES = 2  # the engine's page pool is bfloat16
#: bytes of a query or output element in the engine's activation stream
ACT_BYTES = {"w8a8": 4, "bf16": 2}


def cost(call: Dict, dims: Dict, act_bytes: int):
    """``(flops, bytes)`` of one call."""
    L_, H, Hkv, hd = (dims["n_layers"], dims["n_heads"], dims["n_kv_heads"],
                      dims["head_dim"])
    if call["kind"] == "decode":
        keys = sum(L + 1 for L in call["lens"])
        live = keys
        queries = len(call["lens"])
    else:
        s, n = call["start"], call["n"]
        keys = n * s + n * (n + 1) // 2
        live = s + n
        queries = n
    flops = 4 * H * hd * keys * L_
    nbytes = (2 * Hkv * hd * live * KV_BYTES
              + 2 * H * hd * queries * act_bytes) * L_
    return float(flops), float(nbytes)


def bound_s(call: Dict, dims: Dict, engine: Dict, peak: Dict) -> float:
    """The least time the chip needs for the call."""
    f, b = cost(call, dims, ACT_BYTES[engine["precision"]])
    return max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])
