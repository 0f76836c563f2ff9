"""Operations and bytes of the W8A8 matmul kernel (``kernels/mp_kernel.py``)
for one serving call, all layers.

Each quantized projection ``(K, N)`` of a call with ``M`` live rows:
``2 M K N`` integer operations; its int8 weights and float32 column
scales read once, the int8 activations and their row scales read, and
the outputs written in the engine's float32 activation stream.  Rows
that ride along in the batch and padding to the kernel's block shape are
not counted.  The tied embedding head stays in floating point.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

#: how the kernel's ops are named in the device trace
MATCH = r"^%mp_matmul\b"

OUT_BYTES = 4  # the W8A8 engine's activation stream is float32


def matrices(dims: Dict) -> List[Tuple[int, int]]:
    d, ff = dims["d_model"], dims["d_ff"]
    qd = dims["n_heads"] * dims["head_dim"]
    kvd = dims["n_kv_heads"] * dims["head_dim"]
    up = [(d, ff)] * (dims["mlp_matrices"] - 1)  # up, and gate if gated
    return [(d, qd), (d, kvd), (d, kvd), (qd, d)] + up + [(ff, d)]


def cost(call: Dict, dims: Dict):
    """``(int8 ops, bytes)`` of one call."""
    M = len(call["lens"]) if call["kind"] == "decode" else call["n"]
    ops = nbytes = 0
    for K, N in matrices(dims):
        ops += 2 * M * K * N
        nbytes += K * N + 4 * N + M * K + 4 * M + OUT_BYTES * M * N
    ops *= dims["n_layers"]
    nbytes *= dims["n_layers"]
    if not dims["tied"]:
        K, N = dims["d_model"], dims["vocab_size"]
        rows = M if call["kind"] == "decode" else 1
        ops += 2 * rows * K * N
        nbytes += K * N + 4 * N + rows * K + 4 * rows + OUT_BYTES * rows * N
    return float(ops), float(nbytes)


def bound_s(call: Dict, dims: Dict, engine: Dict, peak: Dict) -> float:
    """The least time the chip needs for the call."""
    o, b = cost(call, dims)
    return max(o / peak["int8_ops"], b / peak["hbm_bytes_per_s"])
