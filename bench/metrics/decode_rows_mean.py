"""Requests in DECODE per decode step over the window, counted by the
harness at each call of the engine's decode step (engine tick layer)."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    w0, w1 = rec["window"]
    rows = [len(c["lens"]) for c in rec["calls"]
            if c["kind"] == "decode" and w0 < c["t"] <= w1]
    return sum(rows) / len(rows) if rows else None
