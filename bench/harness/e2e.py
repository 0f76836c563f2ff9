"""End-to-end arithmetic over a run's records.

Times are seconds on one clock, with 0 at the first due request.  A
request carries ``due`` (when the open loop was to send it) and
``emits`` (the end of the tick that produced each of its tokens).  The
window is ``(w0, w1]``.

* TTFT is first token minus due time, over every request due in the
  window; one still waiting at ``w1`` counts with its wait so far.
* An inter-token gap is counted where its later token falls in the
  window, over all requests.
* Tokens per second is every token emitted in the window over the
  window's length.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def pct(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (numpy's linear interpolation)."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def _due_in_window(rec: Dict) -> List[Dict]:
    w0, w1 = rec["window"]
    return [r for r in rec["requests"] if w0 <= r["due"] < w1]


def ttfts(rec: Dict) -> List[float]:
    """TTFT of each request due in the window, censored at ``w1``."""
    w1 = rec["window"][1]
    out = []
    for r in _due_in_window(rec):
        first = r["emits"][0] if r["emits"] else None
        if first is None or first > w1:
            first = w1
        out.append(first - r["due"])
    return out


def first_tokens_in_window(rec: Dict) -> int:
    w0, w1 = rec["window"]
    return sum(1 for r in rec["requests"]
               if r["emits"] and w0 < r["emits"][0] <= w1)


def gaps(rec: Dict) -> List[float]:
    """Every inter-token gap whose later token falls in the window."""
    w0, w1 = rec["window"]
    out = []
    for r in rec["requests"]:
        e = r["emits"]
        for a, b in zip(e, e[1:]):
            if w0 < b <= w1:
                out.append(b - a)
    return out


def tokens_in_window(rec: Dict) -> int:
    w0, w1 = rec["window"]
    return sum(1 for r in rec["requests"] for t in r["emits"]
               if w0 < t <= w1)


def waiting_at(rec: Dict, t: float) -> int:
    """Requests due by ``t`` without a first token by then (queued or
    prefilling): the backlog, which grows past the knee."""
    return sum(1 for r in rec["requests"]
               if r["due"] <= t and (not r["emits"] or r["emits"][0] > t))


def decoding_at(rec: Dict, t: float) -> int:
    """Requests with a first token by ``t`` and a later one after it:
    the streams that ``t`` finds decoding."""
    return sum(1 for r in rec["requests"]
               if r["emits"] and r["emits"][0] <= t < r["emits"][-1])


def done_in(rec: Dict) -> int:
    """Requests whose last token fell in the window."""
    w0, w1 = rec["window"]
    return sum(1 for r in rec["requests"] if r["req"] is not None
               and r["req"].done and w0 < r["emits"][-1] <= w1)


def flow(rec: Dict) -> Dict:
    """Whether the window sees a steady state: the requests due and done
    in it, and the backlog and the decoding streams at its open and its
    close (read before the drive loop's records let go of the
    engine's requests)."""
    w0, w1 = rec["window"]
    return {"due": len(_due_in_window(rec)), "done": done_in(rec),
            "waiting": (waiting_at(rec, w0), waiting_at(rec, w1)),
            "decoding": (decoding_at(rec, w0), decoding_at(rec, w1))}


def metrics(rec: Dict) -> Dict[str, Optional[float]]:
    """Every end-to-end number a cell may report, by its name."""
    w0, w1 = rec["window"]
    g = gaps(rec)
    t = ttfts(rec)
    return {
        "ttft_p90_ms": None if not t else pct(t, 90) * 1e3,
        "itl_p50_ms": None if not g else pct(g, 50) * 1e3,
        "itl_p95_ms": None if not g else pct(g, 95) * 1e3,
        "tok_s": tokens_in_window(rec) / (w1 - w0),
    }
