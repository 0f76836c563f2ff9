"""Open-loop traffic from a mix file and a seed.

Every seed gets the same schedule of requests: inter-arrival gaps and
prompt and output lengths are the distributions' quantiles at evenly
spaced points, put in one fixed shuffled order, so the work in a run
does not depend on the seed.  The lead-in and the window each get a
population of their own, ``rate_rps`` times their length (rounded), with
the gaps scaled to fill that span.  The seed draws the token ids.

So the arrivals are not a random (Poisson) process: an exponential
mix has the gaps such a process would have at that rate, laid out as
one fixed schedule.  The order is fixed and not drawn from the seed
because a window holds
only a few requests, each longer than the window: which lengths came
first would decide the tokens in the window, and runs on different
seeds would differ by a quarter or more.

A mix file::

    {"arrival": {"schedule": "quantiles",
                 "gaps": "exponential" | "gamma", "rate_rps": r, "cv": c},
     "prompt_len": {"dist": "lognormal", "median": m, "sigma": s,
                    "min": lo, "max": hi},
     "output_len": {... as prompt_len ...},
     "lead_in_s": t}
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np
from scipy import stats


@dataclasses.dataclass
class Req:
    idx: int
    due: float  # seconds after the first due request
    prompt: List[int]
    max_new: int


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def gap_quantiles(arrival: Dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps with mean ``1 / rate_rps``: the gap
    distribution's quantiles (exponential, the gaps of a Poisson process
    of that rate; or gamma with coefficient of variation ``cv``)."""
    if arrival.get("schedule") != "quantiles":
        raise ValueError(f"unknown schedule {arrival.get('schedule')!r}")
    rate = float(arrival["rate_rps"])
    u = _grid(n)
    if arrival["gaps"] == "exponential":
        g = stats.expon.ppf(u)
    elif arrival["gaps"] == "gamma":
        cv = float(arrival["cv"])
        g = stats.gamma.ppf(u, 1.0 / cv ** 2)
    else:
        raise ValueError(f"unknown gap distribution {arrival['gaps']!r}")
    return g / g.mean() / rate


def length_quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths from a clipped distribution, as whole tokens."""
    u = _grid(n)
    if dist["dist"] == "lognormal":
        x = float(dist["median"]) * np.exp(
            float(dist["sigma"]) * stats.norm.ppf(u))
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def _segment(mix: Dict, order, rng, t0: float, span: float, vocab: int,
             first: int) -> List[Req]:
    """Requests due in ``[t0, t0 + span)``: ``rate_rps * span`` of them
    (rounded), gaps scaled to fill the span, the first due at ``t0``;
    shuffled by ``order``, token ids from ``rng``."""
    n = max(1, int(round(float(mix["arrival"]["rate_rps"]) * span)))
    g = gap_quantiles(mix["arrival"], n)
    gaps = order.permutation(g * span / g.sum())
    plen = order.permutation(length_quantiles(mix["prompt_len"], n))
    olen = order.permutation(length_quantiles(mix["output_len"], n))
    due = t0 + np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return [Req(idx=first + i, due=float(due[i]),
                prompt=[int(t) for t in rng.integers(1, vocab, int(plen[i]))],
                max_new=int(olen[i])) for i in range(n)]


def generate(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """The run's requests, in order of due time: the lead-in's, then the
    window's."""
    order, rng = np.random.default_rng(0), np.random.default_rng(seed)
    lead = float(mix["lead_in_s"])
    out = _segment(mix, order, rng, 0.0, lead, vocab, 0) if lead > 0 else []
    return out + _segment(mix, order, rng, lead, float(seconds), vocab,
                          len(out))
