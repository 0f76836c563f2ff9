"""Traffic generation: deterministic for a seed, and each mix file's
distributions."""
import json

import numpy as np
import pytest

from benchtools import BENCH
from harness import traffic

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.generate(_mix(name), 2**33 + 7, 30, 50257)
    b = traffic.generate(_mix(name), 2**33 + 7, 30, 50257)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_one_population(name):
    """Every seed gets the same schedule (due times and lengths, in the
    same order) and its own token ids."""
    mix = _mix(name)
    a = traffic.generate(mix, 1, 30, 50257)
    b = traffic.generate(mix, 2**33 + 1, 30, 50257)
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in b]
    assert len({len(r.prompt) for r in a}) > 1
    assert all(x.prompt != y.prompt for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_matches_the_file(name):
    mix = _mix(name)
    secs = 30
    reqs = traffic.generate(mix, 5, secs, 50257)
    lead, rate = mix["lead_in_s"], mix["arrival"]["rate_rps"]
    n_lead, n_win = max(1, round(rate * lead)), max(1, round(rate * secs))
    assert len(reqs) == n_lead + n_win
    due = np.array([r.due for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert due[n_lead] == lead and due[-1] < lead + secs
    gaps = traffic.gap_quantiles(mix["arrival"], 2000)
    assert np.isclose(gaps.mean(), 1.0 / rate)
    if mix["arrival"]["gaps"] == "exponential":  # CV 1
        assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05
    for key in ("prompt_len", "output_len"):
        d = mix[key]
        x = traffic.length_quantiles(d, 2001)
        assert x.min() >= d["min"] and x.max() <= d["max"]
        if d["dist"] == "lognormal":
            assert abs(np.median(x) - d["median"]) <= 1
    for r in reqs:
        lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
        assert lo <= len(r.prompt) <= hi
        assert all(1 <= t < 50257 for t in r.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_window_work_does_not_depend_on_the_seed(name):
    mix = _mix(name)
    lead = mix["lead_in_s"]

    def window(seed):
        reqs = traffic.generate(mix, seed, 30, 50257)
        w = [r for r in reqs if r.due >= lead]
        return (sorted(len(r.prompt) for r in w),
                sorted(r.max_new for r in w))

    assert window(1) == window(2**40 + 3)


def test_bursty_gaps_have_their_cv():
    g = traffic.gap_quantiles({"schedule": "quantiles", "gaps": "gamma",
                               "rate_rps": 2.0, "cv": 3.0}, 20000)
    assert np.isclose(g.mean(), 0.5)
    assert abs(g.std() / g.mean() - 3.0) < 0.3
