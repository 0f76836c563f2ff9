"""The four-chip path at CPU size: the test cell ``tiny-gpt2-chat-4chip``
runs ``DistributedServeEngine`` on four virtual CPU devices through
``cell.run``, in one subprocess (``four_chip_runs.py``).  A sound run is
``correct``, traced or not; a timed path broken underneath, the exchange
between chips among the breaks, is not."""
import json
import subprocess
import sys

import pytest

from benchtools import BENCH, make_root


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("checkout"))
    cache = tmp_path_factory.mktemp("jax-cache")
    p = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "four_chip_runs.py"),
         str(root), str(cache)],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out, last = {}, None
    for line in p.stdout.splitlines():
        if line.startswith('{"case"'):
            case = json.loads(line)
            assert case["rc"] == 0, (case, p.stderr[-4000:])
            out[case["case"]] = json.loads(last)
        last = line
    return out


@pytest.mark.parametrize("case", ["sound", "sound_traced"])
def test_sound_four_chip_run_is_correct(runs, case):
    res = runs[case]
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4
    m = res["metrics"]
    if case == "sound":
        assert set(m) == {"itl_p50_ms", "itl_p95_ms", "tok_s", "setup_s"}
    else:
        # the counters the harness reads from the spans and the page
        # managers; the device-trace metrics need a chip
        assert m["decode_rows_mean"]["value"] >= 1.0
        assert 0 < m["kv_pages_peak_frac"]["value"] <= 1.0


@pytest.mark.parametrize("case", ["exchange_left_out", "state_unchanged",
                                  "half_batch_left_out", "altered_tokens"])
def test_broken_four_chip_path_is_not_correct(runs, case):
    res = runs[case]
    assert res["correct"] is False
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
