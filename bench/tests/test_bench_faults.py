"""Whole runs at CPU size, with the chip check skipped: a sound program
is ``correct``; a timed path broken underneath is not; with no TPU the
command exits non-zero and prints no result."""
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchtools import BENCH
from harness import cell, check, spec


@pytest.fixture(autouse=True)
def _no_repo_cache(monkeypatch, tmp_path):
    # the program's compile cache follows JAX_COMPILATION_CACHE_DIR; set
    # after JAX has started, it leaves the persistent cache off here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def _run(root, capsys, workload, fault=None, seed=2**32 + 5):
    rc = cell.run(["--workload", workload, "--seed", str(seed),
                   "--seconds", "2", "--trace", "0"], t_start=0.0,
                  root=root, require_tpu=False, fault=fault)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert list(res)[-1] == "check"
    assert "check: max_logit_gap" in out.err
    return res


def _altered_tokens(eng):
    sample, V = eng._sample, eng.cfg.vocab_size

    def wrong(*a):
        return (sample(*a) + 1) % V

    eng._sample = wrong


def _state_unchanged(eng):
    step = eng._step

    def stale(p, tok, cache, *rest):
        logits, _ = step(p, tok, cache, *rest)
        return logits, cache

    eng._step = stale


@pytest.mark.parametrize("workload", ["tiny-gpt2-chat",
                                      "tiny-nemotron-chat",
                                      "tiny-llama-chat"])
def test_sound_run_is_correct(tiny_root, capsys, workload):
    res = _run(tiny_root, capsys, workload)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p50_ms", "itl_p95_ms", "tok_s",
                                   "setup_s"}


def _half_batch_left_out(eng):
    """Every second decoding row gets no logits of its own."""
    import jax.numpy as jnp

    step = eng._step

    def half(p, tok, cache, *rest):
        logits, cache = step(p, tok, cache, *rest)
        active = rest[-1]
        skip = active & (jnp.cumsum(active) % 2 == 0)
        return jnp.where(skip[:, None], 0.0, logits), cache

    eng._step = half


@pytest.mark.parametrize("workload,fault", [
    pytest.param("tiny-gpt2-chat", f, id=f.__name__)
    for f in (_altered_tokens, _state_unchanged, _half_batch_left_out)
] + [pytest.param("tiny-llama-chat", _state_unchanged,
                  id="llama-_state_unchanged")])
def test_broken_timed_path_is_not_correct(tiny_root, capsys, workload,
                                          fault):
    res = _run(tiny_root, capsys, workload, fault=fault)
    assert res["correct"] is False
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("config", ["tiny-gpt2", "tiny-nemotron",
                                    "tiny-llama"])
def test_control_exceeds_the_limit(tiny_root, config):
    """The control (the reference at the configuration's lower control
    precision) fails the limit on served-length sequences."""
    bench = spec.Bench(tiny_root)
    conf = bench.config(config)
    m = cell.model(bench, conf)
    ref = m.ref
    w = m.weights(9)
    rng = np.random.default_rng(0)
    chosen = []
    for _ in range(3):
        prompt = rng.integers(1, 512, 30).tolist()
        seq = list(prompt)
        for _ in range(20):  # greedy continuation by the reference
            lg = ref.logits(w, conf, seq, np.array([len(seq) - 1]))
            seq.append(int(lg[0].argmax()))
        chosen.append((prompt, seq[len(prompt):]))
    got = check.compare(ref, w, conf, chosen,
                        control=conf["check"]["control"])
    assert got["max_logit_gap"] == 0.0
    assert got["control_max_logit_gap"] > conf["check"]["max_logit_gap"]
    # judged by the predicate that decides a run's ``correct``
    assert check.correct(got, conf["check"], 0) is True
    assert check.correct(check.control_side(got), conf["check"], 0) is False


def test_traced_run_reads_the_engine_spans(tiny_root, capsys):
    """With ``--trace 1`` the drive loop logs the step calls from the
    engine's own spans; the per-layer metrics that need no device trace
    are read from them."""
    rc = cell.run(["--workload", "tiny-gpt2-chat", "--seed", str(2**33 + 3),
                   "--seconds", "2", "--trace", "1"], t_start=0.0,
                  root=tiny_root, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True
    m = res["metrics"]
    assert m["decode_rows_mean"]["value"] >= 1.0
    assert 0 < m["kv_pages_peak_frac"]["value"] <= 1.0


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    rc = cell.run(["--workload", "gpt2-chat", "--seed", "1", "--seconds",
                   "1"], t_start=0.0, root=BENCH.parent)
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""


def test_command_alone_exits_nonzero(tmp_path):
    """In a directory with only BENCHMARK.json and bench/, the command
    fails and prints no result."""
    import shutil

    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("platform,kind,count,chips,ok", [
    ("tpu", "TPU v5 lite", 1, 1, True),
    ("tpu", "TPU v5 lite", 4, 4, True),
    ("cpu", "cpu", 1, 1, False),
    ("tpu", "TPU v5 lite", 1, 4, False),
    ("tpu", "TPU v9 imaginary", 1, 1, False),
])
def test_device_check_refuses_what_the_peaks_table_lacks(
        monkeypatch, platform, kind, count, chips, ok):
    from types import SimpleNamespace as NS

    from harness import device

    devs = [NS(platform=platform, device_kind=kind) for _ in range(count)]
    monkeypatch.setattr(jax, "devices", lambda: devs)
    peaks = spec.Bench(BENCH.parent).peaks()
    if ok:
        got = device.check(chips, peaks)
        assert (got["platform"], got["kind"], got["count"]) == \
            (platform, kind, count)
    else:
        with pytest.raises(device.NoDevice):
            device.check(chips, peaks)
