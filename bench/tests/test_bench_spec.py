"""The harness finds a configuration, a mix, a metric and a kernel cost
by name from their files: adding one adds a file and an entry, and
leaves every file that was there as it was."""
import hashlib
import json
import shutil

import pytest

from benchtools import BENCH, make_root
from harness import spec


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_added_files_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    before = _digests(root / "bench")
    b = root / "bench"
    shutil.copy(b / "configs" / "tiny-gpt2.json",
                b / "configs" / "tiny-gpt2-wide.json")
    mix = json.loads((b / "traffic" / "tiny-chat.json").read_text())
    mix["arrival"]["rate_rps"] = 9.0
    (b / "traffic" / "tiny-burst.json").write_text(json.dumps(mix))
    (b / "metrics" / "ticks_in_window.ms.py").write_text(
        "def read(rec):\n    return len(rec['ticks'])\n")
    (b / "costs" / "ln_res.py").write_text(
        "MATCH = 'ln_res'\n"
        "def bound_s(call, dims, engine, peak):\n    return 1.0\n")
    s = json.loads((root / "BENCHMARK.json").read_text())
    s["workloads"].append({"name": "tiny-burst", "config": "tiny-gpt2-wide",
                           "traffic": "tiny-burst", "chips": 1,
                           "why": "added"})
    s["per_layer"].append({"name": "ticks_in_window.ms", "unit": "ticks",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine tick", "moves": "tok_s",
                           "workloads": ["tiny-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))

    bench = spec.Bench(root)
    cell = bench.workload("tiny-burst")
    assert bench.config(cell["config"])["name"] == "tiny-gpt2"
    assert bench.traffic(cell["traffic"])["arrival"]["rate_rps"] == 9.0
    names = [m["name"] for m in bench.per_layer("tiny-burst")]
    assert "ticks_in_window.ms" in names
    reader = [m for m in bench.per_layer("tiny-burst")
              if m["name"] == "ticks_in_window.ms"][0]["reader"]
    assert reader.read({"ticks": [1, 2, 3]}) == 3
    assert "ticks_in_window.ms" not in [
        m["name"] for m in bench.per_layer("tiny-gpt2-chat")]
    assert bench.cost("ln_res").bound_s({}, {}, {}, {}) == 1.0
    after = _digests(root / "bench")
    assert all(after[k] == v for k, v in before.items())


@pytest.mark.parametrize("kind", ["workload", "config", "traffic",
                                  "reference", "cost"])
def test_unknown_names_are_refused(tiny_root, kind):
    bench = spec.Bench(tiny_root)
    with pytest.raises(spec.SpecError):
        getattr(bench, kind)("no-such-name")


def test_every_cell_of_the_benchmark_resolves():
    bench = spec.Bench(BENCH.parent)
    s = bench.spec
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for w in s["workloads"]:
        conf = bench.config(w["config"])
        bench.traffic(w["traffic"])
        dims = bench.reference(conf["family"]).dims(conf)
        assert dims["n_layers"] > 0
        for m in bench.per_layer(w["name"]):
            assert callable(m["reader"].read)
    for c in s["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    peaks = bench.peaks()["devices"]
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}"
UNIT = r"[A-Za-z0-9_/%.\-]{1,16}"
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source",
                   "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


def test_benchmark_file_keeps_to_its_format():
    import re

    s = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert s["command"] == ["python3", "bench/run.py"]
    assert s["paths"] == ["bench"]
    assert 1 <= s["run_seconds"] <= 51
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e
    for key, allowed in KEYS.items():
        names = [e["name"] for e in s[key]]
        assert len(names) == len(set(names))
        for e in s[key]:
            assert set(e) <= allowed, (key, e)
            assert re.fullmatch(NAME, e["name"])
            for text in ("why", "layer", "source"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and "\n" not in e[text]
            if "unit" in e:
                assert re.fullmatch(UNIT, e["unit"])
                assert e["better"] in ("lower", "higher")
            assert set(e.get("workloads", [])) <= cells
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    perf = (BENCH.parent / "PERF.md").read_text()
    for layer in {m["layer"] for m in s["per_layer"]}:
        assert f"| {layer} |" in perf, layer
    assert {w["config"] for w in s["workloads"]} == \
        {c["name"] for c in s["configs"]}
