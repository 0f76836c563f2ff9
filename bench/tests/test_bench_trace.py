"""The trace reduction on a small recorded trace: one engine tick of
gpt2-chat on a TPU v5e chip (a prefill chunk and a decode step)."""
import gzip
import json

import pytest

from benchtools import BENCH, DATA
from harness import layers, spec
from harness import trace as T


@pytest.fixture(scope="module")
def tick():
    with gzip.open(DATA / "trace_gpt2_tick.json.gz") as f:
        return json.load(f)


def _reduce(tick):
    lo, hi = tick["window_ns"]
    calls = [(c[0], c[1]) for c in tick["calls"]]
    return T.reduce(tick, lo, hi, 1, calls)


def _naive_busy(ops, lo, hi):
    """Busy time by walking sorted endpoints (a second algorithm)."""
    pts = []
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            pts += [(a, 1), (b, -1)]
    pts.sort(key=lambda p: (p[0], -p[1]))
    busy, depth, start = 0.0, 0, None
    for t, step in pts:
        if depth == 0 and step == 1:
            start = t
        depth += step
        if depth == 0:
            busy += t - start
    return busy


def test_op_names_are_the_instruction_names():
    assert T.op_name("%paged_verify.10 = bf16[32,16,1,64]{3,2,1,0} "
                     "custom-call(%a), custom_call_target=\"x\"") \
        == "%paged_verify.10"


def test_busy_is_the_union_of_op_intervals(tick):
    lo, hi = tick["window_ns"]
    r = _reduce(tick)
    ops = tick["devices"][0]["ops"]
    assert r["busy_s"] * 1e9 == pytest.approx(_naive_busy(ops, lo, hi))
    assert 0 < r["busy_s"] <= r["window_s"]
    gaps = T.idle_gaps(T.clip(ops, lo, hi), lo, hi)
    assert sum(g for _, g in gaps) / 1e9 == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_step_programs_pair_with_their_calls(tick):
    r = _reduce(tick)
    ph = r["phases"]
    assert set(ph) == {"prefill", "decode"}
    for kind, p in ph.items():
        assert len({m[0] for m in p["modules"]}) == 1  # one program each
        assert all(tick["calls"][i][0] == kind for i in p["calls"])
    # each step runs the paged kernel once per layer (24 layers), and
    # the W8A8 kernel for six projections per layer
    for kind in ("prefill", "decode"):
        names = [o[0] for o in ph[kind]["ops"]]
        assert sum(n.startswith("%paged_verify") for n in names) == 24
        assert sum(n.startswith("%mp_matmul") for n in names) == 6 * 24


def test_breakdown_leaves_out_loop_containers(tick):
    r = _reduce(tick)
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert names and not any(n.startswith("%while") for n in names)
    assert names[0].startswith("%paged_verify")
    assert len(r["breakdown"]["idle_gaps"]) <= 10
    assert all(lbl in T.HOST_SPANS + ("none",)
               for lbl, _ in r["breakdown"]["idle_gaps"])


def _tick_rec(tick, bench):
    conf = bench.config("gpt2-345m-w8a8")
    return {"trace": _reduce(tick), "cost": bench.cost,
            "dims": bench.reference("gpt2").dims(conf),
            "engine": conf["engine"],
            "peak": bench.peaks()["devices"]["TPU v5 lite"],
            "calls": [{"kind": c[0], "t": c[1] / 1e9,
                       **({"lens": c[2]} if c[0] == "decode"
                          else {"start": c[2], "n": c[3]})}
                      for c in tick["calls"]]}


def test_layer_metrics_read_the_trace(tick):
    rec = _tick_rec(tick, spec.Bench(BENCH.parent))
    for kernel in ("paged_attn", "quant_matmul"):
        v = layers.kernel_roofline(rec, "decode", kernel)
        assert v is not None and 0 < v <= 100
    mfu = layers.step_mfu(rec, "decode")
    assert mfu is not None and 0 < mfu <= 100
    assert 0 <= layers.idle_share(rec) < 100


def test_step_programs_are_found_whatever_their_names(tick):
    """Renamed step programs pair with the same calls: the reduction
    reads the programs' device time, not their names."""
    renamed = dict(tick, devices=[dict(d) for d in tick["devices"]])
    names = {}
    for d in renamed["devices"]:
        d["modules"] = [[names.setdefault(m[0], f"jit_step_{len(names)}"),
                         m[1], m[2]] for m in d["modules"]]
    a, b = _reduce(tick), _reduce(renamed)
    for kind in ("prefill", "decode"):
        assert a["phases"][kind]["calls"] == b["phases"][kind]["calls"]
        assert [m[1:] for m in a["phases"][kind]["modules"]] == \
            [m[1:] for m in b["phases"][kind]["modules"]]


def test_calls_without_a_step_program_stop_the_run(tick):
    lo, hi = tick["window_ns"]
    calls = [{"kind": c[0], "t": c[1] / 1e9} for c in tick["calls"]]
    trace = _reduce(tick)
    trace["phases"].pop("decode")
    rec = {"trace": trace, "calls": calls, "window": (lo / 1e9, hi / 1e9)}
    assert layers.phase(rec, "prefill") is not None
    with pytest.raises(RuntimeError):
        layers.phase(rec, "decode")


def test_layer_metrics_read_the_family_costs(tick, tmp_path):
    """``step_mfu`` and ``kernel_roofline`` read the cost files of the
    run's family where it has them, and the general ones otherwise."""
    from benchtools import make_root

    root = make_root(tmp_path)
    costs = root / "bench" / "costs"
    (costs / "model_step.fam.py").write_text(
        (costs / "model_step.py").read_text()
        + "\n_flops = flops\n\n\ndef flops(call, dims):\n"
        "    return 2 * _flops(call, dims)\n")
    (costs / "paged_attn.fam.py").write_text(
        (costs / "paged_attn.py").read_text().replace(
            "max(f / peak", "0.5 * max(f / peak"))
    bench = spec.Bench(root)
    rec = _tick_rec(tick, bench)
    base = (layers.step_mfu(rec, "decode"),
            layers.kernel_roofline(rec, "decode", "paged_attn"))
    assert layers.step_mfu(dict(rec, family="gpt2"), "decode") == base[0]
    fam = dict(rec, family="fam")
    assert layers.step_mfu(fam, "decode") == pytest.approx(2 * base[0])
    assert layers.kernel_roofline(fam, "decode", "paged_attn") == \
        pytest.approx(0.5 * base[1])
    assert layers.kernel_roofline(fam, "decode", "quant_matmul") == \
        layers.kernel_roofline(rec, "decode", "quant_matmul")
