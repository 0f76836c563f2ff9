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
    """Renamed step programs pair with the same calls: where no program
    carries a step's stable name, the reduction reads the programs'
    device time."""
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


@pytest.mark.parametrize("name,hit", [
    ("%collective-permute-start.3", True),
    ("%collective-permute-done.3", True),
    ("%collective-permute.1", True),
    ("%all-gather-start", True),
    ("%all-reduce.7", True),
    ("%reduce-scatter.2", True),
    ("%all-to-all-done.4", True),
    ("%fusion.12", False),
    ("%paged_verify.10", False),
    ("%copy.135", False),
    ("%all-gathered_copy.1", False),
])
def test_collective_ops_are_matched_by_name(name, hit):
    assert bool(T.COLLECTIVES.match(name)) is hit


def _two_chips():
    """Two chips over the window [100, 200] ns: an async collective
    pair, a synchronous one running past the window's end, compute."""
    return {"host": [], "devices": [
        {"id": 0, "modules": [], "ops": [
            ["%fusion.1", 90.0, 30.0],  # 100-120 in the window
            ["%collective-permute-start.1", 120.0, 5.0],
            ["%fusion.2", 125.0, 25.0],
            ["%collective-permute-done.1", 150.0, 10.0],
            ["%all-gather.2", 190.0, 30.0]]},  # 190-200 in the window
        {"id": 1, "modules": [], "ops": [
            ["%fusion.1", 100.0, 80.0],
            ["%all-reduce-start.4", 180.0, 10.0],
            ["%all-reduce-done.4", 185.0, 10.0]]}]}  # 180-195 with start


def test_collective_share_is_each_chips_collective_time_over_busy():
    r = T.reduce(_two_chips(), 100.0, 200.0, 2)
    # chip 0: busy 100-160 and 190-200 (70 ns), collectives 5 + 10 + 10
    # chip 1: busy 100-195 (95 ns), collectives 180-195 (15 ns)
    assert [c["busy_s"] * 1e9 for c in r["chips"]] == pytest.approx(
        [70.0, 95.0])
    assert [c["collective_s"] * 1e9 for c in r["chips"]] == \
        pytest.approx([25.0, 15.0])
    assert r["busy_s"] * 1e9 == pytest.approx(82.5)
    share = layers.collective_share({"trace": r})
    assert share == pytest.approx(100 * (25 / 70 + 15 / 95) / 2)


def test_collective_share_reads_only_the_cells_chips():
    one = T.reduce(_two_chips(), 100.0, 200.0, 1)
    assert len(one["chips"]) == 1
    assert layers.collective_share({"trace": one}) == \
        pytest.approx(100 * 25 / 70)


def test_collective_share_is_left_out_without_collectives(tick):
    assert layers.collective_share({"trace": _reduce(tick)}) is None
    assert layers.collective_share({"trace": None}) is None


def test_step_mfu_counts_the_peak_of_every_chip(tick):
    rec = _tick_rec(tick, spec.Bench(BENCH.parent))
    one = layers.step_mfu(rec, "decode")
    assert layers.step_mfu(dict(rec, chips=1), "decode") == one
    assert layers.step_mfu(dict(rec, chips=4), "decode") == \
        pytest.approx(one / 4)


def test_distributed_spans_set_the_clock_offset():
    """Calls of the pipelined engine pair with its dispatch spans
    (``wave.dispatch``, ``prefill.round``) as the one-chip engine's pair
    with ``decode.step`` and ``prefill.chunk``."""
    host = [["bench.window", 1.0e9, 40e9],
            ["prefill.round", 2.0e9 + 300.0, 1e6],
            ["wave.dispatch", 2.1e9 + 300.0, 1e6],
            ["wave.consume", 2.2e9 + 250.0, 1e6]]
    calls = [{"kind": "prefill", "t": 1.0}, {"kind": "decode", "t": 1.1}]
    # the window opened at harness time 0.0 (coarse offset 1e9 ns); the
    # spans sit 300 ns later than the calls say
    assert T.clock_offset(host, calls, 0.0) == pytest.approx(1e9 + 300.0)


#: the recorded tick's step programs, under the names the engines give
#: them since their steps are named functions
STEP_NAMES = {"jit__lambda(2212270435489130380)": "jit_prefill_chunk(1)",
              "jit__lambda(7511662022325176231)": "jit_decode_step(2)"}
PROGRAMS = {"prefill": "jit_prefill_chunk", "decode": "jit_decode_step"}


def test_step_programs_are_found_by_their_names(tick):
    """A sampler that outweighs the decode step does not take its place:
    where the programs carry their stable names, each kind's step
    program is the one of its name; by device time alone the sampler
    would win."""
    lo, hi = tick["window_ns"]
    dev = dict(tick["devices"][0])
    dev["modules"] = [[STEP_NAMES.get(m[0], m[0]), m[1], m[2]]
                      for m in dev["modules"]]
    dev["modules"].append(["jit_sample_batch(3)", hi + 1e6, 4e8])
    named = dict(tick, devices=[dev])
    calls = [(c[0], c[1]) for c in tick["calls"]]
    base = _reduce(tick)
    r = T.reduce(named, lo, hi + 5e8, 1, calls, PROGRAMS)
    for kind in ("prefill", "decode"):
        assert {m[0] for m in r["phases"][kind]["modules"]} == \
            {f"{PROGRAMS[kind]}({1 + (kind == 'decode')})"}
        assert r["phases"][kind]["calls"] == base["phases"][kind]["calls"]
        assert [m[1:] for m in r["phases"][kind]["modules"]] == \
            [m[1:] for m in base["phases"][kind]["modules"]]
    by_time = T.reduce(named, lo, hi + 5e8, 1, calls)
    assert by_time["phases"]["decode"]["modules"][0][0] == \
        "jit_sample_batch(3)"
    # a kind whose named program did not run has no step program
    r = T.reduce(named, lo, hi, 1, calls,
                 dict(PROGRAMS, decode="jit_other_step"))
    assert set(r["phases"]) == {"prefill"}


KERNELS = ("paged_attn", "quant_matmul")


def test_kernel_bounds_count_shard_zeros_rows_on_several_chips(tick):
    """On several chips the kernels' time is device 0's, so the bound of
    a decode call is that of shard 0's rows (``shard_lens[0]``), however
    many rows the other shards add; with none on shard 0 the calls need
    no work there and the metric is left out."""
    rec = _tick_rec(tick, spec.Bench(BENCH.parent))
    one = {k: layers.kernel_roofline(rec, "decode", k) for k in KERNELS}
    calls = []
    for c in rec["calls"]:
        if c["kind"] == "decode":
            other = [L + 100 for L in c["lens"]]
            c = dict(c, lens=c["lens"] + 2 * other,
                     shard_lens=[c["lens"], other, [], other])
        calls.append(c)
    four = dict(rec, calls=calls, chips=4)
    for k in KERNELS:
        assert layers.kernel_roofline(four, "decode", k) == \
            pytest.approx(one[k])
    # step_mfu counts every shard's rows
    assert layers.step_mfu(four, "decode") > \
        layers.step_mfu(dict(rec, chips=4), "decode")
    elsewhere = [dict(c, shard_lens=[[]] + c["shard_lens"][1:])
                 if c["kind"] == "decode" else c for c in calls]
    for k in KERNELS:
        assert layers.kernel_roofline(dict(four, calls=elsewhere),
                                      "decode", k) is None
