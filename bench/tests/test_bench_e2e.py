"""End-to-end arithmetic on synthetic records, and the drive loop's
call records from synthetic engine spans."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from harness import drive, e2e


def _rec():
    # window (10, 20]; times are seconds from the first due request
    return {"window": (10.0, 20.0), "requests": [
        # due before the window: not in TTFT, its gaps count
        {"due": 5.0, "emits": [8.0, 10.5, 11.0, 12.0]},
        # due in the window, first token at 13.5
        {"due": 12.0, "emits": [13.5, 14.0, 15.0]},
        # due in the window, never served: waits 20 - 18 = 2
        {"due": 18.0, "emits": []},
        # admitted but no token before the window closes
        {"due": 19.0, "emits": [21.0]},
        # due after the window
        {"due": 20.5, "emits": []},
    ]}


def test_ttft_counts_from_due_and_censors_at_close():
    assert sorted(e2e.ttfts(_rec())) == pytest.approx([1.0, 1.5, 2.0])


def test_gaps_are_every_gap_ending_in_the_window():
    # 8->10.5, 10.5->11, 11->12, 13.5->14, 14->15
    assert sorted(e2e.gaps(_rec())) == pytest.approx(
        [0.5, 0.5, 1.0, 1.0, 2.5])


def test_tokens_per_second_is_window_tokens_over_window():
    # 10.5, 11, 12, 13.5, 14, 15
    assert e2e.tokens_in_window(_rec()) == 6
    m = e2e.metrics(_rec())
    assert m["tok_s"] == pytest.approx(0.6)
    assert m["itl_p50_ms"] == pytest.approx(1000.0)
    assert m["itl_p95_ms"] == pytest.approx(
        np.percentile([0.5, 0.5, 1.0, 1.0, 2.5], 95) * 1e3)
    assert m["ttft_p90_ms"] == pytest.approx(
        np.percentile([1.0, 1.5, 2.0], 90) * 1e3)
    assert e2e.first_tokens_in_window(_rec()) == 1


def test_empty_window_reports_nothing_but_rate():
    m = e2e.metrics({"window": (0.0, 1.0), "requests": []})
    assert m["tok_s"] == 0.0 and m["itl_p50_ms"] is None


def test_the_check_samples_served_requests_with_the_longest():
    from types import SimpleNamespace as NS

    from harness import check

    records = [{"req": NS(prompt=[1] * n, out=[2] * k)}
               for n, k in ((5, 3), (9, 0), (2, 40), (7, 1))]
    records.append({"req": None})  # refused at submit
    served = check.served(records)
    assert [(len(p), len(o)) for p, o in served] == [(5, 3), (2, 40),
                                                      (7, 1)]
    for seed in (1, 2**33 + 9):
        got = check.sample(served, seed, 2)
        assert len(got) == 2 and got[0] == served[1]
        assert got == check.sample(served, seed, 2)


class _Spans:
    """A recording tracer's public surface, with spans set by hand."""

    def __init__(self):
        self.events = []

    def reset(self):
        self.events = []

    def instant(self, name, cat):
        self.events.append(("i", name, cat, 0, 5e6, 0.0, None))


@pytest.mark.parametrize("rows,lens,ok", [
    (2, [40, 51], True),
    (3, [40, 51], False),  # the engine decoded a row the harness missed
    (None, [40], False),  # a decode token with no decode step
])
def test_calls_come_from_the_engine_spans(rows, lens, ok):
    from harness import drive

    tr = _Spans()
    calls = drive.Calls(tr, lambda: 2.0)
    tr.events = [("X", "prefill.chunk", "stage", 0, 6e6, 10.0,
                  {"start": 32, "n": 7})]
    if rows is not None:
        tr.events.append(("X", "decode.step", "stage", 0, 6.5e6, 10.0,
                          {"rows": rows}))
    if not ok:
        with pytest.raises(drive.HarnessMismatch):
            calls.tick(lens)
        return
    calls.tick(lens)
    assert calls.log == [
        {"kind": "prefill", "t": pytest.approx(3.0), "start": 32, "n": 7,
         "args": {"start": 32, "n": 7}},
        {"kind": "decode", "t": pytest.approx(3.5), "lens": lens,
         "args": {"rows": rows}}]
    assert tr.events == []


def test_step_span_args_reach_the_call_record():
    """Every argument of a step span is kept under ``args``: a counter
    the program adds to its spans can be read by a metric reader."""
    from harness import drive

    tr = _Spans()
    calls = drive.Calls(tr, lambda: 2.0)
    chunk = {"rid": 4, "slot": 1, "start": 0, "n": 16, "experts_hit": 5}
    step = {"rows": 2, "pages": 7, "tokens_per_expert": [1, 0, 3]}
    tr.events = [("X", "prefill.chunk", "stage", 0, 6e6, 10.0, chunk),
                 ("X", "decode.step", "stage", 0, 6.5e6, 10.0, step)]
    calls.tick([40, 51])
    pre, dec = calls.log
    assert pre["args"] == chunk and (pre["start"], pre["n"]) == (0, 16)
    assert dec["args"] == step and dec["lens"] == [40, 51]
    assert dec["args"]["pages"] == 7
    assert dec["args"] is not step  # a copy, not the tracer's own dict


def _wave_calls(tr, waves):
    """Two shards of three slots each."""
    return drive.WaveCalls(tr, lambda: 2.0, lambda: list(waves), 3, 2)


def _consume_tick(rows):
    """Tick t + 1 of the pipelined engine: a prefill round, then wave 0
    consumed and redispatched, then wave 1."""
    return [("X", "prefill.round", "stage", 0, 6e6, 10.0,
             {"shards": 2, "tokens": 40}),
            ("X", "wave.consume", "wave", 0, 6.1e6, 10.0,
             {"wave": 0, "kind": "decode", "rows": rows[0]}),
            ("X", "wave.dispatch", "wave", 0, 6.2e6, 10.0,
             {"wave": 0, "rows": rows[0], "kind": "decode"}),
            ("X", "wave.consume", "wave", 0, 6.3e6, 10.0,
             {"wave": 1, "kind": "decode", "rows": rows[1]}),
            ("X", "wave.dispatch", "wave", 0, 6.4e6, 10.0,
             {"wave": 1, "rows": rows[1], "kind": "decode"})]


@pytest.mark.parametrize("rows,ok", [
    ((2, 1), True),
    ((1, 2), False),  # the waves' counts swapped: rows in the wrong wave
    ((2, 2), False),  # the engine decoded a row the harness missed
])
def test_wave_calls_take_rows_from_the_next_consume(rows, ok):
    """The distributed spans map to calls (``prefill.round`` a prefill,
    ``wave.dispatch`` a decode), and a wave's decode gets its rows from
    the wave's consume in the next tick, where its row count is
    checked against the rows the harness saw get a token; the rows'
    lengths are kept per shard too."""
    tr = _Spans()
    # slots 0 and 3 in wave 0, slot 5 in wave 1
    waves = [0, -1, -1, 0, -1, 1]
    calls = _wave_calls(tr, waves)
    tr.events = [("X", "wave.dispatch", "wave", 0, 5e6, 10.0,
                  {"wave": 0, "rows": 2, "kind": "decode"}),
                 ("X", "wave.dispatch", "wave", 0, 5.5e6, 10.0,
                  {"wave": 1, "rows": 1, "kind": "decode"})]
    calls.tick([], [])
    assert [c["kind"] for c in calls.log] == ["decode", "decode"]
    assert calls.records() == []  # both still in flight
    tr.events = _consume_tick(rows)
    if not ok:
        with pytest.raises(drive.HarnessMismatch):
            calls.tick([40, 51, 60], [0, 3, 5])
        return
    calls.tick([40, 51, 60], [0, 3, 5])
    got = calls.records()
    assert [(c["kind"], c["lens"] if c["kind"] == "decode" else None)
            for c in got] == [("decode", [40, 51]), ("decode", [60]),
                              ("prefill", None)]
    assert [c.get("shard_lens") for c in got] == [[[40], [51]],
                                                   [[], [60]], None]
    assert got[0]["t"] == pytest.approx(2.0)
    assert got[2]["args"] == {"shards": 2, "tokens": 40}
    assert len(calls.log) == 5 and tr.events == []


def test_wave_calls_refuse_tokens_of_an_unconsumed_wave():
    tr = _Spans()
    calls = _wave_calls(tr, [0, 1])
    tr.events = [("X", "prefill.round", "stage", 0, 5e6, 10.0,
                  {"shards": 1, "tokens": 8})]
    with pytest.raises(drive.HarnessMismatch):
        calls.tick([12], [1])


def test_one_chip_engine_keeps_its_calls_and_wait():
    """The one-chip engine's view logs the one-chip spans exactly as
    before (the pinned log of a prefill chunk and a decode step), reads
    its pages from ``stats()`` and waits on ``kv.cache``."""
    tr = _Spans()
    eng = NS(tel=NS(tracer=tr), kv=NS(cache=None),
             stats=lambda: {"pages_in_use": 5, "n_free_pages": 95})
    v = drive.view(eng, 1)
    assert type(v) is drive.EngineView
    assert v.pages() == (5, 100)
    assert v.shard(3) is None
    v.wait()
    calls = v.calls(lambda: 2.0)
    assert type(calls) is drive.Calls
    tr.events = [("X", "prefill.chunk", "stage", 0, 6e6, 10.0,
                  {"rid": 3, "slot": 0, "start": 32, "n": 7}),
                 ("X", "decode.step", "stage", 0, 6.5e6, 10.0,
                  {"rows": 2, "pages": 9}),
                 ("X", "tick", "engine", 0, 5.9e6, 700.0, {})]
    calls.tick([40, 51], [4, 1])
    assert calls.records() == [
        {"kind": "prefill", "t": pytest.approx(3.0), "start": 32, "n": 7,
         "args": {"rid": 3, "slot": 0, "start": 32, "n": 7}},
        {"kind": "decode", "t": pytest.approx(3.5), "lens": [40, 51],
         "args": {"rows": 2, "pages": 9}}]


def test_one_chip_engine_is_built_as_before(monkeypatch):
    """At ``chips`` 1 ``build_engine`` makes ``ServeEngine`` with the
    arguments it always had."""
    import repro.serving.engine as engine_mod
    from benchtools import BENCH
    from harness import cell, spec

    got = {}

    def fake(cfg, weights, **kw):
        got.update(kw, cfg=cfg, weights=weights)
        return "engine"

    monkeypatch.setattr(engine_mod, "ServeEngine", fake)
    conf = spec.Bench(BENCH.parent).config("gpt2-345m-w8a8")
    cfg = NS(vocab_size=50257)
    assert cell.build_engine(conf, cfg, "w", 7, "tel", 1) == "engine"
    calib = got.pop("calibration_batches")
    assert [c.shape for c in calib] == [(2, 16)]
    assert got == {"cfg": cfg, "weights": "w", "batch_slots": 32,
                   "max_seq": 1024, "eos_id": -1, "quantized": True,
                   "chunk_size": 32, "page_size": 16, "telemetry": "tel"}


def test_the_cells_chips_choose_the_view():
    """``chips`` picks the view as it picks the engine; on four chips
    global slots are numbered shard by shard."""
    eng = NS(Bs=32, D=4, waves=NS(wave=[0] * 128), tel=NS(tracer=_Spans()))
    v = drive.view(eng, 4)
    assert type(v) is drive.ShardedView
    assert [v.shard(s) for s in (0, 31, 32, 127, None)] == [0, 0, 1, 3,
                                                            None]
    assert type(v.calls(lambda: 0.0)) is drive.WaveCalls


def test_the_check_samples_a_request_of_every_shard():
    """On several chips the sample holds the longest request and one of
    each shard it leaves out, so a fault in one chip's rows is always
    compared; on one chip the draw is as before."""
    from harness import check

    served = [([1] * n, [2] * 10) for n in (50, 5, 6, 7, 8, 9, 10, 11)]
    shards = [{0}, {0}, {0}, {1}, {1}, {2}, {3}, {3}]
    for seed in range(40):
        got = check.sample(served, seed, 4, shards)
        assert got[0] == served[0] and len(got) == 4
        covered = set().union(*(shards[served.index(r)] for r in got))
        assert covered == {0, 1, 2, 3}
        assert got == check.sample(served, seed, 4, shards)
    # fewer shards than the sample: the rest drawn as before
    two = [{0}] * 4 + [{1}] * 4
    got = check.sample(served, 5, 4, two)
    assert len(got) == 4 and len({tuple(map(len, r)) for r in got}) == 4
    assert {1} in [two[served.index(r)] for r in got]
    records = [{"req": NS(prompt=[1], out=[2]), "shards": {2}},
               {"req": NS(prompt=[1], out=[])},
               {"req": None}]
    assert check.shards(records) == [{2}]
    assert check.shards([{"req": NS(prompt=[1], out=[2])}]) is None


def test_flow_reads_the_backlog_and_the_streams_at_both_ends():
    """Requests due and done in the window, and, at its open and its
    close, the requests due without a first token and the streams
    decoding: whether the window sees a steady state."""
    rec = _rec()
    done = [True, False, False, False, False]
    for r, d in zip(rec["requests"], done):
        r["req"] = NS(done=d)
    # at 10: the first request decodes (8.0 ... 12.0); none waits
    # at 20: 18.0 and 19.0 wait; none decodes
    assert e2e.flow(rec) == {"due": 3, "done": 1, "waiting": (0, 2),
                             "decoding": (1, 0)}
    assert e2e.waiting_at(rec, 12.5) == 1 and e2e.decoding_at(rec, 14) == 1
