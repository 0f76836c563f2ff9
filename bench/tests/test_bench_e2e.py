"""End-to-end arithmetic on synthetic records."""
import numpy as np
import pytest

from harness import e2e


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
