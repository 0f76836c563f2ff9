"""The open loop: submit each request at its due time, tick the engine.

The harness drives ``submit`` and ``tick`` itself.  After every tick it
waits for the tick's device work (the engine's cache) before the next:
the engine does not donate its cache, so without the wait prefill-only
ticks queue calls that each hold a whole new pool.  Each token is seen
at the end of the tick that produced it.

With a recording tracer (``Telemetry(trace=True)``) the loop also logs
every step call from the engine's own spans, read after each tick:
``prefill.chunk`` gives a chunk's start and length, ``decode.step`` its
row count, and every argument of either span is kept under ``args``
for the metric readers.  The live length of each decoding row comes
from the tokens the harness saw; the two counts of rows have to agree,
or the run stops.  Pages in use are then read from ``stats()`` at the
end of each tick in the window.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Optional

import jax

SPANS = {"prefill.chunk": "prefill", "decode.step": "decode"}


def _null(_name):
    return contextlib.nullcontext()


class HarnessMismatch(RuntimeError):
    """The engine's spans and the harness's records disagree."""


class Calls:
    """The engine's step calls, from its recording tracer."""

    def __init__(self, tracer, clock: Callable[[], float]):
        self.tracer = tracer
        self.log: List[Dict] = []
        tracer.reset()
        t = clock()
        tracer.instant("bench.clock", "bench")
        # harness time of a span = its start (us, tracer clock) + offset
        self.offset = t - tracer.events[-1][4] / 1e6
        tracer.reset()

    def tick(self, lens: List[int]) -> None:
        """Log the calls of the tick that just ran; ``lens`` are the
        cached lengths of the rows that got a token from its decode
        step, as the harness counted them."""
        spans = sorted((e for e in self.tracer.events
                        if e[0] == "X" and e[1] in SPANS),
                       key=lambda e: e[4])
        self.tracer.reset()
        decodes = 0
        for _, name, _, _, ts, _, args in spans:
            call = {"kind": SPANS[name], "t": ts / 1e6 + self.offset,
                    "args": dict(args)}
            if call["kind"] == "prefill":
                call.update(start=int(args["start"]), n=int(args["n"]))
            else:
                decodes += 1
                if decodes > 1 or int(args["rows"]) != len(lens):
                    raise HarnessMismatch(
                        f"decode step #{decodes} of {args['rows']} rows in "
                        f"one tick; the harness saw {len(lens)} rows get "
                        "a decode token")
                call["lens"] = list(lens)
            self.log.append(call)
        if lens and not decodes:
            raise HarnessMismatch(f"{len(lens)} rows got a decode token "
                                  "in a tick with no decode.step span")


def _request(eng, rid: int):
    """The engine's record of a request it just queued."""
    for r in reversed(eng.queue):
        if r.rid == rid:
            return r
    raise HarnessMismatch(f"request {rid} is not queued after submit")


def drive(eng, reqs, lead_s: float, seconds: float, *,
          annotate: Callable = _null,
          before_window: Optional[Callable[[], None]] = None,
          pre_s: float = 0.0,
          on_open: Optional[Callable[[float], None]] = None,
          on_close: Optional[Callable[[float], None]] = None) -> Dict:
    """Run the loop for ``lead_s + seconds``; return the records.

    ``before_window`` runs ``pre_s`` seconds before the window opens (a
    profiler start); ``on_open``/``on_close`` run at the first and after
    the last iteration inside the window, with the host time."""
    t0 = time.perf_counter()

    def clock():
        return time.perf_counter() - t0

    detail = eng.tel.tracer.enabled
    calls = Calls(eng.tel.tracer, clock) if detail else None
    w0, w1 = lead_s, lead_s + seconds
    records: List[Dict] = []
    live: List[Dict] = []
    ticks: List[Dict] = []
    late, failed = 0.0, 0
    i, n = 0, len(reqs)
    pre_done = before_window is None
    opened = False
    window_ctx = None
    pages = {"peak": None, "total": None}
    while True:
        now = clock()
        if now >= w1:
            break
        if not pre_done and now >= w0 - pre_s:
            before_window()
            pre_done = True
            continue
        if not opened and now >= w0:
            opened = True
            window_ctx = annotate("bench.window")
            window_ctx.__enter__()
            if on_open:
                on_open(now)
        with annotate("bench.submit"):
            while i < n and reqs[i].due <= now:
                r = reqs[i]
                rec = {"due": r.due, "emits": [],
                       "prompt_len": len(r.prompt), "max_new": r.max_new,
                       "req": None}
                try:
                    rid = eng.submit(r.prompt, max_new=r.max_new)
                    rec["req"] = _request(eng, rid)
                    live.append(rec)
                except ValueError:
                    failed += 1
                late = max(late, clock() - r.due)
                records.append(rec)
                i += 1
        if live:
            ta = clock()
            with annotate("bench.tick"):
                eng.tick()
            with annotate("bench.wait"):
                jax.block_until_ready(eng.kv.cache)
            t = clock()
            ticks.append({"t0": ta, "t1": t})
            lens = []
            for rec in list(live):
                req = rec["req"]
                had = len(rec["emits"])
                k = len(req.out) - had
                if k > 0:
                    rec["emits"].extend([t] * k)
                    # the first token comes off the prefill logits; a
                    # decode token k was sampled with k - 1 in the cache
                    if k - (had == 0) > 0:
                        lens.append(rec["prompt_len"] + len(req.out) - 2)
                if req.done:
                    live.remove(rec)
            if detail:
                calls.tick(lens)
                if opened:
                    st = eng.stats()
                    pages["peak"] = max(pages["peak"] or 0,
                                        st["pages_in_use"])
                    pages["total"] = st["pages_in_use"] + st["n_free_pages"]
        else:
            nxt = reqs[i].due if i < n else w1
            if not pre_done:
                nxt = min(nxt, w0 - pre_s)
            with annotate("bench.idle"):
                time.sleep(max(0.0, min(nxt, w1) - clock()))
    t_end = clock()
    if window_ctx is not None:
        window_ctx.__exit__(None, None, None)
        if on_close:
            on_close(t_end)
    return {
        "window": (w0, w1),
        "requests": records,
        "ticks": ticks,
        "calls": calls.log if detail else [],
        "late_max_s": late,
        "failed": failed,
        "attempted": len(records),
        "pages_in_use_peak": pages["peak"],
        "pages_total": pages["total"],
    }
