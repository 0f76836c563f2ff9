"""The open loop: submit each request at its due time, tick the engine.

The harness drives ``submit`` and ``tick`` itself, on either engine: a
cell with ``"chips": 1`` runs ``ServeEngine``, one with ``"chips": 4``
``DistributedServeEngine`` (``cell.build_engine``).  Where the two
differ for the harness (the pool to wait on, the pages in use, the
spans that mark step calls) an :class:`EngineView` answers.  Each token
is seen at the end of the tick that produced it.

After every tick the harness waits for device work, since neither engine
donates its cache and calls queued without a wait each hold a whole new
pool.  The one-chip engine's tick is synchronous, so the harness waits
for the pool that tick left.  The distributed engine's tick is
pipelined: a wave's decode is dispatched in one tick and consumed in the
next, so after tick *t* the harness waits for the pool tick *t - 1*
left, which bounds the run-ahead to one tick and leaves the in-flight
waves in flight.

With a recording tracer (``Telemetry(trace=True)``) the loop also logs
every step call from the engine's own spans, read after each tick, with
every argument of the span kept under ``args`` for the metric readers.
One chip: ``prefill.chunk`` gives a chunk's start and length and
``decode.step`` its row count.  Four chips: ``prefill.round`` is one
prefill call over the shards, ``wave.dispatch`` one decode call over a
wave's rows, and the tokens of that call come out in the wave's next
``wave.consume``; the call keeps its rows' lengths per shard too
(``shard_lens``, the shard of a slot being ``slot // slots_per_shard``),
since each chip runs its own shard's rows.  The live length of each
decoding row comes from the tokens the harness saw; the harness's count
of rows and the span's have to agree (per decode step, or per consumed
wave), or the run stops.  Pages in use are then read at the end of each
tick in the window.

On four chips each request's record also keeps the shards whose slots
served it (``shards``), so that the correctness sample can take a
request from every chip (``check.sample``).
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax


def _null(_name):
    return contextlib.nullcontext()


class HarnessMismatch(RuntimeError):
    """The engine's spans and the harness's records disagree."""


class Calls:
    """The engine's step calls, from its recording tracer."""

    SPANS = {"prefill.chunk": "prefill", "decode.step": "decode"}

    def __init__(self, tracer, clock: Callable[[], float]):
        self.tracer = tracer
        self.log: List[Dict] = []
        tracer.reset()
        t = clock()
        tracer.instant("bench.clock", "bench")
        # harness time of a span = its start (us, tracer clock) + offset
        self.offset = t - tracer.events[-1][4] / 1e6
        tracer.reset()

    def tick(self, lens: List[int], slots: Sequence[int] = ()) -> None:
        """Log the calls of the tick that just ran; ``lens`` are the
        cached lengths of the rows that got a token from its decode
        step, as the harness counted them (``slots``, their slots, are
        for the pipelined engine)."""
        spans = sorted((e for e in self.tracer.events
                        if e[0] == "X" and e[1] in self.SPANS),
                       key=lambda e: e[4])
        self.tracer.reset()
        decodes = 0
        for _, name, _, _, ts, _, args in spans:
            call = {"kind": self.SPANS[name], "t": ts / 1e6 + self.offset,
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

    def records(self) -> List[Dict]:
        return self.log


class WaveCalls(Calls):
    """The pipelined engine's step calls.  A ``wave.dispatch`` is logged
    when its span opens and gets its rows' ``lens`` from the wave's next
    ``wave.consume``, in the following tick, whose row count has to
    match the rows of that wave the harness saw get a decode token.
    ``waves()`` is the engine's slot-to-wave map; it is read after each
    tick, when every dispatched slot is in flight and cannot change
    wave."""

    SPANS = {"prefill.round": "prefill", "wave.dispatch": "decode"}

    def __init__(self, tracer, clock: Callable[[], float],
                 waves: Callable[[], Sequence[int]], slots_per_shard: int,
                 shards: int):
        super().__init__(tracer, clock)
        self.waves = waves
        self.wave_of = list(waves())
        self.Bs, self.shards = slots_per_shard, shards
        self.pending: Dict[int, Dict] = {}

    def tick(self, lens: List[int], slots: Sequence[int] = ()) -> None:
        by_wave: Dict[int, List[Tuple[int, int]]] = {}
        for L, slot in zip(lens, slots):
            by_wave.setdefault(int(self.wave_of[slot]), []).append(
                (L, slot // self.Bs))
        self.wave_of = list(self.waves())
        spans = sorted((e for e in self.tracer.events if e[0] == "X" and (
            e[1] in self.SPANS or e[1] == "wave.consume")),
            key=lambda e: e[4])
        self.tracer.reset()
        for _, name, _, _, ts, _, args in spans:
            if name != "prefill.round" and args["kind"] != "decode":
                raise HarnessMismatch(f"a {args['kind']} wave: the harness "
                                      "logs plain decode waves only")
            if name == "wave.consume":
                w = int(args["wave"])
                got = by_wave.pop(w, [])
                call = self.pending.pop(w, None)
                if call is None or int(args["rows"]) != len(got):
                    raise HarnessMismatch(
                        f"wave {w} consumed {args['rows']} rows; the "
                        f"harness saw {len(got)} of its rows get a decode "
                        "token" + ("" if call else ", and no dispatch"))
                call["lens"] = [L for L, _ in got]
                call["shard_lens"] = [[L for L, s in got if s == shard]
                                      for shard in range(self.shards)]
                continue
            call = {"kind": self.SPANS[name], "t": ts / 1e6 + self.offset,
                    "args": dict(args)}
            if name == "wave.dispatch":
                self.pending[int(args["wave"])] = call
            self.log.append(call)
        if by_wave:
            raise HarnessMismatch(
                f"rows of waves {sorted(by_wave)} got a decode token in a "
                "tick that consumed no such wave")

    def records(self) -> List[Dict]:
        """The log without the dispatches still in flight, which no
        consume has given rows."""
        return [c for c in self.log if c["kind"] != "decode" or "lens" in c]


def _marker(pool):
    """A scalar read from device 0's shard of ``pool``: ready once the
    call that wrote the pool has run, and holding none of it."""
    shard = jax.tree_util.tree_leaves(pool)[0].addressable_shards[0].data
    return shard[(0,) * shard.ndim]


class EngineView:
    """``ServeEngine`` as the harness sees it."""

    def __init__(self, eng):
        self.eng = eng

    def wait(self) -> None:
        """After a tick: the pool it left."""
        jax.block_until_ready(self.eng.kv.cache)

    def drain(self) -> None:
        """All device work, and compile what ``wait`` runs."""
        self.wait()

    def pages(self) -> Tuple[int, int]:
        """KV pages in use and usable pages."""
        st = self.eng.stats()
        return st["pages_in_use"], st["pages_in_use"] + st["n_free_pages"]

    def shard(self, slot: Optional[int]) -> Optional[int]:
        """The chip whose shard holds ``slot``; ``None`` on one chip."""
        return None

    def calls(self, clock: Callable[[], float]) -> Calls:
        return Calls(self.eng.tel.tracer, clock)


class ShardedView(EngineView):
    """``DistributedServeEngine``: the pool is ``eng.cache``, one page
    manager per shard (``eng.kv.shards``), pipelined ticks."""

    def __init__(self, eng):
        super().__init__(eng)
        self.last = None

    def wait(self) -> None:
        """After tick *t*: the pool tick *t - 1* left."""
        ready, self.last = self.last, _marker(self.eng.cache)
        if ready is not None:
            jax.block_until_ready(ready)

    def drain(self) -> None:
        jax.block_until_ready(_marker(self.eng.cache))

    def pages(self) -> Tuple[int, int]:
        used = free = 0
        for m in self.eng.kv.shards:
            st = m.stats()
            used += st["pages_in_use"]
            free += st["n_free_pages"]
        return used, used + free

    def shard(self, slot: Optional[int]) -> Optional[int]:
        """Global slots are numbered shard by shard."""
        return None if slot is None else slot // self.eng.Bs

    def calls(self, clock: Callable[[], float]) -> Calls:
        return WaveCalls(self.eng.tel.tracer, clock,
                         lambda: self.eng.waves.wave.copy(), self.eng.Bs,
                         self.eng.D)


def view(eng, chips: int) -> EngineView:
    """The engine as the harness sees it on the cell's ``chips``, which
    also chose the engine (``cell.build_engine``)."""
    return (EngineView if chips == 1 else ShardedView)(eng)


def _request(eng, rid: int):
    """The engine's record of a request it just queued."""
    for r in reversed(eng.queue):
        if r.rid == rid:
            return r
    raise HarnessMismatch(f"request {rid} is not queued after submit")


def drive(eng, reqs, lead_s: float, seconds: float, *, chips: int,
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

    v = view(eng, chips)
    detail = eng.tel.tracer.enabled
    calls = v.calls(clock) if detail else None
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
                v.wait()
            t = clock()
            ticks.append({"t0": ta, "t1": t})
            lens, slots = [], []
            for rec in list(live):
                req = rec["req"]
                shard = v.shard(req.slot)
                if shard is not None:
                    rec.setdefault("shards", set()).add(shard)
                had = len(rec["emits"])
                k = len(req.out) - had
                if k > 0:
                    rec["emits"].extend([t] * k)
                    # the first token comes off the prefill logits; a
                    # decode token k was sampled with k - 1 in the cache
                    if k - (had == 0) > 0:
                        lens.append(rec["prompt_len"] + len(req.out) - 2)
                        slots.append(req.slot)
                if req.done:
                    live.remove(rec)
            if detail:
                calls.tick(lens, slots)
                if opened:
                    used, pages["total"] = v.pages()
                    pages["peak"] = max(pages["peak"] or 0, used)
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
        "calls": calls.records() if detail else [],
        "late_max_s": late,
        "failed": failed,
        "attempted": len(records),
        "pages_in_use_peak": pages["peak"],
        "pages_total": pages["total"],
    }
