"""Telemetry spine shared by both serving engines: tracer + metrics.

LoopLynx's core claims are *timeline* claims — temporal kernel reuse,
alternating dual-FPGA batches, "all data transfers overlapped and
hidden" — so the serving engines argue them with per-event timelines
rather than scattered ad-hoc counters.  This module is the one backing
store for all of it:

  * **Span tracer** (:class:`Tracer`) — every engine tick emits spans
    for its stages (admission, prefill chunk dispatch, decode dispatch,
    sampling, token emission, wave dispatch/consume, verify,
    accept/commit), every request gets a lifecycle timeline (queued ->
    admitted -> prefill chunks -> decode/verify events -> done), and the
    :class:`~repro.serving.distributed.transfer.TransferScheduler`
    re-emits its transfer events as spans so hidden-vs-exposed traffic
    is visible on the same timeline.  Export is Chrome/Perfetto
    trace-event JSON (``engine.dump_trace(path)`` — load it at
    https://ui.perfetto.dev).  Tracing is **zero-cost when off**: the
    default recorder is the :data:`NULL_TRACER` singleton whose methods
    are no-ops returning a shared context object, so a disabled engine
    tick allocates nothing in this layer (asserted in
    ``tests/test_telemetry.py``); call sites only build span-arg dicts
    under ``tracer.enabled``.  Nothing here ever forces a device sync —
    span durations are host-side time (dispatch + host work); the device
    side of a stage is read from a device profile (next bullet).
  * **Device profile alignment** — ``Telemetry(trace=True,
    annotate=True)`` opens a ``jax.profiler.TraceAnnotation`` of the
    same name around every span, and the span reads its clock inside
    the annotation at both edges.  A profile captured with
    ``jax.profiler.trace`` then holds a twin of each recorded span on
    its host plane, opened and closed at the same instants: the k-th
    span of a name and the k-th host-plane event of that name differ by
    one constant (the profile's clock origin).  The jitted step programs
    carry stable names (``serving.engine.PROGRAMS``), so the device
    plane shows ``jit_decode_step``, ``jit_prefill_chunk`` and
    ``jit_sample_batch`` beside those spans.
  * **Metrics registry** (:class:`MetricsRegistry`) — counters, gauges
    (with high-water marks), and fixed-bucket streaming histograms (no
    unbounded raw value lists).  Both engines' schedule counters
    (``ticks``, ``model_calls``, the ``spec_*`` family) are plain
    attributes *backed by* registry counters (:func:`registry_counter`
    descriptors), and their latency aggregates come from the
    ``ttft_s`` / ``tpot_s`` / ``tick_wall_s`` histograms — one store,
    one documented schema (see ``STATS_KEYS_*`` below and the Telemetry
    section of ``serving/distributed/README.md``).
  * **Bench artifacts** — :func:`write_bench_artifact` is the one
    versioned writer behind every ``BENCH_*.json``: schema version,
    config fingerprint, and the gate thresholds recorded next to the
    metrics, so the in-repo perf trajectory is machine-diffable.

Span taxonomy (``cat`` / ``name``):

  ==============  =============================  =========================
  cat             names                          args
  ==============  =============================  =========================
  engine          tick                           —
  stage           admit, prefill.chunk,          rid/slot/chunk geometry
                  prefill.round, decode.step,    on dispatch spans; rows
                  sample, emit, first_tokens     and (paged) pages per
                                                 layer on ``decode.step``;
                                                 ``greedy`` (0/1) on
                                                 ``sample``
  spec            spec.propose, spec.verify,     counts on the verify
                  spec.accept, spec.compact,     dispatch
                  draft.propose
  wave            wave.consume, wave.dispatch    wave id, occupancy
  transfer        the TransferScheduler event    bytes, hidden, phase,
                  name (e.g. ``decode.w0.        kind (stage/fetch)
                  logits``), cat suffixed
                  ``.hidden`` / ``.exposed``
  request         request (async b/e, id=rid);   rid, slot, shared_tokens;
                  req.queued / req.admitted /    preempt/restore mode,
                  req.first_token / req.done /   migration from/to shard
                  req.preempted / req.restored /  and bytes
                  req.migrated / req.cancelled
                  instants
  ==============  =============================  =========================

``ServeEngine``'s spans on the engine track are :data:`ENGINE_SPANS`.  A
chunked tick nests them as ``tick`` > ``admit``, ``prefill.chunk``
(each followed by ``sample`` when it completes a prompt),
``decode.step``, ``sample`` (the sampler dispatch and the host's wait
for its tokens) and ``emit`` (token bookkeeping after a decode step).
"""
from __future__ import annotations

import hashlib
import json
import time
from bisect import bisect_right
from collections import deque
from typing import Dict, List, Optional

# ---------------------------------------------------------------------------
# schema versions
# ---------------------------------------------------------------------------

#: bumped whenever the BENCH_*.json artifact layout changes shape
BENCH_SCHEMA_VERSION = 2

#: Chrome trace-event track (tid) assignment — one row per concern so
#: the Perfetto timeline separates engine stages, the transfer wire, and
#: request lifecycles.
TID_ENGINE = 0
TID_TRANSFER = 1
TID_REQUEST = 2

_TID_NAMES = {TID_ENGINE: "engine", TID_TRANSFER: "transfers",
              TID_REQUEST: "requests"}

#: every span ``ServeEngine`` opens on the engine track
ENGINE_SPANS = ("tick", "admit", "prefill.chunk", "decode.step", "sample",
                "emit", "spec.propose", "spec.verify", "spec.accept",
                "spec.compact")


# ---------------------------------------------------------------------------
# metrics registry: counters, gauges, fixed-bucket histograms
# ---------------------------------------------------------------------------


class Counter:
    """A monotonic (but resettable/assignable) scalar.  ``value`` is a
    plain attribute so engine hot paths can ``+=`` it directly through
    the :func:`registry_counter` descriptor."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n=1) -> None:
        self.value += n

    def reset(self) -> None:
        self.value = 0


class Gauge:
    """A last-value scalar with a high-water mark (``peak``) — e.g. the
    page pool's in-use count, whose peak survives the sample rate."""

    __slots__ = ("value", "peak")

    def __init__(self):
        self.value = 0.0
        self.peak = 0.0

    def set(self, v) -> None:
        self.value = v
        if v > self.peak:
            self.peak = v

    def reset(self) -> None:
        self.value = 0.0
        self.peak = 0.0


#: default histogram edges: exponential, 16 buckets/decade over
#: [1 µs, 1000 s] — wide enough for TTFT on a CPU test mesh and a real
#: accelerator alike, ~2 KB of int64 counts per histogram, never a raw
#: value list.
def exponential_edges(lo: float = 1e-6, hi: float = 1e3,
                      per_decade: int = 16) -> List[float]:
    import math

    n = int(round(math.log10(hi / lo) * per_decade))
    return [lo * 10 ** (i / per_decade) for i in range(n + 1)]


def linear_edges(lo: float, hi: float, n: int) -> List[float]:
    step = (hi - lo) / n
    return [lo + i * step for i in range(n + 1)]


class Histogram:
    """Fixed-bucket streaming histogram: O(len(edges)) memory forever.

    ``record`` is a bisect + integer increment; ``mean`` is exact
    (running sum/count); quantiles are linearly interpolated within the
    containing bucket (clamped to the exact observed min/max, so the
    under/overflow buckets cannot invent values) — accuracy is the
    bucket width, ~±12 % at the default 16-buckets/decade edges
    (checked against numpy in ``tests/test_telemetry.py``).
    """

    __slots__ = ("edges", "counts", "count", "total", "vmin", "vmax")

    def __init__(self, edges: Optional[List[float]] = None):
        self.edges = list(edges) if edges is not None \
            else exponential_edges()
        assert all(a < b for a, b in zip(self.edges, self.edges[1:])), \
            "histogram edges must be strictly increasing"
        self.counts = [0] * (len(self.edges) + 1)
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")

    def record(self, v: float) -> None:
        self.counts[bisect_right(self.edges, v)] += 1
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v

    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def quantile(self, q: float) -> float:
        """Interpolated q-quantile (0 <= q <= 1); 0.0 when empty."""
        if not self.count:
            return 0.0
        if self.count == 1:
            return self.vmin
        target = q * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            if not c:
                continue
            if cum + c >= target:
                # bucket bounds: underflow bucket starts at vmin, the
                # overflow bucket ends at vmax; every bound clamps to
                # the observed range so interpolation never extrapolates
                lo = self.edges[i - 1] if i > 0 else self.vmin
                hi = self.edges[i] if i < len(self.edges) else self.vmax
                lo = min(max(lo, self.vmin), self.vmax)
                hi = min(max(hi, self.vmin), self.vmax)
                frac = (target - cum) / c
                return lo + (hi - lo) * frac
            cum += c
        return self.vmax

    def summary(self) -> Dict[str, float]:
        return {
            "count": self.count,
            "mean": self.mean(),
            "p50": self.quantile(0.5),
            "p99": self.quantile(0.99),
            "max": self.vmax if self.count else 0.0,
        }

    def reset(self) -> None:
        for i in range(len(self.counts)):
            self.counts[i] = 0
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")


class MetricsRegistry:
    """Named counters/gauges/histograms, created on first use.

    ``histogram(name, edges=...)`` honours ``edges`` only at creation
    (engines pre-create their histograms with the right shape in
    ``__init__``); ``reset()`` zeroes every metric in place, keeping the
    bucket layouts — the benchmarks call it between jit warm-up and the
    measured workload.
    """

    def __init__(self):
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = Counter()
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges[name] = Gauge()
        return g

    def histogram(self, name: str,
                  edges: Optional[List[float]] = None) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = Histogram(edges)
        return h

    def snapshot(self) -> Dict[str, float]:
        """Flat dict of every metric: counters by name, gauges as
        ``name`` + ``name_peak``, histograms as ``name_{count, mean,
        p50, p99, max}``."""
        out: Dict[str, float] = {}
        for name, c in self._counters.items():
            out[name] = c.value
        for name, g in self._gauges.items():
            out[name] = g.value
            out[f"{name}_peak"] = g.peak
        for name, h in self._hists.items():
            for k, v in h.summary().items():
                out[f"{name}_{k}"] = v
        return out

    def reset(self) -> None:
        for c in self._counters.values():
            c.reset()
        for g in self._gauges.values():
            g.reset()
        for h in self._hists.values():
            h.reset()


class registry_counter:
    """Descriptor exposing a registry counter as a plain engine
    attribute: ``self.ticks += 1`` reads and writes
    ``self.tel.registry.counter("ticks").value`` — the registry is the
    single backing store, existing call sites keep their spelling."""

    def __init__(self, name: str):
        self.name = name

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        return obj.tel.registry.counter(self.name).value

    def __set__(self, obj, value) -> None:
        obj.tel.registry.counter(self.name).value = value


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


class _NullCtx:
    """The shared no-op context object every disabled telemetry call
    returns — entering/exiting it allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_CTX = _NullCtx()


class _SpanCtx:
    """Context manager recording one complete ("X") trace event; on an
    annotating tracer it also opens a profiler annotation of the same
    name and reads both of its clock edges inside it."""

    __slots__ = ("tracer", "name", "cat", "tid", "args", "t0", "ann")

    def __init__(self, tracer, name, cat, tid, args):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args

    def __enter__(self):
        tr = self.tracer
        self.ann = None
        if tr._annotation is not None:
            self.ann = tr._annotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        tr._stack.setdefault(self.tid, []).append(self.name)
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        tr = self.tracer
        top = tr._stack[self.tid].pop()
        assert top == self.name, (
            f"span nesting violated: closing {self.name!r} but "
            f"{top!r} is open")
        tr._events.append((
            "X", self.name, self.cat, self.tid,
            (self.t0 - tr._t0) * 1e6, (t1 - self.t0) * 1e6, self.args))
        return False


class NullTracer:
    """No-op recorder: the default.  Every method returns immediately
    (span hands back the shared :data:`_NULL_CTX`), signatures
    are positional-only-friendly with no ``*args``/``**kwargs`` packing,
    so a disabled engine tick performs zero allocations in this layer.
    Call sites must only build ``args`` dicts when ``enabled`` is True.
    """

    enabled = False
    __slots__ = ()

    def span(self, name, cat="stage", tid=TID_ENGINE, args=None):
        return _NULL_CTX

    def instant(self, name, cat="stage", tid=TID_ENGINE, args=None):
        return None

    def async_begin(self, name, id_, cat="request", args=None):
        return None

    def async_end(self, name, id_, cat="request"):
        return None

    def transfer(self, name, t0, nbytes, hidden, phase, kind="stage"):
        return None

    def reset(self):
        return None


NULL_TRACER = NullTracer()


class Tracer(NullTracer):
    """Recording tracer: bounded event ring, Chrome trace-event export.

    Events are stored as tuples in a ``deque(maxlen=max_events)`` —
    a long-lived engine keeps the most recent window rather than growing
    without bound (default 1M events ≈ a few hundred MB of JSON, far
    beyond any benchmark run; the drop is loudest-first visible because
    ``to_chrome`` reports ``dropped_events``).

    ``annotate=True`` additionally wraps every :meth:`span` in a
    ``jax.profiler.TraceAnnotation`` of the same name, so each span has
    a twin on the host plane of a device profile captured with
    ``jax.profiler.trace``, on the same instants.  Nothing in this class
    ever blocks on a device value.
    """

    enabled = True
    __slots__ = ("_t0", "_events", "_stack", "_annotation", "_recorded",
                 "max_events")

    def __init__(self, *, max_events: int = 1_000_000,
                 annotate: bool = False):
        self.max_events = max_events
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
        self._t0 = time.perf_counter()
        self._events = deque(maxlen=max_events)
        self._stack: Dict[int, List[str]] = {}
        self._recorded = 0  # total ever, incl. dropped

    # -- recording ------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def span(self, name, cat="stage", tid=TID_ENGINE, args=None):
        return _SpanCtx(self, name, cat, tid, args)

    def instant(self, name, cat="stage", tid=TID_ENGINE, args=None):
        self._events.append(("i", name, cat, tid, self._now_us(), 0.0,
                             args))

    def async_begin(self, name, id_, cat="request", args=None):
        self._events.append(("b", name, cat, id_, self._now_us(), 0.0,
                             args))

    def async_end(self, name, id_, cat="request"):
        self._events.append(("e", name, cat, id_, self._now_us(), 0.0,
                             None))

    def transfer(self, name, t0, nbytes, hidden, phase, kind="stage"):
        """One TransferScheduler event as a complete span on the
        transfer track, cat-split so exposed traffic is visually (and
        programmatically) distinct from hidden traffic."""
        t1 = time.perf_counter()
        self._events.append((
            "X", name, "transfer." + ("hidden" if hidden else "exposed"),
            TID_TRANSFER, (t0 - self._t0) * 1e6, (t1 - t0) * 1e6,
            {"bytes": nbytes, "hidden": hidden, "phase": phase,
             "kind": kind}))

    def reset(self) -> None:
        """Drop recorded events (benchmarks: between jit warm-up and the
        measured workload) without disturbing open spans."""
        self._events.clear()
        self._recorded = 0

    # -- export ---------------------------------------------------------
    @property
    def events(self) -> List[tuple]:
        return list(self._events)

    def to_chrome(self) -> Dict:
        """Chrome trace-event JSON (the Perfetto legacy-JSON format):
        ``{"traceEvents": [...]}`` with thread-name metadata so the
        engine/transfers/requests tracks are labelled."""
        out = []
        for tid, tname in _TID_NAMES.items():
            out.append({"ph": "M", "pid": 0, "tid": tid,
                        "name": "thread_name", "args": {"name": tname}})
        for ph, name, cat, tid_or_id, ts, dur, args in self._events:
            ev = {"ph": ph, "name": name, "cat": cat, "pid": 0,
                  "ts": ts}
            if ph == "X":
                ev["tid"] = tid_or_id
                ev["dur"] = dur
            elif ph in ("b", "e"):
                # async events: grouped by (cat, id); give them the
                # request track so they render near the instants
                ev["tid"] = TID_REQUEST
                ev["id"] = tid_or_id
            else:  # instant
                ev["tid"] = tid_or_id
                ev["s"] = "t"
            if args:
                ev["args"] = args
            out.append(ev)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
            f.write("\n")
        return path


def validate_chrome_trace(trace: Dict) -> Dict[str, int]:
    """Structural validity check for a Chrome/Perfetto trace dict: the
    required ``ph``/``ts``/``pid``/``tid``/``name`` fields on every
    event, non-negative durations on complete events, and balanced
    async begin/end pairs.  Returns event counts per phase type; raises
    ``ValueError`` on the first violation."""
    evs = trace.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        raise ValueError("trace has no traceEvents list")
    counts: Dict[str, int] = {}
    asyncs: Dict[tuple, int] = {}
    for i, ev in enumerate(evs):
        for field in ("ph", "pid", "name"):
            if field not in ev:
                raise ValueError(f"event {i} missing {field!r}: {ev}")
        ph = ev["ph"]
        counts[ph] = counts.get(ph, 0) + 1
        if ph == "M":
            continue
        for field in ("ts", "tid"):
            if field not in ev:
                raise ValueError(f"event {i} missing {field!r}: {ev}")
        if ph == "X":
            if ev.get("dur", -1) < 0:
                raise ValueError(f"complete event {i} without dur: {ev}")
        elif ph in ("b", "e"):
            key = (ev.get("cat"), ev.get("id"), ev["name"])
            asyncs[key] = asyncs.get(key, 0) + (1 if ph == "b" else -1)
    for key, bal in asyncs.items():
        if bal != 0:
            raise ValueError(f"unbalanced async events for {key}: {bal}")
    return counts


# ---------------------------------------------------------------------------
# the engine-facing bundle
# ---------------------------------------------------------------------------


class Telemetry:
    """One registry + one tracer, the object both engines hang off
    ``self.tel``.  The registry is always live (fixed-size histograms
    and integer counters — the cost today's ad-hoc dicts already paid);
    the tracer defaults to the no-op :data:`NULL_TRACER` and records
    only when constructed with ``trace=True``."""

    def __init__(self, *, trace: bool = False, annotate: bool = False,
                 max_events: int = 1_000_000):
        self.registry = MetricsRegistry()
        self.tracer = (Tracer(max_events=max_events, annotate=annotate)
                       if trace else NULL_TRACER)

    @property
    def tracing(self) -> bool:
        return self.tracer.enabled

    def reset(self) -> None:
        self.registry.reset()
        self.tracer.reset()

    def dump_trace(self, path: str) -> str:
        if not self.tracer.enabled:
            raise ValueError(
                "tracing is disabled on this engine; construct it with "
                "telemetry=Telemetry(trace=True) to record a timeline")
        return self.tracer.dump(path)


# ---------------------------------------------------------------------------
# versioned benchmark artifacts
# ---------------------------------------------------------------------------


def config_fingerprint(config: Dict) -> str:
    """Stable short hash of a benchmark's config dict, so trajectory
    tooling can tell "the number moved" from "the experiment moved"."""
    blob = json.dumps(config, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def write_bench_artifact(path: str, *, bench: str, config: Dict,
                         metrics: Dict, gates: Optional[Dict] = None,
                         extra: Optional[Dict] = None) -> str:
    """The one writer behind every ``BENCH_*.json``: schema version,
    config fingerprint, and the gate thresholds the benchmark asserts
    recorded *next to* the metrics they bound, so a PR-over-PR diff of
    the artifact is self-describing."""
    art = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "bench": bench,
        "config": config,
        "config_fingerprint": config_fingerprint(config),
        "gates": dict(gates or {}),
        "metrics": metrics,
    }
    if extra:
        for k, v in extra.items():
            if k in art:
                raise ValueError(f"extra key {k!r} collides with the "
                                 "artifact schema")
            art[k] = v
    with open(path, "w") as f:
        json.dump(art, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


# ---------------------------------------------------------------------------
# documented stats() schemas (golden keys)
# ---------------------------------------------------------------------------

#: every key ``ServeEngine.stats()`` returns on a paged engine without
#: speculation — the documented schema; ``tests/test_telemetry.py``
#: asserts exact equality so a stats key can only appear or vanish via a
#: deliberate schema change here.
STATS_KEYS_ENGINE = frozenset({
    "ticks", "model_calls", "prefill_calls", "stalled",
    "stalled_queued", "stalled_in_flight", "tokens_per_model_call",
    "requests", "mean_ttft_s", "mean_tok_latency_s",
    "p50_ttft_s", "p99_ttft_s", "p50_tpot_s", "p99_tpot_s",
    "tick_p50_ms", "tick_p99_ms", "mdk_mp_reuse",
    # sampler calls, and those whose rows were all greedy (the argmax
    # branch of sampler.sample_batch)
    "sample_calls", "sample_greedy_calls",
    # request lifecycle (serving/lifecycle.py): preemption/restore/
    # cancel counters and the evicted-bytes footprint
    "preemptions", "preempt_host", "preempt_recompute", "restores",
    "cancelled", "evicted_bytes_total", "evicted_bytes_p99",
    # paged-KV pool (SlotCacheManager engines report the slot analogue
    # instead: slots_in_use / slots_in_use_peak / n_free_slots)
    "pages_in_use", "pages_in_use_peak", "pages_allocated_total",
    "prefix_hit_pages", "n_free_pages", "cached_free_pages",
})

#: the additional keys a ``spec=SpecConfig(...)`` engine reports.
STATS_KEYS_ENGINE_SPEC = STATS_KEYS_ENGINE | frozenset({
    "spec_ticks", "spec_proposed", "spec_accepted", "spec_emitted",
    "acceptance_rate", "tokens_per_verify_call", "draft_calls",
    "spec_accept_len_p50", "spec_accept_len_p99",
    "verify_touched_positions", "verify_dense_positions",
})

#: every key ``DistributedServeEngine.stats()`` returns (paged, no
#: speculation) once both engine phases — prefill-carrying ticks and the
#: pure-decode drain — have occurred; the ``transfers_*_{phase}`` keys
#: materialize with their phase.
STATS_KEYS_DISTRIBUTED = (
    STATS_KEYS_ENGINE - {"tokens_per_model_call"}) | frozenset({
    "n_shards", "decode_waves", "mean_device_utilization",
    "wave_occupancy_mean", "wave_occupancy_p50", "wave_imbalance",
    # live cross-shard migration (DistributedServeEngine.migrate)
    "migrations", "migrated_bytes_total",
    "transfers", "transfers_hidden", "transfers_exposed",
    "transfer_bytes", "transfer_bytes_hidden", "transfer_bytes_exposed",
    "max_transfer_bytes", "overlap_ratio", "byte_overlap_ratio",
    "transfers_prefill", "transfers_exposed_prefill",
    "overlap_ratio_prefill",
    "transfers_drain", "transfers_exposed_drain", "overlap_ratio_drain",
})
