"""Telemetry spine tests: histogram accuracy against numpy, registry
semantics, span nesting/ordering, Chrome/Perfetto export validity, the
golden stats() key schemas of both engines, the zero-allocation disabled
path, stall detail, the versioned bench-artifact writer, spans paired
with a device profile's host plane, and the names of the step
programs."""
import glob
import json
import time
import tracemalloc

import numpy as np
import pytest

from repro.serving.telemetry import (
    BENCH_SCHEMA_VERSION,
    ENGINE_SPANS,
    NULL_TRACER,
    STATS_KEYS_DISTRIBUTED,
    STATS_KEYS_ENGINE,
    STATS_KEYS_ENGINE_SPEC,
    Histogram,
    MetricsRegistry,
    Telemetry,
    Tracer,
    config_fingerprint,
    linear_edges,
    registry_counter,
    validate_chrome_trace,
    write_bench_artifact,
)

# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------


def test_histogram_quantiles_match_numpy():
    """Interpolated quantiles on the default exponential edges stay
    within one bucket width (~±12%) of numpy's exact answer, and the
    mean is exact (running sum, not bucket-derived)."""
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-4.0, sigma=1.2, size=5000)  # ~ms-scale
    h = Histogram()
    for v in vals:
        h.record(float(v))
    assert h.count == len(vals)
    assert np.isclose(h.mean(), vals.mean(), rtol=1e-12)
    for q in (0.1, 0.5, 0.9, 0.99):
        exact = float(np.quantile(vals, q))
        got = h.quantile(q)
        assert abs(got - exact) / exact < 0.16, (q, got, exact)
    assert h.quantile(0.0) >= float(vals.min()) * 0.999
    assert h.quantile(1.0) == float(vals.max())


def test_histogram_empty_single_and_reset():
    h = Histogram(edges=linear_edges(0.0, 10.0, 10))
    assert h.quantile(0.5) == 0.0 and h.mean() == 0.0
    h.record(3.0)
    assert h.quantile(0.5) == 3.0 == h.quantile(0.99)  # clamps to vmin
    for v in (1.0, 2.0, 4.0, 5.0):
        h.record(v)
    assert 0.0 < h.quantile(0.5) <= 5.0
    edges = list(h.edges)
    h.reset()
    assert h.count == 0 and h.quantile(0.99) == 0.0
    assert h.edges == edges  # reset keeps the bucket layout


def test_histogram_identical_values_clamp():
    h = Histogram()
    for _ in range(100):
        h.record(0.25)
    assert h.quantile(0.5) == 0.25 and h.quantile(0.99) == 0.25


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_snapshot_and_reset():
    reg = MetricsRegistry()
    reg.counter("ticks").inc(3)
    reg.gauge("pool").set(5)
    reg.gauge("pool").set(2)  # peak survives the lower sample
    h = reg.histogram("lat", edges=linear_edges(0.0, 1.0, 4))
    h.record(0.5)
    snap = reg.snapshot()
    assert snap["ticks"] == 3
    assert snap["pool"] == 2 and snap["pool_peak"] == 5
    assert snap["lat_count"] == 1 and snap["lat_mean"] == 0.5
    # same-name lookup returns the same object; edges honoured at
    # creation only
    assert reg.histogram("lat", edges=linear_edges(0.0, 9.0, 3)) is h
    reg.reset()
    snap = reg.snapshot()
    assert snap["ticks"] == 0 and snap["pool_peak"] == 0
    assert snap["lat_count"] == 0
    assert reg.histogram("lat").edges == linear_edges(0.0, 1.0, 4)


def test_registry_counter_descriptor():
    class Obj:
        ticks = registry_counter("ticks")

        def __init__(self):
            self.tel = Telemetry()

    o = Obj()
    assert o.ticks == 0
    o.ticks += 2
    o.ticks += 1
    assert o.ticks == 3
    assert o.tel.registry.counter("ticks").value == 3  # single store
    o.tel.reset()
    assert o.ticks == 0


# ---------------------------------------------------------------------------
# tracer: nesting, ordering, export
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("tick", "engine"):
        with tr.span("admit"):
            pass
        with tr.span("decode.step", args={"rows": 2}):
            pass
    evs = tr.events
    # X events are appended at span EXIT: children precede their parent
    names = [e[1] for e in evs]
    assert names == ["admit", "decode.step", "tick"]
    by = {e[1]: e for e in evs}
    for child in ("admit", "decode.step"):
        _, _, _, _, ts, dur, _ = by[child]
        _, _, _, _, pts, pdur, _ = by["tick"]
        assert pts <= ts and ts + dur <= pts + pdur + 1e-6  # contained
    # admit closed before decode.step opened
    a, d = by["admit"], by["decode.step"]
    assert a[4] + a[5] <= d[4] + 1e-6
    assert by["decode.step"][6] == {"rows": 2}


def test_span_misnesting_raises():
    tr = Tracer()
    outer = tr.span("outer")
    inner = tr.span("inner")
    outer.__enter__()
    inner.__enter__()
    with pytest.raises(AssertionError, match="nesting"):
        outer.__exit__(None, None, None)


def test_chrome_export_is_valid_and_labelled(tmp_path):
    tr = Tracer()
    with tr.span("tick", "engine"):
        tr.instant("req.queued", "request", args={"rid": 0})
        tr.async_begin("request", 0)
    tr.transfer("decode.logits", 0.0, 64, True, "drain", "fetch")
    tr.transfer("chunk.tokens", 0.0, 128, False, "prefill", "stage")
    tr.async_end("request", 0)
    trace = tr.to_chrome()
    counts = validate_chrome_trace(trace)
    assert counts["X"] == 3 and counts["i"] == 1
    assert counts["b"] == 1 and counts["e"] == 1
    assert counts["M"] == 3  # engine / transfers / requests track names
    evs = trace["traceEvents"]
    thread_names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert thread_names == {"engine", "transfers", "requests"}
    cats = {e["cat"] for e in evs if e["ph"] == "X"}
    assert {"engine", "transfer.hidden", "transfer.exposed"} <= cats
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"] == {"rid": 0}
    # round-trips through json and the dump helper
    p = tmp_path / "t.json"
    tr.dump(str(p))
    with open(p) as f:
        validate_chrome_trace(json.load(f))


def test_validate_rejects_malformed_traces():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({})
    with pytest.raises(ValueError, match="missing 'ts'"):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "pid": 0, "name": "a"}]})
    with pytest.raises(ValueError, match="without dur"):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "pid": 0, "tid": 0, "ts": 1.0,
                              "name": "a"}]})
    with pytest.raises(ValueError, match="unbalanced"):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "b", "pid": 0, "tid": 0, "ts": 0.0,
                              "cat": "request", "id": 1, "name": "r"}]})


# ---------------------------------------------------------------------------
# the disabled path costs nothing
# ---------------------------------------------------------------------------


def test_null_tracer_zero_allocations():
    """The disabled tracer's hot-path methods allocate NOTHING — every
    call returns a shared singleton or None."""
    tel_file = NULL_TRACER.span.__func__.__code__.co_filename

    def burst(n):
        for i in range(n):
            with NULL_TRACER.span("tick", "engine"):
                with NULL_TRACER.span("decode.step", "stage", 0, None):
                    NULL_TRACER.instant("req.queued", "request")
                NULL_TRACER.transfer("logits", 0.0, 64, True, "drain")
            NULL_TRACER.async_begin("request", i)
            NULL_TRACER.async_end("request", i)

    burst(10)  # warm any lazy interpreter state
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        burst(500)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    flt = [tracemalloc.Filter(True, tel_file)]
    diff = after.filter_traces(flt).compare_to(
        before.filter_traces(flt), "lineno")
    # A real per-call leak over 500 iterations shows up as hundreds of
    # allocations / kilobytes; the adaptive interpreter occasionally pins
    # a few tens of bytes on the ``def`` line itself when warming method
    # call sites, so tolerate that one-time noise floor.
    grown = [d for d in diff if d.size_diff > 256 or d.count_diff >= 100]
    assert not grown, [(d.traceback, d.size_diff) for d in grown]


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each
    annotation's name and host-clock edges."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _Annotation.log.append((self.name, self.t0, time.perf_counter()))
        return False


def test_annotate_false_opens_no_annotation(monkeypatch):
    """Without ``annotate`` no span opens an annotation; with it, every
    span opens one of its own name and reads both clock edges inside
    it."""
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.log = []
    tr = Tracer()
    with tr.span("tick", "engine"):
        with tr.span("sample"):
            pass
    assert _Annotation.log == []
    tr = Tracer(annotate=True)
    with tr.span("tick", "engine"):
        with tr.span("sample"):
            pass
    assert [a[0] for a in _Annotation.log] == ["sample", "tick"]
    for (name, a0, a1), ev in zip(_Annotation.log, tr.events):
        assert ev[1] == name
        t0 = tr._t0 + ev[4] / 1e6
        t1 = t0 + ev[5] / 1e6
        assert a0 <= t0 + 1e-9 and t1 <= a1 + 1e-9


def test_telemetry_dump_requires_tracing():
    tel = Telemetry()  # trace=False default
    assert tel.tracer is NULL_TRACER
    with pytest.raises(ValueError, match="disabled"):
        tel.dump_trace("/tmp/never.json")


# ---------------------------------------------------------------------------
# artifact writer
# ---------------------------------------------------------------------------


def test_write_bench_artifact_schema(tmp_path):
    cfgd = {"model": "gpt2-345m", "seed": 0}
    p = write_bench_artifact(
        str(tmp_path / "BENCH_x.json"), bench="x", config=cfgd,
        metrics={"overlap_ratio": 0.97},
        gates={"overlap_ratio_min": 0.85},
        extra={"baseline": {"ticks": 10}})
    with open(p) as f:
        art = json.load(f)
    assert art["schema_version"] == BENCH_SCHEMA_VERSION
    assert art["bench"] == "x"
    assert art["config_fingerprint"] == config_fingerprint(cfgd)
    assert art["gates"] == {"overlap_ratio_min": 0.85}
    assert art["metrics"]["overlap_ratio"] == 0.97
    assert art["baseline"] == {"ticks": 10}
    # the fingerprint tracks the config, not the metrics
    assert config_fingerprint({"model": "gpt2-345m", "seed": 1}) != \
        art["config_fingerprint"]
    with pytest.raises(ValueError, match="collides"):
        write_bench_artifact(
            str(tmp_path / "BENCH_y.json"), bench="y", config={},
            metrics={}, extra={"metrics": {}})


# ---------------------------------------------------------------------------
# engine integration: golden stats() schemas, traces, zero-cost ticks
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_env():
    import jax

    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("gpt2-345m").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=64)
    return cfg, params


def _drive(eng, n=3, max_new=4):
    rng = np.random.default_rng(0)
    for _ in range(n):
        eng.submit(list(rng.integers(1, 100, 6)), max_new=max_new)
    eng.run()
    return eng


def test_engine_stats_golden_keys(engine_env):
    from repro.serving.engine import ServeEngine

    cfg, params = engine_env
    eng = _drive(ServeEngine(cfg, params, batch_slots=2, max_seq=64,
                             eos_id=-1, chunk_size=8))
    assert set(eng.stats()) == STATS_KEYS_ENGINE


def test_engine_spec_stats_golden_keys(engine_env):
    from repro.serving.engine import ServeEngine
    from repro.serving.speculative import SpecConfig

    cfg, params = engine_env
    eng = _drive(ServeEngine(cfg, params, batch_slots=2, max_seq=64,
                             eos_id=-1, chunk_size=8,
                             spec=SpecConfig(k=3)))
    assert set(eng.stats()) == STATS_KEYS_ENGINE_SPEC


def test_distributed_stats_golden_keys(engine_env):
    from repro.serving.distributed import DistributedServeEngine

    cfg, params = engine_env
    eng = _drive(DistributedServeEngine(
        cfg, params, n_shards=1, slots_per_shard=2, max_seq=64,
        eos_id=-1, chunk_size=8))
    assert set(eng.stats()) == STATS_KEYS_DISTRIBUTED


def test_engine_trace_lifecycle(engine_env, tmp_path):
    """A traced run exports a valid timeline whose request lifecycle is
    ordered: queued -> admitted -> first_token -> done, with balanced
    async request envelopes and tick/stage spans around them."""
    from repro.serving.engine import ServeEngine

    cfg, params = engine_env
    eng = _drive(ServeEngine(cfg, params, batch_slots=2, max_seq=64,
                             eos_id=-1, chunk_size=8,
                             telemetry=Telemetry(trace=True)), n=2)
    p = tmp_path / "trace.json"
    eng.dump_trace(str(p))
    with open(p) as f:
        trace = json.load(f)
    counts = validate_chrome_trace(trace)
    assert counts["b"] == counts["e"] == 2  # one envelope per request
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"tick", "admit", "prefill.chunk", "decode.step", "req.queued",
            "req.admitted", "req.first_token", "req.done"} <= names

    def instants(rid):
        return [e for e in evs if e["ph"] == "i"
                and (e.get("args") or {}).get("rid") == rid]

    for rid in (0, 1):
        seq = sorted(instants(rid), key=lambda e: e["ts"])
        kinds = [e["name"] for e in seq]
        assert kinds[0] == "req.queued"
        assert kinds[1] == "req.admitted"
        assert kinds[-1] == "req.done"
        assert "req.first_token" in kinds[2:-1] or kinds[2] == \
            "req.first_token"
    # the tick's stages: every engine-track span is a declared engine
    # span, the sampler and the token bookkeeping among them
    stages = {e["name"] for e in evs if e["ph"] == "X" and e["tid"] == 0}
    assert stages <= set(ENGINE_SPANS)
    assert {"sample", "emit"} <= stages


def test_disabled_tick_retains_no_telemetry_memory(engine_env):
    """With tracing off, engine ticks retain no memory in the telemetry
    layer: the registry's fixed-size histograms mutate in place, and the
    null tracer allocates nothing — no growth proportional to ticks."""
    import repro.serving.telemetry as T
    from repro.serving.engine import ServeEngine

    cfg, params = engine_env
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      chunk_size=8)
    assert not eng.tel.tracing
    for _ in range(20):  # warm: settle vmin/vmax floats, int caches
        eng.tick()
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(200):
            eng.tick()
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    flt = [tracemalloc.Filter(True, T.__file__)]
    diff = after.filter_traces(flt).compare_to(
        before.filter_traces(flt), "filename")
    # a couple of live rebound floats/ints (histogram totals, counter
    # values) may differ between snapshots; nothing may scale with the
    # 200 ticks (which would be >= 200 * 28 bytes)
    net = sum(d.size_diff for d in diff)
    assert net < 512, [(d.traceback, d.size_diff) for d in diff]


def test_stall_detail_names_requests(engine_env):
    """Satellite: a drain stall reports WHICH requests are stuck and in
    what state, in both the RuntimeError and stats()."""
    from repro.serving.engine import ServeEngine

    cfg, params = engine_env
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=64, eos_id=-1,
                      chunk_size=8)
    eng.submit(list(range(1, 7)), max_new=4)
    eng.submit(list(range(1, 7)), max_new=4)
    with pytest.raises(RuntimeError) as ei:
        eng.run(max_ticks=2)
    msg = str(ei.value)
    assert "stalled" in msg and "queued" in msg and "in-flight" in msg
    assert "rids" in msg
    assert eng.stalled_detail["in_flight"] == [0]
    assert eng.stalled_detail["queued"] == [1]
    s = eng.stats()
    assert s["stalled"] == 2
    assert s["stalled_queued"] == 1 and s["stalled_in_flight"] == 1
    # ignore mode surfaces the same breakdown without raising
    eng.run(on_stall="ignore")  # drains fully now
    s = eng.stats()
    assert s["stalled"] == 0
    assert s["stalled_queued"] == 0 and s["stalled_in_flight"] == 0


def _spans(tracer):
    """The tracer's complete spans as ``(name, start_us, end_us)``, in
    order of start (a parent before the children it opened)."""
    return sorted(((e[1], e[4], e[4] + e[5]) for e in tracer.events
                   if e[0] == "X"), key=lambda s: (s[1], -s[2]))


def test_traced_tick_nests_sample_and_emit(engine_env):
    """``sample`` and ``emit`` lie inside a ``tick``; a decode tick runs
    decode.step, then sample, then emit."""
    from repro.serving.engine import ServeEngine

    cfg, params = engine_env
    eng = _drive(ServeEngine(cfg, params, batch_slots=2, max_seq=64,
                             eos_id=-1, chunk_size=8,
                             telemetry=Telemetry(trace=True)), n=2)
    spans = _spans(eng.tel.tracer)
    ticks = [s for s in spans if s[0] == "tick"]
    decode_ticks = 0
    for name, a, b in spans:
        if name in ("sample", "emit"):
            assert any(ta <= a and b <= tb for _, ta, tb in ticks), name
    for _, ta, tb in ticks:
        inner = [s[0] for s in spans
                 if ta <= s[1] and s[2] <= tb and s[0] != "tick"]
        if "decode.step" in inner:
            decode_ticks += 1
            i = inner.index("decode.step")
            assert inner[i + 1:i + 3] == ["sample", "emit"], inner
    assert decode_ticks >= 3


def test_annotated_spans_pair_with_the_profile_host_plane(engine_env,
                                                          tmp_path):
    """Under the profiler, every span of an annotating tracer has a
    twin on the host plane: the same names in the same order, their
    starts one constant apart."""
    import jax
    from jax.profiler import ProfileData

    from repro.serving.engine import ServeEngine

    cfg, params = engine_env
    eng = _drive(ServeEngine(cfg, params, batch_slots=2, max_seq=64,
                             eos_id=-1, chunk_size=8,
                             telemetry=Telemetry(trace=True,
                                                 annotate=True)), n=1)
    eng.tel.tracer.reset()  # compiled; keep only the profiled run
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive(eng, n=2)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(eng.tel.tracer)
    assert {s[0] for s in spans} >= {"tick", "admit", "prefill.chunk",
                                     "decode.step", "sample", "emit"}
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = {s[0] for s in spans}
    host = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name in names),
                  key=lambda h: (h[1], -h[2]))
    assert [h[0] for h in host] == [s[0] for s in spans]
    offsets = [h[1] - s[1] * 1e3 for h, s in zip(host, spans)]
    assert max(offsets) - min(offsets) < 1e6  # ns


@pytest.mark.parametrize("with_mesh", [False, True],
                         ids=["no_mesh", "mesh"])
def test_step_programs_are_named(engine_env, with_mesh):
    """The lowered step programs carry the names ``PROGRAMS`` declares:
    ``jit_decode_step``, ``jit_prefill_chunk``, ``jit_sample_batch``."""
    import jax
    import jax.numpy as jnp

    from repro.core import compat
    from repro.serving.engine import PROGRAMS, ServeEngine

    cfg, params = engine_env
    mesh = compat.make_mesh((1,), ("model",)) if with_mesh else None
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      chunk_size=8, mesh=mesh)
    assert eng.paged
    bts = jnp.asarray(eng.kv.block_tables)
    lowered = {
        "decode": eng._step.lower(
            eng.params, jnp.asarray(eng.cur_tok), eng.kv.cache,
            eng.kv.lengths, bts, jnp.ones((eng.B,), bool)),
        "prefill": eng._prefill.lower(
            eng.params, jnp.zeros((eng.chunk_size,), jnp.int32),
            eng.kv.cache, 0, bts[0], 0, 1),
        "sample": eng._sample.lower(
            jnp.zeros((eng.B, cfg.vocab_size)), jax.random.PRNGKey(0),
            jnp.asarray(eng._temp), jnp.asarray(eng._topk),
            jnp.asarray(eng._topp)),
    }
    assert PROGRAMS["decode"] == "decode_step"
    assert PROGRAMS["prefill"] == "prefill_chunk"
    for kind, low in lowered.items():
        assert f"module @jit_{PROGRAMS[kind]} " in low.as_text(), kind
