"""Serving benchmark: chunked-prefill admission vs the seed replay path,
the paged KV cache's prefix sharing on a shared-system-prompt fleet, and
the distributed engine's transfer overlap vs the single-device baseline.

    PYTHONPATH=src python benchmarks/serving_bench.py [--requests 8]
        [--chunk 16] [--slots 3] [--max-new 8] [--seed 0]
        [--sys-len 96] [--page-size 16] [--part all|core|dist]

Part 1 drives the same mixed-prompt-length request stream (short
interactive prompts interleaved with long ones) through both admission
modes of ``ServeEngine`` and reports per-mode TTFT, TPOT, ticks, model
calls, and throughput.  Also verifies the tentpole acceptance criteria
directly:

  * chunked prefill generates exactly the replay path's tokens on the same
    greedy stream (logit-level equivalence is asserted in
    ``tests/test_serving.py``), and
  * a P-token prompt costs ``ceil(P / chunk)`` prefill forward calls.

Part 2 is the paged-KV workload every production fleet runs: one shared
system prompt ahead of short per-user tails.  The same greedy stream goes
through the contiguous layout, the paged layout with sharing disabled,
and the paged layout with prefix sharing, and the table reports pages
allocated, prefix-share hit rate, and TTFT.  All three streams must be
token-identical (pages are a layout, not a model change), and sharing
must allocate >=30% fewer pages than no-sharing paged mode (PR-2
acceptance criterion; shared full prompt pages are linked, not copied).

Part "spec" (``--part spec``; also runs under ``--part all``) drives a
repetitive-text workload (short patterns repeated into 24-token prompts,
long generations that fall into the model's greedy cycles — the regime
where decode ticks are pure weight-streaming waste) through the plain
engine and the speculative engine (``spec=SpecConfig(k)``, self-drafting
n-gram proposer).  Tokens must be identical, the speculative engine must
finish with **fewer model calls**, and its **tokens-per-model-call** must
exceed 1.5 (each verify call emits the accepted draft run + one
bonus/corrective token per slot); acceptance rate comes from
``stats()["acceptance_rate"]``.  The part also reports the verify-path
copy traffic (live-page positions touched by the in-place paged verify
vs the retired full-``max_seq`` gather/scatter), runs adaptive draft
sizing (``SpecConfig(adaptive=True)``) on both the repetitive stream
(tokens/model-call must not regress) and a low-acceptance draft-model
stream (drafted-token waste must shrink and tokens per total call —
target + draft forwards — must improve), and writes a
``BENCH_spec.json`` artifact.

Part "hybrid" (``--part hybrid``; also runs under ``--part all``) drives
the mixed-length workload through a rotating-window + recurrent stack
(recurrentgemma-shaped: rglru, rglru, local_attn) in both engine modes.
The universal chunked path must generate exactly the replay tokens while
spending **>= 2x fewer ticks** — the PR-5 acceptance gate: a P-token
prompt costs ``ceil(P / chunk)`` chunked calls instead of P replay
ticks, now for window/recurrent kinds too.  A second section serves a
MIXED stack (attn + local_attn + rglru) on the shared-system-prompt
workload through the per-kind paged layout: all three layouts must be
token-identical and prefix sharing must link shared attn prompt pages
(>= 30% fewer page allocations — a saving that was structurally zero
while paged refused hybrids).  Writes a ``BENCH_hybrid.json`` artifact.

Part "preempt" (``--part preempt``; also runs under ``--part all``)
drives an over-subscribed bursty stream through a KV pool so small the
reservation-based admission (worst-case lifetime pages up front) raises
its never-fits ``ValueError`` for every request, then serves the same
stream through ``OvercommitAdmission``: requests admit on prompt pages
only, decode growth drains the pool, and the engine preempts victims
(lowest priority, most pages, newest first) to host memory or a
recompute requeue until the whole burst completes.  The stream must be
token-identical to a roomy-pool reference run, every request must
finish (completion gate), at least one preemption must fire, and p99
TTFT must stay under an absolute ceiling (the preempt/restore detour
may not starve any request).  Writes a ``BENCH_preempt.json`` artifact.

Part 3 (``--part dist``; auto-spawned in a forced 4-device subprocess
when the main process has fewer devices) drives the mixed-length workload
through ``DistributedServeEngine`` on a 4-shard mesh and reports, next to
the single-device chunked baseline: per-device utilization, p50/p99 tick
latency, transfer counts, and the **transfer-overlap ratio** — the
fraction of host<->device transfers (chunk shipping, block-table rows,
the logits collective) staged while device compute was in flight —
broken down by phase (prefill-carrying ticks vs the pure-decode drain).
Tokens must be identical and the ratio must be >= 0.85 *including the
drain* (the paper's alternating dual-FPGA batches: the engine splits the
slot set into two phase-shifted decode waves, so each wave's fetch hides
behind the other wave's in-flight call even after prefill traffic dries
up).  With ``--spec`` both engines also run speculative decoding and the
distributed stream must still match single-device token-for-token.  A
``BENCH_dist[_spec].json`` artifact (config + every scalar metric,
through the versioned ``write_bench_artifact`` schema like every other
part) is written to the working directory for in-repo perf tracking,
next to a ``TRACE_dist[_spec].json`` Perfetto timeline dumped from the
engine's recording telemetry — validated structurally, and its exposed
transfer spans must match ``stats()["transfers_exposed"]`` one-for-one.

On CPU the wall-clock gap understates the paper's pipeline argument (no
weight-streaming overlap here), so the headline columns are the *schedule*
quantities — ticks, model calls, pages, overlap ratio — which are
hardware-independent.
"""
from __future__ import annotations

import argparse
import math
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import ServeEngine
from repro.serving.telemetry import (
    Telemetry,
    validate_chrome_trace,
    write_bench_artifact,
)


def build_workload(rng: np.random.Generator, n_requests: int, vocab: int):
    """Mixed lengths: alternating short (3-8) and long (32-48) prompts."""
    prompts = []
    for i in range(n_requests):
        lo, hi = ((32, 48) if i % 2 else (3, 8))
        plen = int(rng.integers(lo, hi + 1))
        prompts.append(list(rng.integers(1, vocab, plen)))
    return prompts


def build_shared_workload(rng, n_requests, vocab, sys_len, tail=(4, 16)):
    """One shared system prompt + short unique per-user tails."""
    sys_prompt = list(rng.integers(1, vocab, sys_len))
    return [
        sys_prompt + list(rng.integers(1, vocab,
                                       int(rng.integers(*tail))))
        for _ in range(n_requests)
    ]


def run_mode(cfg, params, prompts, *, mode, chunk, slots, max_new, max_seq,
             kv_layout="auto", page_size=16, prefix_sharing=True,
             spec=None):
    eng = ServeEngine(cfg, params, batch_slots=slots, max_seq=max_seq,
                      eos_id=-1, prefill_mode=mode, chunk_size=chunk,
                      kv_layout=kv_layout, page_size=page_size,
                      prefix_sharing=prefix_sharing, spec=spec)
    # warm the jit caches (prefill-chunk + decode-step compiles) so TTFT
    # measures the schedule, not XLA compilation
    eng.submit(list(range(1, chunk + 2)), max_new=2)
    eng.run()
    warm = len(eng.finished)
    t_ticks, t_calls, t_pcalls = eng.ticks, eng.model_calls, \
        eng.prefill_calls
    t_pages = eng.kv.pages_allocated_total if eng.paged else 0
    t_hits = eng.kv.prefix_hit_pages if eng.paged else 0

    for p in prompts:
        eng.submit(p, max_new=max_new)
    t0 = time.time()
    eng.run()
    wall = time.time() - t0
    done = eng.finished[warm:]
    ttft = [r.ttft for r in done]
    tpot = [(r.t_done - r.t_first) / max(1, len(r.out) - 1) for r in done]
    toks = sum(len(r.out) for r in done)
    return {
        "outs": {tuple(r.prompt): r.out for r in done},
        "ttft_s": float(np.mean(ttft)),
        "tpot_s": float(np.mean(tpot)),
        "ticks": eng.ticks - t_ticks,
        "model_calls": eng.model_calls - t_calls,
        "prefill_calls": eng.prefill_calls - t_pcalls,
        "tok_per_s": toks / max(wall, 1e-9),
        "wall_s": wall,
        "pages": (eng.kv.pages_allocated_total - t_pages
                  if eng.paged else 0),
        "hit_pages": (eng.kv.prefix_hit_pages - t_hits
                      if eng.paged else 0),
    }


def build_repetitive_workload(rng, n_requests, vocab, *, pattern_len=8,
                              repeats=3):
    """Repetitive text: a few short patterns, each repeated into a
    prompt — the n-gram proposer's home turf (and greedy decode of long
    generations settles into cycles it also predicts)."""
    patterns = [list(rng.integers(1, vocab, pattern_len)) for _ in range(3)]
    return [list(patterns[i % len(patterns)]) * repeats
            for i in range(n_requests)]


def _finite_scalars(s):
    return {k: s[k] for k in sorted(s)
            if isinstance(s[k], (int, float)) and np.isfinite(s[k])}


def run_spec_part(args) -> None:
    """Part "spec": speculative decoding vs the plain engine, adaptive
    draft sizing, and the verify-path copy-traffic accounting.

    Two workloads: the repetitive high-acceptance stream (n-gram
    self-drafting; adaptive caps must NOT regress tokens/model-call) and
    a low-acceptance stream (a differently-initialized draft model keeps
    proposing, mostly wrong; adaptive caps must shrink the wasted draft
    work and improve tokens per total call — target + draft forwards).
    Writes a ``BENCH_spec.json`` artifact.
    """
    import os

    from repro.serving.speculative import SpecConfig

    cfg = get_config("gpt2-345m").reduced()
    max_seq = max(args.max_seq, 128)
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=max_seq)
    rng = np.random.default_rng(args.seed)
    prompts = build_repetitive_workload(rng, 6, cfg.vocab_size)
    max_new = 48
    print(f"\nspeculative workload: {len(prompts)} repetitive prompts "
          f"({len(prompts[0])} tokens: 8-token patterns x3), {max_new} new "
          f"tokens each, {args.slots} slots, k={args.spec_k} (n-gram "
          "self-drafting)")

    def drive(spec, workload, m_new):
        eng = ServeEngine(cfg, params, batch_slots=args.slots,
                          max_seq=max_seq, eos_id=-1, chunk_size=args.chunk,
                          spec=spec)
        for p in workload:
            eng.submit(list(p), max_new=m_new)
        t0 = time.time()
        eng.run(max_ticks=50_000)
        s = eng.stats()
        s["wall_s"] = time.time() - t0
        emitted = s["tokens_per_model_call"] * s["model_calls"]
        s["tokens_per_total_call"] = emitted / max(
            s["model_calls"] + s.get("draft_calls", 0), 1)
        return {"outs": {r.rid: r.out for r in eng.finished}, "s": s}

    rows = {
        "plain": drive(None, prompts, max_new),
        "spec": drive(SpecConfig(k=args.spec_k), prompts, max_new),
        "spec+adapt": drive(SpecConfig(k=args.spec_k, adaptive=True),
                            prompts, max_new),
    }
    print(f"\n{'engine':10s} {'ticks':>6s} {'calls':>6s} {'tok/call':>9s} "
          f"{'accept':>7s} {'tok/verify':>11s}")
    for name, r in rows.items():
        s = r["s"]
        print(f"{name:10s} {s['ticks']:6.0f} {s['model_calls']:6.0f} "
              f"{s['tokens_per_model_call']:9.2f} "
              f"{s.get('acceptance_rate', float('nan')):7.2f} "
              f"{s.get('tokens_per_verify_call', float('nan')):11.2f}")

    # verify-path copy traffic: the in-place paged verify touches each
    # row's live pages; "dense" is the retired full-max_seq gather/scatter
    st = rows["spec"]["s"]
    touched, dense = (st["verify_touched_positions"],
                      st["verify_dense_positions"])
    print(f"\nverify copy traffic: {touched} live-page positions vs "
          f"{dense} dense-view positions "
          f"({touched / max(dense, 1):.2f}x of the retired gather)")
    assert 0 < touched < dense, (
        "paged verify must touch only live pages, strictly less than the "
        f"retired full-view gather ({touched} vs {dense})")

    assert (rows["spec"]["outs"] == rows["plain"]["outs"]
            == rows["spec+adapt"]["outs"]), (
        "speculative decoding changed the greedy stream")
    assert rows["spec"]["s"]["model_calls"] < \
        rows["plain"]["s"]["model_calls"], "speculation must reduce calls"
    for name in ("spec", "spec+adapt"):
        tpc = rows[name]["s"]["tokens_per_model_call"]
        assert tpc > 1.5, (
            f"{name} must emit > 1.5 tokens per model call on the "
            f"repetitive workload (got {tpc:.2f})")
    assert (rows["spec+adapt"]["s"]["tokens_per_model_call"]
            >= 0.9 * rows["spec"]["s"]["tokens_per_model_call"]), (
        "adaptive draft sizing regressed the high-acceptance workload")

    # -- low-acceptance stream: adaptive caps cut the wasted draft work --
    draft_params = lm.init(cfg, jax.random.PRNGKey(7), max_seq=max_seq)
    low = build_workload(rng, 6, cfg.vocab_size)
    mk = dict(proposer="model", draft_cfg=cfg, draft_params=draft_params)
    low_rows = {
        "plain": drive(None, low, 24),
        "fixed": drive(SpecConfig(k=args.spec_k, **mk), low, 24),
        "adapt": drive(SpecConfig(k=args.spec_k, adaptive=True, **mk),
                       low, 24),
    }
    print(f"\nlow-acceptance stream (draft model != target, {len(low)} "
          f"mixed prompts, 24 new tokens):")
    print(f"{'engine':8s} {'proposed':>9s} {'accept':>7s} "
          f"{'draft_calls':>12s} {'tok/total':>10s}")
    for name in ("fixed", "adapt"):
        s = low_rows[name]["s"]
        print(f"{name:8s} {s['spec_proposed']:9.0f} "
              f"{s['acceptance_rate']:7.2f} {s['draft_calls']:12.0f} "
              f"{s['tokens_per_total_call']:10.2f}")
    assert (low_rows["adapt"]["outs"] == low_rows["fixed"]["outs"]
            == low_rows["plain"]["outs"]), (
        "adaptive draft sizing changed the greedy stream")
    assert (low_rows["adapt"]["s"]["spec_proposed"]
            < low_rows["fixed"]["s"]["spec_proposed"]), (
        "adaptive caps must shrink drafted tokens under heavy rejection")
    assert (low_rows["adapt"]["s"]["tokens_per_total_call"]
            > low_rows["fixed"]["s"]["tokens_per_total_call"]), (
        "adaptive caps must improve tokens per total (target+draft) call "
        "on the low-acceptance workload")

    out_path = write_bench_artifact(
        os.path.abspath("BENCH_spec.json"),
        bench="serving_spec",
        config={
            "model": cfg.name, "slots": args.slots, "chunk": args.chunk,
            "max_seq": max_seq, "seed": args.seed, "k": args.spec_k,
            "repetitive": {"requests": len(prompts), "max_new": max_new},
            "low_acceptance": {"requests": len(low), "max_new": 24,
                               "proposer": "model"},
        },
        metrics={
            "repetitive": {n: _finite_scalars(r["s"])
                           for n, r in rows.items()},
            "low_acceptance": {n: _finite_scalars(r["s"])
                               for n, r in low_rows.items()},
        },
        gates={
            "tokens_per_model_call_min": 1.5,
            "adaptive_tokens_per_model_call_frac_min": 0.9,
            "low_acceptance_tokens_per_total_call_improves": True,
        })
    print(f"wrote {out_path}")

    print(f"\nmodel-call reduction: {rows['plain']['s']['model_calls']:.0f}"
          f" -> {rows['spec']['s']['model_calls']:.0f} "
          f"({rows['plain']['s']['model_calls'] / rows['spec']['s']['model_calls']:.2f}x)")
    print("SERVING_BENCH_SPEC_OK")


def run_tree_spec_part(args) -> None:
    """Part "spec --tree": token-tree drafting vs the linear chain at
    equal verify width.

    The draft model is the target plus parameter noise: top-1 agreement
    collapses (so linear chains die young) while the target's argmax
    usually survives inside the draft's top-``b`` — exactly the branchy
    low-acceptance regime tree drafting exploits, since sibling
    candidates recover what the chain threw away.  Every engine verifies
    ``k+1``-wide chunks, so the tree's tokens/model-call gain is a pure
    width-for-depth reallocation of the same verify compute; the tree
    also spends only ``ceil(k/branch)`` draft forwards per tick where
    the chain spends ``k``, which compounds into the tokens-per-total-
    call (target + draft forwards) gain.  Writes a
    ``BENCH_tree_spec.json`` artifact.

    All four streams must stay bit-identical: beyond the usual greedy
    gate this doubles as a regression net for the async-dispatch race
    this workload once exposed (accepted-path compaction reading the
    paged block tables while rewind nulled freed entries in place).
    """
    import os

    from repro.serving.speculative import SpecConfig

    cfg = get_config("gpt2-345m").reduced()
    max_seq = max(args.max_seq, 192)
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=max_seq)
    rng = np.random.default_rng(args.seed)
    prompts = build_workload(rng, 8, cfg.vocab_size)
    k, branch, max_new, sigma = 8, 8, 48, 0.25

    # noisy draft: same architecture, each tensor jittered by sigma of
    # its own scale — enough noise that chains break within a step or
    # two, little enough that the truth stays in the draft's top-b
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    draft_params = jax.tree_util.tree_unflatten(treedef, [
        x + sigma * jnp.std(x) * jax.random.normal(kk, x.shape, x.dtype)
        for x, kk in zip(leaves, keys)])

    print(f"\ntree-speculation workload: {len(prompts)} mixed prompts, "
          f"{max_new} new tokens each, k={k} (verify width {k + 1}), "
          f"draft = target + {sigma:.2f}*std parameter noise")

    def drive(spec):
        eng = ServeEngine(cfg, params, batch_slots=4, max_seq=max_seq,
                          eos_id=-1, chunk_size=args.chunk, spec=spec)
        for p in prompts:
            eng.submit(list(p), max_new=max_new)
        t0 = time.time()
        eng.run(max_ticks=50_000)
        s = eng.stats()
        s["wall_s"] = time.time() - t0
        emitted = s["tokens_per_model_call"] * s["model_calls"]
        s["tokens_per_total_call"] = emitted / max(
            s["model_calls"] + s.get("draft_calls", 0), 1)
        return {"outs": {r.rid: r.out for r in eng.finished}, "s": s}

    mk = dict(proposer="model", draft_cfg=cfg, draft_params=draft_params)
    rows = {
        "plain": drive(None),
        "chain": drive(SpecConfig(k=k, **mk)),
        "tree-deep": drive(SpecConfig(k=k, tree=True, branch=3, **mk)),
        "tree-wide": drive(SpecConfig(k=k, tree=True, branch=branch,
                                      **mk)),
    }
    print(f"\n{'engine':10s} {'calls':>6s} {'draft':>6s} {'accept':>7s} "
          f"{'tok/call':>9s} {'tok/total':>10s}")
    for name, r in rows.items():
        s = r["s"]
        print(f"{name:10s} {s['model_calls']:6.0f} "
              f"{s.get('draft_calls', 0):6.0f} "
              f"{s.get('acceptance_rate', float('nan')):7.2f} "
              f"{s['tokens_per_model_call']:9.2f} "
              f"{s['tokens_per_total_call']:10.2f}")

    outs = {n: r["outs"] for n, r in rows.items()}
    assert (outs["chain"] == outs["plain"] == outs["tree-deep"]
            == outs["tree-wide"]), (
        "tree speculation changed the greedy stream")
    ratio = (rows["tree-wide"]["s"]["tokens_per_model_call"]
             / rows["chain"]["s"]["tokens_per_model_call"])
    ratio_total = (rows["tree-wide"]["s"]["tokens_per_total_call"]
                   / rows["chain"]["s"]["tokens_per_total_call"])
    print(f"\ntree vs chain at verify width {k + 1}: {ratio:.3f}x "
          f"tokens/model-call, {ratio_total:.2f}x tokens/total-call "
          f"(draft forwards {rows['tree-wide']['s']['draft_calls']:.0f} "
          f"vs {rows['chain']['s']['draft_calls']:.0f})")
    assert ratio >= 1.15, (
        "tree drafting must emit >= 1.15x tokens per target model call "
        f"over the linear chain at equal verify width (got {ratio:.3f})")
    assert ratio_total >= 1.5, (
        "tree drafting's ceil(k/branch) draft forwards must beat the "
        f"chain's k on total-call economics (got {ratio_total:.2f})")
    assert (rows["tree-wide"]["s"]["draft_calls"]
            < rows["chain"]["s"]["draft_calls"]), (
        "the wide tree must spend fewer draft forwards than the chain")

    out_path = write_bench_artifact(
        os.path.abspath("BENCH_tree_spec.json"),
        bench="serving_tree_spec",
        config={
            "model": cfg.name, "slots": 4, "chunk": args.chunk,
            "max_seq": max_seq, "seed": args.seed, "k": k,
            "branch": branch, "max_new": max_new, "requests": len(prompts),
            "draft_noise_sigma": sigma, "proposer": "model",
        },
        metrics={
            **{n: _finite_scalars(r["s"]) for n, r in rows.items()},
            "tree_vs_chain_tokens_per_model_call": ratio,
            "tree_vs_chain_tokens_per_total_call": ratio_total,
        },
        gates={
            "tree_vs_chain_tokens_per_model_call_min": 1.15,
            "tree_vs_chain_tokens_per_total_call_min": 1.5,
            "greedy_streams_bit_identical": True,
        })
    print(f"wrote {out_path}")
    print("SERVING_BENCH_TREE_SPEC_OK")


def run_preempt_part(args) -> None:
    """Part "preempt": over-commit admission completes an over-subscribed
    bursty stream the reservation-based engine refuses outright.

    The pool is sized so every request's worst-case lifetime reservation
    (``pages_for(prompt + max_new)``) exceeds the usable pool — the
    reservation engine raises its never-fits ``ValueError`` at admission
    — while the *actual* greedy stream terminates early at a probed eos
    token, so prompt-priced over-commit admission can run the burst to
    completion, preempting victims to host memory whenever decode growth
    drains the pool.  Streams must match a roomy-pool reference
    token-for-token; gates: full completion, >= 1 preemption, p99 TTFT
    under an absolute ceiling.  Writes ``BENCH_preempt.json``.
    """
    import os

    from repro.serving.admission import OvercommitAdmission

    cfg = get_config("gpt2-345m").reduced()
    max_seq = 64
    page_size = 16
    n_pages = 4  # 3 usable pages; each request reserves 4 -> never fits
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=max_seq)
    rng = np.random.default_rng(args.seed)
    prompt = list(rng.integers(1, cfg.vocab_size, 10))
    max_new = 40  # prices min(10 + 40, 64) = 50 tokens = 4 pages
    n_req = max(args.requests, 6)

    # probe the free-running greedy stream for an eos whose *first*
    # occurrence is mid-stream: late enough that decode growth spills
    # past the first page (forcing preemption under over-commit), early
    # enough that the actual footprint fits the tiny pool
    probe = ServeEngine(cfg, params, batch_slots=1, max_seq=max_seq,
                        eos_id=-1, chunk_size=8, kv_layout="paged",
                        page_size=page_size)
    probe.submit(prompt, max_new=max_new)
    stream = probe.run()[0].out
    first_idx = {}
    for j, t in enumerate(stream):
        first_idx.setdefault(t, j)
    eos_id, eos_at = max(first_idx.items(), key=lambda kv: kv[1])
    usable_toks = (n_pages - 1) * page_size - len(prompt)
    assert 7 <= eos_at < usable_toks, (
        f"probed eos lands at index {eos_at}, outside [7, {usable_toks})"
        " — pick a different --seed for the preempt part")
    print(f"\npreempt workload: {n_req}-request burst of a "
          f"{len(prompt)}-token prompt, max_new={max_new}, eos token "
          f"{eos_id} (fires at index {eos_at}); pool {n_pages} pages x "
          f"{page_size} tokens (reservation price 4 > {n_pages - 1} "
          "usable)")

    def build(n_pool, admission=None):
        return ServeEngine(cfg, params, batch_slots=3, max_seq=max_seq,
                           eos_id=eos_id, chunk_size=args.chunk,
                           kv_layout="paged", page_size=page_size,
                           n_pages=n_pool, prefix_sharing=False,
                           admission=admission)

    # roomy-pool reference stream (and jit warm-up for the runs below)
    ref = build(64)
    for _ in range(n_req):
        ref.submit(prompt, max_new=max_new)
    ref.run()
    want = [r.out for r in ref.finished]
    assert all(o == stream[:eos_at + 1] for o in want)

    # the reservation engine refuses the very first arrival: 4 pages can
    # never be carved out of 3
    reserve = build(n_pages)
    for _ in range(n_req):
        reserve.submit(prompt, max_new=max_new)
    try:
        reserve.run()
        raise AssertionError(
            "reservation admission accepted a request it cannot ever "
            "seat — never-fits pricing is broken")
    except ValueError as e:
        assert "can never be admitted" in str(e), e
    print("reservation engine: never-fits ValueError at admission (as "
          "designed)")

    # over-commit on the same tiny pool: admit on prompt pages, preempt
    # on decode growth, complete the whole burst
    oc = build(n_pages,
               admission=OvercommitAdmission(cfg, chunk_size=args.chunk))
    for _ in range(n_req):
        oc.submit(prompt, max_new=max_new)
    t0 = time.time()
    done = oc.run(max_ticks=50_000)
    wall = time.time() - t0
    s = oc.stats()
    toks = sum(len(r.out) for r in done)
    completion = len(done) / n_req

    print(f"\n{'engine':12s} {'done':>5s} {'preempt':>8s} "
          f"{'restores':>9s} {'evicted_MB':>11s} {'p99_ttft':>9s} "
          f"{'tok/s':>8s}")
    print(f"{'overcommit':12s} {len(done):5d} {s['preemptions']:8.0f} "
          f"{s['restores']:9.0f} "
          f"{s['evicted_bytes_total'] / 1e6:11.2f} "
          f"{s['p99_ttft_s']:9.3f} {toks / max(wall, 1e-9):8.1f}")

    assert completion == 1.0, (
        f"over-commit completed only {len(done)}/{n_req} requests")
    assert [r.out for r in sorted(done, key=lambda r: r.rid)] == want, (
        "preempted stream diverged from the roomy-pool reference")
    assert s["preemptions"] >= 1, (
        "the over-subscribed burst must preempt at least once")
    assert s["restores"] == s["preemptions"]
    assert s["pages_in_use"] == 0, "pages leaked across preempt/restore"
    p99_ttft_ceiling_s = 120.0
    assert s["p99_ttft_s"] <= p99_ttft_ceiling_s, (
        f"p99 TTFT {s['p99_ttft_s']:.1f}s: the preempt/restore detour "
        "is starving requests")

    out_path = write_bench_artifact(
        os.path.abspath("BENCH_preempt.json"),
        bench="serving_preempt",
        config={
            "model": cfg.name, "requests": n_req, "chunk": args.chunk,
            "max_seq": max_seq, "seed": args.seed,
            "page_size": page_size, "n_pages": n_pages,
            "prompt_len": len(prompt), "max_new": max_new,
            "eos_id": int(eos_id), "eos_at": int(eos_at),
        },
        metrics=dict(_finite_scalars(s), wall_s=wall,
                     completion_ratio=completion,
                     tok_per_s=toks / max(wall, 1e-9)),
        gates={
            "completion_ratio_min": 1.0,
            "preemptions_min": 1,
            "p99_ttft_s_max": p99_ttft_ceiling_s,
            "reservation_never_fits_raises": True,
        })
    print(f"wrote {out_path}")
    print("SERVING_BENCH_PREEMPT_OK")


def run_hybrid_part(args) -> None:
    """Part "hybrid": the windowed/recurrent stack through the universal
    chunked path vs the seed replay engine (PR-5 tick-reduction gate)."""
    cfg = get_config("recurrentgemma-9b").reduced()
    max_seq = args.max_seq
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=max_seq)
    rng = np.random.default_rng(args.seed)
    prompts = build_workload(rng, args.requests, cfg.vocab_size)
    print(f"\nhybrid workload: {cfg.block_pattern} stack (window "
          f"{cfg.window}), {args.requests} requests, prompt lengths "
          f"{sorted(len(p) for p in prompts)}, {args.max_new} new tokens, "
          f"{args.slots} slots, chunk={args.chunk}")

    rows = {
        mode: run_mode(cfg, params, prompts, mode=mode, chunk=args.chunk,
                       slots=args.slots, max_new=args.max_new,
                       max_seq=max_seq)
        for mode in ("replay", "chunked")
    }
    print(f"\n{'mode':10s} {'ttft_ms':>9s} {'ticks':>6s} {'calls':>6s} "
          f"{'prefill':>8s}")
    for mode, r in rows.items():
        print(f"{mode:10s} {r['ttft_s']*1e3:9.2f} {r['ticks']:6d} "
              f"{r['model_calls']:6d} {r['prefill_calls']:8d}")

    expected_prefill = sum(math.ceil(len(p) / args.chunk) for p in prompts)
    tick_gain = rows["replay"]["ticks"] / max(rows["chunked"]["ticks"], 1)
    print(f"\nchunked == replay tokens: "
          f"{rows['chunked']['outs'] == rows['replay']['outs']}")
    print(f"tick reduction: {tick_gain:.2f}x "
          f"({rows['replay']['ticks']} -> {rows['chunked']['ticks']})")
    assert rows["chunked"]["outs"] == rows["replay"]["outs"], (
        "the universal chunked path changed the hybrid greedy stream")
    assert rows["chunked"]["prefill_calls"] == expected_prefill, (
        rows["chunked"]["prefill_calls"], expected_prefill)
    assert tick_gain >= 2.0, (
        "chunked prefill must cut >= 2x the ticks replay spends on the "
        f"windowed/recurrent mixed-length workload (got {tick_gain:.2f}x)")

    # -- per-kind paged layout: a MIXED stack (global attention beside a
    # rotating window and a recurrent layer) pages its attn layers and
    # links shared prompt pages — a saving that was structurally 0 when
    # paged refused every hybrid stack
    import dataclasses
    import os

    mixed = dataclasses.replace(
        cfg, name="hybrid-mixed-reduced",
        block_pattern=("attn", "local_attn", "rglru"))
    mparams = lm.init(mixed, jax.random.PRNGKey(0), max_seq=max_seq)
    shared = build_shared_workload(rng, args.requests, mixed.vocab_size,
                                   args.sys_len)
    print(f"\nmixed-stack shared-prefix workload: {mixed.block_pattern}, "
          f"{args.requests} requests, {args.sys_len}-token system prompt, "
          f"page_size={args.page_size}")
    variants = {
        "stacked": dict(kv_layout="stacked"),
        "paged": dict(kv_layout="paged", prefix_sharing=False),
        "paged+share": dict(kv_layout="paged", prefix_sharing=True),
    }
    srows = {
        name: run_mode(mixed, mparams, shared, mode="chunked",
                       chunk=args.chunk, slots=args.slots,
                       max_new=args.max_new, max_seq=max_seq,
                       page_size=args.page_size, **kw)
        for name, kw in variants.items()
    }
    print(f"\n{'layout':12s} {'ttft_ms':>9s} {'pages':>6s} {'hits':>6s}")
    for name, r in srows.items():
        print(f"{name:12s} {r['ttft_s']*1e3:9.2f} {r['pages']:6d} "
              f"{r['hit_pages']:6d}")
    souts = [r["outs"] for r in srows.values()]
    assert souts[0] == souts[1] == souts[2], (
        "per-kind KV layout changed the mixed stack's greedy stream")
    assert srows["paged+share"]["hit_pages"] > 0, (
        "a mixed stack must link shared prompt pages (previously 0)")
    saved = 1 - srows["paged+share"]["pages"] / max(srows["paged"]["pages"],
                                                    1)
    print(f"mixed-stack pages saved vs no-sharing paged: {saved:.1%}")
    assert saved >= 0.30, (
        "per-kind prefix sharing must allocate >=30% fewer attn pages on "
        f"the shared-system-prompt workload (got {saved:.1%})")

    out_path = write_bench_artifact(
        os.path.abspath("BENCH_hybrid.json"),
        bench="serving_hybrid",
        config={
            "windowed_model": cfg.name, "mixed_pattern": mixed.block_pattern,
            "requests": args.requests, "chunk": args.chunk,
            "slots": args.slots, "max_new": args.max_new,
            "max_seq": max_seq, "sys_len": args.sys_len,
            "page_size": args.page_size, "seed": args.seed,
        },
        metrics={
            "windowed": {m: _finite_scalars(r) for m, r in rows.items()},
            "mixed_shared_prefix": {m: _finite_scalars(r)
                                    for m, r in srows.items()},
            "tick_gain": tick_gain,
            "mixed_pages_saved_frac": saved,
        },
        gates={
            "tick_gain_min": 2.0,
            "mixed_pages_saved_frac_min": 0.30,
        })
    print(f"wrote {out_path}")
    print("SERVING_BENCH_HYBRID_OK")


def run_distributed_part(args) -> None:
    """Part 3: the mixed-length workload over a 4-shard device mesh.

    With ``--spec`` both engines run speculative decode (n-gram
    self-drafting, ``k=--spec-k``) and a few repetitive prompts join the
    stream so acceptance actually engages; the distributed spec stream
    must stay token-identical to ``ServeEngine(spec=...)``.
    """
    import json
    import os

    from repro.serving.distributed import DistributedServeEngine
    from repro.serving.speculative import SpecConfig

    n_shards = min(4, len(jax.devices()))
    assert n_shards >= 2, "distributed part needs forced multi-device"
    cfg = get_config("gpt2-345m").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    # transfer overlap is a steady-state property (the paper's "fully
    # utilized" claim presumes sustained traffic): run a 2x stream of the
    # mixed-length workload so the pipelined middle — not the fill/drain
    # boundaries, where nothing can hide a transfer — dominates
    n_req = 2 * args.requests
    prompts = build_workload(rng, n_req, cfg.vocab_size)
    spec = SpecConfig(k=args.spec_k) if args.spec else None
    if args.spec:
        # one prompt per pattern (distinct), so the n-gram proposer has
        # real accepts while the mixed majority keeps decode phases long
        prompts += build_repetitive_workload(rng, 3, cfg.vocab_size)
    print(f"\ndistributed workload: sustained stream of {len(prompts)} "
          f"requests over {n_shards} KV-pool shards, prompt lengths "
          f"{sorted(len(p) for p in prompts)}, {args.max_new} new tokens"
          + (f", spec k={args.spec_k}" if args.spec else ""))

    base = run_mode(cfg, params, prompts, mode="chunked", chunk=args.chunk,
                    slots=args.slots, max_new=args.max_new,
                    max_seq=args.max_seq, page_size=args.page_size,
                    spec=spec)

    eng = DistributedServeEngine(
        cfg, params, n_shards=n_shards, slots_per_shard=1,
        max_seq=args.max_seq, eos_id=-1, chunk_size=args.chunk,
        page_size=args.page_size, spec=spec,
        telemetry=Telemetry(trace=True))
    eng.submit(list(range(1, args.chunk + 2)), max_new=2)  # warm the jits
    eng.run()
    warm = len(eng.finished)
    # measure the workload only (ticks, calls, utilization, overlap), as
    # run_mode does for the single-device baseline; reset_counters also
    # clears the trace, so the dumped timeline covers exactly the ticks
    # the transfer counters aggregate
    eng.reset_counters()
    for p in prompts:
        eng.submit(p, max_new=args.max_new)
    t0 = time.time()
    eng.run()
    wall = time.time() - t0
    done = eng.finished[warm:]
    outs = {tuple(r.prompt): r.out for r in done}
    toks = sum(len(r.out) for r in done)
    s = eng.stats()
    util = eng.utilization()
    drain = s.get("overlap_ratio_drain", 1.0)

    print(f"\n{'engine':14s} {'ticks':>6s} {'calls':>6s} {'tok/s':>8s}")
    print(f"{'single-device':14s} {base['ticks']:6d} "
          f"{base['model_calls']:6d} {base['tok_per_s']:8.1f}")
    print(f"{'distributed':14s} {s['ticks']:6d} {s['model_calls']:6d} "
          f"{toks / max(wall, 1e-9):8.1f}")
    print(f"\nper-device utilization: {np.round(util, 2).tolist()} "
          f"(mean {np.mean(util):.2f})")
    print(f"tick latency: p50 {s['tick_p50_ms']:.1f}ms / "
          f"p99 {s['tick_p99_ms']:.1f}ms over {s['ticks']} ticks")
    print(f"request latency: TTFT p50 {s['p50_ttft_s']*1e3:.1f}ms / "
          f"p99 {s['p99_ttft_s']*1e3:.1f}ms, TPOT p50 "
          f"{s['p50_tpot_s']*1e3:.1f}ms / p99 {s['p99_tpot_s']*1e3:.1f}ms "
          f"over {s['requests']} requests")
    print(f"wave occupancy: mean {s['wave_occupancy_mean']:.2f} slots/"
          f"dispatch, imbalance {s['wave_imbalance']:.2f}")
    print(f"transfers: {s['transfers']} total, {s['transfers_hidden']} "
          f"hidden behind compute, largest {s['max_transfer_bytes']}B "
          "(metadata/logits only — K/V pages never move)")
    print(f"transfer-overlap ratio: {s['overlap_ratio']:.2f} "
          f"(bytes: {s['byte_overlap_ratio']:.2f}; "
          f"prefill phase {s.get('overlap_ratio_prefill', 1.0):.2f}, "
          f"pure-decode drain {drain:.2f})")
    if args.spec:
        print(f"speculative: acceptance {s['acceptance_rate']:.2f}, "
              f"{s['tokens_per_verify_call']:.2f} tokens/verify over "
              f"{s['spec_ticks']} verify dispatches")

    # -- the dumped timeline must agree with the aggregate counters -----
    # every exposed transfer the scheduler counted is one visible
    # unoverlapped span on the trace's transfer track (reset_counters
    # cleared both at the same boundary, so the sets are comparable)
    trace_path = os.path.abspath(
        f"TRACE_dist{'_spec' if args.spec else ''}.json")
    eng.dump_trace(trace_path)
    with open(trace_path) as f:
        trace = json.load(f)
    counts = validate_chrome_trace(trace)
    exposed_spans = sum(
        1 for ev in trace["traceEvents"]
        if ev.get("ph") == "X" and ev.get("cat") == "transfer.exposed")
    hidden_spans = sum(
        1 for ev in trace["traceEvents"]
        if ev.get("ph") == "X" and ev.get("cat") == "transfer.hidden")
    print(f"trace: {sum(counts.values())} events -> {trace_path} "
          f"({hidden_spans} hidden + {exposed_spans} exposed transfer "
          "spans)")
    assert exposed_spans == s["transfers_exposed"], (
        "trace/counter divergence: every exposed transfer must be a "
        f"visible unoverlapped span ({exposed_spans} spans vs "
        f"{s['transfers_exposed']} counted)")
    assert hidden_spans == s["transfers_hidden"], (
        f"{hidden_spans} hidden spans vs {s['transfers_hidden']} counted")

    # p50/p99 TTFT/TPOT come from the shared registry's histograms, not
    # per-benchmark list math
    assert s["requests"] == len(prompts), (s["requests"], len(prompts))
    for k in ("p50_ttft_s", "p99_ttft_s", "p50_tpot_s", "p99_tpot_s"):
        assert s[k] > 0, f"{k} must be positive with completed requests"
    assert s["p50_ttft_s"] <= s["p99_ttft_s"]
    assert s["p50_tpot_s"] <= s["p99_tpot_s"]

    metrics = {
        k: s[k] for k in sorted(s)
        if isinstance(s[k], (int, float)) and np.isfinite(s[k])
    }
    metrics["tok_per_s"] = toks / max(wall, 1e-9)
    # -- optional wave-count sweep: per-wave batch size vs overlap ------
    # more waves means smaller per-wave dispatches (B/n_waves rows) but
    # more chances to shadow a transfer behind another wave's compute;
    # the sweep quantifies that trade without changing any stream
    extra_sweep = {}
    if getattr(args, "waves", 0) >= 2:
        sweep_ns = [w for w in (2, 3, 4) if w <= args.waves]
        print(f"\nwave sweep: decode_waves in {sweep_ns}")
        print(f"{'waves':>5s} {'rows/dispatch':>14s} {'imbalance':>10s} "
              f"{'overlap':>8s} {'drain':>6s} {'tok/s':>8s}")
        for w in sweep_ns:
            weng = DistributedServeEngine(
                cfg, params, n_shards=n_shards, slots_per_shard=1,
                max_seq=args.max_seq, eos_id=-1, chunk_size=args.chunk,
                page_size=args.page_size, spec=spec, decode_waves=w)
            weng.submit(list(range(1, args.chunk + 2)), max_new=2)
            weng.run()
            wwarm = len(weng.finished)
            weng.reset_counters()
            for p in prompts:
                weng.submit(p, max_new=args.max_new)
            wt0 = time.time()
            weng.run()
            wwall = time.time() - wt0
            wdone = weng.finished[wwarm:]
            ws = weng.stats()
            wtoks = sum(len(r.out) for r in wdone)
            row = {
                "wave_occupancy_mean": ws["wave_occupancy_mean"],
                "wave_imbalance": ws["wave_imbalance"],
                "overlap_ratio": ws["overlap_ratio"],
                "overlap_ratio_drain": ws.get("overlap_ratio_drain", 1.0),
                "byte_overlap_ratio": ws["byte_overlap_ratio"],
                "tok_per_s": wtoks / max(wwall, 1e-9),
            }
            extra_sweep[f"waves{w}"] = row
            print(f"{w:5d} {row['wave_occupancy_mean']:14.2f} "
                  f"{row['wave_imbalance']:10.2f} "
                  f"{row['overlap_ratio']:8.2f} "
                  f"{row['overlap_ratio_drain']:6.2f} "
                  f"{row['tok_per_s']:8.1f}")
            assert {tuple(r.prompt): r.out for r in wdone} == outs, (
                f"decode_waves={w} changed the generated stream")
        metrics["waves_sweep"] = extra_sweep

    out_path = write_bench_artifact(
        os.path.abspath(f"BENCH_dist{'_spec' if args.spec else ''}.json"),
        bench="serving_dist",
        config={
            "model": cfg.name, "n_shards": n_shards, "slots_per_shard": 1,
            "decode_waves": int(s["decode_waves"]),
            "requests": len(prompts), "chunk": args.chunk,
            "max_new": args.max_new, "max_seq": args.max_seq,
            "page_size": args.page_size, "seed": args.seed,
            "spec_k": args.spec_k if args.spec else None,
        },
        metrics=metrics,
        gates={
            "overlap_ratio_min": 0.85,
            "overlap_ratio_drain_min": 0.85,
        },
        extra={
            "baseline_single_device": {
                "ticks": base["ticks"], "model_calls": base["model_calls"],
                "tok_per_s": base["tok_per_s"],
            },
            "trace": {"path": trace_path,
                      "events": {k: counts[k] for k in sorted(counts)}},
        })
    print(f"wrote {out_path}")

    assert outs == base["outs"], (
        "distributed engine changed the generated stream")
    assert s["overlap_ratio"] >= 0.85, (
        "the dual-wave tick must hide >= 85% of transfers behind compute "
        f"(got {s['overlap_ratio']:.2f})")
    assert drain >= 0.85, (
        "pure-decode drain ticks must stay dual-stream-shadowed "
        f"(drain overlap {drain:.2f} < 0.85)")
    if args.spec:
        assert s["spec_accepted"] > 0, "no draft token was ever accepted"
        assert s["spec_emitted"] > s["spec_ticks"], (
            "speculation emitted no more than one token per verify")
    print("SERVING_BENCH_DIST_OK")


def spawn_distributed_part(args) -> None:
    """Re-exec part 3 under forced 4-device XLA_FLAGS (pinned to the CPU
    backend — forcing host devices has no effect on a GPU/TPU default
    backend — with a recursion guard so a spawn that still ends up
    single-device fails instead of forking forever)."""
    import os
    import subprocess

    assert not os.environ.get("_SERVING_BENCH_DIST_CHILD"), (
        "forced 4-device child still saw < 2 devices; cannot run the "
        "distributed part on this host")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["_SERVING_BENCH_DIST_CHILD"] = "1"
    cmd = [sys.executable, os.path.abspath(__file__), "--part", "dist",
           "--requests", str(args.requests), "--chunk", str(args.chunk),
           "--slots", str(args.slots), "--max-new", str(args.max_new),
           "--max-seq", str(args.max_seq), "--seed", str(args.seed),
           "--page-size", str(args.page_size),
           "--spec-k", str(args.spec_k)]
    if args.spec:
        cmd.append("--spec")
    if getattr(args, "waves", 0):
        cmd += ["--waves", str(args.waves)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=900)
    print(proc.stdout, end="")
    if proc.returncode != 0:
        print(proc.stderr, file=sys.stderr)
        raise SystemExit(proc.returncode)


def distributed_part(args) -> None:
    """Part 3 in this process on >= 2 devices.  A one-device CPU backend
    re-runs it in a child with four forced CPU devices; a one-device
    accelerator fails, since the child could not share the chip this
    process holds and a CPU child would time the wrong device."""
    if len(jax.devices()) >= 2:
        run_distributed_part(args)
    elif jax.default_backend() == "cpu":
        spawn_distributed_part(args)
    else:
        raise SystemExit(
            f"--part dist needs >= 2 {jax.default_backend()} devices; "
            f"found {len(jax.devices())}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sys-len", type=int, default=96)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--spec-k", type=int, default=6)
    ap.add_argument("--spec", action="store_true",
                    help="run --part dist with speculative decoding on "
                    "both engines (distributed spec must match "
                    "single-device spec token-for-token)")
    ap.add_argument("--tree", action="store_true",
                    help="run --part spec as the token-tree gate: "
                    "branchy drafting vs the linear chain at equal "
                    "verify width (writes BENCH_tree_spec.json)")
    ap.add_argument("--waves", type=int, default=0,
                    help="with --part dist: also sweep decode_waves "
                    "over 2..N, reporting per-wave batch size vs "
                    "transfer overlap (folded into the BENCH artifact)")
    ap.add_argument("--part",
                    choices=("all", "core", "dist", "spec", "hybrid",
                             "preempt"),
                    default="all")
    args = ap.parse_args()
    enable_compile_cache()

    if args.part == "dist":
        distributed_part(args)
        return
    if args.part == "spec":
        (run_tree_spec_part if args.tree else run_spec_part)(args)
        return
    if args.part == "hybrid":
        run_hybrid_part(args)
        return
    if args.part == "preempt":
        run_preempt_part(args)
        return

    cfg = get_config("gpt2-345m").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    prompts = build_workload(rng, args.requests, cfg.vocab_size)
    plens = sorted(len(p) for p in prompts)
    print(f"workload: {args.requests} requests, prompt lengths {plens}, "
          f"{args.max_new} new tokens each, {args.slots} slots, "
          f"chunk={args.chunk}")

    rows = {}
    for mode in ("replay", "chunked"):
        rows[mode] = run_mode(
            cfg, params, prompts, mode=mode, chunk=args.chunk,
            slots=args.slots, max_new=args.max_new, max_seq=args.max_seq)

    print(f"\n{'mode':10s} {'ttft_ms':>9s} {'tpot_ms':>9s} {'ticks':>6s} "
          f"{'calls':>6s} {'prefill':>8s} {'tok/s':>8s}")
    for mode, r in rows.items():
        print(f"{mode:10s} {r['ttft_s']*1e3:9.2f} {r['tpot_s']*1e3:9.2f} "
              f"{r['ticks']:6d} {r['model_calls']:6d} "
              f"{r['prefill_calls']:8d} {r['tok_per_s']:8.1f}")

    same = rows["chunked"]["outs"] == rows["replay"]["outs"]
    ttft_gain = rows["replay"]["ttft_s"] / max(rows["chunked"]["ttft_s"],
                                               1e-12)
    tick_gain = rows["replay"]["ticks"] / max(rows["chunked"]["ticks"], 1)
    expected_prefill = sum(math.ceil(len(p) / args.chunk) for p in prompts)
    print(f"\nchunked == replay tokens: {same}")
    print(f"TTFT speedup:  {ttft_gain:.2f}x")
    print(f"tick reduction: {tick_gain:.2f}x "
          f"({rows['replay']['ticks']} -> {rows['chunked']['ticks']})")
    print(f"prefill calls: {rows['chunked']['prefill_calls']} "
          f"(= sum ceil(P/chunk) = {expected_prefill})")
    assert same, "chunked admission changed the generated stream"
    assert rows["chunked"]["prefill_calls"] == expected_prefill
    assert rows["chunked"]["ticks"] < rows["replay"]["ticks"]
    assert rows["chunked"]["ttft_s"] < rows["replay"]["ttft_s"]

    # -- part 2: shared-system-prompt fleet through the paged KV cache --
    shared = build_shared_workload(rng, args.requests, cfg.vocab_size,
                                   args.sys_len)
    print(f"\nshared-prefix workload: {args.requests} requests, "
          f"{args.sys_len}-token system prompt, tails "
          f"{sorted(len(p) - args.sys_len for p in shared)}, "
          f"page_size={args.page_size}")
    variants = {
        "stacked": dict(kv_layout="stacked"),
        "paged": dict(kv_layout="paged", prefix_sharing=False),
        "paged+share": dict(kv_layout="paged", prefix_sharing=True),
    }
    srows = {
        name: run_mode(cfg, params, shared, mode="chunked",
                       chunk=args.chunk, slots=args.slots,
                       max_new=args.max_new, max_seq=args.max_seq,
                       page_size=args.page_size, **kw)
        for name, kw in variants.items()
    }
    print(f"\n{'layout':12s} {'ttft_ms':>9s} {'pages':>6s} {'hits':>6s} "
          f"{'hit_rate':>9s}")
    for name, r in srows.items():
        linked = r["pages"] + r["hit_pages"]
        rate = r["hit_pages"] / linked if linked else 0.0
        print(f"{name:12s} {r['ttft_s']*1e3:9.2f} {r['pages']:6d} "
              f"{r['hit_pages']:6d} {rate:9.1%}")

    outs = [r["outs"] for r in srows.values()]
    assert outs[0] == outs[1] == outs[2], (
        "KV layout changed the generated stream")
    saved = 1 - srows["paged+share"]["pages"] / max(srows["paged"]["pages"],
                                                    1)
    print(f"\nshared-prefix pages saved vs no-sharing paged: {saved:.1%}")
    assert saved >= 0.30, (
        "prefix sharing must allocate >=30% fewer pages on the "
        f"shared-system-prompt workload (got {saved:.1%})")

    # -- trace smoke: the single-device engine's recorded timeline ------
    import json
    import os
    eng = ServeEngine(cfg, params, batch_slots=args.slots,
                      max_seq=args.max_seq, eos_id=-1,
                      chunk_size=args.chunk,
                      telemetry=Telemetry(trace=True))
    for p in prompts[:3]:
        eng.submit(list(p), max_new=4)
    eng.run()
    s = eng.stats()
    for k in ("p50_ttft_s", "p99_ttft_s", "p50_tpot_s", "p99_tpot_s"):
        assert s[k] > 0, f"{k} must be positive with completed requests"
    trace_path = os.path.abspath("TRACE_core.json")
    eng.dump_trace(trace_path)
    with open(trace_path) as f:
        counts = validate_chrome_trace(json.load(f))
    assert counts.get("X", 0) > 0 and counts.get("b", 0) == counts.get(
        "e", 0) > 0, counts
    print(f"trace smoke: {sum(counts.values())} events -> {trace_path} "
          f"(TTFT p50 {s['p50_ttft_s']*1e3:.1f}ms / "
          f"p99 {s['p99_ttft_s']*1e3:.1f}ms)")
    print("SERVING_BENCH_OK")

    # -- part "spec": speculative decode vs plain on repetitive text --
    if args.part == "all":
        run_spec_part(args)

    # -- part "hybrid": windowed/recurrent stack, chunked vs replay --
    if args.part == "all":
        run_hybrid_part(args)

    # -- part "preempt": over-commit admission vs reservation pricing --
    if args.part == "all":
        run_preempt_part(args)

    # -- part 3: distributed engine, transfer overlap vs single device --
    if args.part == "all":
        distributed_part(args)


if __name__ == "__main__":
    main()
