"""Scheduler-driven continuous-batching serving core.

The engine is organised the way the paper organises the accelerator
(Fig 1 / Fig 4c): a fixed pool of cache slots executes batched decode
every tick, and *admission work rides along without stalling it*.

  * **Chunked prefill** — an admitted prompt is written into its slot's KV
    cache ``chunk_size`` tokens at a time through
    :func:`repro.models.lm.prefill_into_slot` (one forward call per chunk),
    so a P-token prompt costs ``ceil(P / chunk_size)`` model calls instead
    of P decode ticks.  The per-tick prefill token budget comes from
    :mod:`repro.serving.admission`, which prices one decode tick against
    the analytic stage program (``core/scheduler.model_program`` via
    ``core/perfmodel.py``) — the temporal-reuse analogue of the paper's
    hidden ring transmissions.
  * **Paged KV cache** — by default (``kv_layout="auto"``) every stack
    with at least one global-attention layer stores that K/V in
    :class:`repro.serving.kv_cache.PagedCacheManager`'s page pool:
    page-granular alloc/free through per-request block tables, admission
    priced in pages (``FIFOAdmission.page_price``; mixed stacks max it
    against the slot cost, ``FIFOAdmission.combined_price``) instead of
    whole slots, and copy-free prefix sharing of full prompt pages
    between requests with a common prompt prefix.  The layout is *per
    kind*: a mixed stack's rotating-window rings and recurrent states
    stay slot-resident beside the page pool, so hybrid stacks page too
    (their sharing saves pages, not prefill compute — see
    ``PagedCacheManager.alloc``).  ``kv_layout="stacked"`` keeps the
    contiguous per-slot layout; both produce bit-exact identical tokens
    (asserted in ``tests/test_paged_kv.py`` and
    ``tests/test_hybrid_serving.py``).
  * **Slot management** — allocation, free, and per-slot length accounting
    live behind the manager seam (alloc/free/advance/lengths); freeing is
    mask-only (lengths gate attention; pages additionally refcounted), so
    slot reuse needs no cache surgery.
  * **Per-request sampling** — every request carries a
    :class:`repro.serving.sampler.SamplingParams`; the engine packs them
    into per-slot arrays and one jitted ``sample_batch`` serves the whole
    heterogeneous batch.
  * **Speculative decoding** — with ``spec=SpecConfig(...)`` each decode
    tick proposes up to k draft tokens per slot
    (:mod:`repro.serving.speculative`: self-drafting n-gram lookup or a
    small draft model), verifies every slot's draft in ONE chunked
    forward call (:func:`repro.models.lm.verify_chunk` — the same
    ride-along economics as chunked prefill: decode streams every weight
    through the MDK pipeline anyway), and emits 1..k+1 tokens via the
    distribution-preserving accept/reject rule in
    ``sampler.spec_accept_batch``.  Greedy streams are token-for-token
    identical to plain decode; rejected-draft K/V are discarded by
    ``kv.rewind`` (mask-only on slots, refcounted page release on pages).
  * **Ring-TP** — an optional ``mesh=`` routes the dense matmuls through
    :func:`repro.core.ring.tp_matmul` (the collective-matmul schedule that
    hides synchronisation inside block matmuls).
  * **Quantized serving** — W8A8 via SmoothQuant; the quantized engine runs
    its inter-kernel activation stream in f32, matching the paper's
    shared-buffer precision (activations quantize at each MP kernel's
    input, not between kernels).

The chunked forward body is universal across block kinds
(:func:`repro.models.blocks.block_apply_chunk`): global attention writes
at absolute offsets, rotating windows write ``pos % W`` ring slots, and
recurrent kinds thread their carried state through an intra-chunk scan —
so ``prefill_mode="auto"`` selects the chunked path for *every*
decoder-only stack, hybrid recurrentgemma/xlstm-style configs included.
Speculative decoding covers them too: stacks with rings or carried state
verify with per-row ``valids`` and commit through the
:class:`repro.serving.kv_cache.StateStore` rewind seam (restore rejected
ring writes from the verify-base snapshot, select each recurrent state
off the verify trajectory).  The seed's one-token-per-tick replay engine
survives only as an explicit A/B debug mode (``prefill_mode="replay"``,
the ``benchmarks/serving_bench.py`` baseline); the whisper
encoder-decoder — whose cross-attention sub-block has no chunk path — is
the one config ``auto`` still replays.

Window-capped stacks (no global ``attn`` layer: every layer prices at
``min(len, W)`` slots or O(1) state — ``FIFOAdmission.slot_price``) lose
the ``max_seq`` admission ceiling entirely: prompts longer than the
cache are admitted and served from the same fixed-size slots.

Per-request accounting records TTFT (submit -> first token) and TPOT
(steady-state decode latency); ``mdk_stats`` exposes the temporal-reuse
counters for the Fig 3(c) argument.

**Telemetry** — every schedule counter and latency aggregate is backed
by :mod:`repro.serving.telemetry` (one registry per engine: counters
via :func:`~repro.serving.telemetry.registry_counter` descriptors,
TTFT/TPOT/tick-wall as fixed-bucket histograms, so ``stats()`` reports
p50/p99 next to the means).  Constructing the engine with
``telemetry=Telemetry(trace=True)`` additionally records a span
timeline — tick/stage spans (admit, prefill chunk, decode step, sample,
emit), request lifecycle events, speculative propose/verify/accept
phases — exportable with :meth:`ServeEngine.dump_trace` as
Chrome/Perfetto JSON; ``annotate=True`` puts each span on a device
profile's host plane too, beside the step programs named in
:data:`PROGRAMS`.  The default recorder is a no-op: tracing disabled
adds zero per-tick allocations and no device syncs.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import scheduler as sched
from repro.models import blocks, lm
from repro.models.layers import tp_context
from repro.serving import sampler as samplers, speculative
from repro.serving.admission import FIFOAdmission
from repro.serving.kv_cache import PagedCacheManager, SlotCacheManager
# the request state machine, admission/seating/emission/preemption
# bookkeeping, and the shared run-loop helpers all live in the lifecycle
# core; the names are re-exported here because tests, benchmarks, and
# the distributed engine historically import them from this module
from repro.serving.lifecycle import (  # noqa: F401  (re-exported API)
    DECODE, PREFILL, LifecycleMixin, Request, _fmt_rids, drain_engine,
    latency_stats, submit_request)
from repro.serving.quantize import calibrate, quantize_model_params
from repro.serving.telemetry import (
    TID_ENGINE, Telemetry, linear_edges, registry_counter)

#: the name of each jitted program; a device profile shows program
#: ``name`` as ``jit_<name>(<fingerprint>)``
PROGRAMS = {"decode": "decode_step", "prefill": "prefill_chunk",
            "sample": "sample_batch", "verify": "verify_chunk",
            "verify_tree": "verify_tree", "compact": "compact_pages"}


class ServeEngine(LifecycleMixin):
    # schedule counters live in the telemetry registry (the single
    # backing store stats() reads and reset() zeroes); the descriptor
    # keeps the attribute spelling, so hot paths still write
    # ``self.ticks += 1``
    ticks = registry_counter("ticks")
    model_calls = registry_counter("model_calls")
    prefill_calls = registry_counter("prefill_calls")
    stalled = registry_counter("stalled")
    spec_ticks = registry_counter("spec_ticks")
    spec_proposed = registry_counter("spec_proposed")
    spec_accepted = registry_counter("spec_accepted")
    spec_emitted = registry_counter("spec_emitted")
    verify_touched_positions = registry_counter("verify_touched_positions")
    verify_dense_positions = registry_counter("verify_dense_positions")
    sample_calls = registry_counter("sample_calls")
    sample_greedy_calls = registry_counter("sample_greedy_calls")

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        batch_slots: int = 4,
        max_seq: int = 256,
        eos_id: int = 0,
        quantized: bool = False,
        calibration_batches=None,
        seed: int = 0,
        chunk_size: int = 32,
        prefill_mode: str = "auto",  # auto | chunked | replay
        kv_layout: str = "auto",  # auto | paged | stacked
        page_size: int = 16,
        n_pages: Optional[int] = None,
        prefix_sharing: bool = True,
        admission: Optional[FIFOAdmission] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        act_dtype=None,
        spec: Optional[speculative.SpecConfig] = None,
        telemetry: Optional[Telemetry] = None,
    ):
        # the telemetry bundle must exist before any counter attribute is
        # assigned: the registry_counter descriptors dereference self.tel
        self.tel = telemetry or Telemetry()
        self.cfg = cfg
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.B = batch_slots
        self.chunk_size = min(chunk_size, max_seq)
        if quantized:
            stats = None
            if calibration_batches is not None:
                stats = calibrate(params, cfg, calibration_batches)
            params = quantize_model_params(params, cfg, stats)
        # shared-buffer precision: the W8A8 path re-quantizes activations at
        # every MP kernel input, so the stream between kernels stays f32
        # (bf16 there would stack a second rounding on top of int8 noise)
        self.act_dtype = act_dtype or (jnp.float32 if quantized
                                       else jnp.bfloat16)
        self.params = params

        if prefill_mode == "auto":
            # the chunked body covers every block kind; only the whisper
            # encoder-decoder (no cross-attention chunk path) replays
            prefill_mode = ("chunked" if blocks.chunk_capable(cfg)
                            else "replay")
        if prefill_mode == "chunked" and not blocks.chunk_capable(cfg):
            # ValueError, not assert: the guard must survive python -O
            raise ValueError(
                f"{cfg.name} is encoder-decoder — cross-attention has no "
                "chunk path; serve it with prefill_mode='replay'")
        self.prefill_mode = prefill_mode
        self.admission = admission or FIFOAdmission(
            cfg, chunk_size=self.chunk_size)
        assert self.admission.chunk_size <= self.chunk_size, (
            "admission schedules chunks larger than the engine's "
            f"prefill buffer ({self.admission.chunk_size} > "
            f"{self.chunk_size})")
        # lifecycle bookkeeping (preemption/restore/cancel counters and
        # the over-commit flag mirrored off the admission policy)
        self._init_lifecycle()
        # price a probe request one position past the cache: a stack whose
        # per-layer slot footprint saturates below max_seq — rotating
        # windows at W, recurrent state at O(1); admission.slot_price is
        # the formula — admits prompts of ANY length into fixed-size
        # slots, so the request-length ceiling is lifted.  A learned
        # position table is itself a max_seq-wide absolute buffer and
        # keeps the ceiling regardless of the block pattern.
        probe = self.admission.slot_price(
            cfg, max_seq + 1, 0, max_seq=max_seq + 1)
        self.seq_ceiling: Optional[int] = (
            None if probe <= max_seq and cfg.pos != "learned" else max_seq)

        if kv_layout == "auto":
            # per-kind cache layouts: any stack with at least one global-
            # attention layer pages (mixed stacks keep rings/recurrent
            # states slot-resident beside the page pool); auto still
            # requires a page size that divides max_seq (bit-exactness
            # invariant) rather than degrade page_size
            kv_layout = (
                "paged"
                if blocks.paged_capable(cfg) and max_seq % page_size == 0
                else "stacked")
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        if self.paged:
            # a page size that divides max_seq keeps the gathered paged
            # view exactly the contiguous width (bit-exactness invariant);
            # reject a non-divisor (including page_size > max_seq) loudly
            # rather than substitute one
            if max_seq % page_size:
                raise ValueError(
                    f"page_size={page_size} must divide max_seq={max_seq} "
                    "(pass page_size explicitly or pick a page-multiple "
                    "max_seq)")
            self.kv = PagedCacheManager(
                cfg, batch_slots, max_seq, page_size=page_size,
                n_pages=n_pages, prefix_sharing=prefix_sharing,
                overcommit=self.overcommit,
                watermark=getattr(self.admission, "watermark", 1.0))
        else:
            assert kv_layout == "stacked", kv_layout
            self.kv = SlotCacheManager(cfg, batch_slots, max_seq,
                                       bounded=self.seq_ceiling is not None)
        # sharing needs the chunked path: replay teacher-forces every prompt
        # token through decode, which cannot skip a shared prefix
        self._share = (self.paged and prefix_sharing
                       and self.prefill_mode == "chunked")
        self.cur_tok = np.zeros((batch_slots, 1), np.int32)
        self._temp = np.zeros((batch_slots,), np.float32)
        self._topk = np.zeros((batch_slots,), np.int32)
        self._topp = np.ones((batch_slots,), np.float32)
        self.rng = jax.random.PRNGKey(seed)

        def _traced(fn):
            if mesh is None:
                return fn

            @functools.wraps(fn)
            def wrapped(*args):
                with tp_context(mesh):
                    return fn(*args)

            return wrapped

        # each program is a named function (PROGRAMS), so a device
        # profile tells the steps apart by name
        if self.paged:
            # the paged step carries the really-decoding mask too: mixed
            # stacks keep slot-resident rings/states whose commits must
            # not fire for tag-along rows (pure-attn stacks ignore it —
            # their writes are length-masked either way)
            def decode_step(p, tok, cache, lengths, bt, active):
                return lm.decode_step(
                    p, cfg, tok, cache, lengths, active=active,
                    block_table=bt, dtype=self.act_dtype)

            # slot routes the slot-resident entries of a mixed stack; the
            # block-table row routes the paged attn writes
            def prefill_chunk(p, toks, cache, slot, bt_row, offset, valid):
                return lm.prefill_into_slot(
                    p, cfg, toks, cache, slot, offset, valid=valid,
                    block_table=bt_row, dtype=self.act_dtype)
        else:
            # the batched step takes the really-decoding row mask: rings
            # and recurrent states must not commit for tag-along rows
            # (mid-prefill or empty slots riding the fixed-shape call)
            def decode_step(p, tok, cache, lengths, active):
                return lm.decode_step(
                    p, cfg, tok, cache, lengths, active=active,
                    dtype=self.act_dtype)

            def prefill_chunk(p, toks, cache, slot, offset, valid):
                return lm.prefill_into_slot(
                    p, cfg, toks, cache, slot, offset, valid=valid,
                    dtype=self.act_dtype)
        self._step = jax.jit(_traced(decode_step))
        self._prefill = jax.jit(_traced(prefill_chunk))
        self._sample = jax.jit(samplers.sample_batch)

        self.spec = spec
        self.proposer: Optional[speculative.DraftProposer] = None
        self.adaptive: Optional[speculative.AdaptiveDraft] = None
        # hybrid stacks carry serving state with no length mask (rotating
        # rings, recurrent states): their speculative verify goes through
        # the StateStore rewind seam owned by the slot manager
        self._state_store = getattr(self.kv, "state", None)
        if spec is not None:
            if self.prefill_mode != "chunked":
                raise ValueError(
                    "speculative decoding needs the chunked path "
                    "(verification is a chunked forward call); this "
                    f"config prefills via {self.prefill_mode!r}")
            if spec.k < 1:
                raise ValueError(f"SpecConfig.k={spec.k} must be >= 1")
            if "local_attn" in cfg.block_pattern:
                W = min(cfg.window, max_seq)
                if spec.k + 1 > W:
                    raise ValueError(
                        f"SpecConfig.k={spec.k}: a verify writes k+1 ring "
                        f"positions but the rotating window holds {W} — "
                        "state rewind needs k+1 <= W so an accepted write "
                        "can never share a ring slot with a rejected one")
            self.proposer = speculative.make_proposer(
                spec, batch_slots, max_seq, chunk_size=self.chunk_size,
                dtype=self.act_dtype)
            self.adaptive = speculative.AdaptiveDraft.from_spec(spec)
            if self.paged and self._state_store is not None:
                # mixed paged: block tables route the attn writes AND the
                # slot-resident rings/states need valids + the trajectory
                # for their StateStore commit
                def verify_chunk(p, toks, cache, lens, valids, bts):
                    return lm.verify_chunk(
                        p, cfg, toks, cache, lens, valids=valids,
                        block_tables=bts, with_traj=True,
                        dtype=self.act_dtype)
            elif self.paged:
                def verify_chunk(p, toks, cache, lens, bts):
                    return lm.verify_chunk(
                        p, cfg, toks, cache, lens, block_tables=bts,
                        dtype=self.act_dtype)
            elif self._state_store is not None:
                # per-row valids bound ring writes / state commits; the
                # trajectory feeds StateStore.commit after accept/reject
                def verify_chunk(p, toks, cache, lens, valids):
                    return lm.verify_chunk(
                        p, cfg, toks, cache, lens, valids=valids,
                        with_traj=True, dtype=self.act_dtype)
            else:
                def verify_chunk(p, toks, cache, lens):
                    return lm.verify_chunk(
                        p, cfg, toks, cache, lens, dtype=self.act_dtype)
            self._verify = jax.jit(_traced(verify_chunk))
            self._accept = jax.jit(samplers.spec_accept_batch)
            if spec.tree:
                if spec.branch < 1:
                    raise ValueError(
                        f"SpecConfig.branch={spec.branch} must be >= 1")
                if not blocks.page_addressable(cfg):
                    raise ValueError(
                        "tree speculation forks K/V across sibling "
                        "branches, which only absolute-position attn "
                        "caches support — rings rotate and recurrent "
                        "states carry, neither can hold two candidate "
                        "futures at once.  This stack has kinds "
                        f"{sorted(set(cfg.block_pattern))}; use linear "
                        "speculation (tree=False) for hybrid stacks")
                # tree verify threads the per-row ancestor bitmask and
                # logical (root-path depth) positions; page_addressable
                # rules out the StateStore variants, so only the two
                # attn-cache shapes exist
                if self.paged:
                    def verify_tree(p, toks, cache, lens, bts, anc, dep):
                        return lm.verify_chunk(
                            p, cfg, toks, cache, lens, block_tables=bts,
                            anc=anc, depths=dep, dtype=self.act_dtype)

                    def compact_pages(cache, src, dst, bts):
                        return lm.compact_accepted_path(
                            cfg, cache, src, dst, block_tables=bts)
                else:
                    def verify_tree(p, toks, cache, lens, anc, dep):
                        return lm.verify_chunk(
                            p, cfg, toks, cache, lens, anc=anc,
                            depths=dep, dtype=self.act_dtype)

                    def compact_pages(cache, src, dst):
                        return lm.compact_accepted_path(
                            cfg, cache, src, dst)
                self._verify_tree = jax.jit(_traced(verify_tree))
                self._compact = jax.jit(compact_pages)
                self._accept_tree = jax.jit(samplers.spec_accept_tree)

        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.queue: deque = deque()
        self.finished: List[Request] = []
        self._next_rid = 0
        self.ticks = 0
        self.model_calls = 0  # decode steps + prefill chunks + verifies
        self.prefill_calls = 0
        self.stalled = 0  # unfinished requests when run() gave up
        self.spec_ticks = 0  # verify calls issued
        self.spec_proposed = 0  # draft tokens submitted for verification
        self.spec_accepted = 0  # draft tokens accepted
        self.spec_emitted = 0  # tokens emitted off verify calls
        # verify-path copy traffic, in K/V positions per layer: the
        # in-place paged verify touches each row's live pages only;
        # "dense" is what the retired _paged_view_batch gather/scatter
        # would have moved (a full max_seq view per active row, twice)
        self.verify_touched_positions = 0
        self.verify_dense_positions = 0
        self.mdk_stats = sched.mdk_stats(cfg)
        self.stalled_detail: Dict[str, List[int]] = {
            "queued": [], "in_flight": []}

        # telemetry: pre-create the latency histograms (hot paths record
        # through cached handles, no name lookup)
        reg = self.tel.registry
        self._h_ttft = reg.histogram("ttft_s")
        self._h_tpot = reg.histogram("tpot_s")
        self._h_tick = reg.histogram("tick_wall_s")
        self._h_accept = (
            reg.histogram("spec_accept_len",
                          edges=linear_edges(0.0, spec.k + 2, spec.k + 2))
            if spec is not None else None)
        if self.proposer is not None:
            self.proposer.tracer = self.tel.tracer

    # ------------------------------------------------------------------
    def submit(
        self,
        prompt: List[int],
        max_new: int = 32,
        sampling: Optional[samplers.SamplingParams] = None,
    ) -> int:
        return submit_request(self, prompt, max_new, sampling)

    def _sample_rows(self, logits: jax.Array) -> np.ndarray:
        return self._draw(logits, self._temp, self._topk, self._topp)

    def _sample_one(self, logits: jax.Array, req: Request) -> int:
        sp = req.sampling
        return int(self._draw(
            logits[None], np.asarray([sp.temperature], np.float32),
            np.asarray([sp.top_k], np.int32),
            np.asarray([sp.top_p], np.float32))[0])

    # the ``sample`` span covers the sampler dispatch and the host's
    # wait for its tokens; ``greedy`` says the batch took the argmax
    # branch of ``sample_batch``
    def _draw(self, logits: jax.Array, temp: np.ndarray, topk: np.ndarray,
              topp: np.ndarray) -> np.ndarray:
        greedy = samplers.all_greedy(temp)
        self.sample_calls += 1
        self.sample_greedy_calls += greedy
        tr = self.tel.tracer
        with tr.span("sample",
                     args={"greedy": int(greedy)} if tr.enabled else None):
            self.rng, sub = jax.random.split(self.rng)
            return np.asarray(self._sample(
                logits, sub, jnp.asarray(temp), jnp.asarray(topk),
                jnp.asarray(topp)))

    # ------------------------------------------------------------------
    def tick(self) -> None:
        """One engine tick: a prefill-chunk budget, then one decode step."""
        if self.prefill_mode == "replay":
            return self._tick_replay()
        t_tick = time.perf_counter()
        tr = self.tel.tracer
        with tr.span("tick", "engine"):
            with tr.span("admit"):
                self._admit()
            did = False

            # -- chunked prefill within this tick's token budget (FIFO) --
            # req.context, not req.prompt: a recompute-resume re-prefills
            # the synthetic ``prompt + out[:-1]`` context it lost
            prefilling = sorted(
                (r for r in self.slots
                 if r is not None and r.state == PREFILL),
                key=lambda r: r.rid)
            plan = self.admission.plan_chunks(
                [(r.slot, len(r.context), r.filled) for r in prefilling])
            for ch in plan:
                req = self.slots[ch.slot]
                if not self.kv.has_room(ch.slot, ch.n):
                    # a buggy admission plan (or a prompt that slipped
                    # past submit) would silently corrupt the slot's
                    # mask: the chunk writes past max_seq get dropped
                    # while the length accounting still advances.  Fail
                    # loudly instead.
                    raise ValueError(
                        f"prefill chunk ({ch.n} tokens at offset "
                        f"{ch.start}) overruns slot {ch.slot}'s cache "
                        f"(len={self.kv.length_of(ch.slot)}, "
                        f"max_seq={self.max_seq})")
                chunk = np.zeros((self.chunk_size,), np.int32)
                chunk[:ch.n] = req.context[ch.start:ch.start + ch.n]
                with tr.span(
                        "prefill.chunk", "stage", TID_ENGINE,
                        ({"rid": req.rid, "slot": ch.slot,
                          "start": ch.start, "n": ch.n}
                         if tr.enabled else None)):
                    if self.paged:
                        logits, self.kv.cache = self._prefill(
                            self.params, jnp.asarray(chunk),
                            self.kv.cache, ch.slot,
                            jnp.asarray(self.kv.block_tables[ch.slot]),
                            ch.start, ch.n)
                    else:
                        logits, self.kv.cache = self._prefill(
                            self.params, jnp.asarray(chunk),
                            self.kv.cache, ch.slot, ch.start, ch.n)
                self.model_calls += 1
                self.prefill_calls += 1
                req.filled += ch.n
                self.kv.advance(ch.slot, ch.n)
                if self.proposer is not None:
                    self.proposer.prefill_chunk(ch.slot, chunk, ch.start,
                                                ch.n)
                if req.filled == len(req.context):
                    # first generated token comes straight off the
                    # prefill logits — this is the TTFT the chunked path
                    # buys (a recompute-resume instead swallows them and
                    # restarts decode at its pending out[-1])
                    self._finish_prefill(
                        req, lambda: self._sample_one(logits, req))
                did = True

            # -- one batched decode step over all decoding slots --
            decoding = [r is not None and r.state == DECODE
                        for r in self.slots]
            if any(decoding):
                if self.spec is not None:
                    self._spec_decode(np.asarray(decoding))
                else:
                    self._plain_decode(decoding)
                did = True

        if did:
            self.ticks += 1
            self._h_tick.record(time.perf_counter() - t_tick)

    def _plain_decode(self, decoding) -> None:
        """One single-token batched decode step (the non-speculative path)."""
        # under over-commit a dry pool preempts a victim here and clears
        # its row; reservation pools pass the mask through untouched
        decoding = self._ensure_room(decoding)
        if not decoding.any():
            return
        tr = self.tel.tracer
        args = None
        if tr.enabled:
            args = {"rows": int(decoding.sum())}
            if self.paged:
                # (row, page) pairs the paged kernel visits per layer
                ps = self.kv.page_size
                args["pages"] = int(
                    ((self.kv.lengths[decoding] + ps) // ps).sum())
        with tr.span("decode.step", "stage", TID_ENGINE, args):
            if self.paged:
                logits, self.kv.cache = self._step(
                    self.params, jnp.asarray(self.cur_tok), self.kv.cache,
                    self.kv.lengths, jnp.asarray(self.kv.block_tables),
                    jnp.asarray(decoding, bool))
            else:
                logits, self.kv.cache = self._step(
                    self.params, jnp.asarray(self.cur_tok), self.kv.cache,
                    self.kv.lengths, jnp.asarray(decoding, bool))
        self.model_calls += 1
        sampled = self._sample_rows(logits)
        with tr.span("emit"):
            self.kv.advance_mask(np.asarray(decoding))
            now = time.monotonic()
            for b, req in enumerate(self.slots):
                if req is not None and req.state == DECODE and decoding[b]:
                    self._emit(req, int(sampled[b]), now)

    def _spec_decode(self, decoding: np.ndarray) -> None:
        """One speculative decode tick: propose per slot, verify every
        slot's draft in ONE chunked forward call, emit 1..k+1 tokens.

        Per decoding slot with cache length L the verify chunk holds
        ``[cur_tok, d_1..d_c]`` at absolute positions ``L..L+c`` (c is the
        slot's draft count, capped by its remaining token budget and the
        cache ceiling so writes never pass the admission-time page
        reservation).  ``sampler.spec_accept_batch`` accepts a prefix of
        the drafts and supplies the bonus/corrective token; the accepted
        tokens commit via ``kv.rewind(slot, L+m+1)``, which also releases
        (paged) pages grown for rejected positions — their K/V stay
        masked and are overwritten by the next write at those positions.
        """
        if self.spec.tree:
            self._tree_spec_decode(decoding)
            return
        B, k = self.B, self.spec.k
        tr = self.tel.tracer
        lengths_h = np.asarray(self.kv.lengths).copy()
        # cap so every written position stays below the cache ceiling
        # (window-capped stacks have none: rings wrap, states are O(1))
        # and prompt+max_new (the reservation bound)
        caps = speculative.draft_caps(self.slots, lengths_h, decoding, k,
                                      self.seq_ceiling,
                                      adaptive=self.adaptive)
        with tr.span("spec.propose", "spec"):
            draft, counts = self.proposer.propose(
                self.slots, self.cur_tok, lengths_h, decoding, caps)
        if not counts.any():
            # no slot proposed anything: a (k+1)-wide verify would pay
            # ~(k+1)x a decode step's position-axis compute (and, paged,
            # the full view gather/scatter) for zero speculative gain.
            # Accepting zero drafts IS plain sampling from position 0, so
            # the plain step emits the identical token stream.
            self._plain_decode(list(decoding))
            return
        # room for k+1 verify writes per row BEFORE vlen/valids are
        # derived: an over-committed pool may preempt one of the decoding
        # rows itself, and its cleared bit must park the row
        decoding = self._ensure_room(decoding, counts + 1)
        if not decoding.any():
            return
        toks = np.zeros((B, k + 1), np.int32)
        toks[:, 0] = self.cur_tok[:, 0]
        toks[:, 1:] = draft
        # inactive rows park at max_seq: their absolute-offset writes
        # drop, their logits go unused (ring writes and state commits are
        # additionally gated by valids == 0 on the state-store path)
        vlen = np.where(decoding, lengths_h, self.max_seq).astype(np.int32)
        valids = np.where(decoding, counts + 1, 0).astype(np.int32)
        prev_cache = None
        traj = None
        with tr.span("spec.verify", "spec", TID_ENGINE,
                     ({"rows": int(decoding.sum()),
                       "proposed": int(counts.sum())}
                      if tr.enabled else None)):
            if self.paged:
                mask = np.asarray(decoding, bool)
                live = -(-(lengths_h + counts + 1) // self.kv.page_size)
                self.verify_touched_positions += int(
                    (live[mask] * self.kv.page_size).sum())
                self.verify_dense_positions += (
                    2 * int(mask.sum()) * self.max_seq)
                if self._state_store is not None:
                    # mixed paged: the snapshot/trajectory commit settles
                    # the slot-resident rings/states; kv.rewind below
                    # releases the attn side's rejected pages
                    prev_cache = self.kv.cache
                    logits, self.kv.cache, traj = self._verify(
                        self.params, jnp.asarray(toks), self.kv.cache,
                        jnp.asarray(vlen), jnp.asarray(valids),
                        jnp.asarray(self.kv.block_tables))
                else:
                    logits, self.kv.cache = self._verify(
                        self.params, jnp.asarray(toks), self.kv.cache,
                        jnp.asarray(vlen),
                        jnp.asarray(self.kv.block_tables))
            elif self._state_store is not None:
                # the verify base IS the rewind snapshot (JAX arrays are
                # immutable — holding the reference costs nothing)
                prev_cache = self.kv.cache
                logits, self.kv.cache, traj = self._verify(
                    self.params, jnp.asarray(toks), self.kv.cache,
                    jnp.asarray(vlen), jnp.asarray(valids))
            else:
                logits, self.kv.cache = self._verify(
                    self.params, jnp.asarray(toks), self.kv.cache,
                    jnp.asarray(vlen))
        self.model_calls += 1
        self.spec_ticks += 1
        self.rng, sub = jax.random.split(self.rng)
        with tr.span("spec.accept", "spec"):
            n_acc, next_tok = jax.device_get(self._accept(
                logits, jnp.asarray(draft), jnp.asarray(counts), sub,
                jnp.asarray(self._temp), jnp.asarray(self._topk),
                jnp.asarray(self._topp)))
        if self._state_store is not None:
            # state half of the rewind seam: commit cur_tok + the accepted
            # drafts — rejected ring writes are restored from the
            # snapshot, each recurrent layer's state is selected off the
            # verify trajectory (K/V length rewind stays with kv.rewind)
            commit = np.where(decoding, n_acc + 1, 0).astype(np.int32)
            self.kv.cache = self._state_store.commit(
                prev_cache, self.kv.cache, traj, lengths_h, commit,
                valids, chunk=k + 1)
        now = time.monotonic()
        for b in range(B):
            req = self.slots[b]
            if not decoding[b] or req is None:
                continue
            m = int(n_acc[b])
            self._h_accept.record(m)
            self.spec_proposed += int(counts[b])
            self.spec_accepted += m
            if self.adaptive is not None:
                self.adaptive.observe(b, int(counts[b]), m)
            L = int(lengths_h[b])
            for tok in list(draft[b, :m]) + [int(next_tok[b])]:
                self._emit(req, int(tok), now)
                self.spec_emitted += 1
                if req.done:
                    break
            else:
                # request lives on: commit cur_tok + the m accepted drafts
                # (positions L..L+m); the bonus token becomes cur_tok via
                # _emit and is written next tick
                self.kv.rewind(b, L + m + 1)
                self.proposer.commit(b, req.prompt + req.out, L + m + 1)

    # ------------------------------------------------------------------
    def _tree_spec_decode(self, decoding: np.ndarray) -> None:
        """One tree-speculative decode tick: propose a branchy token tree
        per slot, verify EVERY node in one ancestor-masked chunked call,
        emit the longest accepted root-to-leaf path + a corrective token.

        The verify chunk holds ``[cur_tok, node_1..node_n]`` in DFS
        order; node ``j`` attends exactly its root path (the ``anc``
        bitmask) and is rotated/embedded at its *logical* position
        ``L + depth_j`` even though its K/V land at flat position
        ``L + j``.  After ``sampler.spec_accept_tree`` picks the
        surviving path, :func:`lm.compact_accepted_path` copies the
        path's K/V from flat to contiguous positions ``L+1..L+m`` so the
        cache looks exactly as if plain decode had produced those
        tokens; ``kv.rewind(slot, L+m+1)`` then drops the rejected
        branches.  Tree width rides the same one-verify-per-tick
        economics as linear spec: chunk width stays k+1, the tree just
        spends it on siblings instead of a single deep chain.
        """
        B, k = self.B, self.spec.k
        C = k + 1
        tr = self.tel.tracer
        lengths_h = np.asarray(self.kv.lengths).copy()
        caps = speculative.draft_caps(self.slots, lengths_h, decoding, k,
                                      self.seq_ceiling,
                                      adaptive=self.adaptive)
        with tr.span("spec.propose", "spec"):
            trees = self.proposer.propose_tree(
                self.slots, self.cur_tok, lengths_h, decoding, caps,
                branch=self.spec.branch)
        tokens_a, parents, n_nodes, anc, depths = speculative.tree_arrays(
            trees, k, C)
        if not n_nodes.any():
            # no slot grew a tree: accepting zero nodes IS plain
            # sampling from position 0 (same as the linear fast path)
            self._plain_decode(list(decoding))
            return
        decoding = self._ensure_room(decoding, n_nodes + 1)
        if not decoding.any():
            return
        toks = np.zeros((B, C), np.int32)
        toks[:, 0] = self.cur_tok[:, 0]
        toks[:, 1:] = tokens_a
        # parked rows write at max_seq (dropped) with causal-default
        # masks; their logits go unused
        vlen = np.where(decoding, lengths_h, self.max_seq).astype(np.int32)
        with tr.span("spec.verify", "spec", TID_ENGINE,
                     ({"rows": int(decoding.sum()),
                       "proposed": int(n_nodes.sum()),
                       "tree": True}
                      if tr.enabled else None)):
            if self.paged:
                mask = np.asarray(decoding, bool)
                live = -(-(lengths_h + n_nodes + 1) // self.kv.page_size)
                self.verify_touched_positions += int(
                    (live[mask] * self.kv.page_size).sum())
                self.verify_dense_positions += (
                    2 * int(mask.sum()) * self.max_seq)
                logits, self.kv.cache = self._verify_tree(
                    self.params, jnp.asarray(toks), self.kv.cache,
                    jnp.asarray(vlen),
                    jnp.asarray(self.kv.block_tables),
                    jnp.asarray(anc), jnp.asarray(depths))
            else:
                logits, self.kv.cache = self._verify_tree(
                    self.params, jnp.asarray(toks), self.kv.cache,
                    jnp.asarray(vlen), jnp.asarray(anc),
                    jnp.asarray(depths))
        self.model_calls += 1
        self.spec_ticks += 1
        self.rng, sub = jax.random.split(self.rng)
        with tr.span("spec.accept", "spec"):
            n_acc, acc, next_tok = jax.device_get(self._accept_tree(
                logits, jnp.asarray(tokens_a), jnp.asarray(parents),
                jnp.asarray(n_nodes), sub, jnp.asarray(self._temp),
                jnp.asarray(self._topk), jnp.asarray(self._topp)))
        acc = np.asarray(acc, bool)
        # accepted path per row, in depth order (DFS layout guarantees
        # parent flat pos < child flat pos, so ascending == root-to-leaf)
        paths = [np.flatnonzero(acc[b, 1:]) + 1 if decoding[b]
                 else np.zeros(0, np.int64) for b in range(B)]
        # compact the surviving path's K/V from scattered flat positions
        # to contiguous L+1..L+m BEFORE rewind releases anything; rows
        # whose path is already contiguous (a chain prefix) need no copy
        src = np.full((B, k), self.max_seq, np.int32)
        dst = np.full((B, k), self.max_seq, np.int32)
        need = False
        for b in range(B):
            m = len(paths[b])
            if m == 0:
                continue
            L = int(lengths_h[b])
            src[b, :m] = L + paths[b]
            dst[b, :m] = L + 1 + np.arange(m)
            if not np.array_equal(paths[b], np.arange(1, m + 1)):
                need = True
        if need:
            with tr.span("spec.compact", "spec"):
                if self.paged:
                    # snapshot the block tables: the compact dispatch is
                    # async and jnp.asarray aliases host memory on CPU,
                    # while the rewind below nulls released page entries
                    # in place — without the copy the in-flight gather
                    # races the mutation and reads freed page ids
                    self.kv.cache = self._compact(
                        self.kv.cache, jnp.asarray(src),
                        jnp.asarray(dst),
                        jnp.asarray(self.kv.block_tables.copy()))
                else:
                    self.kv.cache = self._compact(
                        self.kv.cache, jnp.asarray(src),
                        jnp.asarray(dst))
        now = time.monotonic()
        for b in range(B):
            req = self.slots[b]
            if not decoding[b] or req is None:
                continue
            m = len(paths[b])
            self._h_accept.record(m)
            self.spec_proposed += int(n_nodes[b])
            self.spec_accepted += m
            if self.adaptive is not None:
                self.adaptive.observe_tree(b, int(n_nodes[b]), m)
            L = int(lengths_h[b])
            for tok in [int(toks[b, j]) for j in paths[b]] + [
                    int(next_tok[b])]:
                self._emit(req, int(tok), now)
                self.spec_emitted += 1
                if req.done:
                    break
            else:
                # request lives on: keep cur_tok + the m path tokens
                # (now at positions L..L+m after compaction); the
                # corrective token becomes cur_tok via _emit
                self.kv.rewind(b, L + m + 1)
                self.proposer.commit(b, req.prompt + req.out, L + m + 1)

    # ------------------------------------------------------------------
    def _tick_replay(self) -> None:
        """Seed-engine admission: replay the prompt one token per tick
        through the decode path.  No longer an auto fallback — every
        decoder-only stack chunks — but kept as an explicit A/B debug
        mode and the benchmark baseline (and the prefill path for the
        whisper encoder-decoder, whose cross-attention has no chunk
        body)."""
        self._admit()
        if all(s is None for s in self.slots):
            return
        occupied = self._ensure_room([s is not None for s in self.slots])
        if not occupied.any():
            return
        if self.paged:
            logits, self.kv.cache = self._step(
                self.params, jnp.asarray(self.cur_tok), self.kv.cache,
                self.kv.lengths, jnp.asarray(self.kv.block_tables),
                jnp.asarray(occupied, bool))
        else:
            logits, self.kv.cache = self._step(
                self.params, jnp.asarray(self.cur_tok), self.kv.cache,
                self.kv.lengths, jnp.asarray(occupied, bool))
        self.model_calls += 1
        sampled = self._sample_rows(logits)
        lengths_h = np.asarray(self.kv.lengths)
        now = time.monotonic()
        for b, req in enumerate(self.slots):
            if req is None or not occupied[b]:
                continue
            ctx = req.context
            pos = int(lengths_h[b]) + 1  # tokens in cache after this tick
            if pos < len(ctx):  # still prefilling: teacher-force
                req.filled = pos
                self.cur_tok[b, 0] = ctx[pos]
            else:
                req.filled = len(ctx)
                self._finish_prefill(req, lambda: int(sampled[b]))
        # advance every slot that was occupied when the step ran (freed-
        # this-tick slots get their stale +1 reset at the next alloc)
        self.kv.advance_mask(np.asarray(occupied))
        self.ticks += 1

    # ------------------------------------------------------------------
    def run(self, max_ticks: int = 10_000, *,
            on_stall: str = "raise") -> List[Request]:
        """Drive ticks until drained or ``max_ticks`` loop iterations
        pass; see :func:`drain_engine` for the stall contract."""
        return drain_engine(
            self,
            lambda: self.queue or any(s is not None for s in self.slots),
            max_ticks, on_stall)

    # ------------------------------------------------------------------
    def dump_trace(self, path: str) -> str:
        """Write the recorded span timeline as Chrome/Perfetto trace
        JSON (requires ``telemetry=Telemetry(trace=True)``)."""
        return self.tel.dump_trace(path)

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        out = latency_stats(self)
        emitted = sum(len(r.out) for r in self.finished) + sum(
            len(r.out) for r in self.slots if r is not None)
        out.update({
            "ticks": self.ticks,
            "model_calls": self.model_calls,
            "prefill_calls": self.prefill_calls,
            "stalled": self.stalled,
            "stalled_queued": len(self.stalled_detail["queued"]),
            "stalled_in_flight": len(self.stalled_detail["in_flight"]),
            "tokens_per_model_call": emitted / max(self.model_calls, 1),
            "sample_calls": self.sample_calls,
            "sample_greedy_calls": self.sample_greedy_calls,
            "mdk_mp_reuse": self.mdk_stats.reuse_factor().get("mp", 0),
            "tick_p50_ms": self._h_tick.quantile(0.5) * 1e3,
            "tick_p99_ms": self._h_tick.quantile(0.99) * 1e3,
        })
        out.update(self.lifecycle_stats())
        if self.spec is not None:
            out.update({
                "spec_ticks": self.spec_ticks,
                "spec_proposed": self.spec_proposed,
                "spec_accepted": self.spec_accepted,
                "spec_emitted": self.spec_emitted,
                "acceptance_rate": (
                    self.spec_accepted / max(self.spec_proposed, 1)),
                "tokens_per_verify_call": (
                    self.spec_emitted / max(self.spec_ticks, 1)),
                # draft-model forwards (0 for the free n-gram proposer):
                # the cost side tokens_per_model_call excludes, so a
                # proposer="model" benchmark can't read as a free win
                "draft_calls": getattr(self.proposer, "draft_calls", 0),
                "verify_touched_positions": self.verify_touched_positions,
                "verify_dense_positions": self.verify_dense_positions,
                "spec_accept_len_p50": self._h_accept.quantile(0.5),
                "spec_accept_len_p99": self._h_accept.quantile(0.99),
            })
            if self.adaptive is not None:
                out.update(self.adaptive.stats())
        out.update(self.kv.stats())
        return out
