"""Distributed serving engine: the single-device tick over a device mesh.

``DistributedServeEngine`` runs the scheduler-driven serving core
(serving/engine.py) across every device of a ``("shard",)`` mesh — the
multi-FPGA LoopLynx deployment at shard_map level:

  * **Sharded paged KV pool** — each device owns one shard of the page
    pool (:class:`~repro.serving.distributed.sharded_kv.
    ShardedPageAllocator`); a request's pages live on exactly one shard,
    chosen by prefix affinity then load, and only its i32 block-table row
    ever travels with it.  ``kv_layout="stacked"`` shards contiguous slot
    pools the same way.
  * **Per-shard compute via shard_map** — one
    :func:`repro.models.lm.sharded_decode_step` call advances every
    shard's decoding slots per tick (logits return through the
    double-buffered ring all-gather, the tick's activation collective);
    one :func:`repro.models.lm.sharded_prefill_into_slot` call per round
    prefills up to one chunk per shard.
  * **Dual-stream decode waves** — the decoding slot set is split into
    two phase-shifted waves (:class:`~repro.serving.admission.
    DecodeWaveScheduler` — the paper's alternating dual-FPGA batches).
    Each tick consumes and redispatches the waves in turn, so one wave's
    ring-all-gather logits fetch and host-side sampling always land while
    the *other* wave's device call is still in flight.  That shadow
    exists in **pure-decode drain ticks too** — the phase where the
    single-wave pipeline collapsed to exposed fetches (no prefill to hide
    behind); only the final single-slot endgame runs unshadowed.
  * **Overlapped transfers** — the tick is software-pipelined so every
    host<->device transfer is staged behind in-flight compute
    (:class:`~repro.serving.distributed.transfer.TransferScheduler`
    meters it as ``overlap_ratio``, attributed per phase):

        phase A    dispatch this tick's prefill rounds (chunk shipping
                   hides behind the waves' in-flight decodes),
        phase B/C  per wave w in (0, 1):
                     consume wave w's last results (the collective's
                     fetch hides behind wave 1-w's in-flight call and
                     phase A's prefills), then redispatch wave w (input
                     staging hides the same way),
        phase D    consume this tick's prompt-completing prefill logits
                   (hides behind the waves' just-dispatched calls).

    Decode results are therefore emitted one tick after they are
    dispatched — a scheduling change only: greedy outputs are
    token-for-token identical to the single-device ``ServeEngine`` (both
    kv layouts; asserted in ``tests/subscripts/dist_serve_check.py``).
    Non-greedy sampling draws from the same per-request distributions but
    a differently-interleaved engine RNG stream.
  * **Distributed speculative decode** — with ``spec=SpecConfig(...)``
    every wave dispatch becomes one batched
    :func:`repro.models.lm.sharded_verify_chunk` call: per-shard
    proposals (n-gram tables or a draft model, keyed by global slot id =
    shard-local state), accept/reject rides the same one-tick-delayed
    result path, and rejection rolls each slot back on its own shard
    (``kv.rewind`` releases paged draft pages; hybrid stacks — stacked
    *or* per-kind paged, whose rings/states stay slot-resident beside
    the page pool — settle them via ``StateStore.commit_sharded``).  Rows not in
    the dispatched wave are parked (``lengths >= max_seq``, ``valids ==
    0``): they write **nothing**, so a wave's verify can never corrupt
    the other wave's in-flight draft positions.  In spec mode there is no
    plain-decode fallback for that exact reason — a plain step's
    full-shape tag-along write at the other wave's base position would
    land inside its un-consumed verify.  Greedy spec streams stay
    token-for-token identical to ``ServeEngine(spec=...)``.

The admission policy remains host-local per shard (each pool shard prices
requests in its own pages via ``FIFOAdmission.page_price``), exactly the
multi-host seam PR 2's block table was shaped for.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import scheduler as sched
from repro.models import blocks, lm
from repro.serving import sampler as samplers, speculative
from repro.serving.admission import (
    DecodeWaveScheduler, FIFOAdmission, ShardPlacement)
from repro.serving.distributed.sharded_kv import (
    ShardedPageAllocator, ShardedSlotAllocator)
from repro.serving.distributed.transfer import TransferScheduler
from repro.serving.kv_cache import blob_nbytes
from repro.serving.lifecycle import (
    DECODE, MIGRATING, PREFILL, LifecycleMixin, Request, drain_engine,
    latency_stats, submit_request, transition)
from repro.serving.quantize import calibrate, quantize_model_params
from repro.serving.telemetry import (
    TID_ENGINE, TID_REQUEST, Telemetry, linear_edges, registry_counter)


class DistributedServeEngine(LifecycleMixin):
    # schedule counters backed by the telemetry registry (the single
    # store stats() reads), same attribute spelling as before — see
    # repro.serving.telemetry.registry_counter
    ticks = registry_counter("ticks")
    model_calls = registry_counter("model_calls")
    prefill_calls = registry_counter("prefill_calls")
    stalled = registry_counter("stalled")
    spec_ticks = registry_counter("spec_ticks")
    spec_proposed = registry_counter("spec_proposed")
    spec_accepted = registry_counter("spec_accepted")
    spec_emitted = registry_counter("spec_emitted")
    migrations = registry_counter("migrations")
    sample_calls = registry_counter("sample_calls")
    sample_greedy_calls = registry_counter("sample_greedy_calls")

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        mesh: Optional[jax.sharding.Mesh] = None,
        n_shards: Optional[int] = None,
        slots_per_shard: int = 2,
        max_seq: int = 256,
        eos_id: int = 0,
        quantized: bool = False,
        calibration_batches=None,
        seed: int = 0,
        chunk_size: int = 32,
        kv_layout: str = "auto",  # auto | paged | stacked
        page_size: int = 16,
        n_pages: Optional[int] = None,  # per shard
        prefix_sharing: bool = True,
        admission: Optional[FIFOAdmission] = None,
        placement: Optional[ShardPlacement] = None,
        act_dtype=None,
        spec: Optional[speculative.SpecConfig] = None,
        decode_waves: int = 2,
        telemetry: Optional[Telemetry] = None,
    ):
        # must exist before any counter attribute is assigned: the
        # registry_counter descriptors dereference self.tel
        self.tel = telemetry or Telemetry()
        if not blocks.chunk_capable(cfg):
            # ValueError, not assert: the tick is chunked-prefill-only
            # and must refuse encoder-decoder stacks under python -O too
            raise ValueError(
                "the distributed engine drives chunked prefill only; "
                f"{cfg.name} is encoder-decoder (cross-attention has no "
                "chunk path)")
        if mesh is None:
            from repro.launch.mesh import make_serving_mesh

            mesh = make_serving_mesh(n_shards)
        assert "shard" in mesh.axis_names, mesh.axis_names
        self.mesh = mesh
        self.D = mesh.shape["shard"]
        self.Bs = slots_per_shard
        self.B = self.D * self.Bs  # global slots
        self.cfg = cfg
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.chunk_size = min(chunk_size, max_seq)
        if quantized:
            stats = None
            if calibration_batches is not None:
                stats = calibrate(params, cfg, calibration_batches)
            params = quantize_model_params(params, cfg, stats)
        self.act_dtype = act_dtype or (jnp.float32 if quantized
                                       else jnp.bfloat16)
        # replicated on the mesh once, so no sharded step copies weights
        # from the default device on each call
        self.params = jax.device_put(params, NamedSharding(mesh, P()))
        self.admission = admission or FIFOAdmission(
            cfg, chunk_size=self.chunk_size)
        assert self.admission.chunk_size <= self.chunk_size
        # lifecycle bookkeeping (preemption/restore/cancel counters and
        # the over-commit flag mirrored off the admission policy)
        self._init_lifecycle()

        # admission stays bounded per shard — shipping recurrent state
        # between shards for unbounded requests is a named next seam
        self.seq_ceiling: Optional[int] = max_seq
        if kv_layout == "auto":
            # per-kind cache layouts: any stack with a global-attention
            # layer pages (mixed stacks keep rings/recurrent states
            # slot-resident on their shard, beside the page pool)
            kv_layout = ("paged" if blocks.paged_capable(cfg)
                         and max_seq % page_size == 0 else "stacked")
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        if self.paged:
            if max_seq % page_size:
                raise ValueError(
                    f"page_size={page_size} must divide max_seq={max_seq}")
            self.kv = ShardedPageAllocator(
                cfg, self.D, slots_per_shard, max_seq, page_size=page_size,
                n_pages=n_pages, prefix_sharing=prefix_sharing,
                placement=placement, overcommit=self.overcommit,
                watermark=getattr(self.admission, "watermark", 1.0))
        else:
            assert kv_layout == "stacked", kv_layout
            self.kv = ShardedSlotAllocator(
                cfg, self.D, slots_per_shard, max_seq)
        self._share = self.paged and prefix_sharing

        # one device pytree for all shards: leading axis = shard axis,
        # committed to the mesh so shard s's pages live on device s and
        # stay there (in/out specs are P("shard") everywhere; nothing in
        # the tick ever reshards K/V)
        pool = self.kv.n_pages if self.paged else slots_per_shard
        seq = page_size if self.paged else max_seq
        abstract = lm.init_cache_abstract(
            cfg, pool, seq, layout=("paged" if self.paged else "stacked"),
            slots=slots_per_shard, slot_seq=max_seq)
        self.kv_sharding = NamedSharding(mesh, P("shard"))
        # zeros are made on their own shard: no device ever holds the
        # whole pool, as it would if one were made and then split
        self.cache = jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda leaf: jnp.zeros((self.D,) + leaf.shape, leaf.dtype),
                abstract),
            out_shardings=self.kv_sharding)()

        # the transfer meter re-emits its events as trace spans on the
        # same timeline when tracing is on (hidden vs exposed visible)
        self.xfer = TransferScheduler(tracer=self.tel.tracer)
        self.cur_tok = np.zeros((self.D, self.Bs, 1), np.int32)
        self._temp = np.zeros((self.B,), np.float32)
        self._topk = np.zeros((self.B,), np.int32)
        self._topp = np.ones((self.B,), np.float32)
        self.rng = jax.random.PRNGKey(seed)

        # named programs, as ServeEngine's (serving.engine.PROGRAMS)
        if self.paged:
            # the paged step carries the really-decoding mask too: mixed
            # stacks keep slot-resident rings/states whose commits must
            # not fire for tag-along rows (pure-attn shards ignore it)
            def decode_step(p, tok, cache, lengths, bt, acts):
                return lm.sharded_decode_step(
                    p, cfg, mesh, tok, cache, lengths, actives=acts,
                    block_tables=bt, dtype=self.act_dtype)

            def prefill_chunk(p, toks, cache, slots, offs, valids, acts,
                              bts):
                return lm.sharded_prefill_into_slot(
                    p, cfg, mesh, toks, cache, slots, offs, valids, acts,
                    block_tables=bts, dtype=self.act_dtype)
        else:
            # stacked shards carry the really-decoding mask: rings and
            # recurrent states of idle slots must not commit on the
            # fixed-shape batched tick (see lm.decode_step ``active``)
            def decode_step(p, tok, cache, lengths, acts):
                return lm.sharded_decode_step(
                    p, cfg, mesh, tok, cache, lengths, actives=acts,
                    dtype=self.act_dtype)

            def prefill_chunk(p, toks, cache, slots, offs, valids, acts):
                return lm.sharded_prefill_into_slot(
                    p, cfg, mesh, toks, cache, slots, offs, valids, acts,
                    dtype=self.act_dtype)
        self._step = jax.jit(decode_step)
        self._prefill = jax.jit(prefill_chunk)
        self._sample = jax.jit(samplers.sample_batch)

        self.spec = spec
        self.proposer: Optional[speculative.DraftProposer] = None
        self.adaptive: Optional[speculative.AdaptiveDraft] = None
        # hybrid shards carry serving state with no length mask (slot-
        # resident in both layouts under per-kind paging); their
        # speculative commits go through the shard-local StateStore seam
        # (None for pure-attention stacks)
        self._state_store = getattr(self.kv, "state", None)
        if spec is not None:
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
                spec, self.B, max_seq, chunk_size=self.chunk_size,
                dtype=self.act_dtype)
            self.adaptive = speculative.AdaptiveDraft.from_spec(spec)
            if self.paged and self._state_store is not None:
                # mixed paged: block tables route the attn writes AND the
                # slot-resident rings/states need valids + the trajectory
                # for their sharded StateStore commit
                def verify_chunk(p, toks, cache, lens, valids, bts):
                    return lm.sharded_verify_chunk(
                        p, cfg, mesh, toks, cache, lens, valids=valids,
                        block_tables=bts, with_traj=True,
                        dtype=self.act_dtype)
            elif self.paged:
                def verify_chunk(p, toks, cache, lens, bts):
                    return lm.sharded_verify_chunk(
                        p, cfg, mesh, toks, cache, lens, block_tables=bts,
                        dtype=self.act_dtype)
            elif self._state_store is not None:
                def verify_chunk(p, toks, cache, lens, valids):
                    return lm.sharded_verify_chunk(
                        p, cfg, mesh, toks, cache, lens, valids=valids,
                        with_traj=True, dtype=self.act_dtype)
            else:
                def verify_chunk(p, toks, cache, lens):
                    return lm.sharded_verify_chunk(
                        p, cfg, mesh, toks, cache, lens,
                        dtype=self.act_dtype)
            self._verify = jax.jit(verify_chunk)
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
                # tree verify threads per-row ancestor bitmasks and
                # logical (root-path depth) positions through the
                # sharded chunk call; page_addressable rules out the
                # StateStore variants
                if self.paged:
                    def verify_tree(p, toks, cache, lens, bts, anc, dep):
                        return lm.sharded_verify_chunk(
                            p, cfg, mesh, toks, cache, lens,
                            block_tables=bts, anc=anc, depths=dep,
                            dtype=self.act_dtype)

                    def compact_pages(cache, src, dst, bts):
                        return lm.sharded_compact_accepted_path(
                            cfg, mesh, cache, src, dst, block_tables=bts)
                else:
                    def verify_tree(p, toks, cache, lens, anc, dep):
                        return lm.sharded_verify_chunk(
                            p, cfg, mesh, toks, cache, lens, anc=anc,
                            depths=dep, dtype=self.act_dtype)

                    def compact_pages(cache, src, dst):
                        return lm.sharded_compact_accepted_path(
                            cfg, mesh, cache, src, dst)
                self._verify_tree = jax.jit(verify_tree)
                self._compact = jax.jit(compact_pages)
                self._accept_tree = jax.jit(samplers.spec_accept_tree)

        self.slots: List[Optional[Request]] = [None] * self.B
        self.queue: deque = deque()
        self.finished: List[Request] = []
        self._next_rid = 0
        self.ticks = 0
        self.model_calls = 0
        self.prefill_calls = 0
        self.stalled = 0  # unfinished requests when run() gave up
        self.spec_ticks = 0  # verify calls issued
        self.spec_proposed = 0  # draft tokens submitted for verification
        self.spec_accepted = 0  # draft tokens accepted
        self.spec_emitted = 0  # tokens emitted off verify calls
        self.migrations = 0  # live cross-shard request migrations
        self.n_waves = max(1, int(decode_waves))
        self.waves = DecodeWaveScheduler(self.B, self.n_waves)
        # wave-width adaptive verify: each wave's last dispatched chunk
        # width (1..k+1) plus run-wide extremes — the "width < k+1 on a
        # quiet wave" signal (spec mode only; stats() gates on spec)
        self._wave_vwidth = [0] * self.n_waves
        self._vwidth_min = 0
        self._vwidth_max = 0
        # per-wave in-flight dispatch: dicts made by _dispatch_wave, or
        # None; the one-tick-delayed result path, one lane per wave
        self._pending_wave: List[Optional[dict]] = [None] * self.n_waves
        self._busy_ticks = np.zeros((self.D,), np.int64)
        self.mdk_stats = sched.mdk_stats(cfg)
        self.stalled_detail: Dict[str, List[int]] = {
            "queued": [], "in_flight": []}

        # telemetry: cached histogram/gauge handles (hot paths record
        # without name lookups)
        reg = self.tel.registry
        self._h_ttft = reg.histogram("ttft_s")
        self._h_tpot = reg.histogram("tpot_s")
        self._h_tick = reg.histogram("tick_wall_s")
        # per-wave decode occupancy in rows-per-dispatch: the
        # wave-imbalance bubble signal (ROADMAP item 2) as a histogram,
        # plus a live gauge per wave with its high-water mark
        self._h_wave_occ = reg.histogram(
            "wave_occupancy", edges=linear_edges(0.0, self.B + 1,
                                                 self.B + 1))
        self._g_wave = [reg.gauge(f"wave{w}_slots")
                        for w in range(self.n_waves)]
        self._h_accept = (
            reg.histogram("spec_accept_len",
                          edges=linear_edges(0.0, spec.k + 2, spec.k + 2))
            if spec is not None else None)
        self._c_migr_bytes = reg.counter("migrated_bytes_total")
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

    # -- lifecycle hooks (geometry the mixin machine runs through) -------
    def _set_cur_tok(self, slot: int, tok: int) -> None:
        s, ls = self.kv.shard_of(slot)
        self.cur_tok[s, ls, 0] = tok

    def _in_flight_slots(self) -> frozenset:
        """Slots with an un-consumed wave dispatch: their lengths are
        advanced (or a verify holds their draft positions), so eviction,
        cancellation, and migration must wait for the consume."""
        out = set()
        for pend in self._pending_wave:
            if pend is not None:
                out.update(np.flatnonzero(
                    np.asarray(pend["mask"])).tolist())
        return frozenset(out)

    def _slot_shard(self, slot: int) -> int:
        return self.kv.shard_of(slot)[0]

    def _on_decode_start(self, req: Request) -> None:
        # wave-aware admission: the slot lands in the lightest decode
        # wave the moment it starts decoding, so a prefill completion
        # joins the undersized dispatch instead of waiting for a
        # rebalance (joining at seat time would count still-prefilling
        # slots as wave members and skew the balance)
        self.waves.join(req.slot)

    def _release_slot_extra(self, slot: int) -> None:
        self.waves.release(slot)

    def _admit_args(self, req: Request, slot: int,
                    shared_tokens: int) -> dict:
        return {"rid": req.rid, "slot": slot,
                "shard": self._slot_shard(slot),
                "shared_tokens": shared_tokens}

    def _evict_blob(self, req: Request) -> dict:
        # device_get inside the gather orders after any in-flight op
        # writing self.cache, so the snapshot is post-tag-along (garbage
        # above the committed length, never read back)
        return self.kv.evict_to_host(req.slot, cache=self.cache)

    def _restore_blob(self, req: Request) -> Optional[int]:
        res = self.kv.restore(
            req.host_blob,
            lifetime_tokens=len(req.prompt) + req.max_new,
            cache=self.cache, shard=req.forced_shard)
        if res is None:
            return None
        slot, self.cache = res
        return slot

    # ------------------------------------------------------------------
    def migrate(self, rid: int, to_shard: Optional[int] = None,
                *, mode: str = "auto") -> bool:
        """Move a decoding request to another shard between ticks.

        ``mode="state"`` ships the slot's carried cache through the host
        (evict -> restore on the target shard) — for recurrent/windowed
        stacked layouts that is the O(1)/O(W) carried state the paper's
        metadata-only transfer path was shaped for, metered as a
        ``migrate.state`` transfer event.  ``mode="recompute"`` ships
        nothing: the request re-prefills ``prompt + out[:-1]`` on the
        target shard (the cheap choice when the bulk K/V is paged).
        ``"auto"`` picks state for stacked layouts and recompute for
        paged pools.  Either way the greedy stream is token-for-token
        identical to an unmigrated run.  Returns ``True`` if the request
        was detached — or scheduled to detach — toward ``to_shard``
        (default: the least-loaded other shard).  A slot with an
        un-consumed wave dispatch defers to consume time (like cancel);
        a request that finishes off that very dispatch drops the
        migration.  Mid-prefill and cancelling requests are left
        alone."""
        if mode not in ("auto", "state", "recompute"):
            raise ValueError(f"migrate mode {mode!r}")
        req = next((r for r in self.slots
                    if r is not None and r.rid == rid), None)
        if req is None or req.state != DECODE or req.cancel_requested:
            return False
        src = self._slot_shard(req.slot)
        if to_shard is None:
            order = [s for s in self.kv.placement.order(self.kv.shards)
                     if s != src]
            if not order:
                return False
            to_shard = order[0]
        if to_shard == src or not 0 <= to_shard < self.D:
            return False
        if mode == "auto":
            mode = "recompute" if self.paged else "state"
        if req.slot in self._in_flight_slots():
            # the pipelined tick keeps every decoding slot's dispatch in
            # flight across tick boundaries — detach at consume time
            # (same deferral as cancel; dropped if the request finishes
            # off that very dispatch)
            req.migrate_to = (to_shard, mode)
            return True
        self._do_migrate(req, to_shard, mode)
        return True

    def _do_migrate(self, req: Request, to_shard: int, mode: str) -> None:
        """Detach a decoding request toward ``to_shard`` (no in-flight
        dispatch may hold its slot)."""
        req.migrate_to = None
        src = self._slot_shard(req.slot)
        slot = req.slot
        transition(req, MIGRATING)
        if mode == "state":
            blob = self._evict_blob(req)
            nbytes = blob_nbytes(blob)
            # the gather/scatter bytes really moved device->host->device;
            # meter them on the transfer timeline (hidden iff some wave
            # op is still in flight to shadow them)
            self.xfer.note("migrate.state", nbytes)
            req.host_blob = blob
            self._free_slot_state(req, free_kv=False)
        else:
            nbytes = 0
            self._free_slot_state(req)
            req.filled = 0
            req.ctx = list(req.prompt) + req.out[:-1]
            req.resume_decode = True
        req.slot = None
        req.forced_shard = to_shard
        req.n_migrations += 1
        self.migrations += 1
        self._c_migr_bytes.value += nbytes
        self.queue.append(req)
        tr = self.tel.tracer
        if tr.enabled:
            tr.instant("req.migrated", "request", TID_REQUEST,
                       {"rid": req.rid, "slot": slot, "from": src,
                        "to": to_shard, "mode": mode, "bytes": nbytes})

    def _sample_rows(self, logits: np.ndarray) -> np.ndarray:
        return self._draw(logits, self._temp, self._topk, self._topp)

    def _sample_one(self, logits_row: np.ndarray, req: Request) -> int:
        sp = req.sampling
        return int(self._draw(
            logits_row[None], np.asarray([sp.temperature], np.float32),
            np.asarray([sp.top_k], np.int32),
            np.asarray([sp.top_p], np.float32))[0])

    def _draw(self, logits: np.ndarray, temp: np.ndarray, topk: np.ndarray,
              topp: np.ndarray) -> np.ndarray:
        self.sample_calls += 1
        self.sample_greedy_calls += samplers.all_greedy(temp)
        self.rng, sub = jax.random.split(self.rng)
        return np.asarray(self._sample(
            jnp.asarray(logits), sub, jnp.asarray(temp), jnp.asarray(topk),
            jnp.asarray(topp)))

    def _stage(self, name: str, value) -> jax.Array:
        return self.xfer.stage(name, value, self.kv_sharding)

    # ------------------------------------------------------------------
    def _plan_prefill(self):
        """Per-shard FIFO chunk plans (at most one chunk per request per
        tick; each shard spends its own per-tick prefill-token budget)."""
        plans = []
        for s in range(self.D):
            prefilling = sorted(
                (r for r in self.slots[s * self.Bs:(s + 1) * self.Bs]
                 if r is not None and r.state == PREFILL),
                key=lambda r: r.rid)
            triples = []
            for r in prefilling:
                _, ls = self.kv.shard_of(r.slot)
                triples.append((ls, len(r.context), r.filled))
            plans.append(deque(self.admission.plan_chunks(triples)))
        return plans

    def _dispatch_prefill_round(self, chunks):
        """One fixed-shape sharded prefill call: ``chunks[s]`` is shard
        s's PrefillChunk or None.  Returns (op, logits_dev, completions)."""
        C = self.chunk_size
        toks = np.zeros((self.D, C), np.int32)
        slots = np.zeros((self.D,), np.int32)
        offs = np.zeros((self.D,), np.int32)
        valids = np.zeros((self.D,), np.int32)
        acts = np.zeros((self.D,), bool)
        bts = (np.zeros((self.D, self.kv.pages_per_seq), np.int32)
               if self.paged else None)
        live = []
        for s, ch in enumerate(chunks):
            if ch is None:
                continue
            gslot = s * self.Bs + ch.slot
            req = self.slots[gslot]
            if not self.kv.has_room(gslot, ch.n):
                raise ValueError(
                    f"prefill chunk ({ch.n} tokens at offset {ch.start}) "
                    f"overruns slot {gslot}'s cache "
                    f"(len={self.kv.length_of(gslot)}, "
                    f"max_seq={self.max_seq})")
            toks[s, :ch.n] = req.context[ch.start:ch.start + ch.n]
            slots[s] = ch.slot
            offs[s] = ch.start
            valids[s] = ch.n
            acts[s] = True
            if self.paged:
                bts[s] = self.kv.shards[s].block_tables[ch.slot]
            live.append((s, req, ch))

        tr = self.tel.tracer
        with tr.span("prefill.round", "stage", TID_ENGINE,
                     ({"shards": len(live), "tokens": int(valids.sum())}
                      if tr.enabled else None)):
            args = [self.params,
                    self._stage("prefill.tokens", toks), self.cache,
                    self._stage("prefill.slots", slots),
                    self._stage("prefill.offsets", offs),
                    self._stage("prefill.valids", valids),
                    self._stage("prefill.actives", acts)]
            if self.paged:
                args.append(self._stage("prefill.block_tables", bts))
            logits_d, self.cache = self._prefill(*args)
            op = self.xfer.dispatch("prefill", logits_d)

        completions = []
        for s, req, ch in live:
            self.model_calls += 1
            self.prefill_calls += 1
            req.filled += ch.n
            self.kv.advance(req.slot, ch.n)
            if self.proposer is not None:
                self.proposer.prefill_chunk(req.slot, toks[s], ch.start,
                                            ch.n)
            if req.filled == len(req.context):
                completions.append((s, req))
        return op, logits_d, completions

    # ------------------------------------------------------------------
    def tick(self) -> None:
        """One pipelined engine tick (phases A, B/C per wave, D — see the
        module docstring)."""
        t0 = time.perf_counter()
        did = False
        tick_ops = []
        tr = self.tel.tracer

        with tr.span("tick", "engine"):
            # -- phase A: dispatch prefill rounds (hidden behind the
            #    waves' in-flight decodes from last tick)
            with tr.span("admit"):
                self._admit()
            plans = self._plan_prefill()
            # phase attribution for the transfer meter: a tick with
            # prefill work is "prefill", a pure-decode tick is "drain" —
            # the phase where the single-wave schedule used to collapse
            self.xfer.set_phase("prefill" if any(plans) else "drain")
            pending_first = []  # (op, logits_dev, [(shard, req)])
            busy = np.zeros((self.D,), bool)
            while any(plans):
                chunks = [p.popleft() if p else None for p in plans]
                op, logits_d, completions = self._dispatch_prefill_round(
                    chunks)
                tick_ops.append(op)
                busy |= np.asarray([c is not None for c in chunks])
                if completions:
                    pending_first.append((op, logits_d, completions))
                did = True

            # -- phases B/C, once per wave: consume the wave's last
            #    results, then redispatch it.  Wave w's fetch and input
            #    staging hide behind wave 1-w's still-in-flight op (and
            #    phase A's prefill ops) — the dual-stream shadow that
            #    holds in drain ticks too.
            for w in range(self.n_waves):
                did |= self._consume_wave(w)
                did |= self._dispatch_wave(w, busy)

            # -- phase D: first tokens off completed prefills (hidden
            #    behind the waves' just-dispatched calls)
            if pending_first:
                with tr.span("first_tokens"):
                    for op, logits_d, completions in pending_first:
                        logits_h = self.xfer.fetch("prefill.logits",
                                                   logits_d, of=op)
                        for s, req in completions:
                            # a fresh request emits its first token off
                            # these logits; a resume-prefill does not
                            # (its pending token is out[-1])
                            self._finish_prefill(
                                req,
                                lambda row=logits_h[s], r=req:
                                self._sample_one(row, r))

            for op in tick_ops:  # prefill ops cannot shadow past the tick
                self.xfer.retire(op)
        if did:
            self._busy_ticks += busy
            self.ticks += 1
            self._h_tick.record(time.perf_counter() - t0)

    # ------------------------------------------------------------------
    def _consume_wave(self, w: int) -> bool:
        """Phase B for wave ``w``: fetch its in-flight logits (hidden
        behind the other wave's op), sample/accept, emit."""
        pend = self._pending_wave[w]
        if pend is None:
            return False
        self._pending_wave[w] = None
        kind = pend["kind"]
        tr = self.tel.tracer
        with tr.span("wave.consume", "wave", TID_ENGINE,
                     ({"wave": w, "kind": kind,
                       "rows": int(np.asarray(pend["mask"]).sum())}
                      if tr.enabled else None)):
            logits_h = self.xfer.fetch(
                f"{kind}.w{w}.logits", pend["logits"], of=pend["op"])
            now = time.monotonic()
            if kind == "decode":
                sampled = self._sample_rows(logits_h)
                for b, req in enumerate(self.slots):
                    if req is None or not pend["mask"][b]:
                        continue
                    if req.cancel_requested:
                        # deferred cancel: the dispatch this consume
                        # settles was already in flight when cancel()
                        # ran — tear the slot down now instead
                        self._free_slot_state(req)
                        self._finalize_cancel(req)
                        continue
                    if req.state == DECODE:
                        self._emit(req, int(sampled[b]), now)
                        if not req.done and req.migrate_to is not None:
                            self._do_migrate(req, *req.migrate_to)
            else:
                self._consume_verify(pend, logits_h, now)
        return True

    def _dispatch_wave(self, w: int, busy: np.ndarray) -> bool:
        """Phase C for wave ``w``: assign/rebalance free decoding slots,
        dispatch the wave's decode step (or speculative verify)."""
        decoding = np.asarray(
            [r is not None and r.state == DECODE for r in self.slots])
        in_flight = np.zeros((self.B,), bool)
        for pend in self._pending_wave:
            if pend is not None:
                in_flight |= np.asarray(pend["mask"])
        # only slots with no un-consumed dispatch may join or change
        # waves (waves never share a slot); rebalance-on-completion runs
        # here, so a collapsed wave refills from the survivor's freed
        # slots — the moved slots idle this round (bounded bubble)
        free = decoding & ~in_flight
        self.waves.assign(np.flatnonzero(free))
        mask = free & (np.asarray(self.waves.wave) == w)
        if not mask.any():
            return False
        if self.spec is None:
            # over-commit: a dry pool preempts a victim here (possibly
            # narrowing the wave) before the decode is dispatched; the
            # verify path prices its own per-row draft room instead
            mask = self._ensure_room(mask)
            if not mask.any():
                return False
        rows = int(mask.sum())
        # per-wave decode occupancy: rows riding this dispatch, the
        # wave-imbalance bubble signal (histogram + live gauge w/ peak)
        self._h_wave_occ.record(rows)
        self._g_wave[w].set(rows)
        tr = self.tel.tracer
        with tr.span("wave.dispatch", "wave", TID_ENGINE,
                     ({"wave": w, "rows": rows,
                       "kind": ("verify" if self.spec is not None
                                else "decode")}
                      if tr.enabled else None)):
            if self.spec is not None:
                self._dispatch_verify_wave(w, mask)
            else:
                self._dispatch_plain_wave(w, mask)
        self.model_calls += 1
        busy |= mask.reshape(self.D, self.Bs).any(axis=1)
        return True

    def _dispatch_plain_wave(self, w: int, mask: np.ndarray) -> None:
        """One single-token sharded decode step over wave ``w``'s slots.

        The call is full-shape: non-wave rows tag along.  Their writes
        land at their *staged* length — one past any in-flight wave's
        real write (lengths advance at dispatch), so the garbage is
        overwritten by that row's own next dispatch and masked until then
        (unallocated paged positions resolve to the null page)."""
        if self.paged:
            logits_d, self.cache = self._step(
                self.params,
                self._stage(f"decode.w{w}.tokens", self.cur_tok),
                self.cache,
                self._stage(f"decode.w{w}.lengths",
                            self.kv.lengths_array()),
                self._stage(f"decode.w{w}.block_tables",
                            self.kv.block_tables_array()),
                self._stage(f"decode.w{w}.actives",
                            mask.reshape(self.D, self.Bs)))
        else:
            logits_d, self.cache = self._step(
                self.params,
                self._stage(f"decode.w{w}.tokens", self.cur_tok),
                self.cache,
                self._stage(f"decode.w{w}.lengths",
                            self.kv.lengths_array()),
                self._stage(f"decode.w{w}.actives",
                            mask.reshape(self.D, self.Bs)))
        self.kv.advance_mask(mask)
        op = self.xfer.dispatch(f"decode.w{w}", logits_d)
        self._pending_wave[w] = {
            "kind": "decode", "op": op, "logits": logits_d, "mask": mask}

    def _dispatch_verify_wave(self, w: int, mask: np.ndarray) -> None:
        """One sharded speculative verify over wave ``w``'s slots.

        In spec mode EVERY wave dispatch is a verify — even when no slot
        proposed anything (the zero-draft plain-step optimization of the
        single-device engine is deliberately not taken): a plain step's
        tag-along rows write at their base position, which for the other
        wave's in-flight verify rows is a *draft* position that must
        survive until its commit.  Verify parks non-wave rows completely
        (``lengths >= max_seq`` drops every write; ``valids == 0`` gates
        ring/state commits), so the waves cannot corrupt each other.

        Host lengths do NOT advance at dispatch; the consume-side
        ``kv.rewind(slot, L + accepted + 1)`` settles them (and returns
        rejected paged pages to the slot's reservation).

        The dispatch width is *wave-adaptive*: the chunk holds
        ``W = max(counts over the wave) + 1`` positions instead of a
        fixed ``k + 1``, so a wave whose slots proposed little (the
        per-slot :class:`~repro.serving.speculative.AdaptiveDraft` caps
        bound ``counts``) pays proportionally less verify compute — a
        zero-proposal wave collapses to ``W == 1``, a plain decode
        step's position-axis cost.  Each distinct width jit-traces once
        (W is bounded by k+1)."""
        if self.spec.tree:
            self._dispatch_tree_verify_wave(w, mask)
            return
        k = self.spec.k
        lengths_h = self.kv.lengths_array().reshape(self.B).copy()
        caps = speculative.draft_caps(self.slots, lengths_h, mask, k,
                                      self.seq_ceiling,
                                      adaptive=self.adaptive)
        draft, counts = self.proposer.propose(
            self.slots, self.cur_tok.reshape(self.B, 1), lengths_h, mask,
            caps)
        # over-commit: preempting for draft room may narrow the wave —
        # cleared rows park (lengths >= max_seq, valids == 0) and write
        # nothing this verify; a fully-narrowed wave still dispatches
        # parked (cheap, and the caller's accounting stays uniform)
        mask = self._ensure_room(mask, counts + 1)
        W = int(counts[mask].max(initial=0)) + 1
        self._record_verify_width(w, W)
        # rows narrowed out of the wave may carry counts > W - 1; they
        # are parked (valids == 0) so clamping is cosmetic but keeps
        # every stored count consistent with the dispatched width
        counts = np.minimum(counts, W - 1)
        toks = np.zeros((self.B, W), np.int32)
        toks[:, 0] = self.cur_tok.reshape(self.B)
        toks[:, 1:] = draft[:, :W - 1]
        vlen = np.where(mask, lengths_h, self.max_seq).astype(np.int32)
        valids = np.where(mask, counts + 1, 0).astype(np.int32)
        toks_d = toks.reshape(self.D, self.Bs, W)
        vlen_d = vlen.reshape(self.D, self.Bs)
        prev_cache = None
        traj = None
        if self.paged:
            if self._state_store is not None:
                # mixed paged: snapshot + trajectory settle the slot-
                # resident rings/states one tick later (consume side);
                # kv.rewind releases the attn side's rejected pages
                prev_cache = self.cache
                logits_d, self.cache, traj = self._verify(
                    self.params,
                    self._stage(f"verify.w{w}.tokens", toks_d),
                    self.cache,
                    self._stage(f"verify.w{w}.lengths", vlen_d),
                    self._stage(f"verify.w{w}.valids",
                                valids.reshape(self.D, self.Bs)),
                    self._stage(f"verify.w{w}.block_tables",
                                self.kv.block_tables_array()))
            else:
                logits_d, self.cache = self._verify(
                    self.params,
                    self._stage(f"verify.w{w}.tokens", toks_d),
                    self.cache,
                    self._stage(f"verify.w{w}.lengths", vlen_d),
                    self._stage(f"verify.w{w}.block_tables",
                                self.kv.block_tables_array()))
        elif self._state_store is not None:
            # the verify base IS the rewind snapshot (immutable arrays);
            # its commit applies one tick later to whatever the cache has
            # become — safe because commit is per-row identity for rows
            # with counts == 0 and nothing else touches the wave's rows
            # while it is in flight (the other wave's verify parks them)
            prev_cache = self.cache
            logits_d, self.cache, traj = self._verify(
                self.params,
                self._stage(f"verify.w{w}.tokens", toks_d), self.cache,
                self._stage(f"verify.w{w}.lengths", vlen_d),
                self._stage(f"verify.w{w}.valids",
                            valids.reshape(self.D, self.Bs)))
        else:
            logits_d, self.cache = self._verify(
                self.params,
                self._stage(f"verify.w{w}.tokens", toks_d), self.cache,
                self._stage(f"verify.w{w}.lengths", vlen_d))
        self.spec_ticks += 1
        op = self.xfer.dispatch(f"verify.w{w}", logits_d)
        self._pending_wave[w] = {
            "kind": "verify", "op": op, "logits": logits_d, "mask": mask,
            "draft": draft, "counts": counts, "lengths": lengths_h,
            "valids": valids, "width": W,
            "prev_cache": prev_cache, "traj": traj}

    def _record_verify_width(self, w: int, W: int) -> None:
        self._wave_vwidth[w] = W
        self._vwidth_min = W if self._vwidth_min == 0 else min(
            self._vwidth_min, W)
        self._vwidth_max = max(self._vwidth_max, W)

    def _dispatch_tree_verify_wave(self, w: int, mask: np.ndarray) -> None:
        """One sharded *tree* verify over wave ``w``'s slots: each slot
        proposes a branchy token tree, every node verifies in the same
        chunk under its per-row ancestor bitmask, and node K/V land at
        flat chunk offsets while rope/learned embeddings use logical
        root-path depths.  Same parking/one-tick-delay contract as the
        linear dispatch; accept + path compaction happen at consume."""
        k = self.spec.k
        lengths_h = self.kv.lengths_array().reshape(self.B).copy()
        caps = speculative.draft_caps(self.slots, lengths_h, mask, k,
                                      self.seq_ceiling,
                                      adaptive=self.adaptive)
        trees = self.proposer.propose_tree(
            self.slots, self.cur_tok.reshape(self.B, 1), lengths_h, mask,
            caps, branch=self.spec.branch)
        tokens_a, parents, n_nodes, anc, depths = speculative.tree_arrays(
            trees, k, k + 1)
        mask = self._ensure_room(mask, n_nodes + 1)
        W = int(n_nodes[mask].max(initial=0)) + 1
        self._record_verify_width(w, W)
        toks = np.zeros((self.B, W), np.int32)
        toks[:, 0] = self.cur_tok.reshape(self.B)
        toks[:, 1:] = tokens_a[:, :W - 1]
        vlen = np.where(mask, lengths_h, self.max_seq).astype(np.int32)
        # truncating the (k+1)-wide masks to the wave width keeps every
        # wave row intact (its n_nodes bound W) and keeps parked rows'
        # causal-default rows causal
        anc_w = np.ascontiguousarray(anc[:, :W, :W])
        dep_w = np.ascontiguousarray(depths[:, :W])
        if self.paged:
            logits_d, self.cache = self._verify_tree(
                self.params,
                self._stage(f"verify.w{w}.tokens",
                            toks.reshape(self.D, self.Bs, W)),
                self.cache,
                self._stage(f"verify.w{w}.lengths",
                            vlen.reshape(self.D, self.Bs)),
                self._stage(f"verify.w{w}.block_tables",
                            self.kv.block_tables_array()),
                self._stage(f"verify.w{w}.anc",
                            anc_w.reshape(self.D, self.Bs, W, W)),
                self._stage(f"verify.w{w}.depths",
                            dep_w.reshape(self.D, self.Bs, W)))
        else:
            logits_d, self.cache = self._verify_tree(
                self.params,
                self._stage(f"verify.w{w}.tokens",
                            toks.reshape(self.D, self.Bs, W)),
                self.cache,
                self._stage(f"verify.w{w}.lengths",
                            vlen.reshape(self.D, self.Bs)),
                self._stage(f"verify.w{w}.anc",
                            anc_w.reshape(self.D, self.Bs, W, W)),
                self._stage(f"verify.w{w}.depths",
                            dep_w.reshape(self.D, self.Bs, W)))
        self.spec_ticks += 1
        op = self.xfer.dispatch(f"verify.w{w}", logits_d)
        self._pending_wave[w] = {
            "kind": "verify", "tree": True, "op": op, "logits": logits_d,
            "mask": mask, "tokens": tokens_a, "parents": parents,
            "n_nodes": n_nodes, "lengths": lengths_h, "width": W}

    def _consume_verify(self, pend: dict, logits_h: np.ndarray,
                        now: float) -> None:
        """Accept/reject a wave's verify results one tick after dispatch:
        the standard spec settle (accept a draft prefix + one bonus or
        corrective token per row), then per-shard length/page rewind and
        — for hybrid stacked — the sharded StateStore commit."""
        if pend.get("tree"):
            self._consume_tree_verify(pend, logits_h, now)
            return
        mask, draft = pend["mask"], pend["draft"]
        counts, base = pend["counts"], pend["lengths"]
        W = pend["width"]  # logits are (B, W, V); draft rides (B, W-1)
        if W == 1:
            # zero-proposal wave: the width-1 verify is a decode step in
            # verify clothing.  spec_accept_batch needs k >= 1, so pad a
            # dummy draft position — with counts == 0 nothing past
            # position 0 is read and next_tok still samples off
            # logits[:, 0] with the same rng stream
            logits_h = np.concatenate([logits_h, logits_h], axis=1)
            draft_s = np.zeros((self.B, 1), np.int32)
        else:
            draft_s = draft[:, :W - 1]
        self.rng, sub = jax.random.split(self.rng)
        n_acc, next_tok = jax.device_get(self._accept(
            jnp.asarray(logits_h), jnp.asarray(draft_s),
            jnp.asarray(counts), sub, jnp.asarray(self._temp),
            jnp.asarray(self._topk), jnp.asarray(self._topp)))
        if self._state_store is not None:
            commit = np.where(mask, n_acc + 1, 0).astype(np.int32)
            self.cache = self._state_store.commit_sharded(
                self.mesh, pend["prev_cache"], self.cache, pend["traj"],
                base.reshape(self.D, self.Bs),
                commit.reshape(self.D, self.Bs),
                pend["valids"].reshape(self.D, self.Bs),
                chunk=W)
        for b in range(self.B):
            req = self.slots[b]
            if not mask[b] or req is None:
                continue
            if req.cancel_requested:
                # deferred cancel (see _consume_wave): drop the verify
                # results — the slot's pages/draft state release here
                self._free_slot_state(req)
                self._finalize_cancel(req)
                continue
            m = int(n_acc[b])
            self._h_accept.record(m)
            self.spec_proposed += int(counts[b])
            self.spec_accepted += m
            if self.adaptive is not None:
                self.adaptive.observe(b, int(counts[b]), m)
            L = int(base[b])
            for tok in list(draft[b, :m]) + [int(next_tok[b])]:
                self._emit(req, int(tok), now)
                self.spec_emitted += 1
                if req.done:
                    break
            else:
                # request lives on: commit cur_tok + the m accepted
                # drafts on the slot's own shard
                self.kv.rewind(b, L + m + 1)
                self.proposer.commit(b, req.prompt + req.out, L + m + 1)
                if req.migrate_to is not None:
                    self._do_migrate(req, *req.migrate_to)

    def _consume_tree_verify(self, pend: dict, logits_h: np.ndarray,
                             now: float) -> None:
        """Tree-verify settle, one tick after dispatch: pick the longest
        accepted root-to-leaf path per row (``sampler.spec_accept_tree``),
        compact the surviving path's K/V from scattered flat chunk
        positions to contiguous ``L+1..L+m`` (one sharded gather/scatter,
        BEFORE any rewind releases pages), then emit/rewind/commit."""
        mask, base = pend["mask"], pend["lengths"]
        tokens_a, parents = pend["tokens"], pend["parents"]
        n_nodes, W = pend["n_nodes"], pend["width"]
        k = self.spec.k
        self.rng, sub = jax.random.split(self.rng)
        n_acc, acc, next_tok = jax.device_get(self._accept_tree(
            jnp.asarray(logits_h), jnp.asarray(tokens_a[:, :W - 1]),
            jnp.asarray(parents[:, :W - 1]), jnp.asarray(n_nodes), sub,
            jnp.asarray(self._temp), jnp.asarray(self._topk),
            jnp.asarray(self._topp)))
        acc = np.asarray(acc, bool)
        paths = [np.flatnonzero(acc[b, 1:]) + 1 if mask[b]
                 else np.zeros(0, np.int64) for b in range(self.B)]
        src = np.full((self.B, k), self.max_seq, np.int32)
        dst = np.full((self.B, k), self.max_seq, np.int32)
        need = False
        for b in range(self.B):
            m = len(paths[b])
            if m == 0:
                continue
            L = int(base[b])
            src[b, :m] = L + paths[b]
            dst[b, :m] = L + 1 + np.arange(m)
            if not np.array_equal(paths[b], np.arange(1, m + 1)):
                need = True
        if need:
            # rows of the *other* wave (and parked rows) carry
            # src == dst == max_seq: their copies drop, so compaction
            # can never disturb an in-flight verify's draft positions
            put = functools.partial(jax.device_put, device=self.kv_sharding)
            if self.paged:
                self.cache = self._compact(
                    self.cache, put(src.reshape(self.D, self.Bs, k)),
                    put(dst.reshape(self.D, self.Bs, k)),
                    put(self.kv.block_tables_array()))
            else:
                self.cache = self._compact(
                    self.cache, put(src.reshape(self.D, self.Bs, k)),
                    put(dst.reshape(self.D, self.Bs, k)))
        for b in range(self.B):
            req = self.slots[b]
            if not mask[b] or req is None:
                continue
            if req.cancel_requested:
                self._free_slot_state(req)
                self._finalize_cancel(req)
                continue
            m = len(paths[b])
            self._h_accept.record(m)
            self.spec_proposed += int(n_nodes[b])
            self.spec_accepted += m
            if self.adaptive is not None:
                self.adaptive.observe_tree(b, int(n_nodes[b]), m)
            L = int(base[b])
            for tok in [int(tokens_a[b, j - 1]) for j in paths[b]] + [
                    int(next_tok[b])]:
                self._emit(req, int(tok), now)
                self.spec_emitted += 1
                if req.done:
                    break
            else:
                self.kv.rewind(b, L + m + 1)
                self.proposer.commit(b, req.prompt + req.out, L + m + 1)
                if req.migrate_to is not None:
                    self._do_migrate(req, *req.migrate_to)

    # ------------------------------------------------------------------
    def run(self, max_ticks: int = 10_000, *,
            on_stall: str = "raise") -> List[Request]:
        """Drive ticks until drained or ``max_ticks`` loop iterations
        pass; see :func:`repro.serving.engine.drain_engine` for the stall
        contract (the transfer log syncs either way)."""
        try:
            return drain_engine(
                self,
                lambda: (self.queue
                         or any(s is not None for s in self.slots)
                         or any(p is not None for p in self._pending_wave)),
                max_ticks, on_stall)
        finally:
            self.xfer.sync()

    # ------------------------------------------------------------------
    def utilization(self) -> np.ndarray:
        """Per-device busy-tick fraction (a shard is busy in a tick when it
        prefilled a chunk or decoded a slot)."""
        return self._busy_ticks / max(self.ticks, 1)

    def reset_counters(self) -> None:
        """Zero the schedule counters, latency histograms, recorded
        trace events, and the transfer log (benchmarks: call between a
        jit warm-up run and the measured workload so ticks, model calls,
        utilization, overlap — and the dumped trace — cover the workload
        only; trace events and exposed-transfer counts stay in one-to-one
        correspondence because both clear at the same boundary).  Only
        valid while drained (no in-flight tick state)."""
        assert all(p is None for p in self._pending_wave)
        self.tel.reset()  # registry counters + histograms + trace events
        self._busy_ticks[:] = 0
        self.xfer.reset()

    # ------------------------------------------------------------------
    def dump_trace(self, path: str) -> str:
        """Write the recorded span timeline as Chrome/Perfetto trace
        JSON (requires ``telemetry=Telemetry(trace=True)``)."""
        return self.tel.dump_trace(path)

    def stats(self) -> Dict[str, float]:
        out = latency_stats(self)
        out.update({
            "ticks": self.ticks,
            "model_calls": self.model_calls,
            "prefill_calls": self.prefill_calls,
            "stalled": self.stalled,
            "stalled_queued": len(self.stalled_detail["queued"]),
            "stalled_in_flight": len(self.stalled_detail["in_flight"]),
            "mdk_mp_reuse": self.mdk_stats.reuse_factor().get("mp", 0),
            "sample_calls": self.sample_calls,
            "sample_greedy_calls": self.sample_greedy_calls,
            "n_shards": self.D,
            "decode_waves": self.n_waves,
            "mean_device_utilization": float(np.mean(self.utilization())),
            "tick_p50_ms": self._h_tick.quantile(0.5) * 1e3,
            "tick_p99_ms": self._h_tick.quantile(0.99) * 1e3,
            # per-wave decode occupancy (rows per dispatch) and the
            # membership imbalance bubble signal
            "wave_occupancy_mean": self._h_wave_occ.mean(),
            "wave_occupancy_p50": self._h_wave_occ.quantile(0.5),
            "wave_imbalance": self.waves.imbalance(),
            # live cross-shard migration (satellite of the lifecycle
            # core: requests leave a hot shard through migrate())
            "migrations": self.migrations,
            "migrated_bytes_total": self._c_migr_bytes.value,
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
                "draft_calls": getattr(self.proposer, "draft_calls", 0),
                "spec_accept_len_p50": self._h_accept.quantile(0.5),
                "spec_accept_len_p99": self._h_accept.quantile(0.99),
                # wave-width adaptive verify: last dispatched chunk
                # width per wave + run-wide extremes (a min below k+1
                # means some wave paid less than the fixed-width cost)
                "verify_width_min": self._vwidth_min,
                "verify_width_max": self._vwidth_max,
            })
            out.update({f"wave{w}_verify_width": self._wave_vwidth[w]
                        for w in range(self.n_waves)})
            if self.adaptive is not None:
                out.update(self.adaptive.stats())
        out.update(self.xfer.stats())
        out.update(self.kv.stats())
        return out
