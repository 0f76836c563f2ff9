"""Paged KV cache: bit-exactness vs the contiguous layout, page
refcount/free under slot churn, copy-free prefix sharing, page-priced
admission, the paged Pallas kernel, and the prefill overrun guard."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import lm
from repro.serving.admission import FIFOAdmission
from repro.serving.engine import Request, ServeEngine
from repro.serving.kv_cache import PagedCacheManager, SlotCacheManager


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = get_config("gpt2-345m").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=64)
    return cfg, params


def _mixed_prompts(vocab, lengths=(3, 17, 26, 40, 5), seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, vocab, int(n))) for n in lengths]


# ---------------------------------------------------------------------------
# bit-exactness: paged == stacked
# ---------------------------------------------------------------------------


def test_paged_engine_bitexact_vs_stacked(gpt2_setup):
    """Greedy decode through the paged engine is token-for-token identical
    to the contiguous layout on mixed prompt lengths (the tentpole
    acceptance criterion)."""
    cfg, params = gpt2_setup
    prompts = _mixed_prompts(cfg.vocab_size)
    outs = {}
    for layout in ("paged", "stacked"):
        eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                          kv_layout=layout, chunk_size=8)
        for p in prompts:
            eng.submit(p, max_new=6)
        done = eng.run()
        assert len(done) == len(prompts)
        outs[layout] = {tuple(r.prompt): r.out for r in done}
    assert outs["paged"] == outs["stacked"]


def test_paged_replay_engine_bitexact_vs_stacked(gpt2_setup):
    """The replay (teacher-forcing) admission path is also layout-exact:
    paged decode gathers the same logical cache content."""
    cfg, params = gpt2_setup
    prompts = _mixed_prompts(cfg.vocab_size, lengths=(4, 11, 7), seed=3)
    outs = {}
    for layout in ("paged", "stacked"):
        eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                          kv_layout=layout, prefill_mode="replay")
        for p in prompts:
            eng.submit(p, max_new=4)
        outs[layout] = {tuple(r.prompt): r.out for r in eng.run()}
    assert outs["paged"] == outs["stacked"]


def test_paged_prefill_matches_contiguous_cache_content(gpt2_setup):
    """prefill_into_slot through a block table leaves each page holding
    exactly the contiguous slot's K/V at the corresponding positions."""
    cfg, params = gpt2_setup
    max_seq, ps = 64, 16
    n_pg = max_seq // ps
    prompt = list(np.random.default_rng(7).integers(1, cfg.vocab_size, 37))
    B, slot = 2, 1
    cache_s = lm.init_cache(cfg, B, max_seq)
    P = 1 + B * n_pg
    cache_p = lm.init_cache(cfg, P, ps, layout="paged")
    bt_row = jnp.asarray([6, 3, 1, 7], jnp.int32)  # deliberately scrambled

    C, pos = 8, 0
    while pos < len(prompt):
        n = min(C, len(prompt) - pos)
        chunk = np.zeros((C,), np.int32)
        chunk[:n] = prompt[pos:pos + n]
        last_s, cache_s = lm.prefill_into_slot(
            params, cfg, jnp.asarray(chunk), cache_s, slot, pos, valid=n)
        last_p, cache_p = lm.prefill_into_slot(
            params, cfg, jnp.asarray(chunk), cache_p, 0, pos, valid=n,
            block_table=bt_row)
        pos += n
    np.testing.assert_array_equal(np.asarray(last_s), np.asarray(last_p))
    for ls, lp in zip(jax.tree_util.tree_leaves(cache_s),
                      jax.tree_util.tree_leaves(cache_p)):
        ax = 1 if ls.ndim == 5 else 0  # periods stack batch/pages on axis 1
        a = jnp.take(ls, slot, axis=ax)[..., :len(prompt), :]
        g = jnp.take(lp, bt_row, axis=ax)  # (.., n_pg, Hkv, ps, hd)
        g = jnp.moveaxis(g, ax, -3)  # page axis next to its token axis
        b = g.reshape(g.shape[:-4] + (g.shape[-4], n_pg * ps, g.shape[-1]))
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b[..., :len(prompt), :]))


# ---------------------------------------------------------------------------
# page lifecycle: refcounts, churn, deterministic reuse
# ---------------------------------------------------------------------------


def test_page_refcount_and_free_under_slot_churn(gpt2_setup):
    """Many requests through few slots: every page returns to the pool,
    refcounts drain to zero, and the peak never exceeds the pool."""
    cfg, params = gpt2_setup
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      page_size=8, chunk_size=8)
    rng = np.random.default_rng(2)
    for i in range(7):
        plen = int(rng.integers(3, 40))
        eng.submit(list(rng.integers(1, cfg.vocab_size, plen)), max_new=5)
    done = eng.run()
    assert len(done) == 7
    kv = eng.kv
    assert kv.pages_in_use == 0
    assert kv.n_free_pages == kv.n_pages - 1  # all but the null page
    np.testing.assert_array_equal(np.asarray(kv._refcount), 0)
    assert kv.pages_in_use_peak <= kv.n_pages - 1
    assert (kv.block_tables == 0).all()
    # every surviving prefix-map entry is a refcount-0 *cached* free page
    # (content retained for future sharers, reclaimable on demand)
    for pid in kv._page_hash:
        assert kv.refcount(pid) == 0 and pid in kv._free_cached_set


def test_paged_manager_deterministic_reuse_order():
    cfg = get_config("gpt2-345m").reduced()
    kv = PagedCacheManager(cfg, 3, 32, page_size=8)
    s0, _ = kv.alloc([1, 2, 3], max_new=1)
    s1, _ = kv.alloc([4, 5, 6], max_new=1)
    assert (s0, s1) == (0, 1)
    p0 = list(kv._slot_pages[0])
    kv.free(0)
    kv.free(1)
    s2, _ = kv.alloc([7, 8], max_new=1)
    assert s2 == 0  # lowest slot first, heap order
    assert kv._slot_pages[0][0] == p0[0]  # lowest page id reused first


def test_slot_manager_heap_free_list_order():
    """Satellite: heap-backed free list keeps the seed's deterministic
    lowest-first reuse order."""
    cfg = get_config("gpt2-345m").reduced()
    kv = SlotCacheManager(cfg, 4, 32)
    slots = [kv.alloc() for _ in range(4)]
    assert slots == [0, 1, 2, 3]
    kv.free(2)
    kv.free(0)
    kv.free(3)
    assert kv.alloc() == 0 and kv.alloc() == 2 and kv.alloc() == 3
    assert kv.alloc() is None


def test_admission_waits_for_pages(gpt2_setup):
    """Page-priced admission: with a deliberately tiny pool the engine
    serves requests one at a time instead of over-committing pages."""
    cfg, params = gpt2_setup
    # pool of 5 real pages; each request prices at 4 pages (24+8 tokens / 8)
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      page_size=8, chunk_size=8, n_pages=6)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(1, cfg.vocab_size, 24)) for _ in range(3)]
    for p in prompts:
        eng.submit(p, max_new=8)
    done = eng.run()
    assert len(done) == 3
    assert eng.kv.pages_in_use_peak <= 5
    # same stream on an ample pool must generate identical tokens
    ample = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                        page_size=8, chunk_size=8)
    for p in prompts:
        ample.submit(p, max_new=8)
    a = {tuple(r.prompt): r.out for r in ample.run()}
    assert {tuple(r.prompt): r.out for r in done} == a


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------


def _tick_until_decoding(eng, rid, limit=50):
    for _ in range(limit):
        req = next((r for r in eng.slots if r is not None and r.rid == rid),
                   None)
        if req is not None and req.state == "decode":
            return req
        eng.tick()
    raise AssertionError(f"request {rid} never reached decode")


def test_prefix_share_hit_allocates_zero_new_pages_for_prefix(gpt2_setup):
    """A request whose prompt extends a live request's prompt re-uses the
    full shared pages: zero fresh allocations for the prefix region, and
    the generated tokens still match a fresh no-sharing engine."""
    cfg, params = gpt2_setup
    ps = 8
    rng = np.random.default_rng(9)
    sys_prompt = list(rng.integers(1, cfg.vocab_size, 3 * ps))  # 3 full pages
    provider = sys_prompt + [7]
    consumer = sys_prompt + [11, 12]

    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      page_size=ps, chunk_size=8)
    rid_a = eng.submit(provider, max_new=30)
    _tick_until_decoding(eng, rid_a)

    before = eng.kv.pages_allocated_total
    eng.submit(consumer, max_new=6)
    eng._admit()  # admission claims the prompt's pages immediately
    # the shared 3-page prefix cost zero fresh allocations: only the tail
    # page (prompt pages 4 minus shared 3) was claimed
    assert eng.kv.pages_allocated_total - before == 1
    assert eng.kv.prefix_hit_pages == 3
    eng.run()
    outs = {tuple(r.prompt): r.out for r in eng.finished}

    solo = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                       page_size=ps, chunk_size=8, prefix_sharing=False)
    solo.submit(provider, max_new=30)
    solo.submit(consumer, max_new=6)
    solo_outs = {tuple(r.prompt): r.out for r in solo.run()}
    assert solo.kv.prefix_hit_pages == 0
    assert outs == solo_outs


def test_shared_pages_survive_provider_free(gpt2_setup):
    """Refcounting: freeing the request that first filled shared pages
    must not release them while a sharer is still decoding on them."""
    cfg, params = gpt2_setup
    ps = 8
    rng = np.random.default_rng(10)
    sys_prompt = list(rng.integers(1, cfg.vocab_size, 2 * ps))
    provider = sys_prompt + [5]
    consumer = sys_prompt + [9]

    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      page_size=ps, chunk_size=8)
    rid_a = eng.submit(provider, max_new=3)  # finishes (and frees) early
    _tick_until_decoding(eng, rid_a)
    eng.submit(consumer, max_new=8)
    done = eng.run()
    assert eng.kv.prefix_hit_pages == 2
    outs = {tuple(r.prompt): r.out for r in done}

    solo = ServeEngine(cfg, params, batch_slots=1, max_seq=64, eos_id=-1,
                       page_size=ps, chunk_size=8)
    solo.submit(consumer, max_new=8)
    assert outs[tuple(consumer)] == solo.run()[0].out
    assert eng.kv.pages_in_use == 0  # shared pages released with last sharer


def test_prefix_share_across_slot_churn_via_cached_pages(gpt2_setup):
    """The shared-system-prompt fleet case: a request admitted AFTER every
    same-prefix request already finished still shares — freed prefix pages
    are cached (content + map entry kept) until the pool reclaims them."""
    cfg, params = gpt2_setup
    ps = 8
    rng = np.random.default_rng(12)
    sys_prompt = list(rng.integers(1, cfg.vocab_size, 3 * ps))

    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      page_size=ps, chunk_size=8)
    eng.submit(sys_prompt + [5], max_new=3)
    eng.run()  # provider fully drained: its slot and pages are freed
    assert eng.kv.pages_in_use == 0
    assert eng.kv.stats()["cached_free_pages"] >= 3

    before = eng.kv.pages_allocated_total
    consumer = sys_prompt + [9, 10]
    eng.submit(consumer, max_new=6)
    eng._admit()
    # the 3-page prefix resurrected from the cached pool: only the tail
    # prompt page was freshly claimed
    assert eng.kv.pages_allocated_total - before == 1
    assert eng.kv.prefix_hit_pages == 3
    eng.run()
    out = next(r.out for r in eng.finished if tuple(r.prompt) ==
               tuple(consumer))

    solo = ServeEngine(cfg, params, batch_slots=1, max_seq=64, eos_id=-1,
                       page_size=ps, chunk_size=8)
    solo.submit(consumer, max_new=6)
    assert solo.run()[0].out == out


def test_same_wave_admission_defers_then_shares(gpt2_setup):
    """Two same-prefix requests submitted together: the second must never
    link the provider's pages while they are unfilled (readiness gate);
    admission defers it until the provider's prefill covers the prefix,
    then links — sharing with outputs identical to a no-sharing engine."""
    cfg, params = gpt2_setup
    ps = 8
    rng = np.random.default_rng(11)
    sys_prompt = list(rng.integers(1, cfg.vocab_size, 2 * ps))
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      page_size=ps, chunk_size=8)
    eng.submit(sys_prompt + [3], max_new=3)
    eng.submit(sys_prompt + [4], max_new=3)
    done = eng.run()
    assert len(done) == 2
    assert eng.kv.prefix_hit_pages == 2
    outs = {tuple(r.prompt): r.out for r in done}

    solo = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                       page_size=ps, chunk_size=8, prefix_sharing=False)
    solo.submit(sys_prompt + [3], max_new=3)
    solo.submit(sys_prompt + [4], max_new=3)
    assert {tuple(r.prompt): r.out for r in solo.run()} == outs


# ---------------------------------------------------------------------------
# paged Pallas kernel vs oracle (interpret mode; hypothesis-free sweeps)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,H,Hkv,D,ps,n_pg",
    [
        (2, 4, 4, 64, 16, 4),  # MHA
        (2, 8, 2, 64, 16, 4),  # GQA
        (1, 4, 1, 128, 8, 6),  # MQA, small pages
        (3, 2, 2, 32, 32, 2),  # page == two blocks
        (2, 24, 8, 128, 16, 12),  # GQA group 3, D 128, 8 + 4 page blocks
        (4, 4, 4, 64, 16, 20),  # three blocks of 8 pages, the last short
    ],
)
def test_paged_kernel_matches_oracle(B, H, Hkv, D, ps, n_pg):
    rng = np.random.default_rng(B * 131 + H * 17 + ps)
    P = 1 + B * n_pg
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    bt = jnp.asarray(
        1 + rng.permutation(B * n_pg).reshape(B, n_pg), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, n_pg * ps + 1, (B,)), jnp.int32)
    out = ops.paged_mha_decode(q, kp, vp, lengths, bt, backend="interpret")
    want = ops.paged_mha_decode(q, kp, vp, lengths, bt, backend="jnp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)


# pages of 16 make blocks of 8 pages (128 keys); 20 pages per row end in
# a block of 4
_EDGE_PS, _EDGE_NPG = 16, 20


@pytest.mark.parametrize(
    "lengths,active,window",
    [
        ((0, 1, 16, 17), None, 0),  # empty row, page edges
        ((128, 129, 127, 320), None, 0),  # block edges, a full row
        ((200, 0, 64, 300), (True, False, False, True), 0),  # tag-alongs
        ((150, 129, 280, 320), None, 40),  # window floor across a block
    ],
)
def test_paged_decode_kernel_visits_live_pages(lengths, active, window):
    """Decode visits only each row's live pages: rows of length 0 and
    rows ``active`` marks False finalize to zero, lengths at page and
    block edges and a full row match the oracle.  The expectation is the
    verify oracle at one position, which also covers length 0 (the decode
    oracle's softmax over no key is undefined there)."""
    B, H, Hkv, D = len(lengths), 4, 2, 64
    rng = np.random.default_rng(sum(lengths) + window)
    q, kp, vp, _, bt = _verify_case(rng, B, H, Hkv, D, _EDGE_PS, _EDGE_NPG,
                                    1)
    lens = jnp.asarray(lengths, jnp.int32)
    act = None if active is None else jnp.asarray(active)
    out = ops.paged_mha_decode(q[:, 0], kp, vp, lens, bt, window=window,
                               active=act, backend="interpret")
    want = ref.paged_verify_ref(q, kp, vp, lens - 1, bt, window=window)[:, 0]
    live = np.ones(B, bool) if active is None else np.asarray(active)
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(want)[live], rtol=3e-5, atol=3e-5)
    assert not np.asarray(out)[~live].any()


def test_paged_oracle_bitexact_vs_contiguous_oracle():
    """The paged reference is the contiguous reference applied to the
    block-table gather — bitwise, not just allclose."""
    rng = np.random.default_rng(0)
    B, H, Hkv, D, ps, n_pg = 3, 4, 2, 16, 8, 4
    P = 1 + B * n_pg
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(B * n_pg).reshape(B, n_pg),
                     jnp.int32)
    lengths = jnp.asarray(rng.integers(1, n_pg * ps + 1, (B,)), jnp.int32)
    paged = ref.paged_mha_decode_ref(q, kp, vp, lengths, bt)
    contiguous = ref.mha_decode_ref(
        q, ref.paged_gather_ref(kp, bt), ref.paged_gather_ref(vp, bt),
        lengths)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(contiguous))


def test_decode_step_tag_along_write_parks_on_null_page(gpt2_setup):
    """An inactive row riding the batched decode step must NOT write at
    its own length: with per-kind prefix sharing a prefilling sharer's
    length points into pages the prefix OWNER still reads, so the
    tag-along write parks on the null page instead.  Regression test for
    a live-prefix corruption the serving bench caught: the owner's
    stream diverged once a sharer was admitted mid-decode."""
    cfg, params = gpt2_setup
    ps, n_pg = 16, 4
    P = 1 + 2 * n_pg
    cache = lm.init_cache(cfg, P, ps, layout="paged")
    # row 1 (mid-prefill, length 0) links row 0's prompt page 1 — the
    # per-kind sharing shape.  Row 0 actively decodes at position 20.
    bt = jnp.asarray([[1, 2, 3, 4], [1, 6, 7, 8]], jnp.int32)
    lengths = jnp.asarray([20, 0], jnp.int32)
    toks = jnp.asarray([[5], [9]], jnp.int32)
    shared_before = jax.tree_util.tree_map(lambda t: t[:, 1], cache)
    _, new_cache = lm.decode_step(
        params, cfg, toks, cache, lengths,
        active=jnp.asarray([True, False]), block_table=bt)
    shared_after = jax.tree_util.tree_map(lambda t: t[:, 1], new_cache)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)),
        shared_before, shared_after)), (
        "tag-along row wrote into a linked (shared) prompt page")
    # the active row's write did land: its page 1 content is the page
    # named for position 20 -> block 1 -> page id 2
    own = jax.tree_util.tree_map(lambda t: t[:, 2], new_cache)
    own_before = jax.tree_util.tree_map(lambda t: t[:, 2], cache)
    assert not jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), own, own_before))


@pytest.mark.parametrize("trace", [True, False])
def test_decode_step_span_counts_live_pages(gpt2_setup, trace):
    """Traced, each ``decode.step`` span carries ``pages``: the sum over
    the step's decoding rows of ``ceil((len + 1) / ps)``, the (row, page)
    pairs the paged kernel visits per layer.  Untraced, the span gets no
    arguments at all."""
    from repro.serving.telemetry import NULL_TRACER, Telemetry

    cfg, params = gpt2_setup
    ps = 8
    eng = ServeEngine(cfg, params, batch_slots=3, max_seq=64, eos_id=-1,
                      kv_layout="paged", page_size=ps, chunk_size=8,
                      telemetry=Telemetry(trace=trace))
    calls, spans = [], []
    step = eng._step

    def recording(p, tok, cache, lengths, bt, active):
        calls.append((np.asarray(lengths).copy(), np.asarray(active)))
        return step(p, tok, cache, lengths, bt, active)

    class SpyTracer:  # the untraced tracer, with its span arguments kept
        enabled = False

        def span(self, name, cat="stage", tid=0, args=None):
            spans.append((name, args))
            return NULL_TRACER.span(name, cat, tid, args)

        def __getattr__(self, name):
            return getattr(NULL_TRACER, name)

    eng._step = recording
    if not trace:
        eng.tel.tracer = SpyTracer()
    for p in _mixed_prompts(cfg.vocab_size, lengths=(3, 17, 26, 9)):
        eng.submit(p, max_new=6)
    assert len(eng.run()) == 4
    want = [int(((ln[act] + ps) // ps).sum()) for ln, act in calls]
    assert len(want) > 3 and max(want) > 3
    if trace:
        got = [e[6]["pages"] for e in eng.tel.tracer.events
               if e[0] == "X" and e[1] == "decode.step"]
        assert got == want
    else:
        assert [a for n, a in spans if n == "decode.step"] == \
            [None] * len(want)


# ---------------------------------------------------------------------------
# paged verify kernel vs oracle (interpret mode; hypothesis-free sweeps)
# ---------------------------------------------------------------------------


def _verify_case(rng, B, H, Hkv, D, ps, n_pg, C):
    """Random paged-verify operands: pool with a null page, scrambled
    block tables, per-row bases anywhere the chunk still fits the pool."""
    P = 1 + B * n_pg
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    bt = jnp.asarray(
        1 + rng.permutation(B * n_pg).reshape(B, n_pg), jnp.int32)
    base = jnp.asarray(rng.integers(0, n_pg * ps - C + 1, (B,)), jnp.int32)
    return q, kp, vp, base, bt


@pytest.mark.parametrize(
    "B,H,Hkv,D,ps,n_pg,C,window",
    [
        (2, 4, 4, 64, 16, 4, 4, 0),   # MHA, k+1 = 4
        (2, 8, 2, 64, 16, 4, 6, 0),   # GQA
        (1, 4, 1, 128, 8, 6, 3, 0),   # MQA, small pages
        (3, 2, 2, 32, 32, 2, 8, 0),   # wide chunk, page == two blocks
        (2, 4, 4, 64, 16, 4, 4, 24),  # sliding window < live length
        (1, 4, 2, 64, 8, 6, 5, 8),    # window == page size
        (2, 24, 8, 128, 16, 12, 32, 0),  # GQA group 3, D 128, chunk 32
        (3, 4, 4, 64, 16, 20, 4, 136),  # window spans block edges
    ],
)
def test_paged_verify_kernel_matches_oracle(B, H, Hkv, D, ps, n_pg, C,
                                            window):
    """The scalar-prefetch verify kernel matches the gather-first oracle
    across page-size / window / chunk-width grids with per-row bases
    drawn anywhere in the pool (mid-page and page-edge landings)."""
    rng = np.random.default_rng(B * 977 + H * 31 + ps + C + window)
    q, kp, vp, base, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, C)
    out = ops.paged_verify(q, kp, vp, base, bt, window=window,
                           backend="interpret")
    want = ops.paged_verify(q, kp, vp, base, bt, window=window,
                            backend="jnp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("base0", [0, 7, 8, 15, 16, 28])
def test_paged_verify_kernel_page_edge_offsets(base0):
    """Deterministic base offsets at and around page boundaries: chunk
    entirely in page 0, straddling the first boundary, starting exactly
    on a boundary, and ending flush with the pool."""
    B, H, Hkv, D, ps, n_pg, C = 1, 2, 2, 32, 8, 4, 4
    rng = np.random.default_rng(base0)
    q, kp, vp, _, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, C)
    base = jnp.asarray([base0], jnp.int32)
    out = ops.paged_verify(q, kp, vp, base, bt, backend="interpret")
    want = ops.paged_verify(q, kp, vp, base, bt, backend="jnp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("base0,window,tree", [
    (_EDGE_NPG * _EDGE_PS - 32, 0, False),  # chunk ends on the last page
    (96, 0, False),  # chunk ends on the first block edge
    (128, 0, False),  # chunk starts on a block edge
    (_EDGE_NPG * _EDGE_PS, 0, False),  # parked past the pool
    (_EDGE_NPG * _EDGE_PS + 50, 24, False),  # parked, the window past it
    (150, 40, False),  # window floor in the block before the chunk
    (120, 0, True),  # tree chunk across a block edge
    (_EDGE_NPG * _EDGE_PS - 32, 0, True),  # tree chunk on the last page
])
def test_paged_verify_kernel_block_edges(base0, window, tree):
    """A 32-position chunk against rows of 20 pages (blocks of 8, 8 and
    4 pages): chunks at block edges and on the last page, parked rows
    and window floors, linear and tree masks."""
    B, H, Hkv, D, C = 1, 4, 2, 64, 32
    rng = np.random.default_rng(base0 + window + tree)
    q, kp, vp, _, bt = _verify_case(rng, B, H, Hkv, D, _EDGE_PS, _EDGE_NPG,
                                    C)
    base = jnp.asarray([base0], jnp.int32)
    anc = _random_anc(rng, B, C) if tree else None
    out = ops.paged_verify(q, kp, vp, base, bt, window=window, anc=anc,
                           backend="interpret")
    want = ops.paged_verify(q, kp, vp, base, bt, window=window, anc=anc,
                            backend="jnp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)


def _random_anc(rng, B, C):
    """Random per-row ancestor bitmasks: each row grows a random token
    tree (0..C-1 nodes, random parents) and takes its padded mask."""
    from repro.serving.speculative import TokenTree

    anc = np.zeros((B, C, C), bool)
    for b in range(B):
        t = TokenTree()
        for _ in range(int(rng.integers(0, C))):
            t.add(int(rng.integers(0, 100)), int(rng.integers(0, t.n + 1)))
        anc[b] = t.ancestor_mask(C)
    return jnp.asarray(anc)


@pytest.mark.parametrize(
    "B,H,Hkv,D,ps,n_pg,C",
    [
        (2, 4, 4, 64, 16, 4, 4),   # MHA
        (2, 8, 2, 64, 16, 4, 6),   # GQA
        (1, 4, 1, 128, 8, 6, 3),   # MQA, small pages
        (3, 2, 2, 32, 32, 2, 8),   # wide chunk, page == two blocks
        (2, 4, 2, 64, 8, 5, 5),    # GQA again, odd widths
        (2, 24, 8, 128, 16, 12, 8),  # GQA group 3, D 128, two blocks
    ],
)
def test_paged_verify_kernel_ancestor_mask_matches_oracle(B, H, Hkv, D,
                                                          ps, n_pg, C):
    """Tree verify: the ancestor-masked kernel matches the gather-first
    oracle across head / GQA / page-size / chunk-width grids with random
    branchy trees and per-row bases anywhere in the pool."""
    rng = np.random.default_rng(B * 977 + H * 31 + ps + C)
    q, kp, vp, base, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, C)
    anc = _random_anc(rng, B, C)
    out = ops.paged_verify(q, kp, vp, base, bt, anc=anc,
                           backend="interpret")
    want = ops.paged_verify(q, kp, vp, base, bt, anc=anc, backend="jnp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("base0", [0, 7, 8, 15, 16, 28])
def test_paged_verify_kernel_anc_page_edge_offsets(base0):
    """Tree chunks straddling page boundaries: deterministic bases at
    and around the edges, branchy masks."""
    B, H, Hkv, D, ps, n_pg, C = 1, 2, 2, 32, 8, 4, 4
    rng = np.random.default_rng(base0)
    q, kp, vp, _, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, C)
    base = jnp.asarray([base0], jnp.int32)
    anc = _random_anc(rng, B, C)
    out = ops.paged_verify(q, kp, vp, base, bt, anc=anc,
                           backend="interpret")
    want = ops.paged_verify(q, kp, vp, base, bt, anc=anc, backend="jnp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_paged_verify_causal_anc_is_bitwise_linear(backend):
    """A causal-tril ancestor mask (what a chain tree or an empty row
    produces) is BITWISE identical to the implicit-causal linear path on
    both backends — the tree-mode reduction that keeps greedy tree-spec
    streams byte-equal to plain decode."""
    B, H, Hkv, D, ps, n_pg, C = 2, 4, 2, 64, 16, 4, 4
    rng = np.random.default_rng(9)
    q, kp, vp, base, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, C)
    tril = jnp.asarray(
        np.broadcast_to(np.tril(np.ones((C, C), bool)), (B, C, C)))
    got = ops.paged_verify(q, kp, vp, base, bt, anc=tril, backend=backend)
    want = ops.paged_verify(q, kp, vp, base, bt, backend=backend)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_paged_verify_window_anc_mutually_exclusive():
    """Sliding windows cut the *prefix*; ancestor masks replace the
    in-chunk causal structure.  Combining them is undefined — both the
    dispatcher and the oracle refuse."""
    B, H, Hkv, D, ps, n_pg, C = 1, 2, 2, 32, 8, 2, 2
    rng = np.random.default_rng(0)
    q, kp, vp, base, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, C)
    anc = _random_anc(rng, B, C)
    with pytest.raises(ValueError, match="exclusive"):
        ops.paged_verify(q, kp, vp, base, bt, window=8, anc=anc)
    with pytest.raises(ValueError, match="exclusive"):
        ref.paged_verify_ref(q, kp, vp, base, bt, window=8, anc=anc)


def test_paged_verify_single_position_matches_decode_oracle():
    """A C=1 verify chunk is a decode step: the verify oracle at base =
    len-1 must agree with the decode oracle at lengths = len (the page
    already holds the position's own K/V in both framings)."""
    rng = np.random.default_rng(5)
    B, H, Hkv, D, ps, n_pg = 2, 4, 2, 32, 8, 3
    q, kp, vp, _, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, 1)
    lengths = jnp.asarray(rng.integers(1, n_pg * ps + 1, (B,)), jnp.int32)
    ver = ref.paged_verify_ref(q, kp, vp, lengths - 1, bt)
    dec = ref.paged_mha_decode_ref(q[:, 0], kp, vp, lengths, bt)
    np.testing.assert_allclose(
        np.asarray(ver[:, 0]), np.asarray(dec), rtol=3e-5, atol=3e-5)


try:  # mirror the decode sweeps: property-test only where hypothesis exists
    import importlib.util as _ilu
    _HAS_HYPOTHESIS = _ilu.find_spec("hypothesis") is not None
except Exception:  # pragma: no cover
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        data=st.data(),
        ps=st.sampled_from([8, 16]),
        n_pg=st.integers(2, 4),
        c=st.integers(1, 6),
        window=st.sampled_from([0, 8, 24]),
    )
    def test_paged_verify_kernel_property(data, ps, n_pg, c, window):
        """Property sweep: for any page size / page count / chunk width /
        window and any in-pool bases, kernel == oracle."""
        B, H, Hkv, D = 2, 4, 2, 32
        seed = data.draw(st.integers(0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        q, kp, vp, base, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, c)
        out = ops.paged_verify(q, kp, vp, base, bt, window=window,
                               backend="interpret")
        want = ops.paged_verify(q, kp, vp, base, bt, window=window,
                                backend="jnp")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)

    @settings(max_examples=25, deadline=None)
    @given(
        data=st.data(),
        ps=st.sampled_from([8, 16]),
        n_pg=st.integers(2, 4),
        c=st.integers(1, 6),
    )
    def test_paged_verify_kernel_tree_property(data, ps, n_pg, c):
        """Property sweep with random branchy ancestor masks: for any
        page size / page count / chunk width and in-pool bases, the
        tree kernel == the tree oracle."""
        B, H, Hkv, D = 2, 4, 2, 32
        seed = data.draw(st.integers(0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        q, kp, vp, base, bt = _verify_case(rng, B, H, Hkv, D, ps, n_pg, c)
        anc = _random_anc(rng, B, c)
        out = ops.paged_verify(q, kp, vp, base, bt, anc=anc,
                               backend="interpret")
        want = ops.paged_verify(q, kp, vp, base, bt, anc=anc,
                                backend="jnp")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)
else:
    @pytest.mark.skip(reason="hypothesis not installed; parametrized "
                      "sweeps above cover the same grid deterministically")
    def test_paged_verify_kernel_property():
        pass

    @pytest.mark.skip(reason="hypothesis not installed; parametrized "
                      "sweeps above cover the same grid deterministically")
    def test_paged_verify_kernel_tree_property():
        pass


# ---------------------------------------------------------------------------
# prefill overrun guard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["paged", "stacked"])
def test_prefill_overrun_raises_not_corrupts(gpt2_setup, layout):
    """A prompt longer than max_seq that slips past submit (e.g. via a
    custom admission front-end) must fail loudly, not silently corrupt
    the slot's mask accounting."""
    cfg, params = gpt2_setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1,
                      kv_layout=layout, chunk_size=8)
    eng.queue.append(Request(rid=99, prompt=list(range(1, 41)), max_new=2))
    with pytest.raises(ValueError, match="max_seq|overruns"):
        eng.run(max_ticks=20)


def test_submit_rejects_oversized_prompt(gpt2_setup):
    cfg, params = gpt2_setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1)
    # ValueError, not assert: validation must survive ``python -O``
    with pytest.raises(ValueError, match="fit the cache"):
        eng.submit(list(range(1, 40)), max_new=2)


def test_never_fitting_request_raises_instead_of_spinning(gpt2_setup):
    """A request whose lifetime page count exceeds the whole pool must
    raise at admission, not leave run() spinning on an un-admittable FIFO
    head forever."""
    cfg, params = gpt2_setup
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      page_size=8, n_pages=4)  # 3 real pages
    eng.submit(list(range(1, 31)), max_new=8)  # needs ceil(38/8)=5 pages
    with pytest.raises(ValueError, match="never"):
        eng.run(max_ticks=50)


def test_engine_rejects_non_divisor_page_size(gpt2_setup):
    """page_size must divide max_seq (bit-exactness invariant); the engine
    rejects a misconfiguration instead of silently substituting one."""
    cfg, params = gpt2_setup
    with pytest.raises(ValueError, match="divide"):
        ServeEngine(cfg, params, batch_slots=1, max_seq=48, eos_id=-1,
                    kv_layout="paged", page_size=32)


def test_page_price_matches_manager_admission():
    """FIFOAdmission.page_price is the formula the manager enforces: a
    request is admitted iff its price fits available_pages (no cached
    shared pages in play here)."""
    cfg = get_config("gpt2-345m").reduced()
    adm = FIFOAdmission(cfg, chunk_size=8)
    kv = PagedCacheManager(cfg, 3, 64, page_size=8, n_pages=9)  # 8 real
    # a holder pins 5 prompt pages + 1 reservation -> 2 pages available
    hold, _ = kv.alloc(list(range(1, 41)), 8, share=False)
    assert kv.available_pages == 2
    for plen, max_new in ((8, 8), (20, 8), (40, 8)):
        price = adm.page_price(plen, max_new, page_size=8, max_seq=64)
        fits = price <= kv.available_pages
        res = kv.alloc(list(range(1, plen + 1)), max_new, share=False)
        assert (res is not None) == fits, (plen, max_new, price)
        if res is not None:
            kv.free(res[0])
