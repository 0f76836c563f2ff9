"""Speculative decoding: greedy bit-exactness vs plain decode on both KV
layouts, the accept/reject sampler (greedy reduction + distribution
preservation), acceptance accounting, verify_chunk per-position logits,
and KV rewind invariants (mask-only stacked, refcounted paged release
under churn without corrupting prefix-sharing chains)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serving import sampler
from repro.serving.engine import ServeEngine
from repro.serving.kv_cache import PagedCacheManager, SlotCacheManager
from repro.serving.speculative import NgramProposer, SpecConfig


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = get_config("gpt2-345m").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=64)
    return cfg, params


def _mixed_prompts(vocab, lengths=(3, 17, 5, 26), seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, vocab, int(n)))) for n in lengths]


def _run(cfg, params, prompts, *, max_new=10, spec=None, kv_layout="auto",
         sampling=None, eos_id=-1, **kw):
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=eos_id,
                      chunk_size=8, kv_layout=kv_layout, spec=spec, **kw)
    for p in prompts:
        eng.submit(p, max_new=max_new, sampling=sampling)
    done = eng.run()
    assert len(done) == len(prompts)
    return eng, {tuple(r.prompt): r.out for r in done}


# ---------------------------------------------------------------------------
# the acceptance criterion: greedy spec == plain decode, both layouts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_layout", ["stacked", "paged"])
def test_greedy_spec_bitexact_vs_plain(gpt2_setup, kv_layout):
    """Greedy speculative decoding is token-for-token identical to plain
    ServeEngine decode — more requests than slots, mixed lengths, so
    the check covers slot churn and mixed prefill/verify ticks."""
    cfg, params = gpt2_setup
    prompts = _mixed_prompts(cfg.vocab_size)
    _, plain = _run(cfg, params, prompts, kv_layout=kv_layout)
    eng, spec = _run(cfg, params, prompts, kv_layout=kv_layout,
                     spec=SpecConfig(k=4))
    assert spec == plain
    assert eng.spec_ticks > 0 and eng.spec_emitted > eng.spec_ticks


@pytest.mark.parametrize("kv_layout", ["stacked", "paged"])
def test_model_draft_spec_bitexact_vs_plain(gpt2_setup, kv_layout):
    """The small-model draft proposer also preserves the greedy stream —
    with a *different* draft model (low acceptance, heavy rejection
    traffic exercises rewind) and with the target itself as draft
    (every draft accepted)."""
    cfg, params = gpt2_setup
    draft_params = lm.init(cfg, jax.random.PRNGKey(7), max_seq=64)
    prompts = _mixed_prompts(cfg.vocab_size, seed=2)
    _, plain = _run(cfg, params, prompts, kv_layout=kv_layout)
    for dp in (draft_params, params):
        eng, spec = _run(cfg, params, prompts, kv_layout=kv_layout,
                         spec=SpecConfig(k=3, proposer="model",
                                         draft_cfg=cfg, draft_params=dp))
        assert spec == plain
    # the second engine drafted with the target itself: every proposal
    # must have been accepted, and the draft model's own forward passes
    # are surfaced next to the target-call metrics
    s = eng.stats()
    assert s["acceptance_rate"] == 1.0
    assert s["draft_calls"] > 0


def test_spec_prefix_sharing_paged(gpt2_setup):
    """Speculation composes with copy-free prefix sharing: shared prompt
    pages stay linked (never scattered over by verify writes) and the
    stream is unchanged."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(5)
    sysp = list(map(int, rng.integers(1, cfg.vocab_size, 32)))
    prompts = [sysp + list(map(int, rng.integers(1, cfg.vocab_size, 4 + i)))
               for i in range(5)]
    _, plain = _run(cfg, params, prompts, kv_layout="paged")
    eng, spec = _run(cfg, params, prompts, kv_layout="paged",
                     spec=SpecConfig(k=3))
    assert spec == plain
    assert eng.kv.prefix_hit_pages > 0


def test_spec_eos_and_budget_stops(gpt2_setup):
    """EOS inside an accepted draft run stops emission mid-batch, and a
    max_new budget smaller than the draft length truncates exactly like
    the plain engine."""
    cfg, params = gpt2_setup
    probe = ServeEngine(cfg, params, batch_slots=1, max_seq=64, eos_id=-1,
                        chunk_size=8)
    probe.submit([3, 4, 5], max_new=6)
    eos = probe.run()[0].out[3]
    for kv_layout in ("stacked", "paged"):
        for max_new in (2, 20):
            _, plain = _run(cfg, params, [[3, 4, 5]], max_new=max_new,
                            kv_layout=kv_layout, eos_id=eos)
            _, spec = _run(cfg, params, [[3, 4, 5]], max_new=max_new,
                           kv_layout=kv_layout, eos_id=eos,
                           spec=SpecConfig(k=4))
            assert spec == plain, (kv_layout, max_new)


def test_spec_sampling_completes_with_accounting(gpt2_setup):
    """Stochastic per-request sampling through the spec path: requests
    complete with in-vocab tokens and the acceptance accounting is
    consistent."""
    cfg, params = gpt2_setup

    class RepeatLast(NgramProposer):
        """Drafts the current token repeated: every decode tick verifies,
        whatever the sampled stream."""

        def propose(self, slots, cur_tok, lengths, active, caps):
            counts = np.where(active, caps, 0).astype(np.int32)
            draft = np.repeat(cur_tok.astype(np.int32), self.k, axis=1)
            return draft, counts

    prompts = _mixed_prompts(cfg.vocab_size, lengths=(9, 6, 12), seed=4)
    sampling = sampler.SamplingParams(temperature=1.2, top_k=50, top_p=0.9)
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      chunk_size=8, spec=SpecConfig(k=4), seed=11)
    eng.proposer = RepeatLast(4)
    for p in prompts:
        eng.submit(p, max_new=8, sampling=sampling)
    eng.run()
    outs = {r.rid: r.out for r in eng.finished}
    assert all(len(o) == 8 for o in outs.values())
    assert all(0 <= t < cfg.vocab_size for o in outs.values() for t in o)
    s = eng.stats()
    assert 0 <= s["spec_accepted"] <= s["spec_proposed"]
    assert 0.0 <= s["acceptance_rate"] <= 1.0
    assert s["spec_ticks"] > 0
    assert s["tokens_per_verify_call"] >= 1.0
    # every emitted token is an accepted draft or one of (at most) one
    # bonus/corrective token per slot per verify call
    assert s["spec_emitted"] <= s["spec_accepted"] + s["spec_ticks"] * eng.B


def test_spec_zero_draft_ticks_fall_back_to_plain_decode(gpt2_setup):
    """A tick where no slot proposes anything must run the 1-token plain
    decode step (same stream, no (k+1)-wide verify compute)."""
    cfg, params = gpt2_setup

    class NeverPropose(NgramProposer):
        def propose(self, slots, cur_tok, lengths, active, caps):
            B = len(slots)
            return (np.zeros((B, self.k), np.int32),
                    np.zeros((B,), np.int32))

    prompts = _mixed_prompts(cfg.vocab_size, lengths=(5, 9), seed=6)
    _, plain = _run(cfg, params, prompts, max_new=6)
    eng, outs = _run(cfg, params, prompts, max_new=6, spec=SpecConfig(k=4))
    eng2 = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                       chunk_size=8, spec=SpecConfig(k=4))
    eng2.proposer = NeverPropose(4)
    for p in prompts:
        eng2.submit(p, max_new=6)
    done = {tuple(r.prompt): r.out for r in eng2.run()}
    assert done == plain == outs
    assert eng2.spec_ticks == 0  # every decode tick took the plain path
    assert eng.spec_ticks > 0


def test_spec_requires_chunked_path():
    """Speculative decoding needs the chunked path (verification is one
    chunked forward call): the explicit replay debug mode must raise, not
    silently decode token-by-token.  Hybrid rotating-window/recurrent
    stacks verify through the universal chunk body now, so the stack
    itself no longer gates spec — their bit-exactness is asserted in
    ``tests/test_hybrid_serving.py``."""
    cfg = get_config("recurrentgemma-9b").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=32)
    with pytest.raises(ValueError, match="chunked"):
        ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1,
                    prefill_mode="replay", spec=SpecConfig(k=2))
    # auto selects chunked for the hybrid stack, and spec composes with it
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1,
                      spec=SpecConfig(k=2))
    assert eng.prefill_mode == "chunked"
    # a verify writes k+1 ring positions: k+1 > W must refuse loudly
    with pytest.raises(ValueError, match="ring"):
        ServeEngine(cfg, params, batch_slots=1, max_seq=32, eos_id=-1,
                    spec=SpecConfig(k=32))


# ---------------------------------------------------------------------------
# verify_chunk: per-position logits against live caches
# ---------------------------------------------------------------------------


def test_verify_chunk_matches_sequential_decode(gpt2_setup):
    """One verify_chunk call returns, per row, the same logits sequential
    decode_step calls produce at those positions, with per-row offsets;
    inactive rows (offset=max_seq) leave their cache bits untouched."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(0)
    B, S, C = 3, 64, 4
    cache = lm.init_cache(cfg, B, S)
    lengths = jnp.zeros((B,), jnp.int32)
    ctx = {0: list(map(int, rng.integers(1, cfg.vocab_size, 7))),
           1: list(map(int, rng.integers(1, cfg.vocab_size, 11)))}
    for b, toks in ctx.items():
        for t in toks:
            tok_b = jnp.zeros((B, 1), jnp.int32).at[b, 0].set(t)
            _, cache = lm.decode_step(params, cfg, tok_b, cache, lengths)
            lengths = lengths.at[b].add(1)

    vt = {b: list(map(int, rng.integers(1, cfg.vocab_size, C)))
          for b in (0, 1)}
    toks = np.zeros((B, C), np.int32)
    toks[0], toks[1] = vt[0], vt[1]
    vlen = jnp.asarray([len(ctx[0]), len(ctx[1]), S], jnp.int32)
    vlogits, vcache = lm.verify_chunk(params, cfg, jnp.asarray(toks), cache,
                                      vlen)
    assert vlogits.shape == (B, C, cfg.vocab_size)

    ref_cache, ref_len = cache, lengths
    for j in range(C):
        tok_b = jnp.zeros((B, 1), jnp.int32)
        for b in (0, 1):
            tok_b = tok_b.at[b, 0].set(vt[b][j])
        lg, ref_cache = lm.decode_step(params, cfg, tok_b, ref_cache,
                                       ref_len)
        ref_len = ref_len + jnp.asarray([1, 1, 0], jnp.int32)
        for b in (0, 1):
            np.testing.assert_allclose(
                np.asarray(vlogits[b, j]), np.asarray(lg[b], np.float32),
                rtol=2e-4, atol=2e-4)
            assert (int(np.argmax(vlogits[b, j]))
                    == int(np.argmax(lg[b])))
    # inactive row 2: bit-identical cache
    for lv, lr in zip(jax.tree_util.tree_leaves(vcache),
                      jax.tree_util.tree_leaves(cache)):
        ax = 1 if lv.ndim == 5 else 0
        assert (np.asarray(jnp.take(lv, 2, axis=ax))
                == np.asarray(jnp.take(lr, 2, axis=ax))).all()


# ---------------------------------------------------------------------------
# accept/reject sampler
# ---------------------------------------------------------------------------


def test_spec_accept_greedy_reduction():
    """Greedy rows accept exactly the longest draft prefix matching the
    argmax chain and emit the argmax at the divergence (or the bonus)."""
    B, k, V = 3, 3, 5
    logits = np.zeros((B, k + 1, V), np.float32)
    for i, t in enumerate([1, 2, 3, 4]):
        logits[0, i, t] = 5.0  # draft matches 2, diverges at position 2
    for i, t in enumerate([2, 2, 2, 2]):
        logits[1, i, t] = 5.0  # full acceptance + bonus
    for i, t in enumerate([4, 1, 1, 1]):
        logits[2, i, t] = 5.0  # immediate rejection
    draft = np.asarray([[1, 2, 0], [2, 2, 2], [0, 1, 1]], np.int32)
    n_draft = np.asarray([3, 3, 3], np.int32)
    n_acc, nxt = sampler.spec_accept_batch(
        jnp.asarray(logits), jnp.asarray(draft), jnp.asarray(n_draft),
        jax.random.PRNGKey(0), jnp.zeros((B,)), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,)))
    assert n_acc.tolist() == [2, 3, 0]
    assert nxt.tolist() == [3, 2, 4]


def test_spec_accept_preserves_target_distribution():
    """Point-mass accept/reject is marginally exact: over many trials the
    first emitted token (draft if accepted, else the corrective resample)
    is distributed as the plain filtered target distribution."""
    V, trials = 4, 4000
    p = np.asarray([0.45, 0.3, 0.15, 0.1])
    logits = np.broadcast_to(np.log(p), (trials, 2, V)).astype(np.float32)
    draft = np.full((trials, 1), 1, np.int32)  # always propose token 1
    n_draft = np.ones((trials,), np.int32)
    n_acc, nxt = sampler.spec_accept_batch(
        jnp.asarray(logits), jnp.asarray(draft), jnp.asarray(n_draft),
        jax.random.PRNGKey(123), jnp.ones((trials,)),
        jnp.zeros((trials,), jnp.int32), jnp.ones((trials,)))
    n_acc, nxt = np.asarray(n_acc), np.asarray(nxt)
    first = np.where(n_acc >= 1, 1, nxt)
    freq = np.bincount(first, minlength=V) / trials
    np.testing.assert_allclose(freq, p, atol=0.03)
    # rejected rows never resample the struck draft token
    assert not np.any(nxt[n_acc == 0] == 1)


def test_ngram_proposer_lookup():
    """The table drafts the continuation of the most recent earlier
    occurrence of the current suffix, longest n first."""

    class R:
        prompt = [5, 6, 7, 8, 5, 6, 7, 9, 5, 6]
        out = [7]

    prop = NgramProposer(k=4, n_max=3, n_min=1)
    prop.alloc(0, R.prompt, 0)
    draft, counts = prop.propose(
        [R()], np.asarray([[7]]), np.asarray([10]),
        np.asarray([True]), np.asarray([4], np.int32))
    # suffix (5, 6, 7) last recurred at positions 4..6, followed by 9, 5...
    assert counts[0] == 4
    assert draft[0].tolist() == [9, 5, 6, 7]
    prop.free(0)
    assert 0 not in prop._tables


# ---------------------------------------------------------------------------
# KV rewind
# ---------------------------------------------------------------------------


def test_slot_manager_rewind_mask_only():
    cfg = get_config("gpt2-345m").reduced()
    kv = SlotCacheManager(cfg, 2, 32, with_cache=False)
    slot = kv.alloc()
    kv.advance(slot, 10)
    kv.rewind(slot, 13)  # commit past the advance (spec verify wrote 3+)
    assert kv.length_of(slot) == 13
    kv.rewind(slot, 11)  # reject the tail
    assert kv.length_of(slot) == 11
    # ValueError, not assert: the guards must survive ``python -O``
    with pytest.raises(ValueError, match="outside"):
        kv.rewind(slot, 40)  # beyond the cache
    kv.free(slot)
    with pytest.raises(ValueError, match="unallocated"):
        kv.rewind(slot, 0)  # not allocated


def test_paged_rewind_releases_pages_and_keeps_reservation():
    """rewind returns rejected-draft pages to the pool and their count to
    the slot's reservation, so (pages held + reserved) stays the
    worst-case lifetime price and later growth cannot fail."""
    cfg = get_config("gpt2-345m").reduced()
    ps = 4
    kv = PagedCacheManager(cfg, 2, 32, page_size=ps, with_cache=False)
    prompt = list(range(1, 11))  # 10 tokens -> 3 prompt pages
    slot, shared = kv.alloc(prompt, max_new=16)  # total 26 -> 7 pages
    assert shared == 0
    total = kv.pages_for(len(prompt) + 16)
    kv.advance(slot, len(prompt))

    def held_plus_reserved():
        return len(kv._slot_pages[slot]) + kv._reserved[slot]

    assert held_plus_reserved() == total
    # speculative tick at L=10: grow for cur_tok + 6 drafts, commit 1
    kv.ensure_decode_room([True, False], 7)
    assert len(kv._slot_pages[slot]) == kv.pages_for(17)
    grown = list(kv._slot_pages[slot])
    kv.rewind(slot, 11)
    assert kv.length_of(slot) == 11
    assert len(kv._slot_pages[slot]) == kv.pages_for(11)
    assert held_plus_reserved() == total
    released = set(grown) - set(kv._slot_pages[slot])
    assert released and all(kv.refcount(p) == 0 for p in released)
    # block-table entries past the kept pages all point at the null page
    assert (kv.block_tables[slot][kv.pages_for(11):] == 0).all()
    # grow again (re-speculation) and free: the pool fully drains
    kv.ensure_decode_room([True, False], 6)
    kv.free(slot)
    assert kv.pages_in_use == 0


def test_paged_rewind_refuses_prompt_and_preserves_sharing():
    """Rewinding below the prompt is refused (prompt pages may be
    prefix-shared); rewinding one sharer's decode tail never disturbs
    the other sharer's pages or the prefix map, across slot churn."""
    cfg = get_config("gpt2-345m").reduced()
    ps = 4
    kv = PagedCacheManager(cfg, 3, 32, page_size=ps, with_cache=False)
    prompt = list(range(1, 10))  # 9 tokens: 2 full shareable pages
    s1, sh1 = kv.alloc(prompt, max_new=8)
    assert sh1 == 0
    kv.advance(s1, len(prompt))  # marks the full prompt pages ready
    s2, sh2 = kv.alloc(prompt, max_new=8)
    assert sh2 == 2 * ps  # linked both full prompt pages
    shared_pids = kv._slot_pages[s1][:2]
    assert all(kv.refcount(p) == 2 for p in shared_pids)

    with pytest.raises(ValueError, match="prefix-shared"):
        kv.rewind(s2, len(prompt) - 1)

    # sharer 2 speculates and rewinds its decode tail repeatedly
    for _ in range(3):
        kv.ensure_decode_room([False, True, False], 5)
        kv.rewind(s2, len(prompt) + 1)
    assert all(kv.refcount(p) == 2 for p in shared_pids)
    kv.free(s2)
    assert all(kv.refcount(p) == 1 for p in shared_pids)
    # a third request still links the chain after the churn
    s3, sh3 = kv.alloc(prompt, max_new=8)
    assert sh3 == 2 * ps
    kv.free(s3)
    kv.free(s1)
    assert kv.pages_in_use == 0


# ---------------------------------------------------------------------------
# adaptive draft sizing: EWMA acceptance -> per-slot caps
# ---------------------------------------------------------------------------


def test_adaptive_draft_shrinks_and_recovers():
    """A rejection streak walks the cap down to k_min; full acceptance
    pulls it back to k within a few observations.  Shrink is monotone
    under sustained rejection (no oscillation)."""
    from repro.serving.speculative import AdaptiveDraft

    ad = AdaptiveDraft(k=4, k_min=1, decay=0.5)
    ad.alloc(0)
    assert ad.cap(0) == 4  # optimistic start: first verify is evidence
    caps = []
    for _ in range(6):
        ad.observe(0, 4, 0)
        caps.append(ad.cap(0))
    assert caps == sorted(caps, reverse=True)
    assert ad.cap(0) == 1  # floored at k_min, never 0
    for _ in range(4):
        ad.observe(0, ad.cap(0), ad.cap(0))
    assert ad.cap(0) == 4  # recovered the full draft length


def test_adaptive_draft_bounds_and_evidence_rules():
    """Caps stay inside [k_min, k] under any observation mix; zero-token
    proposals are not rejection evidence; free() drops the slot; the
    SpecConfig gate returns None unless adaptive=True."""
    from repro.serving.speculative import AdaptiveDraft

    ad = AdaptiveDraft(k=6, k_min=2, decay=0.5)
    ad.alloc(3)
    rng = np.random.default_rng(0)
    for _ in range(50):
        p = int(rng.integers(0, 7))
        ad.observe(3, p, int(rng.integers(0, p + 1)))
        assert 2 <= ad.cap(3) <= 6
    ad.alloc(4)
    for _ in range(10):
        ad.observe(4, 0, 0)  # no-match ticks: estimate untouched
    assert ad.cap(4) == 6
    ad.free(4)
    assert ad.stats()["adaptive_slots"] == 1
    assert AdaptiveDraft.from_spec(SpecConfig(k=4)) is None
    got = AdaptiveDraft.from_spec(SpecConfig(k=4, adaptive=True, k_min=2))
    assert got is not None and got.k_min == 2
    with pytest.raises(ValueError, match="k_min"):
        AdaptiveDraft(k=4, k_min=5)
    with pytest.raises(ValueError, match="ewma_decay"):
        AdaptiveDraft(k=4, decay=0.0)


def test_adaptive_draft_caps_compose_with_safety_bounds():
    """draft_caps still enforces the generation budget and the cache
    ceiling; the adaptive cap only ever shrinks the result."""
    import types

    from repro.serving.speculative import AdaptiveDraft, draft_caps

    ad = AdaptiveDraft(k=6, k_min=1, decay=0.5)
    ad.alloc(0)
    ad.alloc(1)
    ad.observe(1, 6, 0)  # slot 1's estimate halves -> cap 3
    slots = [types.SimpleNamespace(max_new=10, out=[]),
             types.SimpleNamespace(max_new=2, out=[])]
    lengths = np.asarray([60, 10])
    fixed = draft_caps(slots, lengths, [True, True], 6, 64)
    adapt = draft_caps(slots, lengths, [True, True], 6, 64, adaptive=ad)
    assert fixed.tolist() == [3, 2]  # ceiling 64-1-60=3; budget 2
    assert adapt.tolist() == [3, 2]  # adaptive cap 3 never loosens either
    ad.observe(1, 2, 0)  # ewma 0.25 -> cap ceil(1.5) = 2: budget still binds
    assert draft_caps(slots, lengths, [True, True], 6, 64,
                      adaptive=ad).tolist() == [3, 2]
    ad.observe(1, 2, 0)  # ewma 0.125 -> cap 1: now below the budget
    assert draft_caps(slots, lengths, [True, True], 6, 64,
                      adaptive=ad).tolist() == [3, 1]


@pytest.mark.parametrize("kv_layout", ["stacked", "paged"])
def test_adaptive_spec_bitexact_and_reduces_waste(gpt2_setup, kv_layout):
    """With a low-acceptance draft model, adaptive sizing leaves the
    greedy stream bit-identical (shrink-only) while proposing strictly
    fewer draft tokens than the fixed-k engine — the wasted-verify-work
    reduction the knob exists for."""
    cfg, params = gpt2_setup
    draft_params = lm.init(cfg, jax.random.PRNGKey(7), max_seq=64)
    prompts = _mixed_prompts(cfg.vocab_size, seed=2)
    _, plain = _run(cfg, params, prompts, kv_layout=kv_layout)
    eng_f, fixed = _run(cfg, params, prompts, kv_layout=kv_layout,
                        spec=SpecConfig(k=3, proposer="model",
                                        draft_cfg=cfg,
                                        draft_params=draft_params))
    eng_a, adapt = _run(cfg, params, prompts, kv_layout=kv_layout,
                        spec=SpecConfig(k=3, proposer="model",
                                        draft_cfg=cfg,
                                        draft_params=draft_params,
                                        adaptive=True))
    assert adapt == fixed == plain
    assert eng_a.adaptive is not None
    assert eng_f.spec_accepted < eng_f.spec_proposed  # low acceptance
    assert eng_a.spec_proposed < eng_f.spec_proposed  # less drafted waste


# ---------------------------------------------------------------------------
# tree speculative decoding: TokenTree structure, ancestor masks, the
# tree accept rule, and end-to-end greedy bit-exactness
# ---------------------------------------------------------------------------


def _random_tree(rng, n_nodes, vocab=100):
    """Grow a random TokenTree by attaching each node to a random
    existing chunk position (root = 0)."""
    from repro.serving.speculative import TokenTree

    t = TokenTree()
    for _ in range(n_nodes):
        t.add(int(rng.integers(0, vocab)), int(rng.integers(0, t.n + 1)))
    return t


def _check_tree_mask(t, C):
    """Every node's ancestor-mask row is exactly its root path (walked
    independently via the parent pointers); padding rows are causal."""
    anc = t.ancestor_mask(C)
    assert anc.shape == (C, C) and anc.dtype == np.bool_
    assert anc[0].tolist() == [True] + [False] * (C - 1)
    for j in range(1, t.n + 1):
        path = {0, j}
        p = t.parents[j - 1]
        while p != 0:
            path.add(p)
            p = t.parents[p - 1]
        assert set(np.flatnonzero(anc[j]).tolist()) == path, (j, t.parents)
        # depth bookkeeping: |root path| - 1 (root excluded)
        assert t.depths[j - 1] == len(path) - 1
    for j in range(t.n + 1, C):  # padding rows: causal, so a chain/empty
        assert anc[j].tolist() == [True] * (j + 1) + [False] * (C - 1 - j)


def test_token_tree_ancestor_mask_matches_parent_pointers():
    """Deterministic sweep of the hypothesis property: random trees of
    every size up to the chunk budget, plus the degenerate chain — the
    mask row of node j holds exactly j's root path."""
    from repro.serving.speculative import TokenTree

    rng = np.random.default_rng(0)
    for n in range(0, 8):
        for _ in range(20):
            _check_tree_mask(_random_tree(rng, n), C=9)
    chain = TokenTree.chain([5, 6, 7])
    _check_tree_mask(chain, C=4)
    # a chain's mask IS the causal tril: the linear-verify reduction
    assert np.array_equal(chain.ancestor_mask(4),
                          np.tril(np.ones((4, 4), bool)))
    with pytest.raises(ValueError, match="parent"):
        TokenTree().add(1, 1)  # parent must already exist
    with pytest.raises(ValueError):
        TokenTree.chain([1, 2, 3]).ancestor_mask(3)  # n+1 > C


try:
    import importlib.util as _ilu
    _HAS_HYPOTHESIS = _ilu.find_spec("hypothesis") is not None
except Exception:  # pragma: no cover
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=50, deadline=None)
    @given(data=st.data(), n=st.integers(0, 10))
    def test_token_tree_mask_property(data, n):
        seed = data.draw(st.integers(0, 2**31 - 1))
        _check_tree_mask(_random_tree(np.random.default_rng(seed), n),
                         C=n + 1 + data.draw(st.integers(0, 3)))
else:
    @pytest.mark.skip(reason="hypothesis not installed; the deterministic "
                      "sweep above covers the same property")
    def test_token_tree_mask_property():
        pass


def test_tree_arrays_defaults_and_packing():
    """tree_arrays flattens per-slot trees into batch arrays; rows with
    no tree get the chain/causal defaults (tril mask, arange depths) so
    a parked or empty row is indistinguishable from linear verify."""
    from repro.serving.speculative import TokenTree, tree_arrays

    t = TokenTree()
    a = t.add(10, 0)
    b = t.add(11, 0)
    c = t.add(12, a)
    tokens, parents, n_nodes, anc, depths = tree_arrays([t, None], 4, 5)
    assert tokens[0, :3].tolist() == [10, 11, 12]
    assert parents[0, :3].tolist() == [0, 0, a]
    assert n_nodes.tolist() == [3, 0]
    assert np.array_equal(anc[1], np.tril(np.ones((5, 5), bool)))
    assert depths[1].tolist() == [0, 1, 2, 3, 4]
    assert depths[0, :4].tolist() == [0, 1, 1, 2]
    assert anc[0][c].tolist() == [True, True, False, True, False]


def test_spec_accept_tree_chain_reduces_to_batch():
    """On a degenerate chain tree the tree accept rule IS the linear
    rule: same accepted count, same bonus/corrective token, bit-exact —
    greedy rows and stochastic rows alike (shared rng stream)."""
    B, k, V = 6, 4, 50
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(B, k + 1, V)).astype(np.float32))
    draft = jnp.asarray(rng.integers(0, V, (B, k)), jnp.int32)
    n_draft = jnp.asarray([4, 3, 0, 4, 2, 1], jnp.int32)
    parents = jnp.tile(jnp.arange(k, dtype=jnp.int32), (B, 1))
    temp = jnp.asarray([0.0, 0.0, 0.0, 1.0, 0.7, 1.3], jnp.float32)
    topk = jnp.asarray([0, 5, 0, 0, 8, 0], jnp.int32)
    topp = jnp.asarray([0.0, 0.0, 0.9, 0.0, 0.0, 0.95], jnp.float32)
    key = jax.random.PRNGKey(11)
    n_b, tok_b = sampler.spec_accept_batch(
        logits, draft, n_draft, key, temp, topk, topp)
    n_t, acc, tok_t = sampler.spec_accept_tree(
        logits, draft, parents, n_draft, key, temp, topk, topp)
    assert np.array_equal(np.asarray(n_b), np.asarray(n_t))
    assert np.array_equal(np.asarray(tok_b), np.asarray(tok_t))
    # the accepted set is exactly the prefix mask of the chain
    want = np.arange(k + 1)[None, :] <= np.asarray(n_b)[:, None]
    assert np.array_equal(np.asarray(acc), want)


def test_spec_accept_tree_picks_deepest_greedy_path():
    """Greedy rows accept the longest root-to-leaf path that matches the
    target argmax chain — siblings of the argmax token are rejected and
    the corrective token is the argmax at the path's end."""
    B, V = 1, 16
    # chunk: [cur, n1(tok 3), n2(tok 5), n3(tok 7 under n1)]
    # target argmax after cur -> 3; after [3] -> 7; after [3,7] -> 9
    logits = np.full((B, 4, V), -10.0, np.float32)
    logits[0, 0, 3] = 10.0   # after cur: argmax 3
    logits[0, 1, 7] = 10.0   # after [3]: argmax 7  (row of node 1)
    logits[0, 2, 2] = 10.0   # after [5]: unused (node 2 rejected)
    logits[0, 3, 9] = 10.0   # after [3,7]: argmax 9
    tokens = jnp.asarray([[3, 5, 7]], jnp.int32)
    parents = jnp.asarray([[0, 0, 1]], jnp.int32)
    n_nodes = jnp.asarray([3], jnp.int32)
    n_acc, acc, next_tok = sampler.spec_accept_tree(
        jnp.asarray(logits), tokens, parents, n_nodes,
        jax.random.PRNGKey(0), jnp.zeros((B,)), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,)))
    assert int(n_acc[0]) == 2
    assert np.asarray(acc)[0].tolist() == [True, True, False, True]
    assert int(next_tok[0]) == 9


def test_spec_accept_tree_preserves_target_distribution():
    """Sequential sibling rejection-sampling keeps the emitted token's
    marginal equal to the target distribution (first emitted position,
    branchy tree, proposal disagrees with target)."""
    V = 10
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(1, 4, V)).astype(np.float32) * 1.5)
    target = np.asarray(jax.nn.softmax(logits[0, 0]))
    # 3 sibling candidates off the root, fixed disagreeing proposal
    tokens = jnp.asarray([[1, 2, 3]], jnp.int32)
    parents = jnp.asarray([[0, 0, 0]], jnp.int32)
    n_nodes = jnp.asarray([3], jnp.int32)
    ones = jnp.ones((1,), jnp.float32)
    zi = jnp.zeros((1,), jnp.int32)

    @jax.jit
    def first_tok(key):
        n_acc, acc, next_tok = sampler.spec_accept_tree(
            logits, tokens, parents, n_nodes, key, ones, zi, ones)
        # the first emitted token: accepted child of the root, else the
        # corrective sample
        child = jnp.argmax(acc[0, 1:] & (parents[0] == 0), axis=-1)
        has = jnp.any(acc[0, 1:] & (parents[0] == 0))
        return jnp.where(has, tokens[0, child], next_tok[0])

    keys = jax.random.split(jax.random.PRNGKey(42), 20000)
    toks = np.asarray(jax.vmap(first_tok)(keys))
    got = np.bincount(toks, minlength=V) / len(toks)
    np.testing.assert_allclose(got, target, atol=0.015)


@pytest.mark.parametrize("kv_layout", ["stacked", "paged"])
@pytest.mark.parametrize("branch", [1, 3])
def test_greedy_tree_spec_bitexact_vs_plain(gpt2_setup, kv_layout, branch):
    """Greedy tree speculation is token-for-token identical to plain
    decode on both layouts — the n-gram proposer emits branchy trees,
    rejected branches rewind, the surviving path compacts in place."""
    cfg, params = gpt2_setup
    prompts = _mixed_prompts(cfg.vocab_size)
    _, plain = _run(cfg, params, prompts, kv_layout=kv_layout)
    eng, tree = _run(cfg, params, prompts, kv_layout=kv_layout,
                     spec=SpecConfig(k=4, tree=True, branch=branch))
    assert tree == plain
    assert eng.spec_ticks > 0


@pytest.mark.parametrize("kv_layout", ["stacked", "paged"])
def test_model_draft_tree_spec_bitexact_vs_plain(gpt2_setup, kv_layout):
    """The draft-model tree proposer preserves the greedy stream with a
    disagreeing draft (heavy branch rejection + compaction traffic) and
    with the target as its own draft (deep accepted spines)."""
    cfg, params = gpt2_setup
    draft_params = lm.init(cfg, jax.random.PRNGKey(7), max_seq=64)
    prompts = _mixed_prompts(cfg.vocab_size, seed=2)
    _, plain = _run(cfg, params, prompts, kv_layout=kv_layout)
    for dp in (draft_params, params):
        eng, tree = _run(cfg, params, prompts, kv_layout=kv_layout,
                         spec=SpecConfig(k=4, tree=True, branch=2,
                                         proposer="model", draft_cfg=cfg,
                                         draft_params=dp))
        assert tree == plain
    assert eng.stats()["acceptance_rate"] > 0.3  # self-draft spine accepts


def test_tree_spec_sampling_completes_with_accounting(gpt2_setup):
    """Stochastic tree spec completes with coherent accounting (the
    distribution-preservation property itself is unit-tested above)."""
    cfg, params = gpt2_setup
    prompts = _mixed_prompts(cfg.vocab_size, seed=5)
    eng, out = _run(cfg, params, prompts, kv_layout="paged",
                    spec=SpecConfig(k=4, tree=True, branch=2),
                    sampling=sampler.SamplingParams(temperature=0.8,
                                                    top_k=40))
    assert all(len(v) == 10 for v in out.values())
    assert eng.spec_accepted <= eng.spec_proposed
    assert eng.spec_emitted >= eng.spec_ticks


def test_tree_spec_requires_pure_attention_stack(gpt2_setup):
    """Tree mode forks K/V across sibling branches; rings/recurrent
    state cannot hold two candidate futures, so hybrid stacks refuse."""
    import dataclasses

    cfg, params = gpt2_setup
    bad = dataclasses.replace(cfg, block_pattern=("attn", "local_attn"),
                              window=32)
    with pytest.raises(ValueError, match="tree"):
        ServeEngine(bad, params, batch_slots=2, max_seq=64, eos_id=-1,
                    chunk_size=8, spec=SpecConfig(k=2, tree=True))


def test_adaptive_observe_tree_uses_path_over_nodes():
    """Satellite: the per-slot EWMA observes tree ticks as
    accepted-path-length / proposed-nodes — a wide tree with a short
    surviving path is rejection evidence exactly like a rejected chain."""
    from repro.serving.speculative import AdaptiveDraft

    ad = AdaptiveDraft(k=4, k_min=1, decay=0.5)
    ad2 = AdaptiveDraft(k=4, k_min=1, decay=0.5)
    ad.alloc(0)
    ad2.alloc(0)
    for _ in range(4):
        ad.observe_tree(0, 4, 1)  # 4-node tree, 1-deep surviving path
        ad2.observe(0, 4, 1)
    assert ad.cap(0) == ad2.cap(0) < 4
    for _ in range(4):
        ad.observe_tree(0, 4, 4)  # full chain survived
    assert ad.cap(0) == 4
    ad.observe_tree(0, 0, 0)  # zero-node tick: not rejection evidence
    assert ad.cap(0) == 4
