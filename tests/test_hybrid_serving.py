"""Hybrid-stack serving through the universal chunked path.

The chunked forward body covers every block kind now (PR 5): rotating
windows write ``pos % W`` ring slots, recurrent kinds thread carried
state through an intra-chunk scan, and speculative verify commits
through the ``StateStore`` rewind seam.  These tests pin the acceptance
criteria: greedy streams bit-identical between ``prefill_mode="auto"``
(== chunked) and the explicit replay debug mode for windowed, recurrent,
and mixed stacks; speculative greedy bit-exactness under rejected drafts
(the state rewind); window-capped stacks serving prompts longer than the
cache; and the ``ValueError`` gates that survive ``python -O``.
"""
import dataclasses
import math

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import blocks, lm
from repro.serving.engine import ServeEngine
from repro.serving.kv_cache import PagedCacheManager
from repro.serving.speculative import DraftProposer, SpecConfig

MAX_SEQ = 64
CHUNK = 16


@pytest.fixture(scope="module")
def windowed_setup():
    """recurrentgemma-shaped: (rglru, rglru, local_attn), window 32."""
    cfg = get_config("recurrentgemma-9b").reduced()
    return cfg, lm.init(cfg, jax.random.PRNGKey(0), max_seq=MAX_SEQ)


@pytest.fixture(scope="module")
def recurrent_setup():
    """xlstm-shaped: (mlstm, mlstm, mlstm, slstm) — attention-free."""
    cfg = get_config("xlstm-350m").reduced()
    return cfg, lm.init(cfg, jax.random.PRNGKey(1), max_seq=MAX_SEQ)


@pytest.fixture(scope="module")
def mixed_setup():
    """The acceptance-criterion stack: a global-attention layer beside a
    rotating window AND a recurrent layer in one config."""
    cfg = dataclasses.replace(
        get_config("recurrentgemma-9b").reduced(),
        name="hybrid-mixed-reduced",
        block_pattern=("attn", "local_attn", "rglru"))
    return cfg, lm.init(cfg, jax.random.PRNGKey(2), max_seq=MAX_SEQ)


def _prompts(cfg, seed=3):
    """Mixed lengths crossing the rotating window (W=32 reduced)."""
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, cfg.vocab_size, n))
            for n in (5, 40, 37, 12)]


def _serve(cfg, params, prompts, *, mode="auto", max_new=8, spec=None,
           slots=2, layout="auto", **kw):
    eng = ServeEngine(cfg, params, batch_slots=slots, max_seq=MAX_SEQ,
                      eos_id=-1, chunk_size=CHUNK, prefill_mode=mode,
                      spec=spec, kv_layout=layout, **kw)
    for p in prompts:
        eng.submit(list(p), max_new=max_new)
    eng.run(max_ticks=50_000)
    return eng, {r.rid: r.out for r in eng.finished}


# ---------------------------------------------------------------------------
# chunked == replay greedy bit-exactness, every stack shape
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("setup", ["windowed_setup", "recurrent_setup",
                                   "mixed_setup"])
def test_chunked_equals_replay(setup, request):
    cfg, params = request.getfixturevalue(setup)
    prompts = _prompts(cfg)
    eng_c, chunked = _serve(cfg, params, prompts, mode="auto")
    eng_r, replay = _serve(cfg, params, prompts, mode="replay")
    # auto must route every decoder-only stack through the chunked path,
    # at ceil(P/chunk) prefill calls per prompt
    assert eng_c.prefill_mode == "chunked"
    assert eng_c.prefill_calls == sum(
        math.ceil(len(p) / CHUNK) for p in prompts)
    assert eng_c.ticks < eng_r.ticks
    assert chunked == replay


def test_window_crossing_prefill_ring_state(windowed_setup):
    """A prompt longer than the window prefills in ceil(P/chunk) calls
    and leaves exactly the ring a sequential replay would: the next
    decode steps agree bit-for-bit (single slot isolates the ring)."""
    cfg, params = windowed_setup
    prompt = list(np.random.default_rng(11).integers(
        1, cfg.vocab_size, 2 * min(cfg.window, MAX_SEQ) - 5))
    _, chunked = _serve(cfg, params, [prompt], mode="auto", slots=1,
                        max_new=10)
    _, replay = _serve(cfg, params, [prompt], mode="replay", slots=1,
                       max_new=10)
    assert chunked == replay


# ---------------------------------------------------------------------------
# speculative decoding on hybrid stacks: the state-rewind seam
# ---------------------------------------------------------------------------


class _LastDraftWrong(DraftProposer):
    """Drafts the plain greedy continuation with its last token altered,
    so every verify rejects its last draft token and accepts the ones
    before it."""

    def __init__(self, k, plain, vocab):
        self.k, self.plain, self.vocab = k, plain, vocab

    def propose(self, slots, cur_tok, lengths, active, caps):
        B = len(slots)
        draft = np.zeros((B, self.k), np.int32)
        counts = np.zeros((B,), np.int32)
        for b, req in enumerate(slots):
            if not active[b] or caps[b] <= 0 or req is None:
                continue
            toks = list(self.plain[req.rid][len(req.out):][:int(caps[b])])
            if toks:
                toks[-1] = (toks[-1] + 1) % self.vocab
            counts[b] = len(toks)
            draft[b, :len(toks)] = toks
        return draft, counts


@pytest.mark.parametrize("setup", ["windowed_setup", "recurrent_setup",
                                   "mixed_setup"])
def test_spec_greedy_bit_exact_with_rejections(setup, request):
    """Greedy speculative streams must be token-for-token identical to
    plain decode while drafts are partly accepted and partly rejected, so
    the verify-base ring restore and trajectory state selection both
    run.  The proposer forces both outcomes on every verify: it drafts
    the plain stream with the last draft token wrong."""
    cfg, params = request.getfixturevalue(setup)
    rng = np.random.default_rng(7)
    pat = list(rng.integers(1, cfg.vocab_size, 6))
    prompts = [pat * 4,
               list(rng.integers(1, cfg.vocab_size, 40)),
               pat * 3 + list(rng.integers(1, cfg.vocab_size, 5)),
               list(rng.integers(1, cfg.vocab_size, 9))]
    _, plain = _serve(cfg, params, prompts, max_new=12)
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=MAX_SEQ,
                      eos_id=-1, chunk_size=CHUNK, spec=SpecConfig(k=4))
    eng.proposer = _LastDraftWrong(4, plain, cfg.vocab_size)
    for p in prompts:
        eng.submit(list(p), max_new=12)
    eng.run(max_ticks=50_000)
    spec = {r.rid: r.out for r in eng.finished}
    assert eng._state_store is not None  # the hybrid verify path ran
    assert 0 < eng.spec_accepted < eng.spec_proposed
    assert spec == plain


# ---------------------------------------------------------------------------
# per-kind paged layout: attn layers page, rings/states stay slot-resident
# ---------------------------------------------------------------------------


def test_mixed_auto_routes_paged(mixed_setup, windowed_setup):
    """auto flips a mixed stack (has a global-attention layer) to the
    per-kind paged layout; an attention-free stack stays stacked — it
    has nothing to page."""
    cfg_m, params_m = mixed_setup
    eng = ServeEngine(cfg_m, params_m, batch_slots=1, max_seq=MAX_SEQ,
                      eos_id=-1)
    assert eng.paged
    cfg_w, params_w = windowed_setup
    eng = ServeEngine(cfg_w, params_w, batch_slots=1, max_seq=MAX_SEQ,
                      eos_id=-1)
    assert not eng.paged


@pytest.mark.parametrize("spec", [None, SpecConfig(k=4)],
                         ids=["plain", "spec"])
def test_mixed_paged_bitexact_vs_stacked(mixed_setup, spec):
    """Greedy streams through the per-kind paged layout are token-for-
    token identical to the contiguous layout — plain decode and
    speculative (the rejection path exercises page rewind AND the
    slot-resident StateStore commit in one stack)."""
    cfg, params = mixed_setup
    rng = np.random.default_rng(7)
    pat = list(rng.integers(1, cfg.vocab_size, 6))
    prompts = [pat * 4,
               list(rng.integers(1, cfg.vocab_size, 40)),
               list(rng.integers(1, cfg.vocab_size, 9))]
    eng_p, paged = _serve(cfg, params, prompts, max_new=10, spec=spec,
                          layout="paged")
    eng_s, stacked = _serve(cfg, params, prompts, max_new=10, spec=spec,
                            layout="stacked")
    assert eng_p.paged and not eng_s.paged
    assert eng_p._state_store is not None  # slot-resident kinds rode along
    if spec is not None:
        assert eng_p.spec_accepted < eng_p.spec_proposed  # rejections ran
    assert paged == stacked


def test_mixed_paged_prefix_sharing_saves_pages(mixed_setup):
    """Prefix sharing on a mixed stack links the attention layers' prompt
    pages (a real page saving, previously 0 for hybrids) even though the
    slot-resident state forces a full re-prefill; outputs are identical
    to the unshared run."""
    cfg, params = mixed_setup
    ps = 16
    sys_prompt = list(np.random.default_rng(13).integers(
        1, cfg.vocab_size, 2 * ps))
    prompts = [sys_prompt + [3], sys_prompt + [4]]
    eng, shared = _serve(cfg, params, prompts, max_new=4, layout="paged",
                         page_size=ps)
    assert eng.kv.prefix_hit_pages == 2  # second prompt linked 2 pages
    solo, unshared = _serve(cfg, params, prompts, max_new=4,
                            layout="paged", page_size=ps,
                            prefix_sharing=False)
    assert solo.kv.prefix_hit_pages == 0
    assert eng.kv.pages_allocated_total < solo.kv.pages_allocated_total
    assert shared == unshared


# ---------------------------------------------------------------------------
# window-capped stacks: admission without a max_seq ceiling
# ---------------------------------------------------------------------------


def test_window_capped_serves_past_max_seq(windowed_setup):
    """No layer pins more than min(len, W) positions (admission.
    slot_price), so the length ceiling is lifted: a prompt longer than
    the cache admits and generates, identically in both modes."""
    cfg, params = windowed_setup
    assert blocks.window_capped(cfg)
    prompt = list(np.random.default_rng(9).integers(
        1, cfg.vocab_size, MAX_SEQ + 36))
    eng, chunked = _serve(cfg, params, [prompt], mode="auto", slots=1)
    assert eng.seq_ceiling is None
    assert len(chunked[0]) == 8  # full budget, not cut by a ceiling
    _, replay = _serve(cfg, params, [prompt], mode="replay", slots=1)
    assert chunked == replay


def test_bounded_stack_keeps_ceiling(mixed_setup):
    """One global-attention layer prices the full sequence: the ceiling
    stays and an over-long prompt is refused loudly."""
    cfg, params = mixed_setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=MAX_SEQ,
                      eos_id=-1)
    assert eng.seq_ceiling == MAX_SEQ
    with pytest.raises(ValueError, match="fit the cache"):
        eng.submit(list(range(1, MAX_SEQ + 2)), max_new=4)


# ---------------------------------------------------------------------------
# gates: ValueError (python -O safe), not assert
# ---------------------------------------------------------------------------


def test_paged_layout_refuses_attention_free(windowed_setup):
    """A stack with no global-attention layer has nothing to page —
    rings and carried state are slot-resident by construction — so every
    paged entry point must refuse it with ValueError (naming the
    non-pageable layers), not serve a pool nothing would ever use."""
    cfg, params = windowed_setup
    with pytest.raises(ValueError, match="global-attention"):
        PagedCacheManager(cfg, 2, MAX_SEQ)
    with pytest.raises(ValueError, match="global-attention"):
        lm.init_cache(cfg, 2, MAX_SEQ, layout="paged")
    with pytest.raises(ValueError, match="global-attention"):
        ServeEngine(cfg, params, batch_slots=1, max_seq=MAX_SEQ,
                    eos_id=-1, kv_layout="paged")


def test_model_draft_refuses_hybrid(windowed_setup):
    """The draft model's cache rewinds by mask only — a hybrid draft
    stack must be refused (n-gram self-drafting covers those targets)."""
    cfg, params = windowed_setup
    from repro.serving.speculative import ModelDraft

    with pytest.raises(ValueError, match="global-attention"):
        ModelDraft(cfg, params, 2, MAX_SEQ, k=2)


def test_encoder_decoder_still_replays():
    """The one remaining chunk hold-out: whisper's cross-attention has
    no chunk path, so auto falls back to replay and explicit chunked
    raises."""
    cfg = get_config("whisper-large-v3").reduced()
    assert not blocks.chunk_capable(cfg)
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=MAX_SEQ)
    with pytest.raises(ValueError, match="encoder-decoder"):
        ServeEngine(cfg, params, batch_slots=1, max_seq=MAX_SEQ,
                    eos_id=-1, prefill_mode="chunked")
