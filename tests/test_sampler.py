"""The batch sampler's greedy branch: a batch whose rows are all greedy
takes only the argmax, and every batch's tokens equal those of the
single-path sampler (kept below as the reference) bit for bit; both
engines count the calls and the ones that took the greedy branch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serving import sampler
from repro.serving.sampler import SamplingParams, _filter_logits
from repro.serving.telemetry import Telemetry


def _reference_sample_batch(logits, rng, temp, topk, topp):
    """``sample_batch`` as one path: every row runs the filter and the
    draw, and greedy rows keep the argmax."""
    lg = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(lg, axis=-1)
    x = _filter_logits(lg, temp, topk, topp)
    tok = jax.random.categorical(rng, x, axis=-1)
    return jnp.where(temp <= 0.0, greedy_tok, tok).astype(jnp.int32)


V = 1000


def _batch(kind, B, seed):
    """Logits and per-row params for one case.  Logits are small
    integers, so most rows hold tied maxima; greedy rows mix 0 and
    negative temperatures."""
    r = np.random.default_rng(seed)
    logits = r.integers(-4, 4, (B, V)).astype(np.float32)
    temp = np.where(r.random(B) < 0.5, 0.0, -1.0).astype(np.float32)
    topk = np.zeros(B, np.int32)
    topp = np.ones(B, np.float32)
    if kind == "mixed":
        temp[B // 2], topk[B // 2], topp[B // 2] = 0.8, 40, 0.9
    elif kind == "sampled":
        logits = r.normal(0, 3, (B, V)).astype(np.float32)
        temp = r.uniform(0.3, 1.5, B).astype(np.float32)
        topk = r.choice([0, 1, 40], B).astype(np.int32)
        topp = r.choice([1.0, 0.9, 0.5], B).astype(np.float32)
    return (jnp.asarray(logits), jnp.asarray(temp), jnp.asarray(topk),
            jnp.asarray(topp))


@pytest.mark.parametrize("kind,B,dtype", [
    ("greedy", 1, jnp.float32), ("greedy", 32, jnp.float32),
    ("greedy", 128, jnp.float32), ("greedy", 32, jnp.bfloat16),
    ("mixed", 32, jnp.float32), ("sampled", 8, jnp.float32)])
def test_sample_batch_matches_single_path_reference(kind, B, dtype):
    new = jax.jit(sampler.sample_batch)
    ref = jax.jit(_reference_sample_batch)
    for seed in range(3):
        logits, temp, topk, topp = _batch(kind, B, seed)
        logits = logits.astype(dtype)
        key = jax.random.PRNGKey(1000 + seed)
        got = np.asarray(new(logits, key, temp, topk, topp))
        want = np.asarray(ref(logits, key, temp, topk, topp))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)
        if kind == "greedy":  # ties resolve to the first maximum
            np.testing.assert_array_equal(
                got, np.argmax(np.asarray(logits, np.float32), axis=-1))


def _primitives(jaxpr):
    """Every primitive name in a jaxpr, sub-jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    names |= _primitives(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    names |= _primitives(sub)
    return names


def test_greedy_branch_holds_no_sort():
    """``jit_sample_batch`` keeps its name and branches once on the rows'
    temperatures: the greedy branch holds no sort and no draw, the other
    one does."""
    args = (jnp.zeros((4, V)), jax.random.PRNGKey(0),
            jnp.zeros((4,)), jnp.zeros((4,), jnp.int32), jnp.ones((4,)))
    text = jax.jit(sampler.sample_batch).lower(*args).as_text()
    assert "module @jit_sample_batch " in text
    jaxpr = jax.make_jaxpr(sampler.sample_batch)(*args).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    full, greedy = conds[0].params["branches"]  # index 1: all greedy
    assert "argmax" in _primitives(greedy.jaxpr)
    assert not {"sort", "random_bits"} & _primitives(greedy.jaxpr)
    assert {"sort", "random_bits"} <= _primitives(full.jaxpr)
    assert sampler.all_greedy(np.asarray([0.0, -1.0], np.float32))
    assert not sampler.all_greedy(np.asarray([0.0, 0.5], np.float32))


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = get_config("gpt2-345m").reduced()
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=64)
    return cfg, params


def _engine(kind, cfg, params, **kw):
    if kind == "single":
        from repro.serving.engine import ServeEngine

        return ServeEngine(cfg, params, batch_slots=3, max_seq=64,
                           eos_id=-1, chunk_size=8, seed=11, **kw)
    from repro.serving.distributed import DistributedServeEngine

    return DistributedServeEngine(cfg, params, n_shards=1,
                                  slots_per_shard=3, max_seq=64, eos_id=-1,
                                  chunk_size=8, seed=11, **kw)


def _serve(eng, sampled):
    rng = np.random.default_rng(5)
    hot = SamplingParams(temperature=0.8, top_k=40, top_p=0.9)
    for i in range(4):
        eng.submit(list(rng.integers(1, 200, 5 + 3 * i)), max_new=6,
                   sampling=hot if sampled and i == 1 else None)
    return {r.rid: r.out for r in eng.run()}


@pytest.mark.parametrize("kind", ["single", "distributed"])
def test_engine_counts_greedy_sampler_calls(gpt2_setup, kind):
    """An all-greedy run takes the greedy branch on every sampler call;
    one sampled request among greedy ones sends some calls down the full
    path, and every token equals the single-path sampler's at the same
    seed."""
    cfg, params = gpt2_setup
    eng = _engine(kind, cfg, params)
    _serve(eng, sampled=False)
    st = eng.stats()
    assert st["sample_greedy_calls"] == st["sample_calls"] > 0

    eng = _engine(kind, cfg, params)
    got = _serve(eng, sampled=True)
    st = eng.stats()
    assert 0 < st["sample_greedy_calls"] < st["sample_calls"]
    ref = _engine(kind, cfg, params)
    ref._sample = jax.jit(_reference_sample_batch)
    assert got == _serve(ref, sampled=True)


def test_sample_span_says_greedy(gpt2_setup):
    """Each ``sample`` span of ``ServeEngine`` carries ``greedy`` (0/1),
    one per sampler call, summing to the greedy calls."""
    cfg, params = gpt2_setup
    eng = _engine("single", cfg, params, telemetry=Telemetry(trace=True))
    _serve(eng, sampled=True)
    flags = [ev[6]["greedy"] for ev in eng.tel.tracer.events
             if ev[0] == "X" and ev[1] == "sample"]
    assert len(flags) == eng.sample_calls
    assert sum(flags) == eng.sample_greedy_calls
    assert set(flags) == {0, 1}
