"""Each plain reference agrees with the program's own full-sequence
forward at a reduced size, on the same weights from the seed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtools  # noqa: F401  (puts bench/ and src/ on the path)
from harness import cell, params, spec


@pytest.mark.parametrize("config", ["tiny-gpt2", "tiny-nemotron",
                                    "tiny-llama", "tiny-olmoe"])
def test_reference_matches_lm_forward(tiny_root, config):
    from repro.models import lm

    bench = spec.Bench(tiny_root)
    conf = bench.config(config)
    m = cell.model(bench, conf)
    w = params.build(m.ref, m.dims, 2**32 + 17, jnp.float32)
    toks = np.random.default_rng(0).integers(1, m.dims["vocab_size"], 40)
    want = np.arange(40)
    got = m.ref.logits(w, conf, toks.tolist(), want)
    with jax.default_matmul_precision("highest"):
        # exact expert capacity, as the serving steps run it
        prog = lm.forward(w, m.cfg, jnp.asarray(toks[None]), moe_cf=None,
                          dtype=jnp.float32)[0][0]
    np.testing.assert_allclose(got, np.asarray(prog), rtol=1e-4, atol=1e-4)


#: sha256 over every leaf (path, dtype, shape, bytes) of the dense
#: maker's weights, recorded from the maker before a family could bring
#: its own: the dense families' weights must not move
DENSE_DIGESTS = {
    ("tiny-gpt2", 7):
        "5edb7f19ca9cebd220d3d69618614e48257966e7e38e33cfab910cc03965002d",
    ("tiny-gpt2", 2**32 + 5):
        "f29cbe09c996a07608efc7e4825e56855e8c5d35a44104bae8f7db015a637dd1",
    ("tiny-nemotron", 7):
        "e6f974b43878b214749f901e0f99bd91348ddd851d234ded4e6d06e81276d77f",
    ("tiny-nemotron", 2**32 + 5):
        "7ced2dac023f966ef57ad409cf87e0b627943a5b105497d9433c11c35c722c47",
}


@pytest.mark.parametrize("config,seed", list(DENSE_DIGESTS))
def test_dense_weights_are_bit_identical(tiny_root, config, seed):
    import hashlib

    bench = spec.Bench(tiny_root)
    m = cell.model(bench, bench.config(config))
    assert not hasattr(m.ref, "make")
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(m.weights(seed))[0]
    for path, leaf in leaves:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DENSE_DIGESTS[config, seed]


def test_a_family_makes_its_own_weights(tiny_root):
    """``olmoe`` brings its maker: expert banks and a float32 router in
    the program's layout, from the same seed the same arrays."""
    bench = spec.Bench(tiny_root)
    m = cell.model(bench, bench.config("tiny-olmoe"))
    w = m.weights(2**33 + 1)
    moe = w["periods"][0]["moe"]
    L, E, d, ff = (m.dims[k] for k in ("n_layers", "n_experts", "d_model",
                                       "d_ff"))
    assert moe["w_up"].shape == moe["w_gate"].shape == (L, E, d, ff)
    assert moe["w_down"].shape == (L, E, ff, d)
    assert moe["router"]["w"].dtype == jnp.float32
    assert moe["w_up"].dtype == jnp.bfloat16
    again = m.weights(2**33 + 1)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(w), jax.tree_util.tree_leaves(again)))


def test_program_keys_come_from_the_family(tiny_root):
    """The family's ``PROGRAM_KEYS`` are checked (the error names the
    key), and ``"resize": true`` sets them."""
    bench = spec.Bench(tiny_root)
    conf = bench.config("tiny-olmoe")
    ref = bench.reference(conf["family"])
    dims = ref.dims(conf)
    cfg = cell.program_config(conf, dims, ref)
    assert (cfg.n_experts, cfg.experts_per_token) == (8, 2)
    fixed = dict(conf, program={"arch": "olmoe-1b-7b"})
    with pytest.raises(ValueError, match="experts_per_token"):
        cell.program_config(fixed, dict(dims, d_model=2048, n_heads=16,
                                        n_kv_heads=16, head_dim=128,
                                        d_ff=1024, vocab_size=50304,
                                        n_experts=64), ref)


def test_reference_departs_when_the_model_does(tiny_root):
    """The comparison can see a difference: a reference with its rotary
    embedding cut to half the channels (the published Minitron) does not
    match the program, which rotates every channel."""
    from repro.models import lm

    bench = spec.Bench(tiny_root)
    conf = dict(bench.config("tiny-nemotron"), partial_rotary_factor=0.5)
    m = cell.model(bench, conf)
    w = params.build(m.ref, m.dims, 3, jnp.float32)
    toks = np.arange(1, 41)
    got = m.ref.logits(w, conf, toks.tolist(), np.arange(40))
    prog = lm.forward(w, m.cfg, jnp.asarray(toks[None]),
                      dtype=jnp.float32)[0][0]
    assert np.abs(got - np.asarray(prog)).max() > 1e-2


def test_program_cut_comes_from_the_family(tiny_root):
    """``PROGRAM_CUT`` names the fields taken from the file's sizes
    unchecked; without it only ``n_layers`` is, and the default keys are
    checked."""
    from types import SimpleNamespace as NS

    bench = spec.Bench(tiny_root)
    conf = dict(bench.config("tiny-gpt2"), program={"arch": "gpt2-345m"})
    ref = bench.reference(conf["family"])
    dims = dict(ref.dims(conf), d_model=1024, n_heads=16, n_kv_heads=16,
                head_dim=64, d_ff=4096, vocab_size=50257)
    assert cell.program_config(conf, dims, ref).n_layers == 2
    with pytest.raises(ValueError, match="vocab_size"):
        cell.program_config(conf, dict(dims, vocab_size=512), ref)
    cut = NS(PROGRAM_CUT=("n_layers", "vocab_size"),
             PROGRAM_KEYS=cell.PROGRAM_KEYS[:-1])
    cfg = cell.program_config(conf, dict(dims, vocab_size=512), cut)
    assert (cfg.n_layers, cfg.vocab_size) == (2, 512)
