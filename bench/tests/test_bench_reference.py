"""Each plain reference agrees with the program's own full-sequence
forward at a reduced size, on the same weights from the seed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtools  # noqa: F401  (puts bench/ and src/ on the path)
from harness import cell, params, spec


@pytest.mark.parametrize("config", ["tiny-gpt2", "tiny-nemotron"])
def test_reference_matches_lm_forward(tiny_root, config):
    from repro.models import lm

    bench = spec.Bench(tiny_root)
    conf = bench.config(config)
    ref = bench.reference(conf["family"])
    dims = ref.dims(conf)
    cfg = cell.program_config(conf, dims)
    w = params.build(dims, 2**32 + 17, jnp.float32)
    toks = np.random.default_rng(0).integers(1, dims["vocab_size"], 40)
    want = np.arange(40)
    got = ref.logits(w, conf, toks.tolist(), want)
    with jax.default_matmul_precision("highest"):
        prog = lm.forward(w, cfg, jnp.asarray(toks[None]),
                          dtype=jnp.float32)[0][0]
    np.testing.assert_allclose(got, np.asarray(prog), rtol=1e-4, atol=1e-4)


def test_reference_departs_when_the_model_does(tiny_root):
    """The comparison can see a difference: a reference with its rotary
    embedding cut to half the channels (the published Minitron) does not
    match the program, which rotates every channel."""
    from repro.models import lm

    bench = spec.Bench(tiny_root)
    conf = dict(bench.config("tiny-nemotron"), partial_rotary_factor=0.5)
    ref = bench.reference(conf["family"])
    dims = ref.dims(conf)
    cfg = cell.program_config(conf, dims)
    w = params.build(dims, 3, jnp.float32)
    toks = np.arange(1, 41)
    got = ref.logits(w, conf, toks.tolist(), np.arange(40))
    prog = lm.forward(w, cfg, jnp.asarray(toks[None]),
                      dtype=jnp.float32)[0][0]
    assert np.abs(got - np.asarray(prog)).max() > 1e-2
