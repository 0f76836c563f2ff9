"""End-to-end behaviour: train a tiny model on synthetic data, quantize it
with SmoothQuant, serve it through the continuous-batching engine — the
full LoopLynx pipeline (paper Fig 1 + Fig 2) at reduced scale."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import SyntheticLM
from repro.serving.engine import ServeEngine
from repro.training import optimizer as opt
from repro.training.trainer import TrainConfig, Trainer


def test_train_quantize_serve_pipeline():
    cfg = get_config("gpt2-345m").reduced()
    tcfg = TrainConfig(opt=opt.AdamWConfig(lr=1e-3, warmup_steps=5,
                                           total_steps=80))
    data = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(cfg, tcfg, data, d, max_seq=64, ckpt_every=25)
        tr.init_or_restore()
        m = tr.run(60)
        assert np.isfinite(m["loss"])
        params = tr.state.params

    # serve the trained weights, quantized, with batched requests
    cal = [jnp.asarray(data.batch_at(500)["tokens"][:, :8])]
    eng = ServeEngine(cfg, params, batch_slots=2, max_seq=64, eos_id=-1,
                      quantized=True, calibration_batches=cal)
    for i in range(4):
        eng.submit([i + 1, 2, 3], max_new=5)
    done = eng.run()
    assert len(done) == 4
    assert all(len(r.out) == 5 for r in done)
    s = eng.stats()
    assert s["mdk_mp_reuse"] == 4 * cfg.n_layers + 1  # temporal reuse live
    # deterministic: same prompt, same continuation
    outs = {tuple(r.prompt): r.out for r in done}
    eng2 = ServeEngine(cfg, params, batch_slots=1, max_seq=64, eos_id=-1,
                       quantized=True, calibration_batches=cal)
    eng2.submit([1, 2, 3], max_new=5)
    assert eng2.run()[0].out == outs[(1, 2, 3)]


def test_launcher_serves_seeded_prompts_in_range():
    """The launcher's functions (shared with chip_smoke.py) serve every
    request to max_new with prompt lengths drawn from --prompt-len."""
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--reduced", "--requests", "3", "--max-new", "3", "--max-seq", "64",
         "--prompt-len", "5", "7"])
    eng, done = serve.serve(args)
    assert len(done) == 3 and eng.stalled == 0
    assert all(5 <= len(r.prompt) <= 7 and len(r.out) == 3 for r in done)
    assert serve.make_prompts(args, eng.cfg.vocab_size) == [
        r.prompt for r in sorted(done, key=lambda r: r.rid)]


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache goes to the checkout's fixed .jax_cache."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_CACHE)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.REPO_CACHE.parent.joinpath(
            "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
