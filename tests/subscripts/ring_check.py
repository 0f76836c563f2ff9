"""Subprocess body for multi-device ring tests (8 virtual CPU devices).

Exits 0 on success; any assertion error propagates as non-zero exit.
"""
import os
import sys

# forced host devices exist only on the CPU backend; pinning it also
# keeps this child off any chip its parent process holds
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import compat, ring  # noqa: E402
from repro.core.collectives import compressed_psum  # noqa: E402


def main():
    mesh = compat.make_mesh((8,), ("model",))
    rng = np.random.default_rng(0)

    # --- ring / naive collective matmuls == dense matmul ---
    for M, K, N in ((16, 64, 128), (8, 128, 256), (4, 256, 64)):
        x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
        want = np.asarray(x @ w)
        for strat in ("ring_ag", "naive_ag", "ring_rs", "naive_rs"):
            got = np.asarray(ring.tp_matmul(x, w, mesh, "model", strat))
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4,
                                       err_msg=strat)

    # --- ring overlap vs naive: identical results across dtypes ---
    xb = jnp.asarray(rng.normal(size=(16, 64)), jnp.bfloat16)
    wb = jnp.asarray(rng.normal(size=(64, 128)), jnp.bfloat16)
    a = np.asarray(ring.tp_matmul(xb, wb, mesh, "model", "ring_ag"),
                   np.float32)
    b = np.asarray(ring.tp_matmul(xb, wb, mesh, "model", "naive_ag"),
                   np.float32)
    np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2)

    # --- compressed int8 ring all-reduce ~= exact psum ---
    xs = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    f = compat.shard_map(lambda x: compressed_psum(x[0], "model")[None],
                         mesh=mesh, in_specs=P("model", None),
                         out_specs=P("model", None))
    got = np.asarray(f(xs))
    want = np.asarray(jnp.sum(xs, axis=0))
    rel = np.abs(got - want[None]).max() / np.abs(want).max()
    assert rel < 0.05, rel

    # --- explicit ppermute count: ring_ag lowers collective-permute ops ---
    xl = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    wl = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    txt = (
        jax.jit(lambda x, w: ring.tp_matmul(x, w, mesh, "model", "ring_ag"))
        .lower(xl, wl).compile().as_text()
    )
    assert "collective-permute" in txt, "ring schedule missing from HLO"
    txt2 = (
        jax.jit(lambda x, w: ring.tp_matmul(x, w, mesh, "model", "naive_ag"))
        .lower(xl, wl).compile().as_text()
    )
    assert "all-gather" in txt2

    # --- sharded W8A8 matmul: bit-identical to the local Fused MP kernel ---
    from repro.kernels import ops

    xq = jnp.asarray(rng.integers(-127, 128, (8, 64)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (64, 128)), jnp.int8)
    xs_ = jnp.asarray(rng.uniform(0.01, 0.1, (8, 1)), jnp.float32)
    ws_ = jnp.asarray(rng.uniform(0.01, 0.1, (1, 128)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    want_q = np.asarray(ops.quant_matmul(xq, wq, xs_, ws_, bias,
                                         out_dtype=jnp.float32))
    got_q = np.asarray(ring.tp_quant_matmul(xq, wq, xs_, ws_, bias,
                                            mesh=mesh,
                                            out_dtype=jnp.float32))
    # column sharding touches no reduction: results must be bitwise equal
    np.testing.assert_array_equal(got_q, want_q)

    # --- serving engine routed through ring-TP == plain engine ---
    # (dense AND quantized: mesh= must not silently fall back to dense on
    # the W8A8 path — its matmuls route through tp_quant_matmul)
    from repro.configs import get_config
    from repro.models import lm
    from repro.serving.engine import ServeEngine

    cfg = get_config("gpt2-345m").reduced()  # d=64, ff=128, V=512: all %8==0
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=32)
    cal = [jnp.asarray([[2, 3, 4, 5, 6, 7, 8, 9]])]
    for quantized in (False, True):
        outs = {}
        for label, m in (("plain", None), ("ring", mesh)):
            eng = ServeEngine(cfg, params, batch_slots=1, max_seq=32,
                              eos_id=-1, chunk_size=8, mesh=m,
                              quantized=quantized,
                              calibration_batches=cal if quantized else None)
            eng.submit([5, 6, 7, 8], max_new=3)
            outs[label] = eng.run()[0].out
        assert outs["plain"] == outs["ring"], (quantized, outs)

    # the quantized ring path really shards: under tp_context the linear's
    # output is column-partitioned over all 8 devices (each holds N/8
    # columns; no collective is *needed* — replicated-input column
    # parallelism is communication-free, the cheapest point on the ring)
    from repro.core import quant
    from repro.models.layers import linear, tp_context

    qlin = quant.quantize_linear_params(
        jnp.asarray(rng.normal(size=(cfg.d_model, 128)), jnp.float32), None)
    x_in = jnp.asarray(rng.normal(size=(4, cfg.d_model)), jnp.float32)
    with tp_context(mesh):
        y_q = jax.jit(lambda a: linear(qlin, a))(x_in)
    shards = y_q.addressable_shards
    assert len(shards) == 8 and len({s.device for s in shards}) == 8
    assert all(s.data.shape == (4, 128 // 8) for s in shards), (
        "quantized linear under tp_context did not column-shard",
        [s.data.shape for s in shards])

    print("RING_OK")


if __name__ == "__main__":
    main()
