"""End-to-end serving driver — the paper's workload (GPT-2, W8A8, batched
auto-regressive generation through the MDK scheduler).

    PYTHONPATH=src python examples/serve_gpt2.py            # reduced (CPU)
    PYTHONPATH=src python examples/serve_gpt2.py --full     # real 345M cfg

Builds GPT-2, calibrates SmoothQuant on synthetic prompts, serves a batch
of requests through the scheduler-driven engine (chunked prefill +
continuous batching + per-request sampling), and reports TTFT / per-token
latency plus the MDK temporal-reuse counters and the analytic FPGA model's
prediction for the same workload (Table II linkage).
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.perfmodel import FPGAPerfModel
from repro.core.scheduler import mdk_stats, spatial_equivalent_kernels
from repro.data.pipeline import SyntheticLM
from repro.models import lm
from repro.serving.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="use the real 345M config (slow on CPU)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--chunk-size", type=int, default=16)
    ap.add_argument("--prefill-mode", default="auto",
                    choices=("auto", "chunked", "replay"))
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    args = ap.parse_args()

    cfg = get_config("gpt2-345m")
    if not args.full:
        cfg = cfg.reduced()
    max_seq = 128
    print(f"building {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"V={cfg.vocab_size}")
    t0 = time.time()
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=max_seq)
    print(f"init: {time.time()-t0:.1f}s, "
          f"{sum(x.size for x in jax.tree_util.tree_leaves(params))/1e6:.1f}M params")

    data = SyntheticLM(cfg.vocab_size, 16, 2, seed=7)
    cal = [jnp.asarray(data.batch_at(0)["tokens"])]
    eng = ServeEngine(cfg, params, batch_slots=args.slots, max_seq=max_seq,
                      eos_id=-1, quantized=True, calibration_batches=cal,
                      chunk_size=args.chunk_size,
                      prefill_mode=args.prefill_mode)
    print(f"engine: prefill_mode={eng.prefill_mode} "
          f"chunk={eng.chunk_size} budget={eng.admission.budget_tokens} "
          f"tok/tick")

    from repro.serving.sampler import SamplingParams
    sampling = SamplingParams(temperature=args.temperature)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        # mixed lengths: odd requests bring chunk-sized+ prompts (clamped
        # so prompt + generation always fit the cache)
        cap = max_seq - args.max_new - 1
        lo, hi = (3, 9) if i % 2 == 0 else (
            min(args.chunk_size, cap // 2), min(2 * args.chunk_size, cap))
        plen = int(rng.integers(lo, max(hi, lo + 1)))
        eng.submit(list(rng.integers(1, cfg.vocab_size, plen)),
                   max_new=args.max_new, sampling=sampling)
    t0 = time.time()
    done = eng.run()
    wall = time.time() - t0
    toks = sum(len(r.out) for r in done)
    s = eng.stats()
    dev = jax.devices()[0]
    print(f"served {len(done)} requests, {toks} new tokens in {wall:.2f}s "
          f"({toks/wall:.1f} tok/s on {dev.platform} {dev.device_kind}, "
          "compile included)")
    print(f"TTFT {s['mean_ttft_s']*1e3:.1f} ms  "
          f"TPOT {s['mean_tok_latency_s']*1e3:.2f} ms  "
          f"{s['ticks']} ticks, {s['model_calls']} model calls "
          f"({s['prefill_calls']} prefill chunks)")
    print("engine stats:", s)

    stats = mdk_stats(cfg)
    print("\nMDK temporal reuse (one kernel instance serves all stages):")
    for kind, n in sorted(stats.reuse_factor().items()):
        print(f"  {kind:8s} x{n} activations/token "
              f"(spatial arch would instantiate {n} kernels)")

    print("\nanalytic FPGA model for this config (paper Table II method):")
    for n in (1, 2, 4):
        t = FPGAPerfModel(cfg, nodes=n).token_latency()
        print(f"  {n}-node: {t['total']*1e3:.2f} ms/token "
              f"({1/t['total']:.0f} tok/s)")


if __name__ == "__main__":
    main()
