"""Production serving launcher: W8A8 continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-345m --reduced \
        --requests 8 --max-new 16

Loads (or randomly initializes) weights, SmoothQuant-calibrates on
synthetic prompts, and serves a batch of seeded requests, reporting the
device it served on, the generated tokens and the engine's stats.
``--ckpt-dir`` restores trained weights saved by launch/train.py.
``chip_smoke.py`` at the repo root serves through the same functions.
"""
from __future__ import annotations

import argparse
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, list_archs
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import ServeEngine
from repro.serving.lifecycle import Request


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-345m", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(3, 9),
                    metavar=("MIN", "MAX"),
                    help="prompt lengths are drawn uniformly from "
                         "[MIN, MAX] by a fixed seed")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--chunk-size", type=int, default=32)
    ap.add_argument("--prefill-mode", default="auto",
                    choices=("auto", "chunked", "replay"),
                    help="auto == chunked for every block kind (hybrid "
                         "rotating-window/recurrent stacks included); "
                         "replay is a deprecated A/B debug mode")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a launch/train.py checkpoint")
    return ap


def load_model(args: argparse.Namespace):
    """``(cfg, params, calibration_batches)``: the config, its seeded (or
    restored) weights and the SmoothQuant calibration prompts."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder:
        raise ValueError("the serve launcher drives decoder-only archs")
    params = lm.init(cfg, jax.random.PRNGKey(0), max_seq=args.max_seq)
    if args.ckpt_dir:
        from repro.training.trainer import TrainConfig, \
            init_train_state_abstract

        like = init_train_state_abstract(cfg, TrainConfig(),
                                         max_seq=args.max_seq)
        state = CheckpointManager(args.ckpt_dir).restore(None, like)
        params = state.params
        print(f"[serve] restored params from {args.ckpt_dir}")

    data = SyntheticLM(cfg.vocab_size, 16, 2, seed=11)
    return cfg, params, [jnp.asarray(data.batch_at(0)["tokens"])]


def build_engine(args: argparse.Namespace) -> ServeEngine:
    """The launcher's engine: W8A8 unless ``--no-quant``, paged KV,
    chunked prefill."""
    if args.prefill_mode == "replay":
        print("[serve] note: --prefill-mode replay is deprecated — the "
              "chunked path covers every block kind, so auto == chunked; "
              "replay remains only for A/B debugging against the seed "
              "one-token-per-tick engine")
    cfg, params, calibration = load_model(args)
    return ServeEngine(
        cfg, params, batch_slots=args.slots, max_seq=args.max_seq,
        eos_id=-1, quantized=not args.no_quant,
        calibration_batches=calibration,
        chunk_size=args.chunk_size, prefill_mode=args.prefill_mode)


def make_prompts(args: argparse.Namespace, vocab: int) -> List[List[int]]:
    """``--requests`` seeded prompts with lengths in ``--prompt-len``."""
    lo, hi = args.prompt_len
    rng = np.random.default_rng(0)
    prompts = []
    for _ in range(args.requests):
        plen = int(rng.integers(lo, hi + 1))
        prompts.append([int(t) for t in rng.integers(1, vocab, plen)])
    return prompts


def serve(args: argparse.Namespace,
          eng: Optional[ServeEngine] = None
          ) -> Tuple[ServeEngine, List[Request]]:
    """Build the engine (unless given), submit the seeded requests and
    serve them to completion."""
    eng = eng or build_engine(args)
    for p in make_prompts(args, eng.cfg.vocab_size):
        eng.submit(p, max_new=args.max_new)
    return eng, eng.run()


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    eng, done = serve(args)
    dev = jax.devices()[0]
    print(f"[serve] device: {dev.platform} ({dev.device_kind}) "
          f"x{len(jax.devices())}")
    for r in done[:4]:
        print(f"[serve] req {r.rid}: {len(r.prompt)} prompt -> {r.out}")
    print(f"[serve] stats: {eng.stats()}")


if __name__ == "__main__":
    main()
