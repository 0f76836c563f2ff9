"""Whole runs of the CPU-size four-chip test cell on four virtual CPU
devices, one JSON line per case: a sound run (untraced and traced) and
runs with the timed path broken underneath.

    python bench/tests/four_chip_runs.py <checkout> <compile-cache-dir>

``<checkout>`` holds the test cells (``benchtools.make_root``).  The
forced host devices exist only on the CPU backend, so this runs in a
process of its own.
"""
import json
import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[2]
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import cell  # noqa: E402

CELL = "tiny-gpt2-chat-4chip"


def exchange_left_out(eng):
    """Shard 1's rows of the gathered logits are shard 0's: the ring
    all-gather's exchange did not happen for them."""
    step, Bs = eng._step, eng.Bs

    def stale(*a):
        logits, cache = step(*a)
        return logits.at[Bs:2 * Bs].set(logits[:Bs]), cache

    eng._step = stale


def state_unchanged(eng):
    step = eng._step

    def stale(p, tok, cache, *rest):
        logits, _ = step(p, tok, cache, *rest)
        return logits, cache

    eng._step = stale


def half_batch_left_out(eng):
    """Every second decoding row, over all shards, gets no logits."""
    step = eng._step

    def half(*a):
        logits, cache = step(*a)
        active = a[-1].reshape(-1)
        skip = active & (jnp.cumsum(active) % 2 == 0)
        return jnp.where(skip[:, None], 0.0, logits), cache

    eng._step = half


def altered_tokens(eng):
    sample, V = eng._sample, eng.cfg.vocab_size

    def wrong(*a):
        return (sample(*a) + 1) % V

    eng._sample = wrong


CASES = {"sound": (None, 0), "sound_traced": (None, 1)}
CASES.update({f.__name__: (f, 0) for f in (
    exchange_left_out, state_unchanged, half_batch_left_out,
    altered_tokens)})


def main() -> int:
    assert len(jax.devices()) == 4, jax.devices()
    root = pathlib.Path(sys.argv[1])
    for name, (fault, trace) in CASES.items():
        rc = cell.run(["--workload", CELL, "--seed", str(2**32 + 5),
                       "--seconds", "2", "--trace", str(trace)],
                      t_start=0.0, root=root, require_tpu=False,
                      fault=fault)
        sys.stdout.flush()
        print(json.dumps({"case": name, "rc": rc}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
