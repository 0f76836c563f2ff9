"""Smoke run of the serving system on a TPU: gpt2-345m at full width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip phase only

One chip, in order (any failure exits non-zero):

1. The paged attention kernel (decode, linear verify, tree verify; and
   grouped heads at minitron-4b's widths) and the W8A8 matmul against
   their ``kernels/ref.py`` oracles at the shapes the served run uses.
   Each error is printed beside its tolerance, and each attention
   tolerance is shown to reject the oracle with its mask moved by one
   page.
2. The launcher's default engine (``repro.launch.serve``: W8A8, paged KV,
   chunked prefill) serves 8 seeded requests at max_seq 1024 on 8 slots,
   prompts of 64-768 tokens, 32 greedy new tokens each.  Every request
   must complete with finite logits, and the compiled decode and prefill
   steps must hold a Pallas kernel (``tpu_custom_call``).
3. The same with ``--no-quant`` (bf16 weights and activations).

``--chips 4`` runs only the distributed phase: ``DistributedServeEngine``
over all four chips against ``ServeEngine`` on one of them, on the same
requests.  Every K/V shard must sit on its own chip and the greedy
streams must be token-identical; a divergence is printed with the
reference's logit margin and fails the run.

Weights are random from a fixed seed.  The last stdout line is a JSON
object naming the device.  With no TPU the script exits non-zero before
it prints any result.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve as launch_serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# the served run: gpt2-345m at full width, one request per slot; the
# kernel phase uses the same geometry (PAGE is the engine's default)
ARCH, MAX_SEQ, SLOTS, CHUNK, PAGE = "gpt2-345m", 1024, 8, 32, 16
SERVE_ARGV = ["--arch", ARCH, "--max-seq", str(MAX_SEQ),
              "--slots", str(SLOTS), "--requests", str(SLOTS),
              "--prompt-len", "64", "768", "--max-new", "32",
              "--chunk-size", str(CHUNK)]

# attention: bf16 inputs, f32 softmax, bf16 output.  The two bf16 outputs
# may land one rounding step apart (rtol: two bf16 ulps), and the oracle
# rounds its probabilities to bf16 before the value product where the
# kernel does not (atol).  The phase checks that a mask one page off
# breaks this tolerance.
ATTN_ATOL, ATTN_RTOL = 1e-2, 2.0 ** -6
# W8A8: exact int32 accumulation and the same dequant order, so the bf16
# outputs may differ by at most one rounding step
QMM_REL_TOL = 2.0 ** -7


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


class StepRecorder:
    """Stands in for an engine's jitted step: keeps the abstract arguments
    of the first call (to recompile them and read the HLO), folds a
    finiteness check of every returned logits array into one flag, and
    keeps the most device memory in use seen right after a dispatch."""

    def __init__(self, fn):
        self.fn = fn
        self.args = None
        self.calls = 0
        self.finite = jnp.asarray(True)
        self.max_in_use = 0

    def __call__(self, *args):
        if self.args is None:
            self.args = jax.tree_util.tree_map(_abstract, args)
        out = self.fn(*args)
        self.finite = jnp.logical_and(self.finite,
                                      jnp.isfinite(out[0]).all())
        self.calls += 1
        stats = jax.devices()[0].memory_stats() or {}
        self.max_in_use = max(self.max_in_use, stats.get("bytes_in_use", 0))
        return out

    def compiled_hlo(self) -> str:
        return self.fn.lower(*self.args).compile().as_text()


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x


def _release(label: str) -> None:
    """Collect what the finished phase left, then print chip 0's memory."""
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[memory] after {label}: bytes_in_use "
          f"{stats.get('bytes_in_use')}, peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')} on {jax.devices()[0]}")


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# ---------------------------------------------------------------------------
# phase 1: kernels against their oracles
# ---------------------------------------------------------------------------


def _page_shift(pos: jax.Array, top: int) -> jax.Array:
    """Every position moved one page up, or down where that passes
    ``top``."""
    return jnp.where(pos + PAGE <= top, pos + PAGE, pos - PAGE)


def _tree_anc(rng, n: int) -> np.ndarray:
    """Ancestor-or-self bitmask of a random token tree over n nodes."""
    parent = [-1] + [int(rng.integers(0, i)) for i in range(1, n)]
    anc = np.zeros((n, n), bool)
    for i in range(n):
        j = i
        while j >= 0:
            anc[i, j] = True
            j = parent[j]
    return anc


def _attn_case(name, got, want, shifted):
    """Worst ``|got - want| / (atol + rtol * |want|)`` must be <= 1, and
    > 1 against the oracle whose mask is one page off."""
    def worst(other):
        other = other.astype(jnp.float32)
        diff = jnp.abs(got.astype(jnp.float32) - other)
        ratio = diff / (ATTN_ATOL + ATTN_RTOL * jnp.abs(other))
        return float(jnp.max(diff)), float(jnp.max(ratio))

    err, ratio = worst(want)
    off_err, off_ratio = worst(shifted)
    print(f"[kernel] {name}: max_abs_err {err!r}, worst err/tol {ratio!r} "
          f"(tol {ATTN_ATOL} + {ATTN_RTOL} * |oracle|); mask one page off: "
          f"max_abs_err {off_err!r}, worst err/tol {off_ratio!r}")
    _check(ratio <= 1.0, f"{name} exceeds its tolerance ({ratio})")
    _check(off_ratio > 1.0,
           f"{name}: the tolerance does not reject a mask one page off")


def kernel_phase() -> None:
    cfg = get_config(ARCH)
    heads, head_dim = cfg.n_heads, cfg.head_dim
    rng = np.random.default_rng(0)
    n_pg = MAX_SEQ // PAGE
    pool = 1 + SLOTS * n_pg  # page 0 is the null page
    kp = jnp.asarray(rng.standard_normal((pool, heads, PAGE, head_dim)),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((pool, heads, PAGE, head_dim)),
                     jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(np.arange(1, pool)).reshape(SLOTS, n_pg),
                     jnp.int32)

    # decode: lengths on both sides of page edges, up to the full cache
    lens = jnp.asarray([1, 16, 17, 100, 511, 512, 513, 1024])
    q = jnp.asarray(rng.standard_normal((SLOTS, heads, head_dim)),
                    jnp.bfloat16)
    _attn_case(
        "paged decode (C=1)",
        ops.paged_mha_decode(q, kp, vp, lens, bt, backend="pallas"),
        ref.paged_mha_decode_ref(q, kp, vp, lens, bt),
        ref.paged_mha_decode_ref(q, kp, vp, _page_shift(lens, MAX_SEQ), bt))

    # verify / prefill chunk of CHUNK positions, linear and tree masks
    base = jnp.asarray([0, 15, 16, 31, 200, 480, 500, MAX_SEQ - CHUNK])
    moved = _page_shift(base, MAX_SEQ - CHUNK)
    qc = jnp.asarray(rng.standard_normal((SLOTS, CHUNK, heads, head_dim)),
                     jnp.bfloat16)
    anc = jnp.asarray(np.stack([_tree_anc(rng, CHUNK) for _ in range(SLOTS)]))
    for name, tree in (("linear", None), ("tree", anc)):
        _attn_case(
            f"paged verify {name} (C={CHUNK})",
            ops.paged_verify(qc, kp, vp, base, bt, anc=tree,
                             backend="pallas"),
            ref.paged_verify_ref(qc, kp, vp, base, bt, anc=tree),
            ref.paged_verify_ref(qc, kp, vp, moved, bt, anc=tree))

    # grouped heads at minitron-4b's widths (24 query heads over 8 KV heads
    # of 128, max_seq 4096): decode with a tag-along row, and a chunk
    gh, gkv, gd, gseq = 24, 8, 128, 4096
    g_npg = gseq // PAGE
    gpool = 1 + 4 * g_npg
    gk = jnp.asarray(rng.standard_normal((gpool, gkv, PAGE, gd)), jnp.bfloat16)
    gv = jnp.asarray(rng.standard_normal((gpool, gkv, PAGE, gd)), jnp.bfloat16)
    gbt = jnp.asarray(rng.permutation(np.arange(1, gpool)).reshape(4, g_npg),
                      jnp.int32)
    glens = jnp.asarray([1, 129, 1000, gseq])
    gq = jnp.asarray(rng.standard_normal((4, gh, gd)), jnp.bfloat16)
    _attn_case(
        "paged decode GQA (24/8 heads of 128)",
        ops.paged_mha_decode(gq, gk, gv, glens, gbt, backend="pallas"),
        ref.paged_mha_decode_ref(gq, gk, gv, glens, gbt),
        ref.paged_mha_decode_ref(gq, gk, gv, _page_shift(glens, gseq), gbt))
    active = jnp.asarray([True, False, True, False])
    tagged = ops.paged_mha_decode(gq, gk, gv, glens, gbt, active=active,
                                  backend="pallas")
    _check(not bool(jnp.any(tagged[1::2] != 0)),
           "paged decode: a tag-along row's output is not zero")
    gbase = jnp.asarray([0, 120, 2000, gseq - CHUNK])
    gqc = jnp.asarray(rng.standard_normal((4, CHUNK, gh, gd)), jnp.bfloat16)
    _attn_case(
        f"paged verify GQA (C={CHUNK})",
        ops.paged_verify(gqc, gk, gv, gbase, gbt, backend="pallas"),
        ref.paged_verify_ref(gqc, gk, gv, gbase, gbt),
        ref.paged_verify_ref(gqc, gk, gv, _page_shift(gbase, gseq - CHUNK),
                             gbt))

    # W8A8 at the decode batch and the prefill chunk, gpt2's MLP-up shape
    d_model, d_ff = cfg.d_model, cfg.d_ff
    for m in (SLOTS, CHUNK):
        xq = jnp.asarray(rng.integers(-127, 128, (m, d_model)), jnp.int8)
        wq = jnp.asarray(rng.integers(-127, 128, (d_model, d_ff)), jnp.int8)
        xs = jnp.asarray(rng.uniform(1e-3, 5e-2, (m, 1)), jnp.float32)
        ws = jnp.asarray(rng.uniform(1e-3, 5e-2, (1, d_ff)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal(d_ff), jnp.float32)
        got = ops.quant_matmul(xq, wq, xs, ws, bias,
                               backend="pallas").astype(jnp.float32)
        want = ref.quant_matmul_ref(xq, wq, xs, ws, bias).astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(got - want)
                            / jnp.maximum(jnp.abs(want), 1e-6)))
        print(f"[kernel] quant_matmul M={m} K={d_model} N={d_ff}: "
              f"max_rel_err {rel!r} (tol {QMM_REL_TOL})")
        _check(rel <= QMM_REL_TOL, f"quant_matmul M={m} error {rel}")


# ---------------------------------------------------------------------------
# phases 2-3: the launcher's engine serving at full width
# ---------------------------------------------------------------------------


def serve_phase(label: str, argv, clock: CompileClock):
    """Serve the launcher's requests; return the recorded decode and
    prefill steps."""
    args = launch_serve.build_parser().parse_args(argv)
    c0, t0 = clock.seconds, time.perf_counter()
    eng = launch_serve.build_engine(args)
    step, prefill = StepRecorder(eng._step), StepRecorder(eng._prefill)
    eng._step, eng._prefill = step, prefill
    c1, t1 = clock.seconds, time.perf_counter()
    eng, done = launch_serve.serve(args, eng)
    wall = time.perf_counter() - t1
    cfg = eng.cfg
    new_tokens = sum(len(r.out) for r in done)
    plens = sorted(len(r.prompt) for r in done)
    print(f"[serve:{label}] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}x{cfg.head_dim} heads, vocab "
          f"{cfg.vocab_size}; kv {eng.kv_layout}, prefill "
          f"{eng.prefill_mode}, act {jnp.dtype(eng.act_dtype).name}, "
          f"{'W8A8' if not args.no_quant else 'bf16'}")
    print(f"[serve:{label}] {len(done)} requests, prompts {plens}, "
          f"{new_tokens} new tokens; {prefill.calls} prefill calls, "
          f"{step.calls} decode steps; most bytes_in_use right after a "
          f"dispatch: prefill {prefill.max_in_use}, decode "
          f"{step.max_in_use}")
    print(f"[serve:{label}] set-up {t1 - t0!r} s (compile {c1 - c0!r} s); "
          f"serve wall {wall!r} s (compile {clock.seconds - c1!r} s)")
    print(f"[serve:{label}] {new_tokens / wall!r} tokens/s over one smoke "
          f"run (compile included; not a throughput measurement)")
    _check(len(done) == args.requests and eng.stalled == 0,
           f"{label}: {len(done)} of {args.requests} requests completed")
    _check(all(len(r.out) == args.max_new for r in done),
           f"{label}: a request ended before {args.max_new} new tokens")
    _check(bool(step.finite) and bool(prefill.finite),
           f"{label}: non-finite logits")
    return step, prefill


def kernel_sites(label: str, step: StepRecorder,
                 prefill: StepRecorder) -> None:
    """The compiled decode and prefill steps must call Pallas kernels."""
    for name, rec in (("decode", step), ("prefill", prefill)):
        n = rec.compiled_hlo().count("tpu_custom_call")
        print(f"[serve:{label}] compiled {name} step: {n} tpu_custom_call "
              "sites")
        _check(n > 0, f"{label}: compiled {name} step has no Pallas kernel")


# ---------------------------------------------------------------------------
# --chips 4: the distributed engine against one chip
# ---------------------------------------------------------------------------


def _logit_margin(params, cfg, dtype, prompt, prefix) -> float:
    """Top-1 minus top-2 logit of the reference model's next token after
    ``prompt + prefix`` (a plain full-sequence forward)."""
    from repro.models import lm

    toks = jnp.asarray([list(prompt) + list(prefix)], jnp.int32)
    logits = lm.forward(params, cfg, toks, dtype=dtype)[0]
    top2 = jax.lax.top_k(logits[0, -1].astype(jnp.float32), 2)[0]
    return float(top2[0] - top2[1])


def four_chip_phase(argv, clock: CompileClock) -> None:
    from repro.serving.distributed import DistributedServeEngine

    devs = jax.devices()
    _check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    args = launch_serve.build_parser().parse_args(argv)

    c0, t0 = clock.seconds, time.perf_counter()
    ref_eng, ref_done = launch_serve.serve(args)
    print(f"[4chip] ServeEngine on {devs[0]}: {len(ref_done)} requests in "
          f"{time.perf_counter() - t0!r} s (compile {clock.seconds - c0!r} s)")
    want = {tuple(r.prompt): r.out for r in ref_done}
    # keep only what a divergence report needs: chip 0 also holds a shard
    ref_model = (ref_eng.params, ref_eng.cfg, ref_eng.act_dtype)
    del ref_eng, ref_done
    _release("4chip: ServeEngine released")

    cfg, params, calibration = launch_serve.load_model(args)
    c0, t0 = clock.seconds, time.perf_counter()
    eng = DistributedServeEngine(
        cfg, params, n_shards=4, slots_per_shard=args.slots // 4,
        max_seq=args.max_seq, eos_id=-1, quantized=not args.no_quant,
        calibration_batches=calibration, chunk_size=args.chunk_size)
    for p in launch_serve.make_prompts(args, cfg.vocab_size):
        eng.submit(p, max_new=args.max_new)
    done = eng.run()
    print(f"[4chip] DistributedServeEngine over {eng.D} chips "
          f"({eng.kv_layout}): {len(done)} requests in "
          f"{time.perf_counter() - t0!r} s (compile "
          f"{clock.seconds - c0!r} s)")
    _check(len(done) == args.requests and eng.stalled == 0,
           f"distributed: {len(done)} of {args.requests} requests completed")

    leaves = jax.tree_util.tree_leaves(eng.cache)
    for leaf in leaves:
        shard_devs = [s.device for s in leaf.addressable_shards]
        _check(len(set(shard_devs)) == 4
               and all(s.data.shape[0] == 1 for s in leaf.addressable_shards),
               f"cache leaf {leaf.shape} is not one shard per chip")
    print(f"[4chip] cache: {len(leaves)} leaves, each split one shard per "
          f"chip over {sorted(d.id for d in devs)}")
    _check(all(leaf.sharding.is_fully_replicated
               and len(leaf.sharding.device_set) == 4
               for leaf in jax.tree_util.tree_leaves(eng.params)),
           "params are not replicated on all four chips")
    print("[4chip] params: replicated on all four chips")

    diverged = 0
    for r in done:
        ref_out = want[tuple(r.prompt)]
        if r.out == ref_out:
            continue
        diverged += 1
        t = next(i for i, (a, b) in enumerate(zip(r.out, ref_out)) if a != b)
        margin = _logit_margin(*ref_model, r.prompt, ref_out[:t])
        print(f"[4chip] DIVERGED req {r.rid} ({len(r.prompt)}-token prompt) "
              f"at new token {t}: distributed {r.out[t]} vs one chip "
              f"{ref_out[t]}; reference top-2 logit margin {margin!r}")
    print(f"[4chip] greedy streams: {len(done) - diverged} of {len(done)} "
          f"token-identical to the one-chip ServeEngine")
    _check(diverged == 0, f"{diverged} greedy streams diverged")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend: {devs[0].platform});"
              " this smoke run needs a TPU chip and has no CPU fallback",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}")

    if args.chips == 4:
        four_chip_phase(SERVE_ARGV, clock)
    else:
        kernel_phase()
        _release("kernels")
        for label, argv in (("w8a8", SERVE_ARGV),
                            ("bf16", SERVE_ARGV + ["--no-quant"])):
            kernel_sites(label, *serve_phase(label, argv, clock))
            _release(f"serve:{label}")

    _release("end")
    print(f"[device] compile total {clock.seconds!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
