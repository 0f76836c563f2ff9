"""Find a cell's sustained knee on the chip: the highest offered rate at
which the backlog does not grow.

    python bench/sweep.py --workload <cell> --rates 0.2 0.4 ... \
        [--seconds 40] [--seed 1]

One process builds the cell's engine once, then for each rate in turn
offers the cell's traffic at that rate for the lead-in and
``--seconds`` more, and cancels what is left.

Prefill is the stage that saturates first here (one chunk budget per
tick, and every tick as long as a decode step), so each rate reports
the share of the window's ticks that carried a prefill chunk: the
prefill stage's load, about proportional to the rate below the knee.
A rate is sustained when that share stays under 0.95 and the backlog of
requests due and still without a first token is no longer at the
window's end than at its start, give or take one.  ``rate / share`` is
then an estimate of the knee from each rate.  Each rate also reports
the requests due and completed in the window, its first tokens, the
rows per decode step and the share of KV pages in use at most.  The
table goes to stdout; the cell's traffic file takes 0.8 x the highest
rate sustained.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

T_START = time.monotonic()
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from harness import cell, device, drive, e2e, spec, traffic  # noqa


def prefill_share(rec) -> float:
    """Share of the window's ticks in which a prefill chunk ran."""
    w0, w1 = rec["window"]
    ticks = [t for t in rec["ticks"] if w0 < t["t1"] <= w1]
    starts = [c["t"] for c in rec["calls"] if c["kind"] == "prefill"]
    busy = sum(1 for t in ticks
               if any(t["t0"] <= c <= t["t1"] for c in starts))
    return busy / len(ticks) if ticks else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = spec.Bench()
    w = bench.workload(args.workload)
    conf = bench.config(w["config"])
    mix = bench.traffic(w["traffic"])
    try:
        device.check(w["chips"], bench.peaks())
    except device.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.telemetry import Telemetry

    enable_compile_cache()
    m = cell.model(bench, conf)
    cfg = m.cfg
    # a recording tracer: the drive loop logs the prefill calls from it
    eng = cell.build_engine(conf, cfg, m.weights(args.seed), args.seed,
                            Telemetry(trace=True), w["chips"])
    cell.warm_up(eng, w["chips"])
    print("rate_rps tok_s itl_p50_ms itl_p95_ms ttft_p50_ms ttft_p90_ms "
          "waiting_w0 waiting_w1 due done first_tokens rows_mean pages_frac "
          "prefill_share knee_est sustained", flush=True)
    for rate in args.rates:
        m = dict(mix, arrival=dict(mix["arrival"], rate_rps=rate))
        reqs = traffic.generate(m, args.seed, args.seconds, cfg.vocab_size)
        rec = drive.drive(eng, reqs, float(m["lead_in_s"]), args.seconds,
                          chips=w["chips"])
        w0, w1 = rec["window"]
        met = e2e.metrics(rec)
        t = e2e.ttfts(rec)
        q0, q1 = e2e.waiting_at(rec, w0), e2e.waiting_at(rec, w1)
        share = prefill_share(rec)
        ok = q1 <= q0 + 1 and share < 0.95
        knee = rate / share if share > 0 else float("inf")
        rows = [len(c["lens"]) for c in rec["calls"]
                if c["kind"] == "decode" and w0 < c["t"] <= w1]
        print(f"{rate} {met['tok_s']:.1f} "
              f"{met['itl_p50_ms'] or 0:.1f} {met['itl_p95_ms'] or 0:.1f} "
              f"{(e2e.pct(t, 50) or 0) * 1e3:.0f} "
              f"{(e2e.pct(t, 90) or 0) * 1e3:.0f} {q0} {q1} "
              f"{sum(1 for r in rec['requests'] if w0 <= r['due'] < w1)} "
              f"{e2e.done_in(rec)} {e2e.first_tokens_in_window(rec)} "
              f"{sum(rows) / max(len(rows), 1):.2f} "
              f"{(rec['pages_in_use_peak'] or 0) / rec['pages_total']:.3f} "
              f"{share:.3f} {knee:.3f} {ok}", flush=True)
        for r in rec["requests"]:  # free every slot for the next rate
            if r["req"] is not None and not r["req"].done:
                eng.cancel(r["req"].rid)
        eng.finished.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
