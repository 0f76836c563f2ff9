"""Readings that set a cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seeds 11 12 13 ... \
        [--seconds S]

For each seed, one run of the cell as ``run.py`` makes it (weights, the
engine, warm-up, the lead-in and a window of ``--seconds``), then the
comparison of ``harness/check.py`` twice over the same sample of
served requests: the program's served tokens against the float32
reference (the lower reading), and the control, the reference computed
in the configuration's ``check.control`` precision, put in the
program's place (the upper reading).  Each side is judged by the
predicate that decides a run's ``correct`` (``check.correct``).  One
JSON line per seed, then the largest program reading, the smallest
control reading, whether every program side was correct and whether any
control side was.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from harness import cell, check, device, drive, spec, traffic  # noqa


def readings(bench: spec.Bench, name: str, seed: int, seconds: float,
             fault=None) -> dict:
    """One seed's program and control readings."""
    from repro.serving.telemetry import Telemetry

    w = bench.workload(name)
    conf = bench.config(w["config"])
    mix = bench.traffic(w["traffic"])
    m = cell.model(bench, conf)
    eng = cell.build_engine(conf, m.cfg, m.weights(seed), seed, Telemetry(),
                            w["chips"])
    if fault is not None:
        fault(eng)
    cell.warm_up(eng, w["chips"])
    reqs = traffic.generate(mix, seed, seconds, m.cfg.vocab_size)
    rec = drive.drive(eng, reqs, float(mix["lead_in_s"]), seconds,
                      chips=w["chips"])
    served = check.served(rec["requests"])
    shards = check.shards(rec["requests"])
    failed = rec["failed"]
    del eng, rec
    gc.collect()
    chk = conf["check"]
    chosen = check.sample(served, seed, chk["requests"], shards)
    weights = m.weights(seed)
    out = check.compare(m.ref, weights, conf, chosen, control=chk["control"])
    del weights
    gc.collect()
    return dict(out, seed=seed,
                correct=check.correct(out, chk, failed),
                control_correct=check.correct(check.control_side(out), chk,
                                              failed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float,
                    default=spec.Bench().spec["run_seconds"])
    args = ap.parse_args(argv)
    bench = spec.Bench()
    try:
        device.check(bench.workload(args.workload)["chips"], bench.peaks())
    except device.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    got = []
    for seed in args.seeds:
        t0 = time.monotonic()
        r = readings(bench, args.workload, seed, args.seconds)
        r["seconds"] = time.monotonic() - t0
        got.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps({
        "program_max": max(r["max_logit_gap"] for r in got),
        "control_min": min(r["control_max_logit_gap"] for r in got),
        "program_correct": all(r["correct"] for r in got),
        "control_correct": any(r["control_correct"] for r in got)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
