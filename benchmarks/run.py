"""Benchmark harness entry point — one section per paper table/figure plus
the kernel microbenchmarks and the dry-run roofline summary.

Prints ``name,us_per_call,derived`` CSV:
  * measured rows:   us_per_call = wall-clock microseconds (CPU)
  * analytic rows:   us_per_call = model-predicted value,
                     derived = ``paper=<published>;delta=<pct>%``
  * roofline rows:   derived from artifacts/dryrun (skipped with a notice
                     if the dry-run has not produced them yet)
"""
from __future__ import annotations

import sys


def main() -> None:
    sys.path.insert(0, "src")
    from benchmarks import kernelbench, paper_tables, roofline
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    for name, val, want, delta in (
        paper_tables.table2() + paper_tables.table3()
        + paper_tables.fig5() + paper_tables.fig8()
    ):
        print(f"{name},{val:.6g},paper={want:.6g};delta={delta:+.1f}%")

    for name, us, note in kernelbench.rows():
        print(f"{name},{us:.1f},{note}")

    roof = roofline.rows()
    if not roof:
        print("roofline/NOTE,0,run `python -m repro.launch.dryrun` first")
    for name, val, note in roof:
        print(f"{name},{val},{note}")
    # post-§Perf optimized sweep, when present
    for name, val, note in roofline.rows("pod16x16_opt"):
        print(f"{name.replace('roofline/', 'roofline_opt/')},{val},{note}")


if __name__ == "__main__":
    main()
