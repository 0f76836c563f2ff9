"""Paper-artifact benchmarks: one function per LoopLynx table/figure.

Each returns a list of CSV rows (name, value, paper_value, delta_pct) so
``benchmarks.run`` can emit a single machine-readable report.  The FPGA
analytic model (core/perfmodel.py) walks the same MDK stage program the
serving scheduler executes; Table II's 1-node latency calibrates the
bandwidth constants, everything else is *predicted* and compared against
the published numbers.
"""
from __future__ import annotations

from typing import List, Tuple

from repro.configs import get_config
from repro.core.perfmodel import (
    A100Model,
    FPGAPerfModel,
    PAPER_BASELINES,
    PAPER_TABLE2,
    PAPER_TABLE3,
    POWER_W,
)

Row = Tuple[str, float, float, float]


def _row(name: str, got: float, want: float) -> Row:
    delta = (got - want) / want * 100.0 if want else 0.0
    return (name, got, want, delta)


# ---------------------------------------------------------------------------
# Table II: per-token latency, LoopLynx 1/2/4 nodes vs DFX / spatial
# ---------------------------------------------------------------------------


def table2() -> List[Row]:
    cfg = get_config("gpt2-345m")
    rows = []
    for n in (1, 2, 4):
        t = FPGAPerfModel(cfg, nodes=n).token_latency()["total"]
        rows.append(_row(f"table2/latency_ms/{n}node", t * 1e3,
                         PAPER_TABLE2[n] * 1e3))
    # cross-architecture speedups at 4 nodes (paper: 2.11x DFX, 1.64x spatial)
    t4 = FPGAPerfModel(cfg, nodes=4).token_latency()["total"]
    rows.append(_row("table2/speedup_vs_dfx_4node",
                     PAPER_BASELINES["dfx_u280"] / t4, 2.11))
    rows.append(_row("table2/speedup_vs_spatial_4node",
                     PAPER_BASELINES["spatial_u280"] / t4, 1.64))
    t2 = FPGAPerfModel(cfg, nodes=2).token_latency()["total"]
    rows.append(_row("table2/speedup_vs_dfx_2node",
                     PAPER_BASELINES["dfx_u280"] / t2, 1.39))
    rows.append(_row("table2/speedup_vs_spatial_2node",
                     PAPER_BASELINES["spatial_u280"] / t2, 1.08))
    return rows


# ---------------------------------------------------------------------------
# Table III: throughput + scaling factors
# ---------------------------------------------------------------------------


def table3() -> List[Row]:
    cfg = get_config("gpt2-345m")
    rows = []
    tps = {}
    for n in (1, 2, 4):
        tps[n] = FPGAPerfModel(cfg, nodes=n).tokens_per_second()
        rows.append(_row(f"table3/tokens_per_s/{n}node", tps[n],
                         PAPER_TABLE3[n]))
    rows.append(_row("table3/speedup_2v1", tps[2] / tps[1], 1.71))
    rows.append(_row("table3/speedup_4v2", tps[4] / tps[2], 1.51))
    return rows


# ---------------------------------------------------------------------------
# Fig 5: latency breakdown + optimization ablations (context 256)
# ---------------------------------------------------------------------------


def fig5() -> List[Row]:
    cfg = get_config("gpt2-345m")
    S = 256
    unopt = FPGAPerfModel(cfg, nodes=1, fuse_ln_res=False,
                          headwise_pipeline=False).token_latency(S)
    fused = FPGAPerfModel(cfg, nodes=1, fuse_ln_res=True,
                          headwise_pipeline=False).token_latency(S)
    full = FPGAPerfModel(cfg, nodes=1).token_latency(S)
    total_u = unopt["total"]
    rows = [
        _row("fig5/linear_mha_share",
             (unopt["mp"] + unopt["mha"] + unopt["softmax_exposed"])
             / total_u, 0.815),
        _row("fig5/critical_path_share", unopt["critical_path"] / total_u,
             0.185),
        _row("fig5/ln_res_fusion_gain",
             (total_u - fused["total"]) / total_u, 0.11),
        _row("fig5/headwise_pipeline_gain",
             (fused["total"] - full["total"]) / total_u, 0.15),
    ]
    return rows


# ---------------------------------------------------------------------------
# Fig 8: [input:output] sweeps vs A100 — latency + energy efficiency
# ---------------------------------------------------------------------------

SETTINGS = [(32, 32), (64, 64), (128, 128), (32, 512), (64, 512),
            (128, 512), (128, 32)]


def fig8() -> List[Row]:
    cfg = get_config("gpt2-345m")
    a100 = A100Model()
    rows: List[Row] = []
    speed2, speed4 = [], []
    eff = {1: [], 2: [], 4: []}
    for n_in, n_out in SETTINGS:
        t_gpu = a100.request_latency(n_in, n_out)
        for n in (1, 2, 4):
            t = FPGAPerfModel(cfg, nodes=n).request_latency(n_in, n_out)
            if n == 2:
                speed2.append(t_gpu / t)
            if n == 4:
                speed4.append(t_gpu / t)
            e_fpga = n_out / (t * POWER_W[n])
            e_gpu = n_out / (t_gpu * POWER_W["a100"])
            eff[n].append(e_fpga / e_gpu)
        rows.append(_row(f"fig8/latency_s/a100/{n_in}:{n_out}", t_gpu, t_gpu))
    # the paper's headline averages
    rows.append(_row("fig8/avg_speedup_2node_vs_a100",
                     sum(speed2) / len(speed2), 1.67))
    rows.append(_row("fig8/avg_speedup_4node_vs_a100",
                     sum(speed4) / len(speed4), 2.52))
    for n, want in ((1, 2.3), (2, 2.7), (4, 2.1)):
        rows.append(_row(f"fig8/energy_eff_vs_a100_{n}node",
                         sum(eff[n]) / len(eff[n]), want))
    # A100 wins the prefill-heavy setting (paper observation for [128:32])
    t_gpu = a100.request_latency(128, 32)
    t_2n = FPGAPerfModel(cfg, nodes=2).request_latency(128, 32)
    rows.append(_row("fig8/a100_wins_128in_32out", float(t_gpu < t_2n), 1.0))
    return rows
