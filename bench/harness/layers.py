"""Shared arithmetic of the per-layer metric readers (``bench/metrics``).

A reader gets the run's records: the drive loop's requests, ticks and
logged step calls (each with every argument of its engine span under
``args``), the configuration's ``family`` (a cost file of the family's
own is read before the general one), the memory peak, and, in a traced
run, the reduced trace (``harness/trace.py``), whose ``phases`` pair
each step-program execution in the window with the call that
dispatched it.  A reader that finds nothing to read returns ``None`` and
the metric is left out; a traced window whose calls of a kind found no
step program on the device stops the run instead, since the metric would
go silent for a fault of the harness.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

PEAK_KEY = {"w8a8": "int8_ops", "bf16": "bf16_flops"}


def phase(rec: Dict, kind: str) -> Optional[Dict]:
    tr = rec.get("trace")
    if not tr:
        return None
    ph = tr["phases"].get(kind)
    if ph and ph["modules"]:
        return ph
    w0, w1 = rec["window"]
    n = sum(1 for c in rec["calls"] if c["kind"] == kind and w0 < c["t"] <= w1)
    if n:
        raise RuntimeError(f"{n} {kind} calls in the traced window, and no "
                           "step program of theirs in the device trace")
    return None


def phase_calls(rec: Dict, kind: str) -> List[Dict]:
    ph = phase(rec, kind)
    return [rec["calls"][i] for i in ph["calls"]] if ph else []


def step_mfu(rec: Dict, kind: str) -> Optional[float]:
    """Model FLOPs of the phase's calls over the device time of their
    step programs times the configuration's matmul peak, in %.  On
    several chips a call is one SPMD program over every shard's rows:
    its FLOPs are all the rows', its time device 0's, and the peak is
    the chips' together."""
    ph = phase(rec, kind)
    if ph is None:
        return None
    cost = rec["cost"]("model_step", rec.get("family"))
    flops = sum(cost.flops(c, rec["dims"]) for c in phase_calls(rec, kind))
    secs = sum(m[2] for m in ph["modules"]) / 1e9
    peak = (rec["peak"][PEAK_KEY[rec["engine"]["precision"]]]
            * rec.get("chips", 1))
    return 100.0 * flops / (secs * peak) if secs > 0 else None


def on_device0(call: Dict) -> Optional[Dict]:
    """The part of a call that device 0 runs.  On several chips a decode
    call's ``lens`` are every shard's rows, and device 0 runs shard 0's
    (``shard_lens[0]``): ``None`` where shard 0 has no row in the call,
    which then needs no work there."""
    per = call.get("shard_lens")
    if per is None:
        return call
    return dict(call, lens=per[0]) if per[0] else None


def kernel_roofline(rec: Dict, kind: str, kernel: str) -> Optional[float]:
    """The least time the phase's calls need on the kernel (its cost
    file's bound) over the kernel's device time in the phase, in %.  The
    time is device 0's (``trace.reduce``), so the bound is that of the
    rows device 0 runs (``on_device0``)."""
    ph = phase(rec, kind)
    if ph is None:
        return None
    cost = rec["cost"](kernel, rec.get("family"))
    rx = re.compile(cost.MATCH)
    secs = sum(d for name, _, d in ph["ops"] if rx.search(name)) / 1e9
    if secs <= 0:
        return None
    calls = [c for c in map(on_device0, phase_calls(rec, kind)) if c]
    if not calls:
        return None
    bound = sum(cost.bound_s(c, rec["dims"], rec["engine"], rec["peak"])
                for c in calls)
    return 100.0 * bound / secs


def collective_share(rec: Dict) -> Optional[float]:
    """Device time in collective ops (``trace.COLLECTIVES``) over busy
    time, in %, each chip's over the window, averaged over the chips;
    ``None`` where no chip ran a collective."""
    tr = rec.get("trace")
    chips = tr and [c for c in tr["chips"] if c["busy_s"] > 0]
    if not chips or not any(c["collective_s"] for c in chips):
        return None
    return 100.0 * sum(c["collective_s"] / c["busy_s"]
                       for c in chips) / len(chips)


def idle_share(rec: Dict) -> Optional[float]:
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
