"""From a profiler trace to device busy time, op time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the reduction needs, as plain lists:

* for each TPU device plane, its ``XLA Ops`` events (one per executed
  HLO op: fusions, copies, Pallas custom calls) and its ``XLA Modules``
  events (one per program execution);
* the host spans the benchmark and the engines annotate
  (``jax.profiler.TraceAnnotation``): ``bench.*``, and the step spans of
  either engine (``prefill.chunk`` and ``decode.step``; ``prefill.round``,
  ``wave.dispatch`` and ``wave.consume``).

An event is ``[name, start_ns, duration_ns]`` on the trace's one clock.
``reduce`` then works on that form alone, so it is checked on a small
recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

HOST_SPANS = ("bench.window", "bench.tick", "bench.wait", "bench.submit",
              "bench.idle", "prefill.chunk", "decode.step", "prefill.round",
              "wave.dispatch", "wave.consume")
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: ops that only hold other ops (a scanned layer loop): their time is
#: their children's, so the op breakdown leaves them out
CONTAINERS = re.compile(r"^%(while|conditional|call)\b")
#: collective ops between chips, and the start and done halves of their
#: asynchronous forms
COLLECTIVES = re.compile(r"^%(all-gather|all-reduce|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start|-done)?"
                         r"(\.|$)")

Event = Tuple[str, float, float]


def op_name(text: str) -> str:
    """An op event is named by its HLO text; keep the instruction's own
    name (``%paged_verify.10``), which a Pallas kernel takes from its
    kernel function."""
    return text.split(" = ", 1)[0]


def find_xplane(logdir: str) -> str:
    hits = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return sorted(hits)[-1]


def load(logdir: str) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(logdir))
    out: Dict = {"devices": [], "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.search(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[op_name(e.name), float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out["host"].append([e.name, float(e.start_ns),
                                            float(e.duration_ns)])
    out["devices"].sort(key=lambda d: d["id"])
    return out


def describe(logdir: str, per_line: int = 30) -> Dict:
    """Every plane and line with counts and a few events with their
    stats: the look at a trace's shape before code relies on it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(logdir))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            sample = []
            for e in evs[:per_line]:
                try:
                    st = {str(k): str(v)[:120] for k, v in e.stats}
                except Exception as exc:  # stats are best effort here
                    st = {"error": repr(exc)}
                sample.append([e.name, e.start_ns, e.duration_ns, st])
            lines.append({"name": line.name, "n": len(evs),
                          "sample": sample})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to ``[lo, hi]``; those outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals covered by the events."""
    spans = sorted((s, s + d) for _, s, d in events)
    out: List[List[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union(events))


def idle_gaps(events: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """``(start, length)`` of each stretch in ``[lo, hi]`` with no op."""
    gaps, t = [], lo
    for a, b in union(events):
        if a > t:
            gaps.append((t, a - t))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi - t))
    return gaps


def label(host: Sequence[Event], t: float) -> str:
    """The innermost annotated host span around ``t``, or ``none``."""
    best: Optional[Event] = None
    for ev in host:
        if ev[1] <= t <= ev[1] + ev[2] and ev[0] != "bench.window":
            if best is None or ev[2] < best[2]:
                best = ev
    return best[0] if best else "none"


def by_name(events: Sequence[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, d in events:
        out[name] = out.get(name, 0.0) + d
    return out


def within(events: Sequence[Event], spans: Sequence[Event]
           ) -> List[Event]:
    """Events that start inside one of ``spans``."""
    ivs = sorted((s, s + d) for _, s, d in spans)
    starts = [a for a, _ in ivs]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < ivs[i][1]:
            out.append(ev)
    return out


def program(module: str) -> str:
    """A module's program name, without its fingerprint
    (``jit_decode_step(688113719484162613)`` is ``jit_decode_step``)."""
    return module.split("(", 1)[0]


def attribute_modules(modules: Sequence[Event],
                      calls: Sequence[Tuple[str, float]],
                      slack_ns: float = 1e6,
                      programs: Optional[Dict[str, str]] = None
                      ) -> Dict[str, Dict[str, list]]:
    """Step-program executions by the kind of call that dispatched them.

    ``calls`` are ``(kind, dispatch_ns)`` in dispatch order.
    ``programs`` names each kind's step program as the program names its
    jitted steps (``jit_decode_step``); where any module in the window
    carries one of those names, each kind's step program is the module
    of its name with the most device time, and a kind whose program did
    not run has none.  Where none does (an older or renamed program),
    the step program is found by time instead: each program is given
    the kind of call that most often precedes its executions, and of a
    kind's programs the step program is the one with the most device
    time (the others are the sampler and small helpers that run after
    the step).  A kind's step program runs in the order its calls were
    dispatched: the first execution is paired with the latest call of
    that kind dispatched before it started (within ``slack_ns`` of clock
    error), and the rest follow in order.  Returns, per kind, the
    executions and their calls' indices."""
    mods = sorted(modules, key=lambda e: e[1])
    spent = by_name(mods)
    step_of: Dict[str, str] = {}
    named = programs and {program(m) for m in spent} & set(programs.values())
    if named:
        for kind, name in programs.items():
            own = [m for m in spent if program(m) == name]
            if own:
                step_of[kind] = max(own, key=lambda m: spent[m])
    else:
        times = [t for _, t in calls]
        votes: Dict[str, Counter] = defaultdict(Counter)
        for ev in mods:
            i = bisect.bisect_right(times, ev[1]) - 1
            if i >= 0:
                votes[ev[0]][calls[i][0]] += 1
        kind_of = {name: c.most_common(1)[0][0]
                   for name, c in votes.items()}
        for name, kind in kind_of.items():
            if kind not in step_of or spent[name] > spent[step_of[kind]]:
                step_of[kind] = name
    out: Dict[str, Dict[str, list]] = {}
    for kind, name in sorted(step_of.items()):
        evs = [e for e in mods if e[0] == name]
        idx = [i for i, (k, _) in enumerate(calls) if k == kind]
        ktimes = [calls[i][1] for i in idx]
        j0 = bisect.bisect_right(ktimes, evs[0][1] + slack_ns) - 1
        if j0 < 0:
            continue
        pairs = list(zip(evs, idx[j0:]))
        out[kind] = {"modules": [e for e, _ in pairs],
                     "calls": [i for _, i in pairs]}
    return out


#: the dispatch spans of each kind of call, in either engine
SPAN_OF = {"prefill": ("prefill.chunk", "prefill.round"),
           "decode": ("decode.step", "wave.dispatch")}


def clock_offset(host: Sequence[Event], calls: Sequence[Dict],
                 t_open: float) -> float:
    """Trace clock minus the harness clock, in ns.

    The window's marker span gives a first estimate; each logged step
    call is then paired with the engine's dispatch span of its kind
    nearest to it, and the median of those pairs' differences is the
    offset (the call is logged as its dispatch span opens)."""
    marks = [h for h in host if h[0] == "bench.window"]
    coarse = marks[0][1] - t_open * 1e9
    diffs = []
    for kind, spans in SPAN_OF.items():
        starts = sorted(h[1] for h in host if h[0] in spans)
        if not starts:
            continue
        for c in calls:
            if c["kind"] != kind:
                continue
            t = c["t"] * 1e9 + coarse
            i = bisect.bisect_left(starts, t)
            near = [starts[j] for j in (i - 1, i) if 0 <= j < len(starts)]
            best = min(near, key=lambda x: abs(x - t))
            if abs(best - t) < 5e6:
                diffs.append(best - c["t"] * 1e9)
    return statistics.median(diffs) if diffs else coarse


def reduce(tr: Dict, lo: float, hi: float, chips: int,
           calls: Sequence[Tuple[str, float]] = (),
           programs: Optional[Dict[str, str]] = None) -> Dict:
    """The window ``[lo, hi]`` (ns) of a loaded trace.

    Returns busy and window seconds (busy averaged over the cell's
    chips), each chip's busy and collective seconds, op time by name and
    the longest idle gaps with the host span each fell in (device 0),
    and step-program executions with their ops by the kind of call that
    dispatched them (``attribute_modules``, with the step programs'
    names ``programs``)."""
    devs = tr["devices"][:chips]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    host = tr["host"]
    ops = [clip(d["ops"], lo, hi) for d in devs]
    ops0 = ops[0]
    busy = [busy_ns(o) for o in ops]
    coll = [busy_ns([e for e in o if COLLECTIVES.match(e[0])]) for o in ops]
    gaps = sorted(idle_gaps(ops0, lo, hi), key=lambda g: -g[1])
    inside = [m for m in devs[0]["modules"]
              if m[1] >= lo and m[1] + m[2] <= hi]
    mods = attribute_modules(inside, calls, programs=programs)
    phases = {kind: dict(m, ops=within(ops0, m["modules"]))
              for kind, m in mods.items()}
    totals = by_name([o for o in ops0 if not CONTAINERS.match(o[0])])
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "chips": [{"busy_s": b / 1e9, "collective_s": c / 1e9}
                  for b, c in zip(busy, coll)],
        "ops": ops0,
        "phases": phases,
        "breakdown": {
            "device_ops": [[n, d / 1e9] for n, d in top],
            "idle_gaps": [[label(host, s + g / 2), g / 1e9]
                          for s, g in gaps[:10]],
        },
    }
