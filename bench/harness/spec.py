"""Find a cell's pieces by name: the files under ``bench/`` are the data.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, a traffic mix and its chips.  The chips pick the engine
(``harness/cell.build_engine``): one chip is served by ``ServeEngine``,
four by ``DistributedServeEngine`` with the configuration's slots on
each chip.  The configuration and the mix each live in a file of their
own:

* ``bench/configs/<config>.json``   model sizes, engine settings, check
* ``bench/traffic/<traffic>.json``  arrival process, lengths, lead-in
* ``bench/reference/<family>.py``   plain float32 forward of a family
* ``bench/metrics/<metric>.py``     one per-layer metric: ``read(rec)``
* ``bench/costs/<kernel>.py``       operations and bytes of one kernel
  (or of the model step), for every family
* ``bench/costs/<kernel>.<family>.py``  the same for one family; found
  before ``<kernel>.py`` where it exists

A reference module defines ``dims(conf)`` (the sizes the program and
the weight maker need) and ``logits(params, conf, tokens, want,
quant=None)``.  It may also define:

* ``make(dims, key, dtype)``: the family's weights, in the program's
  layout (``harness/params.py``; the default is the dense GPT-style
  stack);
* ``PROGRAM_KEYS``: the program-config fields checked against ``dims``
  (or, with ``"resize": true``, set from them; the default is
  ``harness/cell.PROGRAM_KEYS``);
* ``PROGRAM_CUT``: the fields always taken from ``dims`` (the default is
  ``("n_layers",)``).

Adding a cell, a configuration, a family, a mix or a metric therefore
adds files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A name that no file or entry answers to."""


def _load_json(path: pathlib.Path) -> Dict:
    if not path.is_file():
        raise SpecError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path; names may hold dots (``step_mfu.decode``)."""
    if not path.is_file():
        raise SpecError(f"no such file: {path}")
    name = "bench_" + path.parent.name + "_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the directory beside it."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.dir = self.root / "bench"
        self.spec = _load_json(self.root / "BENCHMARK.json")

    def workload(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        conf = _load_json(self.dir / "configs" / f"{name}.json")
        conf.setdefault("name", name)
        return conf

    def traffic(self, name: str) -> Dict:
        mix = _load_json(self.dir / "traffic" / f"{name}.json")
        mix.setdefault("name", name)
        return mix

    def reference(self, family: str) -> ModuleType:
        return load_module(self.dir / "reference" / f"{family}.py")

    def cost(self, kernel: str, family: Optional[str] = None) -> ModuleType:
        """``costs/<kernel>.<family>.py`` where the family has one, else
        ``costs/<kernel>.py``."""
        own = self.dir / "costs" / f"{kernel}.{family}.py"
        if family and own.is_file():
            return load_module(own)
        return load_module(self.dir / "costs" / f"{kernel}.py")

    def peaks(self) -> Dict:
        return _load_json(self.dir / "peaks.json")

    def end_to_end(self, cell: str) -> List[Dict]:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict]:
        """The cell's per-layer metrics, each with its reader loaded."""
        out = []
        for m in self.spec["per_layer"]:
            if cell not in m.get("workloads", [cell]):
                continue
            reader = load_module(self.dir / "metrics" / f"{m['name']}.py")
            out.append(dict(m, reader=reader))
        return out
