"""Shared pieces of the benchmark's CPU tests: paths, and a checkout
that holds the CPU-size test cells."""
import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

TINY_CELLS = [
    {"name": "tiny-gpt2-chat", "config": "tiny-gpt2", "traffic": "tiny-chat",
     "chips": 1, "why": "test cell"},
    {"name": "tiny-nemotron-chat", "config": "tiny-nemotron",
     "traffic": "tiny-chat", "chips": 1, "why": "test cell"},
    {"name": "tiny-llama-chat", "config": "tiny-llama",
     "traffic": "tiny-chat", "chips": 1, "why": "test cell"},
    {"name": "tiny-gpt2-chat-4chip", "config": "tiny-gpt2",
     "traffic": "tiny-chat", "chips": 4, "why": "test cell"},
]


def make_root(dest: pathlib.Path) -> pathlib.Path:
    """A checkout holding the benchmark's files and the CPU-size test
    cells (configurations and mix from ``tests/data``).  Two more
    families, ``llama`` (served in a cell) and ``olmoe`` (sparse
    experts; weights, reference and step cost only), come in as files of
    their own: a reference module that makes its weights and names its
    program keys, and a model-step cost."""
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name in ("tiny-gpt2", "tiny-nemotron", "tiny-llama", "tiny-olmoe"):
        shutil.copy(DATA / f"{name}.json", dest / "bench" / "configs")
    for kind in ("reference", "costs"):
        for f in (DATA / kind).glob("*.py"):
            shutil.copy(f, dest / "bench" / kind)
    shutil.copy(DATA / "tiny-chat.json", dest / "bench" / "traffic")
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["workloads"] = TINY_CELLS
    for m in spec["per_layer"] + spec["end_to_end"]:
        m.pop("workloads", None)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest
