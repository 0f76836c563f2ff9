"""The benchmark's one command; see ``harness/cell.py``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>
"""
import time

T_START = time.monotonic()

import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from harness import cell  # noqa: E402

if __name__ == "__main__":
    sys.exit(cell.run(t_start=T_START))
