"""CPU tests of the benchmark harness.  The harness imports as
``harness`` from ``bench/``; the program from ``src/``."""
import pathlib
import sys

import pytest

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent)]

from benchtools import make_root  # noqa: E402


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))
