"""Device time in the collective ops between chips (all-gather,
all-reduce, reduce-scatter, collective-permute, all-to-all, and their
asynchronous start and done halves) over each chip's busy time in the
traced window, in %, averaged over the cell's chips (transfers layer)."""
from typing import Dict, Optional

from harness import layers


def read(rec: Dict) -> Optional[float]:
    return layers.collective_share(rec)
