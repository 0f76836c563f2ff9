"""The paged attention kernel inside the traced window's decode steps:
the least time the live K/V work needs (``costs/paged_attn.py``) over
the kernel's device time, in %."""
from typing import Dict, Optional

from harness import layers


def read(rec: Dict) -> Optional[float]:
    return layers.kernel_roofline(rec, "decode", "paged_attn")
