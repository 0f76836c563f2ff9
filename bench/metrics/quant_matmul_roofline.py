"""The W8A8 matmul kernel inside the traced window's decode steps: the
least time its work needs (``costs/quant_matmul.py``) over the kernel's
device time, in %."""
from typing import Dict, Optional

from harness import layers


def read(rec: Dict) -> Optional[float]:
    return layers.kernel_roofline(rec, "decode", "quant_matmul")
