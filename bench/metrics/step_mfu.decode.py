"""Model FLOPs of the decode steps that ran in the traced window
(``costs/model_step.py``) over the device time of their step programs
times the peak of the configuration's matmul precision, in %."""
from typing import Dict, Optional

from harness import layers


def read(rec: Dict) -> Optional[float]:
    return layers.step_mfu(rec, "decode")
