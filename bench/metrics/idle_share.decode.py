"""Share of the traced window in which no op ran on the device (1 minus
the union of the device's op intervals over the window), in %; in the
decode-heavy cells."""
from typing import Dict, Optional

from harness import layers


def read(rec: Dict) -> Optional[float]:
    return layers.idle_share(rec)
