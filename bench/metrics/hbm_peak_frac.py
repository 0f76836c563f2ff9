"""Peak device memory in use over the device's limit, on the fullest
chip (``memory_stats()``: ``peak_bytes_in_use`` / ``bytes_limit``)."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    mem = rec["memory"]
    return mem["peak"] / mem["limit"] if mem["limit"] else None
