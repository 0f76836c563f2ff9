"""Most KV pages in use during the window over the pool's usable pages
(the paged cache manager's ``pages_in_use_peak``, reset at window
start)."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    if rec.get("pages_in_use_peak") is None or not rec["pages_total"]:
        return None
    return rec["pages_in_use_peak"] / rec["pages_total"]
