"""Per-layer metric device_idle_share: 1 - union of device-op intervals
over the traced window.  None without a reduced trace."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    t = rec.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
