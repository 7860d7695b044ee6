"""Per-layer metric host_stall_ms_per_step: device idle before a step's
module while its enqueue had not ended, per traced step that has an
earlier module in the window (``rec["timeline"]``).  None without one."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    t = rec.get("timeline") or {}
    if not t.get("stall_steps"):
        return None
    return 1e3 * t["host_stall_s"] / t["stall_steps"]
