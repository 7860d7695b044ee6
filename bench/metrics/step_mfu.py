"""Per-layer metric step_mfu: model FLOPs of the traced steps over their
device busy time at the chip's peak, in percent.  None without a
reduced trace."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    t = rec.get("trace") or {}
    if not t.get("busy_s") or not t.get("model_flops"):
        return None
    return 100.0 * t["model_flops"] / (t["busy_s"] * t["peak_flops"])
