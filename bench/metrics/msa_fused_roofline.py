"""Per-layer metric msa_fused_roofline: roofline time of the attention
work the traced steps needed over the summed ``msa_fused`` device time,
in percent.  None when the trace holds no ``msa_fused`` time."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    t = rec.get("trace") or {}
    if not t.get("kernel_s") or not t.get("roofline_s"):
        return None
    return 100.0 * t["roofline_s"] / t["kernel_s"]
