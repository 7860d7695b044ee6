"""Per-layer metric msa_fused_roofline: roofline time of the attention
work the traced steps needed over the summed ``msa_fused`` device time
(``rec["trace"]["kernels"]["msa_fused"]``), in percent.  None when the
trace holds no ``msa_fused`` time."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    k = ((rec.get("trace") or {}).get("kernels") or {}).get("msa_fused")
    if not k or not k.get("device_s") or not k.get("roofline_s"):
        return None
    return 100.0 * k["roofline_s"] / k["device_s"]
