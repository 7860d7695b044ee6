"""Per-layer metric mlp_ms_per_step: device seconds of the ops in the
jitted step's ``mlp`` scope (the MLP of every layer: for an expert
layer its router, held experts and shared experts), per traced step
(``rec["timeline"]``).  None without a linked trace."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    t = rec.get("timeline") or {}
    if not t.get("linked_steps") or "scope_s" not in t:
        return None
    return 1e3 * t["scope_s"].get("mlp", 0.0) / t["linked_steps"]
