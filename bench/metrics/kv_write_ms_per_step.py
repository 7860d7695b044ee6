"""Per-layer metric kv_write_ms_per_step: device seconds of the ops in the
jitted step's ``kv_write`` scope, per traced step (``rec["timeline"]``).
None without a linked trace."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    t = rec.get("timeline") or {}
    if not t.get("linked_steps") or "scope_s" not in t:
        return None
    return 1e3 * t["scope_s"].get("kv_write", 0.0) / t["linked_steps"]
