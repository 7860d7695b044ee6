"""Per-layer metric host_ms_per_step: host seconds the server spent
scheduling and assembling steps, per step, over the window.  None when
no step ran."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    steps = rec["steps"]
    return 1e3 * rec["host_s"] / steps if steps else None
