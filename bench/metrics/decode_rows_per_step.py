"""Per-layer metric decode_rows_per_step: decode rows the scheduler put
in a step, averaged over the window's steps.  None when no step ran."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    steps = rec["steps"]
    return rec["decode_rows"] / steps if steps else None
