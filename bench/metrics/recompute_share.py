"""Per-layer metric recompute_share: prompt positions computed again
after eviction over prompt positions, for the turns admitted in the
window.  None when no turn was admitted."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    n = rec["prompt_positions"]
    return rec["recomputed"] / n if n else None
