"""Per-layer metric host_loop_ms_per_step: host seconds of every phase of
the serve loop but ``serve.wait``, per step, over the window (the
program's tracer, ``rec["program"]``).  None without it."""
from typing import Dict, Optional


def read(rec: Dict) -> Optional[float]:
    p = rec.get("program") or {}
    return 1e3 * p["host_loop_s"] / p["steps"] if p.get("steps") else None
