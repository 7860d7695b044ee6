"""Reduction of a profiler trace to device busy time, per-op time, the
time of each named kernel and idle gaps attributed to host spans.

``extract`` reads one ``.xplane.pb`` through ``jax.profiler.ProfileData``
into plain intervals; ``reduce`` turns intervals into numbers.  The two
are apart so that the arithmetic is checked on hand-made intervals and
the reading on a small recorded trace.

Device operations are the events of each device plane's ``XLA Ops``
line, named by their HLO instruction without its numeric suffixes
(``%msa_fused.22 = ...`` is ``msa_fused``).  Busy time is the union of
their intervals inside the traced window, averaged over the devices; an
idle gap is a stretch of the window with no operation on a device, named
by the innermost host span open at its midpoint (``host idle`` when none
is).

The device planes run on a clock of their own, offset from the host's.
The harness drains the device before the window opens, so the first
operation in the window belongs to the first step dispatched in it: each
device's clock is shifted so that its first operation starts with the
window's first ``engine.dispatch`` span.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)

WINDOW_SPAN = "bench.window"
ALIGN_SPAN = "engine.dispatch"
OPS_LINE = "XLA Ops"
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.remat\d*)?(?:\s|=|$)")


def op_name(hlo: str) -> str:
    """``%copy.407.remat2 = bf16[...] copy(...)`` -> ``copy``."""
    m = _OP.match(hlo)
    return m.group(1) if m else hlo.split(" ", 1)[0]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def extract(path: str, span_names: Sequence[str]) -> Dict:
    """Device ops per device plane and host spans, in seconds on the
    trace's common clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    wanted = set(span_names) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((op_name(e.name), e.start_ns * 1e-9,
                                e.end_ns * 1e-9))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns * 1e-9,
                                      e.end_ns * 1e-9))
    return {"devices": devices, "spans": spans}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(spans: Sequence[Tuple[str, float, float]]
              ) -> Optional[Interval]:
    w = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    return max(w, key=lambda x: x[1] - x[0]) if w else None


def open_span(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """Innermost (latest-starting) host span open at ``t``."""
    best = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t < e:
            if best is None or s > best[1]:
                best = (name, s)
    return best[0] if best else "host idle"


def reduce(ex: Dict, kernels: Sequence[str], top: int = 10) -> Dict:
    """Busy and window seconds, per-op seconds, the device seconds of each
    of ``kernels`` (``kernels[name]["device_s"]``) and the longest idle
    gaps, all inside the traced window."""
    win = window_of(ex["spans"])
    if win is None or not ex["devices"]:
        return {}
    lo, hi = win
    first = [s for name, s, e in ex["spans"]
             if name == ALIGN_SPAN and lo <= s < hi]
    busy = []
    kern = {k: 0.0 for k in kernels}
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for ops in ex["devices"].values():
        if first:
            shift = min(s for _, s, _ in ops) - min(first)
            ops = [(n, s - shift, e - shift) for n, s, e in ops]
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        u = union([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in u))
        for n, s, e in inside:
            per_op[n] = per_op.get(n, 0.0) + (e - s)
            if n in kern:
                kern[n] += e - s
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((open_span(ex["spans"], 0.5 * (a + b)), b - a))
    n_dev = len(ex["devices"])
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / n_dev,
        "kernels": {k: {"device_s": v / n_dev} for k, v in kern.items()},
        "device_ops": [[n, s / n_dev] for n, s in ops_sorted[:top]],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps, key=lambda g: -g[1])[:top]],
    }
