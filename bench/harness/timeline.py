"""Reduction of a traced run's profiler trace against the program's own
spans: the host and device clocks linked by run id, the device idle the
host caused, and device seconds by program scope.

``extract`` reads one ``.xplane.pb`` through ``jax.profiler.ProfileData``
into plain tuples; ``reduce`` turns them into numbers, so the arithmetic
is checked on hand-made events and the reading on a recorded trace.

Clock link.  Each ``serve.launch`` span of the serve loop leads to one
host ``DoEnqueueProgram`` event, whose ``run_id`` names the device's
``XLA Modules`` event that ran the step.  The enqueue lies inside the
span when the runtime enqueues at once; when the step's inputs are still
being computed by the step before it, the runtime may enqueue later, on
a thread of its own, before the next launch.  So a launch takes the one
enqueue inside its span, else the one enqueue between its start and the
next launch's start; any other count leaves it unlinked.  A device's
clock shift is the smallest value, over the steps, of module start
(device clock) minus enqueue end (host clock); how much that value
spreads across steps says how far to trust the link.

Per step.  The module's interval on the host clock, the device idle
before it (from the end of the previous module of the window), and the
part of that idle before the step's enqueue ended, whether inside its
launch span or deferred past it: idle the host caused, split by the
program phase (``serve.*`` span) open at the time.  The first module of
the window has no earlier module and is left out.

Scopes.  Each ``XLA Ops`` event inside a linked module is named by its
HLO instruction; ``scope_map`` maps instruction names to the innermost
program scope (``jax.named_scope``) in the compiled variant's HLO
metadata.  An instruction whose metadata names no scope (a fusion whose
root lies outside every scope, a per-layer weight slice, a copy the
compiler put in) takes the scope of the instructions it calls, else of
the first scoped instruction its result flows into; what is left counts
as ``unscoped``.  ``own_scope_map`` keeps the metadata's reading alone.
"""
from __future__ import annotations

import math
import re
from collections import Counter
from typing import Callable, Dict, List, Optional, Sequence, Tuple

SCOPES = ("page_ops", "embed", "qkv", "kv_write", "attention", "mlp",
          "logits")
UNSCOPED = "unscoped"
# the serve loop's phase spans (they tile its host time)
PHASE_SPANS = tuple(f"serve.{p}" for p in (
    "poll", "schedule", "assemble", "launch", "postprocess", "wait",
    "retire"))
LAUNCH_SPAN = "serve.launch"
ENQUEUE = "DoEnqueueProgram"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_META = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")


def instr_name(event_name: str) -> str:
    """``%copy-start.1 = (...) copy-start(...)`` -> ``copy-start.1``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0].lstrip("%")


def scope_of(op_name: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """Innermost of ``scopes`` on an HLO ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return None


def _parse(hlo_text: str, scopes: Sequence[str]):
    """Each instruction's own scope, the computation it calls, its
    users, and the scopes counted in each computation."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    by_comp: Dict[str, Counter] = {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMP.match(line)
        if c:
            comp = c.group(1)
            by_comp.setdefault(comp, Counter())
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        meta = _META.search(line)
        sc = scope_of(meta.group(1), scopes) if meta else None
        own[name] = sc
        if sc is not None and comp is not None:
            by_comp[comp][sc] += 1
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        body = line[m.end():].split(", metadata=", 1)[0]
        for op in _OPERAND.findall(body):
            users.setdefault(op, []).append(name)
    return own, calls, users, by_comp


def own_scope_map(hlo_text: str, scopes: Sequence[str] = SCOPES
                  ) -> Dict[str, str]:
    """HLO instruction name -> innermost program scope in its own
    metadata, else ``unscoped``."""
    own = _parse(hlo_text, scopes)[0]
    return {n: sc or UNSCOPED for n, sc in own.items()}


def scope_map(hlo_text: str, scopes: Sequence[str] = SCOPES
              ) -> Dict[str, str]:
    """HLO instruction name -> program scope: its own metadata's, else
    the most common among the instructions of the computation it calls,
    else that of the first scoped instruction its result flows into."""
    own, calls, users, by_comp = _parse(hlo_text, scopes)
    direct = {}
    for name, sc in own.items():
        if sc is None and by_comp.get(calls.get(name)):
            sc = by_comp[calls[name]].most_common(1)[0][0]
        direct[name] = sc
    out = {}
    for name, sc in direct.items():
        seen, queue = {name}, list(users.get(name, ()))
        while sc is None and queue:
            u = queue.pop(0)
            if u in seen:
                continue
            seen.add(u)
            sc = direct.get(u)
            queue.extend(users.get(u, ()))
        out[name] = sc or UNSCOPED
    return out


def extract(path: str, launch_span: str = LAUNCH_SPAN) -> Dict:
    """Program spans, launch spans, host enqueues, and each device's
    modules and ops, in seconds on the trace's clocks (host events on
    the host's, device events on the device's own)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    phases, launches, enqueues = [], [], []
    devices: Dict[str, Dict[str, List]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        st = dict(e.stats)
                        if "run_id" in st:
                            mods.append((int(st["run_id"]),
                                         e.start_ns * 1e-9, e.end_ns * 1e-9))
                elif line.name == OPS_LINE:
                    ops.extend((instr_name(e.name), e.start_ns * 1e-9,
                                e.end_ns * 1e-9) for e in line.events)
            if mods:
                devices[plane.name] = {"modules": mods, "ops": ops}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name == ENQUEUE:
                        st = dict(e.stats)
                        if "run_id" in st:
                            enqueues.append((int(st["run_id"]),
                                             e.start_ns * 1e-9,
                                             e.end_ns * 1e-9))
                    elif name == launch_span or name in PHASE_SPANS:
                        step = dict(e.stats).get("step")
                        span = (name, None if step is None else int(step),
                                e.start_ns * 1e-9, e.end_ns * 1e-9)
                        if name == launch_span:
                            launches.append(span)
                        if name in PHASE_SPANS:
                            phases.append(span)
    launches.sort(key=lambda s: s[2])
    return {"phases": phases, "launches": launches, "enqueues": enqueues,
            "devices": devices}


def link(ex: Dict) -> Dict:
    """Each launch span's enqueue and, on every device, its module; and
    each device's clock shift (device minus host) with its spread."""
    steps, unmatched = [], 0
    enq = sorted(ex["enqueues"], key=lambda e: e[1])
    launches = ex["launches"]
    for i, (_, step, s, e) in enumerate(launches):
        nxt = launches[i + 1][2] if i + 1 < len(launches) else math.inf
        after = [q for q in enq if s <= q[1] < nxt]
        inside = [q for q in after if q[2] <= e]
        pick = inside or after
        if len(pick) != 1:
            unmatched += 1
            continue
        run_id, _, q_end = pick[0]
        steps.append({"step": step if step is not None else i + 1,
                      "run_id": run_id, "enqueue_end": q_end,
                      "deferred": not inside,
                      "launch": (s, e)})
    shifts = {}
    for dev, d in ex["devices"].items():
        mods = {r: (s, e) for r, s, e in d["modules"]}
        diffs = [mods[st["run_id"]][0] - st["enqueue_end"]
                 for st in steps if st["run_id"] in mods]
        if diffs:
            shifts[dev] = {"shift_s": min(diffs),
                           "spread_s": max(diffs) - min(diffs),
                           "linked": len(diffs)}
    return {"steps": steps, "unmatched_launches": unmatched,
            "deferred_enqueues": sum(1 for st in steps if st["deferred"]),
            "shifts": shifts}


def _phase_overlap(phases, lo: float, hi: float) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, s, e in phases:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[name] = out.get(name, 0.0) + d
    return out


def reduce(ex: Dict,
           scope_for: Optional[Callable[[int], Dict[str, str]]] = None
           ) -> Dict:
    """Clock link, per-step idle and device seconds by scope.
    ``scope_for(step)`` gives the scope map of the variant that step
    ran (None: no scope split)."""
    ln = link(ex)
    if not ln["shifts"]:
        return {}
    phases = ex["phases"]
    per_step: List[Dict] = []
    scope_s: Dict[str, float] = {}
    n_dev = len(ln["shifts"])
    for dev, sh in ln["shifts"].items():
        d = ex["devices"][dev]
        mods = {r: (s, e) for r, s, e in d["modules"]}
        ops = sorted(d["ops"], key=lambda o: o[1])
        prev_end = None
        for st in sorted(ln["steps"], key=lambda x: x["enqueue_end"]):
            if st["run_id"] not in mods:
                continue
            ms, me = mods[st["run_id"]]
            hs, he = ms - sh["shift_s"], me - sh["shift_s"]
            row = {"device": dev, "step": st["step"],
                   "module_host": [hs, he]}
            if prev_end is not None:
                idle = max(0.0, hs - prev_end)
                stall_end = min(hs, st["enqueue_end"])
                row["idle_s"] = idle
                row["host_stall_s"] = max(0.0, stall_end - prev_end)
                row["stall_phases"] = _phase_overlap(phases, prev_end,
                                                     stall_end)
            prev_end = he if prev_end is None else max(prev_end, he)
            per_step.append(row)
            if scope_for is None:
                continue
            smap = scope_for(st["step"])
            for name, s, e in ops:
                if s >= ms and e <= me:
                    sc = smap.get(name, UNSCOPED)
                    scope_s[sc] = scope_s.get(sc, 0.0) + (e - s) / n_dev
    stalls = [r for r in per_step if "host_stall_s" in r]
    stall_by_phase: Dict[str, float] = {}
    for r in stalls:
        for k, v in r["stall_phases"].items():
            stall_by_phase[k] = stall_by_phase.get(k, 0.0) + v
    traced = len({r["step"] for r in per_step})
    out = {
        "linked_steps": traced,
        "unmatched_launches": ln["unmatched_launches"],
        "deferred_enqueues": ln["deferred_enqueues"],
        "shifts": ln["shifts"],
        "steps": per_step,
        "host_stall_s": sum(r["host_stall_s"] for r in stalls) / n_dev,
        "stall_steps": len(stalls) // n_dev,
        "stall_by_phase": stall_by_phase,
    }
    if scope_for is not None:
        out["scope_s"] = dict(sorted(scope_s.items(), key=lambda kv: -kv[1]))
    return out


def _hlo_text(engine, t_b: int, np_b: int, w_b: int) -> str:
    """The compiled step variant's HLO text, compiled afresh when the copy
    the persistent cache gave back carries no scope metadata."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    text = engine.compiled_step(t_b, np_b, w_b).as_text()
    if any(f"/{s}/" in text for s in SCOPES):
        return text
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return engine.compiled_step(t_b, np_b, w_b).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def timeline_record(path: str, server) -> Optional[Dict]:
    """``reduce`` of the trace at ``path``, each linked step's ops mapped
    through the scope map of the step variant it ran (from the server's
    tracer), with the share of device seconds no scope claims.  None
    when the trace links no step."""
    variants = {r.step: r.variant for r in server.tracer.steps()}
    maps: Dict = {}

    def scope_for(step):
        v = variants.get(step)
        if v is None:
            return {}
        if v not in maps:
            maps[v] = scope_map(_hlo_text(server.engine, *v[:3]))
        return maps[v]

    red = reduce(extract(path), scope_for)
    if not red:
        return None
    busy = sum(red["scope_s"].values())
    red["unscoped_share"] = (red["scope_s"].get(UNSCOPED, 0.0) / busy
                             if busy else None)
    red["variants_mapped"] = len(maps)
    return red
