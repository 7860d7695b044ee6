#!/usr/bin/env python3
"""Run one cell as ``bench/run.py`` does and add the program's own records.

    python3 bench/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--no-check] [--out <file.jsonl>]

The run is ``bench/run.py``'s, unchanged, and prints what it prints.  Its
record gains two keys:

* ``program`` (every run): from the server's tracer
  (``repro.serving.tracing``), the window's steps, the step variants
  compiled in it, seconds per serve-loop phase, and the longest
  dispatch-to-dispatch interval with the phases that filled it;
* ``timeline`` (``--trace 1``): ``bench/harness/timeline.py``'s reduction
  of the same trace, with the scope map of each step variant the window
  ran (``Engine.compiled_step(...).as_text()``).

The per-layer metrics that read them (``bench/metrics/
host_loop_ms_per_step.py``, ``kv_write_ms_per_step.py``,
``host_stall_ms_per_step.py``) are printed on a line ``program
metrics: {...}``.  ``--no-check`` skips the comparison with the
reference (no ``correct``): the record and the metrics are printed, not
the result line.  ``--out`` appends the record to a file as one JSON
line.  With a program whose server has no tracer the two keys are left
out.
"""
from __future__ import annotations

import json
import sys
import traceback
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

PROGRAM_METRICS = ("host_loop_ms_per_step", "kv_write_ms_per_step",
                   "host_stall_ms_per_step")


def program_record(tracer, t0: float, t_end: float, profiler_steps=()):
    """The window's steps from the tracer's ring (dispatched at server
    clock ``t0 <= now < t_end``), less ``profiler_steps``: the steps whose
    poll phase started or stopped the profiler (it drains the device and
    writes the trace there)."""
    from repro.serving.tracing import PHASES, POSTPROCESS
    ring = tracer.steps()
    recs = [r for r in ring if t0 <= r.now < t_end
            and r.step not in profiler_steps]
    if not recs:
        return None
    phase_s = {p: sum(r.phases[i] for r in recs)
               for i, p in enumerate(PHASES)}
    # an interval runs from the previous launch's end to this one's: the
    # previous step's postprocess, wait and retire, then this step's poll,
    # schedule, assemble and launch
    prev = {r.step: r for r in ring}
    longest = max(recs, key=lambda r: r.interval_s)
    before = prev.get(longest.step - 1)
    filled = {p: (before.phases[i] if before is not None else 0.0)
              if i >= POSTPROCESS else longest.phases[i]
              for i, p in enumerate(PHASES)}
    return {
        "steps": len(recs),
        "compiles": sum(1 for r in recs if r.compiled),
        "phase_s": phase_s,
        "worklist_s": sum(r.worklist_s for r in recs),
        "host_loop_s": sum(v for p, v in phase_s.items() if p != "wait"),
        "interval_s": sum(r.interval_s for r in recs),
        "longest_step": dict(longest.as_dict(), interval_phases=filled),
        "intervals_over_1_5_s": sum(1 for r in recs if r.interval_s > 1.5),
        "profiler_steps": sorted(profiler_steps),
    }


def _hlo_text(engine, t_b: int, np_b: int, w_b: int) -> str:
    """The compiled variant's HLO text, compiled afresh when the copy the
    persistent cache gave back carries no scope metadata."""
    import jax
    from bench.harness import timeline as tl
    from jax.experimental.compilation_cache import compilation_cache as cc
    text = engine.compiled_step(t_b, np_b, w_b).as_text()
    if any(f"/{s}/" in text for s in tl.SCOPES):
        return text
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return engine.compiled_step(t_b, np_b, w_b).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def timeline_record(path: str, server):
    """``timeline.reduce`` of the trace, each linked step's ops mapped
    through the scope map of the variant it ran."""
    from bench.harness import timeline as tl
    tracer = getattr(server, "tracer", None)
    if tracer is None:
        return None
    variants = {r.step: r.variant for r in tracer.steps()}
    maps = {}

    def scope_for(step):
        v = variants.get(step)
        if v is None:
            return {}
        if v not in maps:
            maps[v] = tl.scope_map(_hlo_text(server.engine, *v[:3]))
        return maps[v]

    red = tl.reduce(tl.extract(path), scope_for)
    if red:
        busy = sum(red["scope_s"].values())
        red["unscoped_share"] = (red["scope_s"].get(tl.UNSCOPED, 0.0) / busy
                                 if busy else None)
    return red


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    no_check = "--no-check" in argv
    out = None
    if "--out" in argv:
        i = argv.index("--out")
        out = argv[i + 1]
        del argv[i:i + 2]
    argv = [a for a in argv if a != "--no-check"]
    from bench.harness import serve as sv
    from bench.harness import trace as trc
    run = sv.load_module(BENCH / "run.py")
    held = {}

    build_server, instrument, extract, trace_hook = (
        sv.build_server, sv.instrument, trc.extract, sv.trace_hook)
    held["profiler_steps"] = set()

    def build_server_(*a, **k):
        held["server"] = build_server(*a, **k)
        return held["server"]

    def instrument_(server, probe, traced):
        held["probe"] = probe
        return instrument(server, probe, traced)

    def trace_hook_(now, server, probe, trace):
        was = (trace.get("started"), trace.get("stopped"))
        trace_hook(now, server, probe, trace)
        tracer = getattr(server, "tracer", None)
        if tracer is not None and was != (trace.get("started"),
                                          trace.get("stopped")):
            held["profiler_steps"].add(tracer.step)

    def extract_(path, spans):
        try:
            held["timeline"] = timeline_record(path, held["server"])
        except Exception:  # noqa: BLE001 — keep the cell's own run going
            held["timeline"] = {"error": traceback.format_exc()}
        return extract(path, spans)

    run_cell = run.run_cell

    def run_cell_(args, cell, cfgd, spec, devs, check=True):
        rec = run_cell(args, cell, cfgd, spec, devs, check=not no_check)
        tracer = getattr(held["server"], "tracer", None)
        probe = held["probe"]
        if tracer is not None:
            rec["program"] = program_record(tracer, probe.t0, probe.t_end,
                                            held["profiler_steps"])
        if held.get("timeline"):
            rec["timeline"] = held["timeline"]
        mets = {}
        for name in PROGRAM_METRICS:
            v = sv.load_module(BENCH / "metrics" / f"{name}.py").read(rec)
            if v is not None:
                mets[name] = v
        held["rec"], held["metrics"] = rec, mets
        if no_check:
            skip = ("ttft", "gaps", "queue", "model", "engine", "spec")
            print("record: " + json.dumps(
                {k: v for k, v in rec.items() if k not in skip},
                default=float))
            print("end to end: " + json.dumps(
                {"setup_s": rec["setup_s"],
                 "output_tok_s": rec["output_tokens"] / rec["seconds"]}))
        return rec

    sv.build_server, sv.instrument, trc.extract, sv.trace_hook = (
        build_server_, instrument_, extract_, trace_hook_)
    run.run_cell = run_cell_
    if no_check:
        run.report = lambda *a, **k: 0
    rc = run.main(argv)
    if "rec" in held:
        print("program metrics: " + json.dumps(held["metrics"]))
        if out:
            skip = ("ttft", "gaps", "queue")
            line = {k: v for k, v in held["rec"].items() if k not in skip}
            line["program_metrics"] = held["metrics"]
            line["rc"] = rc
            with open(out, "a") as f:
                f.write(json.dumps(line, default=float) + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
