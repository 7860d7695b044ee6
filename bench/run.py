#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``), whose
``arch`` names its architecture module (``bench/models/<arch>.py``: the
program's model config, the weight layout, the reference, the work
counts and the kernels), and a traffic mix
(``bench/traffic/<traffic>.json``, generated by
``bench/traffic/<kind>.py``); per-layer metrics are read by
``bench/metrics/<metric>.py`` from the run's record, which carries the
program's own records (``program``; traced, also ``timeline``).  All of
them are found by name, so a new cell, architecture, kernel or metric
needs only new files and new entries.

Without a TPU (or with fewer chips than the cell asks for) it exits 2
and prints no result.  ``--dry`` runs the same path on the CPU at the
configuration's smoke size with the Pallas kernel interpreted; it prints
the record with every device number marked not measured and never
prints a result line.  ``--control`` puts the lower-precision control in
the program's place: the tokens the module's fp8 reference puts first at
the same positions are judged by the same rule, so ``correct`` comes out
false (the program's own reading is still printed beside it).

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``--trace 1``: also
``breakdown``) and, last, ``checks``: each compared number with its
limit, which also end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
# served tokens compared with the reference per run, and most turns
CHECK_TOKENS = 400
CHECK_TURNS = 8
DRY_RATE_SCALE = 20.0
TRACE_AT = 0.3          # trace starts this share of the window in
TRACE_LEN_S = 4.0       # seconds of server clock traced


class CompileClock:
    """Backend compile count and seconds, from JAX's own monitoring."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration


def load_cell(name: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfgd = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                      .read_text())
    spec = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                      .read_text())
    return bench, cell, cfgd, spec


def cell_metrics(bench, cell, traced: bool):
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    ones (those that list the cell, or report an end-to-end metric the
    cell reports)."""
    def has(m):
        return "workloads" not in m or cell["name"] in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if has(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (has(m) if "workloads" in m else m["moves"] in names)]


def dry_spec(spec):
    """Traffic at 1/16 of every length and DRY_RATE_SCALE times the rate."""
    out = dict(spec)
    for k, v in spec.items():
        if isinstance(v, dict) and "median" in v:
            out[k] = {"median": max(2, v["median"] // 16), "sigma": v["sigma"],
                      "min": max(1, v["min"] // 16),
                      "max": max(2, v["max"] // 16)}
    out["system_len"] = max(1, spec["system_len"] // 16)
    out["max_context"] = spec["max_context"] // 16
    out["rate"] = spec["rate"] * DRY_RATE_SCALE
    for k in ("history_s", "ramp_s", "drain_s"):
        out[k] = spec[k] / DRY_RATE_SCALE
    return out


def queue_slope(queue, t0, t_end):
    """Least-squares growth of the turns in the system (waiting or
    running) over the window, in turns per second of server clock."""
    import numpy as np
    pts = [(t, n) for t, n in queue if t0 <= t < t_end]
    if len(pts) < 2:
        return 0.0
    t, n = np.asarray(pts, float).T
    return float(np.polyfit(t - t0, n, 1)[0])


def pct(xs, q):
    import numpy as np
    return float(np.percentile(xs, q)) if xs else math.nan


def pick_checked(turns, seed, rc):
    """Turns whose served tokens are compared, from every turn the run
    served: the longest, then finished turns before those cut off at the
    close, turns served as several
    cached and recomputed segments before the others and later turns of
    a session before first turns, each group in an order drawn from the
    seed, until CHECK_TOKENS served tokens or CHECK_TURNS turns."""
    rng = random.Random(f"{seed}:check")
    served = [r for r in turns
              if 0 < len(r.sampled_ids) <= len(r.output_script)]
    if not served:
        return []
    longest = max(served, key=lambda r: (r.prompt_len + len(r.sampled_ids),
                                         r.rid))
    rest = [r for r in served if r is not longest]
    rng.shuffle(rest)
    rest.sort(key=lambda r: (r.status != "finished", not rc[r.rid][2],
                             r.turn_index == 0))
    out, n = [longest], len(longest.sampled_ids)
    for r in rest:
        if n >= CHECK_TOKENS or len(out) >= CHECK_TURNS:
            break
        out.append(r)
        n += len(r.sampled_ids)
    return out


def compare(arch, params, p, turns, control: bool):
    """Widest gap below the reference's best of a served token, and with
    ``control`` the same for the tokens the fp8 control puts first; the
    reference is the architecture module's ``logits``."""
    import numpy as np
    from bench.harness import check
    widest, ctl, n_tok = 0.0, 0.0, 0
    for r in turns:
        n = len(r.sampled_ids)
        toks = list(r.prompt_tokens) + list(r.output_script[:n - 1])
        at = check.served_positions(r.prompt_len, n)
        lg = arch.logits(params, p, toks, at, "f32")
        widest = max(widest, float(check.gaps(lg, r.sampled_ids).max()))
        n_tok += len(at)
        if control:
            lc = arch.logits(params, p, toks, at, "fp8")
            ctl = max(ctl, float(check.gaps(lg, np.argmax(lc, 1)).max()))
    return widest, (ctl if control else None), n_tok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    bench, cell, cfgd, spec = load_cell(args.workload)
    if args.dry:
        spec = dry_spec(spec)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    # every step program must persist: no size cap may refuse an entry
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if not args.dry and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        print(f"bench: {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} device(s) of platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    rec = run_cell(args, cell, cfgd, spec, devs)
    return report(args, bench, cell, cfgd, rec, devs)


def run_cell(args, cell, cfgd, spec, devs, check: bool = True):
    import jax
    from bench.harness import serve as sv
    from bench.harness import traffic as tr
    from bench.harness import weights
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clock = CompileClock()
    arch = sv.arch_module(cfgd)
    p = sv.program_block(cfgd, args.dry)
    mcfg = arch.model_config(cfgd["name"], p)
    e = sv.engine_config(cfgd, args.dry)
    params = weights.make(arch.shapes(p), args.seed)
    impl = "pallas_interpret" if args.dry else "pallas"
    server = sv.build_server(mcfg, params, e, impl)
    n_var = sv.warm_lattice(server.engine)
    t_warm = time.perf_counter() - T_START

    gen = sv.load_module(BENCH / "traffic" / f"{spec['kind']}.py")
    S, ramp = float(args.seconds), float(spec["ramp_s"])
    plans = gen.generate(spec, spec["rate"], args.seed,
                         -max(spec["history_s"], ramp), S, p["vocab_size"])
    past, live = tr.split_at_window(plans, -ramp)
    pool_tokens = e["num_pages"] * e["page_size"]
    warm = tr.warm_histories(past, int(spec["warm_pool_fill"]
                                       * pool_tokens))
    warm_tokens = sv.prefill_histories(server, [h for _, h in warm])
    t_prefill = time.perf_counter() - T_START

    t0 = server.now + ramp
    probe = sv.Probe(server, clock, t0, t0 + S)
    trace = None
    if args.trace:
        trace = {"dir": tempfile.mkdtemp(prefix="bench_trace_"),
                 "at": TRACE_AT * S, "len": min(TRACE_LEN_S, 0.5 * S)}
    sv.instrument(server, probe, traced=bool(args.trace))
    fe = sv.make_frontend(server, sv.scripts_of(live, t0), spec, probe,
                          trace)
    evict0 = server.bm.n_evictions
    fe.run()
    if math.isnan(probe.close.now):
        probe.mark(probe.close)
    t_done = time.perf_counter()
    gc.callbacks.remove(probe.on_gc)

    due = sv.window_turns(fe, probe)
    rc = sv.recompute_counts(fe, e["page_size"],
                             {sid: len(h) for sid, h in warm})
    started = [r for r in due if not math.isnan(r.first_token_at)]
    stop = server.now
    ttft = [(r.first_token_at if not math.isnan(r.first_token_at)
             else stop) - r.arrival for r in due]
    missed = [r for r in due if r.status in ("failed", "rejected")
              or (not spec["saturated"] and math.isnan(r.first_token_at))]
    served = [r for s in fe.sessions for r in s.requests]
    gaps, out_tok = [], 0
    for r in served:
        ts = probe.token_times.get(r.rid, [])
        out_tok += sum(1 for t in ts if probe.t0 <= t < probe.t_end)
        gaps += [b - a for a, b in zip(ts, ts[1:])
                 if probe.t0 <= b < probe.t_end]
    admitted = [r for r in served
                if probe.t0 <= r.admitted_at < probe.t_end]
    rec = {
        "model": p, "engine": e, "spec": spec, "seed": args.seed,
        "seconds": S, "setup_s": probe.open.wall - T_START,
        "warm_s": t_warm, "prefill_s": t_prefill - t_warm,
        "window_wall_s": probe.close.wall - probe.open.wall,
        "run_wall_s": t_done - T_START, "variants": n_var,
        "compiles_total": clock.n, "compile_s": clock.secs,
        "compiles_in_window": probe.close.compiles - probe.open.compiles,
        "warm_tokens": warm_tokens, "warm_sessions": len(warm),
        "pool_tokens": pool_tokens,
        "live_sessions": len(live),
        "turns_due": len(due), "turns_started": len(started),
        "turns_finished": sum(1 for r in due if r.status == "finished"),
        "missed": len(missed),
        "attempted": len(due) if not spec["saturated"] else len(admitted),
        "ttft": ttft, "gaps": gaps, "output_tokens": out_tok,
        "recomputed": sum(rc[r.rid][0] for r in admitted),
        "prompt_positions": sum(rc[r.rid][1] for r in admitted),
        "multi_segment": sum(1 for r in admitted if rc[r.rid][2]),
        "steps": probe.close.steps - probe.open.steps,
        "decode_rows": probe.close.decode_rows - probe.open.decode_rows,
        "host_s": probe.close.host_s - probe.open.host_s,
        "evictions": server.bm.n_evictions - evict0,
        "backlog_at_close": sum(
            1 for r in due if not (r.admitted_at < probe.t_end)),
        "queue_slope_per_s": queue_slope(probe.queue, probe.t0,
                                         probe.t_end),
        "queue": probe.queue,
        "longest_dispatch_gap": probe.longest_gap,
        "gc_s": probe.gc_s, "gc_max_s": probe.gc_max_s,
    }
    stats = devs[0].memory_stats() or {}
    rec["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    rec["memory_limit_bytes"] = stats.get("bytes_limit")
    rec["program"] = sv.program_record(
        server.tracer, probe.t0, probe.t_end,
        trace.get("steps", ()) if trace else ())
    if args.trace and trace.get("stopped"):
        t_red = time.perf_counter()
        from bench.harness import timeline as tl
        from bench.harness import trace as trc
        path = trc.find_xplane(trace["dir"])
        rec["trace"] = reduce_trace(trc.extract(path, sv.SPANS), arch, p,
                                    probe.traced_steps, devs, args.dry)
        t_tl = time.perf_counter()
        try:
            rec["timeline"] = tl.timeline_record(path, server)
        except Exception:  # noqa: BLE001 — the run's other numbers stand
            rec["timeline"] = {"error": traceback.format_exc()}
            print(rec["timeline"]["error"], file=sys.stderr)
        rec["trace_reduce_s"] = t_tl - t_red
        rec["timeline_s"] = time.perf_counter() - t_tl
        shutil.rmtree(trace["dir"], ignore_errors=True)

    if not check:
        sv.release(server)
        return rec
    checked = pick_checked(served, args.seed, rc)
    rec["checked_turns"] = len(checked)
    rec["checked_multi_segment"] = sum(1 for r in checked if rc[r.rid][2])
    rec["checked_later_turns"] = sum(1 for r in checked if r.turn_index)
    rec["checked_finished"] = sum(1 for r in checked
                                  if r.status == "finished")
    for r in checked:       # drop what the reference does not need
        r.first_logits = None
    del fe, live, plans, past, warm, served, due, admitted
    sv.release(server)
    del server
    t_ref = time.perf_counter()
    gap, ctl, n_tok = compare(arch, params, p, checked, args.control)
    rec["reference_s"] = time.perf_counter() - t_ref
    rec["logit_gap"], rec["control_gap"], rec["checked_tokens"] = \
        gap, ctl, n_tok
    return rec


def reduce_trace(ex, arch, p, traced_steps, devs, dry: bool):
    """Busy, idle gaps and each of the module's ``KERNELS``' device
    seconds, and on the chip their roofline seconds and the model FLOPs
    of the traced steps, from the module's work counts."""
    from bench.harness import trace as trc
    from bench.harness import work
    from bench.harness.peaks import peaks_for
    red = trc.reduce(ex, arch.KERNELS)
    if not dry and red:
        pk = peaks_for(devs[0].device_kind)
        layers = [arch.layer_work(sq, p) for sq, _ in traced_steps]
        for k, kr in red["kernels"].items():
            kr["roofline_s"] = sum(work.roofline_seconds(lw, k, pk)
                                   for lw in layers)
        red["model_flops"] = sum(arch.step_flops(sq, rows, p)
                                 for sq, rows in traced_steps)
        red["peak_flops"] = pk["flops"]
    red["traced_steps"] = len(traced_steps)
    return red


def report(args, bench, cell, cfgd, rec, devs) -> int:
    from bench.harness import serve as sv
    check = cfgd["dry"].get("check", cfgd["check"]) if args.dry \
        else cfgd["check"]
    limit = check["logit_gap_limit"]
    judged = rec["control_gap"] if args.control else rec["logit_gap"]
    correct = rec["checked_turns"] > 0 and judged <= limit
    e2e = {
        "setup_s": rec["setup_s"],
        "ttft_p50_s": pct(rec["ttft"], 50),
        "ttft_p90_s": pct(rec["ttft"], 90),
        "itl_p99_ms": 1e3 * pct(rec["gaps"], 99),
        "output_tok_s": rec["output_tokens"] / rec["seconds"],
    }
    metrics = {}
    for m in cell_metrics(bench, cell, bool(args.trace)):
        if args.trace:
            mod = sv.load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = mod.read(rec)
        else:
            v = e2e.get(m["name"])
        if v is not None and not (isinstance(v, float) and math.isnan(v)):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    t = rec.get("trace") or {}
    if args.trace:
        device["busy_s"] = t.get("busy_s")
        device["window_s"] = t.get("window_s")
    checks = {"logit_gap": {"value": rec["logit_gap"], "limit": limit}}
    if rec["control_gap"] is not None:
        checks["control_logit_gap"] = {"value": rec["control_gap"],
                                       "limit": limit}

    skip = ("ttft", "gaps", "queue", "model", "engine", "spec")
    info = {k: v for k, v in rec.items() if k not in skip}
    if isinstance(info.get("timeline"), dict):     # per-step rows stay out
        info["timeline"] = {k: v for k, v in info["timeline"].items()
                            if k != "steps"}
    print("record: " + json.dumps(info, default=float))
    print(f"turns in the system: growth {rec['queue_slope_per_s']:.4g} "
          f"turns/s over the window, {rec['backlog_at_close']} turns due in "
          f"it not yet admitted at its close")
    print(f"working set {rec['warm_tokens']} warm tokens, pool "
          f"{rec['pool_tokens']} tokens; compiles in the window: "
          f"{rec['compiles_in_window']}")
    print(f"end to end: {json.dumps(e2e)}")
    lines = [f"{k}: {v['value']:.6g} (limit {v['limit']:g})"
             for k, v in checks.items()]
    lines.insert(0, f"compared {rec['checked_tokens']} served tokens of "
                 f"{rec['checked_turns']} turns "
                 f"({rec['checked_finished']} finished, "
                 f"{rec['checked_multi_segment']} multi-segment, "
                 f"{rec['checked_later_turns']} later turns)")
    if args.dry:
        device_src = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
        for m in cell_metrics(bench, cell, bool(args.trace)):
            if m["name"] in device_src:
                metrics[m["name"]] = "not measured"
        print("dry record: " + json.dumps(
            {"correct": correct, "metrics": metrics,
             "device": dict(device, memory_peak_bytes="not measured"),
             "program": rec["program"],
             "trace": rec.get("trace")}, default=float))
        for line in lines:
            print(line, file=sys.stderr)
        print("dry run: no result line")
        return 0 if correct else 1
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["missed"], "metrics": metrics, "device": device}
    if args.trace and t:
        out["breakdown"] = {"device_ops": t.get("device_ops", []),
                            "idle_gaps": t.get("idle_gaps", [])}
    out["checks"] = checks
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(out, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
