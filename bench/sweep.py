#!/usr/bin/env python3
"""Knee sweep: serve one cell at several fixed rates in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.05,0.1,0.2

Each rate gets a fresh server and set-up (the step programs come from the
persistent compile cache after the first) and one window of ``seconds``;
no reference check runs.  One JSON line per rate reports the turns due,
started and finished in the window, the waiting queue's growth over the
window, the turns due but not yet admitted at its close, TTFT and output
tokens/s.  The knee is the highest rate whose queue does not grow.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args(argv)
    bench, cell, cfgd, spec0 = bench_run.load_cell(args.workload)
    if args.dry:
        spec0 = bench_run.dry_spec(spec0)
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench_run.CACHE_DIR)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    sys.path.insert(0, str(bench_run.ROOT))
    import jax
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if not args.dry and devs[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    for rate in (float(x) for x in args.rates.split(",")):
        spec = dict(spec0, rate=rate)
        ns = argparse.Namespace(seed=args.seed, seconds=args.seconds,
                                trace=0, dry=args.dry, control=False)
        rec = bench_run.run_cell(ns, cell, cfgd, spec, devs, check=False)
        out = {k: rec[k] for k in (
            "turns_due", "turns_started", "turns_finished", "missed",
            "backlog_at_close", "queue_slope_per_s", "output_tokens",
            "steps", "evictions", "recomputed", "prompt_positions",
            "warm_tokens", "pool_tokens", "setup_s", "window_wall_s")}
        out.update(rate=rate, ttft_p50_s=bench_run.pct(rec["ttft"], 50),
                   ttft_p90_s=bench_run.pct(rec["ttft"], 90),
                   itl_p99_ms=1e3 * bench_run.pct(rec["gaps"], 99),
                   output_tok_s=rec["output_tokens"] / rec["seconds"])
        print("sweep: " + json.dumps(out), flush=True)
        del rec
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
