"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  Fig. 11/12  e2e_serving        policy x dispersion x dataset TTFT/TPOT
  Table 2     evictor_complexity O(log n) vs O(n) vs LRU end-to-end
  Fig. 9      evictor_scaling    control-plane time vs cache size
  Fig. 14     sensitivity        lifespan / reuse-prob / slope sweeps
  Fig. 15     agentic            Continuum integration, QPS sweep
  Fig. 3/7    workload_stats     hit-position + reuse-interval PDFs
  (ours)      roofline_report    dry-run three-term roofline table
  (ours)      prefix_sharing     cross-request sharing vs no-sharing
  (ours)      sharded_serving    N-way sequence-sharded engine vs single
  §6.5/§8     agentic_online     closed-loop Continuum frontend + prefetch
  (ours)      control_plane_stress  k-step decode dispatch + 5k-session O(·)
  (ours)      chaos_soak         fault injection + graceful degradation
  (ours)      prefix_store       cross-restart + multi-tenant store gates
"""
import argparse
import sys
import time
import traceback

MODULES = [
    ("e2e_serving", {}),
    ("evictor_complexity", {}),
    ("evictor_scaling", {}),
    ("sensitivity", {}),
    ("agentic", {}),
    ("workload_stats", {}),
    ("offload", {}),
    ("roofline_report", {}),
    ("prefix_sharing", {}),
    # runs its measurement in a child process with 4 forced host devices,
    # so it is insensitive to this process's jax device-count lock
    ("sharded_serving", {}),
    ("agentic_online", {}),
    ("control_plane_stress", {}),
    ("chaos_soak", {}),
    ("prefix_store", {}),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names")
    ap.add_argument("--check", action="store_true",
                    help="import every module and verify it exposes a "
                         "callable main(), without running anything — "
                         "the fast wiring check the analyze CI job runs")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    if args.check:
        bad = 0
        for name, _kw in MODULES:
            if only and name not in only:
                continue
            try:
                mod = __import__(f"benchmarks.{name}", fromlist=["main"])
                assert callable(getattr(mod, "main", None)), \
                    f"benchmarks.{name} has no callable main()"
                print(f"check/{name},ok")
            except Exception as e:  # noqa: BLE001
                bad += 1
                traceback.print_exc(file=sys.stderr)
                print(f"check/{name},FAILED:{type(e).__name__}")
        sys.exit(1 if bad else 0)

    print("name,us_per_call,derived")
    failures = 0
    for name, kw in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            rows = mod.main(**kw)
            rows.emit()
            print(f"bench/{name}/_elapsed,{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc(file=sys.stderr)
            print(f"bench/{name}/_elapsed,{(time.time()-t0)*1e6:.0f},"
                  f"FAILED:{type(e).__name__}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
