"""Sharded multi-device MSA serving vs the single-device fused engine.

The distributed generalization of Multi-Segment Attention: the KV page
pool sequence-shards over an N-way mesh (each device's pages are one
segment subset), per-shard attention partials merge through the exact
log-sum-exp combine, weights shard by the decode sharding rules, and the
block manager stripes every sequence's blocks across shards.

All gates are **deterministic counters** — host wall clock drifts 1.5-2x
on shared containers, and CPU "devices" here are host threads, so timing
says nothing about the sharding's value anyway:

  * 2- and 4-way sharded runs produce IDENTICAL greedy tokens and
    generated sequences as the single-device fused engine (and first-token
    logits within f32 LSE-merge epsilon), at pipeline depth 0 and 1;
  * identical step counts and occupancy-bucket histograms (the scheduler
    is shard-oblivious at plan level — ``StepPlan`` buckets unchanged);
  * ``jit_traces == len(buckets_used)``: the compile-once-per-bucket
    cache survives ``shard_map``;
  * per-shard page occupancy sums to the global count and stays balanced
    under striped allocation (bounded imbalance);
  * the compiled sharded step contains the merge collectives (>= 1
    all-reduce per layer, from HLO op counts); the single-device step
    contains none.

The measurement runs in a CHILD process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` because jax locks
the host device count at first init — so this module works standalone
AND from ``benchmarks/run.py`` after other benchmarks already
initialized jax with one device.

    PYTHONPATH=src:. python -m benchmarks.run --only sharded_serving
    PYTHONPATH=src:. python benchmarks/sharded_serving.py --smoke  # CI gate
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import Rows, write_bench_json

N_DEVICES = 4
SHARDINGS = (2, 4)

_CHILD = r"""
import json, sys
import numpy as np
import jax
from repro.configs import get_smoke_config, scaled_config
from repro.models import init_params
from repro.serving import (AsymCacheServer, EngineConfig, SchedulerConfig,
                           ServerConfig, AgenticConfig, agentic_workload)

n_jobs, seed = int(sys.argv[1]), int(sys.argv[2])
cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
params = init_params(cfg, jax.random.PRNGKey(0))

def mk_workload():
    # ragged agentic mix under memory pressure: evictions, host-tier
    # swaps and multi-segment recompute all on
    return agentic_workload(AgenticConfig(
        n_jobs=n_jobs, tool_calls_per_job=(2, 4), system_prefix_len=48,
        task_len=(70, 200), tool_result_len=(33, 120), output_len=(20, 44),
        tool_duration=(0.2, 0.8), qps=3.0, seed=seed))

def run(n_shards, depth):
    scfg = ServerConfig(
        policy="asymcache", num_blocks=64, block_size=16, clock="model",
        pipeline_depth=depth, n_shards=n_shards, host_blocks=16,
        scheduler=SchedulerConfig(token_budget=192, max_chunk=64,
                                  max_prefills=2, max_decodes=16,
                                  decode_threshold=4))
    ecfg = EngineConfig(num_pages=64, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=16, max_blocks_per_seq=24)
    srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
    wl = mk_workload()
    res = srv.run(wl)
    return wl, res, srv

out = {"n_layers": cfg.n_layers, "shardings": {}}
w1, r1, s1 = run(1, 0)
out["base"] = {
    "steps": r1["steps"], "evictions": r1["evictions"],
    "swap_ins": r1["swap_ins"],
    "bucket_counts": r1["bucket_counts"],
    "jit_traces": s1.engine.jit_traces,
    "buckets_used": len(s1.engine.buckets_used),
    "collectives": s1.engine.collective_counts(),
}
for n in (2, 4):
    rec = {}
    for depth in (0, 1):
        wn, rn, sn = run(n, depth)
        rec[f"depth{depth}"] = {
            "steps": rn["steps"],
            "tokens_identical": bool(all(
                a.sampled_ids == b.sampled_ids and a.generated == b.generated
                for a, b in zip(w1, wn))),
            "max_first_logit_diff": max(
                float(np.max(np.abs(a.first_logits - b.first_logits)))
                for a, b in zip(w1, wn)),
            "bucket_counts": rn["bucket_counts"],
            "jit_traces": sn.engine.jit_traces,
            "buckets_used": len(sn.engine.buckets_used),
            "per_shard_used": rn["per_shard_used"],
            "shard_size": sn.bm.shard_size,
            "instep_copies": rn["instep_copies"],
            "eager_copies": rn["eager_copies"],
            "instep_swaps": rn["instep_swaps"],
            "eager_swaps": rn["eager_swaps"],
        }
        if depth == 0:
            rec["collectives"] = sn.engine.collective_counts()
    out["shardings"][str(n)] = rec
print("RESULT " + json.dumps(out))
"""


def _run_child(n_jobs: int, seed: int) -> dict:
    env = dict(os.environ)
    # host devices only: never compete with a parent for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEVICES}"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(n_jobs), str(seed)],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded child failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in child output:\n{proc.stdout}")


def _predict_trace_keys(n_jobs: int, seed: int):
    """Compile-free trace-key prediction in the PARENT process (no
    forced host devices): replay the child's workload on the simulated
    control plane with the child's exact configs.  The sharded arms pin
    their plan streams to the single-device reference (bucket_counts
    equality gate), so one single-device prediction covers every arm.
    Must mirror ``run()``/``mk_workload()`` inside ``_CHILD``."""
    from repro.analysis.lattice import predict_trace_keys
    from repro.configs import get_smoke_config, scaled_config
    from repro.serving import (AgenticConfig, EngineConfig,
                               SchedulerConfig, ServerConfig,
                               agentic_workload)
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    scfg = ServerConfig(
        policy="asymcache", num_blocks=64, block_size=16, clock="model",
        pipeline_depth=0, host_blocks=16,
        scheduler=SchedulerConfig(token_budget=192, max_chunk=64,
                                  max_prefills=2, max_decodes=16,
                                  decode_threshold=4))
    ecfg = EngineConfig(num_pages=64, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=16,
                        max_blocks_per_seq=24)
    wl = agentic_workload(AgenticConfig(
        n_jobs=n_jobs, tool_calls_per_job=(2, 4), system_prefix_len=48,
        task_len=(70, 200), tool_result_len=(33, 120),
        output_len=(20, 44), tool_duration=(0.2, 0.8), qps=3.0,
        seed=seed))
    return predict_trace_keys(cfg, scfg, [wl], ecfg=ecfg)


def main(smoke: bool = False, n_jobs: int = 8, seed: int = 5) -> Rows:
    if smoke:
        n_jobs = 5
    predicted = _predict_trace_keys(n_jobs, seed)
    res = _run_child(n_jobs, seed)
    L = res["n_layers"]
    base = res["base"]

    # artifact first, gates second — a failed gate must still leave the
    # counters on disk for the CI artifact upload
    write_bench_json("sharded_serving", {
        "n_layers": L,
        "base": base,
        "shardings": res["shardings"],
        "jit_traces_predicted": len(predicted),
        "smoke": smoke,
    })

    # compile-once-per-bucket, cross-checked against the static auditor:
    # the single-device reference must compile exactly the predicted
    # trace-key set (the per-arm gates below then carry it to every
    # sharding via bucket_counts equality)
    assert base["jit_traces"] == len(predicted), (
        f"base jit_traces {base['jit_traces']} != "
        f"predicted {len(predicted)} ({predicted})")

    rows = Rows()
    rows.add("sharded_serving/single/steps", base["steps"],
             f"evictions={base['evictions']};swap_ins={base['swap_ins']}")
    for n in SHARDINGS:
        rec = res["shardings"][str(n)]
        coll = rec["collectives"]
        ar_per_layer = coll.get("all-reduce", 0) / L
        for depth in (0, 1):
            d = rec[f"depth{depth}"]
            # ---- deterministic gates --------------------------------
            assert d["steps"] == base["steps"], (n, depth, d["steps"])
            assert d["tokens_identical"], \
                f"{n}-way depth {depth}: greedy tokens diverged"
            assert d["max_first_logit_diff"] < 1e-4, (n, depth, d)
            assert d["bucket_counts"] == base["bucket_counts"], (n, depth)
            assert d["jit_traces"] == d["buckets_used"], (n, depth, d)
            assert d["jit_traces"] == len(predicted), (n, depth, d)
            used = d["per_shard_used"]
            assert len(used) == n
            assert all(0 <= u <= d["shard_size"] for u in used), used
            # striped allocation keeps residency balanced: no shard may
            # dominate (pressure-dependent skew bounded at half the pool
            # share)
            assert max(used) - min(used) <= max(2, d["shard_size"] // 2), \
                (n, depth, used)
        assert coll.get("all-reduce", 0) >= L, (n, coll)
        assert sum(base["collectives"].values()) == 0, base["collectives"]
        d0 = rec["depth0"]
        rows.add(f"sharded_serving/{n}way/max_logit_diff",
                 d0["max_first_logit_diff"] * 1e6,
                 f"x1e-6;tokens_identical={d0['tokens_identical']}")
        rows.add(f"sharded_serving/{n}way/allreduce_per_layer", ar_per_layer,
                 ";".join(f"{k}={v}" for k, v in sorted(coll.items())))
        rows.add(f"sharded_serving/{n}way/per_shard_used",
                 float(max(d0["per_shard_used"])),
                 f"used={d0['per_shard_used']};"
                 f"instep_copies={d0['instep_copies']};"
                 f"eager_copies={d0['eager_copies']}")

    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config; deterministic-counter gates")
    ap.add_argument("--jobs", type=int, default=8)
    a = ap.parse_args()
    main(smoke=a.smoke, n_jobs=a.jobs).emit()
