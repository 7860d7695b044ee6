"""Compile-free bucket-lattice auditor.

Enumerates the occupancy lattice an :class:`~repro.serving.engine.EngineConfig`
implies — via the same :func:`~repro.serving.engine.derive_bucket_lattice`
the engine itself compiles from — and, per bucket:

* sizes the abstract step footprint with ``jax.eval_shape`` (pack
  buffer, residual stream, attention score tile, logits) and the
  bucket-independent KV pool + parameter bytes, against a declared
  device budget;
* predicts the **exact** trace-key set a scripted workload sequence
  produces, by replaying the serving control plane in discrete-event
  simulation (``execute_model=False``: the real block manager, evictor
  and scheduler run; the engine is the Eq.-6 cost model) and mapping
  every dispatched plan through a replica of ``Engine.buckets_for``.

The runtime benchmarks close the loop: ``benchmarks/kernel_fusion.py``
and ``benchmarks/sharded_serving.py`` assert measured ``jit_traces``
equals the prediction, so the compile-once-per-bucket invariant is
checked from both sides of the compile boundary.

Prediction scope: the ``attn_impl="xla"`` engines the CI gates run
(``w_bucket == 0``).  Pallas work-list buckets are data-dependent
powers of two and are reported as a family, not predicted per step.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.common import Finding

PASS = "lattice"


# ---------------------------------------------------------------------------
# lattice enumeration

def enumerate_lattice(ecfg, n_shards: int = 1,
                      max_decode_steps: int = 1) -> Dict[str, object]:
    """The (t, np, w, k) key lattice implied by an EngineConfig."""
    from repro.serving.engine import WL_BUCKET, derive_bucket_lattice
    token_buckets, np_buckets = derive_bucket_lattice(ecfg)
    if ecfg.attn_impl == "xla":
        w_buckets: Tuple[int, ...] = (0,)
        w_note = "xla impl: no Pallas work-list"
    else:
        w_buckets = ()
        w_note = (f"data-dependent powers of two >= WL_BUCKET="
                  f"{WL_BUCKET} (not statically enumerable)")
    multi_token_ok = (ecfg.attn_mode == "fused" and n_shards == 1
                      and ecfg.assembly != "legacy")
    kmax = max_decode_steps if multi_token_ok else 1
    k_values = tuple(1 << i for i in range(max(1, kmax).bit_length())
                     if (1 << i) <= max(1, kmax))
    return {
        "token_buckets": list(token_buckets),
        "np_buckets": list(np_buckets),
        "w_buckets": list(w_buckets),
        "w_note": w_note,
        "k_values": list(k_values),
        "max_trace_keys": (len(token_buckets) * len(np_buckets)
                           * max(1, len(w_buckets)) * len(k_values)),
    }


# ---------------------------------------------------------------------------
# abstract footprints (jax.eval_shape — zero FLOPs, zero device memory)

def _bytes_of(tree) -> int:
    import jax
    return sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def bucket_footprints(cfg, ecfg, n_shards: int = 1,
                      device_budget_bytes: Optional[int] = None,
                      k_values: Sequence[int] = (1,)
                      ) -> Tuple[Dict[str, object], List[Finding]]:
    """Per-bucket abstract byte footprints vs a declared device budget.

    Every shape goes through ``jax.eval_shape`` so the sizes come out of
    JAX's abstract machinery (dtype promotion included), never from a
    real allocation."""
    import jax
    import jax.numpy as jnp
    from repro.models import abstract_params
    from repro.serving.engine import derive_bucket_lattice, pack_layout_for

    findings: List[Finding] = []
    token_buckets, np_buckets = derive_bucket_lattice(ecfg)
    heads = max(1, cfg.n_heads // max(1, n_shards))
    kv_heads = max(1, cfg.n_kv_heads // max(1, n_shards))
    pool_dt = np.dtype(cfg.dtype)

    params_bytes = _bytes_of(abstract_params(cfg))
    kv_pool = jax.eval_shape(
        lambda: jnp.zeros((cfg.n_layers, 2, ecfg.num_pages,
                           kv_heads, ecfg.page_size, cfg.head_dim),
                          pool_dt))
    kv_pool_bytes = _bytes_of(kv_pool)

    w_b = 0 if ecfg.attn_impl == "xla" else 64
    buckets = []
    worst = 0
    for t_b in token_buckets:
        for np_b in np_buckets:
            for k in k_values:
                _, size = pack_layout_for(ecfg, n_shards, t_b, np_b,
                                          w_b, k)
                shapes = jax.eval_shape(lambda: {
                    "pack": jnp.zeros((size,), jnp.int32),
                    "residual": jnp.zeros((k * t_b, cfg.d_model),
                                          jnp.float32),
                    "attn_scores": jnp.zeros(
                        (heads, t_b, np_b * ecfg.page_size), jnp.float32),
                    "logits": jnp.zeros(
                        (ecfg.max_prefills + ecfg.max_decodes,
                         cfg.vocab_size), jnp.float32),
                })
                act = sum(_bytes_of(v) for v in shapes.values())
                total = act + kv_pool_bytes + params_bytes
                worst = max(worst, total)
                buckets.append({
                    "t_bucket": t_b, "np_bucket": np_b, "k": k,
                    "pack_bytes": _bytes_of(shapes["pack"]),
                    "activation_bytes": act,
                    "total_bytes": total,
                })
                if device_budget_bytes and total > device_budget_bytes:
                    findings.append(Finding(
                        PASS, "src/repro/serving/engine.py", 1,
                        "bucket-over-budget",
                        f"bucket (t={t_b}, np={np_b}, k={k}): abstract "
                        f"footprint {total} B exceeds the declared "
                        f"device budget {device_budget_bytes} B"))
    report = {
        "params_bytes": params_bytes,
        "kv_pool_bytes": kv_pool_bytes,
        "per_bucket": buckets,
        "worst_case_total_bytes": worst,
        "device_budget_bytes": device_budget_bytes,
    }
    return report, findings


# ---------------------------------------------------------------------------
# trace-key prediction (discrete-event replay of the control plane)

def _key_for_plan(ecfg, token_buckets, np_buckets, n_shards, plan
                  ) -> Tuple[int, int, int, int]:
    """Replica of ``Engine.buckets_for`` + ``build_inputs``'s w/k —
    kept in lockstep with src/repro/serving/engine.py (the benchmark
    cross-checks fail loudly if the two ever diverge)."""
    if ecfg.attn_impl != "xla":
        raise NotImplementedError(
            "trace-key prediction covers attn_impl='xla' engines "
            "(Pallas work-list buckets are data-dependent)")
    k = plan.decode_steps
    if ecfg.attn_mode != "fused":
        return (ecfg.max_prefills * ecfg.max_chunk + ecfg.max_decodes,
                ecfg.max_blocks_per_seq, 0, k)
    need_t = plan.n_compute_tokens
    t_b = next((b for b in token_buckets if b >= need_t),
               token_buckets[-1])
    bs = ecfg.page_size
    need_p = 1
    for c in plan.prefills:
        need_p = max(need_p, -(-(int(c.positions[-1]) + 1) // bs))
    for req in plan.decodes:
        ctx = req.prompt_len + len(req.generated) + plan.decode_steps - 1
        need_p = max(need_p, -(-ctx // bs))
    need_p = min(need_p, ecfg.max_blocks_per_seq)
    np_b = next((b for b in np_buckets if b >= need_p), np_buckets[-1])
    return (t_b, np_b, 0, k)


def predict_trace_keys(cfg, scfg, workloads: Sequence,
                       ecfg=None) -> List[Tuple[int, int, int, int]]:
    """Distinct (t, np, w, k) trace keys the workload sequence compiles.

    Replays the full serving sequence on ONE simulated server
    (``execute_model=False``) — the scheduler, block manager and evictor
    run for real under ``clock="model"``, so the dispatched plan stream
    is the real engine's plan stream (workload outputs are scripted, so
    generated tokens and hence prefix-trie hits match too) — and maps
    each plan through the ``buckets_for`` replica.  Sharded runs are
    predicted with the same single-device replay: the sharded gates
    already pin their plan streams to the single-device reference
    (``bucket_counts`` equality)."""
    from repro.serving import AsymCacheServer
    from repro.serving.engine import EngineConfig, derive_bucket_lattice

    scfg = copy.deepcopy(scfg)
    scfg.execute_model = False
    scfg.clock = "model"
    n_shards = scfg.n_shards
    scfg.n_shards = 1
    if ecfg is None:
        ecfg = EngineConfig(
            num_pages=scfg.num_blocks, page_size=scfg.block_size,
            max_chunk=scfg.scheduler.max_chunk,
            max_prefills=scfg.scheduler.max_prefills,
            max_decodes=scfg.scheduler.max_decodes,
            attn_mode=scfg.attn_mode)
    token_buckets, np_buckets = derive_bucket_lattice(ecfg)
    srv = AsymCacheServer(cfg, None, scfg, ecfg=None)
    # mirror the real server's scheduler wiring (__init__ only applies
    # it on the execute_model path)
    srv.sched.cfg.token_buckets = token_buckets
    srv.sched.cfg.page_buckets = np_buckets
    if (n_shards > 1 or ecfg.attn_mode != "fused"
            or ecfg.assembly == "legacy"):
        srv.sched.cfg.max_decode_steps = 1

    keys: List[Tuple[int, int, int, int]] = []
    inner = srv.engine.dispatch

    def spy(plan):
        keys.append(_key_for_plan(ecfg, token_buckets, np_buckets,
                                  n_shards, plan))
        return inner(plan)

    srv.engine.dispatch = spy
    for wl in workloads:
        srv.run(wl)
    return sorted(set(keys))


# ---------------------------------------------------------------------------
# optional compiled-collectives probe (needs devices; NOT compile-free)

def collective_probe(cfg, params, scfg, ecfg=None) -> Dict[str, Dict]:
    """Per-bucket collective counts from compiled HLO (opt-in: compiles
    one step per (t, np) bucket pair).  ``launch/dryrun.py``-style cost
    probing; import stays lazy because importing that module mutates
    XLA_FLAGS."""
    from repro.serving import AsymCacheServer
    srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
    eng = srv.engine
    out: Dict[str, Dict] = {}
    for t_b in eng.token_buckets:
        for np_b in eng.np_buckets:
            out[f"T{t_b}xNP{np_b}"] = eng.collective_counts(t_b, np_b)
    return out


# ---------------------------------------------------------------------------
# the audit the CLI runs: the kernel-fusion gate configuration

#: default audit budget: smoke-scale serving must fit a 2 GiB device
DEFAULT_DEVICE_BUDGET = 2 << 30


def _gate_setup():
    """The fused single-device gate configuration of
    benchmarks/kernel_fusion.py (smoke scale), rebuilt here so the audit
    covers exactly the lattice CI compiles."""
    from repro.configs import get_smoke_config, scaled_config
    from repro.serving import SchedulerConfig, ServerConfig
    from repro.serving.engine import EngineConfig
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    scfg = ServerConfig(
        policy="asymcache", num_blocks=256, block_size=16,
        clock="model", pipeline_depth=1, attn_mode="fused",
        scheduler=SchedulerConfig(token_budget=256, max_chunk=96,
                                  max_prefills=2, max_decodes=24,
                                  decode_threshold=4, max_running=64))
    ecfg = EngineConfig(
        num_pages=256, page_size=16, max_prefills=2, max_chunk=96,
        max_decodes=24, max_blocks_per_seq=32, attn_mode="fused")
    return cfg, scfg, ecfg


def _gate_workloads(smoke: bool = True):
    """The exact workload sequence the kernel-fusion gate serves on its
    depth-1 fused server (warmup + identity run + counter run +
    segments x perf run)."""
    from repro.serving import AgenticConfig, agentic_workload

    def wl(n_jobs, seed):
        return agentic_workload(AgenticConfig(
            n_jobs=n_jobs, tool_calls_per_job=(2, 4),
            system_prefix_len=48, task_len=(70, 230),
            tool_result_len=(33, 150), output_len=(24, 56),
            tool_duration=(0.2, 0.8), qps=3.0, seed=seed))

    n_jobs, seed = (6, 5) if smoke else (10, 5)
    segments = 2 if smoke else 4
    return ([wl(1, 999), wl(n_jobs, seed), wl(n_jobs, seed + 1)]
            + [wl(n_jobs, seed + 2) for _ in range(segments)])


def audit(root: Path, device_budget_bytes: Optional[int] = None,
          predict: bool = True
          ) -> Tuple[Dict[str, object], List[Finding]]:
    """The full lattice audit: enumeration + footprints (+ replay
    prediction).  Everything here is compile-free."""
    budget = device_budget_bytes or DEFAULT_DEVICE_BUDGET
    cfg, scfg, ecfg = _gate_setup()
    lattice = enumerate_lattice(ecfg, n_shards=1,
                                max_decode_steps=scfg.scheduler
                                .max_decode_steps)
    footprints, findings = bucket_footprints(
        cfg, ecfg, n_shards=1, device_budget_bytes=budget,
        k_values=lattice["k_values"])
    report: Dict[str, object] = {"lattice": lattice,
                                 "footprints": footprints}
    if predict:
        keys = predict_trace_keys(cfg, scfg, _gate_workloads(smoke=True),
                                  ecfg=ecfg)
        report["predicted_trace_keys"] = [list(k) for k in keys]
        report["predicted_jit_traces"] = len(keys)
        if len(keys) > lattice["max_trace_keys"]:
            findings.append(Finding(
                PASS, "src/repro/serving/engine.py", 1,
                "off-lattice-key",
                f"replay predicts {len(keys)} trace keys but the "
                f"lattice only admits {lattice['max_trace_keys']}"))
    return report, findings
