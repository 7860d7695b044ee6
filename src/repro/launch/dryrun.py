import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run driver (deliverable e) + structured cost extraction.

Per (architecture x input-shape x mesh) cell, two artifacts:

1. FULL compile — ``jax.jit(step).lower(...).compile()`` of the real
   config (scanned layers, grad accumulation, remat).  Success proves the
   sharding config is coherent; ``memory_analysis()`` proves it fits.

2. COST PROBES — XLA's ``cost_analysis()`` counts a ``while`` body ONCE
   regardless of trip count, so scanned-loop modules under-report
   FLOPs/bytes/collectives.  We therefore compile two scan-UNROLLED probe
   variants (1 and 2 layers, one microbatch) and difference them:

       per-layer cost   C2 = P(2L) - P(1L)
       per-microbatch   C1 = P(1L) - C2
       optimizer        O(L) from two update-only probes
       total            = accum x (C1 + L*·C2) + O0 + L*·O_L

   Every quantity (FLOPs, bytes, per-kind collective wire bytes) gets the
   same treatment.  This is exact w.r.t. XLA's own cost model because the
   module really is affine in (layers, accumulation steps).

Usage:
  python -m repro.launch.dryrun --arch chatglm3-6b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --both-meshes
  python -m repro.launch.dryrun --list
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import (
    ARCH_IDS,
    SHAPE_BY_NAME,
    SHAPES,
    cell_is_runnable,
    get_config,
)
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.context import DistContext, use_dist
from repro.distributed.sharding import (
    batch_shardings,
    decode_state_shardings,
    effective_config,
    make_context,
    opt_shardings,
    param_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.models import abstract_params, decode_step, forward, init_decode_state
from repro.models.model import loss_fn
from repro.roofline import parse_collectives, roofline, total_wire_bytes
from repro.training.optimizer import for_arch
from repro.training.train_step import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape_kind: str, batch: int,
                seq: int) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input — weak-type-correct,
    shardable, no device allocation."""
    dt = jnp.dtype(cfg.dtype)
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    out: Dict = {}
    if shape_kind in ("train", "prefill"):
        if cfg.inputs_are_embeddings and not cfg.enc_dec:
            out["embeds"] = sds((batch, seq, cfg.d_model), dt)
        else:
            out["tokens"] = sds((batch, seq), i32)
        if cfg.enc_dec:
            out["enc_embeds"] = sds((batch, cfg.encoder_len, cfg.d_model), dt)
        if shape_kind == "train":
            out["labels"] = sds((batch, seq), i32)
        return out
    state = init_decode_state(cfg, batch, seq, abstract=True)
    return {"state": state, "tokens": sds((batch,), i32)}


def grad_accum_for(cfg: ModelConfig, shape: ShapeConfig, dp_total: int,
                   act_budget_bytes: float = 4e9) -> int:
    """Largest microbatch whose remat-saved layer inputs fit the activation
    budget — more accumulation steps mean more FSDP weight re-gathers per
    step (measured: the dominant collective cost), so microbatches should
    be as large as memory allows."""
    per_dev = max(1, shape.global_batch // dp_total)
    saved_per_seq = cfg.n_layers * shape.seq_len * cfg.d_model * 2
    micro = max(1, min(per_dev, int(act_budget_bytes // max(saved_per_seq, 1))))
    while per_dev % micro:   # microbatch must divide the per-device batch
        micro -= 1
    return max(1, per_dev // micro)


def _probe_cfg(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    changes: Dict[str, Any] = {"n_layers": n_layers}
    if cfg.enc_dec:
        changes["n_encoder_layers"] = n_layers
    return dataclasses.replace(cfg, **changes)


# ---------------------------------------------------------------------------
# Compilation helpers
# ---------------------------------------------------------------------------

def _costs_of(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    out = {"flops": float(ca.get("flops", 0.0)),
           "bytes": float(ca.get("bytes accessed", 0.0)),
           "wire": total_wire_bytes(coll)}
    for kind, v in coll.items():
        out[f"wire:{kind}"] = v["wire_bytes"]
        out[f"count:{kind}"] = v["count"]
    return out


def _combine(p1: Dict, p2: Dict, mult_layer: float, mult_outer: float,
             fixed: Optional[Dict] = None) -> Dict[str, float]:
    """total = mult_outer x (C1 + mult_layer·C2) + fixed, per key."""
    keys = set(p1) | set(p2) | set(fixed or {})
    out = {}
    for k in keys:
        a, b = p1.get(k, 0.0), p2.get(k, 0.0)
        c2 = max(b - a, 0.0)
        c1 = max(a - c2, 0.0)
        out[k] = mult_outer * (c1 + mult_layer * c2) + (fixed or {}).get(k, 0.0)
    return out


def _compile_train(cfg: ModelConfig, mesh, ctx: DistContext, batch_specs,
                   accum: int, with_opt: bool, donate: bool):
    rules = ctx.rules
    params_sh = param_shardings(cfg, mesh, rules)
    params_abs = abstract_params(cfg)
    b_sh = batch_shardings(cfg, mesh, rules, batch_specs)
    if with_opt:
        opt = for_arch(cfg.param_count())
        opt_abs = jax.eval_shape(opt.init, params_abs)
        opt_sh = opt_shardings(opt.name, cfg, mesh, rules)
        step = make_train_step(cfg, opt, grad_accum=accum)
        fn = jax.jit(step, in_shardings=(params_sh, opt_sh, b_sh, None),
                     out_shardings=(params_sh, opt_sh, None),
                     donate_argnums=(0, 1) if donate else ())
        args = (params_abs, opt_abs, batch_specs,
                jax.ShapeDtypeStruct((), jnp.int32))
    else:
        def grads_only(params, batch):
            return jax.value_and_grad(loss_fn)(params, cfg, batch)
        fn = jax.jit(grads_only, in_shardings=(params_sh, b_sh),
                     out_shardings=(None, params_sh))
        args = (params_abs, batch_specs)
    with use_dist(ctx), mesh:
        return fn.lower(*args).compile()


def _compile_opt_update(cfg: ModelConfig, mesh, ctx: DistContext):
    rules = ctx.rules
    params_sh = param_shardings(cfg, mesh, rules)
    params_abs = abstract_params(cfg)
    opt = for_arch(cfg.param_count())
    opt_abs = jax.eval_shape(opt.init, params_abs)
    opt_sh = opt_shardings(opt.name, cfg, mesh, rules)

    def upd(grads, state, params, step):
        return opt.update(grads, state, params, step)

    fn = jax.jit(upd, in_shardings=(params_sh, opt_sh, params_sh, None),
                 out_shardings=(params_sh, opt_sh))
    with use_dist(ctx), mesh:
        return fn.lower(params_abs, opt_abs, params_abs,
                        jax.ShapeDtypeStruct((), jnp.int32)).compile()


def _compile_prefill(cfg: ModelConfig, mesh, ctx: DistContext, batch_specs):
    from jax.sharding import NamedSharding, PartitionSpec as P
    rules = ctx.rules
    params_sh = param_shardings(cfg, mesh, rules)
    params_abs = abstract_params(cfg)
    b_sh = batch_shardings(cfg, mesh, rules, batch_specs)
    ret_kv = cfg.family != "ssm"

    def prefill_step(params, batch):
        return forward(params, cfg, batch, return_kv=ret_kv, last_only=True)

    logits_sh = NamedSharding(mesh, P(rules.get("batch"), None,
                                      rules.get("vocab")))
    kv_sh = NamedSharding(mesh, P(None, rules.get("batch"), "model",
                                  None, None))
    out_sh = (logits_sh, (kv_sh, kv_sh)) if ret_kv else logits_sh
    fn = jax.jit(prefill_step, in_shardings=(params_sh, b_sh),
                 out_shardings=out_sh)
    with use_dist(ctx), mesh:
        return fn.lower(params_abs, batch_specs).compile()


def _compile_decode(cfg: ModelConfig, mesh, ctx: DistContext, specs,
                    donate: bool):
    from jax.sharding import NamedSharding, PartitionSpec as P
    rules = ctx.rules
    params_sh = param_shardings(cfg, mesh, rules)
    params_abs = abstract_params(cfg)
    state_abs, tokens_abs = specs["state"], specs["tokens"]
    state_sh = decode_state_shardings(cfg, mesh, rules, state_abs)
    tok_sh = NamedSharding(mesh, P(rules.get("batch")))
    logits_sh = NamedSharding(mesh, P(rules.get("batch"), rules.get("vocab")))

    def serve_step(params, state, tokens):
        return decode_step(params, cfg, state, tokens)

    fn = jax.jit(serve_step, in_shardings=(params_sh, state_sh, tok_sh),
                 out_shardings=(logits_sh, state_sh),
                 donate_argnums=(1,) if donate else ())
    with use_dist(ctx), mesh:
        return fn.lower(params_abs, state_abs, tokens_abs).compile()


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, force: bool = False,
             save_hlo: bool = False, skip_probes: bool = False) -> Dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    ok, why = cell_is_runnable(arch, shape_name)
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "status": "skipped", "reason": why}
    if not ok:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[{arch} x {shape_name} x {mesh_name}] SKIP: {why}")
        return rec

    shape = SHAPE_BY_NAME[shape_name]
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = 1
        for v in mesh.shape.values():
            chips *= v
        dp_all = chips // mesh.shape["model"]
        cfg = effective_config(get_config(arch), tp=mesh.shape["model"],
                               ep=dp_all)
        ctx = make_context(cfg, mesh, shape.kind,
                           batch_size=shape.global_batch)
        probe_flags = dict(ctx.flags, unroll_scans=True)
        dp_total = chips // mesh.shape["model"]
        meta: Dict[str, Any] = {"rules": {k: str(v) for k, v in
                                          ctx.rules.items()}}

        # ---- 1. full compile (proof + memory) --------------------------
        if shape.kind == "train":
            accum = grad_accum_for(cfg, shape, dp_total)
            meta["grad_accum"] = accum
            meta["optimizer"] = for_arch(cfg.param_count()).name
            batch = input_specs(cfg, "train", shape.global_batch,
                                shape.seq_len)
            compiled = _compile_train(cfg, mesh, ctx, batch, accum,
                                      with_opt=True, donate=True)
        elif shape.kind == "prefill":
            batch = input_specs(cfg, "prefill", shape.global_batch,
                                shape.seq_len)
            compiled = _compile_prefill(cfg, mesh, ctx, batch)
        else:
            specs = input_specs(cfg, "decode", shape.global_batch,
                                shape.seq_len)
            compiled = _compile_decode(cfg, mesh, ctx, specs, donate=True)
        t_full = time.time() - t0
        mem = compiled.memory_analysis()
        raw = _costs_of(compiled)
        if save_hlo:
            with open(out_path.replace(".json", ".hlo.txt"), "w") as f:
                f.write(compiled.as_text())
        del compiled

        # ---- 2. cost probes (unrolled 1 vs 2 layers) --------------------
        totals = dict(raw)
        if not skip_probes:
            probes = {}
            if shape.kind == "train":
                micro_batch = max(dp_total,
                                  shape.global_batch // meta["grad_accum"])
                for L in (1, 2):
                    pcfg = _probe_cfg(cfg, L)
                    pctx = DistContext(mesh, ctx.rules, probe_flags)
                    pbatch = input_specs(pcfg, "train", micro_batch,
                                         shape.seq_len)
                    probes[L] = _costs_of(_compile_train(
                        pcfg, mesh, pctx, pbatch, 1, with_opt=False,
                        donate=False))
                opt_probes = {}
                for L in (1, 2):
                    pcfg = _probe_cfg(cfg, L)
                    pctx = DistContext(mesh, ctx.rules, probe_flags)
                    opt_probes[L] = _costs_of(_compile_opt_update(
                        pcfg, mesh, pctx))
                fixed = _combine(opt_probes[1], opt_probes[2],
                                 mult_layer=cfg.n_layers, mult_outer=1.0)
                totals = _combine(probes[1], probes[2],
                                  mult_layer=cfg.n_layers,
                                  mult_outer=meta["grad_accum"], fixed=fixed)
            else:
                for L in (1, 2):
                    pcfg = _probe_cfg(cfg, L)
                    pctx = DistContext(mesh, ctx.rules, probe_flags)
                    if shape.kind == "prefill":
                        pbatch = input_specs(pcfg, "prefill",
                                             shape.global_batch,
                                             shape.seq_len)
                        probes[L] = _costs_of(_compile_prefill(
                            pcfg, mesh, pctx, pbatch))
                    else:
                        pspecs = input_specs(pcfg, "decode",
                                             shape.global_batch,
                                             shape.seq_len)
                        probes[L] = _costs_of(_compile_decode(
                            pcfg, mesh, pctx, pspecs, donate=False))
                totals = _combine(probes[1], probes[2],
                                  mult_layer=cfg.n_layers, mult_outer=1.0)
            meta["probe_1L"] = probes.get(1)
            meta["probe_2L"] = probes.get(2)

        terms = roofline(cfg, shape, chips,
                         per_device_flops=totals["flops"],
                         per_device_bytes=totals["bytes"],
                         per_device_wire_bytes=totals["wire"])
        rec.update({
            "status": "ok",
            "chips": chips,
            "compile_s": round(t_full, 1),
            "total_s": round(time.time() - t0, 1),
            "meta": meta,
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "peak_bytes": mem.peak_memory_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            },
            "raw_module_costs": raw,
            "costs_per_device": totals,
            "roofline": {
                "compute_s": terms.compute_s,
                "memory_s": terms.memory_s,
                "collective_s": terms.collective_s,
                "bottleneck": terms.bottleneck,
                "model_flops": terms.model_flops,
                "hlo_flops_global": terms.hlo_flops_global,
                "useful_ratio": terms.useful_ratio,
                "roofline_fraction": terms.roofline_fraction,
            },
        })
        hbm = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9
        print(f"[{arch} x {shape_name} x {mesh_name}] OK "
              f"compile={t_full:.0f}s total={time.time()-t0:.0f}s")
        print(f"  memory/device: args={mem.argument_size_in_bytes/1e9:.2f}GB "
              f"temp={mem.temp_size_in_bytes/1e9:.2f}GB (~{hbm:.1f}GB of 16GB"
              f" v5e HBM)")
        print(f"  per-device: flops={totals['flops']:.3e} "
              f"bytes={totals['bytes']:.3e} wire={totals['wire']:.3e}")
        print(f"  roofline: compute={terms.compute_s*1e3:.2f}ms "
              f"memory={terms.memory_s*1e3:.2f}ms "
              f"collective={terms.collective_s*1e3:.2f}ms "
              f"-> {terms.bottleneck}-bound useful={terms.useful_ratio:.3f} "
              f"frac={terms.roofline_fraction:.3f}")
    except Exception as e:  # noqa: BLE001 — record the failure verbatim
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
        print(f"[{arch} x {shape_name} x {mesh_name}] FAILED: {e}")
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-probes", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    if args.list:
        for a in archs:
            for s in shapes:
                ok, why = cell_is_runnable(a, s)
                print(f"{a:20s} {s:12s} {'RUN' if ok else 'SKIP: ' + why}")
        return

    failures = 0
    for a in archs:
        for s in shapes:
            for mp in meshes:
                rec = run_cell(a, s, mp, out_dir=args.out, force=args.force,
                               save_hlo=args.save_hlo,
                               skip_probes=args.skip_probes or mp)
                if rec["status"] == "error":
                    failures += 1
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
