#!/usr/bin/env python3
"""Smoke run of the served path on a TPU v5e.  One run, not a measurement.

One chip (default): Llama-3.1-8B at its published widths (d_model 4096,
32/8 heads, head_dim 128, d_ff 14336, vocab 128256) in bf16 with seeded
random weights, depth cut to 16 of 32 layers (~9.1 GB of weights), served
end to end by ``AsymCacheServer.run`` through the compiled Pallas MSA
kernel (``attn_impl="pallas"``).  The paged KV pool holds 2048 blocks of
16 tokens (1 MiB each at 16 layers, 2 GiB in all).  Two-turn sessions
with 1-2k-token prompts overflow it, so blocks are evicted and second
turns recompute the evicted parts of their context (multi-segment
recompute).  Prefill and decode logits are compared with the float32
dense reference (``reference_logits``).

Four chips (``--chips 4``): only the sequence-sharded engine
(``ServerConfig.n_shards=4``) at full depth (32 layers, ~16 GB of bf16
weights, more than one chip holds) and the dense reference with the
weights on the same four chips.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # four TPU chips

Without a TPU it exits nonzero and prints no result.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0

# Largest accepted max|served - reference| / max|reference| over one
# logits row.  The served path keeps bf16 activations and a bf16 KV pool
# through 2 x depth residual sublayers; the reference is float32 at
# highest matmul precision over the same bf16 weights.  On a TPU v5e that
# rounding alone read at most 1.63e-2 here (16 layers) and 1.98e-2 with
# --chips 4 (32 layers).  One wrong block-table entry (page 1 of each
# context reading page 2's K/V) planted in the one-chip served path read
# 9.1e-2 to 1.5e-1 on the decode rows when only decode reads were wrong,
# and 6.4e-1 to 9.1e-1 on every row when every read was.  4e-2 sits about
# a factor of two from each side.
LOGITS_TOL = 4e-2


def one_chip_setup(cfg):
    """Server configuration and traffic of the one-chip phase."""
    from repro.serving import (EngineConfig, SchedulerConfig, ServerConfig,
                               WorkloadConfig, multi_turn_workload)
    ecfg = EngineConfig(
        num_pages=2048, page_size=16, max_prefills=2, max_chunk=1024,
        max_decodes=16, max_blocks_per_seq=128,   # contexts up to 2048
        token_buckets=(16,), np_buckets=(128,))
    scfg = ServerConfig(
        num_blocks=ecfg.num_pages, block_size=ecfg.page_size, clock="model",
        record_decode_logits=True,
        scheduler=SchedulerConfig(
            token_budget=2048, max_chunk=ecfg.max_chunk,
            max_prefills=ecfg.max_prefills, max_decodes=ecfg.max_decodes))
    # 24 two-turn sessions: first turns fill the 32k-token pool, second
    # turns arrive after it has overflowed
    wl = multi_turn_workload(WorkloadConfig(
        n_sessions=24, turns_per_session=(2, 2), system_prefix_len=64,
        first_ctx_len=(896, 1408), user_len=(32, 96), output_len=(32, 128),
        vocab=cfg.vocab_size, qps=8.0, intra_ratio=20.0, seed=SEED))
    return ecfg, scfg, wl


def four_chip_setup(cfg):
    """Server configuration and traffic of the four-chip phase.  The
    sharded engine's attention is the XLA partial, whose per-shard gather
    grows with tokens x context: the token buckets stay small."""
    from repro.serving import (EngineConfig, SchedulerConfig, ServerConfig,
                               WorkloadConfig, multi_turn_workload)
    ecfg = EngineConfig(
        num_pages=512, page_size=16, max_prefills=1, max_chunk=64,
        max_decodes=8, max_blocks_per_seq=64,     # contexts up to 1024
        attn_impl="xla", token_buckets=(8,), np_buckets=(64,))
    scfg = ServerConfig(
        num_blocks=ecfg.num_pages, block_size=ecfg.page_size, clock="model",
        n_shards=4, record_decode_logits=True,
        scheduler=SchedulerConfig(
            token_budget=64, max_chunk=ecfg.max_chunk,
            max_prefills=ecfg.max_prefills, max_decodes=ecfg.max_decodes))
    wl = multi_turn_workload(WorkloadConfig(
        n_sessions=3, turns_per_session=(2, 2), system_prefix_len=64,
        first_ctx_len=(256, 512), user_len=(16, 48), output_len=(16, 32),
        vocab=cfg.vocab_size, qps=2.0, seed=SEED))
    return ecfg, scfg, wl


def init_weights(cfg, out_shardings=None):
    """Seeded random weights, made on the device in one jitted program
    (no float32 copy of the whole model is ever materialized)."""
    import jax
    from repro.models import init_params
    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    init = jax.jit(init_params, static_argnums=0, **kw)
    return jax.block_until_ready(init(cfg, jax.random.PRNGKey(SEED)))


def serve(cfg, params, ecfg, scfg, wl, cost_model=None):
    from repro.serving import AsymCacheServer
    srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg,
                          cost_model=cost_model)
    t0 = time.perf_counter()
    res = srv.run(wl)
    return srv, res, time.perf_counter() - t0


def context_stats(wl, block):
    """Recompute accounting per session: a turn's prompt begins with the
    previous turn's prompt and output, whose full blocks were computed
    and committed then.  Computing a position of those blocks again is a
    recompute of an evicted block; a compute list of several runs means
    the context was served as several cached and recomputed segments."""
    import numpy as np
    done = {}
    recomputed = multi_segment = 0
    for r in sorted(wl, key=lambda r: r.rid):
        before = done.get(r.session_id, 0)
        cl = np.asarray(r.compute_list)
        recomputed += int(np.sum(cl < (before - 1) // block * block))
        # cached blocks before, between or after the computed runs
        gaps = int(np.sum(np.diff(cl) > 1)) if cl.size else 0
        if cl.size and (gaps or (0 < cl[0] and cl[0] < (before - 1) // block
                                 * block)):
            multi_segment += 1
        done[r.session_id] = r.prompt_len + len(r.output_script)
    return recomputed, multi_segment


def logits_errors(cfg, params, requests):
    """max|served - reference| / max|reference| per compared row, split
    into the prefill's last row and decode rows through the cache."""
    import numpy as np
    from repro.serving import reference_logits
    pre, dec = [], []
    for r in requests:
        toks = list(r.prompt_tokens) + list(r.output_script)
        rows = [(r.prompt_len - 1, r.first_logits, pre)]
        rows += [(p, lg, dec) for p, lg in r.decode_logits]
        ref = reference_logits(cfg, params, toks, [p for p, _, _ in rows])
        for (_, got, out), want in zip(rows, ref):
            got = np.asarray(got, np.float32)
            out.append(float(np.max(np.abs(got - want)))
                       / max(float(np.max(np.abs(want))), 1e-9))
    return pre, dec


def pick_checked(wl, n):
    """Requests whose logits are checked: the ones with the most
    recomputed segments first, then in arrival order."""
    import numpy as np
    runs = lambda r: int(np.sum(np.diff(np.asarray(r.compute_list)) > 1))
    return sorted(wl, key=lambda r: (-runs(r), r.rid))[:n]


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


def report_serving(res, srv, wl, serve_s):
    finished = sum(1 for r in wl if r.status == "finished")
    print(f"requests: {len(wl)} sent, {finished} finished, "
          f"{res['n_failed']} failed, {res['n_rejected']} rejected")
    recomputed, multi = context_stats(wl, srv.scfg.block_size)
    print(f"evictions: {res['evictions']}  recomputed tokens: {recomputed}  "
          f"requests served as several segments: {multi}  "
          f"prefill compute tokens: {res['prefill_compute_tokens']} of "
          f"{res['prompt_tokens']} prompt tokens")
    print(f"engine steps: {res['steps']}  step variants compiled "
          f"(jit_traces): {srv.engine.jit_traces}  buckets (t, np, w, k): "
          f"{sorted(srv.engine.buckets_used)}")
    print(f"serve wall seconds (compiles included, one run): "
          f"{serve_s:.1f}")
    return finished == len(wl) and not res["n_failed"] \
        and not res["n_rejected"]


def check_errors(pre, dec):
    print(f"logits vs float32 reference, max |err| / max |ref|: prefill "
          f"{max(pre):.3e} over {len(pre)} rows, decode {max(dec):.3e} over "
          f"{len(dec)} rows (tolerance {LOGITS_TOL:g})")
    return bool(pre) and bool(dec) and max(pre + dec) <= LOGITS_TOL


def phase_one_chip(clock):
    import jax
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("llama31-8b"), n_layers=16)
    ecfg, scfg, wl = one_chip_setup(cfg)
    ecfg = dataclasses.replace(ecfg, attn_impl="pallas")
    scfg = dataclasses.replace(scfg, attn_impl="pallas")
    print(f"config: {cfg.name} d_model {cfg.d_model} heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads} head_dim {cfg.head_dim} d_ff {cfg.d_ff} vocab "
          f"{cfg.vocab_size} {cfg.dtype}, depth {cfg.n_layers} of 32 layers, "
          f"seeded random weights")
    params = init_weights(cfg)
    block_bytes = (2 * cfg.n_layers * cfg.n_kv_heads * ecfg.page_size
                   * cfg.head_dim * 2)                  # K and V, bf16
    print(f"attn_impl: {ecfg.attn_impl}  KV pool: {ecfg.num_pages} blocks x "
          f"{ecfg.page_size} tokens, "
          f"{block_bytes * ecfg.num_pages / 2**30:.2f} GiB  "
          f"token buckets {ecfg.token_buckets} np buckets {ecfg.np_buckets}")
    srv, res, serve_s = serve(cfg, params, ecfg, scfg, wl)
    ok = report_serving(res, srv, wl, serve_s)
    ok &= res["evictions"] > 0
    t_b, np_b, w_b, _ = min(srv.engine.buckets_used)
    hlo = srv.engine.compiled_step(t_b, np_b, w_b).as_text()
    kernel = "tpu_custom_call" in hlo
    print(f"served step (t {t_b}, np {np_b}, w {w_b}) HLO contains "
          f"tpu_custom_call: {kernel}")
    ok &= kernel
    pre, dec = logits_errors(cfg, params, pick_checked(wl, 8))
    ok &= check_errors(pre, dec)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    print(f"backend compiles: {clock.n}, {clock.secs:.1f} s (one run)")
    return ok


def phase_four_chips(clock):
    import jax
    from repro.configs import get_config
    from repro.distributed.sharding import serving_param_shardings
    from repro.launch.mesh import make_serving_mesh
    cfg = get_config("llama31-8b")
    ecfg, scfg, wl = four_chip_setup(cfg)
    print(f"config: {cfg.name} published widths, {cfg.dtype}, full depth "
          f"{cfg.n_layers} layers, seeded random weights, 4-way "
          f"sequence-sharded engine")
    _, param_sh = serving_param_shardings(cfg, make_serving_mesh(4))
    params = init_weights(cfg, out_shardings=param_sh)
    t_max = ecfg.max_prefills * ecfg.max_chunk + ecfg.max_decodes
    s_ctx = ecfg.max_blocks_per_seq * ecfg.page_size
    gather = t_max * s_ctx * cfg.n_kv_heads * cfg.head_dim * (2 + 4) * 2
    print(f"attn_impl: xla — the sharded engine runs the XLA partial "
          f"(msa_fused_partial_ref) per shard plus the LSE merge, not the "
          f"Pallas kernel.  Its per-shard K/V gather at the largest bucket "
          f"(T {t_max} x {s_ctx} context tokens x {cfg.n_kv_heads} heads x "
          f"{cfg.head_dim}, bf16 + f32 copies, K and V): {gather} bytes "
          f"per layer")
    srv, res, serve_s = serve(cfg, params, ecfg, scfg, wl)
    ok = report_serving(res, srv, wl, serve_s)
    eng = srv.engine
    compiled = eng.compiled_step(t_max, ecfg.max_blocks_per_seq)
    arg_shardings = compiled.input_shardings[0]
    pool_sh = arg_shardings[1]
    leaves = jax.tree_util.tree_leaves(arg_shardings[0])
    split = sum(not s.is_fully_replicated for s in leaves)
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    total = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        (eng.params, eng.k_pools, eng.v_pools)))
    print(f"compiled step: pool sharding {pool_sh.spec} over "
          f"{len(pool_sh.device_set)} devices; {split} of {len(leaves)} "
          f"weight arrays split over the mesh (the rest are norms); "
          f"argument bytes per device {per_dev} of {total} in all")
    for d in jax.devices()[:4]:
        print(f"device {d.id}: bytes_in_use "
              f"{(d.memory_stats() or {}).get('bytes_in_use', 'n/a')}")
    ok &= pool_sh.spec[1] == "model" and len(pool_sh.device_set) == 4
    ok &= per_dev < 0.5 * total
    coll = eng.collective_counts(t_max, ecfg.max_blocks_per_seq)
    print(f"collectives in the compiled step: {coll}")
    pre, dec = logits_errors(cfg, eng.params, pick_checked(wl, 6))
    ok &= check_errors(pre, dec)
    print(f"backend compiles: {clock.n}, {clock.secs:.1f} s (one run)")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devs)} device(s) of platform {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(f"chip_smoke: single smoke run on {args.chips} of {len(devs)} "
          f"{devs[0].device_kind} chip(s); every time below is from this "
          f"one run, not a measurement")
    clock = CompileClock()
    ok = phase_four_chips(clock) if args.chips == 4 else \
        phase_one_chip(clock)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
