"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the AsymCache serving stack either for real (reduced model) or in
discrete-event mode at full scale.  ``--attn-impl pallas`` serves through
the compiled TPU kernel (a TPU backend is required; the XLA oracle is the
CPU default).

``--devices N`` serves sharded: KV page pools sequence-shard over an
N-way mesh with the flash-decode LSE merge (docs/ARCHITECTURE.md
§Sharded serving).  On CPU the device count must be forced before jax
initializes, which is why it is peeked from argv below.
"""
import argparse
import os
import sys

def _peek_devices(argv):
    """Pre-argparse peek at --devices (both "--devices N" and
    "--devices=N" forms); malformed values are left for argparse to
    reject with a proper usage error."""
    for i, tok in enumerate(argv):
        if tok == "--devices" and i + 1 < len(argv):
            val = argv[i + 1]
        elif tok.startswith("--devices="):
            val = tok.split("=", 1)[1]
        else:
            continue
        return val if val.isdigit() and int(val) >= 1 else None
    return None


_n = _peek_devices(sys.argv)  # must precede the first jax import
if _n is not None:
    _flag = f"--xla_force_host_platform_device_count={_n}"
    if _flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import jax

from repro.configs import ARCH_IDS, get_config, get_smoke_config, scaled_config
from repro.core import TPU_V5E, analytic_cost_model
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import (
    AsymCacheServer,
    SchedulerConfig,
    ServerConfig,
    WorkloadConfig,
    multi_turn_workload,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama31-8b",
                    choices=list(ARCH_IDS) + ["llama31-8b", "llama31-70b"])
    ap.add_argument("--policy", default="asymcache")
    ap.add_argument("--mode", default="real",
                    choices=["real", "sim", "online"])
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--host-blocks", type=int, default=32,
                    help="host-tier blocks for online mode (0 = off)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="online mode: disable predictive host-tier "
                         "prefetch of suspended sessions")
    ap.add_argument("--attn-impl", default="xla",
                    choices=["xla", "pallas", "pallas_interpret"])
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the engine over N devices (real mode; on "
                         "CPU forces N host devices before jax init)")
    args = ap.parse_args()
    if args.devices < 1:
        ap.error(f"--devices must be >= 1, got {args.devices}")
    enable_compile_cache()

    if args.mode == "online":
        # closed-loop agent serving: sessions suspend on tool calls, the
        # lifespan predictor prefetches their KV ahead of the resume
        from repro.serving import (AgenticConfig, FrontendConfig,
                                   OnlineFrontend, agentic_session_scripts)
        cfg = scaled_config(get_smoke_config(args.arch), dtype="float32")
        assert cfg.family in ("dense", "moe"), \
            f"{args.arch}: engine serves token LMs (DESIGN.md §5)"
        params = init_params(cfg, jax.random.PRNGKey(0))
        scripts = agentic_session_scripts(AgenticConfig(
            n_jobs=args.sessions, tool_calls_per_job=(2, 4),
            system_prefix_len=32, task_len=(32, 64),
            tool_result_len=(16, 48), output_len=(12, 24),
            tool_duration=(0.6, 1.5), qps=1.5))
        srv = AsymCacheServer(cfg, params, ServerConfig(
            policy=args.policy, num_blocks=args.blocks, block_size=16,
            clock="model", host_blocks=args.host_blocks,
            attn_impl=args.attn_impl,
            scheduler=SchedulerConfig(token_budget=160, max_chunk=96,
                                      max_prefills=2, max_decodes=8)))
        fe = OnlineFrontend(srv, scripts,
                            FrontendConfig(prefetch=not args.no_prefetch))
        res = fe.run()
        for k, v in res.items():
            print(f"{k}: {v:.4f}" if isinstance(v, float) else f"{k}: {v}")
        return
    if args.mode == "real":
        cfg = scaled_config(get_smoke_config(args.arch), dtype="float32")
        assert cfg.family in ("dense", "moe"), \
            f"{args.arch}: engine serves token LMs (DESIGN.md §5)"
        params = init_params(cfg, jax.random.PRNGKey(0))
        wl = multi_turn_workload(WorkloadConfig(
            n_sessions=args.sessions, first_ctx_len=(96, 200),
            output_len=(16, 40), qps=1.0))
        # shard-divisible pool, never rounded down to zero (at least one
        # page per shard)
        n_dev = max(args.devices, 1)
        blocks = max(n_dev, args.blocks - args.blocks % n_dev)
        if blocks != args.blocks:
            print(f"note: --blocks {args.blocks} adjusted to {blocks} "
                  f"(pool must divide across {n_dev} devices)")
        srv = AsymCacheServer(cfg, params, ServerConfig(
            policy=args.policy, num_blocks=blocks, block_size=16,
            clock="wall", n_shards=args.devices, attn_impl=args.attn_impl,
            scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                      max_prefills=2, max_decodes=8)))
    else:
        cfg = get_config(args.arch)
        cm = analytic_cost_model(cfg, TPU_V5E, n_chips=256)
        wl = multi_turn_workload(WorkloadConfig(
            n_sessions=args.sessions, first_ctx_len=(8_000, 24_000),
            output_len=(400, 1200), vocab=min(cfg.vocab_size, 50_000),
            qps=0.05))
        srv = AsymCacheServer(cfg, None, ServerConfig(
            policy=args.policy, num_blocks=args.blocks * 512, block_size=16,
            clock="model", execute_model=False,
            scheduler=SchedulerConfig(token_budget=4096, max_chunk=2048,
                                      max_prefills=4, max_decodes=64)),
            cost_model=cm, sim_cost_model=cm)
    res = srv.run(wl)
    for k, v in res.items():
        print(f"{k}: {v:.4f}" if isinstance(v, float) else f"{k}: {v}")


if __name__ == "__main__":
    main()
