"""Sharded multi-device serving tests.

Host-side shard accounting (block manager striping, COW shard affinity)
runs in-process — it needs no devices.  Engine equivalence runs in
subprocesses with forced host device counts (``conftest.run_devices``)."""
import pytest

from conftest import run_devices
from repro.core import BlockManager, FreqParams, analytic_cost_model, \
    make_policy
from repro.configs import get_smoke_config, scaled_config


def _run_devices(code: str, n_devices: int = 4) -> str:
    return run_devices(code, n_devices)


def _mk_bm(num_blocks=32, n_shards=4):
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    freq = FreqParams.from_turning_point(30.0, 0.5, 40.0)
    return BlockManager(num_blocks, 16, make_policy("asymcache", freq),
                        analytic_cost_model(cfg), freq, n_shards=n_shards)


# ---------------------------------------------------------------------------
# Host-side shard accounting (no devices required)
# ---------------------------------------------------------------------------

def test_striped_allocation_balances_shards():
    bm = _mk_bm(num_blocks=32, n_shards=4)
    slots = bm.allocate(16, now=1.0)
    per = [0] * 4
    for s in slots:
        per[bm.shard_of(s)] += 1
    assert per == [4, 4, 4, 4], per
    # consecutive blocks of one allocation stripe across shards: no two
    # adjacent blocks land on the same shard while others have more room
    shards = [bm.shard_of(s) for s in slots[:4]]
    assert len(set(shards)) == 4, shards
    assert bm.per_shard_used() == [4, 4, 4, 4]


def test_per_shard_used_invariants():
    bm = _mk_bm(num_blocks=32, n_shards=4)
    a = bm.allocate(10, now=1.0)
    used = bm.per_shard_used()
    assert sum(used) == 10
    assert max(used) - min(used) <= 1          # striped start stays balanced
    bm.release(a[:5], now=2.0)                 # uncommitted -> back to free
    assert sum(bm.per_shard_used()) == 5
    # every slot maps to exactly one shard, consistent with the contiguous
    # run layout the page-axis sharding produces
    for s in range(32):
        assert bm.shard_of(s) == s // bm.shard_size


def test_allocation_prefers_most_free_shard():
    bm = _mk_bm(num_blocks=32, n_shards=4)
    a = bm.allocate(8, now=1.0)                # 2 per shard
    # free shard 2's blocks only
    sh2 = [s for s in a if bm.shard_of(s) == 2]
    bm.release(sh2, now=2.0)
    nxt = bm.allocate(2, now=3.0)
    assert all(bm.shard_of(s) == 2 for s in nxt), \
        (nxt, [bm.shard_of(s) for s in nxt])


def test_single_shard_keeps_legacy_order():
    """n_shards=1 must preserve the original pop-from-end determinism
    (existing tests and benchmarks depend on the exact slot sequence)."""
    bm = _mk_bm(num_blocks=8, n_shards=1)
    assert bm.allocate(3, now=1.0) == [0, 1, 2]


def test_cow_prefers_donor_shard():
    """The scheduler swaps a fresh COW destination onto the donor's shard
    so the fork stays a shard-local (in-step foldable) copy."""
    from repro.serving.request import Request
    from repro.serving.scheduler import ChunkingScheduler, SchedulerConfig

    bm = _mk_bm(num_blocks=32, n_shards=4)
    sched = ChunkingScheduler(SchedulerConfig(block_size=16), bm)
    req = Request(rid=0, session_id=0, arrival=0.0,
                  prompt_tokens=list(range(64)), output_script=[1, 2])
    # fresh allocation, deliberately NOT on the donor's shard at index 1
    req.block_slots = [0, 8, 16, 24]           # shards 0,1,2,3
    req.hit_mask = [False] * 4
    donor = 25                                 # shard 3
    sched._prefer_donor_shard(req, 1, donor, set(), n_prompt_blocks=4)
    assert bm.shard_of(req.block_slots[1]) == bm.shard_of(donor)
    assert sorted(req.block_slots) == [0, 8, 16, 24]   # a swap, not a leak


# ---------------------------------------------------------------------------
# Engine equivalence (subprocess, forced host devices)
# ---------------------------------------------------------------------------

_EQUIV = """
    import numpy as np, jax
    from repro.configs import get_smoke_config, scaled_config
    from repro.models import init_params
    from repro.serving import (AsymCacheServer, EngineConfig,
                               SchedulerConfig, ServerConfig,
                               AgenticConfig, agentic_workload)

    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def run(n_shards, depth):
        wl = agentic_workload(AgenticConfig(
            n_jobs=4, tool_calls_per_job=(2, 3), system_prefix_len=48,
            task_len=(70, 150), tool_result_len=(33, 80),
            output_len=(16, 28), tool_duration=(0.2, 0.8), qps=3.0, seed=7))
        scfg = ServerConfig(
            num_blocks=48, block_size=16, clock="model",
            pipeline_depth=depth, n_shards=n_shards, host_blocks=16,
            scheduler=SchedulerConfig(token_budget=128, max_chunk=48,
                                      max_prefills=2, max_decodes=8))
        ecfg = EngineConfig(num_pages=48, page_size=16, max_prefills=2,
                            max_chunk=48, max_decodes=8,
                            max_blocks_per_seq=24)
        srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
        return wl, srv.run(wl), srv

    w1, r1, s1 = run(1, 0)
    assert r1["evictions"] > 0            # the workload stresses the pool
    for n in (2, 4):
        for depth in (0, 1):
            wn, rn, sn = run(n, depth)
            assert rn["steps"] == r1["steps"], (n, depth)
            # pipeline depth 0: greedy-token-identical to single-device
            assert all(a.sampled_ids == b.sampled_ids
                       for a, b in zip(w1, wn)), (n, depth)
            assert all(a.generated == b.generated
                       for a, b in zip(w1, wn)), (n, depth)
            diff = max(float(np.max(np.abs(a.first_logits - b.first_logits)))
                       for a, b in zip(w1, wn))
            assert diff < 1e-4, (n, depth, diff)
            # per-shard page accounting invariants
            used = rn["per_shard_used"]
            assert len(used) == n and sum(used) >= 0
            assert all(0 <= u <= sn.bm.shard_size for u in used), used
            # compile-once-per-bucket survives shard_map
            assert sn.engine.jit_traces == len(sn.engine.buckets_used), \\
                (n, depth, sn.engine.jit_traces, sn.engine.buckets_used)
            # shared drain audit (inlined: conftest is not importable in
            # the forced-device subprocess)
            sn.bm.check_invariants()
            assert all(b.ref_count == 0 for b in sn.bm.blocks)
            assert not sn.bm.pending_copies
    s1.bm.check_invariants()
    assert all(b.ref_count == 0 for b in s1.bm.blocks)
    print("OK")
"""


@pytest.mark.slow
def test_sharded_engine_token_equivalence():
    """2- and 4-way sharded engines vs the single-device fused engine:
    identical greedy tokens (depth 0 and 1), first-token logits within f32
    merge epsilon, per-shard accounting sane, jit cache invariant holds."""
    out = _run_devices(_EQUIV, n_devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_attention_unit_equivalence():
    """Unit contract: per-shard partial + LSE merge == single-device
    fused oracle, for full-causal and sliding-window attention."""
    _run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_serving_mesh
        from repro.distributed.flash_decode import sharded_msa_fused
        from repro.kernels.msa.ref import msa_fused_ref, write_kv_pages

        rng = np.random.default_rng(0)
        Pg, page, KH, D, H, T, N, NP = 16, 4, 2, 8, 4, 12, 5, 6
        kp = jnp.asarray(rng.normal(size=(Pg, KH, page, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(Pg, KH, page, D)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(T, KH, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(T, KH, D)), jnp.float32)
        bt = jnp.asarray(rng.integers(0, Pg, size=(N, NP)), jnp.int32)
        ctx = jnp.asarray(rng.integers(1, NP * page, size=(N,)), jnp.int32)
        sid = jnp.asarray(rng.integers(0, N, size=(T,)), jnp.int32)
        pos = jnp.minimum(jnp.asarray(
            rng.integers(0, NP * page, size=(T,)), jnp.int32), ctx[sid] - 1)
        valid = jnp.asarray(rng.random(T) < 0.8)
        ws = jnp.asarray(rng.integers(0, Pg, size=(T,)), jnp.int32)
        wo = jnp.asarray(rng.integers(0, page, size=(T,)), jnp.int32)

        kp1, vp1 = write_kv_pages(kp, vp, kn, vn, ws, wo, valid)
        for window, softcap in ((0, 0.0), (7, 5.0)):
            ref = msa_fused_ref(q, kp1, vp1, bt, ctx, pos, sid, valid,
                                window=window, softcap=softcap)
            for n in (2, 4):
                mesh = make_serving_mesh(n)
                sh = NamedSharding(mesh, P("model", None, None, None))
                kps, vps = jax.device_put(kp, sh), jax.device_put(vp, sh)
                kp2, vp2, attn = jax.jit(
                    lambda a, b: sharded_msa_fused(
                        q, a, b, kn, vn, ws, wo, valid, bt, ctx, pos, sid,
                        mesh=mesh, window=window, softcap=softcap))(kps, vps)
                assert float(jnp.max(jnp.abs(kp2 - kp1))) == 0.0
                assert float(jnp.max(jnp.abs(vp2 - vp1))) == 0.0
                err = float(jnp.max(jnp.abs(attn - ref)))
                assert err < 1e-5, (n, window, err)
        print("OK")
    """)


@pytest.mark.slow
def test_sharded_collectives_present():
    """The compiled sharded step must contain the LSE-merge collectives;
    the single-device step must contain none (deterministic HLO counts)."""
    _run_devices("""
        import jax
        from repro.configs import get_smoke_config, scaled_config
        from repro.models import init_params
        from repro.serving import (AsymCacheServer, EngineConfig,
                                   SchedulerConfig, ServerConfig)

        cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        def mk(n):
            scfg = ServerConfig(num_blocks=32, block_size=16, clock="model",
                                n_shards=n,
                                scheduler=SchedulerConfig(
                                    token_budget=64, max_chunk=32,
                                    max_prefills=2, max_decodes=4))
            ecfg = EngineConfig(num_pages=32, page_size=16, max_prefills=2,
                                max_chunk=32, max_decodes=4,
                                max_blocks_per_seq=16)
            return AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
        coll1 = mk(1).engine.collective_counts()
        coll2 = mk(2).engine.collective_counts()
        assert sum(coll1.values()) == 0, coll1
        # at least one all-reduce per layer (the 2-term psum of the merge)
        assert coll2.get("all-reduce", 0) >= cfg.n_layers, coll2
        print("OK", coll1, coll2)
    """)
