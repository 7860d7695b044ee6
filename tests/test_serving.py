"""Integration tests for the serving runtime: end-to-end losslessness under
eviction + multi-segment recomputation, policy behaviour, adaptive
chunking, Continuum TTL pinning, and engine/kernel integration."""
import numpy as np
import jax
import pytest

from repro.configs import get_smoke_config, scaled_config
from repro.models import init_params
from repro.serving import (
    AgenticConfig,
    AsymCacheServer,
    EngineConfig,
    SchedulerConfig,
    ServerConfig,
    WorkloadConfig,
    agentic_workload,
    multi_turn_workload,
    reference_logits,
)
from repro.serving.server import DECODE_LOGITS_KEPT

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small_model():
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, KEY)
    return cfg, params


def _run(cfg, params, policy="asymcache", n_sessions=3, num_blocks=64,
         clock="wall", continuum=False, agentic=False, attn_impl="xla",
         seed=0, record_decode_logits=False, **wl_kw):
    if agentic:
        wl = agentic_workload(AgenticConfig(n_jobs=n_sessions, seed=seed))
    else:
        kw = dict(first_ctx_len=(96, 180), output_len=(12, 30), qps=1.0)
        kw.update(wl_kw)
        wl = multi_turn_workload(WorkloadConfig(
            n_sessions=n_sessions, turns_per_session=(2, 3), seed=seed, **kw))
    scfg = ServerConfig(
        policy=policy, num_blocks=num_blocks, block_size=16, clock=clock,
        continuum_ttl=continuum, record_decode_logits=record_decode_logits,
        attn_impl=attn_impl,
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    ecfg = EngineConfig(num_pages=num_blocks, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=8, attn_impl=attn_impl)
    srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
    res = srv.run(wl)
    return wl, res, srv


@pytest.mark.parametrize("policy", ["asymcache", "lru", "pensieve",
                                    "maxscore", "asymcache-on"])
def test_lossless_under_all_policies(small_model, policy):
    """THE core invariant: with eviction forcing multi-segment recompute,
    every prefill's final logits equal the dense no-cache reference."""
    cfg, params = small_model
    wl, res, srv = _run(cfg, params, policy=policy, num_blocks=56)
    assert res["n_requests"] == len(wl)
    for r in wl:
        ref = reference_logits(cfg, params, r.prompt_tokens)
        err = float(np.max(np.abs(ref - r.first_logits)))
        rel = err / max(1e-9, float(np.max(np.abs(ref))))
        assert rel < 2e-3, (policy, r.rid, rel)


def test_eviction_actually_happens(small_model):
    cfg, params = small_model
    _, res, srv = _run(cfg, params, num_blocks=48, n_sessions=4)
    assert res["evictions"] > 0
    assert res["block_hit_rate"] > 0


def test_multi_segment_hits_occur(small_model):
    """Under memory pressure AsymCache must produce non-prefix hit
    patterns (a hit segment after a gap) — the MSA case.  clock="model"
    keeps the eviction sequence (and thus the hit pattern) deterministic
    regardless of host speed."""
    cfg, params = small_model
    wl, res, srv = _run(cfg, params, num_blocks=40, n_sessions=4,
                        clock="model")
    multi_seg = sum(
        1 for r in wl
        if any(not h1 and h2 for h1, h2 in zip(r.hit_mask, r.hit_mask[1:])))
    assert multi_seg > 0, "no gap-then-hit (multi-segment) pattern generated"


def test_decode_logits_through_cache_match_reference(small_model):
    """Decode steps read their context from the paged pool (evicted and
    recomputed segments included); each recorded decode row must equal
    the dense reference's row at the same logical position."""
    cfg, params = small_model
    wl, res, srv = _run(cfg, params, num_blocks=48, n_sessions=4,
                        clock="model", record_decode_logits=True)
    assert res["evictions"] > 0
    rows = 0
    for r in wl:
        assert len(r.decode_logits) == min(DECODE_LOGITS_KEPT,
                                          len(r.output_script) - 1)
        toks = r.prompt_tokens + r.output_script
        ref = reference_logits(cfg, params, toks,
                               [p for p, _ in r.decode_logits])
        for (p, got), want in zip(r.decode_logits, ref):
            assert p >= r.prompt_len
            rel = float(np.max(np.abs(want - got))) / max(
                1e-9, float(np.max(np.abs(want))))
            assert rel < 2e-3, (r.rid, p, rel)
            rows += 1
    assert rows > 0


def test_planted_wrong_page_fails_the_check(small_model, monkeypatch):
    """The 2e-3 check sees one wrong block-table entry: when page 1 of
    every context reads page 2's K/V, each first-token and decode row
    whose context spans it leaves the tolerance."""
    from repro.serving.engine import Engine
    assemble = Engine._assemble_fused

    def one_wrong_page(self, plan, t_bucket, np_bucket):
        f = assemble(self, plan, t_bucket, np_bucket)
        spans = f["ctx"] > 2 * self.ecfg.page_size
        f["bt"][spans, 1] = f["bt"][spans, 2]
        return f

    monkeypatch.setattr(Engine, "_assemble_fused", one_wrong_page)
    cfg, params = small_model
    wl, res, srv = _run(cfg, params, num_blocks=48, n_sessions=4,
                        clock="model", record_decode_logits=True)
    errs = []
    for r in wl:
        rows = [(r.prompt_len - 1, r.first_logits)] + r.decode_logits
        toks = r.prompt_tokens + r.output_script
        ref = reference_logits(cfg, params, toks, [p for p, _ in rows])
        errs += [float(np.max(np.abs(want - got)))
                 / max(1e-9, float(np.max(np.abs(want))))
                 for (_, got), want in zip(rows, ref)]
    print(f"planted wrong page: rel err min {min(errs):.3e} "
          f"max {max(errs):.3e} over {len(errs)} rows")
    assert len(errs) > len(wl) and min(errs) > 2e-3


def test_pallas_impl_refuses_non_tpu_backend(small_model):
    """The compiled kernel is never replaced in silence by the
    interpreter or the oracle."""
    from repro.serving.engine import Engine
    cfg, params = small_model
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU backend compiles the kernel")
    with pytest.raises(ValueError, match="pallas"):
        Engine(cfg, EngineConfig(num_pages=8, attn_impl="pallas"), params)


def test_engine_with_pallas_interpret(small_model):
    """Full server loop through the Pallas kernels (interpret mode)."""
    cfg, params = small_model
    wl, res, srv = _run(cfg, params, n_sessions=1, attn_impl="pallas_interpret",
                        first_ctx_len=(48, 80), num_blocks=48)
    assert res["n_requests"] == len(wl)
    for r in wl:
        ref = reference_logits(cfg, params, r.prompt_tokens)
        rel = float(np.max(np.abs(ref - r.first_logits))) / max(
            1e-9, float(np.max(np.abs(ref))))
        assert rel < 2e-3, rel


def test_moe_engine_lossless():
    cfg = scaled_config(get_smoke_config("grok-1-314b"), dtype="float32")
    params = init_params(cfg, KEY)
    wl, res, srv = _run(cfg, params, n_sessions=2, num_blocks=56)
    for r in wl:
        ref = reference_logits(cfg, params, r.prompt_tokens)
        rel = float(np.max(np.abs(ref - r.first_logits))) / max(
            1e-9, float(np.max(np.abs(ref))))
        assert rel < 2e-3, rel


def test_sliding_window_engine_lossless():
    cfg = scaled_config(get_smoke_config("gemma3-12b"), dtype="float32")
    params = init_params(cfg, KEY)
    wl, res, srv = _run(cfg, params, n_sessions=2, num_blocks=64)
    for r in wl:
        ref = reference_logits(cfg, params, r.prompt_tokens)
        rel = float(np.max(np.abs(ref - r.first_logits))) / max(
            1e-9, float(np.max(np.abs(ref))))
        assert rel < 2e-3, rel


def test_model_clock_monotone(small_model):
    cfg, params = small_model
    _, res, _ = _run(cfg, params, clock="model", n_sessions=2)
    assert res["sim_time"] > 0
    assert np.isfinite(res["ttft_mean"])
    assert res["ttft_mean"] > 0


def test_adaptive_chunking_shrinks():
    from repro.core import (BlockManager, FreqParams, analytic_cost_model,
                            make_policy)
    from repro.configs import get_config
    from repro.serving.scheduler import ChunkingScheduler, SchedulerConfig
    fp = FreqParams.from_turning_point(10.0)
    bm = BlockManager(64, 16, make_policy("lru", fp),
                      analytic_cost_model(get_config("llama31-8b")), fp)
    sc = ChunkingScheduler(SchedulerConfig(max_chunk=128, min_chunk=16,
                                           decode_threshold=4), bm)
    assert sc._chunk_size(0, 1) == 128
    assert sc._chunk_size(20, 1) < 128
    assert sc._chunk_size(1000, 1) >= 16     # lower bound (§5.1)


def test_continuum_pinning_improves_agentic_hits(small_model):
    cfg, params = small_model
    _, res_plain, _ = _run(cfg, params, agentic=True, n_sessions=4,
                           num_blocks=192, policy="lru", continuum=False)
    _, res_ttl, _ = _run(cfg, params, agentic=True, n_sessions=4,
                         num_blocks=192, policy="lru", continuum=True)
    # TTL pinning must not lose requests and should not hurt hit rate
    assert res_ttl["n_requests"] == res_plain["n_requests"]
    assert res_ttl["block_hit_rate"] >= res_plain["block_hit_rate"] - 0.02


def test_asymcache_hits_trailing_blocks(small_model):
    """Position-aware eviction retains suffix blocks that LRU drops.
    clock="model" keeps the eviction sequence deterministic."""
    cfg, params = small_model
    wl_a, res_a, _ = _run(cfg, params, policy="asymcache", num_blocks=48,
                          n_sessions=4, seed=2, clock="model")
    # AsymCache suffix retention: some request has a hit AFTER a miss
    suffix_hits = sum(
        1 for r in wl_a
        if any(not h1 and h2 for h1, h2 in zip(r.hit_mask, r.hit_mask[1:])))
    assert suffix_hits > 0


def test_host_tier_offload_lossless(small_model):
    """Paper §7 (future work, implemented here): evicted blocks spill to a
    host tier and swap back in instead of recomputing — outputs must stay
    exact, and swap-ins must actually occur under memory pressure."""
    cfg, params = small_model
    wl = multi_turn_workload(WorkloadConfig(
        n_sessions=4, turns_per_session=(2, 3), first_ctx_len=(96, 200),
        output_len=(16, 40), qps=1.0, seed=0))
    scfg = ServerConfig(
        policy="asymcache", num_blocks=40, block_size=16, clock="wall",
        host_blocks=128,
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    srv = AsymCacheServer(cfg, params, scfg)
    res = srv.run(wl)
    assert res["swap_ins"] > 0 and res["swap_outs"] > 0
    for r in wl:
        ref = reference_logits(cfg, params, r.prompt_tokens)
        rel = float(np.max(np.abs(ref - r.first_logits))) / max(
            1e-9, float(np.max(np.abs(ref))))
        assert rel < 2e-3, rel


def test_host_tier_capacity_lru():
    """Host tier is bounded and evicts LRU."""
    from repro.core import (BlockManager, FreqParams, analytic_cost_model,
                            make_policy)
    from repro.configs import get_config
    fp = FreqParams.from_turning_point(10.0)
    bm = BlockManager(8, 4, make_policy("asymcache", fp),
                      analytic_cost_model(get_config("llama31-8b")), fp,
                      host_blocks=2)
    toks = list(range(32))  # 8 blocks
    hashes = bm.block_hashes(toks)
    slots = bm.allocate(8, now=1.0)
    for i, (s, h) in enumerate(zip(slots, hashes)):
        bm.commit(s, h, i)
    bm.release(slots, now=2.0)
    bm.allocate(8, now=3.0)          # evict all 8 -> host keeps last 2
    assert len(bm.host_tier) == 2
    m = bm.match(toks, now=4.0, acquire=False)
    assert sum(m.host_hits) == 2
