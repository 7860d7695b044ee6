"""Overlapped execution pipeline: compile-once invariant, pipelined-vs-
synchronous equivalence, and in-step page-op folding (COW copies +
host-tier swap-ins)."""
import numpy as np
import jax
import pytest

from repro.configs import get_smoke_config, scaled_config
from repro.models import init_params
from repro.serving import (
    AsymCacheServer,
    EngineConfig,
    SchedulerConfig,
    ServerConfig,
    WorkloadConfig,
    multi_turn_workload,
    reference_logits,
)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small_model():
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, KEY)
    return cfg, params


def _mk_server(cfg, params, depth, num_blocks=64, host_blocks=0,
               **ecfg_kw):
    scfg = ServerConfig(
        policy="asymcache", num_blocks=num_blocks, block_size=16,
        clock="model", pipeline_depth=depth, host_blocks=host_blocks,
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    ecfg = EngineConfig(num_pages=num_blocks, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=8, **ecfg_kw)
    return AsymCacheServer(cfg, params, scfg, ecfg=ecfg)


def _wl(n_sessions=3, seed=0, **kw):
    base = dict(first_ctx_len=(96, 180), output_len=(12, 30), qps=1.0)
    base.update(kw)
    return multi_turn_workload(WorkloadConfig(
        n_sessions=n_sessions, turns_per_session=(2, 3), seed=seed, **base))


def test_step_compiles_exactly_once(small_model):
    """The static-bucket invariant the pipeline depends on: across a
    multi-step run mixing prefill chunks (several per prefill: prompts >
    max_chunk) and decodes, the jitted step traces exactly once PER
    occupancy bucket used (the compile-once-per-bucket cache)."""
    cfg, params = small_model
    srv = _mk_server(cfg, params, depth=1)
    res = srv.run(_wl(n_sessions=3, first_ctx_len=(100, 180)))
    assert res["steps"] > 10
    assert srv.engine.steps_executed == res["steps"]
    used = len(srv.engine.buckets_used)
    assert 1 <= used <= (len(srv.engine.token_buckets)
                         * len(srv.engine.np_buckets))
    assert srv.engine.jit_traces == used, (
        srv.engine.jit_traces, sorted(srv.engine.buckets_used))
    # a second identical run re-uses every per-bucket compilation
    srv.run(_wl(n_sessions=3, first_ctx_len=(100, 180)))
    assert srv.engine.jit_traces == used


def test_pipelined_matches_synchronous(small_model):
    """Identical generated tokens, device-side samples, and byte-identical
    first-token logits between pipeline_depth=0 and pipeline_depth=1."""
    cfg, params = small_model
    srv0 = _mk_server(cfg, params, depth=0)
    srv1 = _mk_server(cfg, params, depth=1)
    wl0, wl1 = _wl(seed=3), _wl(seed=3)
    r0, r1 = srv0.run(wl0), srv1.run(wl1)
    assert r0["steps"] == r1["steps"]
    for a, b in zip(wl0, wl1):
        assert a.generated == b.generated
        assert a.sampled_ids == b.sampled_ids and a.sampled_ids
        assert np.array_equal(a.first_logits, b.first_logits)


def test_host_tier_swaps_fold_into_step(small_model):
    """Losslessness with swap-ins routed through the in-step scatter AND
    the eager overflow fallback (bucket smaller than the swap bursts)."""
    cfg, params = small_model
    wl = multi_turn_workload(WorkloadConfig(
        n_sessions=4, turns_per_session=(2, 3), first_ctx_len=(96, 200),
        output_len=(16, 40), qps=1.0, seed=0))
    srv = _mk_server(cfg, params, depth=1, num_blocks=40, host_blocks=128,
                     max_instep_swaps=2)
    res = srv.run(wl)
    assert res["swap_ins"] > 0 and res["swap_outs"] > 0
    for r in wl:
        ref = reference_logits(cfg, params, r.prompt_tokens)
        rel = float(np.max(np.abs(ref - r.first_logits))) / max(
            1e-9, float(np.max(np.abs(ref))))
        assert rel < 2e-3, rel


def test_cow_copies_fold_into_step(small_model):
    """COW forks through the in-step copy path give byte-identical logits
    to the eager fallback path (bucket 0)."""
    from repro.serving import Request
    cfg, params = small_model
    prefix = [7] * 100
    mk = lambda: [
        Request(rid=0, session_id=0, prompt_tokens=prefix + [11] * 40,
                output_script=[3, 4, 5], arrival=0.0),
        Request(rid=1, session_id=1, prompt_tokens=prefix + [13] * 40,
                output_script=[6, 7, 8], arrival=10.0),
    ]
    runs = {}
    for copies in (8, 0):
        wl = mk()
        srv = _mk_server(cfg, params, depth=1, num_blocks=64,
                         max_instep_copies=copies)
        srv.run(wl)
        assert wl[1].n_cow_forks == 1
        runs[copies] = wl
    for a, b in zip(runs[8], runs[0]):
        assert np.array_equal(a.first_logits, b.first_logits)


def test_chunk_size_folds_prefill_count():
    """§5.1 shrink formula divides the per-request chunk by the number of
    co-scheduled prefills (total prefill tokens per step stay bounded)."""
    from repro.core import (BlockManager, FreqParams, analytic_cost_model,
                            make_policy)
    from repro.configs import get_config
    from repro.serving.scheduler import ChunkingScheduler, SchedulerConfig
    fp = FreqParams.from_turning_point(10.0)
    bm = BlockManager(64, 16, make_policy("lru", fp),
                      analytic_cost_model(get_config("llama31-8b")), fp)
    sc = ChunkingScheduler(SchedulerConfig(max_chunk=128, min_chunk=16,
                                           decode_threshold=4), bm)
    # no decode pressure: prefill count does not shrink chunks
    assert sc._chunk_size(0, 4) == 128
    # under decode pressure, more co-scheduled prefills -> smaller chunks
    assert sc._chunk_size(8, 2) < sc._chunk_size(8, 1)
    assert sc._chunk_size(8, 1) == sc._chunk_size(8, 0)
    assert sc._chunk_size(1000, 4) >= 16      # §5.1 floor holds
