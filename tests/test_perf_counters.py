"""Schema freeze for the deterministic counter surfaces the benchmark
gates read (`Engine.perf_counters()`, `collective_counts()`, the sim
engine's counter parity, and `BlockManager.control_plane_counts()`).

A renamed or dropped key would silently turn a benchmark gate vacuous —
these tests pin the key sets, the monotonicity of the cumulative
counters, and the reset semantics (accounting zeroes; the jit-cache
invariant state survives).
"""
import jax
import pytest

from repro.configs import get_config, get_smoke_config, scaled_config
from repro.core import H20, analytic_cost_model
from repro.models import init_params
from repro.serving import (
    AsymCacheServer,
    EngineConfig,
    SchedulerConfig,
    ServerConfig,
    decode_burst_workload,
)
from repro.serving.server import _SimEngine

BLOCK = 16

# frozen key set of Engine.perf_counters() — additions are fine but must
# be added HERE too; renames/removals break benchmark gates
ENGINE_COUNTER_KEYS = frozenset({
    "attn_dispatches",
    "attn_dispatches_per_step",
    "padded_token_fraction",
    "bucket_counts",
    "instep_copies",
    "eager_copies",
    "instep_swaps",
    "eager_swaps",
    "swap_bytes_shipped",
    "kv_pages_written",
    "attn_items",
    "attn_narrow_items",
    "engine_dispatches",
    "decode_only_dispatches",
    "decode_tokens_emitted",
    "multi_token_dispatches",
    "multi_token_iterations",
    "multi_token_rollbacks",
    "k_counts",
    "kv_bytes_per_page",
    "layers_by_kind",
})

# cumulative integer counters that must never decrease across dispatches
MONOTONIC_KEYS = (
    "attn_dispatches",
    "engine_dispatches",
    "decode_only_dispatches",
    "decode_tokens_emitted",
    "multi_token_dispatches",
    "multi_token_iterations",
    "multi_token_rollbacks",
    "instep_copies",
    "eager_copies",
    "instep_swaps",
    "eager_swaps",
    "swap_bytes_shipped",
    "kv_pages_written",
    "attn_items",
    "attn_narrow_items",
)

# the sim engine mirrors this subset so stress-benchmark gates read the
# same names from either engine
SIM_ENGINE_KEYS = frozenset({
    "engine_dispatches",
    "decode_only_dispatches",
    "decode_tokens_emitted",
    "multi_token_dispatches",
    "multi_token_iterations",
    "multi_token_rollbacks",
    "k_counts",
})

# frozen key set of ChunkingScheduler.perf_counters() — the bucket fill's
# accounting, merged with the engine's into every server result
SCHEDULER_COUNTER_KEYS = frozenset({
    "bucket_fill_steps",
    "bucket_fill_tokens",
})

CONTROL_PLANE_KEYS = frozenset({
    "treap_ops",
    "evictor_adds",
    "evictor_removes",
    "evictor_evicts",
    "evictor_reranks",
    "trie_nodes_visited",
    "pin_heap_ops",
})

# frozen key set of BlockManager.counters() — the asymmetric-offload
# accounting serve() merges verbatim into every result dict, and what
# benchmarks/offload.py's bytes-moved gates read
BM_COUNTER_KEYS = frozenset({
    "swap_ins",
    "swap_outs",
    "evictions",
    "bytes_swapped_in_k",
    "bytes_swapped_in_v",
    "bytes_swapped_out_k",
    "bytes_swapped_out_v",
    "host_resident_bytes",
    "host_entries",
    "n_host_evictions",
    "n_host_half_drops",
    "clean_half_spills",
    "v_half_streams",
    "k_early_prefetches",
    "pending_purges",
})

# frozen key set of PrefixStore.counters() — the content-addressed
# store/tenancy accounting serve() merges into every result (zeros when
# the store is disabled), read by benchmarks/prefix_store.py's gates
STORE_COUNTER_KEYS = frozenset({
    "store_entries",
    "store_bytes",
    "store_puts",
    "store_hits",
    "store_misses",
    "store_evictions",
    "store_expired",
    "store_restored",
    "store_corrupt_drops",
    "store_fingerprint_drops",
    "store_quota_rejects",
    "store_preflight_reports",
    "store_preflight_dup_blocks",
    "store_preflight_holds",
    "tenant_count",
    "tenant_quota_evictions",
    "tenant_shed_ownerships",
})


@pytest.fixture(scope="module")
def served():
    """One real-engine burst served with multi-token dispatch enabled:
    counters before (mid-run snapshots) and after."""
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServerConfig(
        policy="asymcache", num_blocks=256, block_size=BLOCK, clock="model",
        scheduler=SchedulerConfig(token_budget=160, max_chunk=96,
                                  max_prefills=2, max_decodes=8,
                                  max_decode_steps=4))
    ecfg = EngineConfig(num_pages=256, page_size=BLOCK, max_prefills=2,
                        max_chunk=96, max_decodes=8, max_blocks_per_seq=32)
    srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)

    snapshots = []
    orig = srv.engine.dispatch

    def snapping(plan):
        handle = orig(plan)
        snapshots.append(srv.engine.perf_counters())
        return handle

    srv.engine.dispatch = snapping
    srv.run(decode_burst_workload(n_requests=6, seed=4))
    return srv, snapshots


def test_engine_counter_schema(served):
    srv, snapshots = served
    pc = srv.engine.perf_counters()
    assert set(pc) == ENGINE_COUNTER_KEYS
    for key in MONOTONIC_KEYS:
        assert isinstance(pc[key], int) and pc[key] >= 0
    assert isinstance(pc["bucket_counts"], dict)
    assert isinstance(pc["k_counts"], dict)
    # the run exercised the multi-token path, so its counters are live
    assert pc["multi_token_dispatches"] > 0
    assert pc["multi_token_iterations"] > pc["multi_token_dispatches"]
    assert pc["decode_only_dispatches"] > 0
    # every dispatch writes the KV rows of its tokens: one page at least
    assert pc["engine_dispatches"] <= pc["kv_pages_written"]
    assert all(k.startswith("k") for k in pc["k_counts"])


def test_engine_counters_monotonic(served):
    _, snapshots = served
    assert len(snapshots) >= 2
    for a, b in zip(snapshots, snapshots[1:]):
        for key in MONOTONIC_KEYS:
            assert b[key] >= a[key], f"{key} decreased mid-run"


def test_reset_semantics(served):
    srv, _ = served
    eng = srv.engine
    traces, buckets = eng.jit_traces, set(eng.buckets_used)
    assert traces == len(buckets) > 0
    eng.reset_perf_counters()
    pc = eng.perf_counters()
    for key in MONOTONIC_KEYS:
        assert pc[key] == 0, f"{key} survived reset"
    assert pc["bucket_counts"] == {} and pc["k_counts"] == {}
    # jit-cache state spans the engine lifetime: NOT reset
    assert eng.jit_traces == traces
    assert set(eng.buckets_used) == buckets
    # multi-token bucket keys carry k as the 4th component
    assert all(len(b) == 4 for b in buckets)
    assert any(b[3] > 1 for b in buckets)


def test_collective_counts_schema(served):
    srv, _ = served
    traces = srv.engine.jit_traces
    coll = srv.engine.collective_counts()
    assert isinstance(coll, dict)
    assert all(isinstance(v, int) and v >= 0 for v in coll.values())
    # lowering a variant for inspection must not count as a trace
    assert srv.engine.jit_traces == traces


def test_sim_engine_counter_parity():
    eng = _SimEngine(SchedulerConfig())
    pc = eng.perf_counters()
    assert set(pc) == SIM_ENGINE_KEYS
    assert SIM_ENGINE_KEYS <= ENGINE_COUNTER_KEYS


def test_scheduler_counter_schema(served):
    """The scheduler's bucket-fill counters are frozen and reach the
    server's counters beside the engine's.  The served burst never has
    more decode rows than the §5.1 threshold, so no chunk was shrunk and
    the fill added nothing."""
    srv, _ = served
    pc = srv.sched.perf_counters()
    assert set(pc) == SCHEDULER_COUNTER_KEYS
    assert pc == {"bucket_fill_steps": 0, "bucket_fill_tokens": 0}
    assert set(srv.perf_counters()) == \
        ENGINE_COUNTER_KEYS | SCHEDULER_COUNTER_KEYS


@pytest.mark.parametrize("lattice", [(16, 144), ()])
def test_bucket_fill_counters_under_decode_pressure(lattice):
    """A served burst with 16 decode rows beside long prompts: on a token
    lattice the fill lengthens the shrunk chunks and counts it; with no
    lattice the counters stay 0."""
    cfg = get_config("llama31-8b")
    cm = analytic_cost_model(cfg, H20)
    scfg = ServerConfig(
        policy="asymcache", num_blocks=512, block_size=BLOCK,
        clock="model", execute_model=False,
        scheduler=SchedulerConfig(token_budget=144, max_chunk=64,
                                  max_prefills=2, max_decodes=16,
                                  token_buckets=lattice))
    sim = AsymCacheServer(cfg, None, scfg, cost_model=cm, sim_cost_model=cm)
    res = sim.run(decode_burst_workload(n_requests=24, prompt_len=(200, 400),
                                        output_len=(40, 60), seed=6))
    assert SCHEDULER_COUNTER_KEYS <= set(res)
    steps, tokens = res["bucket_fill_steps"], res["bucket_fill_tokens"]
    if lattice:
        assert 0 < steps <= tokens
    else:
        assert steps == tokens == 0


def test_bm_counter_schema_and_server_result(served):
    """BlockManager.counters() keys are frozen, and every server result —
    host tier on or off — carries them (zeros, never missing), so the
    offload benchmark's counter gates can't silently go vacuous."""
    srv, _ = served
    bc = srv.bm.counters()
    assert set(bc) == BM_COUNTER_KEYS
    for key in BM_COUNTER_KEYS:
        assert isinstance(bc[key], int) and bc[key] >= 0, key

    cfg = get_config("llama31-8b")
    cm = analytic_cost_model(cfg, H20)
    for host_blocks in (0, 64):
        scfg = ServerConfig(
            policy="asymcache", num_blocks=128, block_size=BLOCK,
            clock="model", execute_model=False, host_blocks=host_blocks,
            scheduler=SchedulerConfig(token_budget=256, max_chunk=96,
                                      max_prefills=2, max_decodes=8))
        sim = AsymCacheServer(cfg, None, scfg, cost_model=cm,
                              sim_cost_model=cm)
        res = sim.run(decode_burst_workload(n_requests=6, seed=5))
        assert BM_COUNTER_KEYS <= set(res)
        if host_blocks == 0:
            assert res["bytes_swapped_out_k"] == 0
            assert res["host_entries"] == 0


def test_store_counter_schema_and_server_result():
    """PrefixStore.counters() keys are frozen, and every server result —
    store enabled or disabled — carries them (zeros, never missing), so
    the prefix-store benchmark's gates can't silently go vacuous."""
    from repro.core import PrefixStoreConfig
    cfg = get_config("llama31-8b")
    cm = analytic_cost_model(cfg, H20)
    for pscfg in (None, PrefixStoreConfig(capacity_bytes=1 << 20,
                                          tenant_quota_bytes=1 << 18)):
        scfg = ServerConfig(
            policy="asymcache", num_blocks=128, block_size=BLOCK,
            clock="model", execute_model=False, prefix_store=pscfg,
            scheduler=SchedulerConfig(token_budget=256, max_chunk=96,
                                      max_prefills=2, max_decodes=8))
        sim = AsymCacheServer(cfg, None, scfg, cost_model=cm,
                              sim_cost_model=cm)
        sc = sim.store.counters()
        assert set(sc) == STORE_COUNTER_KEYS
        res = sim.run(decode_burst_workload(n_requests=6, seed=5))
        assert STORE_COUNTER_KEYS <= set(res)
        for key in STORE_COUNTER_KEYS:
            assert isinstance(res[key], int) and res[key] >= 0, key
        if pscfg is None:
            assert all(res[k] == 0 for k in STORE_COUNTER_KEYS)


def test_control_plane_counts_schema():
    cfg = get_config("llama31-8b")
    cm = analytic_cost_model(cfg, H20)
    scfg = ServerConfig(
        policy="asymcache", num_blocks=512, block_size=BLOCK,
        clock="model", execute_model=False,
        scheduler=SchedulerConfig(token_budget=256, max_chunk=96,
                                  max_prefills=2, max_decodes=8))
    srv = AsymCacheServer(cfg, None, scfg, cost_model=cm, sim_cost_model=cm)
    before = srv.bm.control_plane_counts()
    assert set(before) == CONTROL_PLANE_KEYS
    res = srv.run(decode_burst_workload(n_requests=6, seed=5))
    after = srv.bm.control_plane_counts()
    assert set(after) == CONTROL_PLANE_KEYS
    for key in CONTROL_PLANE_KEYS:
        assert isinstance(after[key], int)
        assert after[key] >= before[key]
    assert after["treap_ops"] > 0 and after["evictor_adds"] > 0
    # serve() merges the same keys into its summary for the benchmark
    assert CONTROL_PLANE_KEYS <= set(res)
