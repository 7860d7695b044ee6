"""Property sweep over the §5.1 chunk decision + multi-token k gating.

Invariants (ISSUE 6):
  * the adaptive chunk size always lands in ``[min_chunk, max_chunk]``;
  * ``StepPlan.total_tokens`` (per-iteration token-stream width) never
    exceeds the selected ``t_bucket``;
  * a multi-token ``k > 1`` plan is never emitted while a prefill chunk
    is admissible (any running request still prefilling);
  * a ``k > 1`` plan is never emitted while a swap-in or COW page op is
    queued (block-manager ``pending_copies`` or the engine's pending
    queues via ``pending_ops_fn``).

Hypothesis drives the pure chunk-size function when installed
(``tests/_hypothesis_compat.py`` turns the sweep into a skip on a bare
interpreter); the plan-level invariants are checked deterministically by
recording every plan of simulated closed-loop runs, so they hold in CI
with or without hypothesis.
"""
import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core import H20, analytic_cost_model
from repro.serving import (
    AsymCacheServer,
    FrontendConfig,
    OnlineFrontend,
    SchedulerConfig,
    ServerConfig,
    StressConfig,
    control_plane_stress_scripts,
    decode_burst_workload,
)
from repro.serving.request import RequestState

BLOCK = 16


def _sim_server(max_decode_steps: int, num_blocks: int = 1024,
                **sched_kw) -> AsymCacheServer:
    cfg = get_config("llama31-8b")
    cm = analytic_cost_model(cfg, H20)
    kw = dict(token_budget=256, max_chunk=96, min_chunk=16, max_prefills=4,
              max_decodes=16, max_running=16,
              max_decode_steps=max_decode_steps)
    kw.update(sched_kw)
    scfg = ServerConfig(
        policy="asymcache", num_blocks=num_blocks, block_size=BLOCK,
        clock="model", execute_model=False, host_blocks=num_blocks // 2,
        scheduler=SchedulerConfig(**kw))
    return AsymCacheServer(cfg, None, scfg, cost_model=cm, sim_cost_model=cm)


def _record_plans(srv):
    """Wrap the scheduler so every emitted plan is captured alongside the
    page-op queue state observed at emission time."""
    plans = []
    orig = srv.sched.schedule

    def recording(now):
        plan = orig(now)
        plans.append((
            plan,
            bool(srv.bm.pending_copies),
            [r.state for r in srv.sched.running],
        ))
        return plan

    srv.sched.schedule = recording
    return plans


def _check_plan_invariants(plans, cfg: SchedulerConfig):
    assert plans, "run emitted no plans"
    saw_k = False
    for plan, had_pending_copies, running_states in plans:
        if plan.empty():
            continue
        # chunk emission never exceeds the §5.1 upper bound
        for ch in plan.prefills:
            assert 0 < len(ch.positions) <= cfg.max_chunk
        # the per-iteration token width always fits the chosen bucket
        if plan.t_bucket is not None:
            assert plan.total_tokens <= plan.t_bucket
        if plan.decode_steps > 1:
            saw_k = True
            # never alongside admissible prefill work
            assert not plan.prefills
            assert all(s is RequestState.DECODE for s in running_states)
            # never with a queued COW fork
            assert not had_pending_copies
            # k is a power of two within the configured cap, and every
            # rider consumes 1..k iterations (max rider defines k)
            k = plan.decode_steps
            assert 1 < k <= cfg.max_decode_steps
            assert k & (k - 1) == 0
            assert len(plan.decode_iters) == len(plan.decodes)
            assert all(1 <= it <= k for it in plan.decode_iters)
            assert max(plan.decode_iters) == k
            assert plan.emitted_tokens == sum(plan.decode_iters)
    return saw_k


# ---------------------------------------------------------------------------
# chunk-size bounds: deterministic sweep + hypothesis property
# ---------------------------------------------------------------------------

def _chunk_cfg(max_chunk, min_chunk, decode_threshold):
    sched = _sim_server(1).sched
    sched.cfg.max_chunk = max_chunk
    sched.cfg.min_chunk = min_chunk
    sched.cfg.decode_threshold = decode_threshold
    return sched


def test_chunk_size_bounds_sweep():
    sched = _chunk_cfg(max_chunk=128, min_chunk=16, decode_threshold=8)
    for n_decodes in range(0, 64):
        for n_prefills in range(0, 6):
            size = sched._chunk_size(n_decodes, n_prefills)
            assert sched.cfg.min_chunk <= size <= sched.cfg.max_chunk


@settings(max_examples=200, deadline=None)
@given(max_chunk=st.integers(min_value=16, max_value=4096),
       min_chunk=st.integers(min_value=1, max_value=16),
       decode_threshold=st.integers(min_value=1, max_value=64),
       n_decodes=st.integers(min_value=0, max_value=512),
       n_prefills=st.integers(min_value=0, max_value=16))
def test_chunk_size_bounds_property(max_chunk, min_chunk, decode_threshold,
                                    n_decodes, n_prefills):
    sched = _chunk_cfg(max_chunk, min_chunk, decode_threshold)
    size = sched._chunk_size(n_decodes, n_prefills)
    assert min_chunk <= size <= max_chunk


# ---------------------------------------------------------------------------
# plan-level invariants over whole simulated runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("max_decode_steps", [1, 2, 8])
def test_plan_invariants_closed_loop(max_decode_steps):
    """Every plan of a closed-loop stress run (prefetch pins, swap-ins,
    COW sharing, multi-token decode all active) satisfies the §5.1 and
    k-gating invariants."""
    srv = _sim_server(max_decode_steps, num_blocks=512)
    plans = _record_plans(srv)
    scripts = control_plane_stress_scripts(StressConfig(n_sessions=48,
                                                        seed=2))
    OnlineFrontend(srv, scripts,
                   FrontendConfig(prefetch=True, prefetch_lead=0.5)).run()
    saw_k = _check_plan_invariants(plans, srv.sched.cfg)
    assert saw_k == (max_decode_steps > 1), \
        "decode-dominated phases must emit k>1 exactly when enabled"


def test_plan_invariants_decode_burst():
    """All-at-once burst: prefill and decode phases interleave sharply,
    so k>1 must appear only after the last prefill chunk drains."""
    srv = _sim_server(8)
    plans = _record_plans(srv)
    srv.run(decode_burst_workload(n_requests=8, seed=1))
    assert _check_plan_invariants(plans, srv.sched.cfg)


# ---------------------------------------------------------------------------
# k gating against queued page ops (direct unit checks)
# ---------------------------------------------------------------------------

def _decode_only_state(srv):
    """Drive a burst until the scheduler reaches a decode-only state."""
    from repro.serving import ScriptedSource
    src = ScriptedSource(decode_burst_workload(n_requests=4, seed=3))
    for req in src.pop_due(0.0):
        srv._on_arrival(req)
    for _ in range(64):
        plan = srv.sched.schedule(srv.now)
        assert not plan.empty()
        if not plan.prefills and all(
                r.state is RequestState.DECODE for r in srv.sched.running):
            return plan
        srv.engine.dispatch(plan)
        srv.now += srv._step_latency(plan)
        srv._postprocess(plan)
    raise AssertionError("never reached a decode-only step")


def test_k_suppressed_by_pending_copies():
    srv = _sim_server(8)
    plan = _decode_only_state(srv)
    assert plan.decode_steps > 1          # sanity: k fires when clean
    # roll the plan back and re-schedule with a queued COW copy
    srv.bm.pending_copies.append((0, 1))
    replay = srv.sched.schedule(srv.now)
    assert replay.decode_steps == 1 and not replay.decode_iters
    srv.bm.pending_copies.clear()


def test_k_suppressed_by_pending_engine_ops():
    srv = _sim_server(8)
    plan = _decode_only_state(srv)
    assert plan.decode_steps > 1
    srv.sched.pending_ops_fn = lambda: True   # engine swap/copy queued
    replay = srv.sched.schedule(srv.now)
    assert replay.decode_steps == 1 and not replay.decode_iters
    srv.sched.pending_ops_fn = None
    again = srv.sched.schedule(srv.now)
    assert again.decode_steps > 1


# ---------------------------------------------------------------------------
# bucket fill: a step with prefill pays for its whole token bucket, so the
# bucket's rows beyond the §5.1 plan go to its prefill chunks
# ---------------------------------------------------------------------------

def _fill_sched(token_buckets=(), page_buckets=(), **kw):
    """A scheduler over hand-built running requests.  Planning reads no
    block-manager state when nothing waits and no prefill-free plan asks
    for k > 1, so no block manager is needed."""
    from repro.serving.scheduler import ChunkingScheduler
    cfg = dict(token_budget=2048, max_chunk=1024, min_chunk=16,
               max_prefills=2, max_decodes=16, max_running=64)
    cfg.update(kw)
    return ChunkingScheduler(SchedulerConfig(
        token_buckets=tuple(token_buckets), page_buckets=tuple(page_buckets),
        **cfg), None)


def _running(sched, n_decodes, compute_lists, ctx=64):
    from repro.serving import Request
    for i in range(n_decodes):
        r = Request(rid=i, session_id=i, prompt_tokens=[1] * ctx,
                    output_script=[1] * 8, arrival=0.0)
        r.state = RequestState.DECODE
        sched.running.append(r)
    for j, cl in enumerate(compute_lists):
        cl = np.asarray(cl, np.int32)
        r = Request(rid=n_decodes + j, session_id=n_decodes + j,
                    prompt_tokens=[1] * (int(cl[-1]) + 1),
                    output_script=[1] * 8, arrival=0.0)
        r.state = RequestState.PREFILL
        r.compute_list = cl
        sched.running.append(r)
    return sched


def test_bucket_fill_chatglm_lattice():
    """Lattice [16, 2064] at chatglm's token budget: 16 decodes and two
    long prompts fill the step to 2048 rows (1024 + 1008 prefill), where
    the §5.1 shrink alone plans 170 + 170 in the same 2064-row bucket."""
    sched = _running(_fill_sched((16, 2064), (512,)), 16,
                     [np.arange(3000), np.arange(2500)])
    plan = sched.schedule(0.0)
    assert [len(c.positions) for c in plan.prefills] == [1024, 1008]
    assert sum(len(c.positions) for c in plan.prefills) == 2048 - 16
    assert plan.t_bucket == 2064 and plan.np_bucket == 512
    assert sched.perf_counters() == {"bucket_fill_steps": 1,
                                     "bucket_fill_tokens": 2032 - 340}
    # the chunks are the leading runs of the compute lists
    for ch in plan.prefills:
        assert np.array_equal(ch.positions, np.arange(len(ch.positions)))
        assert ch.req.compute_ptr == len(ch.positions)
        assert not ch.completes_prefill


def test_bucket_fill_stops_at_the_smaller_bucket():
    """Lattice [16, 512, 2064]: the shrunk step (16 + 340 rows) lands in
    512, and the fill takes it to 512, never to 2064."""
    sched = _running(_fill_sched((16, 512, 2064), (512,)), 16,
                     [np.arange(3000), np.arange(2500)])
    plan = sched.schedule(0.0)
    assert plan.t_bucket == 512 and plan.n_compute_tokens == 512
    # the first chunk takes the 156 rows the two 170-token chunks left
    assert [len(c.positions) for c in plan.prefills] == [170 + 156, 170]


def test_bucket_fill_off_without_a_lattice():
    """With no token lattice the plan is the §5.1 one: each of two
    prompts gets ``_chunk_size(16, 2)`` tokens and no bucket is chosen."""
    sched = _running(_fill_sched(), 16, [np.arange(3000), np.arange(2500)])
    chunk = sched._chunk_size(16, 2)
    plan = sched.schedule(0.0)
    assert chunk == 1024 // 6
    assert [len(c.positions) for c in plan.prefills] == [chunk, chunk]
    assert plan.t_bucket is None and plan.np_bucket is None
    assert sched.perf_counters() == {"bucket_fill_steps": 0,
                                     "bucket_fill_tokens": 0}


def test_bucket_fill_completes_a_short_prompt_and_flows_on():
    """What one chunk cannot take flows on to the next prefill, and a
    chunk that reaches the end of its compute list completes its prefill."""
    sched = _running(_fill_sched((16, 2064), (512,)), 16,
                     [np.arange(300), np.arange(4000)])
    plan = sched.schedule(0.0)
    assert [len(c.positions) for c in plan.prefills] == [300, 1024]
    assert plan.prefills[0].completes_prefill
    assert not plan.prefills[1].completes_prefill


def test_bucket_fill_keeps_the_page_bucket():
    """A chunk is never lengthened past the step's page bucket: a prompt
    whose compute list runs past 32 pages stops at position 511."""
    sched = _running(_fill_sched((16, 2064), (32, 64)), 16,
                     [np.arange(1500)])
    plan = sched.schedule(0.0)
    assert plan.np_bucket == 32
    assert int(plan.prefills[0].positions[-1]) == 32 * BLOCK - 1


def _plan_without_fill(sched):
    sched._fill_bucket = lambda plan: None
    return sched.schedule(0.0)


def _check_fill(buckets, page_buckets, n_decodes, lists, budget, max_chunk,
                min_chunk):
    kw = dict(token_budget=budget, max_chunk=max_chunk, min_chunk=min_chunk)
    base = _plan_without_fill(_running(
        _fill_sched(buckets, page_buckets, **kw), n_decodes, lists))
    sched = _running(_fill_sched(buckets, page_buckets, **kw), n_decodes,
                     lists)
    plan = sched.schedule(0.0)
    assert plan.t_bucket == base.t_bucket
    assert plan.np_bucket == base.np_bucket
    assert len(plan.prefills) == len(base.prefills)
    assert len(plan.decodes) == len(base.decodes)
    assert plan.n_compute_tokens <= max(budget, base.n_compute_tokens)
    if plan.n_compute_tokens > base.n_compute_tokens:
        assert plan.n_compute_tokens <= min(plan.t_bucket, budget)
    added = 0
    for ch, b in zip(plan.prefills, base.prefills):
        cl = ch.req.compute_list
        n = len(ch.positions)
        assert len(b.positions) <= n <= max_chunk
        assert n <= len(cl)
        assert np.array_equal(ch.positions, cl[:n])
        assert ch.req.compute_ptr == n
        assert ch.completes_prefill == (n == len(cl))
        added += n - len(b.positions)
    pc = sched.perf_counters()
    assert pc["bucket_fill_tokens"] == added
    assert pc["bucket_fill_steps"] == int(added > 0)


def _random_case(rng):
    lattice = sorted(rng.sample(range(8, 2200, 8), rng.randint(1, 4)))
    pages = sorted(rng.sample(range(1, 300), rng.randint(1, 3)))
    lists = []
    for _ in range(rng.randint(1, 3)):
        n = rng.randint(1, 3000)
        keep = [p for p in range(n + rng.randint(0, 800))
                if rng.random() < 0.8][:n] or [0]
        lists.append(np.asarray(keep, np.int32))
    return (lattice, pages, rng.randint(0, 16), lists,
            rng.randint(64, 2048), rng.randint(16, 1024), rng.randint(1, 16))


def test_bucket_fill_invariants_sweep():
    """Seeded sweep of the fill's invariants: t_bucket and np_bucket
    stay, chunks stay within max_chunk, the token budget and their
    compute lists, and the counters count exactly the tokens added."""
    import random
    rng = random.Random(20)
    for _ in range(300):
        _check_fill(*_random_case(rng))


@settings(max_examples=150, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2 ** 32 - 1))
def test_bucket_fill_invariants_property(seed):
    import random
    _check_fill(*_random_case(random.Random(seed)))
