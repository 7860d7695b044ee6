"""The serve loop's tracer (``repro.serving.tracing``): phases that tile
``serve()``, one ring record per dispatched step, ``control_plane_time``
read from the same marks, the compile flag, the spans in a profiler
trace, and the jitted step's named scopes in its HLO."""
import glob

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
)
from repro.serving import tracing
from repro.serving.tracing import (ASSEMBLE, LAUNCH, PHASES, POLL,
                                   POSTPROCESS, RETIRE, SCHEDULE, WAIT,
                                   ServeTracer)

SCOPES = ("page_ops", "embed", "qkv", "kv_write", "attention", "mlp",
          "logits")


@pytest.fixture(scope="module")
def small_model():
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _server(cfg, params, depth=1, attn_impl="xla"):
    scfg = ServerConfig(
        num_blocks=64, block_size=16, clock="wall", pipeline_depth=depth,
        attn_impl=attn_impl,
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    ecfg = EngineConfig(num_pages=64, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=8, attn_impl=attn_impl)
    return AsymCacheServer(cfg, params, scfg, ecfg=ecfg)


def _wl(n_sessions=3, seed=0, **kw):
    base = dict(first_ctx_len=(96, 180), output_len=(12, 30), qps=1.0,
                turns_per_session=(2, 3))
    base.update(kw)
    return multi_turn_workload(WorkloadConfig(n_sessions=n_sessions,
                                              seed=seed, **base))


@pytest.fixture(scope="module")
def served(small_model):
    cfg, params = small_model
    srv = _server(cfg, params)
    return srv, srv.run(_wl())


def _fake_step(tr, now, variant=(16, 1, 0, 1), compiled=False):
    for i in (POLL, SCHEDULE, ASSEMBLE, LAUNCH):
        tr.phase(i)
    tr.launched(variant, compiled)
    tr.phase(POSTPROCESS)
    tr.dispatched(now, 3, 2)
    tr.phase(WAIT)
    tr.phase(RETIRE)


def test_ring_keeps_the_last_steps_and_filters_by_server_clock():
    tr = ServeTracer()
    tr.start()
    n = tracing.RING_STEPS + 5
    for k in range(n):
        _fake_step(tr, float(k), compiled=k == 0)
    tr.stop()
    assert tr.n_steps == n and len(tr.ring) == tracing.RING_STEPS
    recs = tr.steps()
    assert [r.step for r in recs] == list(range(6, n + 1))
    assert [r.now for r in tr.steps(10.0, 13.0)] == [10.0, 11.0, 12.0]
    last = recs[-1].as_dict()
    assert set(last["phases"]) == set(PHASES)
    assert (last["prefill_tokens"], last["decode_rows"],
            last["t_bucket"]) == (3, 2, 16)
    assert not any(r.compiled for r in recs)     # step 1 fell out
    assert tr.calls[POLL] == n and tr.calls[LAUNCH] == n


def test_phases_tile_serve(served):
    srv, res = served
    total = sum(srv.tracer.totals)
    assert total == pytest.approx(res["wall_time"], rel=0.01)
    assert all(t >= 0 for t in srv.tracer.totals)
    assert srv.tracer.totals[LAUNCH] > 0 and srv.tracer.totals[WAIT] > 0


def test_one_record_per_dispatched_step(served):
    srv, res = served
    recs = srv.tracer.steps()
    assert res["steps"] > 10
    assert len(recs) == res["steps"] == srv.engine.steps_executed
    assert [r.step for r in recs] == list(range(1, res["steps"] + 1))
    assert sum(r.prefill_tokens + r.decode_rows for r in recs) \
        == srv.engine.valid_token_rows
    assert {r.variant for r in recs} == srv.engine.buckets_used
    assert all(r.interval_s > 0 for r in recs)
    assert sum(r.interval_s for r in recs) <= res["wall_time"]
    # the compile flag: one flagged dispatch per traced step variant
    assert sum(r.compiled for r in recs) == srv.engine.jit_traces \
        == len(srv.engine.buckets_used)


def test_control_plane_time_is_schedule_plus_assembly(served):
    srv, res = served
    tr = srv.tracer
    want = tr.totals[SCHEDULE] + tr.totals[ASSEMBLE]
    assert srv.control_plane_time == want
    assert res["control_plane_time"] == want > 0
    totals = tr.phase_totals()
    assert totals["schedule"]["calls"] >= res["steps"]
    assert totals["assemble"]["calls"] == res["steps"]


def test_synchronous_loop_times_the_wait(small_model):
    cfg, params = small_model
    srv = _server(cfg, params, depth=0)
    res = srv.run(_wl(n_sessions=1, turns_per_session=(1, 1)))
    tr = srv.tracer
    assert sum(tr.totals) == pytest.approx(res["wall_time"], rel=0.01)
    # each step is waited for once before its postprocess, once at retire
    assert tr.calls[WAIT] == 2 * res["steps"]
    assert len(tr.steps()) == res["steps"]


def test_spans_carry_step_ids_in_a_profiler_trace(small_model, tmp_path):
    from jax.profiler import ProfileData
    cfg, params = small_model
    srv = _server(cfg, params, attn_impl="pallas_interpret")
    wl = _wl(n_sessions=1, turns_per_session=(1, 1),
             first_ctx_len=(20, 24), output_len=(3, 3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = srv.run(wl)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.setdefault(e.name, []).append(dict(e.stats))
    n = res["steps"]
    assert n >= 3
    for name in tracing.SPAN_NAMES:
        assert name in spans, name
        assert all(1 <= st["step"] <= n for st in spans[name])
    assert sorted(st["step"] for st in spans["serve.launch"]) == \
        list(range(1, n + 1))
    assert sorted(st["step_num"] for st in spans["serve.step"]) == \
        list(range(1, n + 1))
    flagged = [st["step"] for st in spans["serve.launch"]
               if st.get("compiled") == 1]
    assert flagged == [r.step for r in srv.tracer.steps() if r.compiled]
    assert len(flagged) == srv.engine.jit_traces
    assert len(spans[tracing.WORKLIST_SPAN]) == n
    assert srv.tracer.worklist_calls == n
    assert 0 < srv.tracer.worklist_s < srv.tracer.totals[ASSEMBLE]


def test_records_count_the_narrow_items(small_model):
    """Each step's record counts its work-list items of one-token runs:
    some on every step that carries a decode row, all of them on a
    decode-only step; the engine's counters sum the records."""
    cfg, params = small_model
    srv = _server(cfg, params, attn_impl="pallas_interpret")
    srv.run(_wl(n_sessions=2, turns_per_session=(1, 1),
                first_ctx_len=(20, 40), output_len=(4, 6)))
    recs = srv.tracer.steps()
    assert any(r.prefill_tokens and r.decode_rows for r in recs)
    assert any(r.decode_rows and not r.prefill_tokens for r in recs)
    for r in recs:
        assert 0 <= r.wl_narrow_items <= r.wl_items
        if r.decode_rows:
            assert r.wl_narrow_items > 0, r
        if not r.prefill_tokens:
            assert r.wl_narrow_items == r.wl_items, r
    pc = srv.engine.perf_counters()
    assert pc["attn_items"] == sum(r.wl_items for r in recs)
    assert pc["attn_narrow_items"] == sum(r.wl_narrow_items for r in recs)


def test_step_hlo_names_every_scope(served):
    srv, _ = served
    eng = srv.engine
    text = eng.compiled_step(eng.token_buckets[0], eng.np_buckets[0]).as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope
