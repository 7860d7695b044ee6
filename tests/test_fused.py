"""Fused varlen mixed-batch MSA: kernel-vs-oracle property sweeps, the
bitwise fused-vs-two-dispatch contract, the prefill-kernel q-row masking
regression, ragged-QP round-up, and the occupancy-bucket engine
invariants (compile-once-per-bucket, dispatch/padded-token accounting)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.msa import (WL_FIELDS, build_worklist, msa_fused,
                               msa_prefill)
from repro.kernels.msa import ref as msa_ref

KEY = jax.random.PRNGKey(0)


def _rand(shape, k, dtype=jnp.float32):
    return jax.random.normal(k, shape, jnp.float32).astype(dtype)


def _varlen_case(rng, *, n_pre, n_dec, page, NP, P, H, KH, D, max_run=14):
    """Random mixed varlen batch: ragged multi-segment prefill runs plus
    decode rows, flattened into one (T, H, D) stream."""
    n = n_pre + n_dec
    q_lens, q_pos, ctx = [], [], []
    for _ in range(n_pre):
        c = rng.randint(4, NP * page)
        ln = rng.randint(1, min(max_run, c) + 1)
        # multi-segment gaps: any sorted subset of [0, c), forced to end
        # at the sampling position c-1 like the scheduler does
        pos = np.sort(rng.choice(c, size=ln, replace=False))
        pos[-1] = c - 1
        pos = np.unique(pos)
        q_lens.append(len(pos))
        q_pos.append(pos)
        ctx.append(c)
    for _ in range(n_dec):
        c = rng.randint(1, NP * page)
        q_lens.append(1)
        q_pos.append(np.asarray([c - 1]))
        ctx.append(c)
    T = int(np.sum(q_lens))
    q_start = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    seq_ids = np.repeat(np.arange(n, dtype=np.int32),
                        np.asarray(q_lens, np.int64))
    ks = jax.random.split(jax.random.PRNGKey(rng.randint(1 << 30)), 3)
    return dict(
        q=_rand((T, H, D), ks[0]),
        k_pages=_rand((P, KH, page, D), ks[1]),
        v_pages=_rand((P, KH, page, D), ks[2]),
        bt=jnp.asarray(rng.randint(0, P, (n, NP)), jnp.int32),
        ctx=jnp.asarray(ctx, jnp.int32),
        q_pos=jnp.asarray(np.concatenate(q_pos), jnp.int32),
        seq_ids=jnp.asarray(seq_ids),
        valid=jnp.ones((T,), bool),
        q_start=jnp.asarray(q_start),
        q_len=jnp.asarray(q_lens, jnp.int32),
        n=n, T=T)


def _worklist_for(case, *, page, q_tile, window):
    TQ = min(q_tile, case["T"])
    n_tiles = -(-case["T"] // TQ)
    wl, _ = build_worklist(
        np.asarray(case["q_start"]), np.asarray(case["q_len"]),
        np.asarray(case["ctx"]), np.asarray(case["bt"]),
        np.asarray(case["q_pos"]), page=page, q_tile=TQ,
        n_tiles=n_tiles, window=window)
    return tuple(jnp.asarray(wl[f]) for f in WL_FIELDS)


# ---------------------------------------------------------------------------
# fused oracle == the two split oracles, bitwise (the engine's byte-identity
# acceptance gate rests on this)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), window=st.sampled_from([0, 11]),
       softcap=st.sampled_from([0.0, 25.0]))
def test_fused_ref_bitwise_matches_split_refs(seed, window, softcap):
    rng = np.random.RandomState(seed)
    page, NP, P, H, KH, D = 8, 5, 24, 4, 2, 16
    c = _varlen_case(rng, n_pre=2, n_dec=3, page=page, NP=NP, P=P,
                     H=H, KH=KH, D=D)
    o = msa_fused(c["q"], c["k_pages"], c["v_pages"], c["bt"], c["ctx"],
                  c["q_pos"], c["seq_ids"], c["valid"],
                  window=window, softcap=softcap, impl="xla")
    # per-sequence split-oracle calls over the same rows
    sid = np.asarray(c["seq_ids"])
    for s in range(c["n"]):
        rows = np.nonzero(sid == s)[0]
        qs = c["q"][rows][None]                       # (1, L, H, D)
        ps = c["q_pos"][rows][None]
        want = msa_ref.msa_prefill_ref(
            qs, c["k_pages"], c["v_pages"], c["bt"][s][None],
            c["ctx"][s][None], ps,
            jnp.asarray([len(rows)], jnp.int32),
            window=window, softcap=softcap)[0]
        assert np.array_equal(np.asarray(o[rows]), np.asarray(want)), s
    # decode rows additionally match the decode oracle bitwise
    dec = np.nonzero(np.asarray(c["q_len"]) == 1)[0]
    if dec.size:
        rows = np.asarray([np.nonzero(sid == s)[0][0] for s in dec])
        od = msa_ref.msa_decode_ref(
            c["q"][rows], c["k_pages"], c["v_pages"], c["bt"][dec],
            c["ctx"][dec], window=window, softcap=softcap)
        assert np.array_equal(np.asarray(o[rows]), np.asarray(od))


# ---------------------------------------------------------------------------
# fused Pallas kernel (interpret) vs the varlen oracle: property sweep over
# ragged runs, GQA groups, window, softcap, multi-segment gaps, tile sizes
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       h_kh=st.sampled_from([(4, 2), (4, 4), (8, 1)]),
       window=st.sampled_from([0, 9]),
       softcap=st.sampled_from([0.0, 20.0]),
       q_tile=st.sampled_from([4, 8, 16]))
def test_fused_kernel_property_sweep(seed, h_kh, window, softcap, q_tile):
    rng = np.random.RandomState(seed)
    H, KH = h_kh
    page, NP, P, D = 8, 5, 24, 16
    c = _varlen_case(rng, n_pre=rng.randint(1, 4), n_dec=rng.randint(0, 4),
                     page=page, NP=NP, P=P, H=H, KH=KH, D=D)
    o_ref = msa_fused(c["q"], c["k_pages"], c["v_pages"], c["bt"], c["ctx"],
                      c["q_pos"], c["seq_ids"], c["valid"],
                      window=window, softcap=softcap, impl="xla")
    wl = _worklist_for(c, page=page, q_tile=q_tile, window=window)
    o_pal = msa_fused(c["q"], c["k_pages"], c["v_pages"], c["bt"], c["ctx"],
                      c["q_pos"], c["seq_ids"], c["valid"],
                      q_start=c["q_start"], q_len=c["q_len"], worklist=wl,
                      window=window, softcap=softcap, q_tile=q_tile,
                      impl="pallas_interpret")
    err = float(jnp.max(jnp.abs(o_ref - o_pal)))
    assert err < 1e-5, err


def test_fused_kernel_zeroes_bucket_slack_tiles():
    """Occupancy-bucket slack beyond the real tokens can span whole q
    tiles with no work; build_worklist emits a sentinel item per empty
    tile so every output tile is WRITTEN — exact zeros, never an
    uninitialized buffer."""
    rng = np.random.RandomState(3)
    page, NP, P, H, KH, D, TQ = 8, 5, 24, 4, 2, 16, 8
    c = _varlen_case(rng, n_pre=1, n_dec=2, page=page, NP=NP, P=P,
                     H=H, KH=KH, D=D)
    t_bucket = c["T"] + 2 * TQ + 3           # > 2 wholly-empty tail tiles
    q = jnp.pad(c["q"], ((0, t_bucket - c["T"]), (0, 0), (0, 0)))
    q_pos = jnp.pad(c["q_pos"], (0, t_bucket - c["T"]))
    n_tiles = -(-t_bucket // TQ)
    wl, _ = build_worklist(
        np.asarray(c["q_start"]), np.asarray(c["q_len"]),
        np.asarray(c["ctx"]), np.asarray(c["bt"]), np.asarray(q_pos),
        page=page, q_tile=TQ, n_tiles=n_tiles, window=0)
    assert set(np.asarray(wl["wl_qtile"])) == set(range(n_tiles))
    o = msa_fused(q, c["k_pages"], c["v_pages"], c["bt"], c["ctx"], q_pos,
                  jnp.pad(c["seq_ids"], (0, t_bucket - c["T"])),
                  jnp.pad(c["valid"], (0, t_bucket - c["T"])),
                  q_start=c["q_start"], q_len=c["q_len"],
                  worklist=tuple(jnp.asarray(wl[f]) for f in WL_FIELDS),
                  q_tile=TQ, impl="pallas_interpret")
    assert np.all(np.asarray(o[c["T"]:]) == 0.0), "slack rows not zeroed"
    o_ref = msa_fused(c["q"], c["k_pages"], c["v_pages"], c["bt"], c["ctx"],
                      c["q_pos"], c["seq_ids"], c["valid"], impl="xla")
    assert float(jnp.max(jnp.abs(o[:c["T"]] - o_ref))) < 1e-5


def test_fused_kernel_worklist_shared_across_windows():
    """The engine builds ONE full-causal work-list for all layers; a
    sliding-window layer must still mask correctly against it."""
    rng = np.random.RandomState(7)
    page, NP, P, H, KH, D = 8, 6, 24, 4, 2, 16
    c = _varlen_case(rng, n_pre=2, n_dec=2, page=page, NP=NP, P=P,
                     H=H, KH=KH, D=D)
    wl = _worklist_for(c, page=page, q_tile=8, window=0)   # full-causal list
    for window in (0, 6, 17):
        o_ref = msa_fused(c["q"], c["k_pages"], c["v_pages"], c["bt"],
                          c["ctx"], c["q_pos"], c["seq_ids"], c["valid"],
                          window=window, impl="xla")
        o_pal = msa_fused(c["q"], c["k_pages"], c["v_pages"], c["bt"],
                          c["ctx"], c["q_pos"], c["seq_ids"], c["valid"],
                          q_start=c["q_start"], q_len=c["q_len"],
                          worklist=wl, window=window, q_tile=8,
                          impl="pallas_interpret")
        assert float(jnp.max(jnp.abs(o_ref - o_pal))) < 1e-5, window


# ---------------------------------------------------------------------------
# satellite regressions on the split prefill kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 12])
def test_prefill_kernel_masks_invalid_q_rows(window):
    """Padded q rows (beyond q_lens, qpos 0) must neither attend nor
    drag the tile's position range: the kernel output must equal the
    oracle's qvalid-masked output on EVERY row — invalid rows exactly
    zero, not garbage."""
    R, QP, NP, P, page, H, KH, D = 2, 16, 5, 32, 8, 4, 2, 32
    ks = jax.random.split(KEY, 4)
    q = _rand((R, QP, H, D), ks[0])
    k_pages = _rand((P, KH, page, D), ks[1])
    v_pages = _rand((P, KH, page, D), ks[2])
    bt = jax.random.randint(ks[3], (R, NP), 0, P).astype(jnp.int32)
    ctx = jnp.array([NP * page, 2 * page + 3], jnp.int32)
    q_pos = jnp.stack([
        jnp.concatenate([jnp.arange(30, 30 + QP // 2),
                         jnp.arange(NP * page - QP // 2, NP * page)]),
        jnp.arange(QP),
    ]).astype(jnp.int32)
    # heavily ragged: rows past q_lens are padding with qpos 0
    q_lens = jnp.array([QP - 6, 3], jnp.int32)
    q_pos = jnp.where(jnp.arange(QP)[None, :] < q_lens[:, None], q_pos, 0)

    o_ref = msa_prefill(q, k_pages, v_pages, bt, ctx, q_pos, q_lens,
                        window=window, impl="xla")
    o_pal = msa_prefill(q, k_pages, v_pages, bt, ctx, q_pos, q_lens,
                        window=window, q_tile=8, impl="pallas_interpret")
    # full-array comparison — includes the invalid rows (oracle: zeros)
    err = float(jnp.max(jnp.abs(o_ref - o_pal)))
    assert err < 1e-5, err
    invalid = np.asarray(o_pal)[1, 3:]
    assert np.all(invalid == 0.0), "padded q rows attended"


@pytest.mark.parametrize("qp,q_tile", [(20, 16), (5, 128), (13, 8)])
def test_prefill_wrapper_rounds_ragged_qp_up(qp, q_tile):
    """Legal ragged QP shapes must round up to the tile inside the
    wrapper instead of raising (the old ValueError path)."""
    R, NP, P, page, H, KH, D = 2, 4, 16, 8, 4, 2, 16
    ks = jax.random.split(KEY, 4)
    q = _rand((R, qp, H, D), ks[0])
    k_pages = _rand((P, KH, page, D), ks[1])
    v_pages = _rand((P, KH, page, D), ks[2])
    bt = jax.random.randint(ks[3], (R, NP), 0, P).astype(jnp.int32)
    ctx = jnp.array([NP * page, 2 * page + 1], jnp.int32)
    q_pos = jnp.stack([jnp.arange(qp), jnp.arange(qp)]).astype(jnp.int32)
    q_lens = jnp.array([qp, max(1, qp - 2)], jnp.int32)
    o_ref = msa_prefill(q, k_pages, v_pages, bt, ctx, q_pos, q_lens,
                        impl="xla")
    o_pal = msa_prefill(q, k_pages, v_pages, bt, ctx, q_pos, q_lens,
                        q_tile=q_tile, impl="pallas_interpret")
    assert o_pal.shape == o_ref.shape
    assert float(jnp.max(jnp.abs(o_ref - o_pal))) < 1e-5


# ---------------------------------------------------------------------------
# engine integration: fused layout vs the two-dispatch baseline
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    from repro.configs import get_smoke_config, scaled_config
    from repro.models import init_params
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, KEY)
    return cfg, params


def _mk_server(cfg, params, attn_mode, depth=1, num_blocks=64):
    from repro.serving import (AsymCacheServer, EngineConfig,
                               SchedulerConfig, ServerConfig)
    scfg = ServerConfig(
        policy="asymcache", num_blocks=num_blocks, block_size=16,
        clock="model", pipeline_depth=depth, attn_mode=attn_mode,
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    ecfg = EngineConfig(num_pages=num_blocks, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=8, attn_mode=attn_mode)
    return AsymCacheServer(cfg, params, scfg, ecfg=ecfg)


def _wl(seed=3):
    from repro.serving import WorkloadConfig, multi_turn_workload
    return multi_turn_workload(WorkloadConfig(
        n_sessions=3, turns_per_session=(2, 3), first_ctx_len=(96, 180),
        output_len=(12, 30), qps=1.0, seed=seed))


@pytest.mark.parametrize("depth", [0, 1])
def test_fused_engine_byte_identical_to_split(small_model, depth):
    """The acceptance gate: byte-identical sampled tokens, generated
    tokens, and prefill logit rows between the fused single-dispatch and
    the split two-dispatch layouts, at pipeline depth 0 and 1 — while
    the fused engine issues HALF the attention dispatches per step."""
    cfg, params = small_model
    srv_f = _mk_server(cfg, params, "fused", depth=depth)
    srv_s = _mk_server(cfg, params, "split", depth=depth)
    wf, ws = _wl(), _wl()
    rf, rs = srv_f.run(wf), srv_s.run(ws)
    assert rf["steps"] == rs["steps"]
    for a, b in zip(wf, ws):
        assert a.generated == b.generated
        assert a.sampled_ids == b.sampled_ids and a.sampled_ids
        assert np.array_equal(a.first_logits, b.first_logits)
    assert rf["attn_dispatches_per_step"] == cfg.n_layers
    assert rs["attn_dispatches_per_step"] == 2 * cfg.n_layers
    assert rf["padded_token_fraction"] < rs["padded_token_fraction"]


def test_every_used_bucket_compiles_exactly_once(small_model):
    """Compile-counter regression across the occupancy lattice: each
    (t_bucket, np_bucket) the workload exercises traces the step exactly
    once; re-running the same workload adds no traces."""
    cfg, params = small_model
    srv = _mk_server(cfg, params, "fused")
    srv.run(_wl())
    eng = srv.engine
    assert len(eng.buckets_used) >= 2, sorted(eng.buckets_used)
    assert eng.jit_traces == len(eng.buckets_used)
    # bucket accounting covers every step
    assert sum(eng.bucket_counts.values()) == eng.steps_executed
    srv.run(_wl(seed=11))
    assert eng.jit_traces == len(eng.buckets_used)
    # the lattice always contains the maximal shape, so any legal plan fits
    assert eng.token_buckets[-1] == eng.t_max
    assert eng.np_buckets[-1] == eng.ecfg.max_blocks_per_seq


def test_engine_rejects_foreign_scheduler_buckets(small_model):
    """A plan carrying buckets from another engine's lattice (e.g. two
    servers built over one shared SchedulerConfig) must not crash or
    grow off-lattice jit variants — the engine snaps to its own
    lattice."""
    from repro.serving.scheduler import StepPlan
    cfg, params = small_model
    srv = _mk_server(cfg, params, "fused")
    eng = srv.engine
    plan = StepPlan()                       # decode-only foreign plan
    plan.decodes = []
    plan.t_bucket = 7                       # not in any derived lattice
    plan.np_bucket = 1000
    t_b, np_b = eng.buckets_for(plan)
    assert t_b in eng.token_buckets and np_b in eng.np_buckets
    # a too-small foreign bucket must be overridden, not asserted on
    wl = _wl()
    for r in wl:
        srv._on_arrival(r)
    plan = srv.sched.schedule(now=1e9)
    assert not plan.empty()
    plan.t_bucket = 8                       # smaller than the plan's tokens
    t_b, _ = eng.buckets_for(plan)
    assert t_b in eng.token_buckets and t_b >= plan.n_compute_tokens


def test_fused_engine_through_pallas_worklist(small_model):
    """Engine-level fused Pallas path (interpret): the work-list grid +
    scalar prefetch must reproduce the xla oracle's losslessness."""
    from repro.serving import (AsymCacheServer, EngineConfig,
                               SchedulerConfig, ServerConfig,
                               WorkloadConfig, multi_turn_workload,
                               reference_logits)
    cfg, params = small_model
    wl = multi_turn_workload(WorkloadConfig(
        n_sessions=1, turns_per_session=(2, 2), first_ctx_len=(48, 80),
        output_len=(8, 12), qps=1.0, seed=0))
    scfg = ServerConfig(
        policy="asymcache", num_blocks=48, block_size=16, clock="model",
        attn_impl="pallas_interpret",
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    ecfg = EngineConfig(num_pages=48, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=8, max_blocks_per_seq=16,
                        attn_impl="pallas_interpret", q_tile=16)
    srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
    res = srv.run(wl)
    assert res["n_requests"] == len(wl)
    assert res["attn_dispatches_per_step"] == cfg.n_layers
    for r in wl:
        ref = reference_logits(cfg, params, r.prompt_tokens)
        rel = float(np.max(np.abs(ref - r.first_logits))) / max(
            1e-9, float(np.max(np.abs(ref))))
        assert rel < 2e-3, rel
