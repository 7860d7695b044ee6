"""Latent attention (MLA) and the expert layer at one chip's share, as the
DeepSeek-V3 block (``bench/models/deepseek_v3.py``) is served.

At small widths on the CPU, in float32: the latent page tile, the fused
kernel taking V from the cached latent, the absorbed form against the
expanded one, the expert shares against the uncut layer, the latent
pool's bytes, and the served path (prefill, then decode through the
cache, with evictions) against the architecture module's reference.
"""
import importlib.util
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.msa import build_worklist, msa_fused, pad_worklist
from repro.kernels.msa.latent import LatentLayout
from repro.kernels.msa.msa_fused import narrow_rows
from repro.models.layers import moe_ffn_local, swiglu_mlp
from repro.serving import (AsymCacheServer, EngineConfig, SchedulerConfig,
                           ServerConfig, WorkloadConfig, multi_turn_workload)
from repro.serving.engine import Engine
from repro.serving.server import DECODE_LOGITS_KEPT

ROOT = Path(__file__).resolve().parents[1]
CELL = "moonlight-16b-a3b-ep8.agent-sat"
# float32 throughout: the served path and the reference differ only in
# summation order (absorbed vs expanded attention, paged vs dense), so
# logits agree to the 2e-3 relative tolerance the other served-path
# tests use
REL_TOL = 2e-3

# the cell's block at smoke widths: 3 layers (one dense, two MoE), 16
# experts routed top-6 of which this chip holds 4 (experts 4-7), a
# 128-lane latent and a 64-lane RoPE key (the page tile needs a latent
# of whole 128-lane chunks)
PROGRAM = {"family": "moe", "n_layers": 3, "d_model": 64, "n_heads": 4,
           "n_kv_heads": 4, "d_ff": 32, "vocab_size": 256,
           "rope_theta": 50000.0, "norm_eps": 1e-5, "tie_embeddings": False,
           "dtype": "float32", "kv_lora_rank": 128, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 64, "v_head_dim": 16, "first_k_dense": 1,
           "dense_d_ff": 128,
           "moe": {"num_experts": 16, "top_k": 6, "dropless": True,
                   "score_fn": "sigmoid", "routed_scale": 2.446,
                   "shared_d_ff": 64, "expert_offset": 4,
                   "experts_held": 4}}


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        "t_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arch():
    return _load(ROOT / "bench" / "models" / "deepseek_v3.py")


@pytest.fixture(scope="module")
def model(arch):
    weights = _load(ROOT / "bench" / "harness" / "weights.py")
    cfg = arch.model_config("tiny-deepseek-v3", PROGRAM)
    params = weights.make(arch.shapes(PROGRAM), 3, dtype=jnp.float32)
    return cfg, params


def test_latent_tile_roundtrip():
    """A page's cached vectors survive the (rows, 128) tile both ways, and
    the tile holds exactly their values (no padding)."""
    lay = LatentLayout(page=16, rank=512, rope=64)
    assert (lay.rows, lay.q_width) == (72, 768)
    assert lay.rows * 128 == lay.page * lay.width
    vecs = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 576))
    tile = lay.to_tile(vecs)
    assert tile.shape == (3, 72, 128)
    np.testing.assert_array_equal(lay.from_tile(tile), vecs)


def _latent_case(seed, lay, n_pages=24, heads=16):
    """Random latent pool tiles, two sequences (a prefill chunk over a
    paged context and a decode row), and the step fields."""
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n_pages, 1, lay.page, lay.width))
    pool = lay.to_tile(jnp.asarray(vecs, jnp.float32))
    bt = np.zeros((2, 10), np.int32)
    bt[0, :6] = rng.permutation(n_pages)[:6]
    bt[1, :9] = rng.permutation(n_pages)[:9]
    ctx = np.array([90, 140], np.int32)
    pos = np.concatenate([np.arange(60, 90), [139]]).astype(np.int32)
    t = 32
    q_pos = np.zeros((t,), np.int32)
    q_pos[:31] = pos
    seq_ids = np.array([0] * 30 + [1] + [0], np.int32)
    valid = np.array([True] * 31 + [False])
    q = jnp.asarray(rng.standard_normal((t, heads, lay.width)), jnp.float32)
    return pool, q, bt, ctx, q_pos, seq_ids, valid


def test_msa_fused_takes_v_from_the_cached_latent():
    """The Pallas kernel (interpreted) over latent page tiles equals the
    oracle, and both equal attention written out plainly: K = [c | pe],
    V = c, at scale 1/sqrt(rank + rope)."""
    lay = LatentLayout(page=16, rank=128, rope=64)
    pool, q, bt, ctx, q_pos, seq_ids, valid = _latent_case(0, lay)
    q_start = np.array([0, 30], np.int32)
    q_len = np.array([30, 1], np.int32)
    wl, _, _, _ = build_worklist(q_start, q_len, ctx, bt, q_pos, page=16,
                              q_tile=16, n_tiles=2)
    wl = pad_worklist(wl, 64, sentinel_seq=2)
    args = (q, pool, None, jnp.asarray(bt), jnp.asarray(ctx),
            jnp.asarray(q_pos), jnp.asarray(seq_ids), jnp.asarray(valid))
    got = msa_fused(*args, q_start=jnp.asarray(q_start),
                    q_len=jnp.asarray(q_len),
                    worklist=tuple(jnp.asarray(wl[f]) for f in wl),
                    q_tile=16, latent=lay, impl="pallas_interpret")
    want = msa_fused(*args, latent=lay, impl="xla")
    assert got.shape == (32, 16, lay.rank)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # plainly: gather each sequence's cached vectors, softmax, V = c
    vecs = lay.from_tile(pool)[:, 0]                  # (P, page, width)
    for row in range(31):
        s = seq_ids[row]
        keys = vecs[bt[s]].reshape(-1, lay.width)[:q_pos[row] + 1]
        sc = keys @ q[row].T / math.sqrt(lay.width)   # (keys, H)
        p = jax.nn.softmax(sc, axis=0)
        np.testing.assert_allclose(got[row], p.T @ keys[:, :lay.rank],
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(got[31], 0.0)


def test_msa_fused_latent_decode_rows_go_narrow():
    """Latent attention at G=16 and TQ=32: decode rows whose items
    compute only their token's 16 query rows, in one tile with a
    prefill chunk, other decode rows and padding, equal the oracle."""
    lay = LatentLayout(page=16, rank=128, rope=64)
    assert narrow_rows(16, 32) == 16
    rng = np.random.default_rng(5)
    n_pages, n, t = 48, 7, 32
    vecs = rng.standard_normal((n_pages, 1, lay.page, lay.width))
    pool = lay.to_tile(jnp.asarray(vecs, jnp.float32))
    bt = np.stack([rng.permutation(n_pages)[:20] for _ in range(n)])
    bt = bt.astype(np.int32)
    # a 20-token chunk, then six decode rows over 1 to 3 128-key blocks
    ctx = np.array([120, 7, 129, 300, 64, 257, 18], np.int32)
    q_len = np.array([20] + [1] * 6, np.int32)
    q_start = np.concatenate([[0], np.cumsum(q_len)[:-1]]).astype(np.int32)
    q_pos = np.zeros((t,), np.int32)
    q_pos[:20] = np.arange(100, 120)
    q_pos[20:26] = ctx[1:] - 1
    seq_ids = np.zeros((t,), np.int32)
    seq_ids[:26] = np.repeat(np.arange(n), q_len)
    valid = np.arange(t) < 26
    q = jnp.asarray(rng.standard_normal((t, 16, lay.width)), jnp.float32)
    wl, n_items, _, n_narrow = build_worklist(q_start, q_len, ctx, bt,
                                              q_pos, page=16, q_tile=32,
                                              n_tiles=1)
    assert n_narrow == n_items - 1 > 6
    args = (q, pool, None, jnp.asarray(bt), jnp.asarray(ctx),
            jnp.asarray(q_pos), jnp.asarray(seq_ids), jnp.asarray(valid))
    got = msa_fused(*args, q_start=jnp.asarray(q_start),
                    q_len=jnp.asarray(q_len),
                    worklist=tuple(jnp.asarray(wl[f]) for f in wl),
                    q_tile=32, latent=lay, impl="pallas_interpret")
    want = msa_fused(*args, latent=lay, impl="xla")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[26:], 0.0)


def test_absorbed_latent_attention_equals_expanded(model, arch):
    """One layer's attention as the engine computes it (absorbed: the
    query through W_UK, attention over the cached latent, the output
    through W_UV) equals the reference's expanded form (per-head keys
    [k_nope | k_pe], values from W_kvb)."""
    cfg, params = model
    S = arch.QB           # the reference attends in query blocks of QB
    x = jax.random.normal(jax.random.PRNGKey(1), (S, cfg.d_model))
    blk = {k: v[1] for k, v in params["blocks"].items()}
    pos = jnp.arange(S, dtype=jnp.int32)
    eng = Engine(cfg, EngineConfig(num_pages=4, max_decodes=2,
                                   max_blocks_per_seq=4), params)
    with jax.default_matmul_precision("highest"):
        h = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * x \
            * (1.0 + blk["attn_norm"])
        q, latent = eng._latent_qk(h, blk, pos)
        sc = jnp.einsum("qhd,kd->hqk", q, latent[:, 0]) \
            / math.sqrt(cfg.cache_dim)
        sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
        o_lat = jnp.einsum("hqk,kr->qhr", jax.nn.softmax(sc, -1),
                           latent[:, 0, :cfg.kv_lora_rank])
        o = jnp.einsum("thr,rhv->thv", o_lat,
                       blk["w_kvb"][:, :, cfg.qk_nope_head_dim:])
        absorbed = jnp.einsum("thk,hkd->td", o, blk["wo"])
        expanded = arch._attention(x, blk, arch._frozen(PROGRAM), False)
    np.testing.assert_allclose(absorbed, expanded, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("seed", [0, 1])
def test_expert_shares_sum_to_the_uncut_layer(seed):
    """Four chips holding 4 of 16 experts each (offsets 0, 4, 8, 12):
    their parts, with the shared experts counted once, add up to the
    layer that holds all 16, and to a per-token sum over each token's
    top-6 experts, so no slot is dropped."""
    d, f, e, k, fs = 32, 24, 16, 6, 48
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    x = jax.random.normal(ks[0], (37, d))
    router = jax.random.normal(ks[1], (d, e)) / math.sqrt(d)
    bias = 0.1 * jax.random.normal(ks[2], (e,))
    we1, we3 = (jax.random.normal(kk, (e, d, f)) / math.sqrt(d)
                for kk in ks[3:5])
    we2 = jax.random.normal(ks[5], (e, f, d)) / math.sqrt(f)
    ws1, ws3 = (jax.random.normal(kk, (d, fs)) / math.sqrt(d)
                for kk in ks[6:8])
    ws2 = jax.random.normal(ks[8], (fs, d)) / math.sqrt(fs)
    kw = dict(dropless=True, score_fn="sigmoid", router_bias=bias,
              routed_scale=2.446)
    with jax.default_matmul_precision("highest"):
        shared = swiglu_mlp(x, ws1, ws3, ws2)
        uncut = moe_ffn_local(x, router, we1, we3, we2, k, **kw) + shared
        parts = [moe_ffn_local(x, router, we1[o:o + 4], we3[o:o + 4],
                               we2[o:o + 4], k, expert_offset=o, **kw)
                 for o in (0, 4, 8, 12)]
        # each token through its own top-6, one expert at a time
        scores = jax.nn.sigmoid(x @ router)
        _, top = jax.lax.top_k(scores + bias, k)
        w = jnp.take_along_axis(scores, top, -1)
        w = 2.446 * w / jnp.sum(w, -1, keepdims=True)
        per_token = shared + sum(
            w[:, j:j + 1] * jnp.stack([swiglu_mlp(x[t], we1[top[t, j]],
                                                  we3[top[t, j]],
                                                  we2[top[t, j]])
                                       for t in range(x.shape[0])])
            for j in range(k))
    np.testing.assert_allclose(sum(parts) + shared, uncut, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(uncut, per_token, rtol=1e-5, atol=1e-5)
    # every share computes something: the routing reaches all four
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)


def test_latent_pool_holds_one_vector_per_token(arch):
    """At Moonlight's widths a pool page holds L·page·576·2 bytes (27
    layers, 16 tokens, kv_lora_rank 512 + qk_rope 64, bf16) and no V
    bytes; the counters name the layers' kinds."""
    cfgd = json.loads((ROOT / "bench" / "configs"
                       / "moonlight-16b-a3b-ep8.json").read_text())
    cfg = arch.model_config(cfgd["name"], cfgd["program"])
    eng = Engine(cfg, EngineConfig(num_pages=2, max_decodes=2,
                                   max_blocks_per_seq=4), {})
    pc = eng.perf_counters()
    assert pc["kv_bytes_per_page"] == 27 * 16 * 576 * 2 == 497_664
    assert eng.k_pools.nbytes == 2 * 497_664
    assert eng.v_pools.size == 0
    assert pc["layers_by_kind"] == {"latent+dense": 1, "latent+moe": 26}


def test_step_keeps_the_new_scopes(model):
    """The step's HLO metadata names the latent projection inside ``qkv``
    and the router, held experts and shared experts inside ``mlp``, so a
    trace can split the time among them."""
    cfg, params = model
    eng = Engine(cfg, EngineConfig(num_pages=8, max_prefills=1, max_chunk=16,
                                   max_decodes=2, max_blocks_per_seq=4,
                                   token_buckets=(18,), np_buckets=(4,)),
                 params)
    hlo = eng.compiled_step(18, 4).as_text()
    for scope in ("qkv/latent", "mlp/router", "mlp/experts",
                  "mlp/shared_experts", "kv_write", "attention"):
        assert f"/{scope}/" in hlo, scope


def test_latent_attention_refuses_a_mesh(model):
    """The latent path runs on one device only."""
    cfg, params = model

    class TwoShards:
        shape = {"model": 2}

    with pytest.raises(ValueError, match="one device"):
        Engine(cfg, EngineConfig(num_pages=8), params, mesh=TwoShards())


def _serve(cfg, params, impl, host_blocks=0):
    wl = multi_turn_workload(WorkloadConfig(
        n_sessions=4, turns_per_session=(2, 3), first_ctx_len=(96, 180),
        output_len=(12, 30), qps=1.0, seed=0, vocab=cfg.vocab_size))
    scfg = ServerConfig(
        policy="asymcache", num_blocks=48, block_size=16, clock="model",
        record_decode_logits=True, attn_impl=impl, host_blocks=host_blocks,
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    ecfg = EngineConfig(num_pages=48, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=8, attn_impl=impl,
                        q_tile=64)
    srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
    return wl, srv.run(wl), srv


@pytest.mark.parametrize("impl,host_blocks", [
    ("xla", 0), ("pallas_interpret", 0), ("xla", 64)])
def test_served_path_matches_the_reference(model, arch, impl, host_blocks):
    """Prefill and then decode through the latent cache, with evictions
    that serve later turns as cached plus recomputed segments (and, with
    a host tier, latent pages spilled and swapped back as K halves
    alone), give the reference's logits at every compared row."""
    cfg, params = model
    wl, res, srv = _serve(cfg, params, impl, host_blocks)
    assert res["evictions"] > 0
    multi = sum(1 for r in wl
                if any(not a and b for a, b in zip(r.hit_mask,
                                                   r.hit_mask[1:])))
    if not host_blocks:
        assert multi > 0, "no turn served as cached and recomputed segments"
    else:
        assert res["swap_ins"] > 0
        assert srv.bm.bytes_swapped_out_v == 0
        assert all(e.v is None for e in srv.bm.host_tier.values())
    rows = 0
    for r in wl:
        assert len(r.decode_logits) == min(DECODE_LOGITS_KEPT,
                                          len(r.output_script) - 1)
        got = [(r.prompt_len - 1, r.first_logits)] + r.decode_logits
        ref = arch.logits(params, PROGRAM, r.prompt_tokens + r.output_script,
                          [p for p, _ in got])
        for (p, g), want in zip(got, ref):
            rel = float(np.max(np.abs(want - g))) / max(
                1e-9, float(np.max(np.abs(want))))
            assert rel < REL_TOL, (impl, r.rid, p, rel)
            rows += 1
    assert rows > len(wl)


def test_dry_run_of_the_cell_is_correct(tmp_path):
    """``bench/run.py --dry`` serves the cell at its smoke size through the
    interpreted kernel and the reference judges it correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELL,
         "--dry", "--seconds", "4", "--seed", "2147483999"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("dry record: "))
    assert json.loads(line[len("dry record: "):])["correct"] is True
