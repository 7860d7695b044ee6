"""Per-kernel validation: the fused MSA kernel (Pallas interpret=True) vs
its pure-jnp oracle, swept over shapes/dtypes, plus hypothesis property
tests on the MSA contract (multi-segment causal masking)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.msa import (WL_FIELDS, build_worklist, msa_fused,
                               write_kv_pages)
from repro.kernels.msa import ref as msa_ref
from repro.models.layers import (causal_conv1d, causal_conv1d_step,
                                 decode_attention, flash_attention,
                                 repeat_kv, ssd_chunked, ssd_decode_step)

KEY = jax.random.PRNGKey(0)


def _rand(shape, k, dtype):
    return jax.random.normal(k, shape, jnp.float32).astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-5


# ---------------------------------------------------------------------------
# fused MSA kernel: shape/dtype sweeps vs the varlen oracle
# ---------------------------------------------------------------------------

def _fused_both(q, k_pages, v_pages, bt, ctx, runs, *, t_bucket, page,
                q_tile, window=0, softcap=0.0):
    """``msa_fused`` through the oracle and through the Pallas kernel
    (interpret) over one varlen stream: ``runs`` lists each sequence's
    logical q positions in stream order, ``t_bucket`` rows in all (rows
    past the runs are bucket slack)."""
    q_len = np.asarray([len(p) for p in runs], np.int32)
    q_start = np.concatenate([[0], np.cumsum(q_len)[:-1]]).astype(np.int32)
    t = int(q_len.sum())
    q_pos = np.zeros((t_bucket,), np.int32)
    q_pos[:t] = np.concatenate(runs)
    seq_ids = np.zeros((t_bucket,), np.int32)
    seq_ids[:t] = np.repeat(np.arange(len(runs)), q_len)
    valid = np.arange(t_bucket) < t
    tq = min(q_tile, t_bucket)
    wl, _, _, _ = build_worklist(q_start, q_len, np.asarray(ctx),
                              np.asarray(bt), q_pos, page=page, q_tile=tq,
                              n_tiles=-(-t_bucket // tq), window=window)
    args = (q, k_pages, v_pages, bt, ctx, jnp.asarray(q_pos),
            jnp.asarray(seq_ids), jnp.asarray(valid))
    o_ref = msa_fused(*args, window=window, softcap=softcap, impl="xla")
    o_pal = msa_fused(*args, q_start=jnp.asarray(q_start),
                      q_len=jnp.asarray(q_len),
                      worklist=tuple(jnp.asarray(wl[f]) for f in WL_FIELDS),
                      window=window, softcap=softcap, q_tile=tq,
                      impl="pallas_interpret")
    return o_ref, o_pal


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kh,d", [(4, 2, 32), (4, 4, 16), (8, 2, 64)])
@pytest.mark.parametrize("page,q_tile", [(8, 8), (16, 4)])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (12, 0.0), (0, 30.0)])
def test_msa_fused_prefill_sweep(dtype, h, kh, d, page, q_tile, window,
                                 softcap):
    """Two prefill chunks in one stream: the first runs over two
    non-contiguous position ranges of a five-page context, the second is
    ragged (13 of 16 rows) over a short one."""
    QP, NP, P = 16, 5, 32
    ks = jax.random.split(KEY, 4)
    runs = [np.r_[3:3 + QP // 2, NP * page - QP // 2:NP * page],
            np.arange(QP - 3)]
    t = sum(len(r) for r in runs)
    q = _rand((t, h, d), ks[0], dtype)
    k_pages = _rand((P, kh, page, d), ks[1], dtype)
    v_pages = _rand((P, kh, page, d), ks[2], dtype)
    bt = jax.random.randint(ks[3], (2, NP), 0, P).astype(jnp.int32)
    ctx = jnp.array([NP * page, 2 * page + 3], jnp.int32)
    o_ref, o_pal = _fused_both(q, k_pages, v_pages, bt, ctx, runs,
                               t_bucket=t, page=page, q_tile=q_tile,
                               window=window, softcap=softcap)
    err = float(jnp.max(jnp.abs(o_ref.astype(jnp.float32)
                                - o_pal.astype(jnp.float32))))
    assert err < _tol(dtype), err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kh,d", [(4, 2, 32), (8, 8, 16), (8, 1, 64)])
@pytest.mark.parametrize("window", [0, 10])
def test_msa_fused_decode_sweep(dtype, h, kh, d, window):
    """Three decode rows (runs of one token at ``ctx - 1``) in an
    8-row decode bucket; the five slack rows come out as zeros."""
    B, NP, P, page, T = 3, 6, 24, 8, 8
    ks = jax.random.split(KEY, 4)
    q = _rand((T, h, d), ks[0], dtype)
    k_pages = _rand((P, kh, page, d), ks[1], dtype)
    v_pages = _rand((P, kh, page, d), ks[2], dtype)
    bt = jax.random.randint(ks[3], (B, NP), 0, P).astype(jnp.int32)
    ctx = np.asarray([NP * page, 17, 1], np.int32)
    o_ref, o_pal = _fused_both(q, k_pages, v_pages, jnp.asarray(bt),
                               jnp.asarray(ctx), [[c - 1] for c in ctx],
                               t_bucket=T, page=page, q_tile=128,
                               window=window)
    err = float(jnp.max(jnp.abs(o_ref.astype(jnp.float32)
                                - o_pal.astype(jnp.float32))))
    assert err < _tol(dtype), err
    assert np.all(np.asarray(o_pal[B:]) == 0)


# ---------------------------------------------------------------------------
# MSA semantics: the paper's Eq. 2 — multi-segment == concatenated attention
# ---------------------------------------------------------------------------

def _scatter_pages(k_full, v_full, perm, n_slots, page):
    """Write a contiguous (S, KH, D) context into pool pages ``perm``."""
    kh, d = k_full.shape[-2:]
    k_pages = jnp.zeros((n_slots, kh, page, d))
    v_pages = jnp.zeros((n_slots, kh, page, d))
    for j, slot in enumerate(perm):
        k_pages = k_pages.at[slot].set(
            k_full[0, j * page:(j + 1) * page].transpose(1, 0, 2))
        v_pages = v_pages.at[slot].set(
            v_full[0, j * page:(j + 1) * page].transpose(1, 0, 2))
    return k_pages, v_pages


def test_msa_equals_contiguous_attention():
    """A paged multi-segment context must give the semantics of ordinary
    causal attention over the logically contiguous sequence, through the
    varlen oracle and the fused kernel that serves."""
    S, H, KH, D, page = 48, 4, 2, 32, 8
    ks = jax.random.split(KEY, 3)
    k_full = _rand((1, S, KH, D), ks[0], jnp.float32)
    v_full = _rand((1, S, KH, D), ks[1], jnp.float32)
    q_full = _rand((1, S, H, D), ks[2], jnp.float32)

    # oracle: plain causal attention
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    o_dense = flash_attention(q_full, k_full, v_full, pos, pos, chunk_size=16)

    # paged: scatter KV into shuffled pool pages
    NP = S // page
    perm = np.random.RandomState(0).permutation(16)[:NP]
    k_pages, v_pages = _scatter_pages(k_full, v_full, perm, 16, page)
    bt = jnp.asarray(perm)[None, :].astype(jnp.int32)
    for o_paged in _fused_both(q_full[0], k_pages, v_pages, bt,
                               jnp.array([S], jnp.int32), [np.arange(S)],
                               t_bucket=S, page=page, q_tile=16):
        np.testing.assert_allclose(np.asarray(o_dense[0]),
                                   np.asarray(o_paged), atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    n_seg=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_msa_segment_merge_property(n_seg, seed):
    """Property: attention over q tokens split across arbitrary gap
    structures equals attention computed over the same logical positions
    contiguously (Eq. 2 generalized to any segment count), through the
    varlen oracle and the fused kernel."""
    rng = np.random.RandomState(seed)
    page, KH, H, D = 4, 2, 4, 16
    S = 40
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_full = _rand((1, S, KH, D), ks[0], jnp.float32)
    v_full = _rand((1, S, KH, D), ks[1], jnp.float32)

    # pick n_seg disjoint gap runs as "compute" tokens
    idx = np.sort(rng.choice(S, size=min(16, S), replace=False))
    q_pos = jnp.asarray(idx, jnp.int32)[None, :]
    q = _rand((1, len(idx), H, D), ks[2], jnp.float32)

    o_dense = flash_attention(q, k_full, v_full, q_pos,
                              jnp.arange(S, dtype=jnp.int32)[None], chunk_size=8)

    NP = S // page
    perm = rng.permutation(NP + 4)[:NP]
    k_pages, v_pages = _scatter_pages(k_full, v_full, perm, NP + 4, page)
    bt = jnp.asarray(perm)[None, :].astype(jnp.int32)
    for o_paged in _fused_both(q[0], k_pages, v_pages, bt,
                               jnp.array([S], jnp.int32), [idx],
                               t_bucket=len(idx), page=page, q_tile=8):
        np.testing.assert_allclose(np.asarray(o_dense[0]),
                                   np.asarray(o_paged), atol=1e-5)


def test_write_kv_pages_roundtrip():
    P, page, KH, D, T = 6, 4, 2, 8, 10
    ks = jax.random.split(KEY, 3)
    k_pages = jnp.zeros((P, KH, page, D))
    v_pages = jnp.zeros((P, KH, page, D))
    k_new = _rand((T, KH, D), ks[0], jnp.float32)
    v_new = _rand((T, KH, D), ks[1], jnp.float32)
    slot_ids = jnp.array([0, 0, 0, 0, 2, 2, 2, 2, 5, 5], jnp.int32)
    offs = jnp.array([0, 1, 2, 3, 0, 1, 2, 3, 0, 1], jnp.int32)
    valid = jnp.array([True] * 8 + [False, True])
    k2, v2 = write_kv_pages(k_pages, v_pages, k_new, v_new, slot_ids, offs, valid)
    np.testing.assert_allclose(np.asarray(k2[0, :, 0]), np.asarray(k_new[0]))
    np.testing.assert_allclose(np.asarray(k2[2, :, 3]), np.asarray(k_new[7]))
    # dropped write leaves zeros
    np.testing.assert_allclose(np.asarray(k2[5, :, 0]), np.zeros((KH, D)))
    np.testing.assert_allclose(np.asarray(v2[5, :, 1]), np.asarray(v_new[9]))


# ---------------------------------------------------------------------------
# flash_attention (model XLA path) vs naive softmax attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2)])
@pytest.mark.parametrize("chunk", [7, 16, 64])
def test_flash_attention_matches_naive(h, kh, chunk):
    B, S, D = 2, 33, 16
    ks = jax.random.split(KEY, 3)
    q = _rand((B, S, h, D), ks[0], jnp.float32)
    k = _rand((B, S, kh, D), ks[1], jnp.float32)
    v = _rand((B, S, kh, D), ks[2], jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    out = flash_attention(q, k, v, pos, pos, chunk_size=chunk)

    kf = repeat_kv(k, h // kh).astype(jnp.float32)
    vf = repeat_kv(v, h // kh).astype(jnp.float32)
    s = jnp.einsum("bqhd,bshd->bhqs", q / math.sqrt(D), kf)
    mask = pos[:, None, :, None] >= pos[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    naive = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), vf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(naive), atol=1e-5)


def test_decode_attention_matches_prefill_row():
    B, S, H, KH, D = 2, 12, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    k = _rand((B, S, KH, D), ks[0], jnp.float32)
    v = _rand((B, S, KH, D), ks[1], jnp.float32)
    q = _rand((B, H, D), ks[2], jnp.float32)
    kv_len = jnp.array([S, 7], jnp.int32)
    out = decode_attention(q, k, v, kv_len)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    qpos = (kv_len - 1)[:, None]
    full = flash_attention(q[:, None], k, v, qpos, pos, kv_len=kv_len,
                           chunk_size=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, 0]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# SSD: chunked scan vs naive recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 8])
def test_ssd_chunked_matches_recurrence(chunk):
    B, L, H, P, G, N = 2, 16, 4, 8, 2, 8
    ks = jax.random.split(KEY, 5)
    x = _rand((B, L, H, P), ks[0], jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand((B, L, H), ks[1], jnp.float32))
    A = -jnp.exp(_rand((H,), ks[2], jnp.float32) * 0.3)
    B_ = _rand((B, L, G, N), ks[3], jnp.float32) * 0.5
    C_ = _rand((B, L, G, N), ks[4], jnp.float32) * 0.5

    y, final = ssd_chunked(x, dt, A, B_, C_, chunk)

    # naive recurrence oracle
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(L):
        yt, state = ssd_decode_step(x[:, t], dt[:, t], A, B_[:, t], C_[:, t], state)
        ys.append(yt)
    y_naive = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_naive),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(final), np.asarray(state),
                               atol=1e-4, rtol=1e-3)


def test_causal_conv_step_consistency():
    B, L, C, K = 2, 10, 6, 4
    ks = jax.random.split(KEY, 3)
    x = _rand((B, L, C), ks[0], jnp.float32)
    w = _rand((C, K), ks[1], jnp.float32)
    b = _rand((C,), ks[2], jnp.float32)
    full = causal_conv1d(x, w, b)
    state = jnp.zeros((B, K - 1, C))
    outs = []
    for t in range(L):
        o, state = causal_conv1d_step(x[:, t], state, w, b)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(full), np.asarray(jnp.stack(outs, 1)),
                               atol=1e-5)
