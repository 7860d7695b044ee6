"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes/dtypes, plus hypothesis property tests on the MSA
contract (multi-segment causal masking)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.msa import msa_decode, msa_prefill, write_kv_pages
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
# MSA prefill kernel: shape/dtype sweep vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kh,d", [(4, 2, 32), (4, 4, 16), (8, 2, 64)])
@pytest.mark.parametrize("page,q_tile", [(8, 8), (16, 4)])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (12, 0.0), (0, 30.0)])
def test_msa_prefill_sweep(dtype, h, kh, d, page, q_tile, window, softcap):
    R, QP, NP, P = 2, 16, 5, 32
    ks = jax.random.split(KEY, 4)
    q = _rand((R, QP, h, d), ks[0], dtype)
    k_pages = _rand((P, kh, page, d), ks[1], dtype)
    v_pages = _rand((P, kh, page, d), ks[2], dtype)
    bt = jax.random.randint(ks[3], (R, NP), 0, P).astype(jnp.int32)
    ctx = jnp.array([NP * page, 2 * page + 3], jnp.int32)
    q_pos = jnp.stack([
        jnp.concatenate([jnp.arange(3, 3 + QP // 2),
                         jnp.arange(NP * page - QP // 2, NP * page)]),
        jnp.arange(QP),
    ]).astype(jnp.int32)
    q_lens = jnp.array([QP, QP - 3], jnp.int32)

    o_ref = msa_prefill(q, k_pages, v_pages, bt, ctx, q_pos, q_lens,
                        window=window, softcap=softcap, impl="xla")
    o_pal = msa_prefill(q, k_pages, v_pages, bt, ctx, q_pos, q_lens,
                        window=window, softcap=softcap, q_tile=q_tile,
                        impl="pallas_interpret")
    valid = (jnp.arange(QP)[None, :] < q_lens[:, None])[..., None, None]
    err = float(jnp.max(jnp.abs(jnp.where(
        valid, o_ref.astype(jnp.float32) - o_pal.astype(jnp.float32), 0))))
    assert err < _tol(dtype), err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kh,d", [(4, 2, 32), (8, 8, 16), (8, 1, 64)])
@pytest.mark.parametrize("window", [0, 10])
def test_msa_decode_sweep(dtype, h, kh, d, window):
    B, NP, P, page = 3, 6, 24, 8
    ks = jax.random.split(KEY, 4)
    q = _rand((B, h, d), ks[0], dtype)
    k_pages = _rand((P, kh, page, d), ks[1], dtype)
    v_pages = _rand((P, kh, page, d), ks[2], dtype)
    bt = jax.random.randint(ks[3], (B, NP), 0, P).astype(jnp.int32)
    ctx = jnp.array([NP * page, 17, 1], jnp.int32)
    o_ref = msa_decode(q, k_pages, v_pages, bt, ctx, window=window, impl="xla")
    o_pal = msa_decode(q, k_pages, v_pages, bt, ctx, window=window,
                       impl="pallas_interpret")
    err = float(jnp.max(jnp.abs(o_ref.astype(jnp.float32)
                                - o_pal.astype(jnp.float32))))
    assert err < _tol(dtype), err


# ---------------------------------------------------------------------------
# MSA semantics: the paper's Eq. 2 — multi-segment == concatenated attention
# ---------------------------------------------------------------------------

def test_msa_equals_contiguous_attention():
    """A paged multi-segment context must give bit-identical semantics to
    ordinary causal attention over the logically contiguous sequence."""
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
    k_pages = jnp.zeros((16, KH, page, D))
    v_pages = jnp.zeros((16, KH, page, D))
    for j in range(NP):
        k_pages = k_pages.at[perm[j]].set(k_full[0, j * page:(j + 1) * page].transpose(1, 0, 2))
        v_pages = v_pages.at[perm[j]].set(v_full[0, j * page:(j + 1) * page].transpose(1, 0, 2))
    bt = jnp.asarray(perm)[None, :].astype(jnp.int32)
    o_paged = msa_prefill(q_full, k_pages, v_pages, bt,
                          jnp.array([S], jnp.int32), pos,
                          jnp.array([S], jnp.int32), impl="xla")
    np.testing.assert_allclose(np.asarray(o_dense), np.asarray(o_paged),
                               atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    n_seg=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_msa_segment_merge_property(n_seg, seed):
    """Property: attention over q tokens split across arbitrary gap
    structures equals attention computed over the same logical positions
    contiguously (Eq. 2 generalized to any segment count)."""
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
    k_pages = jnp.zeros((NP + 4, KH, page, D))
    v_pages = jnp.zeros((NP + 4, KH, page, D))
    for j in range(NP):
        k_pages = k_pages.at[perm[j]].set(k_full[0, j * page:(j + 1) * page].transpose(1, 0, 2))
        v_pages = v_pages.at[perm[j]].set(v_full[0, j * page:(j + 1) * page].transpose(1, 0, 2))
    bt = jnp.asarray(perm)[None, :].astype(jnp.int32)
    o_paged = msa_prefill(q, k_pages, v_pages, bt, jnp.array([S], jnp.int32),
                          q_pos, jnp.array([len(idx)], jnp.int32), impl="xla")
    np.testing.assert_allclose(np.asarray(o_dense), np.asarray(o_paged),
                               atol=1e-5)


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
