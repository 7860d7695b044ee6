"""Cross-chip flash-decoding: decode attention over a sequence-sharded KV
cache, combined with the numerically exact log-sum-exp merge.

This is the distributed generalization of Multi-Segment Attention: each
chip's KV shard is one "segment"; per-shard partials (o_i, lse_i) merge as

    m = max_i lse_i;   out = Σ_i e^{lse_i - m}·o_i / Σ_i e^{lse_i - m}

via one psum over the sequence-sharding axes.  Replicated-KV callers
(whisper cross-attention) degenerate gracefully: identical partials merge
to themselves.

Collectives per layer: pmax + 2-term psum over the kv_seq axes (tiny:
(B, H, D) + (B, H)) — this is why sequence-sharding beats head-sharding
for long-context decode in the roofline's collective term.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import context as ctx

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def lse_merge(o: jax.Array, lse: jax.Array, axes) -> jax.Array:
    """Numerically exact cross-shard softmax merge (paper's MSA combine).

    ``o`` is the locally-normalized attention output (numerator / local
    softmax mass), ``lse`` the local log-sum-exp.  Must run inside
    ``shard_map``/``pmap`` over ``axes``.  Rows whose every shard is fully
    masked (``lse == NEG_INF`` everywhere) merge to exact zeros."""
    m = jax.lax.pmax(lse, axes)
    w = jnp.exp(lse - m)                       # NEG_INF-lse rows -> 0
    o_sum = jax.lax.psum(o * w[..., None], axes)
    w_sum = jax.lax.psum(w, axes)
    return o_sum / jnp.maximum(w_sum, 1e-30)[..., None]


def _local_partial(q, k, v, start, kv_len, window, softcap):
    """Partial attention over a local KV shard.

    q: (B, H, D); k/v: (B, S_loc, KH, D); start: global index of this
    shard's first position.  Returns (o (B,H,D) f32, lse (B,H) f32)."""
    b, s_loc, kh, d = k.shape
    h = q.shape[1]
    n_rep = h // kh
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32).reshape(b, kh, n_rep, d) * scale
    # NOTE: no k.astype(f32) — that would materialize the full KV shard in
    # fp32 (2x HBM traffic at decode, which is KV-read bound).  The MXU
    # accumulates in fp32 via preferred_element_type (§Perf iteration C).
    s_ = jnp.einsum("bgrd,bsgd->bgrs", qf.astype(k.dtype), k,
                    preferred_element_type=jnp.float32)
    if softcap and softcap > 0:
        s_ = softcap * jnp.tanh(s_ / softcap)
    gpos = start + jnp.arange(s_loc, dtype=jnp.int32)          # global pos
    mask = gpos[None, None, None, :] < kv_len[:, None, None, None]
    if window is not None:
        weff = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window),
                         jnp.iinfo(jnp.int32).max // 2)
        mask = mask & (gpos[None, None, None, :]
                       >= kv_len[:, None, None, None] - weff)
    s_ = jnp.where(mask, s_, NEG_INF)
    m = jnp.max(s_, axis=-1)                                   # (B,KH,R)
    p = jnp.exp(s_ - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    # normalize o to the "softmax numerator / l" form for stable merging
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, h, d), lse.reshape(b, h)


def sharded_decode_attention(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, kv_len: jax.Array,
                             *, window=None, softcap: float = 0.0) -> jax.Array:
    """q: (B,H,D); k/v_cache: (B,S,KH,D) with S sharded over the context's
    ``kv_seq`` axes and B over the ``batch`` axes."""
    dc = ctx.current()
    assert dc is not None
    mesh = dc.mesh
    seq_axes = dc.rules.get("kv_seq")           # e.g. "model" or ("data","model")
    batch_axes = dc.rules.get("batch")
    if seq_axes is None:
        from repro.models.layers import decode_attention
        return decode_attention(q, k_cache, v_cache, kv_len, window=window,
                                softcap=softcap)
    seq_tuple = (seq_axes,) if isinstance(seq_axes, str) else tuple(seq_axes)
    n_shards = 1
    for a in seq_tuple:
        n_shards *= mesh.shape[a]
    s_total = k_cache.shape[1]
    # non-divisible KV length (whisper cross-attention, 1500 frames):
    # keep the cache replicated over the seq axes; identical partials
    # merge to themselves through the lse combine.
    replicated = (s_total % n_shards) != 0
    s_loc = s_total if replicated else s_total // n_shards

    q_spec = P(batch_axes, None, None)
    kv_spec = P(batch_axes, None if replicated else seq_axes, None, None)
    len_spec = P(batch_axes)

    def local_fn(ql, kl, vl, lenl):
        # shard index along the flattened seq axes
        idx = 0 if replicated else jax.lax.axis_index(seq_tuple)
        start = idx * s_loc
        o, lse = _local_partial(ql, kl, vl, start, lenl, window, softcap)
        return lse_merge(o, lse, seq_tuple).astype(q.dtype)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, len_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k_cache, v_cache, kv_len)


# ---------------------------------------------------------------------------
# Sharded *paged* attention (serving engine)
#
# The paged generalization of the flash-decode merge above: the KV page
# pool (P pages) is sharded over the mesh's ``model`` axis into contiguous
# runs of P/n pages per device, and a sequence's pages are striped across
# shards by the block manager — so each device holds ~1/n of every
# sequence's context.  A device's local pages are one "segment subset";
# per-shard partials (o_i, lse_i) from ``msa_fused_partial_ref`` merge
# exactly through :func:`lse_merge`.  Collectives per layer: pmax + 2-term
# psum over ``model`` (tiny: (T, H, D) + (T, H)), same shape family as the
# dense flash-decode path.
# ---------------------------------------------------------------------------


def sharded_msa_fused(q, k_pool, v_pool, k_new, v_new, write_slot,
                      write_off, valid, bt, context_lens, q_pos, seq_ids,
                      *, mesh, axis: str = "model", window: int = 0,
                      softcap: float = 0.0):
    """One layer's KV page write + fused varlen MSA over a page-sharded
    pool, inside ``shard_map``.  Returns ``(k_pool', v_pool', attn)``.

    ``k_pool``/``v_pool`` are the layer's (P, KH, page, D) pools sharded on
    the page axis over ``axis``; everything else is replicated.  Each shard
    (a) scatters the new tokens whose destination page it owns (non-local
    rows steered out of range and dropped — the same mechanism that drops
    padding rows on one device), then (b) computes the attention partial
    over its local pages only (``page_valid`` masks block-table entries
    owned by other shards), and (c) merges via the exact LSE combine."""
    from repro.kernels.msa.ops import msa_fused_partial, write_kv_pages

    n = mesh.shape[axis]
    p_total = k_pool.shape[0]
    assert p_total % n == 0, (p_total, n)
    p_loc = p_total // n
    pool_spec = P(axis, None, None, None)

    def local_fn(ql, kp, vp, kn, vn, ws, wo, va, bt_, ctx_, pos_, sid):
        i = jax.lax.axis_index(axis)
        lo = i * p_loc
        ls = ws - lo
        local_ok = va & (ls >= 0) & (ls < p_loc)
        kp, vp = write_kv_pages(kp, vp, kn, vn,
                                jnp.where(local_ok, ls, p_loc), wo, local_ok)
        page_valid = (bt_ >= lo) & (bt_ < lo + p_loc)
        o, lse = msa_fused_partial(
            ql, kp, vp, jnp.where(page_valid, bt_ - lo, 0), ctx_, pos_, sid,
            va, page_valid, window=window, softcap=softcap)
        return kp, vp, lse_merge(o, lse, axis).astype(ql.dtype)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), pool_spec, pool_spec, P(), P(), P(), P(), P(), P(),
                  P(), P(), P()),
        out_specs=(pool_spec, pool_spec, P()), check_vma=False,
    )(q, k_pool, v_pool, k_new, v_new, write_slot, write_off, valid, bt,
      context_lens, q_pos, seq_ids)


def sharded_pool_ops(k_pools, v_pools, swap_k_dst, swap_v_dst,
                     swap_k, swap_v, copy_src, copy_dst, *, mesh,
                     axis: str = "model"):
    """Per-shard in-step page maintenance on the full (L, P, ...) pools.

    ``swap_k_dst``/``swap_v_dst``/``copy_src``/``copy_dst`` are (n, S) /
    (n, C) int32 in shard-LOCAL page indices (row i = shard i's queue;
    padding: swap dst == P_loc, copies repeat the last real local pair
    or the identity 0 -> 0).  The K and V swap halves carry independent
    destination buckets (split residency: a V-only swap-in ships no K
    payload).  ``swap_k``/``swap_v`` are (n, L, S, KH, page, D) payloads
    sharded on the leading shard axis (full precision only — quantized
    payloads require the single-device engine).  Cross-shard copies
    cannot be expressed here — the engine routes them through its eager
    fallback."""
    from repro.kernels.msa.ops import apply_page_copies, apply_swap_ins

    pool_spec = P(None, axis, None, None, None)
    swap_spec = P(axis, None, None, None, None, None)

    def local_fn(k, v, skd, svd, sk, sv, cs, cd):
        i = jax.lax.axis_index(axis)
        k, v = apply_swap_ins(k, v, skd[i], svd[i], sk[0], sv[0])
        k, v = apply_page_copies(k, v, cs[i], cd[i])
        return k, v

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(pool_spec, pool_spec, P(), P(), swap_spec, swap_spec,
                  P(), P()),
        out_specs=(pool_spec, pool_spec), check_vma=False,
    )(k_pools, v_pools, swap_k_dst, swap_v_dst, swap_k, swap_v,
      copy_src, copy_dst)
