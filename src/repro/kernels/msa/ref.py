"""Pure-jnp oracles for the Multi-Segment Attention kernel.

These define the exact contract the fused Pallas kernel implements:

Paged KV layout: ``k_pages``/``v_pages`` are head-major (P, KH, page, D)
pools, so one (page, D) tile of one KV head is contiguous — the block
shape the TPU kernel streams (Mosaic tiles the last two dims).  A
request's logical KV space is mapped to pool pages through its row of
``block_tables`` (R, NP): logical block j lives in pool page
``block_tables[r, j]``.  *Multi-segment* contexts need no special casing —
non-contiguity exists only in pool-slot space; logical positions stay
dense, and the causal mask compares logical positions.  Gaps being
recomputed have had their K/V written into freshly allocated pages before
the attention call, so attention always reads a fully materialized context.

``msa_prefill_ref``: q is (R, QP, H, D) — each request's *compute* tokens
(padded to QP).  ``q_pos`` (R, QP) gives each compute token's logical
position — these may be non-contiguous runs (the chunk can span several
cache gaps).  ``msa_fused_ref`` is the same contract over the flattened
varlen (T, H, D) stream the engine serves, where a decode row is a run
of one token at logical position ``context_lens[s] - 1``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.msa.latent import LatentLayout

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _gather_kv(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(P, KH, page, D), (R, NP) -> (R, NP*page, KH, D)."""
    r, np_ = block_tables.shape
    p, kh, page, d = pages.shape
    out = pages[block_tables]            # (R, NP, KH, page, D)
    return out.transpose(0, 1, 3, 2, 4).reshape(r, np_ * page, kh, d)


def msa_prefill_ref(
    q: jax.Array,              # (R, QP, H, D)
    k_pages: jax.Array,        # (P, KH, page, D)
    v_pages: jax.Array,        # (P, KH, page, D)
    block_tables: jax.Array,   # (R, NP) int32
    context_lens: jax.Array,   # (R,) int32 — total logical kv length
    q_pos: jax.Array,          # (R, QP) int32 logical position per q token
    q_lens: jax.Array,         # (R,) int32 valid q rows
    *,
    window: int = 0,           # 0 = full causal
    softcap: float = 0.0,
    latent: Optional[LatentLayout] = None,
) -> jax.Array:
    r, qp, h, d = q.shape
    kh = k_pages.shape[1]
    n_rep = h // kh
    scale = 1.0 / math.sqrt(d)

    if latent is not None:                  # latent tiles -> cached vectors
        k_pages = latent.from_tile(k_pages)
    k = _gather_kv(k_pages, block_tables)   # (R, S, KH, D)
    v = (k[..., :latent.rank] if latent is not None
         else _gather_kv(v_pages, block_tables))
    s_len = k.shape[1]

    # GQA via grouped heads: fold the query-head replication into the
    # einsum instead of materializing jnp.repeat'ed (R, S, H, D) K/V
    # copies — the repeat doubled the step's memory traffic and dominated
    # the XLA step time on CPU
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = (q.astype(jnp.float32) * scale).reshape(r, qp, kh, n_rep, d)

    scores = jnp.einsum("rqhgd,rshd->rhgqs", qf, kf)    # (R, KH, G, QP, S)
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)

    kv_pos = jnp.arange(s_len, dtype=jnp.int32)
    mask = kv_pos[None, None, :] < context_lens[:, None, None]
    rel = q_pos[:, :, None] - kv_pos[None, None, :]
    mask = mask & (rel >= 0)
    if window > 0:
        mask = mask & (rel < window)
    qvalid = (jnp.arange(qp, dtype=jnp.int32)[None, :] < q_lens[:, None])
    mask = (mask & qvalid[:, :, None])[:, None, None]   # (R, 1, 1, QP, S)

    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(mask, p, 0.0)             # fully-masked rows -> 0
    out = jnp.einsum("rhgqs,rshd->rqhgd", p, vf)
    return out.reshape(r, qp, h, vf.shape[-1]).astype(q.dtype)


def msa_fused_ref(
    q: jax.Array,              # (T, H, D) flattened mixed token stream
    k_pages: jax.Array,        # (P, KH, page, D)
    v_pages: jax.Array,
    block_tables: jax.Array,   # (N, NP) int32 — one row per sequence
    context_lens: jax.Array,   # (N,) int32
    q_pos: jax.Array,          # (T,) int32 logical position per token
    seq_ids: jax.Array,        # (T,) int32 — owning sequence row per token
    q_valid: jax.Array,        # (T,) bool — padding rows are False
    *,
    window: int = 0,
    softcap: float = 0.0,
    latent: Optional[LatentLayout] = None,
) -> jax.Array:
    """Varlen oracle for the fused mixed-batch MSA dispatch.

    Prefill chunks and decode rows share one flattened ``(T, H, D)``
    stream; each token resolves its paged context through its sequence's
    row of ``block_tables``.  Implemented by delegation to
    :func:`msa_prefill_ref` viewed as T single-token requests, so every
    valid row equals a per-sequence :func:`msa_prefill_ref` call over the
    same operands up to float32 rounding (XLA may order a reduction
    differently for the two shapes); invalid rows are zeros, as in the
    padded ref.  Under ``latent`` (latent attention) the pool holds
    latent page tiles (``LatentLayout``), ``v_pages`` is not read, K is
    each cached vector and V its first ``rank`` lanes, and the output is
    ``rank`` wide."""
    out = msa_prefill_ref(
        q[:, None], k_pages, v_pages,
        block_tables[seq_ids], context_lens[seq_ids],
        q_pos[:, None], q_valid.astype(jnp.int32),
        window=window, softcap=softcap, latent=latent)
    return out[:, 0]


def msa_fused_partial_ref(
    q: jax.Array,              # (T, H, D) flattened mixed token stream
    k_pages: jax.Array,        # (P_loc, KH, page, D) — a LOCAL pool shard
    v_pages: jax.Array,
    block_tables: jax.Array,   # (N, NP) int32 — LOCAL page ids
    context_lens: jax.Array,   # (N,) int32
    q_pos: jax.Array,          # (T,) int32
    seq_ids: jax.Array,        # (T,) int32
    q_valid: jax.Array,        # (T,) bool
    page_valid: jax.Array,     # (N, NP) bool — False = page lives elsewhere
    *,
    window: int = 0,
    softcap: float = 0.0,
):
    """Partial varlen MSA over a *subset* of a context's pages, in the
    normalized ``(o, lse)`` form of the multi-segment/flash-decode merge:

        o   = softmax-weighted V restricted to the valid pages
        lse = log-sum-exp of the restricted scores

    This is the per-shard term of the distributed generalization of MSA:
    each device's local page pool is one "segment subset"; partials merge
    exactly via ``pmax``/``psum`` over the kv-sharding axis (see
    ``repro.distributed.flash_decode``).  With ``page_valid`` all-True and
    one shard, ``exp(lse)``-weighting recovers :func:`msa_fused_ref` up to
    f32 summation order.

    Tokens with no valid page in context (all their KV lives on other
    shards) return ``lse = NEG_INF`` and ``o = 0`` — a zero-weight term in
    the merge.  Returns ``(o (T, H, D) f32, lse (T, H) f32)``."""
    t, h, d = q.shape
    kh = k_pages.shape[1]
    page = k_pages.shape[2]
    n_rep = h // kh
    scale = 1.0 / math.sqrt(d)

    bt = block_tables[seq_ids]                      # (T, NP)
    k = _gather_kv(k_pages, bt)                     # (T, S, KH, D)
    v = _gather_kv(v_pages, bt)
    s_len = k.shape[1]

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = (q.astype(jnp.float32) * scale).reshape(t, 1, kh, n_rep, d)
    scores = jnp.einsum("tqhgd,tshd->thgqs", qf, kf)[:, :, :, 0, :]
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)  # (T, KH, G, S)

    ctx = context_lens[seq_ids]                     # (T,)
    kv_pos = jnp.arange(s_len, dtype=jnp.int32)
    mask = kv_pos[None, :] < ctx[:, None]
    rel = q_pos[:, None] - kv_pos[None, :]
    mask = mask & (rel >= 0)
    if window > 0:
        mask = mask & (rel < window)
    pv = page_valid[seq_ids]                        # (T, NP)
    mask = mask & jnp.repeat(pv, page, axis=1)
    mask = (mask & q_valid[:, None])[:, None, None, :]   # (T, 1, 1, S)

    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                    # (T, KH, G)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("thgs,tshd->thgd", p, vf)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    # fully-masked rows: l == 0 -> o already 0; pin lse to NEG_INF so the
    # cross-shard merge gives them zero weight
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    return o.reshape(t, h, d), lse.reshape(t, h)


def write_kv_pages(
    k_pages: jax.Array,        # (P, KH, page, D)
    v_pages: jax.Array,
    k_new: jax.Array,          # (T, KH, D)
    v_new: jax.Array,
    slot_ids: jax.Array,       # (T,) int32 — pool page per new token
    slot_offsets: jax.Array,   # (T,) int32 — offset within page
    valid: jax.Array,          # (T,) bool
):
    """Scatter freshly computed K/V into the paged pool (pre-attention).

    Invalid (padding) rows are routed out of range and dropped by the
    scatter itself — no read-modify-write, stays a pure scatter.  The
    (T, KH, D) rows land at ``[slot, :, offset]`` of the head-major pool.
    A latent cache has no V half: ``v_pages`` None is passed through."""
    p = k_pages.shape[0]
    oob = jnp.where(valid, slot_ids, p)     # out-of-range -> dropped
    k_pages = k_pages.at[oob, :, slot_offsets].set(k_new, mode="drop")
    if v_pages is not None:
        v_pages = v_pages.at[oob, :, slot_offsets].set(v_new, mode="drop")
    return k_pages, v_pages
