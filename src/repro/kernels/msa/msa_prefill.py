"""Multi-Segment Attention prefill kernel (Pallas TPU).

TPU adaptation of the paper's CUDA/CUTLASS MSA kernel (§4.1): one kernel
call computes attention for a batch of prefill chunks whose KV contexts are
arbitrary interleavings of cached and freshly-computed segments.

Where the CUDA kernel dispatches each segment to a CTA group, here
non-contiguity is expressed through **block-table indirection in the
BlockSpec index_map**: grid step (r, h, qt, j) streams logical KV page j of
request r from wherever it lives in the paged HBM pool into VMEM, and the
causal mask compares *logical* positions (prefetched per-q-token), so any
number of segments works without host-side kernel splitting — the single
fused dispatch the paper identifies as essential (Fig. 13).

Grid: (R, H, QP/TQ, NP) — the last (KV page) axis iterates sequentially on
a TPU core, carrying the flash-attention running max/sum in VMEM scratch.

VMEM working set per step (defaults TQ=128, page=64, D=128, f32 scratch):
  q tile 128·128·2B + k/v pages 2·64·128·2B + acc 128·128·4B + p 128·64·4B
  ≈ 164 KB ≪ 16 MB VMEM; MXU contractions are (128×128)·(128×64).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _msa_prefill_kernel(
    # scalar prefetch
    block_tables,     # (R, NP) int32
    context_lens,     # (R,) int32
    q_lens,           # (R,) int32
    # inputs
    q_pos_ref,        # (1, TQ, 1) int32 — logical positions of this q tile
    q_ref,            # (1, 1, TQ, D)
    k_ref,            # (1, 1, page, D)
    v_ref,            # (1, 1, page, D)
    # outputs
    o_ref,            # (1, 1, TQ, D)
    # scratch
    acc_ref,          # (TQ, D) f32
    m_ref,            # (TQ, 1) f32
    l_ref,            # (TQ, 1) f32
    *,
    page: int,
    num_pages: int,
    window: int,
    softcap: float,
    q_tile: int,
):
    r = pl.program_id(0)
    qt = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = context_lens[r]
    qpos = q_pos_ref[0]                          # (TQ, 1)
    # q rows at padded indices >= q_lens[r] carry qpos 0; they must not
    # attend (the ref zeroes them) and must not drag the tile's position
    # range — a padding qpos of 0 would pull `lo` to the bottom of the
    # sequence and defeat the sliding-window page skip
    rows = qt * q_tile + jax.lax.broadcasted_iota(
        jnp.int32, (q_tile, 1), 0)               # (TQ, 1)
    qvalid = rows < q_lens[r]
    kv_base = j * page
    # page needed iff it starts inside the context and inside the causal
    # horizon of the tile's VALID rows (and, under a sliding window, not
    # fully below their window band); an all-padding tile skips every
    # page and emits exact zeros
    horizon = jnp.max(jnp.where(qvalid, qpos, -1))
    lo = (jnp.min(jnp.where(qvalid, qpos, jnp.int32(2**30)))
          - window + 1) if window > 0 else 0

    @pl.when((kv_base < ctx) & (kv_base <= horizon) & (kv_base + page > lo))
    def _compute():
        d = q_ref.shape[-1]
        scale = 1.0 / math.sqrt(d)
        qt = q_ref[0, 0].astype(jnp.float32) * scale            # (TQ, D)
        kt = k_ref[0, 0].astype(jnp.float32)                    # (page, D)
        vt = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(qt, kt, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)

        kv_pos = kv_base + jax.lax.broadcasted_iota(jnp.int32, (q_tile, page), 1)
        rel = qpos - kv_pos
        mask = qvalid & (rel >= 0) & (kv_pos < ctx)
        if window > 0:
            mask = mask & (rel < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == num_pages - 1)
    def _emit():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def msa_prefill_pallas(
    q: jax.Array,              # (R, QP, H, D)
    k_pages: jax.Array,        # (P, KH, page, D) head-major pool
    v_pages: jax.Array,
    block_tables: jax.Array,   # (R, NP) int32
    context_lens: jax.Array,   # (R,) int32
    q_pos: jax.Array,          # (R, QP) int32
    q_lens: jax.Array,         # (R,) int32
    *,
    window: int = 0,
    softcap: float = 0.0,
    q_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    r, qp, h, d = q.shape
    p_, kh, page, _ = k_pages.shape
    np_ = block_tables.shape[1]
    assert qp % q_tile == 0, (qp, q_tile)
    qt_per_req = qp // q_tile
    grp = h // kh

    # q head-major, (R, H, QP, D), and positions as a (R, QP, 1) column:
    # every block then ends in a (TQ, D) or (TQ, 1) tile.  Only the
    # activations move; the pools are already head-major.
    qh = q.transpose(0, 2, 1, 3)
    qpos = q_pos.astype(jnp.int32).reshape(r, qp, 1)

    def q_index(r_, h_, qt_, j_, *refs):
        return (r_, h_, qt_, 0)

    def qpos_index(r_, h_, qt_, j_, *refs):
        return (r_, qt_, 0)

    def kv_index(r_, h_, qt_, j_, block_tables_, context_lens_, q_lens_):
        return (block_tables_[r_, j_], h_ // grp, 0, 0)

    grid = (r, h, qt_per_req, np_)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_tile, 1), qpos_index),
            pl.BlockSpec((1, 1, q_tile, d), q_index),
            pl.BlockSpec((1, 1, page, d), kv_index),
            pl.BlockSpec((1, 1, page, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, q_tile, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((q_tile, d), jnp.float32),
            pltpu.VMEM((q_tile, 1), jnp.float32),
            pltpu.VMEM((q_tile, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _msa_prefill_kernel, page=page, num_pages=np_, window=window,
        softcap=softcap, q_tile=q_tile)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      q_lens.astype(jnp.int32), qpos, qh, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3)
