"""Paged flash-decode kernel (Pallas TPU) — the decode half of MSA.

One new token per sequence attends over its paged KV context.  GQA head
groups are kept together so the MXU contraction is (G×D)·(D×page) per
step: grid (B, KH, NP), sequential over the KV-page axis with flash
running-max/sum scratch, exactly like the prefill kernel but with a
(G, D) q tile per kv head.

In the serving engine a *mixed* batch lowers decode rows into the same
varlen layout as prefill chunks (the paper's POD-attention-style fused
dispatch); this standalone kernel is used by the pure-decode fast path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(
    # scalar prefetch
    block_tables,    # (B, NP)
    context_lens,    # (B,)
    # inputs
    q_ref,           # (1, 1, G, D)
    k_ref,           # (1, 1, page, D)
    v_ref,           # (1, 1, page, D)
    # outputs
    o_ref,           # (1, 1, G, D)
    # scratch
    acc_ref,         # (G, D) f32
    m_ref,           # (G, 1) f32
    l_ref,           # (G, 1) f32
    *,
    page: int,
    num_pages: int,
    window: int,
    softcap: float,
):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = context_lens[b]
    kv_base = j * page
    lo = ctx - window if window > 0 else 0

    @pl.when((kv_base < ctx) & (kv_base + page > lo))
    def _compute():
        d = q_ref.shape[-1]
        scale = 1.0 / math.sqrt(d)
        g = q_ref.shape[2]
        qt = q_ref[0, 0, :, :].astype(jnp.float32) * scale     # (G, D)
        kt = k_ref[0, 0].astype(jnp.float32)                   # (page, D)
        vt = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(qt, kt, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kv_pos = kv_base + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
        mask = kv_pos < ctx
        if window > 0:
            mask = mask & (kv_pos >= ctx - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
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
        o_ref[0, 0, :, :] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def msa_decode_pallas(
    q: jax.Array,              # (B, H, D)
    k_pages: jax.Array,        # (P, KH, page, D) head-major pool
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, NP)
    context_lens: jax.Array,   # (B,)
    *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    p_, kh, page, _ = k_pages.shape
    np_ = block_tables.shape[1]
    grp = h // kh
    qg = q.reshape(b, kh, grp, d)

    def q_index(b_, g_, j_, *refs):
        return (b_, g_, 0, 0)

    def kv_index(b_, g_, j_, block_tables_, context_lens_):
        return (block_tables_[b_, j_], g_, 0, 0)

    grid = (b, kh, np_)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, grp, d), q_index),
            pl.BlockSpec((1, 1, page, d), kv_index),
            pl.BlockSpec((1, 1, page, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, grp, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((grp, d), jnp.float32),
            pltpu.VMEM((grp, 1), jnp.float32),
            pltpu.VMEM((grp, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, page=page, num_pages=np_,
                               window=window, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(b, h, d)
