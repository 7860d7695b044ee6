"""Fused varlen mixed-batch Multi-Segment Attention kernel (Pallas TPU).

One dispatch per layer serves every prefill chunk *and* every decode row
of a step (paper §4.1, Fig. 13: chunks and decode tokens over arbitrary
multi-segment contexts must run as one fused attention call).  The padded
``(R, QP, H, D)`` prefill layout is replaced by a flattened token stream
``(T, H, D)`` with per-sequence ``q_start``/``q_len`` runs — decode rows
are simply runs of length 1 — so ragged chunks stop paying for padding
rows and the decode half stops being a second kernel launch.

Instead of a dense ``(R, H, QT, NP)`` grid that streams all NP pages for
every request, the grid iterates a **compacted (sequence, q-tile,
kv-page) work-list** built on the host at step-assembly time
(:func:`build_worklist`): only pages that intersect a sequence's context,
its causal horizon, and (under a sliding window) its window band ever
become grid steps, so short contexts stop streaming the full page table.
All work-list metadata is scalar-prefetched; the kv-page BlockSpec
index_map streams the *pool slot* recorded in the work-list straight out
of paged HBM.

Layout: the pools are head-major ``(P, KH, page, D)`` and the wrapper
feeds q grouped by KV head, ``(KH, G, T, D)`` with ``G = H / KH``, so
every block ends in a ``(rows, D)`` tile — ``(page, D)`` for K/V,
``(TQ, D)`` per query head for q/out, ``(TQ, 1)`` for the q positions —
which is what Mosaic's (8, 128) tiling accepts.  A size-1 head axis
second to last (the token-major ``(P, page, KH, D)`` layout) is refused
by the TPU compiler.

Grid: ``(KH, W)`` — W iterates sequentially on a TPU core.  One grid
step serves all G query heads of a KV head: the ``(G, TQ, D)`` q block is
one ``(G·TQ, D)`` matmul operand, so each K/V page is fetched once per
KV head (not once per query head) and the grid has KH·W steps, not H·W.
Items of one q tile are consecutive, carrying the flash running max/sum
in VMEM scratch across pages (and across the several sequences that may
share a tile: each item contributes only rows inside its own sequence's
run; the row-wise accumulator merges them exactly).  Work-list padding
items point at the sentinel sequence row N (``q_len == 0``), mask every
row, and are exact no-ops.

VMEM working set at TQ=128, G=4, page=16, D=128: q block 128 KB (bf16),
f32 accumulator 256 KB, two 4 KB K/V pages, double-buffered — well under
the scoped VMEM limit.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# work-list field order — assembly, packing, and the kernel agree on it
WL_FIELDS = ("wl_seq", "wl_qtile", "wl_slot", "wl_kvbase", "wl_init",
             "wl_last")


def build_worklist(
    q_start: np.ndarray,        # (N,) int32 — first stream row per sequence
    q_len: np.ndarray,          # (N,) int32 — run length (0 = inactive row)
    context_lens: np.ndarray,   # (N,) int32
    block_tables: np.ndarray,   # (N, NP) int32 — pool slot per logical page
    q_pos: np.ndarray,          # (T,) int32 — logical position per stream row
    *,
    page: int,
    q_tile: int,
    n_tiles: int,
    window: int = 0,
    pad_to: Optional[int] = None,
) -> Tuple[Dict[str, np.ndarray], int]:
    """Host-side (numpy) construction of the compacted work-list.

    Emits one item per (sequence, q-tile, needed kv page), grouped by q
    tile in ascending order (the kernel's accumulator residency depends
    on items of a tile being consecutive).  A page is *needed* iff it
    starts inside the sequence's context, at or below the tile's causal
    horizon (max valid q_pos), and — under a sliding window — not
    entirely below the window's lower edge.  Returns the field dict and
    the real (pre-padding) item count."""
    n = q_start.shape[0]
    np_width = block_tables.shape[1]
    seqs, qtiles, slots, kvbases, inits, lasts = [], [], [], [], [], []
    for t in range(n_tiles):
        t_lo, t_hi = t * q_tile, (t + 1) * q_tile
        first_of_tile = len(seqs)
        for s in range(n):
            ql = int(q_len[s])
            if ql <= 0:
                continue
            lo = max(int(q_start[s]), t_lo)
            hi = min(int(q_start[s]) + ql, t_hi)
            if lo >= hi:
                continue
            ctx = int(context_lens[s])
            horizon = int(q_pos[lo:hi].max())
            wlo = int(q_pos[lo:hi].min()) - window + 1 if window > 0 else 0
            n_pages = min(-(-ctx // page), np_width)
            for j in range(n_pages):
                base = j * page
                if base >= ctx or base > horizon or base + page <= wlo:
                    continue
                seqs.append(s)
                qtiles.append(t)
                slots.append(int(block_tables[s, j]))
                kvbases.append(base)
                inits.append(0)
                lasts.append(0)
        if len(seqs) > first_of_tile:
            inits[first_of_tile] = 1
            lasts[-1] = 1
        else:
            # all-padding tile (bucket slack): one masked sentinel item
            # that inits+emits, so EVERY output tile is written — exact
            # zeros on invalid rows, matching the oracle (never garbage
            # from an uninitialized buffer)
            seqs.append(n)
            qtiles.append(t)
            slots.append(0)
            kvbases.append(0)
            inits.append(1)
            lasts.append(1)
    count = len(seqs)
    out = {"wl_seq": np.asarray(seqs, np.int32),
           "wl_qtile": np.asarray(qtiles, np.int32),
           "wl_slot": np.asarray(slots, np.int32),
           "wl_kvbase": np.asarray(kvbases, np.int32),
           "wl_init": np.asarray(inits, np.int32),
           "wl_last": np.asarray(lasts, np.int32)}
    if pad_to is not None:
        out = pad_worklist(out, pad_to, sentinel_seq=n)
    return out, count


def pad_worklist(wl: Dict[str, np.ndarray], w: int,
                 sentinel_seq: int) -> Dict[str, np.ndarray]:
    """Pad every work-list field to length ``w`` with exact no-op items:
    the sentinel sequence row (``q_len == 0``) masks every q row, and
    ``wl_qtile`` repeats the last real tile so the output block index
    stays monotone.  THE single source of the padding rules — the engine
    and the kernel's no-op-item invariant both rely on it."""
    count = wl["wl_seq"].shape[0]
    if count > w:
        raise ValueError(f"work-list {count} items > pad_to={w}")
    if count == w:
        return wl
    fills = {"wl_seq": sentinel_seq, "wl_qtile": int(wl["wl_qtile"][-1]),
             "wl_slot": 0, "wl_kvbase": 0, "wl_init": 0, "wl_last": 0}
    return {f: np.concatenate(
        [a, np.full((w - count,), fills[f], np.int32)])
        for f, a in wl.items()}


def _msa_fused_kernel(
    # scalar prefetch (work-list + per-sequence metadata, sentinel row N)
    wl_seq,           # (W,)  sequence row per item
    wl_qtile,         # (W,)  q tile per item
    wl_slot,          # (W,)  pool page slot per item
    wl_kvbase,        # (W,)  logical position of the page start
    wl_init,          # (W,)  1 = first item of its q tile
    wl_last,          # (W,)  1 = last item of its q tile
    q_start,          # (N+1,) stream row where each sequence's run begins
    q_len,            # (N+1,) run length (sentinel row: 0)
    context_lens,     # (N+1,)
    # inputs
    q_pos_ref,        # (G, TQ, 1) int32 — logical positions of this q tile
    q_ref,            # (1, G, TQ, D) — the G query heads of one KV head
    k_ref,            # (1, 1, page, D)
    v_ref,            # (1, 1, page, D)
    # outputs
    o_ref,            # (1, G, TQ, D)
    # scratch
    acc_ref,          # (G*TQ, D) f32
    m_ref,            # (G*TQ, 1) f32
    l_ref,            # (G*TQ, 1) f32
    *,
    page: int,
    window: int,
    softcap: float,
    q_tile: int,
):
    w = pl.program_id(1)
    s = wl_seq[w]
    _, grp, tq, d = q_ref.shape
    rows_g = grp * tq

    @pl.when(wl_init[w] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    scale = 1.0 / math.sqrt(d)
    # the G heads' (TQ, D) tiles stacked into one (G*TQ, D) operand
    qt = q_ref[0].astype(jnp.float32).reshape(rows_g, d) * scale
    kt = k_ref[0, 0].astype(jnp.float32)                        # (page, D)
    vt = v_ref[0, 0].astype(jnp.float32)

    sc = jax.lax.dot_general(qt, kt, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if softcap > 0:
        sc = softcap * jnp.tanh(sc / softcap)

    # rows of this tile that belong to THIS item's sequence run; rows of
    # other sequences sharing the tile are handled by their own items
    rows = wl_qtile[w] * q_tile + jax.lax.broadcasted_iota(
        jnp.int32, (grp, tq, 1), 1).reshape(rows_g, 1)          # (G*TQ, 1)
    row_ok = (rows >= q_start[s]) & (rows < q_start[s] + q_len[s])

    ctx = context_lens[s]
    kv_pos = wl_kvbase[w] + jax.lax.broadcasted_iota(
        jnp.int32, (rows_g, page), 1)
    rel = q_pos_ref[...].reshape(rows_g, 1) - kv_pos            # (G*TQ, page)
    mask = row_ok & (rel >= 0) & (kv_pos < ctx)
    if window > 0:
        mask = mask & (rel < window)
    sc = jnp.where(mask, sc, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    p = jnp.exp(sc - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, vt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(wl_last[w] == 1)
    def _emit():
        # fully masked rows (padding / other sequences' rows already
        # emitted by their items' earlier tiles never reach here with
        # l == 0 except true padding, which emits exact zeros like the ref
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.reshape(grp, tq, d).astype(o_ref.dtype)


def msa_fused_pallas(
    q: jax.Array,              # (T, H, D) flattened mixed token stream
    k_pages: jax.Array,        # (P, KH, page, D) head-major pool
    v_pages: jax.Array,
    q_start: jax.Array,        # (N,) int32
    q_len: jax.Array,          # (N,) int32
    q_pos: jax.Array,          # (T,) int32
    context_lens: jax.Array,   # (N,) int32
    wl_seq: jax.Array,         # (W,) int32 work-list (see build_worklist)
    wl_qtile: jax.Array,
    wl_slot: jax.Array,
    wl_kvbase: jax.Array,
    wl_init: jax.Array,
    wl_last: jax.Array,
    *,
    window: int = 0,
    softcap: float = 0.0,
    q_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    t, h, d = q.shape
    _, kh, page, _ = k_pages.shape
    grp = h // kh
    q_tile = min(q_tile, t)
    n_tiles = -(-t // q_tile)
    t_pad = n_tiles * q_tile
    if t_pad != t:
        q = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, t_pad - t))
    # q grouped by KV head, (KH, G, T, D): one block per (KV head, q tile)
    # carries every query head that reads the same K/V pages.  Only the
    # activations move; the pools are already head-major.
    qg = q.reshape(t_pad, kh, grp, d).transpose(1, 2, 0, 3)
    qpos = jnp.broadcast_to(q_pos.astype(jnp.int32).reshape(1, t_pad, 1),
                            (grp, t_pad, 1))
    # sentinel sequence row N: padding work-list items resolve to it and
    # mask every q row (q_len 0)
    zero = jnp.zeros((1,), jnp.int32)
    qs = jnp.concatenate([q_start.astype(jnp.int32), zero])
    ql = jnp.concatenate([q_len.astype(jnp.int32), zero])
    ctx = jnp.concatenate([context_lens.astype(jnp.int32), zero])

    def qpos_index(g_, w_, wl_seq_, wl_qtile_, *refs):
        return (0, wl_qtile_[w_], 0)

    def q_index(g_, w_, wl_seq_, wl_qtile_, *refs):
        return (g_, 0, wl_qtile_[w_], 0)

    def kv_index(g_, w_, wl_seq_, wl_qtile_, wl_slot_, *refs):
        return (wl_slot_[w_], g_, 0, 0)

    grid = (kh, wl_seq.shape[0])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=grid,
        in_specs=[
            pl.BlockSpec((grp, q_tile, 1), qpos_index),
            pl.BlockSpec((1, grp, q_tile, d), q_index),
            pl.BlockSpec((1, 1, page, d), kv_index),
            pl.BlockSpec((1, 1, page, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, grp, q_tile, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((grp * q_tile, d), jnp.float32),
            pltpu.VMEM((grp * q_tile, 1), jnp.float32),
            pltpu.VMEM((grp * q_tile, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _msa_fused_kernel, page=page, window=window, softcap=softcap,
        q_tile=q_tile)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
        name="msa_fused",
    )(wl_seq.astype(jnp.int32), wl_qtile.astype(jnp.int32),
      wl_slot.astype(jnp.int32), wl_kvbase.astype(jnp.int32),
      wl_init.astype(jnp.int32), wl_last.astype(jnp.int32),
      qs, ql, ctx, qpos, qg, k_pages, v_pages)
    return out.transpose(2, 0, 1, 3).reshape(t_pad, h, d)[:t]
