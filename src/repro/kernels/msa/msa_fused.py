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
page-block) work-list** built on the host at step-assembly time
(:func:`build_worklist`): only pages that intersect a sequence's context,
its causal horizon, and (under a sliding window) its window band are
fetched, so short contexts stop streaming the full page table.  An item
covers up to ``NB = block_pages(page)`` logically consecutive pages of
one sequence (128 keys: 8 pages of 16 tokens), whose pool slots may lie
anywhere in the pool — gathering non-contiguous segments is the point of
MSA.  All work-list metadata is scalar-prefetched.

Layout: the pools are head-major ``(P, KH, page, D)`` and stay in HBM;
the engine hands the kernel its whole layer-stacked ``(L, P, KH, page,
D)`` pools and a scalar-prefetched layer index, so no layer's pool is
ever sliced out into a buffer of its own (a per-layer pool is the
``L = 1`` case);
the wrapper feeds q grouped by KV head and token-major inside it,
``(KH, T·G, D)`` with ``G = H / KH``: row ``t·G + j`` is query head ``j``
of the KV head's group for stream token ``t``, so one token's G heads
are G adjacent rows (the q positions, ``(T·G, 1)``, and the output,
``(KH, T·G, DV)``, alike).  Every block ends in a ``(rows, D)`` tile —
``(page, D)`` for a K/V page, ``(TQ·G, D)`` for q/out, ``(TQ·G, 1)`` for
the q positions — which is what Mosaic's (8, 128) tiling accepts.  A
size-1 head axis second to last (the token-major ``(P, page, KH, D)``
pool layout) is refused by the TPU compiler.

Grid: ``(KH, W)``, both axes sequential on a TPU core.  One grid step
serves all G query heads of a KV head against one item: the item's NB
K pages and NB V pages are DMA'd from the pool slots in ``wl_slot`` into
a double-buffered ``(2, NB, page, D)`` VMEM block (the next item's
copies are in flight while this one computes) and read as one
``(NB·page, D)`` key block, and the step runs one online-softmax update
of the tile's rows against it.  Each K/V page is fetched once per KV
head (not once per query head).  Items of one q tile are consecutive,
carrying the flash running max/sum in VMEM scratch across blocks (and
across the several sequences that may share a tile: each item
contributes only rows inside its own sequence's run; the row-wise
accumulator merges them exactly).  Keys past the needed range (a block's
tail) are masked by logical position.  Work-list padding items point at
the sentinel sequence row N (``q_len == 0``), mask every row, and are
exact no-ops.

Wide and narrow items: an item of a run of several tokens (a prefill
chunk) computes the whole ``(TQ·G, NB·page)`` score tile.  An item of a
one-token run (a decode row: ``q_len == 1``, read from the
scalar-prefetched metadata) computes only the ``R = round_up(G, 8)``
rows of the 8-row-aligned slice that holds its token's G heads
(``narrow_rows``), an ``(R, NB·page)`` score tile, and updates only those
rows of the running max, sum and accumulator; the rest of the tile's
rows are untouched, as a masked row would leave them.  Every needed row
takes the same operations either way.  The narrow path slices the q
block and the scratch in place (no buffer of its own) and is compiled
only where it saves rows: ``R < TQ·G``, ``TQ·G`` a whole number of
8-row tiles, and G a divisor or a multiple of 8 (so G heads never
straddle two 8-row tiles); else every item is wide.

VMEM working set at TQ=128, page=16, NB=8, D=128, bf16 q and pools, for
G=16 (2,048 rows, ChatGLM3's 2 KV groups): q and out blocks 512 KiB each,
double-buffered (2 MiB); q positions ``(TQ·G, 1)`` int32, lane-padded,
1 MiB double-buffered (2 MiB); K/V page buffers 2 x 2 x 32 KiB; f32
accumulator 1 MiB and running max/sum 1 MiB each (lane-padded); f32
score, probability and upcast-q temporaries of ``(2048, 128)`` 1 MiB
each: about 10 MiB by this count, and it compiles for v5e inside the
default scoped VMEM limit (``tests/test_tpu_compile.py``); at G=4 a
quarter of it.  A narrow item's temporaries are ``R`` rows of the same
widths (16 rows at G=16: 8 KiB each), beside the wide ones.

Latent attention (``latent``, a ``LatentLayout``): one cached vector per
token serves every query head as key and, in its first ``rank`` lanes,
as value.  The pool holds one ``(rows, 128)`` tile a page and no V pool
(``latent.py``); the kernel DMAs K tiles only, assembles an item's keys
from whole 8-row, 128-lane slices of them, and scores a query widened
by ``LatentLayout.kernel_q``.  At G=16, TQ=64 and 768 query lanes the q,
output and accumulator blocks and the upcast q take about 12 MiB of VMEM.
A narrow item still assembles its keys and values from its page tiles
once; only its query side shrinks, to 16 of the tile's 1,024 rows.
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

from repro.kernels.msa.latent import LatentLayout

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
# lanes of a vreg: a work-list item covers enough pages to fill a score
# tile's 128 lanes with keys
LANES = 128

# work-list field order — assembly, packing, and the kernel agree on it.
# Every field has one entry per item except ``wl_slot``, which has
# block_pages(page) entries per item (flat, item-major).
WL_FIELDS = ("wl_seq", "wl_qtile", "wl_slot", "wl_kvbase", "wl_init",
             "wl_last")


def block_pages(page: int) -> int:
    """Pages one work-list item covers: ``128 // page`` (at least 1), so
    an item attends its q tile over a full 128-lane tile of keys."""
    return max(1, LANES // page)


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
) -> Tuple[Dict[str, np.ndarray], int, int, int]:
    """Host-side (numpy) construction of the compacted work-list.

    A page is *needed* by a (sequence, q tile) pair iff it starts inside
    the sequence's context, at or below the tile's causal horizon (max
    valid q_pos), and — under a sliding window — not entirely below the
    window's lower edge; the needed pages form one contiguous range of
    logical pages.  That range is cut into blocks of
    ``NB = block_pages(page)`` logically consecutive pages, and each
    block is one item ``(seq, qtile, first page j0)`` whose NB pool
    slots (``wl_slot[item*NB : (item+1)*NB]``, logical order) may sit
    anywhere in the pool.  Slots past the range's end repeat its last
    slot; the kernel masks them by logical position.  Items are grouped
    by q tile in ascending order (the kernel's accumulator residency
    depends on items of a tile being consecutive).

    Returns the field dict, the count of page-block items, the count of
    pages they cover and the count of *narrow* items, those of one-token
    runs (decode rows), which the kernel computes on their token's rows
    alone where ``narrow_rows`` allows (all before padding, sentinel
    items not counted)."""
    n = q_start.shape[0]
    np_width = block_tables.shape[1]
    nb = block_pages(page)
    seqs, qtiles, slots, kvbases, inits, lasts = [], [], [], [], [], []
    n_pages = n_narrow = 0
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
            # needed pages [j_lo, j_hi): end above wlo, start below ctx
            # and at or below the horizon
            j_lo = max(0, wlo // page)
            j_hi = min(-(-ctx // page), np_width, horizon // page + 1)
            row = block_tables[s]
            for j0 in range(j_lo, j_hi, nb):
                j1 = min(j0 + nb, j_hi)
                blk = [int(x) for x in row[j0:j1]]
                seqs.append(s)
                qtiles.append(t)
                slots.extend(blk + blk[-1:] * (nb - len(blk)))
                kvbases.append(j0 * page)
                inits.append(0)
                lasts.append(0)
                n_pages += j1 - j0
                n_narrow += ql == 1
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
            slots.extend([0] * nb)
            kvbases.append(0)
            inits.append(1)
            lasts.append(1)
    n_items = sum(1 for x in seqs if x != n)
    out = {"wl_seq": np.asarray(seqs, np.int32),
           "wl_qtile": np.asarray(qtiles, np.int32),
           "wl_slot": np.asarray(slots, np.int32),
           "wl_kvbase": np.asarray(kvbases, np.int32),
           "wl_init": np.asarray(inits, np.int32),
           "wl_last": np.asarray(lasts, np.int32)}
    if pad_to is not None:
        out = pad_worklist(out, pad_to, sentinel_seq=n)
    return out, n_items, n_pages, n_narrow


def pad_worklist(wl: Dict[str, np.ndarray], w: int,
                 sentinel_seq: int) -> Dict[str, np.ndarray]:
    """Pad the work-list to ``w`` items (``wl_slot`` to ``w`` blocks of
    slots) with exact no-op items: the sentinel sequence row
    (``q_len == 0``) masks every q row, their slots are pool slot 0, and
    ``wl_qtile`` repeats the last real tile so the output block index
    stays monotone.  THE single source of the padding rules — the engine
    and the kernel's no-op-item invariant both rely on it."""
    count = wl["wl_seq"].shape[0]
    if count > w:
        raise ValueError(f"work-list {count} items > pad_to={w}")
    if count == w:
        return wl
    nb = wl["wl_slot"].shape[0] // count
    fills = {"wl_seq": sentinel_seq, "wl_qtile": int(wl["wl_qtile"][-1]),
             "wl_slot": 0, "wl_kvbase": 0, "wl_init": 0, "wl_last": 0}
    return {f: np.concatenate(
        [a, np.full(((w - count) * (nb if f == "wl_slot" else 1),),
                    fills[f], np.int32)])
        for f, a in wl.items()}


def narrow_rows(grp: int, tq: int) -> Optional[int]:
    """Rows a narrow item computes: its token's G query rows rounded up
    to a whole 8-row sublane tile, ``round_up(G, 8)``.  None where no
    narrow item pays: the tile is no wider (``G·TQ`` rows), is no whole
    number of 8-row tiles, or a token's G rows could straddle two 8-row
    tiles (G neither divides 8 nor is a multiple of 8)."""
    r = -(-grp // 8) * 8
    if r >= grp * tq or (grp * tq) % 8 or (8 % grp and grp % 8):
        return None
    return r


def _msa_fused_kernel(
    # scalar prefetch (work-list + per-sequence metadata, sentinel row N)
    wl_seq,           # (W,)  sequence row per item
    wl_qtile,         # (W,)  q tile per item
    wl_slot,          # (W*NB,) pool page slots per item, logical order
    wl_kvbase,        # (W,)  logical position of the block's first page
    wl_init,          # (W,)  1 = first item of its q tile
    wl_last,          # (W,)  1 = last item of its q tile
    q_start,          # (N+1,) stream row where each sequence's run begins
    q_len,            # (N+1,) run length (sentinel row: 0)
    context_lens,     # (N+1,)
    layer,            # (1,)  layer of the stacked pools to attend over
    # inputs
    q_pos_ref,        # (TQ*G, 1) int32 — logical position of each q row
    q_ref,            # (1, TQ*G, D) — one KV head's query rows, token-major
    k_hbm,            # (L, P, KH, page, D) stacked pools, left in HBM
    *refs,
    nb: int,
    page: int,
    scale: float,
    window: int,
    softcap: float,
    q_tile: int,
    latent: Optional[LatentLayout],
    interpret: bool,
):
    """``refs``: ``v_hbm`` (L, P, KH, page, D) unless ``latent``, then
    the output ``o_ref`` (1, TQ*G, DV), and the scratch: ``k_buf`` (2,
    NB, page, D) — this item's pages and the next's —, ``v_buf`` like it
    unless ``latent``, the DMA semaphores (2 [K, V], 2 buffers), the f32
    accumulator ``acc_ref`` (TQ*G, DV) and running max and sum
    ``m_ref``/``l_ref`` (TQ*G, 1).  Row ``r`` of a tile is query head
    ``r % G`` of the tile's token ``r // G``.  Under ``latent`` a page
    is one ``(rows, 128)`` latent tile (``LatentLayout``), K and V both
    come from it, and no V page moves."""
    if latent is not None:
        o_ref, k_buf, sem, acc_ref, m_ref, l_ref = refs
        v_hbm = v_buf = None
    else:
        v_hbm, o_ref, k_buf, v_buf, sem, acc_ref, m_ref, l_ref = refs
    g = pl.program_id(0)
    w = pl.program_id(1)
    s = wl_seq[w]
    lyr = layer[0]
    rows_g = q_ref.shape[1]
    grp = rows_g // q_tile

    def each_page(item, buf, op):
        """``op`` on each of the item's NB K and NB V page copies into
        buffer ``buf``."""
        def body(i, carry):
            slot = wl_slot[item * nb + i]
            op(pltpu.make_async_copy(
                k_hbm.at[lyr, slot, g], k_buf.at[buf, i], sem.at[0, buf]))
            if v_buf is not None:
                op(pltpu.make_async_copy(
                    v_hbm.at[lyr, slot, g], v_buf.at[buf, i],
                    sem.at[1, buf]))
            return carry
        # unrolled on the chip, so the scalar core issues the descriptors
        # back to back; rolled under the interpreter, where unrolling
        # only lengthens tracing
        jax.lax.fori_loop(0, nb, body, 0, unroll=not interpret)

    # double buffering across grid steps: the first item of a KV head
    # fetches its own pages; every item starts the next item's fetch
    # before it waits for its own
    @pl.when(w == 0)
    def _first():
        each_page(0, 0, lambda c: c.start())

    @pl.when(w + 1 < pl.num_programs(1))
    def _prefetch():
        each_page(w + 1, (w + 1) % 2, lambda c: c.start())

    buf = w % 2
    each_page(w, buf, lambda c: c.wait())

    @pl.when(wl_init[w] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # this item's sequence run as tile rows [run_lo, run_hi); rows of
    # other sequences sharing the tile are handled by their own items
    run_lo = (q_start[s] - wl_qtile[w] * q_tile) * grp
    run_hi = run_lo + q_len[s] * grp

    def attend(r0, n_rows):
        """The item's score, mask, online-softmax and PV steps on tile
        rows ``[r0, r0 + n_rows)``: only those rows of the running
        max, sum and accumulator move."""
        rs = pl.ds(r0, n_rows)
        # the item's rows of the (TQ*G, D) operand; the NB gathered pages
        # stacked into one (NB*page, D) block of keys
        qt = q_ref[0, rs, :].astype(jnp.float32) * scale
        if latent is not None:
            kt, vt = _latent_kv(k_buf[buf].astype(jnp.float32), latent)
        else:
            d = k_buf.shape[-1]
            kt = k_buf[buf].astype(jnp.float32).reshape(nb * page, d)
            vt = v_buf[buf].astype(jnp.float32).reshape(nb * page, d)

        sc = jax.lax.dot_general(qt, kt, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if softcap > 0:
            sc = softcap * jnp.tanh(sc / softcap)

        rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0)
        row_ok = (rows >= run_lo) & (rows < run_hi)

        # logical key positions of the block: slots past the needed
        # range land beyond the horizon or the context and are masked
        ctx = context_lens[s]
        kv_off = jax.lax.broadcasted_iota(jnp.int32, (1, nb * page), 1)
        if latent is not None:
            kv_off = _latent_key_offsets(kv_off, nb, latent)
        kv_pos = wl_kvbase[w] + kv_off                           # (1, K)
        rel = q_pos_ref[rs, :] - kv_pos                     # (n_rows, K)
        mask = row_ok & (rel >= 0) & (kv_pos < ctx)
        if window > 0:
            mask = mask & (rel < window)
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_ref[rs, :]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rs, :] = l_ref[rs, :] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
        acc_ref[rs, :] = acc_ref[rs, :] * alpha + jax.lax.dot_general(
            p, vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[rs, :] = m_new

    narrow = narrow_rows(grp, q_tile)
    if narrow is None:
        attend(0, rows_g)
    else:
        # a one-token run (a decode row) needs only its token's G rows:
        # the whole 8-row tiles that hold them
        one_token = q_len[s] == 1

        @pl.when(one_token)
        def _narrow():
            attend(pl.multiple_of(run_lo & -8, 8), narrow)

        @pl.when(jnp.logical_not(one_token))
        def _wide():
            attend(0, rows_g)

    @pl.when(wl_last[w] == 1)
    def _emit():
        # fully masked rows (padding / other sequences' rows already
        # emitted by their items' earlier tiles never reach here with
        # l == 0 except true padding, which emits exact zeros like the ref
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def _latent_kv(tiles, lay: LatentLayout):
    """The item's K (NB*page, q_width) and V (NB*page, rank) from its NB
    latent page tiles (NB, rows, 128), keys in (group, page,
    token-in-group) order: a group's K rows are its tokens' latent
    chunks, then ``pe`` in the group's 128-lane block and zeros in the
    others (``LatentLayout.kernel_q`` puts ``q_pe`` at the group's lanes
    of that block).  Every slice is a whole 8-row, 128-lane tile."""
    nb = tiles.shape[0]
    pg, n = lay.per_group, lay.chunks * lay.page
    pe = tiles[:, n:n + pg, :]
    zeros = jnp.zeros_like(pe)
    ks, vs = [], []
    for grp in range(lay.groups):
        c = [tiles[:, j * lay.page + grp * pg:j * lay.page + (grp + 1) * pg, :]
             for j in range(lay.chunks)]
        blocks = [pe if h == grp else zeros for h in range(lay.groups)]
        ks.append(jnp.concatenate(c + blocks, axis=-1).reshape(
            nb * pg, lay.q_width))
        vs.append(jnp.concatenate(c, axis=-1).reshape(nb * pg, lay.rank))
    return jnp.concatenate(ks, axis=0), jnp.concatenate(vs, axis=0)


def _latent_key_offsets(j, nb: int, lay: LatentLayout):
    """Column ``j``'s key offset from the item's first page under the
    (group, page, token-in-group) order of ``_latent_kv``, by shifts:
    every extent is a power of two."""
    bits = [int(x).bit_length() - 1 for x in (nb * lay.per_group,
                                               lay.per_group, lay.page)]
    assert all(1 << b == x for b, x in zip(
        bits, (nb * lay.per_group, lay.per_group, lay.page))), lay
    grp = jax.lax.shift_right_logical(j, bits[0])
    rem = j & ((1 << bits[0]) - 1)
    pg = jax.lax.shift_right_logical(rem, bits[1])
    i = rem & ((1 << bits[1]) - 1)
    return (pg << bits[2]) + (grp << bits[1]) + i


# jitted so that a step's layers share one trace and one Mosaic lowering
# of the kernel per shape, instead of lowering it again for every layer
# (the layer index is an operand, not a static argument, for that reason)
@functools.partial(jax.jit,
                   static_argnames=("window", "softcap", "q_tile", "latent",
                                    "interpret"))
def msa_fused_pallas(
    q: jax.Array,              # (T, H, D) flattened mixed token stream
    k_pages: jax.Array,        # (L, P, KH, page, D) stacked, or (P, KH,
    v_pages: Optional[jax.Array],  # page, D) one layer's head-major pool
                               # (None with ``latent``)
    q_start: jax.Array,        # (N,) int32
    q_len: jax.Array,          # (N,) int32
    q_pos: jax.Array,          # (T,) int32
    context_lens: jax.Array,   # (N,) int32
    wl_seq: jax.Array,         # (W,) int32 work-list (see build_worklist)
    wl_qtile: jax.Array,
    wl_slot: jax.Array,        # (W*NB,) int32
    wl_kvbase: jax.Array,
    wl_init: jax.Array,
    wl_last: jax.Array,
    layer: jax.Array = 0,      # () int32: the stacked pools' layer to read
    *,
    window: int = 0,
    softcap: float = 0.0,
    q_tile: int = 128,
    latent: Optional[LatentLayout] = None,
    interpret: bool = False,
) -> jax.Array:
    """(T, H, DV) attention output at scale 1/sqrt(D), DV = D.  Under
    ``latent`` q is (T, H, rank + rope), the pools hold latent page
    tiles ``(.., 1, rows, 128)``, ``v_pages`` is not read (None), and DV
    is ``rank``: V is each cached latent."""
    if k_pages.ndim == 4:      # one layer's pool: a stack of one
        k_pages = k_pages[None]
        v_pages = None if latent is not None else v_pages[None]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    t, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    if latent is not None:
        q = latent.kernel_q(q)
        page, d, dv = latent.page, latent.q_width, latent.rank
    else:
        page, dv = k_pages.shape[3], d
    kh = k_pages.shape[2]
    grp = h // kh
    n_items = wl_seq.shape[0]
    nb = block_pages(page)
    if wl_slot.shape[0] != n_items * nb:
        raise ValueError(f"wl_slot has {wl_slot.shape[0]} slots, want "
                         f"{n_items} items x {nb} pages")
    q_tile = min(q_tile, t)
    n_tiles = -(-t // q_tile)
    t_pad = n_tiles * q_tile
    if t_pad != t:
        q = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, t_pad - t))
    # q grouped by KV head and token-major inside it, (KH, T*G, D): one
    # block per (KV head, q tile) carries every query head that reads
    # the same K/V pages, and one token's G heads are G adjacent rows.
    # Only the activations move; the pools are already head-major.
    qg = q.reshape(t_pad, kh, grp, d).transpose(1, 0, 2, 3).reshape(
        kh, t_pad * grp, d)
    qpos = jnp.repeat(q_pos.astype(jnp.int32), grp).reshape(t_pad * grp, 1)
    # sentinel sequence row N: padding work-list items resolve to it and
    # mask every q row (q_len 0)
    zero = jnp.zeros((1,), jnp.int32)
    qs = jnp.concatenate([q_start.astype(jnp.int32), zero])
    ql = jnp.concatenate([q_len.astype(jnp.int32), zero])
    ctx = jnp.concatenate([context_lens.astype(jnp.int32), zero])

    def qpos_index(g_, w_, wl_seq_, wl_qtile_, *refs):
        return (wl_qtile_[w_], 0)

    def q_index(g_, w_, wl_seq_, wl_qtile_, *refs):
        return (g_, wl_qtile_[w_], 0)

    pools = [k_pages] if latent is not None else [k_pages, v_pages]
    page_bufs = [pltpu.VMEM((2, nb) + p.shape[3:], p.dtype) for p in pools]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=10,
        grid=(kh, n_items),
        in_specs=[
            pl.BlockSpec((q_tile * grp, 1), qpos_index),
            pl.BlockSpec((1, q_tile * grp, d), q_index),
        ] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, q_tile * grp, dv), q_index),
        scratch_shapes=page_bufs + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((grp * q_tile, dv), jnp.float32),
            pltpu.VMEM((grp * q_tile, 1), jnp.float32),
            pltpu.VMEM((grp * q_tile, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _msa_fused_kernel, nb=nb, page=page, scale=scale, window=window,
        softcap=softcap, q_tile=q_tile, latent=latent, interpret=interpret)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kh, t_pad * grp, dv), q.dtype),
        # the page prefetch of item w+1 runs during item w, so both axes
        # run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="msa_fused",
    )(wl_seq.astype(jnp.int32), wl_qtile.astype(jnp.int32),
      wl_slot.astype(jnp.int32), wl_kvbase.astype(jnp.int32),
      wl_init.astype(jnp.int32), wl_last.astype(jnp.int32),
      qs, ql, ctx, layer, qpos, qg, *pools)
    return out.reshape(kh, t_pad, grp, dv).transpose(1, 0, 2, 3).reshape(
        t_pad, h, dv)[:t]
