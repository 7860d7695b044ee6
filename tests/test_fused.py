"""Fused varlen mixed-batch MSA: the varlen oracle against per-sequence
padded-oracle calls, kernel-vs-oracle property sweeps and targeted
batches (page blocks, padding rows, ragged tiles), and the
occupancy-bucket engine invariants (compile-once-per-bucket,
dispatch/padded-token accounting)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.msa import (WL_FIELDS, block_pages, build_worklist,
                               msa_fused)
from repro.kernels.msa import ref as msa_ref
from repro.kernels.msa.msa_fused import narrow_rows

KEY = jax.random.PRNGKey(0)


def _rand(shape, k, dtype=jnp.float32):
    return jax.random.normal(k, shape, jnp.float32).astype(dtype)


def _varlen_meta(rng, *, n_pre, n_dec, page, NP, P, max_run=14):
    """Random mixed varlen batch metadata: ragged multi-segment prefill
    runs plus decode rows, flattened into one stream (numpy only)."""
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
    key = rng.randint(1 << 30)
    return dict(
        bt=rng.randint(0, P, (n, NP)).astype(np.int32),
        ctx=np.asarray(ctx, np.int32),
        q_pos=np.concatenate(q_pos).astype(np.int32),
        seq_ids=seq_ids, q_start=q_start,
        q_len=np.asarray(q_lens, np.int32), n=n, T=T, key=key)


def _varlen_case(rng, *, n_pre, n_dec, page, NP, P, H, KH, D, max_run=14):
    """Random mixed varlen batch: ragged multi-segment prefill runs plus
    decode rows, flattened into one (T, H, D) stream."""
    m = _varlen_meta(rng, n_pre=n_pre, n_dec=n_dec, page=page, NP=NP, P=P,
                     max_run=max_run)
    T = m["T"]
    ks = jax.random.split(jax.random.PRNGKey(m.pop("key")), 3)
    return dict(
        q=_rand((T, H, D), ks[0]),
        k_pages=_rand((P, KH, page, D), ks[1]),
        v_pages=_rand((P, KH, page, D), ks[2]),
        bt=jnp.asarray(m["bt"]), ctx=jnp.asarray(m["ctx"]),
        q_pos=jnp.asarray(m["q_pos"]), seq_ids=jnp.asarray(m["seq_ids"]),
        valid=jnp.ones((T,), bool), q_start=jnp.asarray(m["q_start"]),
        q_len=jnp.asarray(m["q_len"]), n=m["n"], T=T)


def _worklist_for(case, *, page, q_tile, window):
    TQ = min(q_tile, case["T"])
    n_tiles = -(-case["T"] // TQ)
    wl, _, _, _ = build_worklist(
        np.asarray(case["q_start"]), np.asarray(case["q_len"]),
        np.asarray(case["ctx"]), np.asarray(case["bt"]),
        np.asarray(case["q_pos"]), page=page, q_tile=TQ,
        n_tiles=n_tiles, window=window)
    return tuple(jnp.asarray(wl[f]) for f in WL_FIELDS)


# ---------------------------------------------------------------------------
# fused oracle == per-sequence padded-oracle calls
# ---------------------------------------------------------------------------

# float32 tolerance of the fused-vs-per-sequence oracle comparison: the
# two compile to different XLA programs, which may order a reduction
# differently (a few ulps on O(1) outputs)
REF_ATOL, REF_RTOL = 1e-6, 1e-5


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), window=st.sampled_from([0, 11]),
       softcap=st.sampled_from([0.0, 25.0]))
def test_fused_ref_bitwise_matches_split_refs(seed, window, softcap):
    """``msa_fused_ref`` over a mixed stream equals ``msa_prefill_ref``
    over the same sequences laid out one per padded row (prefill chunks
    and decode rows alike), within float32 rounding.  Both sides keep
    one shape across examples (the stream in a 32-row bucket, each
    sequence padded to ``max_run`` rows), so each compiles once."""
    rng = np.random.RandomState(seed)
    page, NP, P, H, KH, D, QP, T_B = 8, 5, 24, 4, 2, 16, 14, 32
    m = _varlen_meta(rng, n_pre=2, n_dec=3, page=page, NP=NP, P=P,
                     max_run=QP)
    T, n = m["T"], m["n"]
    ks = jax.random.split(jax.random.PRNGKey(m["key"]), 3)
    q = _rand((T_B, H, D), ks[0])
    k_pages = _rand((P, KH, page, D), ks[1])
    v_pages = _rand((P, KH, page, D), ks[2])
    q_pos = np.zeros((T_B,), np.int32)
    q_pos[:T] = m["q_pos"]
    seq_ids = np.zeros((T_B,), np.int32)
    seq_ids[:T] = m["seq_ids"]
    o = msa_fused(q, k_pages, v_pages, jnp.asarray(m["bt"]),
                  jnp.asarray(m["ctx"]), jnp.asarray(q_pos),
                  jnp.asarray(seq_ids), jnp.asarray(np.arange(T_B) < T),
                  window=window, softcap=softcap, impl="xla")
    # sequence s's stream rows, padded to QP (pad rows are masked)
    idx = np.zeros((n, QP), np.int32)
    for s, (start, ln) in enumerate(zip(m["q_start"], m["q_len"])):
        idx[s, :ln] = start + np.arange(ln)
    want = msa_ref.msa_prefill_ref(
        q[idx], k_pages, v_pages, jnp.asarray(m["bt"]),
        jnp.asarray(m["ctx"]), jnp.asarray(q_pos[idx]),
        jnp.asarray(m["q_len"]), window=window, softcap=softcap)
    o, want = np.asarray(o), np.asarray(want)
    for s, ln in enumerate(m["q_len"]):
        np.testing.assert_allclose(o[idx[s, :ln]], want[s, :ln],
                                   rtol=REF_RTOL, atol=REF_ATOL,
                                   err_msg=f"sequence {s}")
    assert np.all(o[T:] == 0)


# ---------------------------------------------------------------------------
# fused Pallas kernel (interpret) vs the varlen oracle: property sweep over
# ragged runs, GQA groups, window, softcap, multi-segment gaps, tile sizes
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       h_kh=st.sampled_from([(4, 2), (4, 4), (8, 1), (16, 1), (16, 2)]),
       window=st.sampled_from([0, 9]),
       softcap=st.sampled_from([0.0, 20.0]),
       q_tile=st.sampled_from([4, 8, 16, 32]))
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
    wl, _, _, _ = build_worklist(
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
# page blocks: one work-list item attends its q tile over NB = 128 // page
# gathered pages (NB 8, 2, 1 at pages 16, 64, 128)
# ---------------------------------------------------------------------------

# a context of two full 128-key blocks and a ragged third
LONG_CTX = 2 * 128 + 37


def _scattered_row(n_pages, P):
    """A block-table row whose first half lies descending at the top of
    the pool and second half ascending at the bottom: blocks gather
    out-of-order slots and one straddles the two segments."""
    h = n_pages // 2
    return np.concatenate([P - 1 - 2 * np.arange(h),
                           3 + 3 * np.arange(n_pages - h)])


def _runs_case(runs, *, page, seed, H=4, KH=2, D=16):
    """Explicit varlen batch: ``runs`` lists each sequence's (q
    positions, context length) in stream order."""
    NP = -(-LONG_CTX // page) + 1
    P = 5 * NP + 8                  # room for _scattered_row's two halves
    q_lens = [len(pos) for pos, _ in runs]
    T = sum(q_lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bt = np.stack([np.roll(_scattered_row(NP, P), i) for i in range(len(runs))])
    return dict(
        q=_rand((T, H, D), ks[0]),
        k_pages=_rand((P, KH, page, D), ks[1]),
        v_pages=_rand((P, KH, page, D), ks[2]),
        bt=jnp.asarray(bt, jnp.int32),
        ctx=jnp.asarray([c for _, c in runs], jnp.int32),
        q_pos=jnp.asarray(np.concatenate([p for p, _ in runs]), jnp.int32),
        seq_ids=jnp.asarray(np.repeat(np.arange(len(runs)), q_lens),
                            jnp.int32),
        valid=jnp.ones((T,), bool),
        q_start=jnp.asarray(np.cumsum([0] + q_lens[:-1]), jnp.int32),
        q_len=jnp.asarray(q_lens, jnp.int32),
        n=len(runs), T=T)


# name -> (runs, window, softcap, rows of bucket slack appended, q tile)
_SCATTERED = [(np.r_[40:43, LONG_CTX - 3:LONG_CTX], LONG_CTX),   # 2 segments
              (np.asarray([149]), 150)]
# a ragged two-segment chunk and a short one: 13 rows, so 3 padding rows
# (q position 0) share the second 8-row tile with real ones
_PADDED = [(np.r_[30:38, LONG_CTX - 2:LONG_CTX], LONG_CTX),
           (np.arange(16, 19), 19)]


def _chunk(n):
    """One chunk of ``n`` tokens over two segments of the long context."""
    return [(np.r_[40:40 + n // 2, LONG_CTX - (n - n // 2):LONG_CTX],
             LONG_CTX)]


PAGE_BLOCK_CASES = {
    "scattered_ragged_ctx": (_SCATTERED, 0, 0.0, 0, 8),
    "chunk_and_decodes_share_tile": (
        [(np.arange(200, 205), 205), (np.asarray([76]), 77),
         (np.asarray([129]), 130), (np.asarray([LONG_CTX - 1]), LONG_CTX)],
        0, 0.0, 0, 8),
    # lower edge 292 - 150 + 1 = 143: inside a page and inside a block
    "window_band_inside_block": (
        [(np.arange(250, 256), 256), (np.asarray([LONG_CTX - 1]), LONG_CTX),
         (np.asarray([259]), 260)], 150, 0.0, 0, 8),
    "softcap": (_SCATTERED, 0, 20.0, 0, 8),
    "all_padding_tiles": (_SCATTERED, 0, 0.0, 2 * 8 + 3, 8),
    # one q tile, as in the decode bucket, whose warm-up list emits it
    "warm_lattice_sentinels": ([], 0, 0.0, 8 - 1, 8),
    # padding rows neither attend nor drag their tile's position range
    "padding_rows_in_tile": (_PADDED, 0, 0.0, 3, 8),
    "padding_rows_in_tile_window": (_PADDED, 12, 0.0, 3, 8),
    # a stream that is not a whole number of q tiles rounds up inside
    # the wrapper, and the tile shrinks to a shorter stream
    "ragged_chunk_20_tile_16": (_chunk(20), 0, 0.0, 0, 16),
    "ragged_chunk_5_tile_128": (_chunk(5), 0, 0.0, 0, 128),
    "ragged_chunk_13_tile_8": (_chunk(13), 0, 0.0, 0, 8),
}


@pytest.mark.parametrize("name,page", [
    *((name, 16) for name in PAGE_BLOCK_CASES),
    ("scattered_ragged_ctx", 64), ("scattered_ragged_ctx", 128),
    ("window_band_inside_block", 64), ("window_band_inside_block", 128)])
def test_fused_kernel_page_blocks(name, page):
    """The blocked kernel against the varlen oracle on targeted batches;
    rows of no sequence (bucket slack) come out as exact zeros."""
    runs, window, softcap, pad, TQ = PAGE_BLOCK_CASES[name]
    if runs:
        c = _runs_case(runs, page=page, seed=page)
    else:
        # the warm-up list of bench/harness/serve.py warm_lattice: every
        # item the sentinel row, one init, one emit, all slots 0
        c = _runs_case([(np.asarray([5]), 6)], page=page, seed=page)
        c["q_len"] = jnp.zeros_like(c["q_len"])
        c["valid"] = jnp.zeros_like(c["valid"])
    t_b = c["T"] + pad
    q = jnp.pad(c["q"], ((0, pad), (0, 0), (0, 0)))
    q_pos = jnp.pad(c["q_pos"], (0, pad))
    seq_ids = jnp.pad(c["seq_ids"], (0, pad))
    valid = jnp.pad(c["valid"], (0, pad))
    n_tiles = -(-t_b // TQ)
    if runs:
        wl, n_items, n_pages, _ = build_worklist(
            np.asarray(c["q_start"]), np.asarray(c["q_len"]),
            np.asarray(c["ctx"]), np.asarray(c["bt"]), np.asarray(q_pos),
            page=page, q_tile=TQ, n_tiles=n_tiles, window=window)
        assert set(np.asarray(wl["wl_qtile"])) == set(range(n_tiles))
        assert n_pages > n_items or block_pages(page) == 1
    else:
        w = 64
        wl = {f: np.zeros((w,), np.int32) for f in WL_FIELDS}
        wl["wl_slot"] = np.zeros((w * block_pages(page),), np.int32)
        wl["wl_seq"][:] = c["n"]
        wl["wl_init"][0] = 1
        wl["wl_last"][-1] = 1
    o = msa_fused(q, c["k_pages"], c["v_pages"], c["bt"], c["ctx"], q_pos,
                  seq_ids, valid, q_start=c["q_start"], q_len=c["q_len"],
                  worklist=tuple(jnp.asarray(wl[f]) for f in WL_FIELDS),
                  window=window, softcap=softcap, q_tile=TQ,
                  impl="pallas_interpret")
    o_ref = msa_fused(q, c["k_pages"], c["v_pages"], c["bt"], c["ctx"],
                      q_pos, seq_ids, valid, window=window, softcap=softcap,
                      impl="xla")
    assert float(jnp.max(jnp.abs(o - o_ref))) < 1e-5
    assert np.all(np.asarray(o)[~np.asarray(valid)] == 0.0)


@pytest.mark.parametrize("layer", [0, 2])
def test_fused_kernel_reads_its_layer_of_stacked_pools(layer):
    """Handed the layer-stacked pools and a layer index, the kernel gives
    bitwise what it gives on that layer's pool alone; every other layer
    is NaN, so a page read from any of them would show."""
    runs, window, softcap, _, _ = \
        PAGE_BLOCK_CASES["chunk_and_decodes_share_tile"]
    c = _runs_case(runs, page=16, seed=layer)
    wl = _worklist_for(c, page=16, q_tile=8, window=window)
    stack = [jnp.full_like(c["k_pages"], jnp.nan)] * 3
    k_pools = jnp.stack(stack[:layer] + [c["k_pages"]] + stack[layer + 1:])
    v_pools = jnp.stack(stack[:layer] + [c["v_pages"]] + stack[layer + 1:])
    args = (c["bt"], c["ctx"], c["q_pos"], c["seq_ids"], c["valid"])
    kw = dict(q_start=c["q_start"], q_len=c["q_len"], worklist=wl,
              window=window, softcap=softcap, q_tile=8,
              impl="pallas_interpret")
    want = msa_fused(c["q"], c["k_pages"], c["v_pages"], *args, **kw)
    got = msa_fused(c["q"], k_pools, v_pools, *args, layer=layer, **kw)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.all(np.isfinite(np.asarray(got)))


# decode rows (one-token runs) inside the tiles of prefill chunks and of
# each other: a chunk of 5, three decode rows, a chunk of 3, two decode
# rows (one over three 128-key blocks), so at TQ 8 a decode row's
# 8-row-aligned slice of the tile also holds rows of other sequences
_NARROW_RUNS = [(np.arange(200, 205), 205), (np.asarray([76]), 77),
                (np.asarray([129]), 130), (np.asarray([3]), 4),
                (np.arange(40, 43), 43), (np.asarray([LONG_CTX - 1]),
                                          LONG_CTX),
                (np.asarray([150]), 151)]


@pytest.mark.parametrize("grp,q_tile", [(2, 8), (4, 8), (16, 8), (4, 16)])
def test_fused_kernel_narrow_decode_items(grp, q_tile):
    """A decode row's items compute only its token's query rows (the
    8-row tiles that hold its G heads) while prefill chunks and other
    decode rows share its q tile; the kernel agrees with the varlen
    oracle on every row."""
    assert narrow_rows(grp, q_tile) == max(8, grp)
    c = _runs_case(_NARROW_RUNS, page=16, seed=grp, H=2 * grp, KH=2)
    wl = _worklist_for(c, page=16, q_tile=q_tile, window=0)
    args = (c["q"], c["k_pages"], c["v_pages"], c["bt"], c["ctx"],
            c["q_pos"], c["seq_ids"], c["valid"])
    o = msa_fused(*args, q_start=c["q_start"], q_len=c["q_len"],
                  worklist=wl, q_tile=q_tile, impl="pallas_interpret")
    o_ref = msa_fused(*args, impl="xla")
    assert float(jnp.max(jnp.abs(o - o_ref))) < 1e-5


def test_narrow_rows_only_where_they_save_rows():
    """The narrow item's rows: whole 8-row tiles holding one token's G
    heads, and none where that is the whole tile, the tile is no whole
    number of 8-row tiles, or G heads could straddle two of them."""
    assert [narrow_rows(g, 16) for g in (1, 2, 4, 8, 16, 32)] == \
        [8, 8, 8, 8, 16, 32]
    assert narrow_rows(1, 8) is None and narrow_rows(2, 4) is None
    assert narrow_rows(4, 3) is None and narrow_rows(4, 4) == 8
    assert narrow_rows(3, 16) is None and narrow_rows(12, 16) is None


def test_worklist_counts_narrow_items():
    """Every real item of a decode-only step is narrow; in a mixed step
    the narrow items are the decode rows' items."""
    c = _runs_case(_NARROW_RUNS, page=16, seed=0)
    meta = {k: np.asarray(c[k]) for k in ("q_start", "q_len", "ctx", "bt",
                                           "q_pos")}
    decode = meta["q_len"] == 1
    wl, n_items, _, n_narrow = build_worklist(
        *meta.values(), page=16, q_tile=8, n_tiles=-(-c["T"] // 8))
    real = wl["wl_seq"] < c["n"]
    assert n_items == real.sum()
    assert n_narrow == decode[wl["wl_seq"][real]].sum()
    assert 0 < n_narrow < n_items
    # the decode rows alone, as a decode-only step's stream
    dec = np.flatnonzero(decode)
    q_pos = meta["q_pos"][meta["q_start"][dec]]
    wl, n_items, _, n_narrow = build_worklist(
        np.arange(len(dec), dtype=np.int32), np.ones(len(dec), np.int32),
        meta["ctx"][dec], meta["bt"][dec], q_pos, page=16, q_tile=8,
        n_tiles=1)
    assert n_narrow == n_items == (wl["wl_seq"] < len(dec)).sum() > len(dec)


def _needed_pages(c, *, page, q_tile, n_tiles, window):
    """Page-granular rule, one page at a time: (seq, tile) -> needed
    logical pages."""
    need = {}
    np_width = c["bt"].shape[1]
    for t in range(n_tiles):
        for s in range(c["n"]):
            lo = max(c["q_start"][s], t * q_tile)
            hi = min(c["q_start"][s] + c["q_len"][s], (t + 1) * q_tile)
            if lo >= hi:
                continue
            pos = c["q_pos"][lo:hi]
            wlo = pos.min() - window + 1 if window > 0 else 0
            pages = [j for j in range(min(-(-c["ctx"][s] // page), np_width))
                     if j * page < c["ctx"][s] and j * page <= pos.max()
                     and (j + 1) * page > wlo]
            if pages:
                need[(s, t)] = pages
    return need


@pytest.mark.parametrize("page", [16, 64, 128])
def test_worklist_blocks_the_needed_pages(page):
    """Blocked items = sum over (seq, tile) of ceil(pages / NB); each
    item's NB slots are its sequence's block-table slots in logical
    order (the tail repeats the last one); the block fill counters match."""
    nb = block_pages(page)
    rng = np.random.RandomState(page)
    for trial in range(12):
        window = (0, 37, 300)[trial % 3]
        c = _varlen_meta(rng, n_pre=rng.randint(1, 4),
                         n_dec=rng.randint(0, 5), page=page,
                         NP=-(-900 // page), P=64, max_run=40)
        q_tile = (8, 16, 32)[trial % 3]
        n_tiles = -(-c["T"] // q_tile)
        wl, n_items, n_pages, n_narrow = build_worklist(
            c["q_start"], c["q_len"], c["ctx"], c["bt"], c["q_pos"],
            page=page, q_tile=q_tile, n_tiles=n_tiles, window=window)
        need = _needed_pages(c, page=page, q_tile=q_tile, n_tiles=n_tiles,
                             window=window)
        assert n_items == sum(-(-len(v) // nb) for v in need.values())
        assert n_pages == sum(len(v) for v in need.values())
        assert n_narrow == sum(-(-len(v) // nb) for (s, _), v in need.items()
                               if c["q_len"][s] == 1)
        assert wl["wl_slot"].shape == (wl["wl_seq"].shape[0] * nb,)
        seen = {}
        for i, s in enumerate(wl["wl_seq"]):
            if s == c["n"]:
                continue
            t, j0 = int(wl["wl_qtile"][i]), int(wl["wl_kvbase"][i]) // page
            pages = need[(int(s), t)]
            blk = [j for j in pages if j0 <= j < j0 + nb]
            assert blk == list(range(j0, j0 + len(blk)))
            slots = wl["wl_slot"][i * nb:(i + 1) * nb]
            assert list(slots[:len(blk)]) == list(c["bt"][s, blk])
            assert np.all(slots[len(blk):] == slots[len(blk) - 1])
            seen.setdefault((int(s), t), []).extend(blk)
        assert seen == need


def test_blocked_worklist_fits_the_warm_lattice():
    """The largest blocked list a step of the benchmark's cells can build
    fits the W buckets ``bench/harness/serve.py`` compiles before the
    window, and the engine's capacity for its bucket: prefill chunks that
    straddle the most q tiles at the deepest context, and every decode
    row at the deepest context."""
    import json
    import sys
    from pathlib import Path
    from types import SimpleNamespace
    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench.harness.serve import w_buckets
    from repro.serving.engine import WL_BUCKET, EngineConfig, wl_capacity
    for cell in sorted((root / "bench" / "configs").glob("*.json")):
        e = json.loads(cell.read_text())["engine"]
        ecfg = EngineConfig(
            num_pages=e["num_pages"], page_size=e["page_size"],
            max_prefills=e["max_prefills"], max_chunk=e["max_chunk"],
            max_decodes=e["max_decodes"],
            max_blocks_per_seq=e["max_blocks_per_seq"])
        R, B, page = ecfg.max_prefills, ecfg.max_decodes, ecfg.page_size
        n = R + B
        eng = SimpleNamespace(ecfg=ecfg, n_seqs=n)
        for t_b in e["token_buckets"]:
            for np_b in e["np_buckets"]:
                tq = min(ecfg.q_tile, t_b)
                ctx = np_b * page
                chunk = min(ecfg.max_chunk, max(1, (t_b - B) // R))
                q_len = [chunk] * R + [1] * B
                if sum(q_len) > t_b:
                    q_len = [0] * R + [1] * min(B, t_b)
                    q_len += [0] * (n - len(q_len))
                # with room, the first chunk starts one row into a tile
                q_start = np.cumsum([int(sum(q_len) < t_b)] + q_len[:-1])
                q_pos = np.zeros((t_b,), np.int32)
                for s in range(n):
                    lo = q_start[s]
                    q_pos[lo:lo + q_len[s]] = np.arange(ctx - q_len[s], ctx)
                bt = np.arange(n * np_b).reshape(n, np_b) % ecfg.num_pages
                wl, _, _, _ = build_worklist(
                    q_start, np.asarray(q_len), np.full((n,), ctx), bt,
                    q_pos, page=page, q_tile=tq, n_tiles=-(-t_b // tq))
                w = max(WL_BUCKET, 1 << (wl["wl_seq"].shape[0] - 1)
                        .bit_length())
                assert w in w_buckets(eng, t_b, np_b), (cell.name, t_b, w)
                assert w <= wl_capacity(ecfg, t_b, np_b)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    from repro.configs import get_smoke_config, scaled_config
    from repro.models import init_params
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, KEY)
    return cfg, params


def _mk_server(cfg, params):
    from repro.serving import (AsymCacheServer, EngineConfig,
                               SchedulerConfig, ServerConfig)
    scfg = ServerConfig(
        policy="asymcache", num_blocks=64, block_size=16, clock="model",
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    ecfg = EngineConfig(num_pages=64, page_size=16, max_prefills=2,
                        max_chunk=64, max_decodes=8)
    return AsymCacheServer(cfg, params, scfg, ecfg=ecfg)


def _wl(seed=3):
    from repro.serving import WorkloadConfig, multi_turn_workload
    return multi_turn_workload(WorkloadConfig(
        n_sessions=3, turns_per_session=(2, 3), first_ctx_len=(96, 180),
        output_len=(12, 30), qps=1.0, seed=seed))


def test_every_used_bucket_compiles_exactly_once(small_model):
    """Compile-counter regression across the occupancy lattice: each
    (t_bucket, np_bucket) the workload exercises traces the step exactly
    once; re-running the same workload adds no traces."""
    cfg, params = small_model
    srv = _mk_server(cfg, params)
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
    srv = _mk_server(cfg, params)
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


def test_multi_token_decode_through_pallas_worklist(small_model):
    """The k>1 decode dispatch builds one work-list per fused iteration
    (each with its own slot blocks) inside one packed buffer; through the
    Pallas kernel it samples what the xla oracle's engine samples and
    leaves the same K/V in the pools (every layer past the first writes
    K/V computed from the attention below it)."""
    from repro.serving import (AsymCacheServer, EngineConfig,
                               SchedulerConfig, ServerConfig)
    from repro.serving.workload import decode_burst_workload
    cfg, params = small_model
    runs = {}
    for impl in ("xla", "pallas_interpret"):
        scfg = ServerConfig(
            policy="asymcache", num_blocks=64, block_size=16, clock="model",
            attn_impl=impl,
            scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                      max_prefills=2, max_decodes=4,
                                      max_decode_steps=4))
        ecfg = EngineConfig(num_pages=64, page_size=16, max_prefills=2,
                            max_chunk=64, max_decodes=4,
                            max_blocks_per_seq=8, attn_impl=impl, q_tile=16)
        srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
        # decodes cross the 32-token page boundary inside k>1 dispatches
        wl = decode_burst_workload(n_requests=3, prompt_len=(24, 30),
                                   output_len=(12, 16), seed=2)
        srv.run(wl)
        runs[impl] = (srv, wl)
    srv, wl = runs["pallas_interpret"]
    assert srv.engine.multi_token_dispatches > 0
    for a, b in zip(runs["xla"][1], wl):
        assert a.generated == b.generated
        assert a.sampled_ids == b.sampled_ids and b.sampled_ids
    ref = runs["xla"][0].engine
    for pools, want in ((srv.engine.k_pools, ref.k_pools),
                        (srv.engine.v_pools, ref.v_pools)):
        np.testing.assert_allclose(np.asarray(pools), np.asarray(want),
                                   rtol=0, atol=1e-5)
