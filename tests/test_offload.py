"""Asymmetric K/V offload (core/offload.py + split-residency block
manager + quantized engine payloads).

Covers the exactness chain of the quantized payload formats (round-trip
bitwise identity in lossless mode, bounded one-time error + exact
requantization in lossy mode), the split-half host-tier accounting
(clean spills, keep-K drop policy, LRU drop counters — the old silent
``popitem`` regression), the k-early prefetch V-streaming flow, and the
evict-while-swap-queued safety net under split/quantized payloads.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config, scaled_config
from repro.core import (
    BlockManager,
    CostModel,
    FreqParams,
    HostHalf,
    OffloadConfig,
    analytic_cost_model,
    dequantize_half,
    make_policy,
    quantize_half,
    snap_to_grid_np,
)
from repro.models import init_params
from repro.serving import (
    AsymCacheServer,
    SchedulerConfig,
    ServerConfig,
    multi_turn_workload,
)
from repro.serving.workload import WorkloadConfig
from conftest import assert_drained

BS = 16
GRID = 8.0 / 127.0


@pytest.fixture(scope="module")
def small_model():
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _bm(num_blocks=8, host_blocks=4, offload=None, swap_out_fn=None,
        swap_in_fn=None, block_bytes=None, payload_half_bytes=None,
        pcie_bw=1.2e10, cost_model=None):
    fp = FreqParams.from_turning_point(10.0)
    policy = make_policy("asymcache", fp)
    cm = cost_model or analytic_cost_model(get_config("llama31-8b"))
    return BlockManager(num_blocks, BS, policy, cm, fp,
                       host_blocks=host_blocks, swap_out_fn=swap_out_fn,
                       swap_in_fn=swap_in_fn, offload=offload,
                       block_bytes=block_bytes,
                       payload_half_bytes=payload_half_bytes,
                       pcie_bw=pcie_bw)


def _commit_release(bm, n, start=0, now=1.0):
    """Allocate, commit and release ``n`` blocks of fresh content;
    returns (slots, hashes, tokens)."""
    toks = list(range(start * BS, (start + n) * BS))
    hashes = bm.block_hashes(toks)
    slots = bm.allocate(n, now=now)
    assert slots is not None
    for i, (s, h) in enumerate(zip(slots, hashes)):
        bm.commit(s, h, i)
    bm.release(slots, now=now + 0.5)
    return slots, hashes, toks


# ---------------------------------------------------------------------------
# quantized payload exactness
# ---------------------------------------------------------------------------

def test_lossless_int8_roundtrip_bitwise():
    """Snap-at-write makes the int8 payload round-trip exact BY
    CONSTRUCTION: quantizing snapped values recovers exact codes, and
    dequantizing them reproduces the pool bytes bit-for-bit.  A second
    spill/restore generation must also be a fixed point."""
    rng = np.random.default_rng(0)
    arr = (rng.standard_normal((4, 2, BS, 8)) * 3).astype(np.float32)
    snapped = snap_to_grid_np(arr, "int8", GRID)
    hh = quantize_half(snapped, "q8", static_scale=GRID)
    back = dequantize_half(hh, np.float32)
    assert back.dtype == np.float32
    assert np.array_equal(back, snapped)            # bitwise round-trip
    hh2 = quantize_half(back, "q8", static_scale=GRID)
    assert np.array_equal(hh2.data, hh.data)        # generation-2 fixed point
    # the whole point: ~4x fewer wire bytes than the f32 half
    assert hh.nbytes < snapped.nbytes / 3.5


def test_lossless_fp8_roundtrip_bitwise():
    pytest.importorskip("ml_dtypes")
    rng = np.random.default_rng(1)
    arr = rng.standard_normal((2, 1, BS, 4)).astype(np.float32)
    snapped = snap_to_grid_np(arr, "fp8", 0.0)
    hh = quantize_half(snapped, "f8")
    back = dequantize_half(hh, np.float32)
    assert np.array_equal(back, snapped)
    assert hh.nbytes == snapped.nbytes // 4


def test_lossy_error_bounded_and_requant_exact():
    """Lossy mode: dynamic per-(layer, head) scales bound the first
    restore's error by scale/2 per element; requantizing restored
    content with the REMEMBERED scale recovers identical codes, so the
    error is incurred exactly once."""
    rng = np.random.default_rng(2)
    arr = (rng.standard_normal((3, 2, BS, 4)) * 5).astype(np.float32)
    hh = quantize_half(arr, "q8")                   # dynamic max-abs scale
    back = dequantize_half(hh, np.float32)
    bound = hh.scale[:, :, None, None] * 0.5 + 1e-6
    assert np.all(np.abs(back - arr) <= bound)
    hh2 = quantize_half(back, "q8", scale=hh.scale)
    assert np.array_equal(hh2.data, hh.data)
    assert np.array_equal(hh2.scale, hh.scale)
    # and therefore the second dequantization changes nothing
    assert np.array_equal(dequantize_half(hh2, np.float32), back)


# ---------------------------------------------------------------------------
# host-tier LRU drop accounting (the silent-popitem regression)
# ---------------------------------------------------------------------------

def test_host_lru_drops_are_counted():
    """Over-budget host drops used to be a bare ``popitem`` — invisible
    to every counter.  They must now show up in ``n_host_evictions``
    and keep ``host_resident_bytes`` consistent with the entries."""
    bm = _bm(num_blocks=4, host_blocks=2)
    _commit_release(bm, 4)
    bm.allocate(4, now=3.0)                        # evicts all 4
    c = bm.counters()
    assert c["host_entries"] == 2                  # budget: 2 blocks
    assert c["n_host_evictions"] == 2              # the dropped pair
    assert c["host_resident_bytes"] == \
        sum(e.nbytes for e in bm.host_tier.values())
    assert c["swap_outs"] == 4 and c["evictions"] == 4
    assert c["bytes_swapped_out_k"] == 4 and c["bytes_swapped_out_v"] == 4


def test_keep_k_drop_policy_sheds_v_first():
    """Kcache asymmetry: over budget, the V half goes first and the K
    half of deep-position blocks (positive §4 per-half gain) survives
    as a re-aged remnant; shallow blocks drop entirely.  A kept-K
    remnant is NOT a host hit (the block still needs recomputing)."""
    nb = 1000
    cm = CostModel(k=(0.0, 1.0, 0.0, 0.0, 1.0, 0.0), beta=0.0)
    # swap_latency(nb, bw) = 100; keep K iff block_cost(pos)/2 > 100,
    # i.e. (2*pos + 2) * 16 > 200  <=>  pos_tokens > 4.25 (block_pos >= 1)
    bm = _bm(num_blocks=4, host_blocks=2,
             offload=OffloadConfig(keep_k_half=True), cost_model=cm,
             block_bytes=(nb, nb), pcie_bw=nb / 100.0)
    _, hashes, toks = _commit_release(bm, 4)
    bm.allocate(4, now=3.0)                        # spill all 4, 8000 bytes
    c = bm.counters()
    assert c["host_resident_bytes"] <= 2 * 2 * nb  # byte budget
    # the budget is enforced after EVERY spill: block 0 sheds its V then
    # drops whole (negative gain); blocks 1 and 2 shed V and survive as
    # K remnants
    assert c["n_host_half_drops"] == 3
    assert c["n_host_evictions"] == 1              # block_pos 0: whole drop
    remnants = [e for e in bm.host_tier.values()
                if e.k is not None and e.v is None]
    assert len(remnants) == 2
    assert all(e.block_pos >= 1 for e in remnants)
    # only COMPLETE entries serve host hits
    m = bm.match(toks, now=4.0, acquire=False)
    assert sum(m.host_hits) == len(bm.host_tier) - len(remnants) == 1


def test_retained_host_copy_makes_clean_spills():
    """retain_host: committed content is immutable, so a block whose
    halves the host still holds re-evicts with ZERO bytes moved and no
    pool read — the engine-side swap_out is called only to purge."""
    calls = []
    arr = np.full((2, BS, 1, 4), 0.5, np.float32)
    nb = arr.nbytes

    def swap_out_fn(slot, need_k=True, need_v=True):
        calls.append((slot, need_k, need_v))
        return (arr if need_k else None, arr if need_v else None)

    bm = _bm(num_blocks=4, host_blocks=8,
             offload=OffloadConfig(retain_host=True),
             swap_out_fn=swap_out_fn, swap_in_fn=lambda s, pl: None,
             block_bytes=(nb, nb))
    slots, hashes, toks = _commit_release(bm, 2)
    extra = bm.allocate(2, now=2.0)                # 2 free slots remain
    evictors = bm.allocate(2, now=3.0)             # evicts the released 2
    assert all(c[1] and c[2] for c in calls)       # first spill ships both
    b_out = bm.bytes_swapped_out_k + bm.bytes_swapped_out_v
    assert b_out == 4 * nb
    # restore both blocks (entries are retained in the tier)
    bm.release(extra + evictors, now=3.5)          # uncommitted -> free
    back = bm.allocate(2, now=4.0)
    for i, (s, h) in enumerate(zip(back, hashes)):
        assert bm.swap_in(h, s, i, now=4.0)
    assert len(bm.host_tier) == 2                  # retained after swap-in
    fill = bm.allocate(2, now=4.2)                 # pin down the free pool
    assert fill is not None
    bm.release(back, now=4.5)
    calls.clear()
    bm.allocate(2, now=5.0)                        # re-evict the restored 2
    assert calls and all(not c[1] and not c[2] for c in calls)
    assert bm.bytes_swapped_out_k + bm.bytes_swapped_out_v == b_out  # +0
    assert bm.counters()["clean_half_spills"] == 4


# ---------------------------------------------------------------------------
# k-early prefetch: V streams on acquire; purge paths
# ---------------------------------------------------------------------------

def _k_early_bm():
    shipped = []
    arr = np.arange(2 * BS * 1 * 4, dtype=np.float32).reshape(2, BS, 1, 4)
    nb = arr.nbytes

    def swap_out_fn(slot, need_k=True, need_v=True):
        shipped.append(("out", slot, need_k, need_v))
        return (arr if need_k else None, arr + 1 if need_v else None)

    def swap_in_fn(slot, payload):
        shipped.append(("in", slot, payload[0] is not None,
                        payload[1] is not None))

    bm = _bm(num_blocks=2, host_blocks=8,
             offload=OffloadConfig(k_early_prefetch=True),
             swap_out_fn=swap_out_fn, swap_in_fn=swap_in_fn,
             block_bytes=(nb, nb))
    return bm, shipped


def test_k_early_prefetch_streams_v_on_acquire():
    bm, shipped = _k_early_bm()
    slots, hashes, toks = _commit_release(bm, 2)
    bm.allocate(2, now=3.0)                        # evict both to host
    bm.release(list(range(2)), now=3.5)            # free the pool again
    res = bm.prefetch(hashes[:1], now=4.0, until=9.0)
    assert res["swapped_in"] == 1
    c = bm.counters()
    assert c["k_early_prefetches"] == 1
    # only the K half was shipped at prefetch time
    assert shipped[-1][0] == "in" and shipped[-1][2] and not shipped[-1][3]
    assert c["bytes_swapped_in_k"] > 0 and c["bytes_swapped_in_v"] == 0
    slot = bm.table[hashes[0]]
    assert bm.blocks[slot].v_pending
    # acquiring the block is a DEVICE hit that streams the V half
    m = bm.match(toks[:BS], now=5.0, acquire=True)
    assert m.hit_mask == [True]
    assert shipped[-1] == ("in", slot, False, True)
    c = bm.counters()
    assert c["v_half_streams"] == 1 and c["bytes_swapped_in_v"] > 0
    assert not bm.blocks[slot].v_pending


def test_k_early_block_purged_when_host_v_vanishes():
    bm, shipped = _k_early_bm()
    slots, hashes, toks = _commit_release(bm, 2)
    bm.allocate(2, now=3.0)
    bm.release(list(range(2)), now=3.5)
    bm.prefetch(hashes[:1], now=4.0, until=9.0)
    slot = bm.table[hashes[0]]
    bm._consume_entry(hashes[0])                   # simulate a host drop
    shipped.clear()
    m = bm.match(toks[:BS], now=5.0, acquire=False)
    # can never be completed -> degrades to a lossless recompute miss,
    # purging any queued K half so it cannot clobber the freed slot
    assert m.hit_mask == [False]
    assert bm.counters()["pending_purges"] == 1
    assert ("out", slot, False, False) in shipped
    assert hashes[0] not in bm.table and slot in bm.free


def test_k_early_evict_before_acquire_is_clean():
    """A half-restored (v_pending) block evicted before it was ever
    acquired: the host still holds BOTH halves (the entry was pinned),
    so the spill moves zero bytes, and the engine purge runs."""
    bm, shipped = _k_early_bm()
    slots, hashes, toks = _commit_release(bm, 2)
    bm.allocate(2, now=3.0)
    bm.release(list(range(2)), now=3.5)
    bm.prefetch(hashes[:1], now=4.0, until=4.5)
    slot = bm.table[hashes[0]]
    b_out = bm.bytes_swapped_out_k + bm.bytes_swapped_out_v
    bm.unpin_expired(5.0)                          # pin lapses un-acquired
    shipped.clear()
    taken = bm.allocate(2, now=5.0)                # must re-evict it
    assert slot in taken
    assert ("out", slot, False, False) in shipped  # nothing shipped, purged
    assert bm.bytes_swapped_out_k + bm.bytes_swapped_out_v == b_out
    assert bm.counters()["clean_half_spills"] >= 2
    # the entry survived complete: still a host hit afterwards
    m = bm.match(toks[:BS], now=6.0, acquire=False)
    assert m.host_hits == [True]


# ---------------------------------------------------------------------------
# end-to-end: quantized lossless serving is byte-identical to the
# full-precision-payload control arm (the benchmark gates this at scale)
# ---------------------------------------------------------------------------

def _offload_server(cfg, params, offload, depth=1):
    scfg = ServerConfig(
        policy="asymcache", num_blocks=40, block_size=16, clock="model",
        host_blocks=128, pipeline_depth=depth, offload=offload,
        scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                  max_prefills=2, max_decodes=8))
    return AsymCacheServer(cfg, params, scfg)


def test_quantized_offload_serving_byte_identical(small_model):
    """Same snapped numerics, different wire format: shipping int8
    codes+scales instead of f32 payloads must not change one bit of any
    output — while moving ~4x fewer swap bytes through the engine."""
    cfg, params = small_model
    wl_args = dict(n_sessions=3, turns_per_session=(2, 3),
                   first_ctx_len=(96, 200), output_len=(12, 24),
                   qps=1.0, seed=0)
    base_off = OffloadConfig(quant="int8", payload_fp=True,
                             retain_host=True)
    split_off = OffloadConfig(quant="int8", retain_host=True)

    wl_a = multi_turn_workload(WorkloadConfig(**wl_args))
    srv_a = _offload_server(cfg, params, base_off)
    res_a = srv_a.run(wl_a)
    wl_b = multi_turn_workload(WorkloadConfig(**wl_args))
    srv_b = _offload_server(cfg, params, split_off)
    res_b = srv_b.run(wl_b)
    assert_drained(srv_a)
    assert_drained(srv_b)

    assert res_a["swap_ins"] > 0 and res_b["swap_ins"] == res_a["swap_ins"]
    for a, b in zip(wl_a, wl_b):
        assert a.generated == b.generated
        assert a.sampled_ids == b.sampled_ids
        assert np.array_equal(a.first_logits, b.first_logits)
    # the engine shipped the compressed wire bytes
    sa = srv_a.engine.perf_counters()["swap_bytes_shipped"]
    sb = srv_b.engine.perf_counters()["swap_bytes_shipped"]
    assert sa > 0 and sb * 2 < sa
    # jit lattice unchanged by the split swap queues
    assert srv_b.engine.jit_traces == len(srv_b.engine.buckets_used)
