"""Cross-request prefix sharing: radix trie, copy-on-write forks,
refcounted sharing between concurrent requests, evictor protection of
shared blocks, and the end-to-end suffix-only prefill."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    BlockManager,
    FreqParams,
    PrefixTrie,
    analytic_cost_model,
    chain_hash,
    make_policy,
)


def _mk_bm(policy="asymcache", blocks=32, bs=4, **kw):
    fp = FreqParams.from_turning_point(lifespan=10.0)
    cm = analytic_cost_model(get_config("llama31-8b"))
    return BlockManager(blocks, bs, make_policy(policy, fp), cm, fp, **kw)


# ---------------------------------------------------------------------------
# Radix trie
# ---------------------------------------------------------------------------

def test_trie_insert_and_longest_match():
    t = PrefixTrie()
    t.insert([1, 2, 3, 4, 5, 6])
    assert t.match([1, 2, 3, 4, 5, 6]).length == 6
    assert t.match([1, 2, 3]).length == 3          # mid-edge
    assert t.match([1, 2, 9, 9]).length == 2       # diverges mid-edge
    assert t.match([7, 8]).length == 0
    assert t.match([]).length == 0


def test_trie_edge_split_preserves_both_paths():
    t = PrefixTrie()
    t.insert([1, 2, 3, 4, 5])
    t.insert([1, 2, 3, 9, 9])                      # splits edge after 3
    assert t.match([1, 2, 3, 4, 5]).length == 5
    assert t.match([1, 2, 3, 9, 9]).length == 5
    assert t.match([1, 2, 3, 7]).length == 3
    # split creates: root -> [1,2,3] -> {[4,5], [9,9]}
    assert t.n_nodes() == 4


def test_trie_completions_reconstruct_donor_blocks():
    t = PrefixTrie()
    t.insert([1, 2, 3, 4, 5, 6, 7, 8])
    t.insert([1, 2, 3, 4, 9, 9])
    pm = t.match([1, 2, 3, 4, 100])                # diverges at 4
    assert pm.length == 4
    comps = set(t.completions(pm, 2))
    assert comps == {(5, 6), (9, 9)}
    # dead-end paths shorter than `need` are skipped
    t2 = PrefixTrie()
    t2.insert([1, 2, 3])
    assert list(t2.completions(t2.match([1, 2]), 5)) == []


def test_trie_budget_reset():
    t = PrefixTrie(max_tokens=10)
    t.insert(list(range(100)))
    t.insert(list(range(100, 112)))                # over budget -> reset first
    assert t.n_resets == 1
    assert t.match(list(range(100, 110))).length == 10


# ---------------------------------------------------------------------------
# Refcounted sharing + evictor protection
# ---------------------------------------------------------------------------

def test_two_concurrent_requests_share_blocks():
    """Request B acquires A's committed blocks; refcount 2 pins them until
    BOTH release; the evictor never sees a referenced block."""
    bm = _mk_bm(blocks=8, bs=4)
    toks = list(range(16))                          # 4 blocks
    hashes = bm.block_hashes(toks)
    a_slots = bm.allocate(4, now=1.0)
    for i, (s, h) in enumerate(zip(a_slots, hashes)):
        bm.commit(s, h, i)
    # B matches while A still holds its refs
    m = bm.match(toks, now=2.0, acquire=True)
    assert m.num_hits == 4 and m.hit_slots == a_slots
    assert all(bm.blocks[s].ref_count == 2 for s in a_slots)
    assert all(bm.blocks[s].peak_ref == 2 for s in a_slots)
    assert len(bm.policy) == 0                      # nothing evictable
    # only the 4 unreferenced blocks can be allocated
    assert bm.allocate(5, now=3.0) is None
    # A releases: blocks still pinned by B
    bm.release(a_slots, now=4.0)
    assert bm.allocate(5, now=4.0) is None
    assert all(bm.blocks[s].ref_count == 1 for s in a_slots)
    # B releases: now evictable
    bm.release(a_slots, now=5.0)
    assert len(bm.policy) == 4
    assert bm.allocate(8, now=6.0) is not None


def test_evictor_refuses_pinned_shared_blocks():
    """TTL-pinned shared blocks survive allocation pressure even at ref 0."""
    bm = _mk_bm(blocks=8, bs=4)
    toks = list(range(16))
    hashes = bm.block_hashes(toks)
    slots = bm.allocate(4, now=1.0)
    for i, (s, h) in enumerate(zip(slots, hashes)):
        bm.commit(s, h, i)
    bm.match(toks, now=2.0, acquire=True)           # second sharer
    bm.pin(slots, until=100.0)
    bm.release(slots, now=3.0)
    bm.release(slots, now=3.5)                      # both refs dropped
    assert bm.allocate(8, now=4.0) is None          # pinned: unevictable
    m = bm.match(toks, now=5.0, acquire=False)
    assert m.num_hits == 4


def test_shared_blocks_weighted_in_eviction_objective():
    """peak_ref folds shared savings into the cost term: with equal recency
    and position, the never-shared block is evicted first."""
    bm = _mk_bm(blocks=8, bs=4)
    toks_a = [1] * 4
    toks_b = [2] * 4
    for toks in (toks_a, toks_b):
        (slot,) = bm.allocate(1, now=1.0)
        bm.commit(slot, bm.block_hashes(toks)[0], 0)
    # toks_a acquires a second (concurrent) sharer, then both release
    m = bm.match(toks_a, now=1.0, acquire=True)
    shared_slot = m.hit_slots[0]
    bm.release([s for s in bm.table.values()], now=2.0)
    bm.release([shared_slot], now=2.0)
    bm.free.clear()                                 # force eviction
    victim = bm.policy.evict(now=3.0)
    assert victim is not None and victim != shared_slot


# ---------------------------------------------------------------------------
# Copy-on-write forks
# ---------------------------------------------------------------------------

def test_match_shared_prefix_finds_partial_donor():
    bm = _mk_bm(blocks=16, bs=4)
    donor = [5, 6, 7, 8, 9, 10, 11, 12]             # 2 full blocks
    hashes = bm.block_hashes(donor)
    slots = bm.allocate(2, now=1.0)
    for i, (s, h) in enumerate(zip(slots, hashes)):
        bm.commit(s, h, i)
    bm.register_prefix(donor)
    # requester shares 6 tokens: 1 full block + 2 tokens into block 1
    req = [5, 6, 7, 8, 9, 10, 99, 98]
    matched, donor_slot = bm.match_shared_prefix(req, bm.block_hashes(req))
    assert matched == 6
    assert donor_slot == slots[1]
    # fork: requester's fresh block receives a pending page copy
    (dst,) = bm.allocate(1, now=2.0)
    bm.fork_into(donor_slot, dst, now=2.0)
    assert bm.blocks[donor_slot].ref_count == 2     # protected until drain
    assert bm.drain_pending_copies() == [(donor_slot, dst)]
    bm.release([donor_slot], now=2.0)
    assert bm.n_cow_forks == 1


def test_match_shared_prefix_evicted_donor_degrades_to_miss():
    bm = _mk_bm(blocks=16, bs=4)
    donor = list(range(8))
    bm.register_prefix(donor)                       # trie knows the tokens...
    req = donor[:6] + [99, 98]                      # ...but no block resident
    matched, donor_slot = bm.match_shared_prefix(req, bm.block_hashes(req))
    assert matched == 6
    assert donor_slot is None


# ---------------------------------------------------------------------------
# End-to-end: suffix-only prefill + losslessness through the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    import jax
    from repro.configs import get_smoke_config, scaled_config
    from repro.models import init_params
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _serve(cfg, params, wl, sharing=True, num_blocks=256):
    from repro.serving import (AsymCacheServer, SchedulerConfig,
                               ServerConfig)
    srv = AsymCacheServer(cfg, params, ServerConfig(
        policy="asymcache", num_blocks=num_blocks, block_size=16,
        clock="wall", prefix_sharing=sharing,
        scheduler=SchedulerConfig(token_budget=256, max_chunk=128,
                                  max_prefills=2, max_decodes=8)))
    res = srv.run(wl)
    return res, srv


def test_second_request_computes_only_suffix(small_model):
    """A request arriving after one with the same system prompt prefills
    only its own suffix — the shared prefix is served from cache, with a
    copy-on-write fork covering the partial block."""
    from repro.serving import Request
    cfg, params = small_model
    prefix = [7] * 100                               # 6 blocks + 4 tokens
    wl = [
        Request(rid=0, session_id=0, prompt_tokens=prefix + [11] * 40,
                output_script=[3, 4, 5], arrival=0.0),
        Request(rid=1, session_id=1, prompt_tokens=prefix + [13] * 40,
                output_script=[6, 7, 8], arrival=10.0),
    ]
    res, srv = _serve(cfg, params, wl)
    first, second = wl
    assert first.n_prefill_compute == first.prompt_len
    assert second.prefix_len == 100
    assert second.n_cow_forks == 1
    # all 100 shared positions skipped: 6 full blocks + 4 COW tokens
    assert second.n_prefill_compute == second.prompt_len - 100
    assert all(p >= 100 for p in second.compute_list)
    # losslessness through the forked page
    from repro.serving import reference_logits
    for r in wl:
        ref = reference_logits(cfg, params, r.prompt_tokens)
        rel = float(np.max(np.abs(ref - r.first_logits))) / max(
            1e-9, float(np.max(np.abs(ref))))
        assert rel < 2e-3, (r.rid, rel)


def test_sharing_disabled_recomputes_everything(small_model):
    from repro.serving import Request
    cfg, params = small_model
    prefix = [7] * 100
    mk = lambda: [
        Request(rid=0, session_id=0, prompt_tokens=prefix + [11] * 40,
                output_script=[3, 4, 5], arrival=0.0),
        Request(rid=1, session_id=1, prompt_tokens=prefix + [13] * 40,
                output_script=[6, 7, 8], arrival=10.0),
    ]
    wl = mk()
    res, srv = _serve(cfg, params, wl, sharing=False)
    assert all(r.n_prefill_compute == r.prompt_len for r in wl)
    assert res["cow_forks"] == 0 and res["prefix_matched_tokens"] == 0
    # identical tokens either way (sharing is lossless); the first-token
    # logits come from differently shaped steps (a full prefill against a
    # suffix chunk), which XLA may reduce in another order, so they agree
    # within float32 rounding (rtol 1e-5, atol 1e-6)
    wl_s = mk()
    _serve(cfg, params, wl_s, sharing=True)
    for a, b in zip(wl, wl_s):
        assert a.generated == b.generated
        assert a.sampled_ids == b.sampled_ids and a.sampled_ids
        np.testing.assert_allclose(a.first_logits, b.first_logits,
                                   rtol=1e-5, atol=1e-6)


def test_shared_prefix_workload_properties():
    from repro.serving import SharedPrefixConfig, shared_prefix_workload
    cfg = SharedPrefixConfig(n_jobs=40, shared_fraction=0.7, seed=1)
    wl = shared_prefix_workload(cfg)
    assert len(wl) == 40
    heads = [tuple(r.prompt_tokens[:cfg.system_prefix_len]) for r in wl]
    common = max(set(heads), key=heads.count)
    assert heads.count(common) / len(wl) >= 0.6
    assert cfg.system_prefix_len % 16 != 0          # exercises the COW path
