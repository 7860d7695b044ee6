"""The page-granular KV write into the layer-stacked pools.

``write_layer_pages`` gathers the pages a step touches, writes the new
rows into that small buffer and scatters the whole pages back; the pools
it leaves must equal, bit for bit, the row scatter ``write_kv_pages``
applied to the one layer.  The host side (``touched_pages``) must list
every written slot once, map each row back to its slot, and never need
more than ``kv_write_capacity`` entries.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.msa import write_kv_pages
from repro.serving.engine import (EngineConfig, kv_write_capacity,
                                  touched_pages, write_layer_pages)

L, P, KH, PAGE, D = 3, 40, 2, 16, 8
R, B = 2, 4
ECFG = EngineConfig(num_pages=P, page_size=PAGE, max_prefills=R,
                    max_decodes=B)


def _rows(runs, t_bucket, rng, *, invalid_hits_pages=False):
    """Stream rows of ``runs`` — each a request's (block-table row, token
    positions) — then invalid padding rows up to ``t_bucket``.  With
    ``invalid_hits_pages`` the padding rows carry the slots and offsets
    of real pages (the device must still drop them)."""
    slot = np.zeros((t_bucket,), np.int32)
    off = np.zeros((t_bucket,), np.int32)
    valid = np.zeros((t_bucket,), np.int32)
    r = 0
    for bt, pos in runs:
        pos = np.asarray(pos)
        n = pos.shape[0]
        slot[r:r + n] = bt[pos // PAGE]
        off[r:r + n] = pos % PAGE
        valid[r:r + n] = 1
        r += n
    if invalid_hits_pages and r:
        slot[r:] = rng.choice(slot[:r], t_bucket - r)
        off[r:] = rng.randint(0, PAGE, t_bucket - r)
    return slot, off, valid


def _tables(rng, n):
    """Block-table rows of ``n`` requests: disjoint, scattered pool slots."""
    perm = rng.permutation(P).astype(np.int32)
    return [perm[i * 8:(i + 1) * 8] for i in range(n)]


# name -> (runs as (request, positions), token bucket, invalid rows aim at
# written pages)
CASES = {
    # one prefill chunk starting mid-page and straddling three pages
    "chunk_mid_page": (lambda bt: [(bt[0], np.arange(13, 46))], 40, False),
    # two chunks and decode rows, one of them the first row of its page
    "two_chunks_and_decodes": (
        lambda bt: [(bt[0], np.arange(5, 21)), (bt[1], np.arange(30, 34)),
                    (bt[2], [47]), (bt[3], [16]), (bt[4], [0])], 32, False),
    # padding rows whose write coordinates name pages the step writes
    "invalid_rows_on_written_pages": (
        lambda bt: [(bt[0], np.arange(60, 70)), (bt[1], [31])], 24, True),
    # a whole step of padding (the warm-up steps' all-zero inputs)
    "no_valid_rows": (lambda bt: [], 16, False),
}


@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("name", sorted(CASES))
def test_page_write_equals_row_scatter(name, layer):
    runs_of, t_bucket, hits = CASES[name]
    rng = np.random.RandomState(len(name) + layer)
    runs = runs_of(_tables(rng, 5))
    slot, off, valid = _rows(runs, t_bucket, rng, invalid_hits_pages=hits)
    ks = jax.random.split(jax.random.PRNGKey(layer), 4)
    k_pools = jax.random.normal(ks[0], (L, P, KH, PAGE, D))
    v_pools = jax.random.normal(ks[1], (L, P, KH, PAGE, D))
    k_new = jax.random.normal(ks[2], (t_bucket, KH, D))
    v_new = jax.random.normal(ks[3], (t_bucket, KH, D))

    nw = kv_write_capacity(ECFG, t_bucket)
    write_page, write_row = touched_pages(slot, valid, nw, P)
    assert write_page[-1] == P, "the case should carry padding entries"
    got_k, got_v = jax.jit(write_layer_pages, static_argnums=2)(
        k_pools, v_pools, layer, k_new, v_new, jnp.asarray(write_page),
        jnp.asarray(write_row), jnp.asarray(off), jnp.asarray(valid, bool))

    kp, vp = write_kv_pages(k_pools[layer], v_pools[layer], k_new, v_new,
                            jnp.asarray(slot), jnp.asarray(off),
                            jnp.asarray(valid, bool))
    want_k, want_v = k_pools.at[layer].set(kp), v_pools.at[layer].set(vp)
    assert np.array_equal(np.asarray(got_k), np.asarray(want_k))
    assert np.array_equal(np.asarray(got_v), np.asarray(want_v))
    # the step wrote what it should, and nothing on any other layer
    live = valid.astype(bool)
    if live.any():
        assert np.array_equal(
            np.asarray(got_k)[layer, slot[live], :, off[live]],
            np.asarray(k_new)[live])
    others = [i for i in range(L) if i != layer]
    assert np.array_equal(np.asarray(got_k)[others],
                          np.asarray(k_pools)[others])


def _check_touched(slot, valid, nw, num_pages):
    write_page, write_row = touched_pages(slot, valid, nw, num_pages)
    live = valid.astype(bool)
    n = int(np.count_nonzero(write_page < num_pages))
    pages = write_page[:n]
    assert np.all(write_page[n:] == num_pages)
    assert np.unique(pages).shape[0] == n
    assert set(pages.tolist()) == set(slot[live].tolist())
    assert np.array_equal(write_page[write_row[live]], slot[live])
    return n


def _adversarial_step(rng, ecfg, t_bucket, pool):
    """A legal step's write coordinates that touch many pages: up to R
    chunks, each starting one row before a page boundary, then decode
    rows, every request with its own pages."""
    e = ecfg
    n_dec = rng.randint(0, min(e.max_decodes, t_bucket) + 1)
    room = t_bucket - n_dec
    n_pre = rng.randint(0, e.max_prefills + 1) if room else 0
    cuts = np.sort(rng.randint(1, room + 1, n_pre)) if n_pre else []
    lens = np.diff(np.concatenate([[0], cuts])).astype(int)
    slot = np.zeros((t_bucket,), np.int32)
    valid = np.zeros((t_bucket,), np.int32)
    perm = iter(rng.permutation(pool))
    r = 0
    for n in list(lens) + [1] * n_dec:
        if n == 0:
            continue
        first = e.page_size - 1 if rng.rand() < 0.7 else 0
        pos = first + np.arange(n)
        pages = {j: next(perm) for j in np.unique(pos // e.page_size)}
        slot[r:r + n] = [pages[j] for j in pos // e.page_size]
        valid[r:r + n] = 1
        r += n
    return slot, valid


@pytest.mark.parametrize("page,r,b,t_bucket", [
    (16, 2, 16, 16), (16, 2, 16, 2064),      # the benchmark's cells
    (16, 2, 4, 40), (8, 3, 2, 29), (4, 4, 1, 9)])
def test_touched_pages_fit_capacity(page, r, b, t_bucket):
    """Every written slot is listed once, every row maps back to its
    slot, and no legal step needs more than ``kv_write_capacity``."""
    ecfg = EngineConfig(num_pages=1 << 14, page_size=page, max_prefills=r,
                        max_decodes=b)
    nw = kv_write_capacity(ecfg, t_bucket)
    rng = np.random.RandomState(t_bucket)
    most = 0
    for _ in range(60):
        slot, valid = _adversarial_step(rng, ecfg, t_bucket, ecfg.num_pages)
        most = max(most, _check_touched(slot, valid, nw, ecfg.num_pages))
    # two-row chunks straddling a page boundary, then decode rows: the
    # most pages per row a step can touch
    n_dec = min(b, t_bucket - 2 * r)
    runs = [2] * r + [1] * n_dec
    slot = np.arange(sum(runs), dtype=np.int32)
    valid = np.ones_like(slot)
    assert _check_touched(slot, valid, nw, ecfg.num_pages) == sum(runs)
    assert most <= nw


def test_engine_assembly_lists_touched_pages():
    """Every step a served run assembles — single and multi-token — names
    its written slots in ``write_page`` and its rows in ``write_row``,
    and ``kv_pages_written`` sums the pages."""
    from repro.configs import get_smoke_config, scaled_config
    from repro.models import init_params
    from repro.serving import (AsymCacheServer, SchedulerConfig,
                               ServerConfig, decode_burst_workload)
    cfg = scaled_config(get_smoke_config("llama31-8b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServerConfig(
        policy="asymcache", num_blocks=96, block_size=16, clock="model",
        scheduler=SchedulerConfig(token_budget=96, max_chunk=48,
                                  max_prefills=2, max_decodes=4,
                                  max_decode_steps=3))
    ecfg = EngineConfig(num_pages=96, page_size=16, max_prefills=2,
                        max_chunk=48, max_decodes=4, max_blocks_per_seq=16)
    srv = AsymCacheServer(cfg, params, scfg, ecfg=ecfg)
    eng = srv.engine
    counts = []

    def checked(assemble):
        def run(plan, t_b, np_b, *k):
            f = assemble(plan, t_b, np_b, *k)
            nw = kv_write_capacity(eng.ecfg, t_b)
            slots = f["write_slot"].reshape(-1, t_b)
            valid = f["valid"].reshape(-1, t_b)
            pages = f["write_page"].reshape(-1, nw)
            rows = f["write_row"].reshape(-1, t_b)
            for i in range(slots.shape[0]):
                n = _check_touched(slots[i], valid[i], nw, eng.ecfg.num_pages)
                want = touched_pages(slots[i], valid[i], nw,
                                     eng.ecfg.num_pages)
                assert np.array_equal(pages[i], want[0])
                assert np.array_equal(rows[i], want[1])
                counts.append(n)
            return f
        return run

    eng._assemble_fused = checked(eng._assemble_fused)
    eng._assemble_fused_multi = checked(eng._assemble_fused_multi)
    srv.run(decode_burst_workload(n_requests=5, prompt_len=(40, 70),
                                  output_len=(10, 20), seed=3))
    assert eng.multi_token_dispatches > 0
    assert eng.perf_counters()["kv_pages_written"] == sum(counts) > 0
