"""Work counts and weights of the dense GQA module, against counts made by
hand and against the single-layer formulas and weight ordering the
harness used before architectures were modules."""
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import serve as sv
from bench.harness import weights, work

BENCH = Path(__file__).resolve().parents[1]
dense = sv.load_module(BENCH / "models" / "dense_gqa.py")

M = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 16, "d_ff": 96, "vocab_size": 100}
PEAKS = {"flops": 1e12, "hbm_bytes_s": 1e9}


def test_decode_row():
    # one token at position 99 attends to 100 keys
    seq = work.seq_of_positions([99])
    assert seq.ctx == 100 and list(seq.positions) == [99]
    flops, nbytes = dense.attention_work([seq], M)
    assert flops == 4 * 4 * 16 * 100
    # q in + out: 2 * 1 * 4 * 16; K and V of 100 positions, 2 KV heads
    assert nbytes == 2 * (2 * 4 * 16 + 2 * 100 * 2 * 16)


def test_prefill_chunk():
    # positions 10..13: keys 11 + 12 + 13 + 14 = 50
    seq = work.seq_of_positions(np.arange(10, 14))
    assert seq.ctx == 14
    flops, nbytes = dense.attention_work([seq], M)
    assert flops == 4 * 4 * 16 * 50
    assert nbytes == 2 * (2 * 4 * 4 * 16 + 2 * 14 * 2 * 16)


def test_multi_segment_context_reads_context_once():
    # recomputed positions 0..1 and 6..7 (2..5 cached): K/V of 8 once
    seq = work.seq_of_positions([0, 1, 6, 7])
    assert seq.ctx == 8
    flops, nbytes = dense.attention_work([seq], M)
    assert flops == 4 * 4 * 16 * (1 + 2 + 7 + 8)
    assert nbytes == 2 * (2 * 4 * 4 * 16 + 2 * 8 * 2 * 16)


def test_step_flops_and_roofline():
    seqs = [work.seq_of_positions([99]), work.seq_of_positions(range(4))]
    lin = 64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64 + 3 * 64 * 96
    assert dense.linear_params(M) == lin
    att = 4 * 4 * 16 * (100 + 10)
    want = 2 * (2 * lin * 5 + att) + 2 * 64 * 100 * 2
    assert dense.step_flops(seqs, 2, M) == want
    flops, nbytes = dense.attention_work(seqs, M)
    layers = dense.layer_work(seqs, M)
    assert layers == [{"msa_fused": (flops, nbytes)}] * 2
    assert work.roofline_seconds(layers, "msa_fused", PEAKS) == \
        pytest.approx(2 * max(flops / 1e12, nbytes / 1e9))
    assert work.roofline_seconds(layers, "no_such_kernel", PEAKS) == 0


def test_padding_counts_zero():
    class Plan:
        prefills, decodes = [], []
    seqs, rows = sv.plan_summary(Plan())
    assert (seqs, rows) == ([], 0)
    assert dense.step_flops(seqs, rows, M) == 0
    assert work.roofline_seconds(dense.layer_work(seqs, M), "msa_fused",
                                 PEAKS) == 0


# ---- the single-layer formulas, as the harness had them -------------------
def _old_attention_work(seqs, m, bytes_per=2):
    H, KH, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = nbytes = 0.0
    for n, keys, ctx in seqs:
        flops += 4.0 * H * D * keys
        nbytes += bytes_per * (2.0 * n * H * D + 2.0 * ctx * KH * D)
    return flops, nbytes


def _old_step_flops(seqs, rows, m):
    tokens = sum(n for n, _, _ in seqs)
    att, _ = _old_attention_work(seqs, m)
    return m["n_layers"] * (2.0 * dense.linear_params(m) * tokens + att) \
        + 2.0 * m["d_model"] * m["vocab_size"] * rows


def _old_roofline_seconds(seqs, m, peaks):
    flops, nbytes = _old_attention_work(seqs, m)
    return m["n_layers"] * max(flops / peaks["flops"],
                               nbytes / peaks["hbm_bytes_s"])


@pytest.mark.parametrize("seed", range(8))
def test_summed_layers_equal_old_single_layer_counts(seed):
    """Random steps at granite's widths: prefill chunks of scattered
    positions and decode rows; the per-layer sums equal n_layers x one
    layer to 1e-12 relative."""
    m = {"n_layers": 20, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
         "head_dim": 128, "d_ff": 12800, "vocab_size": 49155}
    peaks = {"flops": 197e12, "hbm_bytes_s": 819e9}
    rng = np.random.default_rng(seed)
    for _ in range(20):
        new, old = [], []
        for _ in range(rng.integers(0, 3)):
            ctx = int(rng.integers(1, 4096))
            pos = np.sort(rng.choice(ctx, size=min(ctx, int(
                rng.integers(1, 1024))), replace=False))
            new.append(work.seq_of_positions(pos))
            old.append((pos.size, int(pos.sum() + pos.size),
                        int(pos.max()) + 1))
        for _ in range(rng.integers(0, 17)):
            p = int(rng.integers(0, 4096))
            new.append(work.Seq(np.array([p]), p + 1))
            old.append((1, p + 1, p + 1))
        rows = int(rng.integers(0, 19))
        assert math.isclose(dense.step_flops(new, rows, m),
                            _old_step_flops(old, rows, m), rel_tol=1e-12)
        assert math.isclose(
            work.roofline_seconds(dense.layer_work(new, m), "msa_fused",
                                  peaks),
            _old_roofline_seconds(old, m, peaks), rel_tol=1e-12,
            abs_tol=0.0)


def _old_make(p, seed, dtype=jnp.bfloat16):
    """The harness's draw before the tree came from a module: blocks in
    their listed order, then the top-level leaves."""
    spec = dense.shapes(p)
    flat = [("blocks", k, s) for k, s in spec["blocks"].items()]
    flat += [(None, k, s) for k, s in spec.items() if k != "blocks"]

    def init(key):
        keys = jax.random.split(key, len(flat))
        out = {"blocks": {}}
        for k_i, (group, name, (shape, std)) in zip(keys, flat):
            leaf = (jax.random.normal(k_i, shape, dtype)
                    * jnp.asarray(std, dtype))
            (out["blocks"] if group else out)[name] = leaf
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.jit(init)(key)


@pytest.mark.parametrize("tied", [True, False])
def test_same_seed_gives_old_weight_tree(tied):
    p = dict(M, tie_embeddings=tied)
    seed = 2**31 + 77
    new = weights.make(dense.shapes(p), seed)
    old = _old_make(p, seed)
    flat_new = jax.tree_util.tree_flatten_with_path(new)[0]
    flat_old = jax.tree_util.tree_flatten_with_path(old)[0]
    assert [k for k, _ in flat_new] == [k for k, _ in flat_old]
    for (_, a), (_, b) in zip(flat_new, flat_old):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert ("lm_head" in new) == (not tied)
