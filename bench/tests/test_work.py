"""Work counts from step plans, against counts made by hand."""
import numpy as np
import pytest

from bench.harness import serve as sv
from bench.harness import work

M = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 16, "d_ff": 96, "vocab_size": 100}
PEAKS = {"flops": 1e12, "hbm_bytes_s": 1e9}


def test_decode_row():
    # one token at position 99 attends to 100 keys
    seq = work.seq_of_positions([99])
    assert seq == (1, 100, 100)
    flops, nbytes = work.attention_work([seq], M)
    assert flops == 4 * 4 * 16 * 100
    # q in + out: 2 * 1 * 4 * 16; K and V of 100 positions, 2 KV heads
    assert nbytes == 2 * (2 * 4 * 16 + 2 * 100 * 2 * 16)


def test_prefill_chunk():
    # positions 10..13: keys 11 + 12 + 13 + 14 = 50
    seq = work.seq_of_positions(np.arange(10, 14))
    assert seq == (4, 50, 14)
    flops, nbytes = work.attention_work([seq], M)
    assert flops == 4 * 4 * 16 * 50
    assert nbytes == 2 * (2 * 4 * 4 * 16 + 2 * 14 * 2 * 16)


def test_multi_segment_context_reads_context_once():
    # recomputed positions 0..1 and 6..7 (2..5 cached): K/V of 8 once
    seq = work.seq_of_positions([0, 1, 6, 7])
    assert seq == (4, 1 + 2 + 7 + 8, 8)
    _, nbytes = work.attention_work([seq], M)
    assert nbytes == 2 * (2 * 4 * 4 * 16 + 2 * 8 * 2 * 16)


def test_step_flops_and_roofline():
    seqs = [work.seq_of_positions([99]), work.seq_of_positions(range(4))]
    lin = 64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64 + 3 * 64 * 96
    assert work.linear_params(M) == lin
    att = 4 * 4 * 16 * (100 + 10)
    want = 2 * (2 * lin * 5 + att) + 2 * 64 * 100 * 2
    assert work.step_flops(seqs, 2, M) == want
    flops, nbytes = work.attention_work(seqs, M)
    assert work.roofline_seconds(seqs, M, PEAKS) == pytest.approx(
        2 * max(flops / 1e12, nbytes / 1e9))


def test_padding_counts_zero():
    class Plan:
        prefills, decodes = [], []
    seqs, rows = sv.plan_summary(Plan())
    assert (seqs, rows) == ([], 0)
    assert work.step_flops(seqs, rows, M) == 0
    assert work.roofline_seconds(seqs, M, PEAKS) == 0
