"""Operations and bytes that a step's work needs, counted from its plan.

The counts are of the work the algorithm needs, not of what a kernel
happens to do: every computed query token attends to its causal context
once, K/V of each sequence are read once per KV head, and padding rows
and padding work-list items count zero.  So the counts do not move when
the kernel's implementation does.

A step is a list of sequences, each summarized as ``(n, keys, ctx)``: the
query tokens it computes, the sum over them of the keys each attends to
(a token at position p attends to p + 1 keys), and its context length.
A prefill chunk of a multi-segment context computes scattered positions;
a decode row computes one.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

Seq = Tuple[int, int, int]      # (query tokens, summed keys, context)


def seq_of_positions(positions) -> Seq:
    """Summary of one sequence computing the given positions."""
    pos = np.asarray(positions, np.int64)
    return int(pos.size), int(pos.sum() + pos.size), int(pos.max()) + 1


def attention_work(seqs: Sequence[Seq], m: Dict, bytes_per: int = 2
                   ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's attention over a step's sequences.

    FLOPs: QK^T and PV, 2·H·D each per (query, key) pair.  Bytes: q in
    and the output out once per token and head, and the K and V of each
    sequence's context once per KV head."""
    H, KH, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = nbytes = 0.0
    for n, keys, ctx in seqs:
        flops += 4.0 * H * D * keys
        nbytes += bytes_per * (2.0 * n * H * D + 2.0 * ctx * KH * D)
    return flops, nbytes


def linear_params(m: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, H, KH, D, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    return d * H * D + 2 * d * KH * D + H * D * d + 3 * d * f


def step_flops(seqs: Sequence[Seq], n_logit_rows: int, m: Dict) -> float:
    """Model FLOPs of one step: every computed token through every
    layer's matmuls and attention, and the vocabulary head for the rows
    whose next token is sampled."""
    tokens = sum(n for n, _, _ in seqs)
    att, _ = attention_work(seqs, m)
    return m["n_layers"] * (2.0 * linear_params(m) * tokens + att) \
        + 2.0 * m["d_model"] * m["vocab_size"] * n_logit_rows


def roofline_seconds(seqs: Sequence[Seq], m: Dict, peaks: Dict) -> float:
    """Least time the chip needs for one step's attention calls, one per
    layer: the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s, per call."""
    flops, nbytes = attention_work(seqs, m)
    return m["n_layers"] * max(flops / peaks["flops"],
                               nbytes / peaks["hbm_bytes_s"])
