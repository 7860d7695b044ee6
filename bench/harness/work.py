"""A step's work as the architecture modules count it, and its roofline.

A step is summarized by the sequences it computes, each as the positions
it computes and its context length (a prefill chunk of a multi-segment
context computes scattered positions; a decode row computes one), and by
the rows whose next token it samples.  The module
(``bench/models/<arch>.py``) turns that summary into per-layer
``{kernel: (FLOPs, bytes)}`` (``layer_work``) and model FLOPs
(``step_flops``); what a position costs is the module's to say, so a
windowed layer can count ``min(p + 1, W)`` keys.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Sequence, Tuple

import numpy as np


class Seq(NamedTuple):
    positions: np.ndarray       # the positions computed this step
    ctx: int                    # context length: keys up to the last one


def seq_of_positions(positions) -> Seq:
    """One sequence computing the given positions."""
    pos = np.asarray(positions, np.int64)
    return Seq(pos, int(pos.max()) + 1)


def roofline_seconds(layers: Sequence[Dict[str, Tuple[float, float]]],
                     kernel: str, peaks: Dict) -> float:
    """Least time the chip needs for ``kernel``'s calls in one step: per
    layer that calls it, the larger of FLOPs over peak FLOP/s and bytes
    over peak bytes/s, summed."""
    return sum(max(w[kernel][0] / peaks["flops"],
                   w[kernel][1] / peaks["hbm_bytes_s"])
               for w in layers if kernel in w)
