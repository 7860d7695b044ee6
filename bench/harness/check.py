"""The comparison that decides ``correct``, apart from any architecture:
which positions a served turn sampled, and how far below the reference's
best each sampled token's logit lies.  The reference's logits come from
the configuration's architecture module (``logits``)."""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def gaps(ref: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """How far below each row's best the chosen token's logit lies."""
    ch = np.asarray(chosen, np.int64)
    return ref.max(axis=1) - ref[np.arange(ref.shape[0]), ch]


def served_positions(prompt_len: int, n_sampled: int) -> List[int]:
    """Positions whose argmax the served path sampled: the last prompt
    position, then each decode step's input position."""
    return list(range(prompt_len - 1, prompt_len - 1 + n_sampled))
