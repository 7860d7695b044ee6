"""The page tile of a latent (MLA) KV cache.

Under latent attention every token caches one vector per layer, its
normalized latent ``c`` (``rank`` lanes) and its RoPE key part ``pe``
(``rope`` lanes), and every query head attends over it: K is ``[c | pe]``
and V is ``c``.  A page of ``page`` such vectors is ``page x (rank +
rope)`` values (DeepSeek-V3: 16 x 576), whose last dim is no multiple of
the TPU's 128 lanes, and the kernel's DMA refuses a pool tile off that
tiling.  So a page is stored as a ``(rows, 128)`` tile holding exactly
those values, with no padding:

* rows ``[j*page, (j+1)*page)`` hold lanes ``[128 j, 128 (j+1))`` of
  ``c`` for the page's tokens in order (``rank / 128`` chunks);
* the last ``page * rope / 128`` rows hold ``pe``: row ``i``, lanes
  ``[g*rope, (g+1)*rope)`` is the ``pe`` of token ``g * per_group + i``,
  for the ``groups = 128 / rope`` groups of ``per_group = page / groups``
  consecutive tokens.

The kernel reads a group's tokens as whole 8-row slices: K rows ``[c |
pe block]`` where the q it is scored against carries ``q_pe`` in the
group's lanes of a 128-lane block per group and zeros elsewhere
(``kernel_q``), so one matmul gives every group's score without moving
lanes.  Keys come out in (group, page, token-in-group) order.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

LANES = 128


@dataclass(frozen=True)
class LatentLayout:
    page: int     # tokens per page
    rank: int     # latent lanes (kv_lora_rank)
    rope: int     # RoPE key lanes (qk_rope_head_dim)

    def __post_init__(self):
        assert self.rank % LANES == 0 and LANES % self.rope == 0, self
        assert self.page % self.groups == 0, self

    @property
    def groups(self) -> int:
        return LANES // self.rope

    @property
    def per_group(self) -> int:
        return self.page // self.groups

    @property
    def chunks(self) -> int:
        return self.rank // LANES

    @property
    def rows(self) -> int:
        """Rows of a page's (rows, 128) tile."""
        return self.chunks * self.page + self.per_group

    @property
    def width(self) -> int:
        """Lanes of a cached vector: ``rank + rope``."""
        return self.rank + self.rope

    @property
    def q_width(self) -> int:
        """Lanes of the kernel's query: the latent part and one 128-lane
        block per group."""
        return self.rank + self.groups * LANES

    def to_tile(self, vecs: jax.Array) -> jax.Array:
        """(..., page, rank + rope) cached vectors -> (..., rows, 128)."""
        lead = vecs.shape[:-2]
        c = vecs[..., :self.rank].reshape(*lead, self.page, self.chunks,
                                          LANES)
        c = jnp.swapaxes(c, -2, -3).reshape(*lead, self.chunks * self.page,
                                            LANES)
        pe = vecs[..., self.rank:].reshape(*lead, self.groups,
                                           self.per_group, self.rope)
        pe = jnp.swapaxes(pe, -2, -3).reshape(*lead, self.per_group, LANES)
        return jnp.concatenate([c, pe], axis=-2)

    def from_tile(self, tile: jax.Array) -> jax.Array:
        """(..., rows, 128) -> (..., page, rank + rope): ``to_tile``'s
        inverse."""
        lead = tile.shape[:-2]
        n = self.chunks * self.page
        c = tile[..., :n, :].reshape(*lead, self.chunks, self.page, LANES)
        c = jnp.swapaxes(c, -2, -3).reshape(*lead, self.page, self.rank)
        pe = tile[..., n:, :].reshape(*lead, self.per_group, self.groups,
                                      self.rope)
        pe = jnp.swapaxes(pe, -2, -3).reshape(*lead, self.page, self.rope)
        return jnp.concatenate([c, pe], axis=-1)

    def kernel_q(self, q: jax.Array) -> jax.Array:
        """(..., rank + rope) queries -> (..., q_width): ``q_pe`` placed
        at group g's lanes of block g."""
        q_lat, q_pe = q[..., :self.rank], q[..., self.rank:]
        zeros = jnp.zeros(q_pe.shape, q.dtype)
        blocks = []
        for g in range(self.groups):
            blocks += [zeros] * g + [q_pe] + [zeros] * (self.groups - 1 - g)
        return jnp.concatenate([q_lat] + blocks, axis=-1)
