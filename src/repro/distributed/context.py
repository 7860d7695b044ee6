"""Distribution context: logical-axis sharding rules threaded through model code.

Model code annotates activations with *logical* axis names via ``constrain``.
When a ``DistContext`` is active, logical names resolve to mesh axes through
the arch's sharding policy and become ``with_sharding_constraint`` hints;
with no context (CPU smoke tests) they are no-ops.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


@dataclass
class DistContext:
    mesh: Mesh
    # logical axis name -> mesh axis name (or tuple of mesh axes) or None
    rules: Dict[str, object] = field(default_factory=dict)
    # free-form flags consulted by model code ("moe_alltoall", "flash_decode", ...)
    flags: Dict[str, object] = field(default_factory=dict)

    def spec(self, *axes: Optional[str]) -> P:
        resolved = []
        for ax in axes:
            if ax is None:
                resolved.append(None)
            else:
                resolved.append(self.rules.get(ax))
        return P(*resolved)

    def sharding(self, *axes: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*axes))

    def axis_size(self, logical: str) -> int:
        mesh_axes = self.rules.get(logical)
        if mesh_axes is None:
            return 1
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        n = 1
        for a in mesh_axes:
            n *= self.mesh.shape[a]
        return n


def current() -> Optional[DistContext]:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def use_dist(ctx: Optional[DistContext]):
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = ctx
    try:
        yield ctx
    finally:
        _STATE.ctx = prev


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Annotate ``x``'s dims with logical axis names (no-op without context)."""
    ctx = current()
    if ctx is None:
        return x
    assert len(axes) == x.ndim, (axes, x.shape)
    return jax.lax.with_sharding_constraint(x, ctx.sharding(*axes))


def flag(name: str, default=None):
    ctx = current()
    if ctx is None:
        return default
    return ctx.flags.get(name, default)
