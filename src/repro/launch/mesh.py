"""Mesh construction.

Mesh builders are FUNCTIONS (not module-level constants) so importing this
module never touches jax device state — critical because smoke tests must
see 1 CPU device while the dry-run forces 512 host devices via XLA_FLAGS
before any jax import.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagates
    shardings; JAX's own default is ``Explicit``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         (AxisType.Auto,) * len(axes), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """Single-device mesh with the production axis names (CPU tests)."""
    return make_mesh(shape, axes)


def make_serving_mesh(n_shards: int, *,
                      devices: Optional[Sequence] = None):
    """Mesh for the sharded serving engine: KV page pools (and TP-friendly
    weight dims) shard over ``model``; the serving batch is host-driven and
    stays replicated, so ``data`` is 1.  On CPU validate with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if len(devs) < n_shards:
        raise ValueError(
            f"serving mesh needs {n_shards} devices, have {len(devs)} "
            "(CPU: set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return make_mesh((1, n_shards), ("data", "model"),
                     devices=devs[:n_shards])
