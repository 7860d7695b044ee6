"""Seeded random weights, made on the device in one jitted call.

The layout is the architecture module's (``bench/models/<arch>.py``
``shapes``): a nested tree of dicts and lists whose leaves are
``(shape, std)``.  The benchmark makes the arrays; the program and the
reference are both handed the same ones.

Leaves are drawn depth first in the order the module lists them, one key
each from ``jax.random.split`` of the seed's key, so a tree keeps its
draws as long as its listing order does.
"""
from __future__ import annotations

from typing import Any, Iterator, List, Tuple

import jax
import jax.numpy as jnp


def _is_leaf(v: Any) -> bool:
    return (isinstance(v, tuple) and len(v) == 2
            and isinstance(v[1], (int, float)))


def _leaves(tree: Any) -> List[Tuple]:
    if _is_leaf(tree):
        return [tree]
    items = tree.values() if isinstance(tree, dict) else tree
    return [x for v in items for x in _leaves(v)]


def _fill(tree: Any, it: Iterator):
    """``tree`` with each leaf replaced by the next of ``it``."""
    if _is_leaf(tree):
        return next(it)
    if isinstance(tree, dict):
        return {k: _fill(v, it) for k, v in tree.items()}
    return [_fill(v, it) for v in tree]


def make(spec: Any, seed: int, dtype=jnp.bfloat16):
    """Every leaf of ``spec`` drawn from ``seed`` in ``dtype``, in one
    jitted call; the result has ``spec``'s structure."""
    flat = _leaves(spec)

    def init(key):
        keys = jax.random.split(key, len(flat))
        return _fill(spec, iter(
            jax.random.normal(k_i, shape, dtype) * jnp.asarray(std, dtype)
            for k_i, (shape, std) in zip(keys, flat)))

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.block_until_ready(jax.jit(init)(key))
