"""Persistent XLA compile cache for the entry points.

Called by ``chip_smoke.py`` and ``repro.launch.serve`` — never on library
import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here.  Otherwise the cache lives at a fixed directory
of the checkout (``.jax_cache/``, git-ignored): the cache path is part of
what makes an entry found again, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
