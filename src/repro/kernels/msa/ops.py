"""Dispatch wrappers for the fused MSA kernel, plus the in-step page ops.

``impl`` selects the backend:
  * "pallas"            — compiled Pallas (TPU)
  * "pallas_interpret"  — Pallas interpreter (CPU validation)
  * "xla"               — pure-jnp oracle (CPU serving / dry-run lowering)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.msa import ref
from repro.kernels.msa.msa_fused import msa_fused_pallas

DEFAULT_IMPL = "xla"  # the oracle; the served TPU path passes "pallas"


def msa_fused(q, k_pages, v_pages, block_tables, context_lens, q_pos,
              seq_ids, q_valid, *, q_start=None, q_len=None, worklist=None,
              layer=None, window: int = 0, softcap: float = 0.0,
              q_tile: int = 128, latent=None,
              impl: str = DEFAULT_IMPL) -> jax.Array:
    """One fused dispatch over the flattened (T, H, D) mixed token stream
    (prefill chunks + decode rows).  The xla oracle resolves each token's
    context through ``seq_ids``; the Pallas kernel iterates the compacted
    work-list (``msa_fused.build_worklist``) with per-sequence
    ``q_start``/``q_len`` runs.  With ``layer`` the pools are the
    layer-stacked ``(L, P, KH, page, D)`` ones: the kernel DMAs that
    layer's pages straight from them, the oracle slices the layer out.
    Under ``latent`` (a ``LatentLayout``: latent attention) the pools
    hold latent page tiles, ``v_pages`` is not read, V is each cached
    latent and the output is ``latent.rank`` wide."""
    if impl == "xla":
        if layer is not None:
            k_pages = k_pages[layer]
            v_pages = None if latent is not None else v_pages[layer]
        return ref.msa_fused_ref(q, k_pages, v_pages, block_tables,
                                 context_lens, q_pos, seq_ids, q_valid,
                                 window=window, softcap=softcap,
                                 latent=latent)
    if q_start is None or q_len is None or worklist is None:
        raise ValueError("pallas msa_fused needs q_start/q_len + worklist")
    interpret = impl == "pallas_interpret"
    return msa_fused_pallas(q, k_pages, v_pages, q_start, q_len, q_pos,
                            context_lens, *worklist,
                            0 if layer is None else layer, window=window,
                            softcap=softcap, q_tile=q_tile,
                            latent=latent, interpret=interpret)


def msa_fused_partial(q, k_pages, v_pages, block_tables, context_lens,
                      q_pos, seq_ids, q_valid, page_valid, *,
                      window: int = 0, softcap: float = 0.0,
                      impl: str = DEFAULT_IMPL):
    """Per-shard partial of the fused varlen dispatch: attention restricted
    to the pages marked valid, in the normalized ``(o, lse)`` form the
    cross-shard log-sum-exp merge consumes (``repro.distributed.
    flash_decode``).  Each shard's local page pool is one segment subset
    of the multi-segment context."""
    if impl != "xla":
        # partial+merge is the CPU/host-device validation path; a fused
        # Pallas partial (TPU pools sharded across chips) would reuse the
        # same work-list machinery with an lse output — future work
        raise NotImplementedError("msa_fused_partial: xla impl only")
    return ref.msa_fused_partial_ref(q, k_pages, v_pages, block_tables,
                                     context_lens, q_pos, seq_ids, q_valid,
                                     page_valid, window=window,
                                     softcap=softcap)


write_kv_pages = ref.write_kv_pages


# ---------------------------------------------------------------------------
# In-step page maintenance (overlapped pipeline)
#
# Copy-on-write forks and host-tier swap-ins used to run as eager un-jitted
# ``.at[].set`` dispatches between steps; folding them into the jitted step
# as padded index arrays removes those host round-trips.  Both operate on
# the layer-stacked pools (L, P, KH, page, D) and use out-of-range
# destination indices (dst == P) as padding, dropped by the scatter.
# ---------------------------------------------------------------------------

def apply_page_copies(k_pools: jax.Array, v_pools: jax.Array,
                      copy_src: jax.Array, copy_dst: jax.Array):
    """COW page copies ``src -> dst`` across all layers, inside the step.

    ``copy_src``/``copy_dst`` are (C,) int32.  Padding entries REPEAT the
    last real copy (idempotent) or are the identity ``0 -> 0`` when the
    step has no copies at all — see ``Engine._fold_page_ops``.

    All source pages are gathered *before* any write (copy sources are
    committed blocks, destinations fresh allocations, so sources never
    alias destinations), then written with unrolled dynamic-slice updates.
    A scatter whose update operand gathers from the scattered array itself
    would force XLA to materialize a full defensive pool copy per step;
    the gather-then-update form keeps the update operand independent so
    the writes happen in place in the donated pools.  A latent cache
    (``v_pools`` None) copies its one half."""
    c = copy_src.shape[0]
    if c == 0:
        return k_pools, v_pools
    pools = [k_pools] if v_pools is None else [k_pools, v_pools]
    pages = [p[:, copy_src] for p in pools]  # (L, C, KH, page, D) — small
    for j in range(c):
        pools = [jax.lax.dynamic_update_slice_in_dim(
            p, pg[:, j:j + 1], copy_dst[j], axis=1)
            for p, pg in zip(pools, pages)]
    return pools[0], (None if v_pools is None else pools[1])


def _dequant_payload(payload: jax.Array, scale, dtype) -> jax.Array:
    """In-step dequantization of a (L, S, KH, page, D) swap payload.
    int8 codes carry a per-page-per-head (L, S, KH) scale; fp8 payloads
    just cast.  The f32 multiply matches the host-side
    ``offload.dequantize_half`` operand order exactly, so eager and
    in-step swap-ins reproduce identical pool bytes."""
    if scale is not None:
        out = payload.astype(jnp.float32) * scale[:, :, :, None, None]
        return out.astype(dtype)
    if payload.dtype != dtype:
        return payload.astype(dtype)
    return payload


def apply_swap_ins(k_pools: jax.Array, v_pools: jax.Array,
                   swap_k_dst: jax.Array, swap_v_dst: jax.Array,
                   swap_k: jax.Array, swap_v: jax.Array,
                   swap_k_scale=None, swap_v_scale=None):
    """Host-tier swap-ins: scatter (L, S, KH, page, D) payloads into pool
    pages, padding steered out of range and dropped.

    The K and V halves carry INDEPENDENT destination buckets
    (``swap_k_dst`` / ``swap_v_dst``, each (S,)): a V-only swap-in (the
    k-early prefetch's on-demand V stream) ships no K payload at all
    instead of a zero page.  Quantized payloads (int8 codes + scale, or
    fp8) dequantize here, inside the jitted step — the host->device
    transfer carries the compressed bytes."""
    if swap_k_dst.shape[0] > 0:
        k_pools = k_pools.at[:, swap_k_dst].set(
            _dequant_payload(swap_k, swap_k_scale, k_pools.dtype),
            mode="drop")
    if swap_v_dst.shape[0] > 0:
        v_pools = v_pools.at[:, swap_v_dst].set(
            _dequant_payload(swap_v, swap_v_scale, v_pools.dtype),
            mode="drop")
    return k_pools, v_pools
