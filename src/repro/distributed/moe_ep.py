"""Expert-parallel MoE with all-to-all token routing (kimi-k2 path).

shard_map over the full mesh: experts are sharded across the ``data`` axis
(384/16 = 24 per chip) and each expert's FFN across ``model`` (2048/16);
tokens are dispatched with the sort-based capacity scatter (no GShard
one-hot einsum — that would cost O(S·E·C·d) FLOPs, ~100x the useful
expert compute at E=384) and exchanged with a single ``all_to_all`` per
direction.  The second expert matmul is row-parallel over ``model`` and
reduced with one ``psum``.

Collectives per MoE layer: 2 x all_to_all(data) + 1 x psum(model) — the
pattern the roofline's collective term tracks.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import context as ctx
from repro.models.layers import capacity_dispatch, topk_route


def moe_ffn_alltoall(x: jax.Array, router_w: jax.Array, we1: jax.Array,
                     we3: jax.Array, we2: jax.Array,
                     cfg: ModelConfig) -> jax.Array:
    """x: (B, S, d) batch-sharded over (pod?, data); returns same shape."""
    dc = ctx.current()
    assert dc is not None, "moe_ffn_alltoall requires a DistContext"
    mesh = dc.mesh
    batch_axes = dc.rules.get("batch")          # e.g. ("pod","data") or "data"
    ep_axis = dc.rules.get("experts", "data")   # "data" or ("pod","data")
    tp_axis = "model"
    if isinstance(ep_axis, str):
        n_ep = mesh.shape[ep_axis]
    else:
        ep_axis = tuple(ep_axis)
        n_ep = 1
        for a in ep_axis:
            n_ep *= mesh.shape[a]
    e_global = cfg.moe.num_experts            # virtual experts
    split = cfg.moe.expert_split
    assert e_global % n_ep == 0, (e_global, n_ep)
    top_k = cfg.moe.top_k
    k_eff = top_k * split
    cf = cfg.moe.capacity_factor

    x_spec = P(batch_axes, None, None)
    w_router_spec = P(None, None)
    w13_spec = P(ep_axis, None, tp_axis)        # (E, d, f)
    w2_spec = P(ep_axis, tp_axis, None)         # (E, f, d)

    def local_fn(xl, rw, w1, w3, w2):
        b_l, s_l, d = xl.shape
        t = b_l * s_l
        xt = xl.reshape(t, d)
        logits = xt @ rw                                   # (t, E_phys)
        weights, topi = topk_route(logits, top_k)          # (t, k)
        from repro.models.layers import expand_virtual_experts
        weights, topi = expand_virtual_experts(weights, topi, split)
        n = t * k_eff
        flat_e = topi.reshape(n)
        if cfg.moe.dropless:
            cap = t          # worst case: every local token on one expert
        else:
            cap = max(1, int(math.ceil(t * k_eff / e_global * cf)))
        pos, keep = capacity_dispatch(flat_e, e_global, cap)
        slot = jnp.where(keep, flat_e * cap + pos, e_global * cap)
        x_rep = jnp.repeat(xt, k_eff, axis=0)
        buf = jnp.zeros((e_global * cap + 1, d), xt.dtype).at[slot].set(x_rep)
        buf = buf[:-1].reshape(e_global, cap, d)

        # all_to_all: expert dim split across data shards; each device
        # receives its experts' slots from every source shard
        recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                  tiled=True)              # (E/n, n*cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, w1)) * jnp.einsum(
            "ecd,edf->ecf", recv, w3)                      # f sharded on model
        y = jnp.einsum("ecf,efd->ecd", h, w2)              # PARTIAL over f

        # §Perf iteration B: every op from here to the token combine is
        # linear, so the model-axis reduction commutes to the END — the
        # psum shrinks from the slot buffer (E/n x n·cap x d, ~590 MB at
        # kimi train scale) to the token activations (t x d, ~58 MB):
        # 10x less all-reduce wire per MoE layer.
        y = y.astype(xt.dtype)   # bf16 on the wire: halves the return a2a
        back = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                                  tiled=True)              # (E, cap, d) partial
        y_flat = back.reshape(e_global * cap, d)
        safe = jnp.where(keep, flat_e * cap + pos, 0)
        gathered = jnp.where(keep[:, None], y_flat[safe], 0.0)
        gathered = gathered * weights.reshape(n)[:, None].astype(xt.dtype)
        out = jnp.sum(gathered.reshape(t, k_eff, d), axis=1).astype(xt.dtype)
        out = jax.lax.psum(out, tp_axis)                   # bf16 on the wire
        return out.reshape(b_l, s_l, d)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, w_router_spec, w13_spec, w13_spec, w2_spec),
        out_specs=x_spec, check_vma=False,
    )(x, router_w, we1, we3, we2)
