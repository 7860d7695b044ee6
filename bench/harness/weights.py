"""Seeded random weights, made on the device in one jitted call.

The layout is the served program's parameter tree for a dense GQA
decoder: per-layer leaves stacked on a leading layer axis, q/k/v as
``(d, heads, head_dim)``, the output projection as ``(heads, head_dim,
d)``, SwiGLU ``w1``/``w3``/``w2``, and RMSNorm weights applied as
``1 + w``.  The benchmark makes them; the program and the reference are
both handed the same arrays.

Scales keep a random model well conditioned: q/k/v contract over
d_model (unit-variance attention logits), output projections are scaled
by 1/sqrt(2L) so the residual stream stays O(1) with depth, and the
embedding and head use 0.02.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def shapes(p: Dict) -> Dict:
    """Leaf shapes and standard deviations, keyed like the program's
    parameter tree."""
    L, d, H, KH, hd, f, V = (p["n_layers"], p["d_model"], p["n_heads"],
                             p["n_kv_heads"], p["head_dim"], p["d_ff"],
                             p["vocab_size"])
    depth = 1.0 / math.sqrt(2.0 * L)
    blocks = {
        "attn_norm": ((L, d), 0.1),
        "wq": ((L, d, H, hd), 1.0 / math.sqrt(d)),
        "wk": ((L, d, KH, hd), 1.0 / math.sqrt(d)),
        "wv": ((L, d, KH, hd), 1.0 / math.sqrt(d)),
        "wo": ((L, H, hd, d), depth / math.sqrt(H * hd)),
        "mlp_norm": ((L, d), 0.1),
        "w1": ((L, d, f), 1.0 / math.sqrt(d)),
        "w3": ((L, d, f), 1.0 / math.sqrt(d)),
        "w2": ((L, f, d), depth / math.sqrt(f)),
    }
    top = {"embed": ((V, d), 0.02), "final_norm": ((d,), 0.1)}
    if not p["tie_embeddings"]:
        top["lm_head"] = ((d, V), 0.02)
    return {**top, "blocks": blocks}


def make(p: Dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """Every leaf drawn from ``seed`` in ``dtype``, in one jitted call."""
    spec = shapes(p)
    flat = [("blocks", k, s) for k, s in spec["blocks"].items()]
    flat += [(None, k, s) for k, s in spec.items() if k != "blocks"]

    def init(key):
        keys = jax.random.split(key, len(flat))
        out: Dict = {"blocks": {}}
        for k_i, (group, name, (shape, std)) in zip(keys, flat):
            leaf = (jax.random.normal(k_i, shape, dtype)
                    * jnp.asarray(std, dtype))
            (out["blocks"] if group else out)[name] = leaf
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.block_until_ready(jax.jit(init)(key))
