"""Plain reference of a dense GQA decoder, and its lower-precision control.

Straightforward ``jax.numpy`` over the benchmark's weights, one layer at
a time: RMSNorm (``1 + w``), q/k/v projections, rotary embedding on the
two halves of each head, causal softmax attention with grouped K/V
heads, the output projection, and a SwiGLU MLP, each sublayer added to
the residual stream.  Nothing is imported from the program.

``mode="f32"``: float32 activations and every matmul at ``highest``
precision.  ``mode="fp8"``: the control, every linear layer's weight and
input rounded to float8 e4m3 (scaled per tensor and per row) before the
matmul, the precision step below the bf16 the configurations state.

Rows are padded to a multiple of ``PAD`` so that one compiled layer
serves many lengths (causal: padding never reaches earlier positions);
attention runs in query blocks of ``QB`` rows so that it fits beside the
weights.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PAD = 1024
QB = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _f8_tensor(w):
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / F8_MAX
    return (w / s).astype(F8).astype(jnp.float32) * s


def _f8_rows(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                    1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _linear(x, w, fp8: bool):
    """x (S, a) @ w (a, b) in float32, or through fp8 for the control."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _f8_rows(x), _f8_tensor(w)
    return x @ w


def _norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """x (S, heads, hd): rotate the (first half, second half) pairs."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("p", "fp8"))
def _layer(x, blk, p, fp8: bool):
    """One decoder layer over rows ``x`` (S, d) at positions 0..S-1."""
    S, d = x.shape
    H, KH, hd = p["n_heads"], p["n_kv_heads"], p["head_dim"]
    G = H // KH
    pos = jnp.arange(S, dtype=jnp.int32)
    h = _norm(x, blk["attn_norm"], p["norm_eps"])
    q = _linear(h, blk["wq"].reshape(d, H * hd), fp8).reshape(S, H, hd)
    k = _linear(h, blk["wk"].reshape(d, KH * hd), fp8).reshape(S, KH, hd)
    v = _linear(h, blk["wv"].reshape(d, KH * hd), fp8).reshape(S, KH, hd)
    if p["rope_theta"] > 0:
        q, k = _rope(q, pos, p["rope_theta"]), _rope(k, pos, p["rope_theta"])
    q = q.reshape(S // QB, QB, KH, G, hd)

    def block(args):
        qb, i = args                                  # (QB, KH, G, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        qp = i * QB + jnp.arange(QB)
        s = jnp.where(qp[:, None] >= pos[None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", a, v)

    o = jax.lax.map(block, (q, jnp.arange(S // QB)))
    o = o.reshape(S, H * hd)
    x = x + _linear(o, blk["wo"].reshape(H * hd, d), fp8)
    h = _norm(x, blk["mlp_norm"], p["norm_eps"])
    u = jax.nn.silu(_linear(h, blk["w1"], fp8)) * _linear(h, blk["w3"], fp8)
    return x + _linear(u, blk["w2"], fp8)


@functools.partial(jax.jit, static_argnames=("p", "fp8"))
def _head(x, at, final_norm, head, p, fp8: bool):
    h = _norm(x[at], final_norm, p["norm_eps"])
    return _linear(h, head, fp8)


class _Frozen(dict):
    """Hashable view of the program block, for jit's static arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits(params: Dict, p: Dict, tokens: Sequence[int],
           positions: Sequence[int], mode: str = "f32") -> np.ndarray:
    """Logits (len(positions), V) of the reference at ``positions``,
    teacher-forced over ``tokens``."""
    fp8 = mode == "fp8"
    pz = _Frozen({k: p[k] for k in ("n_heads", "n_kv_heads", "head_dim",
                                    "norm_eps", "rope_theta")})
    n = len(tokens)
    S = max(PAD, -(-n // PAD) * PAD)
    toks = np.zeros((S,), np.int32)
    toks[:n] = tokens
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for l in range(p["n_layers"]):
            blk = {k: v[l] for k, v in params["blocks"].items()}
            x = _layer(x, blk, pz, fp8)
        head = (params["embed"].T if p["tie_embeddings"]
                else params["lm_head"])
        out = _head(x, jnp.asarray(np.asarray(positions, np.int32)),
                    params["final_norm"], head, pz, fp8)
    return np.asarray(out)


def gaps(ref: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """How far below each row's best the chosen token's logit lies."""
    ch = np.asarray(chosen, np.int64)
    return ref.max(axis=1) - ref[np.arange(ref.shape[0]), ch]


def served_positions(prompt_len: int, n_sampled: int) -> List[int]:
    """Positions whose argmax the served path sampled: the last prompt
    position, then each decode step's input position."""
    return list(range(prompt_len - 1, prompt_len - 1 + n_sampled))
