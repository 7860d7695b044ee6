"""Architecture module: a dense GQA decoder (the Llama-style block).

Everything of the benchmark that depends on this block's equations:
the program's ``ModelConfig``, the weight layout the benchmark draws,
the plain float32 reference and its lower-precision control, and the
operations and bytes of each kernel in each layer.  A configuration
names it with ``"arch": "dense_gqa"``.

The block: RMSNorm (``1 + w``), q/k/v projections, rotary embedding on
the two halves of each head, causal softmax attention with grouped K/V
heads, the output projection, and a SwiGLU MLP, each sublayer added to
the residual stream; a final RMSNorm and the vocabulary head (the
embedding's transpose when tied).

Reference.  Straightforward ``jax.numpy`` over the benchmark's weights,
one layer at a time; nothing is imported from the program.
``mode="f32"``: float32 activations and every matmul at ``highest``
precision.  ``mode="fp8"``: the control, every linear layer's weight and
input rounded to float8 e4m3 (scaled per tensor and per row) before the
matmul, the precision step below the bf16 the configurations state.
Rows are padded to a multiple of ``PAD`` so that one compiled layer
serves many lengths (causal: padding never reaches earlier positions);
attention runs in query blocks of ``QB`` rows so that it fits beside the
weights.

Work.  Counted from the positions each sequence of a step computes, not
from what a kernel happens to do: every computed query token attends to
its causal context once (a token at position p attends to p + 1 keys),
K/V of each sequence are read once per KV head, and padding counts zero.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KERNELS = ("msa_fused",)

PAD = 1024
QB = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def model_config(name: str, program: Dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=name, **program)


def shapes(p: Dict) -> Dict:
    """Leaf shapes and standard deviations, keyed like the program's
    parameter tree: per-layer leaves stacked on a leading layer axis,
    q/k/v as ``(d, heads, head_dim)``, the output projection as
    ``(heads, head_dim, d)``.

    Scales keep a random model well conditioned: q/k/v contract over
    d_model (unit-variance attention logits), output projections are
    scaled by 1/sqrt(2L) so the residual stream stays O(1) with depth,
    and the embedding and head use 0.02."""
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
    return {"blocks": blocks, **top}


# ---- reference ------------------------------------------------------------
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


# ---- work -----------------------------------------------------------------
def attention_work(seqs: Sequence, m: Dict, bytes_per: int = 2
                   ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's attention over a step's sequences
    (each with ``positions`` computed and context ``ctx``).

    FLOPs: QK^T and PV, 2·H·D each per (query, key) pair.  Bytes: q in
    and the output out once per token and head, and the K and V of each
    sequence's context once per KV head."""
    H, KH, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = nbytes = 0.0
    for s in seqs:
        n = int(s.positions.size)
        keys = int(s.positions.sum()) + n
        flops += 4.0 * H * D * keys
        nbytes += bytes_per * (2.0 * n * H * D + 2.0 * s.ctx * KH * D)
    return flops, nbytes


def linear_params(m: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, H, KH, D, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    return d * H * D + 2 * d * KH * D + H * D * d + 3 * d * f


def layer_work(seqs: Sequence, m: Dict) -> List[Dict[str, Tuple[float,
                                                                  float]]]:
    """Per layer, ``{kernel: (FLOPs, bytes)}``: every layer calls
    ``msa_fused`` once over the step's sequences."""
    att = attention_work(seqs, m)
    return [{"msa_fused": att} for _ in range(m["n_layers"])]


def step_flops(seqs: Sequence, n_logit_rows: int, m: Dict) -> float:
    """Model FLOPs of one step: every computed token through every
    layer's matmuls and attention, and the vocabulary head for the rows
    whose next token is sampled."""
    tokens = sum(int(s.positions.size) for s in seqs)
    lin = 2.0 * linear_params(m) * tokens
    total = 0.0
    for layer in layer_work(seqs, m):
        total += lin + layer["msa_fused"][0]
    return total + 2.0 * m["d_model"] * m["vocab_size"] * n_logit_rows
