"""Architecture module: the DeepSeek-V3 block (Moonlight-16B-A3B), at one
chip's share of expert parallelism.

Everything of the benchmark that depends on this block's equations
(DeepSeek-V3, arXiv:2412.19437, section 2.1): the program's
``ModelConfig``, the weight layout, the plain float32 reference and its
lower-precision control, and the operations and bytes of each kernel in
each layer.  A configuration names it with ``"arch": "deepseek_v3"``.

The block.  RMSNorm (``1 + w``), then multi-head latent attention with
no query compression: ``q = h·W_q`` split per head into ``q_nope`` and
``q_pe``; ``[c_kv | k_pe] = h·W_kva``; ``c_kv`` RMSNorm-ed; per head
``[k_nope | v] = c_kv·W_kvb``; RoPE on ``q_pe`` and on the one ``k_pe``
every head shares; causal softmax over ``[q_nope | q_pe]·[k_nope |
k_pe]`` at scale 1/sqrt(qk_nope + qk_rope); ``o·W_o``.  Then the MLP:
the first ``first_k_dense`` layers a SwiGLU of ``dense_d_ff``, the rest
the expert layer: router logits in float32, sigmoid scores, the top-k of
``score + bias`` chosen, their unbiased scores normalised over the k and
times ``routed_scale`` as weights, each chosen expert a SwiGLU of
``d_ff``, plus the shared experts as one SwiGLU of ``shared_d_ff``.  A
final RMSNorm and an untied vocabulary head.

The share.  This chip holds experts ``[expert_offset, expert_offset +
experts_held)`` of ``num_experts``; routing runs over all of them, and
what the absent experts would add is left out, here and in the program
alike (the deployment's other chips would add it).

Reference.  Straightforward ``jax.numpy`` over the benchmark's weights,
one layer at a time, with attention in the expanded form (per-head keys
``[k_nope | k_pe]`` and values from ``W_kvb``): it shares no algebra with
the program's absorbed form, which attends over the cached latent.
``mode="f32"``: float32 activations and every matmul at ``highest``
precision.  ``mode="fp8"``: the control, in float8 e4m3 where the
served path holds bf16, the precision step below the bf16 the
configuration states: every linear layer's weight and input (the
router's too) rounded before the matmul (scaled per tensor and per
row), and the activations held between operations (the residual stream
after each addition, the cached latent) rounded per row; sums in
float32.  Rows are padded to a multiple of ``PAD`` (causal:
padding never reaches earlier positions); attention runs in query blocks
of ``QB`` rows and the held experts one at a time, so that it fits
beside the weights.

Departures from the published model, in the program and the reference
alike: norms scale by ``1 + w``; RoPE rotates the two halves of the
rope dims (published: interleaved pairs, a fixed permutation of them).

Work.  Counted from the positions each sequence of a step computes:
every computed query token attends to its causal context once (a token
at position p attends to p + 1 keys).  ``msa_fused`` runs the absorbed
form, MQA over the latent: ``2·H·(r + dr)`` FLOPs for scores and
``2·H·r`` for the latent output per (query, key) pair; bytes are q in
and the output out per token and head, and each context's latent once.
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
EMBED_STD = 4.0
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def model_config(name: str, program: Dict):
    """The program's ``ModelConfig``.  ``moe.experts_held`` is this
    module's: the program reads the share from the expert weights'
    leading dimension (``shapes`` lays out that many)."""
    from repro.configs.base import ModelConfig, MoEConfig
    p = dict(program)
    moe = {k: v for k, v in p.pop("moe").items() if k != "experts_held"}
    return ModelConfig(name=name, moe=MoEConfig(**moe), **p)


def shapes(p: Dict) -> Dict:
    """Leaf shapes and standard deviations, keyed like the program's
    parameter tree.  ``dense_blocks``: the leading ``first_k_dense``
    layers (attention, norms and the dense SwiGLU), stacked over those;
    ``blocks``: every other layer (attention, norms, the router, its
    selection bias, the held experts ``(E_held, d, f)`` each and the
    shared experts), stacked over those.

    Scales make a random model conditioned as a trained one is, so that
    bf16 activations stay close to the float32 reference: projections
    contract with 1/sqrt(fan-in) (unit-variance attention logits and
    router logits), output projections are scaled by 1/sqrt(2L), and the
    embedding draws at std ``EMBED_STD``, so that the residual stream
    is large beside any one layer's output, as in trained models.  Then
    an expert that a near-tie of the router takes in or out under bf16
    moves a token's residual by a few percent and not by a large share
    of it.  The vocabulary head uses 0.02."""
    L, d, H, V = p["n_layers"], p["d_model"], p["n_heads"], p["vocab_size"]
    r, dn, dr, dv = (p["kv_lora_rank"], p["qk_nope_head_dim"],
                     p["qk_rope_head_dim"], p["v_head_dim"])
    m = p["moe"]
    n_dense = p["first_k_dense"]
    n_moe = L - n_dense
    f, fd, fs, eh = p["d_ff"], p["dense_d_ff"], m["shared_d_ff"], \
        m["experts_held"]
    depth = 1.0 / math.sqrt(2.0 * L)

    def attention(n):
        return {
            "attn_norm": ((n, d), 0.1),
            "wq": ((n, d, H, dn + dr), 1.0 / math.sqrt(d)),
            "w_kva": ((n, d, r + dr), 1.0 / math.sqrt(d)),
            "kv_norm": ((n, r), 0.1),
            "w_kvb": ((n, r, H, dn + dv), 1.0 / math.sqrt(r)),
            "wo": ((n, H, dv, d), depth / math.sqrt(H * dv)),
            "mlp_norm": ((n, d), 0.1),
        }

    dense = dict(attention(n_dense), **{
        "w1": ((n_dense, d, fd), 1.0 / math.sqrt(d)),
        "w3": ((n_dense, d, fd), 1.0 / math.sqrt(d)),
        "w2": ((n_dense, fd, d), depth / math.sqrt(fd)),
    })
    moe = dict(attention(n_moe), **{
        "router": ((n_moe, d, m["num_experts"]), 1.0 / math.sqrt(d)),
        "router_bias": ((n_moe, m["num_experts"]), 0.1),
        "we1": ((n_moe, eh, d, f), 1.0 / math.sqrt(d)),
        "we3": ((n_moe, eh, d, f), 1.0 / math.sqrt(d)),
        "we2": ((n_moe, eh, f, d), depth / math.sqrt(f)),
        "ws1": ((n_moe, d, fs), 1.0 / math.sqrt(d)),
        "ws3": ((n_moe, d, fs), 1.0 / math.sqrt(d)),
        "ws2": ((n_moe, fs, d), depth / math.sqrt(fs)),
    })
    return {"dense_blocks": dense, "blocks": moe,
            "embed": ((V, d), EMBED_STD), "final_norm": ((d,), 0.1),
            "lm_head": ((d, V), 0.02)}


# ---- reference ------------------------------------------------------------
def _f8_tensor(w):
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / F8_MAX
    return (w / s).astype(F8).astype(jnp.float32) * s


def _f8_rows(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                    1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _hold(x, fp8: bool):
    """An activation as the control holds it between operations: in
    float8 (scaled per row), as the served path holds its own in bf16."""
    return _f8_rows(x) if fp8 else x


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
    """x (S, heads, dr): rotate the (first half, second half) pairs."""
    dr = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, dr/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(h, w1, w3, w2, fp8: bool):
    return _linear(jax.nn.silu(_linear(h, w1, fp8)) * _linear(h, w3, fp8),
                   w2, fp8)


def _attention(x, blk, p, fp8: bool):
    """Expanded-form latent attention over rows ``x`` (S, d) at
    positions 0..S-1: the residual's increment."""
    S, d = x.shape
    H, r = p["n_heads"], p["kv_lora_rank"]
    dn, dr, dv = p["qk_nope_head_dim"], p["qk_rope_head_dim"], \
        p["v_head_dim"]
    pos = jnp.arange(S, dtype=jnp.int32)
    h = _norm(x, blk["attn_norm"], p["norm_eps"])
    q = _linear(h, blk["wq"].reshape(d, H * (dn + dr)), fp8).reshape(
        S, H, dn + dr)
    kva = _linear(h, blk["w_kva"], fp8)                     # (S, r + dr)
    c = _hold(_norm(kva[:, :r], blk["kv_norm"], p["norm_eps"]), fp8)
    kv = _linear(c, blk["w_kvb"].reshape(r, H * (dn + dv)), fp8).reshape(
        S, H, dn + dv)
    q_pe = _rope(q[..., dn:], pos, p["rope_theta"])
    k_pe = _hold(_rope(kva[:, None, r:], pos, p["rope_theta"]),
                 fp8)                                       # (S, 1, dr)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))],
                        -1)
    v = kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)

    def block(args):
        qb, i = args                                        # (QB, H, dn+dr)
        s = jnp.einsum("qhd,thd->hqt", qb, k) * scale
        qp = i * QB + jnp.arange(QB)
        s = jnp.where(qp[:, None] >= pos[None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqt,thd->qhd", a, v)

    o = jax.lax.map(block, (q.reshape(S // QB, QB, H, dn + dr),
                            jnp.arange(S // QB)))
    return _linear(o.reshape(S, H * dv), blk["wo"].reshape(H * dv, d), fp8)


def route(h, m, p, fp8: bool = False):
    """(weights (S, k), chosen experts (S, k)) of the expert layer for
    rows ``h``: float32 router logits, sigmoid scores, the top-k of
    score + bias, their unbiased scores normalised over the k, times the
    routed scale."""
    mo = p["moe"]
    scores = jax.nn.sigmoid(_linear(h, m["router"], fp8))
    _, top = jax.lax.top_k(scores + m["router_bias"].astype(jnp.float32),
                           mo["top_k"])
    w = jnp.take_along_axis(scores, top, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * mo["routed_scale"], top


def _experts(h, m, p, fp8: bool):
    """The held experts' part of the expert layer, plus the shared
    experts, for rows ``h`` (S, d)."""
    mo = p["moe"]
    w, top = route(h, m, p, fp8)
    y = _swiglu(h, m["ws1"], m["ws3"], m["ws2"], fp8)
    for j in range(mo["experts_held"]):
        gate = jnp.sum(jnp.where(top == mo["expert_offset"] + j, w, 0.0),
                       axis=-1, keepdims=True)              # (S, 1)
        y = y + gate * _swiglu(h, m["we1"][j], m["we3"][j], m["we2"][j],
                               fp8)
    return y


@functools.partial(jax.jit, static_argnames=("p", "kind", "fp8"))
def _layer(x, blk, p, kind: str, fp8: bool):
    """One decoder layer over rows ``x`` (S, d)."""
    x = _hold(x + _attention(x, blk, p, fp8), fp8)
    h = _norm(x, blk["mlp_norm"], p["norm_eps"])
    if kind == "dense":
        return _hold(x + _swiglu(h, blk["w1"], blk["w3"], blk["w2"], fp8),
                     fp8)
    return _hold(x + _experts(h, blk, p, fp8), fp8)


@functools.partial(jax.jit, static_argnames=("p", "fp8"))
def _head(x, at, final_norm, head, p, fp8: bool):
    h = _norm(x[at], final_norm, p["norm_eps"])
    return _linear(h, head, fp8)


class _Frozen(dict):
    """Hashable view of the program block, for jit's static arguments."""

    def __hash__(self):
        return hash(tuple(sorted((k, v if not isinstance(v, dict)
                                  else tuple(sorted(v.items())))
                                 for k, v in self.items())))


def _frozen(p: Dict) -> _Frozen:
    keys = ("n_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "norm_eps", "rope_theta")
    return _Frozen({**{k: p[k] for k in keys},
                    "moe": _Frozen({k: p["moe"][k] for k in (
                        "top_k", "routed_scale", "experts_held",
                        "expert_offset")})})


def _layer_of(params: Dict, p: Dict, layer: int) -> Tuple[str, Dict]:
    """(MLP kind, weights) of ``layer``, as ``shapes`` lays them out."""
    n_dense = p["first_k_dense"]
    if layer < n_dense:
        return "dense", {k: v[layer]
                         for k, v in params["dense_blocks"].items()}
    return "moe", {k: v[layer - n_dense]
                   for k, v in params["blocks"].items()}


def logits(params: Dict, p: Dict, tokens: Sequence[int],
           positions: Sequence[int], mode: str = "f32") -> np.ndarray:
    """Logits (len(positions), V) of the reference at ``positions``,
    teacher-forced over ``tokens``."""
    fp8 = mode == "fp8"
    pz = _frozen(p)
    n = len(tokens)
    S = max(PAD, -(-n // PAD) * PAD)
    toks = np.zeros((S,), np.int32)
    toks[:n] = tokens
    with jax.default_matmul_precision("highest"):
        x = _hold(params["embed"][jnp.asarray(toks)].astype(jnp.float32),
                  fp8)
        for l in range(p["n_layers"]):
            kind, blk = _layer_of(params, p, l)
            x = _layer(x, blk, pz, kind, fp8)
        out = _head(x, jnp.asarray(np.asarray(positions, np.int32)),
                    params["final_norm"], params["lm_head"], pz, fp8)
    return np.asarray(out)


# ---- work -----------------------------------------------------------------
def attention_work(seqs: Sequence, m: Dict, bytes_per: int = 2
                   ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's latent attention over a step's
    sequences (each with ``positions`` computed and context ``ctx``), as
    ``msa_fused`` runs it: MQA with one cached head of width r + dr.

    FLOPs: scores over r + dr lanes and the latent output over r, 2·H
    each per lane and (query, key) pair.  Bytes: q (r + dr) in and the
    output (r) out once per token and head, and each sequence's cached
    latent (r + dr per key) once."""
    H, r, dr = m["n_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    flops = nbytes = 0.0
    for s in seqs:
        n = int(s.positions.size)
        keys = int(s.positions.sum()) + n
        flops += 2.0 * H * (r + dr + r) * keys
        nbytes += bytes_per * (n * H * (r + dr + r) + s.ctx * (r + dr))
    return flops, nbytes


def _attn_params(m: Dict) -> int:
    """Attention weights one token multiplies through in one layer, in
    the absorbed form: q, the latent and its RoPE part, the absorption
    of q_nope into the latent (W_UK), the latent output's W_UV, W_o."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["v_head_dim"]
    return d * H * (dn + dr) + d * (r + dr) + H * dn * r + H * r * dv \
        + H * dv * d


def layer_work(seqs: Sequence, m: Dict) -> List[Dict[str, Tuple[float,
                                                                  float]]]:
    """Per layer, ``{kernel: (FLOPs, bytes)}``: every layer calls
    ``msa_fused`` once over the step's sequences."""
    att = attention_work(seqs, m)
    return [{"msa_fused": att} for _ in range(m["n_layers"])]


def step_flops(seqs: Sequence, n_logit_rows: int, m: Dict) -> float:
    """Model FLOPs of one step: every computed token through every
    layer's matmuls and attention, and the vocabulary head for the rows
    whose next token is sampled.  An expert layer's routed experts are
    counted at the expected slots on the held share, T·top_k·E_held/E
    (an expectation over routing, not the step's own count), beside the
    router and the shared experts for every token."""
    T = sum(int(s.positions.size) for s in seqs)
    d, mo = m["d_model"], m["moe"]
    n_dense = m["first_k_dense"]
    n_moe = m["n_layers"] - n_dense
    slots = T * mo["top_k"] * mo["experts_held"] / mo["num_experts"]
    att = attention_work(seqs, m)[0]
    total = m["n_layers"] * (2.0 * _attn_params(m) * T + att)
    total += n_dense * 2.0 * 3 * d * m["dense_d_ff"] * T
    total += n_moe * (2.0 * d * (mo["num_experts"] + 3 * mo["shared_d_ff"])
                      * T + 2.0 * 3 * d * m["d_ff"] * slots)
    return total + 2.0 * d * m["vocab_size"] * n_logit_rows
