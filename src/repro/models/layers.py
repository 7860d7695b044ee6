"""Core neural layers shared by every architecture family.

Functional style: params are nested dicts of jnp arrays; every layer is a
pure function.  Per-layer params carry a leading ``L`` axis and the model
body runs under ``jax.lax.scan`` so the HLO stays one-layer-sized even for
61-layer/1T-param configs.

Activation sharding is annotated with logical axis names via
``repro.distributed.context.constrain`` — a no-op on a single device.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.context import constrain, flag

# Logical activation axes used throughout:
#  "batch"   -> data parallel axes (pod, data)
#  "heads"   -> tensor parallel axis (model)
#  "ffn"     -> tensor parallel axis (model)
#  "kv_seq"  -> model axis for sequence-sharded KV caches (decode shapes)
#  "vocab"   -> model axis for the logits shard
NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    if theta <= 0:
        return jnp.zeros((head_dim // 2,), jnp.float32)
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    if theta <= 0:
        return x
    freqs = rope_freqs(x.shape[-1], theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings, computed on the fly."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention (XLA path: chunked flash with online softmax)
# ---------------------------------------------------------------------------

def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return cap * jnp.tanh(scores / cap)
    return scores


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KH, D) -> (B, S, KH*n_rep, D)."""
    if n_rep == 1:
        return k
    b, s, kh, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, d))
    return k.reshape(b, s, kh * n_rep, d)


def flash_attention(
    q: jax.Array,                 # (B, Sq, H, D)
    k: jax.Array,                 # (B, Sk, KH, D)
    v: jax.Array,                 # (B, Sk, KH, D)
    q_pos: jax.Array,             # (B, Sq) logical positions (multi-segment aware)
    kv_pos: jax.Array,            # (B, Sk)
    *,
    causal: bool = True,
    window: Optional[jax.Array] = None,   # scalar int32; <=0 -> full
    softcap: float = 0.0,
    kv_len: Optional[jax.Array] = None,   # (B,) valid kv length (padding mask)
    chunk_size: int = 1024,
) -> jax.Array:
    """Memory-efficient attention with online softmax over KV chunks.

    Positions are *logical*: causal masking compares logical positions, so a
    non-contiguous (multi-segment) context works by construction.  This is
    the pure-XLA oracle path; the Pallas MSA kernel implements the same
    contract on TPU.
    """
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    n_rep = h // kh
    scale = 1.0 / math.sqrt(d)

    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)

    nchunks = max(1, (sk + chunk_size - 1) // chunk_size)
    pad = nchunks * chunk_size - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=jnp.iinfo(jnp.int32).max)
    if kv_len is None:
        kv_len = jnp.full((b,), sk, jnp.int32)

    kc = k.reshape(b, nchunks, chunk_size, h, d)
    vc = v.reshape(b, nchunks, chunk_size, h, d)
    pc = kv_pos.reshape(b, nchunks, chunk_size)
    ic = jnp.arange(nchunks * chunk_size, dtype=jnp.int32).reshape(nchunks, chunk_size)

    qf = (q.astype(jnp.float32) * scale)

    def step(carry, xs):
        m, l, acc = carry
        k_i, v_i, p_i, idx_i = xs            # (b, c, h, d), (b, c)
        s = jnp.einsum("bqhd,bchd->bhqc", qf, k_i.astype(jnp.float32))
        s = _softcap(s, softcap)
        mask = idx_i[None, None, None, :] < kv_len[:, None, None, None]
        if causal:
            rel = q_pos[:, None, :, None] - p_i[:, None, None, :]  # (b,1,sq,c)
            mask = mask & (rel >= 0)
            if window is not None:
                mask = mask & (rel < jnp.maximum(window, 1) + jnp.where(window > 0, 0, sk + 10**9))
        s = jnp.where(mask, s, NEG_INF)
        m_i = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqc,bchd->bhqd", p, v_i.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc), _ = lax.scan(
        step, (m0, l0, acc0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
         pc.transpose(1, 0, 2), ic),
        unroll=bool(flag("unroll_scans", False)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)      # (B, Sq, H, D)


def banded_flash_attention(
    q: jax.Array,                 # (B, S, H, D) — self-attention layout
    k: jax.Array,                 # (B, S, KH, D)
    v: jax.Array,                 # (B, S, KH, D)
    *,
    window: int = 0,              # STATIC; 0 = full causal
    softcap: float = 0.0,
    q_tile: int = 512,
    kv_tile: int = 512,
) -> jax.Array:
    """Causal/banded flash attention with STATIC tile skipping.

    The chunked path in ``flash_attention`` computes the full S² score
    rectangle and masks — fine for short sequences, but at 32K with a
    1-2K sliding window it wastes 10-30x FLOPs (measured: hymba prefill
    useful ratio 0.048).  Here the kv-tile range per q tile is computed
    statically from the causal band:

        kv_lo(t) = max(0, t·C - window)   [window > 0]
        kv_hi(t) = (t+1)·C

    so compute is O(S·(window+C)) for windowed layers and exactly the
    lower triangle (~S²/2) for full-causal layers.  Contiguous positions
    only (train/prefill); the MSA paged kernels own the serving path."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    n_rep = h // kh
    scale = 1.0 / math.sqrt(d)
    assert s % q_tile == 0 and s % kv_tile == 0, (s, q_tile, kv_tile)
    nq, nk = s // q_tile, s // kv_tile

    kf = repeat_kv(k, n_rep)
    vf = repeat_kv(v, n_rep)
    out = jnp.zeros((b, s, h, d), q.dtype)

    # static per-q-tile kv ranges (uniform count so the loop is regular)
    per_tile = []
    for t in range(nq):
        hi = (t + 1) * q_tile
        lo = max(0, t * q_tile - window + 1) if window > 0 else 0
        lo_tile = lo // kv_tile
        hi_tile = (hi + kv_tile - 1) // kv_tile
        per_tile.append((lo_tile, hi_tile))
    max_tiles = max(ht - lt for lt, ht in per_tile)

    def q_tile_body(t_idx):
        lo_tile, hi_tile = per_tile[t_idx]
        n_t = hi_tile - lo_tile
        qt = jax.lax.dynamic_slice_in_dim(q, t_idx * q_tile, q_tile, 1)
        qt = qt.astype(jnp.float32) * scale
        q_pos = t_idx * q_tile + jnp.arange(q_tile, dtype=jnp.int32)

        m = jnp.full((b, h, q_tile), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, q_tile), jnp.float32)
        acc = jnp.zeros((b, h, q_tile, d), jnp.float32)
        for j in range(lo_tile, hi_tile):
            kt = jax.lax.dynamic_slice_in_dim(kf, j * kv_tile, kv_tile, 1)
            vt = jax.lax.dynamic_slice_in_dim(vf, j * kv_tile, kv_tile, 1)
            s_ = jnp.einsum("bqhd,bchd->bhqc", qt, kt,
                            preferred_element_type=jnp.float32)
            s_ = _softcap(s_, softcap)
            kv_pos = j * kv_tile + jnp.arange(kv_tile, dtype=jnp.int32)
            rel = q_pos[:, None] - kv_pos[None, :]
            mask = rel >= 0
            if window > 0:
                mask = mask & (rel < window)
            s_ = jnp.where(mask[None, None], s_, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            p = jnp.exp(s_ - m_new[..., None])
            p = jnp.where(mask[None, None], p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqc,bchd->bhqd", p, vt, preferred_element_type=jnp.float32)
            m = m_new
        o = (acc / jnp.maximum(l[..., None], 1e-30)).transpose(0, 2, 1, 3)
        return o.astype(q.dtype)

    outs = [q_tile_body(t) for t in range(nq)]
    return jnp.concatenate(outs, axis=1)


def decode_attention(
    q: jax.Array,            # (B, H, D) one new token per sequence
    k_cache: jax.Array,      # (B, S, KH, D)
    v_cache: jax.Array,      # (B, S, KH, D)
    kv_len: jax.Array,       # (B,) number of valid tokens (includes new one)
    *,
    window: Optional[jax.Array] = None,  # scalar int32; <=0 -> full attention
    softcap: float = 0.0,
) -> jax.Array:
    """Single-step decode attention over a (possibly sharded) KV cache."""
    b, s, kh, d = k_cache.shape
    h = q.shape[1]
    n_rep = h // kh
    scale = 1.0 / math.sqrt(d)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(b, kh, n_rep, d) * scale
    s_ = jnp.einsum("bgrd,bsgd->bgrs", qf, kf)
    s_ = _softcap(s_, softcap)
    idx = jnp.arange(s, dtype=jnp.int32)[None, None, None, :]
    mask = idx < kv_len[:, None, None, None]
    if window is not None:
        weff = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window),
                         jnp.iinfo(jnp.int32).max // 2)
        mask = mask & (idx >= kv_len[:, None, None, None] - weff)
    s_ = jnp.where(mask, s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p, vf)
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def swiglu_mlp(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    """x: (..., d); w1/w3: (d, f); w2: (f, d).

    No sharding constraint on ``h``: the f@model sharding is inferred from
    w1/w3, and annotating the leading dims ``None`` would *force* a
    full-batch all-gather (measured: +5.2 GB/layer wire at 6B scale —
    see EXPERIMENTS.md §Perf)."""
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2


# ---------------------------------------------------------------------------
# MoE: top-k routing with sort-based capacity dispatch (no one-hot einsum)
# ---------------------------------------------------------------------------

def topk_route(logits: jax.Array, k: int, *, score_fn: str = "softmax",
               bias: Optional[jax.Array] = None,
               scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """logits (T, E) -> (weights (T,k) f32, idx (T,k)).

    Softmax routing (the default, which takes no bias or scale): weights
    softmaxed over the top-k logits.  Sigmoid routing (DeepSeek-V3): each
    expert's score is ``sigmoid(logit)``; the top-k of ``score + bias``
    are chosen (the bias steers the choice only); their unbiased scores,
    normalised over the k chosen, times ``scale`` are the weights."""
    if score_fn == "softmax":
        assert bias is None and scale == 1.0
        topv, topi = lax.top_k(logits, k)
        return jax.nn.softmax(topv.astype(jnp.float32), axis=-1), topi
    assert score_fn == "sigmoid", score_fn
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, topi = lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, topi, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, topi


def capacity_dispatch(flat_expert: jax.Array, num_experts: int, capacity: int):
    """Compute per-slot position within its expert bucket + keep mask.

    flat_expert: (N,) int32 expert ids.  Returns (pos (N,), keep (N,) bool).
    O(N log N) sort-based ranking; overflow slots beyond ``capacity`` drop
    (their tokens fall back to the residual path), matching GShard-style
    capacity-factor dispatch.
    """
    n = flat_expert.shape[0]
    order = jnp.argsort(flat_expert, stable=True)            # rank -> slot
    sorted_e = flat_expert[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(num_experts, dtype=flat_expert.dtype))
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - start[sorted_e]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < capacity
    return pos, keep


def expand_virtual_experts(weights: jax.Array, topi: jax.Array,
                           split: int) -> Tuple[jax.Array, jax.Array]:
    """Map physical top-k routing to virtual (column-split) experts.

    weights/topi: (T, k).  Each physical expert e becomes `split` virtual
    experts e*split+j whose outputs SUM to the physical expert's output
    (SwiGLU decomposes exactly over d_ff column blocks), so each virtual
    slot carries the same router weight.  Returns (T, k*split) arrays."""
    if split == 1:
        return weights, topi
    t, k = topi.shape
    virt = topi[:, :, None] * split + jnp.arange(split, dtype=topi.dtype)
    w = jnp.broadcast_to(weights[:, :, None], (t, k, split))
    return w.reshape(t, k * split), virt.reshape(t, k * split)


def moe_ffn_local(
    x: jax.Array,           # (T, d) token activations (local shard)
    router_w: jax.Array,    # (d, E_physical)
    we1: jax.Array,         # (E_virtual, d, f) — the experts held here
    we3: jax.Array,         # (E_virtual, d, f)
    we2: jax.Array,         # (E_virtual, f, d)
    top_k: int,
    capacity_factor: float = 1.25,
    dropless: bool = False,
    expert_split: int = 1,
    *,
    score_fn: str = "softmax",
    router_bias: Optional[jax.Array] = None,
    routed_scale: float = 1.0,
    expert_offset: int = 0,
) -> jax.Array:
    """The expert layer: routes every token over all ``E_physical``
    experts and computes the part of the result that the experts held
    here give.

    Holding all of them (``we1`` has every virtual expert) is
    single-program MoE with sort-based capacity dispatch: tokens stay
    put, all experts computed locally.  ``dropless=True`` sets capacity =
    T (an expert can receive at most one slot per token since top-k
    indices are distinct), which guarantees no drops — required for
    lossless serving.

    Holding a share (``we1`` has fewer experts than the router scores:
    one chip's share of expert parallelism, physical experts
    ``[expert_offset, expert_offset + E_held)``) computes only the slots
    routed to the held experts, as grouped matmuls over the slots sorted
    by expert (``lax.ragged_dot``): no capacity, no dropped slot.  The
    routing weights are those of the whole top-k, so the shares of all
    chips sum to the whole layer; the absent experts' part is left out.
    Routing with ``score_fn="sigmoid"`` takes its logits in float32."""
    t, d = x.shape
    e = we1.shape[0]                                          # virtual
    e_route = router_w.shape[1]
    with jax.named_scope("router"):
        if score_fn == "softmax":
            logits = x @ router_w                             # (T, E_phys)
        else:
            logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        weights, topi = topk_route(logits, top_k, score_fn=score_fn,
                                   bias=router_bias,
                                   scale=routed_scale)        # (T, k)
    if e // expert_split < e_route:
        assert expert_split == 1, "a held share takes whole experts"
        with jax.named_scope("experts"):
            return _held_share(x, weights, topi - expert_offset, we1, we3,
                               we2)
    weights, topi = expand_virtual_experts(weights, topi, expert_split)
    k_eff = top_k * expert_split
    n = t * k_eff
    flat_e = topi.reshape(n)
    if dropless:
        capacity = t
    else:
        capacity = max(1, int(math.ceil(t * k_eff / e * capacity_factor)))
    pos, keep = capacity_dispatch(flat_e, e, capacity)

    slot = jnp.where(keep, flat_e * capacity + pos, e * capacity)  # overflow row
    x_rep = jnp.repeat(x, k_eff, axis=0)                      # (N, d)
    buf = jnp.zeros((e * capacity + 1, d), x.dtype).at[slot].set(x_rep)
    buf = buf[:-1].reshape(e, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we1)) * jnp.einsum(
        "ecd,edf->ecf", buf, we3)
    y = jnp.einsum("ecf,efd->ecd", h, we2)                    # (E, C, d)

    y_flat = y.reshape(e * capacity, d)
    safe_slot = jnp.where(keep, flat_e * capacity + pos, 0)
    gathered = jnp.where(keep[:, None], y_flat[safe_slot], 0.0)
    gathered = gathered * weights.reshape(n)[:, None].astype(x.dtype)
    return jnp.sum(gathered.reshape(t, k_eff, d), axis=1)


def _held_share(x: jax.Array, weights: jax.Array, local: jax.Array,
                we1: jax.Array, we3: jax.Array, we2: jax.Array) -> jax.Array:
    """Sum over each token's slots routed to the held experts of weight x
    SwiGLU expert output.  ``local`` (T, k) is each slot's expert index
    relative to the first held one; slots outside ``[0, E_held)`` are
    absent.  The slots are sorted by expert (absent ones last, past
    every group) and run as three grouped matmuls whose group sizes are
    the held experts' slot counts."""
    t, d = x.shape
    k = local.shape[1]
    e = we1.shape[0]
    flat = local.reshape(t * k)
    held = (flat >= 0) & (flat < e)
    group = jnp.where(held, flat, e)
    order = jnp.argsort(group, stable=True)                  # slots by expert
    tok = (order // k).astype(jnp.int32)
    sizes = jnp.bincount(group, length=e + 1)[:e].astype(jnp.int32)
    xs = x[tok]                                               # (T*k, d)
    h = jax.nn.silu(lax.ragged_dot(xs, we1, sizes)) * lax.ragged_dot(
        xs, we3, sizes)
    y = lax.ragged_dot(h, we2, sizes, preferred_element_type=jnp.float32)
    w = jnp.where(held, weights.reshape(t * k), 0.0)[order]
    y = jnp.where(held[order][:, None], y * w[:, None], 0.0)
    return jnp.zeros((t, d), jnp.float32).at[tok].add(y).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) — chunked scan, pure JAX
# ---------------------------------------------------------------------------

def segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k] for i>=j else -inf."""
    cs = x.shape[-1]
    xc = jnp.cumsum(x, axis=-1)
    out = xc[..., :, None] - xc[..., None, :]
    mask = jnp.tril(jnp.ones((cs, cs), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(
    x: jax.Array,      # (B, L, H, P) input heads (already multiplied by nothing)
    dt: jax.Array,     # (B, L, H) positive step sizes
    A: jax.Array,      # (H,) negative decay rates
    B_: jax.Array,     # (B, L, G, N)
    C_: jax.Array,     # (B, L, G, N)
    chunk: int,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Minimal SSD (Mamba-2 Listing 1 style).  Returns (y, final_state)."""
    b, l, h, p = x.shape
    g, n = B_.shape[2], B_.shape[3]
    assert l % chunk == 0, (l, chunk)
    c = l // chunk
    rep = h // g

    xd = x * dt[..., None]                                    # (B,L,H,P)
    a = dt * A[None, None, :]                                 # (B,L,H) log-decay per step

    xc = xd.reshape(b, c, chunk, h, p)
    ac = a.reshape(b, c, chunk, h)
    Bc = jnp.repeat(B_.reshape(b, c, chunk, g, n), rep, axis=3)   # (B,c,cs,H,N)
    Cc = jnp.repeat(C_.reshape(b, c, chunk, g, n), rep, axis=3)

    a_cum = jnp.cumsum(ac, axis=2)                            # (B,c,cs,H)

    # 1. intra-chunk output (quadratic within chunk)
    Lmat = jnp.exp(segsum(ac.transpose(0, 1, 3, 2)))          # (B,c,H,cs,cs)
    scores = jnp.einsum("bcihn,bcjhn->bchij", Cc, Bc)
    y_diag = jnp.einsum("bchij,bchij,bcjhp->bcihp", scores, Lmat,
                        xc.astype(jnp.float32)).astype(x.dtype)

    # 2. chunk-final states
    decay_states = jnp.exp(a_cum[:, :, -1:, :] - a_cum)       # (B,c,cs,H)
    states = jnp.einsum("bcihn,bcih,bcihp->bchpn", Bc, decay_states,
                        xc.astype(jnp.float32))               # (B,c,H,P,N)

    # 3. inter-chunk recurrence
    chunk_decay = jnp.exp(a_cum[:, :, -1, :])                 # (B,c,H)
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    def scan_fn(carry, xs):
        st, dk = xs                                           # (B,H,P,N), (B,H)
        new = carry * dk[..., None, None] + st
        return new, carry                                     # emit state *before* this chunk

    final, prev_states = lax.scan(
        scan_fn, init_state.astype(jnp.float32),
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
        unroll=bool(flag("unroll_scans", False)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)        # (B,c,H,P,N)

    # 4. inter-chunk output
    state_decay_out = jnp.exp(a_cum)                          # (B,c,cs,H)
    y_off = jnp.einsum("bcihn,bchpn,bcih->bcihp", Cc, prev_states,
                       state_decay_out).astype(x.dtype)

    y = (y_diag + y_off).reshape(b, l, h, p)
    return y, final


def ssd_decode_step(
    x: jax.Array,      # (B, H, P)
    dt: jax.Array,     # (B, H)
    A: jax.Array,      # (H,)
    B_: jax.Array,     # (B, G, N)
    C_: jax.Array,     # (B, G, N)
    state: jax.Array,  # (B, H, P, N) float32
) -> Tuple[jax.Array, jax.Array]:
    h, g = x.shape[1], B_.shape[1]
    rep = h // g
    Bh = jnp.repeat(B_, rep, axis=1)                          # (B,H,N)
    Ch = jnp.repeat(C_, rep, axis=1)
    decay = jnp.exp(dt * A[None, :])                          # (B,H)
    xd = (x * dt[..., None]).astype(jnp.float32)
    new_state = state * decay[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", xd, Bh.astype(jnp.float32))
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch.astype(jnp.float32))
    return y.astype(x.dtype), new_state


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, L, C); w: (C, K); b: (C,)."""
    k = w.shape[1]
    l = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):  # out[t] = sum_i x[t-(k-1)+i] * w[:, i]
        out = out + xp[:, i:i + l, :] * w[:, i][None, None, :]
    return out + b[None, None, :]


def causal_conv1d_step(x_new: jax.Array, conv_state: jax.Array,
                       w: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single decode step.  conv_state: (B, K-1, C) previous inputs."""
    window = jnp.concatenate([conv_state, x_new[:, None, :]], axis=1)  # (B,K,C)
    out = jnp.einsum("bkc,ck->bc", window, w) + b[None, :]
    return out, window[:, 1:, :]
