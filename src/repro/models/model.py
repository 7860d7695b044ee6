"""Composable model definition for all assigned architecture families.

``init_params(cfg, key)`` builds the parameter pytree (per-layer params are
stacked with a leading ``L`` axis and the body runs under ``lax.scan``);
``param_axes(cfg)`` returns a same-structure pytree of *logical* sharding
axes consumed by ``repro.distributed.sharding``.

Execution entry points:
  * ``forward(params, cfg, batch)``         — full-sequence causal forward (train/prefill)
  * ``init_decode_state(cfg, batch_size, max_len)``
  * ``decode_step(params, cfg, state, tokens)``
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.context import constrain, flag
from repro.models import layers
from repro.models.layers import (
    apply_rope,
    causal_conv1d,
    causal_conv1d_step,
    decode_attention,
    flash_attention,
    moe_ffn_local,
    rms_norm,
    sinusoidal_positions,
    ssd_chunked,
    ssd_decode_step,
    swiglu_mlp,
)

# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------


class _Builder:
    """Builds params and the mirrored logical-axis tree in one pass."""

    def __init__(self, key: jax.Array, dtype: jnp.dtype, abstract: bool = False):
        self.key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: Dict = {}
        self.axes: Dict = {}

    def _split(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def add(self, tree: Dict, axtree: Dict, name: str, shape, axes,
            scale: Optional[float] = None, zeros: bool = False):
        assert len(shape) == len(axes), (name, shape, axes)
        if self.abstract:
            tree[name] = jax.ShapeDtypeStruct(tuple(shape), self.dtype)
        elif zeros:
            tree[name] = jnp.zeros(shape, self.dtype)
        else:
            if scale is None:
                scale = 1.0 / math.sqrt(shape[-2] if len(shape) >= 2 else shape[-1])
            tree[name] = (jax.random.normal(self._split(), shape, jnp.float32)
                          * scale).astype(self.dtype)
        axtree[name] = tuple(axes)


def _block_defs(cfg: ModelConfig, b: _Builder, blocks: Dict, axes: Dict,
                n_layers: int, *, cross_attn: bool = False,
                causal_family: bool = True) -> None:
    """Declare one transformer-block family's stacked params.

    Residual-output projections (wo/w2/we2/ssm_out) are depth-scaled by
    1/sqrt(2L) (GPT-2 style) so activations and gradients stay O(1) with
    depth — without it the tied-embedding gradient grows ~exponentially
    past ~4 layers (measured)."""
    L = n_layers
    d, hd = cfg.d_model, cfg.head_dim
    H, KH = cfg.n_heads, cfg.n_kv_heads
    depth = 1.0 / math.sqrt(2.0 * max(L, 1))

    has_attn = cfg.family != "ssm"
    has_ssm = cfg.ssm is not None
    # q/k/v projections contract over d_model (not the head axis the
    # default shape[-2] rule would pick): unit-variance q and k keep the
    # attention logits O(1) instead of O(d/H), where random weights give
    # near-argmax attention and a chaotic forward
    qkv = 1.0 / math.sqrt(d)
    if has_attn:
        b.add(blocks, axes, "attn_norm", (L, d), (None, None), zeros=True)
        b.add(blocks, axes, "wq", (L, d, H, hd), (None, "fsdp", "heads", None),
              scale=qkv)
        b.add(blocks, axes, "wk", (L, d, KH, hd),
              (None, "fsdp", "kv_heads", None), scale=qkv)
        b.add(blocks, axes, "wv", (L, d, KH, hd),
              (None, "fsdp", "kv_heads", None), scale=qkv)
        b.add(blocks, axes, "wo", (L, H, hd, d), (None, "heads", None, "fsdp"),
              scale=depth / math.sqrt(H * hd))
    if cross_attn:
        b.add(blocks, axes, "xattn_norm", (L, d), (None, None), zeros=True)
        b.add(blocks, axes, "xwq", (L, d, H, hd),
              (None, "fsdp", "heads", None), scale=qkv)
        b.add(blocks, axes, "xwk", (L, d, KH, hd),
              (None, "fsdp", "kv_heads", None), scale=qkv)
        b.add(blocks, axes, "xwv", (L, d, KH, hd),
              (None, "fsdp", "kv_heads", None), scale=qkv)
        b.add(blocks, axes, "xwo", (L, H, hd, d), (None, "heads", None, "fsdp"),
              scale=depth / math.sqrt(H * hd))
    if has_ssm:
        s = cfg.ssm
        di = s.d_inner(d)
        nh = s.n_heads(d)
        gn = s.n_groups * s.d_state
        conv_dim = di + 2 * gn
        b.add(blocks, axes, "ssm_norm", (L, d), (None, None), zeros=True)
        b.add(blocks, axes, "in_proj", (L, d, 2 * di + 2 * gn + nh),
              (None, "fsdp", "ssm_inner"))
        b.add(blocks, axes, "conv_w", (L, conv_dim, s.d_conv),
              (None, "ssm_inner", None), scale=0.5)
        b.add(blocks, axes, "conv_b", (L, conv_dim), (None, "ssm_inner"), zeros=True)
        b.add(blocks, axes, "A_log", (L, nh), (None, "ssm_heads"), scale=1.0)
        b.add(blocks, axes, "D", (L, nh), (None, "ssm_heads"), scale=1.0)
        b.add(blocks, axes, "dt_bias", (L, nh), (None, "ssm_heads"), scale=1.0)
        b.add(blocks, axes, "gnorm", (L, di), (None, "ssm_inner"), zeros=True)
        b.add(blocks, axes, "ssm_out", (L, di, d), (None, "ssm_inner", "fsdp"),
              scale=depth / math.sqrt(di))
    if cfg.moe is not None:
        E, f = cfg.moe.num_experts, cfg.d_ff   # virtual experts / split d_ff
        b.add(blocks, axes, "mlp_norm", (L, d), (None, None), zeros=True)
        b.add(blocks, axes, "router",
              (L, d, cfg.moe.num_physical_experts), (None, None, None))
        b.add(blocks, axes, "we1", (L, E, d, f),
              (None, "experts", "expert_fsdp", "expert_ffn"))
        b.add(blocks, axes, "we3", (L, E, d, f),
              (None, "experts", "expert_fsdp", "expert_ffn"))
        b.add(blocks, axes, "we2", (L, E, f, d),
              (None, "experts", "expert_ffn", "expert_fsdp"),
              scale=depth / math.sqrt(f))
    elif cfg.d_ff > 0:
        f = cfg.d_ff
        b.add(blocks, axes, "mlp_norm", (L, d), (None, None), zeros=True)
        b.add(blocks, axes, "w1", (L, d, f), (None, "fsdp", "ffn"))
        b.add(blocks, axes, "w3", (L, d, f), (None, "fsdp", "ffn"))
        b.add(blocks, axes, "w2", (L, f, d), (None, "ffn", "fsdp"),
              scale=depth / math.sqrt(f))


def _build(cfg: ModelConfig, key: jax.Array, abstract: bool) -> Tuple[Dict, Dict]:
    b = _Builder(key, jnp.dtype(cfg.dtype), abstract=abstract)
    params: Dict = {}
    axes: Dict = {}

    b.add(params, axes, "embed", (cfg.vocab_size, cfg.d_model), ("vocab", None),
          scale=0.02)
    blocks: Dict = {}
    blocks_axes: Dict = {}
    _block_defs(cfg, b, blocks, blocks_axes, cfg.n_layers,
                cross_attn=cfg.enc_dec)
    params["blocks"] = blocks
    axes["blocks"] = blocks_axes

    if cfg.enc_dec:
        enc: Dict = {}
        enc_axes: Dict = {}
        _block_defs(cfg, b, enc, enc_axes, cfg.n_encoder_layers)
        params["enc_blocks"] = enc
        axes["enc_blocks"] = enc_axes
        b.add(params, axes, "enc_final_norm", (cfg.d_model,), (None,), zeros=True)

    b.add(params, axes, "final_norm", (cfg.d_model,), (None,), zeros=True)
    if not cfg.tie_embeddings:
        b.add(params, axes, "lm_head", (cfg.d_model, cfg.vocab_size),
              (None, "vocab"), scale=0.02)
    return params, axes


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict:
    return _build(cfg, key, abstract=False)[0]


def abstract_params(cfg: ModelConfig) -> Dict:
    return _build(cfg, jax.random.PRNGKey(0), abstract=True)[0]


def param_axes(cfg: ModelConfig) -> Dict:
    return _build(cfg, jax.random.PRNGKey(0), abstract=True)[1]


# ---------------------------------------------------------------------------
# Block application (full-sequence mode)
# ---------------------------------------------------------------------------


def _attn_sublayer(x, blk, cfg: ModelConfig, q_pos, kv_pos, window, *,
                   prefix: str = "", k_ext=None, v_ext=None, causal=True,
                   return_kv=False):
    """Self- (or cross-) attention sublayer. x: (B,S,d).

    ``window`` may be a traced scalar (scan path) or a static python int —
    the latter enables the banded kernel, which statically skips kv tiles
    outside the causal band / sliding window (EXPERIMENTS.md §Perf)."""
    h = rms_norm(x, blk[prefix + "attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, blk[prefix + "wq"])
    src = h if k_ext is None else k_ext
    k = jnp.einsum("bsd,dhk->bshk", src, blk[prefix + "wk"])
    v = jnp.einsum("bsd,dhk->bshk", src if v_ext is None else v_ext,
                   blk[prefix + "wv"])
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    if causal and cfg.rope_theta > 0:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, kv_pos, cfg.rope_theta)
    s_len = q.shape[1]
    use_banded = (flag("banded_attention", False) and causal
                  and k_ext is None and isinstance(window, int))
    if use_banded:
        # tile size trades FLOP-skipping granularity against HLO size
        # (the banded loop is unrolled): window-sized tiles keep compute
        # <= 2x window per token with ~2 kv tiles per q tile
        tile = min(window, 2048) if window > 0 else max(1024, s_len // 8)
        if s_len % tile == 0:
            out = layers.banded_flash_attention(
                q, k, v, window=window, softcap=cfg.attn_logit_softcap,
                q_tile=tile, kv_tile=tile)
        else:
            use_banded = False
    if not use_banded:
        out = flash_attention(
            q, k, v, q_pos, kv_pos, causal=causal,
            window=window if not isinstance(window, int) or window > 0
            else None,
            softcap=cfg.attn_logit_softcap,
            chunk_size=int(flag("attn_chunk", 1024)))
    out = constrain(out, "batch", None, "heads", None)
    out = jnp.einsum("bshk,hkd->bsd", out, blk[prefix + "wo"])
    if return_kv:
        return out, (k, v)
    return out


def _ssm_sublayer(x, blk, cfg: ModelConfig):
    """Mamba2 SSD sublayer (full sequence). x: (B,S,d) -> (B,S,d)."""
    s = cfg.ssm
    bsz, L, d = x.shape
    di = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    gn = s.n_groups * s.d_state

    h = rms_norm(x, blk["ssm_norm"], cfg.norm_eps)
    zxbcdt = h @ blk["in_proj"]
    zxbcdt = constrain(zxbcdt, "batch", None, "ssm_inner")
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * gn], axis=-1)
    xBC = jax.nn.silu(causal_conv1d(xBC, blk["conv_w"], blk["conv_b"]))
    xs, B_, C_ = jnp.split(xBC, [di, di + gn], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + blk["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(blk["A_log"].astype(jnp.float32))

    # pad to chunk multiple (zero dt => no state contribution)
    chunk = s.chunk_size
    pad = (-L) % chunk
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    xh = xs.reshape(bsz, L + pad, nh, s.head_dim)
    y, _ = ssd_chunked(
        xh, dt, A,
        B_.reshape(bsz, L + pad, s.n_groups, s.d_state),
        C_.reshape(bsz, L + pad, s.n_groups, s.d_state),
        chunk)
    y = y + xh * blk["D"].astype(xh.dtype)[None, None, :, None]
    y = y.reshape(bsz, L + pad, di)[:, :L]
    y = rms_norm(y * jax.nn.silu(z), blk["gnorm"], cfg.norm_eps)
    return y @ blk["ssm_out"]


def _ffn_sublayer(x, blk, cfg: ModelConfig):
    if cfg.moe is not None:
        h = rms_norm(x, blk["mlp_norm"], cfg.norm_eps)
        bsz, L, d = h.shape
        if flag("moe_alltoall", False):
            from repro.distributed.moe_ep import moe_ffn_alltoall
            y = moe_ffn_alltoall(h, blk["router"], blk["we1"], blk["we3"],
                                 blk["we2"], cfg)
        else:
            y = moe_ffn_local(h.reshape(bsz * L, d), blk["router"], blk["we1"],
                              blk["we3"], blk["we2"], cfg.moe.top_k,
                              cfg.moe.capacity_factor,
                              dropless=cfg.moe.dropless,
                              expert_split=cfg.moe.expert_split,
                              ).reshape(bsz, L, d)
        return y
    if cfg.d_ff > 0:
        h = rms_norm(x, blk["mlp_norm"], cfg.norm_eps)
        return swiglu_mlp(h, blk["w1"], blk["w3"], blk["w2"])
    return None


def _apply_block(x, blk, cfg: ModelConfig, q_pos, window, enc_out=None,
                 collect_kv: bool = False):
    """One decoder block, full-sequence mode. Returns (x, kv-or-None)."""
    kv = None
    if cfg.family == "ssm":
        x = x + _ssm_sublayer(x, blk, cfg)
    elif cfg.hybrid_attn_ssm:
        attn, kv = _attn_sublayer(x, blk, cfg, q_pos, q_pos, window,
                                  return_kv=True)
        ssm = _ssm_sublayer(x, blk, cfg)
        x = x + 0.5 * (attn + ssm)
    else:
        attn, kv = _attn_sublayer(x, blk, cfg, q_pos, q_pos, window,
                                  return_kv=True)
        x = x + attn
    if cfg.enc_dec and enc_out is not None:
        enc_pos = jnp.zeros(enc_out.shape[:2], jnp.int32)
        x = x + _attn_sublayer(x, blk, cfg, q_pos, enc_pos, None,
                               prefix="x", k_ext=enc_out, causal=False)
    ffn = _ffn_sublayer(x, blk, cfg)
    if ffn is not None:
        x = x + ffn
    if flag("seq_parallel", False):
        # Megatron-style sequence parallelism (kept selectable; REFUTED as
        # a default — see §Perf: GSPMD added gathers instead of splitting
        # the all-reduces into RS+AG)
        x = constrain(x, "batch", "seq_sp", None)
    if flag("ar_barrier", False):
        # stop XLA from hoisting the next norm's f32 upcast across the
        # model-axis all-reduce (measured: f32 AR doubles residual wire)
        x = jax.lax.optimization_barrier(x)
    return x, (kv if collect_kv else None)


def _layer_windows(cfg: ModelConfig, n_layers: int) -> jnp.ndarray:
    """Per-layer attention window (0 = full attention)."""
    win = []
    for i in range(n_layers):
        if cfg.sliding_window > 0 and cfg.layer_is_local(i):
            win.append(cfg.sliding_window)
        else:
            win.append(0)
    return jnp.asarray(win, jnp.int32)


def _scan_blocks(x, blocks, cfg: ModelConfig, q_pos, n_layers, enc_out=None,
                 remat: bool = False, collect_kv: bool = False):
    unroll = bool(flag("unroll_scans", False))
    static_windows = [cfg.sliding_window if (cfg.sliding_window > 0
                                             and cfg.layer_is_local(i)) else 0
                      for i in range(n_layers)]

    if flag("banded_attention", False) and cfg.family != "ssm":
        distinct = sorted(set(static_windows))
        if len(distinct) == 1:
            # uniform window: plain scan, window static via closure
            def body(carry, blk):
                return _apply_block(carry, blk, cfg, q_pos, distinct[0],
                                    enc_out=enc_out, collect_kv=collect_kv)
            if remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, kvs = lax.scan(body, x, blocks, unroll=unroll)
            return (x, kvs) if collect_kv else x
        period = cfg.local_global_ratio + 1
        if n_layers % period == 0:
            # mixed local/global: scan over superblocks of one full period
            # so every layer's window stays STATIC inside the body
            n_super = n_layers // period
            sblocks = jax.tree_util.tree_map(
                lambda a: a.reshape(n_super, period, *a.shape[1:]), blocks)

            def body(carry, sblk):
                kvs = []
                for i in range(period):
                    blk_i = jax.tree_util.tree_map(lambda a: a[i], sblk)
                    carry, kv = _apply_block(
                        carry, blk_i, cfg, q_pos, static_windows[i],
                        enc_out=enc_out, collect_kv=collect_kv)
                    kvs.append(kv)
                if collect_kv:
                    kv = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *kvs)
                else:
                    kv = None
                return carry, kv

            if remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, kvs = lax.scan(body, x, sblocks, unroll=unroll)
            if collect_kv:
                kvs = jax.tree_util.tree_map(
                    lambda a: a.reshape(n_layers, *a.shape[2:]), kvs)
            return (x, kvs) if collect_kv else x
        # fall through to the traced-window scan

    windows = _layer_windows(cfg, n_layers)

    def body(carry, xs):
        blk, win = xs
        out, kv = _apply_block(carry, blk, cfg, q_pos, win, enc_out=enc_out,
                               collect_kv=collect_kv)
        return out, kv

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, kvs = lax.scan(body, x, (blocks, windows),
                      unroll=unroll)
    return (x, kvs) if collect_kv else x


# ---------------------------------------------------------------------------
# Public: full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def embed_inputs(params, cfg: ModelConfig, batch: Dict) -> jax.Array:
    if "embeds" in batch and batch["embeds"] is not None:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = params["embed"][batch["tokens"]]
    if cfg.rope_theta <= 0 and not cfg.enc_dec:
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
        x = x + sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)
    return x


def _encoder_forward(params, cfg: ModelConfig, enc_embeds: jax.Array,
                     remat: bool = False) -> jax.Array:
    x = enc_embeds.astype(jnp.dtype(cfg.dtype))
    pos = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32)[None, :],
                           x.shape[:2])
    x = x + sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)

    def body(carry, blk):
        h = carry + _attn_sublayer(carry, blk, cfg, pos, pos, None, causal=False)
        return h + _ffn_sublayer(h, blk, cfg), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = lax.scan(body, x, params["enc_blocks"],
                    unroll=bool(flag("unroll_scans", False)))
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, batch: Dict, *, remat: bool = False,
            return_kv: bool = False, last_only: bool = False, at=None):
    """Full causal forward: returns logits (B, S, V).

    ``return_kv`` additionally returns the per-layer KV cache stacks
    (L, B, S, KH, D) — the product of an inference *prefill* step.
    ``last_only`` computes logits for the final position only (prefill);
    ``at`` (an int array) for those positions only."""
    x = embed_inputs(params, cfg, batch)
    x = constrain(x, "batch", None, None)
    bsz, S = x.shape[:2]
    q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (bsz, S))
    if cfg.enc_dec:
        enc_out = _encoder_forward(params, cfg, batch["enc_embeds"], remat=remat)
        x = x + sinusoidal_positions(q_pos, cfg.d_model).astype(x.dtype)
    else:
        enc_out = None
    out = _scan_blocks(x, params["blocks"], cfg, q_pos, cfg.n_layers,
                       enc_out=enc_out, remat=remat, collect_kv=return_kv)
    x, kvs = out if return_kv else (out, None)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    elif at is not None:
        x = x[:, at]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    logits = constrain(logits, "batch", None, "vocab")
    if return_kv:
        return logits, kvs
    return logits


def loss_fn(params, cfg: ModelConfig, batch: Dict, *, remat: bool = True) -> jax.Array:
    logits = forward(params, cfg, batch, remat=remat)
    labels = batch["labels"]
    if cfg.real_vocab and cfg.real_vocab < cfg.vocab_size:
        pad_mask = jnp.arange(cfg.vocab_size) >= cfg.real_vocab
        logits = jnp.where(pad_mask[None, None, :], -1e9, logits)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# Decode state + step
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch_size: int, max_len: int,
                      *, dtype: Optional[str] = None, abstract: bool = False,
                      enc_out: Optional[jax.Array] = None) -> Dict:
    """Dense (contiguous per-sequence) decode cache used by dry-run/decode.

    The serving engine uses the paged layout in ``repro.serving`` instead.
    """
    dt = jnp.dtype(dtype or cfg.dtype)
    L = cfg.n_layers
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else (
        lambda s, d: jnp.zeros(s, d))
    state: Dict = {"pos": mk((batch_size,), jnp.int32)}
    if cfg.family != "ssm":
        kv_len = max_len
        if cfg.sliding_window > 0 and cfg.local_global_ratio <= 0:
            kv_len = min(max_len, cfg.sliding_window)
        state["k"] = mk((L, batch_size, kv_len, cfg.n_kv_heads, cfg.head_dim), dt)
        state["v"] = mk((L, batch_size, kv_len, cfg.n_kv_heads, cfg.head_dim), dt)
    if cfg.ssm is not None:
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        nh = s.n_heads(cfg.d_model)
        conv_dim = di + 2 * s.n_groups * s.d_state
        state["conv"] = mk((L, batch_size, s.d_conv - 1, conv_dim), dt)
        state["ssm"] = mk((L, batch_size, nh, s.head_dim, s.d_state), jnp.float32)
    if cfg.enc_dec:
        state["xk"] = mk((L, batch_size, cfg.encoder_len, cfg.n_kv_heads,
                          cfg.head_dim), dt)
        state["xv"] = mk((L, batch_size, cfg.encoder_len, cfg.n_kv_heads,
                          cfg.head_dim), dt)
    return state


def prep_cross_attention(params, cfg: ModelConfig, enc_embeds: jax.Array,
                         state: Dict) -> Dict:
    """Run encoder once and cache per-layer cross K/V."""
    enc_out = _encoder_forward(params, cfg, enc_embeds)

    def per_layer(blk):
        k = jnp.einsum("bsd,dhk->bshk", enc_out, blk["xwk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, blk["xwv"])
        return k, v

    xk, xv = jax.vmap(per_layer)(params["blocks"])
    return dict(state, xk=xk, xv=xv)


def _decode_attn_sublayer(x1, blk, cfg: ModelConfig, k_l, v_l, pos, window,
                          *, prefix: str = "", rope: bool = True,
                          update_cache: bool = True, kv_len_override=None,
                          ring: bool = False):
    """x1: (B, d) single token. ``window`` may be a traced int32 scalar
    (0 = full attention). Returns (out (B,d), new_k, new_v)."""
    b, d = x1.shape
    h = rms_norm(x1, blk[prefix + "attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bd,dhk->bhk", h, blk[prefix + "wq"])
    if rope and cfg.rope_theta > 0:
        q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    if update_cache:
        k_new = jnp.einsum("bd,dhk->bhk", h, blk[prefix + "wk"])
        v_new = jnp.einsum("bd,dhk->bhk", h, blk[prefix + "wv"])
        if rope and cfg.rope_theta > 0:
            k_new = apply_rope(k_new[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        s_max = k_l.shape[1]
        if ring:
            slot = pos % s_max        # ring buffer for pure sliding-window cache
        else:
            slot = jnp.minimum(pos, s_max - 1)
        # where-form single-row update instead of scatter: GSPMD partitions
        # the elementwise select cleanly along the sharded seq dim, and the
        # CPU backend's scatter lowering would upcast the whole cache to
        # f32 (measured 5x bytes; §Perf iteration C)
        sel = (jnp.arange(s_max, dtype=jnp.int32)[None, :]
               == slot[:, None])[..., None, None]
        k_l = jnp.where(sel, k_new[:, None], k_l)
        v_l = jnp.where(sel, v_new[:, None], v_l)
    kv_len = kv_len_override if kv_len_override is not None else pos + 1
    if flag("flash_decode", False):
        from repro.distributed.flash_decode import sharded_decode_attention
        out = sharded_decode_attention(q, k_l, v_l, kv_len, window=window,
                                       softcap=cfg.attn_logit_softcap)
    else:
        out = decode_attention(q, k_l, v_l, kv_len, window=window,
                               softcap=cfg.attn_logit_softcap)
    out = jnp.einsum("bhk,hkd->bd", out, blk[prefix + "wo"])
    return out, k_l, v_l


def _decode_ssm_sublayer(x1, blk, cfg: ModelConfig, conv_state, ssm_state):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    gn = s.n_groups * s.d_state
    h = rms_norm(x1, blk["ssm_norm"], cfg.norm_eps)
    zxbcdt = h @ blk["in_proj"]
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * gn], axis=-1)
    xBC, conv_state = causal_conv1d_step(xBC, conv_state, blk["conv_w"],
                                         blk["conv_b"])
    xBC = jax.nn.silu(xBC)
    xs, B_, C_ = jnp.split(xBC, [di, di + gn], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + blk["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(blk["A_log"].astype(jnp.float32))
    bsz = x1.shape[0]
    y, ssm_state = ssd_decode_step(
        xs.reshape(bsz, nh, s.head_dim), dt, A,
        B_.reshape(bsz, s.n_groups, s.d_state),
        C_.reshape(bsz, s.n_groups, s.d_state), ssm_state)
    y = y + xs.reshape(bsz, nh, s.head_dim) * blk["D"].astype(y.dtype)[None, :, None]
    y = rms_norm(y.reshape(bsz, di) * jax.nn.silu(z), blk["gnorm"], cfg.norm_eps)
    return y @ blk["ssm_out"], conv_state, ssm_state


def decode_step(params, cfg: ModelConfig, state: Dict,
                tokens: jax.Array) -> Tuple[jax.Array, Dict]:
    """One decode step. tokens: (B,) int32. Returns (logits (B,V), state)."""
    x = params["embed"][tokens]
    if cfg.rope_theta <= 0:
        x = x + sinusoidal_positions(state["pos"], cfg.d_model).astype(x.dtype)
    x = constrain(x, "batch", None)
    pos = state["pos"]
    windows = _layer_windows(cfg, cfg.n_layers)

    has_attn = cfg.family != "ssm"
    has_ssm = cfg.ssm is not None

    def body(carry, xs):
        x1 = carry
        blk = xs["blk"]
        win = xs["win"]
        outs = {}
        if cfg.family == "ssm":
            y, outs["conv"], outs["ssm"] = _decode_ssm_sublayer(
                x1, blk, cfg, xs["conv"], xs["ssm"])
            x1 = x1 + y
        elif cfg.hybrid_attn_ssm:
            a, outs["k"], outs["v"] = _decode_attn_sublayer(
                x1, blk, cfg, xs["k"], xs["v"], pos, win)
            m, outs["conv"], outs["ssm"] = _decode_ssm_sublayer(
                x1, blk, cfg, xs["conv"], xs["ssm"])
            x1 = x1 + 0.5 * (a + m)
        else:
            a, outs["k"], outs["v"] = _decode_attn_sublayer(
                x1, blk, cfg, xs["k"], xs["v"], pos, win)
            x1 = x1 + a
        if cfg.enc_dec:
            enc_len = jnp.full((x1.shape[0],), cfg.encoder_len, jnp.int32)
            xa, _, _ = _decode_attn_sublayer(
                x1, blk, cfg, xs["xk"], xs["xv"], pos, None, prefix="x",
                rope=False, update_cache=False, kv_len_override=enc_len)
            x1 = x1 + xa
        ffn = _ffn_single(x1, blk, cfg)
        if ffn is not None:
            x1 = x1 + ffn
        return x1, outs

    xs = {"blk": params["blocks"], "win": windows}
    for key in ("k", "v", "conv", "ssm", "xk", "xv"):
        if key in state:
            xs[key] = state[key]
    x, outs = lax.scan(body, x, xs,
                       unroll=bool(flag("unroll_scans", False)))

    new_state = dict(state)
    for key in ("k", "v", "conv", "ssm"):
        if key in outs:
            new_state[key] = outs[key]
    new_state["pos"] = pos + 1

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return constrain(logits, "batch", "vocab"), new_state


def _ffn_single(x1, blk, cfg: ModelConfig):
    """FFN on a single-token batch (B, d) — routes through the same
    (possibly expert-parallel) path as the full-sequence sublayer."""
    if cfg.moe is None and cfg.d_ff <= 0:
        return None
    y = _ffn_sublayer(x1[:, None, :], blk, cfg)
    return None if y is None else y[:, 0]
