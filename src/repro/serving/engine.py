"""Inference engine: one jitted device step executing a mixed batch of
multi-segment prefill chunks and decode tokens (paper §4.1/§5.3).

All prefill chunks and decode rows share one flattened varlen ``(T, H,
D)`` token stream (paper: "hidden states of two segments can directly be
concatenated when computing MLP and LayerNorm"): prefill chunks pack
densely at its head with per-sequence ``q_start``/``q_len`` runs, decode
rows follow as runs of length 1, and attention runs as **one**
``msa_fused`` dispatch per layer over the paged KV pool (the single fused
dispatch the paper identifies as essential, Fig. 13).

Step shapes are static per **occupancy bucket**: the step compiles once
per ``(t_bucket, np_bucket)`` drawn from a small lattice (default
``T ∈ {B, Tmax/16, Tmax/8, Tmax/4, Tmax/2, Tmax}`` ×
``NP ∈ {NPmax/4, NPmax}``), selected
per step by the scheduler from its §5.1 chunk decision — decode-only
steps stop paying for the full prefill allowance and short contexts stop
streaming the full page table.  The jit cache *is* the
compile-once-per-bucket cache (bucket dims are static argnums);
``jit_traces`` must equal ``len(buckets_used)``.

Overlapped pipeline support (one-step-deep, see docs/ARCHITECTURE.md):

  * ``dispatch`` assembles inputs with vectorized numpy scatters over
    per-request arrays cached on ``Request`` (no per-token Python loops),
    packed into ONE int32 device transfer, and returns a
    :class:`StepHandle` without waiting for the step itself — JAX async
    dispatch lets the host schedule/assemble step N+1 while step N runs
    (with donated pools, dispatching N+1 waits for N to finish: the
    one-step pipeline barrier).
  * Sampling happens on device: the step returns ``(R+B,)`` greedy token
    ids plus only the ``(R, V)`` prefill logit rows needed for
    losslessness checks, never the full ``(R+B, V)`` logits transfer.
  * Copy-on-write page forks and host-tier swap-ins are queued
    (``queue_copies`` / ``queue_swap_in``) and folded INTO the jitted
    step as padded ``(src, dst)`` index arrays; overflow past the static
    buckets falls back to the eager paths so shapes stay static.

Deterministic accounting: the engine counts attention dispatches (``L``
per fused iteration), valid vs total token rows (padded-token fraction),
and per-bucket step counts.

Sharded multi-device mode (``mesh`` argument): the KV page pools shard
over the mesh's ``model`` axis into contiguous runs of ``num_pages / n``
pages per device (the block manager stripes every sequence's blocks
across shards), weights shard by ``sharding_rules(cfg, mesh, "decode")``
(GSPMD tensor parallelism for the projections/FFN/logits), and each
layer's KV write + fused varlen attention runs under ``shard_map``: every
shard scatters the new tokens it owns, computes the attention partial
over its local pages only, and the partials merge through the exact
log-sum-exp combine (``repro.distributed.flash_decode``) — the
distributed generalization of Multi-Segment Attention, each shard's
pages being one segment subset.  The layer body is the single-device
one; only its write-and-attend step differs.  In-step COW copies and
swap-ins carry per-shard queues (cross-shard copies fall back to the
eager global-view path).  The occupancy-bucket jit cache is unchanged:
``jit_traces == len(buckets_used)`` holds under ``shard_map`` too.

Engine scope: decoder-only token LMs (dense / MoE / sliding-window mixes,
and latent attention with a latent-only pool on one device: see
docs/ARCHITECTURE.md).
SSM-family archs have no evictable KV cache and are served by the dense
decode path in ``repro.models`` instead.
"""
from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.msa import (
    WL_FIELDS,
    apply_page_copies,
    apply_swap_ins,
    block_pages,
    build_worklist,
    msa_fused,
    pad_worklist,
    write_kv_pages,
)
from repro.kernels.msa.latent import LatentLayout
from repro.core.offload import HostHalf, dequantize_half
from repro.models.layers import apply_rope, moe_ffn_local, rms_norm, swiglu_mlp
from repro.models.model import _layer_windows
from repro.serving.scheduler import StepPlan
from repro.serving.tracing import ASSEMBLE, LAUNCH, ServeTracer

# minimum work-list bucket (fused Pallas path only); lengths round up to
# the next power of two above this, so the per-W jit variants are at
# most log2(Wmax) many.  The xla oracle ships no work-list (W = 0).
WL_BUCKET = 64


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def wl_capacity(ecfg: "EngineConfig", t_bucket: int, np_bucket: int) -> int:
    """The W bucket that holds the most work-list items a step of
    ``(t_bucket, np_bucket)`` can build: a prefill chunk spans at most
    ceil(chunk / q_tile) + 1 q tiles and a decode row one, each
    (sequence, tile) pair needs at most ceil(np_bucket / NB) page blocks,
    and each all-padding tile adds one sentinel item.  A larger W bucket
    is laid out and run at this one: the kernel scalar-prefetches
    W * (5 + NB) int32 words, and a TPU core has 1 MiB of SMEM."""
    e = ecfg
    tq = min(e.q_tile, t_bucket)
    n_tiles = -(-t_bucket // tq)
    chunk = min(e.max_chunk, t_bucket)
    pairs = e.max_prefills * (-(-chunk // tq) + 1) + e.max_decodes
    items = pairs * -(-np_bucket // block_pages(e.page_size)) + n_tiles
    return max(WL_BUCKET, 1 << (items - 1).bit_length())


# step fields that carry one row per fused iteration of a multi-token
# decode plan (the rest — sequence rows, block tables, page-op queues —
# are shared by the iterations)
PER_ITER_FIELDS = ("tokens", "positions", "valid", "write_slot",
                   "write_off", "write_page", "write_row", "qlen",
                   "ctx") + WL_FIELDS


def kv_write_capacity(ecfg: "EngineConfig", t_bucket: int) -> int:
    """``NW``: the most distinct pool pages one fused iteration of
    ``t_bucket`` token rows can write.  A prefill chunk of ``n`` tokens
    touches at most ``ceil(n / page) + 1`` pages (its run may start
    mid-page), so R chunks and B decode rows touch fewer than
    ``t_bucket / page + 2R + B``; and no step writes more pages than it
    has rows."""
    e = ecfg
    return min(t_bucket, -(-t_bucket // e.page_size)
               + 2 * e.max_prefills + e.max_decodes)


def touched_pages(write_slot: np.ndarray, valid: np.ndarray, nw: int,
                  num_pages: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host side of the page write: ``write_page``, the distinct pool
    slots the valid rows write, padded with the out-of-range slot
    ``num_pages`` to ``nw`` entries; and ``write_row``, each row's index
    into ``write_page`` (0 on invalid rows, which the device drops)."""
    live = valid.astype(bool)
    pages, rows = np.unique(write_slot[live], return_inverse=True)
    n = pages.shape[0]
    if n > nw:
        raise ValueError(f"step writes {n} pages > kv_write_capacity {nw}")
    write_page = np.full((nw,), num_pages, np.int32)
    write_page[:n] = pages
    write_row = np.zeros(write_slot.shape, np.int32)
    write_row[live] = rows
    return write_page, write_row


def write_layer_pages(k_pools, v_pools, layer: int, k_new, v_new,
                      write_page, write_row, write_off, valid,
                      latent: Optional[LatentLayout] = None):
    """Write one layer's new K/V rows into the layer-stacked, donated
    ``(L, P, KH, page, D)`` pools, page by page: gather the ``NW`` touched
    pages, scatter the rows into that small buffer (``write_kv_pages``
    with ``write_row`` for slots), and scatter the whole pages back.

    A row scatter straight into the stacked pool leaves the same bytes,
    but its index dimensions (slot, offset) straddle the KV-head axis, and
    XLA on TPU then moves the whole pool into another layout and back on
    every layer; a scatter whose window is whole ``(KH, page, D)`` pages
    stays in place.  Padding entries of ``write_page`` (slot P) gather a
    clamped page and their write-back is dropped.  A latent cache
    (``latent``; ``v_pools`` None) writes its one half: the touched page
    tiles open into rows of cached vectors, take the new rows and close
    again."""
    if latent is not None:
        rows = latent.from_tile(k_pools[layer, write_page])
        rows, _ = write_kv_pages(rows, None, k_new, None, write_row,
                                 write_off, valid)
        return (k_pools.at[layer, write_page].set(latent.to_tile(rows),
                                                  mode="drop"), None)
    k_pages, v_pages = write_kv_pages(
        k_pools[layer, write_page], v_pools[layer, write_page], k_new, v_new,
        write_row, write_off, valid)
    return (k_pools.at[layer, write_page].set(k_pages, mode="drop"),
            v_pools.at[layer, write_page].set(v_pages, mode="drop"))


def write_and_attend(k_pools, v_pools, layer: int, q, k_new, v_new,
                     f: Dict[str, jax.Array], worklist, *, window: int,
                     softcap: float, q_tile: int, impl: str,
                     latent: Optional[LatentLayout] = None):
    """A fused single-device layer's KV write and its ONE ``msa_fused``
    dispatch, which reads ``layer`` of the stacked pools in place: no
    layer's pool is ever sliced out into a buffer of its own.  ``f``
    holds one fused iteration's step fields.  A latent cache passes its
    ``latent`` layout and ``v_pools`` and ``v_new`` None.  Returns the
    pools and the attention output."""
    with jax.named_scope("kv_write"):
        k_pools, v_pools = write_layer_pages(
            k_pools, v_pools, layer, k_new, v_new, f["write_page"],
            f["write_row"], f["write_off"], f["valid"], latent)
    with jax.named_scope("attention"):
        attn = msa_fused(q, k_pools, v_pools, f["bt"], f["ctx"],
                         f["positions"], f["seq_ids"], f["valid"],
                         q_start=f["qstart"], q_len=f["qlen"],
                         worklist=worklist, layer=layer, window=window,
                         softcap=softcap, q_tile=q_tile, latent=latent,
                         impl=impl)
    return k_pools, v_pools, attn


def derive_bucket_lattice(ecfg: "EngineConfig"
                          ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """``(token_buckets, np_buckets)`` implied by an :class:`EngineConfig`.

    The single source of the occupancy lattice: ``Engine.__init__``
    compiles from it and the static auditor
    (``repro.analysis.lattice``) enumerates it without instantiating
    pools — the two must never disagree, or the auditor's predicted
    trace-key set stops matching ``jit_traces``.

    Token buckets: a decode-full bucket (decode-only steps are the
    continuous-batching common case — at full decode occupancy that
    bucket carries no padding at all) plus power-of-two fractions of
    Tmax down to Tmax/16."""
    R, QP, B, NP = (ecfg.max_prefills, ecfg.max_chunk,
                    ecfg.max_decodes, ecfg.max_blocks_per_seq)
    t_max = R * QP + B
    tb = ecfg.token_buckets or (
        max(8, _round_up(B, 8)),
        max(8, _round_up(t_max // 16, 8)),
        max(8, _round_up(t_max // 8, 8)),
        max(8, _round_up(t_max // 4, 8)),
        max(8, _round_up(t_max // 2, 8)),
    )
    nb = ecfg.np_buckets or (max(1, NP // 4),)
    token_buckets = tuple(sorted(
        {min(t_max, max(1, int(t))) for t in tb} | {t_max}))
    np_buckets = tuple(sorted(
        {min(NP, max(1, int(n))) for n in nb} | {NP}))
    return token_buckets, np_buckets


def pack_layout_for(ecfg: "EngineConfig", n_shards: int, t_bucket: int,
                    np_bucket: int, w_bucket: int, n_iter: int = 1
                    ) -> Tuple[List[Tuple[str, int, int]], int]:
    """(name, offset, size) triples of the flat int32 pack buffer for
    one occupancy bucket, plus its total length.

    Pure function of the config so the static auditor can size every
    bucket's host->device transfer without an :class:`Engine`;
    ``Engine.pack_layout`` delegates here (with a per-engine cache).

    Multi-token decode plans (``n_iter > 1``) carry
    PER-ITERATION copies of the fields that change between the fused
    decode iterations (tokens/positions/valid/write coords/ctx/qlen and
    the Pallas work-list); the sequence-row structure
    (seq_ids/sel/qstart/bt) and the page-op queues are shared.  The
    ``n_iter == 1`` layout is byte-identical to the single-step one."""
    e = ecfg
    R, B = e.max_prefills, e.max_decodes
    # per-shard in-step op queues: shard i's copies/swaps live in row i
    # (shard-LOCAL page indices); single-device keeps the flat layout
    C = n_shards * e.max_instep_copies
    S = n_shards * e.max_instep_swaps
    t, n, k = t_bucket, R + B, n_iter
    fields = [("tokens", k * t), ("positions", k * t),
              ("valid", k * t), ("write_slot", k * t),
              ("write_off", k * t),
              ("write_page", k * kv_write_capacity(e, t)),
              ("write_row", k * t), ("seq_ids", t),
              ("sel", R + B), ("qstart", n), ("qlen", k * n),
              ("ctx", k * n), ("bt", n * np_bucket)]
    # wl_slot carries block_pages(page) pool slots per item
    nb = block_pages(e.page_size)
    w = min(w_bucket, wl_capacity(e, t_bucket, np_bucket))
    fields += [(f, k * w * (nb if f == "wl_slot" else 1))
               for f in WL_FIELDS]
    fields += [("copy_src", C), ("copy_dst", C),
               ("swap_k_dst", S), ("swap_v_dst", S)]
    layout: List[Tuple[str, int, int]] = []
    off = 0
    for name, size in fields:
        layout.append((name, off, size))
        off += size
    return layout, off


@dataclass(frozen=True)
class EngineConfig:
    """Static shape and behaviour of one engine.

    Losslessness scope: dense and dropless MoE models.  MoE with
    dropless=False derives expert capacity from the step's TOTAL row count
    (padding included), so its drop decisions depend on the occupancy
    bucket (``moe_ffn_local`` documents dropless=True as required for
    lossless serving; the model zoo complies)."""
    num_pages: int                 # KV pool pages (= block manager blocks)
    page_size: int = 16
    max_prefills: int = 4          # R
    max_chunk: int = 128           # QP (per-request compute tokens per step)
    max_decodes: int = 64          # B
    max_blocks_per_seq: int = 64   # NP
    # "pallas" (TPU only) | "xla" (oracle) | "pallas_interpret" (CPU)
    attn_impl: str = "xla"
    q_tile: int = 128
    # occupancy bucket lattices.  Empty tuples derive the
    # defaults {B, Tmax//16, Tmax//8, Tmax//4, Tmax//2, Tmax} (B = a
    # decode-full bucket) and {NPmax//4, NPmax}; the maximal bucket is
    # always included so every legal plan fits.
    token_buckets: Tuple[int, ...] = ()
    np_buckets: Tuple[int, ...] = ()
    # static buckets for page ops folded into the jitted step; overflow
    # falls back to the eager dispatch paths (shapes must stay static).
    # Setting a bucket to 0 routes ALL ops of that kind through the eager
    # fallback (the pre-pipeline behaviour).
    max_instep_copies: int = 8     # COW forks per step
    max_instep_swaps: int = 4      # host-tier swap-ins per step
    # wire format of the host-tier swap payloads travelling through the
    # split swap queues: "fp" ships pool-dtype pages; "q8" ships int8
    # codes + a per-page-per-head f32 scale, dequantized INSIDE the
    # jitted step next to apply_swap_ins (~4x fewer bytes per queued
    # block vs fp32); "f8" ships float8_e4m3fn casts.  Must match the
    # block manager's OffloadConfig.wire_format (the server wires both).
    swap_payload: str = "fp"
    # KV pool grid snap applied to k_new/v_new at write time, inside the
    # step: "int8" rounds to the static snap_scale grid, "fp8" rounds
    # through float8 — the lossless-offload invariant (every pool value
    # is on-grid from the instant it exists, so payload quantization
    # round-trips bitwise by construction; recompute reproduces it
    # exactly because the snap is part of the deterministic write path).
    snap: str = "off"
    snap_scale: float = 0.0
    # True makes the step return the full (R+B, V) logits and
    # StepHandle.block() transfer them all to the host (the server sets
    # it for record_decode_logits).  False (default) keeps sampling on
    # device: only (R+B,) token ids and the (R, V) prefill rows ever
    # leave it.
    return_full_logits: bool = False
    # buffer-donate the KV pools into the step.  Donation halves pool
    # memory (XLA aliases input to output) and avoids a full pool copy at
    # the jit boundary.  Dispatching step N+1 blocks until step N (the
    # donated buffer's producer) has finished — which is exactly the
    # one-step pipeline barrier: every OTHER host action (postprocess,
    # scheduling, assembly, device_put) overlaps step N, and dispatch
    # with an already-materialized pool is asynchronous.  Set False to
    # queue more than one step on the device (pipeline_depth > 1) at the
    # cost of a per-step pool copy.
    donate_pools: bool = True


@dataclass
class StepHandle:
    """Asynchronous result of one dispatched step.

    Holds device arrays; nothing is transferred until the ``*_np``
    accessors run, so the server can keep assembling the next step while
    this one executes.  ``block`` waits for the device — and when the
    engine runs with ``return_full_logits`` it also transfers the full
    (R+B, V) logits to the host."""
    token_ids: jax.Array           # (R+B,) device-side greedy samples
    prefill_logits: jax.Array      # (R, V) rows ((R+B, V) full-logits mode)
    full_logits: bool = False
    # each decode row's input position (set by the server when it records
    # decode logits)
    decode_pos: Optional[List[int]] = None
    _ids_np: Optional[np.ndarray] = None
    _pre_np: Optional[np.ndarray] = None

    def block(self) -> None:
        if self.full_logits:
            self.prefill_logits_np()   # the full-vocab transfer
            self.token_ids_np()
        else:
            jax.block_until_ready((self.token_ids, self.prefill_logits))

    def token_ids_np(self) -> np.ndarray:
        if self._ids_np is None:
            self._ids_np = np.asarray(self.token_ids)
        return self._ids_np

    def prefill_logits_np(self) -> np.ndarray:
        if self._pre_np is None:
            self._pre_np = np.asarray(self.prefill_logits)
        return self._pre_np


class Engine:
    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig, params,
                 mesh=None):
        assert cfg.family in ("dense", "moe", "vlm"), cfg.family
        assert not cfg.enc_dec
        assert ecfg.attn_impl in ("xla", "pallas", "pallas_interpret"), \
            ecfg.attn_impl
        if ecfg.attn_impl == "pallas" and jax.default_backend() != "tpu":
            # never degrade silently to the interpreter or the oracle
            raise ValueError(
                "attn_impl='pallas' compiles the TPU kernel; this backend is "
                f"{jax.default_backend()!r} (use 'xla' or 'pallas_interpret')")
        self.cfg = cfg
        self.ecfg = ecfg
        self.mesh = mesh
        self.n_shards = 1 if mesh is None else int(mesh.shape["model"])
        if cfg.latent and self.n_shards > 1:
            raise ValueError("latent attention runs on one device only")
        dt = jnp.dtype(cfg.dtype)
        L = cfg.n_layers
        # split swap-queue wire format + write-time pool-grid snap
        assert ecfg.swap_payload in ("fp", "q8", "f8"), ecfg.swap_payload
        assert ecfg.snap in ("off", "int8", "fp8"), ecfg.snap
        assert ecfg.snap != "int8" or ecfg.snap_scale > 0.0
        self._payload_fmt = ecfg.swap_payload
        self._snap_mode = ecfg.snap
        self._snap_scale = ecfg.snap_scale
        if "f8" in (self._payload_fmt,) or self._snap_mode == "fp8":
            if not hasattr(jnp, "float8_e4m3fn"):
                raise ValueError("fp8 payloads need jnp.float8_e4m3fn "
                                 "(ml_dtypes)")
        self._payload_dtype = {"fp": dt, "q8": jnp.int8,
                               "f8": getattr(jnp, "float8_e4m3fn", None),
                               }[self._payload_fmt]
        if self._payload_fmt == "f8":
            import ml_dtypes
            self._payload_npdt = np.dtype(ml_dtypes.float8_e4m3fn)
        else:
            self._payload_npdt = (np.dtype(cfg.dtype)
                                  if self._payload_fmt == "fp"
                                  else np.dtype(np.int8))
        # head-major pools: one (page, D) tile per (slot, kv head) is the
        # block the TPU kernel streams.  A latent cache is its K half
        # alone, one (rows, 128) tile per slot holding the page's
        # (page, kv_lora_rank + qk_rope_head_dim) cached vectors
        # (``LatentLayout``); its ``v_pools`` is an empty placeholder that
        # every step passes through untouched.
        self.latent = (LatentLayout(ecfg.page_size, cfg.kv_lora_rank,
                                    cfg.qk_rope_head_dim)
                       if cfg.latent else None)
        page_shape = ((1, self.latent.rows, 128) if cfg.latent else
                      (cfg.n_kv_heads, ecfg.page_size, cfg.head_dim))
        self.k_pools = jnp.zeros((L, ecfg.num_pages) + page_shape, dt)
        self.v_pools = (jnp.zeros((0,), dt) if cfg.latent
                        else jnp.zeros_like(self.k_pools))
        in_shardings = None
        if self.n_shards > 1:
            # sharded serving: xla oracle impl (Pallas-on-mesh is a TPU
            # deployment concern, not a CPU-host-device validation one)
            assert self._payload_fmt == "fp" and self._snap_mode == "off", \
                "quantized offload requires the single-device engine"
            assert ecfg.attn_impl == "xla", "sharded engine requires xla impl"
            assert ecfg.num_pages % self.n_shards == 0, \
                (ecfg.num_pages, self.n_shards)
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.distributed.sharding import serving_param_shardings
            rules, param_sh = serving_param_shardings(cfg, mesh)
            self.rules = rules
            self._pool_sh = NamedSharding(
                mesh, P(None, "model", None, None, None))
            self._swap_sh = NamedSharding(
                mesh, P("model", None, None, None, None, None))
            self._repl = NamedSharding(mesh, P())
            self.params = jax.device_put(params, param_sh)
            self.k_pools = jax.device_put(self.k_pools, self._pool_sh)
            self.v_pools = jax.device_put(self.v_pools, self._pool_sh)
            in_shardings = (param_sh, self._pool_sh, self._pool_sh,
                            {"pack": self._repl, "swap_k": self._swap_sh,
                             "swap_v": self._swap_sh})
        else:
            self.params = params
        self.windows = [int(w) for w in np.asarray(_layer_windows(cfg, L))]
        self._step = jax.jit(
            self._step_impl,
            static_argnums=(4, 5, 6, 7),
            donate_argnums=(1, 2) if ecfg.donate_pools else (),
            **({"in_shardings": in_shardings}
               if in_shardings is not None else {}))
        self.steps_executed = 0
        # the serve loop's tracer (set by the server that drives this
        # engine): dispatch marks its assemble and launch phases
        self.tracer: Optional[ServeTracer] = None
        # trace counter: must equal len(buckets_used) — the
        # compile-once-per-bucket invariant
        self.jit_traces = 0
        self.buckets_used: set = set()
        self._pending_copies: List[Tuple[int, int]] = []
        # SPLIT swap queues (asymmetric K/V offload): the K and V halves
        # of a block queue independently, so a V-only swap-in (the
        # k-early prefetch's on-demand V stream) never ships a zero K
        # payload.  Entries are (slot, HostHalf).
        self._pending_swap_k: List[Tuple[int, HostHalf]] = []
        self._pending_swap_v: List[Tuple[int, HostHalf]] = []
        # device-resident zero swap payload (in the wire dtype), reused
        # on swap-free steps/halves (their destinations are all padded
        # out of range anyway).  Sharded mode carries one payload row per
        # shard, sharded over the leading axis so each device transfers
        # only its own slice.
        pdt = self._payload_dtype
        if self.n_shards > 1:
            self._zero_swap = jax.device_put(jnp.zeros(
                (self.n_shards, L, ecfg.max_instep_swaps, cfg.n_kv_heads,
                 ecfg.page_size, cfg.head_dim), dt), self._swap_sh)
        else:
            self._zero_swap = jnp.zeros(
                (L, ecfg.max_instep_swaps) + page_shape, pdt)
        self._zero_scale = (jnp.zeros(
            (L, ecfg.max_instep_swaps, page_shape[0]), jnp.float32)
            if self._payload_fmt == "q8" else None)
        R, QP, B, NP = (ecfg.max_prefills, ecfg.max_chunk,
                        ecfg.max_decodes, ecfg.max_blocks_per_seq)
        self.n_seqs = R + B
        self.t_max = R * QP + B
        # one derivation shared with the static lattice auditor
        # (repro.analysis.lattice enumerates the same function)
        self.token_buckets, self.np_buckets = derive_bucket_lattice(ecfg)
        self._t_bucket_set = set(self.token_buckets)
        self._np_bucket_set = set(self.np_buckets)
        # deterministic accounting
        self.attn_dispatches = 0       # per-layer attention kernel launches
        self.valid_token_rows = 0      # real compute tokens executed
        self.total_token_rows = 0      # token rows incl. bucket padding
        self.bucket_counts: Dict[Tuple[int, int], int] = {}
        # page-op routing: folded into the jitted step vs eager fallback
        # (sharded mode also routes cross-shard copies eagerly)
        self.instep_copies = 0
        self.eager_copies = 0
        # swap accounting is per HALF now (split queues): one full block
        # restore counts 2, a V-only stream counts 1
        self.instep_swaps = 0
        self.eager_swaps = 0
        # host->device payload bytes actually shipped by folded swap
        # buffers (codes + scales in q8 mode) — the wire-level half of
        # the bytes_swapped_* accounting the block manager keeps
        self.swap_bytes_shipped = 0
        # distinct pool pages written by fused steps, summed over steps
        # (and iterations): each layer's KV write moves this many pages
        self.kv_pages_written = 0
        # work-list items of the Pallas steps, summed over steps (and
        # iterations), and those of one-token runs, which the kernel
        # computes on their token's query rows alone
        self.attn_items = 0
        self.attn_narrow_items = 0
        # multi-token decode dispatch + decode-phase accounting
        self.decode_only_dispatches = 0    # dispatches with no prefill chunk
        self.decode_tokens_emitted = 0     # decode tokens across iterations
        self.multi_token_dispatches = 0    # dispatches with k > 1
        self.multi_token_iterations = 0    # sum of k over those
        self.multi_token_rollbacks = 0     # masked (unconsumed) iterations
        self.k_counts: Dict[int, int] = {}
        # packed-input layouts: every int32 input in one flat host buffer
        # -> ONE device_put per step instead of ~14; one layout per
        # (t_bucket, np_bucket, w_bucket, n_iter)
        self._layouts: Dict[Tuple[int, int, int, int],
                            Tuple[List[Tuple[str, int, int]], int]] = {}

    # ------------------------------------------------------------------
    def pack_layout(self, t_bucket: int, np_bucket: int, w_bucket: int,
                    n_iter: int = 1):
        """(name, offset, size) triples of the flat int32 pack buffer for
        one occupancy bucket (cached; trace-time and assembly agree).
        Delegates to :func:`pack_layout_for` — the pure form the static
        auditor sizes buckets with."""
        key = (t_bucket, np_bucket, w_bucket, n_iter)
        cached = self._layouts.get(key)
        if cached is not None:
            return cached
        layout, off = pack_layout_for(self.ecfg, self.n_shards, t_bucket,
                                      np_bucket, w_bucket, n_iter)
        self._layouts[key] = (layout, off)
        return layout, off

    def buckets_for(self, plan: StepPlan) -> Tuple[int, int]:
        """Resolve the step's (t_bucket, np_bucket).  The scheduler's
        §5.1-informed selection (``plan.t_bucket``/``plan.np_bucket``) is
        honored when it names an entry of THIS engine's lattice that fits
        the plan; anything else (no selection, a foreign lattice from a
        shared SchedulerConfig, a stale too-small bucket) falls back to
        the smallest fitting own-lattice entry — so the jit cache can
        never grow off-lattice variants and a legal plan always fits."""
        need_t = plan.n_compute_tokens
        tb = plan.t_bucket
        if tb not in self._t_bucket_set or tb < need_t:
            tb = next((b for b in self.token_buckets if b >= need_t),
                      self.token_buckets[-1])
        bs = self.ecfg.page_size
        need_p = 1
        for c in plan.prefills:
            need_p = max(need_p, -(-(int(c.positions[-1]) + 1) // bs))
        for req in plan.decodes:
            # a k-step plan's last iteration reads k-1 positions past the
            # current context — the page bucket must cover it
            ctx = req.prompt_len + len(req.generated) \
                + plan.decode_steps - 1
            need_p = max(need_p, -(-ctx // bs))
        need_p = min(need_p, self.ecfg.max_blocks_per_seq)
        nb = plan.np_bucket
        if nb not in self._np_bucket_set or nb < need_p:
            nb = next((b for b in self.np_buckets if b >= need_p),
                      self.np_buckets[-1])
        assert tb >= need_t, (tb, need_t)
        return tb, nb

    # ------------------------------------------------------------------
    def _step_impl(self, params, k_pools, v_pools, inp,
                   t_bucket: int, np_bucket: int, w_bucket: int,
                   n_iter: int = 1):
        """One step: the in-step page ops, then ``n_iter`` fused forward
        passes (one for a mixed prefill/decode step, k for a multi-token
        decode plan), each followed by its device-side greedy sample.

        Iteration ``i > 0`` of a k-token decode feeds each row the
        host-forced id when ≥ 0, else (sentinel -1) iteration ``i-1``'s
        sample for that row — device sampling feeding the next token
        without leaving the device.  The scripted serving loop always
        forces, so runs stay teacher-forced and byte-comparable to k=1.
        Iterations at or past a request's ``decode_iters`` are masked out
        on device (valid 0: no KV write; qlen 0: no attention row) and
        their sampled ids are rolled back on the host by never being
        consumed."""
        # repro: allow(jit-hazard) — intentional trace-time-only side
        # effect: counts compiled step variants for the
        # compile-once-per-bucket gate; never traced into the graph
        self.jit_traces += 1
        cfg, e = self.cfg, self.ecfg
        # trace-time slicing of the pack into named views
        inp = self._unpack(inp, t_bucket, np_bucket, w_bucket, n_iter)

        # a latent cache has no V half: its placeholder passes through
        v_out, v_pools = v_pools, (None if cfg.latent else v_pools)
        # in-step page maintenance: swap-ins land first (they commit pages
        # a COW fork in the same round may use as its donor), then copies;
        # both must precede the KV writes/attention that read those pages
        with jax.named_scope("page_ops"):
            if self.n_shards > 1:
                from repro.distributed.flash_decode import sharded_pool_ops
                k_pools, v_pools = sharded_pool_ops(
                    k_pools, v_pools, inp["swap_k_dst"], inp["swap_v_dst"],
                    inp["swap_k"], inp["swap_v"], inp["copy_src"],
                    inp["copy_dst"], mesh=self.mesh)
            else:
                # quantized payloads dequantize inside apply_swap_ins —
                # the transfer above carried the compressed wire bytes
                # (a latent cache swaps its one half in: no V entries)
                k_pools, v_pools = apply_swap_ins(
                    k_pools, v_pools, inp["swap_k_dst"],
                    inp["swap_v_dst"][:0] if cfg.latent
                    else inp["swap_v_dst"], inp["swap_k"], inp["swap_v"],
                    inp.get("swap_k_scale"), inp.get("swap_v_scale"))
                k_pools, v_pools = apply_page_copies(
                    k_pools, v_pools, inp["copy_src"], inp["copy_dst"])

        ids_steps = []
        ids = out_logits = None
        for i in range(n_iter):
            f = inp if n_iter == 1 else {
                name: (a[i] if name in PER_ITER_FIELDS else a)
                for name, a in inp.items()}
            tokens = f["tokens"]
            if ids is not None:
                tokens = jnp.maximum(
                    jnp.where(tokens >= 0, tokens, ids[inp["seq_ids"]]), 0)
            k_pools, v_pools, x = self._fused_pass(
                params, k_pools, v_pools, tokens, f, t_bucket)
            with jax.named_scope("logits"):
                x = rms_norm(x, params["final_norm"], cfg.norm_eps)
                head = (params["embed"].T if cfg.tie_embeddings
                        else params["lm_head"])
                logits = x[inp["sel"]] @ head            # (R+B, V)
                # device-side greedy sampling: only (R+B,) ids and the R
                # prefill rows (losslessness checks) leave the device,
                # unless full logits are requested
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out_logits = (logits if e.return_full_logits
                              else logits[:e.max_prefills])
            ids_steps.append(ids)
        # (R+B,) for one iteration, (n_iter, R+B) for a k-token decode
        token_ids = ids if n_iter == 1 else jnp.stack(ids_steps)
        return (token_ids, out_logits, k_pools,
                v_out if cfg.latent else v_pools)

    def _snap(self, x):
        """Snap freshly computed K/V to the offload quantization grid at
        WRITE time (lossless-offload invariant: pool values are on-grid
        from the instant they exist, so spill-time quantization recovers
        the exact codes and swap-in dequantization reproduces the pool
        bytes bit-for-bit — and recompute, running this same
        deterministic write path, reproduces them too)."""
        if self._snap_mode == "off":
            return x
        if self._snap_mode == "int8":
            s = jnp.float32(self._snap_scale)
            q = jnp.clip(jnp.round(x.astype(jnp.float32) / s),
                         -127.0, 127.0)
            return (q * s).astype(x.dtype)
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    def _layer_params(self, params, layer: int):
        """Layer ``layer``'s weights: the leading ``first_k_dense`` layers
        (a dense MLP before the MoE layers) are stacked in
        ``dense_blocks``, every other layer in ``blocks``."""
        k = self.cfg.first_k_dense
        group, i = ((params["dense_blocks"], layer) if layer < k
                    else (params["blocks"], layer - k))
        return jax.tree_util.tree_map(lambda a: a[i], group)

    def _mlp_sublayer(self, x, blk, layer: int):
        cfg = self.cfg
        h2 = rms_norm(x, blk["mlp_norm"], cfg.norm_eps)
        if cfg.mlp_kind(layer) == "moe":
            mo = cfg.moe
            y = moe_ffn_local(h2, blk["router"], blk["we1"], blk["we3"],
                              blk["we2"], mo.top_k, mo.capacity_factor,
                              dropless=mo.dropless,
                              expert_split=mo.expert_split,
                              score_fn=mo.score_fn,
                              router_bias=blk.get("router_bias"),
                              routed_scale=mo.routed_scale,
                              expert_offset=mo.expert_offset)
            if mo.shared_d_ff:
                with jax.named_scope("shared_experts"):
                    y = y + swiglu_mlp(h2, blk["ws1"], blk["ws3"],
                                       blk["ws2"])
        else:
            y = swiglu_mlp(h2, blk["w1"], blk["w3"], blk["w2"])
        return x + y

    def _latent_qk(self, h, blk, pos):
        """Latent attention in absorbed form (DeepSeek-V2 §2.1.3): the
        kernel's query ``[q_nope·W_UKᵀ | RoPE(q_pe)]`` (T, H, r + dr) and
        the token's cached vector ``[RMSNorm(c_kv) | RoPE(k_pe)]`` (T, 1,
        r + dr).  The kernel scales scores by 1/sqrt(r + dr); the model's
        1/sqrt(dn + dr) is folded into q."""
        cfg = self.cfg
        r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        q = jnp.einsum("td,dhk->thk", h, blk["wq"])        # (T, H, dn + dr)
        kva = h @ blk["w_kva"]                             # (T, r + dr)
        c_kv = rms_norm(kva[:, :r], blk["kv_norm"], cfg.norm_eps)
        k_pe = apply_rope(kva[:, None, r:], pos, cfg.rope_theta)
        q_pe = apply_rope(q[..., dn:], pos, cfg.rope_theta)
        scale = math.sqrt((r + dr) / (dn + dr))
        q_lat = jnp.einsum("thn,rhn->thr", q[..., :dn],
                           blk["w_kvb"][:, :, :dn],
                           preferred_element_type=jnp.float32)  # (T, H, r)
        q = jnp.concatenate([q_lat * scale,
                             q_pe.astype(jnp.float32) * scale], axis=-1)
        return (q.astype(h.dtype),
                jnp.concatenate([c_kv[:, None], k_pe], axis=-1))

    def _fused_pass(self, params, k_pools, v_pools, tokens, f,
                    t_bucket: int):
        """One forward over a varlen token stream (``f``: one fused
        iteration's step fields): per layer the projections, the KV write
        and the ONE attention dispatch, then the MLP of the layer's kind.
        A single device writes and attends with ``write_and_attend``, a
        mesh with ``_write_and_attend_sharded``.  Under latent attention
        ``v_pools`` is None, the kernel takes V from the cached latent,
        and each head's latent output goes up through W_UV before the
        output projection.  Returns the updated pools and the
        pre-final-norm residual."""
        cfg, e = self.cfg, self.ecfg
        pos = f["positions"]
        worklist = (None if e.attn_impl == "xla"
                    else tuple(f[name] for name in WL_FIELDS))
        with jax.named_scope("embed"):
            x = params["embed"][tokens]                 # (T, d)
        for l in range(cfg.n_layers):
            blk = self._layer_params(params, l)
            window = self.windows[l]
            with jax.named_scope("qkv"):
                h = rms_norm(x, blk["attn_norm"], cfg.norm_eps)
                if cfg.latent:
                    with jax.named_scope("latent"):
                        q, k_new = self._latent_qk(h, blk, pos)
                    k_new, v_new = self._snap(k_new), None
                else:
                    q = jnp.einsum("td,dhk->thk", h, blk["wq"])
                    k_new = jnp.einsum("td,dhk->thk", h, blk["wk"])
                    v_new = jnp.einsum("td,dhk->thk", h, blk["wv"])
                    if cfg.rope_theta > 0:
                        q = apply_rope(q, pos, cfg.rope_theta)
                        k_new = apply_rope(k_new, pos, cfg.rope_theta)
                    k_new = self._snap(k_new)
                    v_new = self._snap(v_new)
            if self.n_shards > 1:
                k_pools, v_pools, attn = self._write_and_attend_sharded(
                    k_pools, v_pools, l, q, k_new, v_new, f, window)
            else:
                k_pools, v_pools, attn = write_and_attend(
                    k_pools, v_pools, l, q, k_new, v_new, f, worklist,
                    window=window, softcap=cfg.attn_logit_softcap,
                    q_tile=min(e.q_tile, t_bucket), impl=e.attn_impl,
                    latent=self.latent)
            with jax.named_scope("attention"):
                if cfg.latent:
                    attn = jnp.einsum(
                        "thr,rhv->thv", attn,
                        blk["w_kvb"][:, :, cfg.qk_nope_head_dim:])
                x = x + jnp.einsum("thk,hkd->td", attn, blk["wo"])
            with jax.named_scope("mlp"):
                x = self._mlp_sublayer(x, blk, l)
        return k_pools, v_pools, x

    def _write_and_attend_sharded(self, k_pools, v_pools, layer: int, q,
                                  k_new, v_new, f: Dict[str, jax.Array],
                                  window: int):
        """The mesh's write-and-attend: per-shard KV write, attention
        partial over the shard's local pages and exact LSE merge, in one
        ``shard_map`` per layer (still ONE logical attention dispatch —
        each shard computes its segment subset of the same fused varlen
        stream)."""
        from repro.distributed.flash_decode import sharded_msa_fused
        with jax.named_scope("attention"):
            kp, vp, attn = sharded_msa_fused(
                q, k_pools[layer], v_pools[layer], k_new, v_new,
                f["write_slot"], f["write_off"], f["valid"], f["bt"],
                f["ctx"], f["positions"], f["seq_ids"], mesh=self.mesh,
                window=window, softcap=self.cfg.attn_logit_softcap)
        with jax.named_scope("kv_write"):
            k_pools = k_pools.at[layer].set(kp)
            v_pools = v_pools.at[layer].set(vp)
        return k_pools, v_pools, attn

    # ------------------------------------------------------------------
    def build_inputs(self, plan: StepPlan):
        """Host-side assembly of the padded device arrays for one step.

        Returns ``(inp, (t_bucket, np_bucket, w_bucket))`` — the static
        bucket dims select the jit variant.  Every int32 field is
        assembled directly into named views of ONE flat host buffer and
        transferred with a single ``device_put`` (plus the two
        swap-payload buffers)."""
        t_b, np_b = self.buckets_for(plan)
        n_it = plan.decode_steps
        if n_it > 1 and self.n_shards > 1:
            raise ValueError("multi-token decode dispatch requires the "
                             "single-device engine")
        # one derivation of the varlen metadata feeds BOTH the packed
        # buffer and (Pallas impls) the work-list builder
        if n_it > 1:
            fields = self._assemble_fused_multi(plan, t_b, np_b, n_it)
        else:
            fields = self._assemble_fused(plan, t_b, np_b)
        self.kv_pages_written += int(np.count_nonzero(
            fields["write_page"] < self.ecfg.num_pages))
        w_b = 0
        wls = None
        if self.ecfg.attn_impl != "xla":
            # one work-list per fused iteration (n_it == 1: exactly the
            # single-step list), all padded to one shared W so the bucket
            # key stays (t, np, w, k)
            tq = min(self.ecfg.q_tile, t_b)
            per_it = (lambda a, i: a[i] if n_it > 1 else a)
            wls = []
            items = pages = narrow = 0
            with (self.tracer.worklist() if self.tracer is not None
                  else contextlib.nullcontext()):
                for i in range(n_it):
                    wl, n_items, n_pages, n_narrow = build_worklist(
                        fields["qstart"], per_it(fields["qlen"], i),
                        per_it(fields["ctx"], i), fields["bt"],
                        per_it(fields["positions"], i),
                        page=self.ecfg.page_size, q_tile=tq,
                        n_tiles=-(-t_b // tq), window=0)
                    wls.append(wl)
                    items += n_items
                    pages += n_pages
                    narrow += n_narrow
            self.attn_items += items
            self.attn_narrow_items += narrow
            if self.tracer is not None:
                self.tracer.worklist_built(items, pages, narrow)
            # power-of-two W buckets keep the per-W jit variants at most
            # log2(Wmax) many
            w_b = max(WL_BUCKET, 1 << (max(
                wl["wl_seq"].shape[0] for wl in wls) - 1).bit_length())
            wls = [pad_worklist(wl, w_b, sentinel_seq=self.n_seqs)
                   for wl in wls]
        layout, size = self.pack_layout(t_b, np_b, w_b, n_it)
        buf = np.zeros((size,), np.int32)
        views = {name: buf[off:off + size_] for name, off, size_ in layout}
        for name, arr in fields.items():
            views[name][:] = arr.reshape(-1)
        if wls is not None:
            for f in WL_FIELDS:
                dst = views[f].reshape(n_it, -1)
                for i, wl in enumerate(wls):
                    dst[i] = wl[f]
        ops = self._fold_page_ops(views)
        inp = {"pack": jnp.asarray(buf),
               "swap_k": jnp.asarray(ops["swap_k"]),
               "swap_v": jnp.asarray(ops["swap_v"])}
        if self._payload_fmt == "q8":
            inp["swap_k_scale"] = jnp.asarray(ops["swap_k_scale"])
            inp["swap_v_scale"] = jnp.asarray(ops["swap_v_scale"])
        return inp, (t_b, np_b, w_b)

    def _unpack(self, inp: Dict[str, jax.Array], t_bucket: int,
                np_bucket: int, w_bucket: int,
                n_iter: int = 1) -> Dict[str, jax.Array]:
        """Static slices of the packed buffer back into named step inputs
        (trace-time only — compiles to views of the one transferred
        buffer)."""
        e = self.ecfg
        layout, _ = self.pack_layout(t_bucket, np_bucket, w_bucket, n_iter)
        buf = inp["pack"]
        out = {name: buf[off:off + size] for name, off, size in layout}
        out["valid"] = out["valid"].astype(bool)
        if self.n_shards > 1:
            ns = self.n_shards
            out["copy_src"] = out["copy_src"].reshape(ns, e.max_instep_copies)
            out["copy_dst"] = out["copy_dst"].reshape(ns, e.max_instep_copies)
            out["swap_k_dst"] = out["swap_k_dst"].reshape(
                ns, e.max_instep_swaps)
            out["swap_v_dst"] = out["swap_v_dst"].reshape(
                ns, e.max_instep_swaps)
        out["bt"] = out["bt"].reshape(self.n_seqs, np_bucket)
        if n_iter > 1:
            # per-iteration fields fold out to (k, ·)
            for f in PER_ITER_FIELDS:
                out[f] = out[f].reshape(n_iter, -1)
        out["swap_k"] = inp["swap_k"]
        out["swap_v"] = inp["swap_v"]
        if "swap_k_scale" in inp:          # q8 wire format
            out["swap_k_scale"] = inp["swap_k_scale"]
            out["swap_v_scale"] = inp["swap_v_scale"]
        return out

    # ------------------------------------------------------------------
    def _assemble_fused(self, plan: StepPlan, t_bucket: int,
                        np_bucket: int) -> Dict[str, np.ndarray]:
        """Varlen assembly: prefill chunks pack densely at the head of
        the flattened stream (no per-request QP padding), decode rows
        follow as runs of length 1.  Sequence rows 0..R-1 are prefills,
        R..R+B-1 decodes; only bucket slack at the tail is padding.

        Returns the named field arrays (the single source of truth for
        the packed buffer AND the Pallas work-list builder — the two
        consumers must never derive this metadata independently)."""
        e = self.ecfg
        bs = e.page_size
        R, B = e.max_prefills, e.max_decodes
        t = t_bucket
        tokens = np.zeros((t,), np.int32)
        positions = np.zeros((t,), np.int32)
        valid = np.zeros((t,), np.int32)
        write_slot = np.zeros((t,), np.int32)
        write_off = np.zeros((t,), np.int32)
        seq_ids = np.zeros((t,), np.int32)
        sel = np.zeros((R + B,), np.int32)
        qstart = np.zeros((self.n_seqs,), np.int32)
        qlen = np.zeros((self.n_seqs,), np.int32)
        ctx = np.zeros((self.n_seqs,), np.int32)
        bt = np.zeros((self.n_seqs, np_bucket), np.int32)

        assert len(plan.prefills) <= R and len(plan.decodes) <= B
        off = 0
        for r, chunk in enumerate(plan.prefills):
            req = chunk.req
            pos = np.asarray(chunk.positions, np.int32)
            n = pos.shape[0]
            slots = req.slot_array()
            tokens[off:off + n] = req.token_array()[pos]
            positions[off:off + n] = pos
            valid[off:off + n] = True
            write_slot[off:off + n] = slots[pos // bs]
            write_off[off:off + n] = pos % bs
            seq_ids[off:off + n] = r
            qstart[r] = off
            qlen[r] = n
            ctx[r] = pos[-1] + 1
            k = min(np_bucket, slots.shape[0])
            bt[r, :k] = slots[:k]
            sel[r] = off + n - 1
            off += n

        nd = len(plan.decodes)
        if nd:
            p = np.fromiter(
                (req.prompt_len + len(req.generated) - 1
                 for req in plan.decodes), np.int32, nd)
            tokens[off:off + nd] = np.fromiter(
                (req.generated[-1] for req in plan.decodes), np.int32, nd)
            positions[off:off + nd] = p
            valid[off:off + nd] = True
            write_slot[off:off + nd] = np.fromiter(
                (req.slot_array()[pi // bs]
                 for req, pi in zip(plan.decodes, p)), np.int32, nd)
            write_off[off:off + nd] = p % bs
            rows = off + np.arange(nd, dtype=np.int32)
            seq_ids[off:off + nd] = R + np.arange(nd, dtype=np.int32)
            qstart[R:R + nd] = rows
            qlen[R:R + nd] = 1
            ctx[R:R + nd] = p + 1
            for i, req in enumerate(plan.decodes):
                slots = req.slot_array()
                k = min(np_bucket, slots.shape[0])
                bt[R + i, :k] = slots[:k]
            sel[R:R + nd] = rows
            off += nd
        assert off <= t_bucket, (off, t_bucket)
        write_page, write_row = touched_pages(
            write_slot, valid, kv_write_capacity(e, t), e.num_pages)
        return dict(tokens=tokens, positions=positions, valid=valid,
                    write_slot=write_slot, write_off=write_off,
                    write_page=write_page, write_row=write_row,
                    seq_ids=seq_ids, sel=sel, qstart=qstart, qlen=qlen,
                    ctx=ctx, bt=bt)

    def _assemble_fused_multi(self, plan: StepPlan, t_bucket: int,
                              np_bucket: int,
                              k: int) -> Dict[str, np.ndarray]:
        """Per-iteration varlen assembly of a decode-only multi-token
        plan (``decode_steps == k > 1``).

        Iteration ``i`` of decode row ``j`` feeds the teacher-forced
        token at logical position ``p0_j + i`` — the id iteration ``i-1``
        emits under forcing (``output_script[gen-1+i]``; a -1 here would
        select the previous iteration's device-side sample instead) —
        and writes that position's KV page.  Iterations at or past
        ``decode_iters[j]`` (request out of scripted output) are masked
        out entirely: valid 0 (no KV write), qlen 0 (no attention row);
        the device still computes the row's logits, garbage the host
        rolls back by never consuming them."""
        e = self.ecfg
        bs = e.page_size
        R, B = e.max_prefills, e.max_decodes
        t, n = t_bucket, self.n_seqs
        nd = len(plan.decodes)
        assert not plan.prefills and 0 < nd <= B
        iters = np.asarray(plan.decode_iters, np.int32)
        assert iters.shape == (nd,) and int(iters.max()) == k

        tokens = np.zeros((k, t), np.int32)
        positions = np.zeros((k, t), np.int32)
        valid = np.zeros((k, t), np.int32)
        write_slot = np.zeros((k, t), np.int32)
        write_off = np.zeros((k, t), np.int32)
        seq_ids = np.zeros((t,), np.int32)
        sel = np.zeros((R + B,), np.int32)
        qstart = np.zeros((n,), np.int32)
        qlen = np.zeros((k, n), np.int32)
        ctx = np.zeros((k, n), np.int32)
        bt = np.zeros((n, np_bucket), np.int32)

        rows = np.arange(nd, dtype=np.int32)
        p0 = np.fromiter((req.prompt_len + len(req.generated) - 1
                          for req in plan.decodes), np.int32, nd)
        gen = np.fromiter((len(req.generated) for req in plan.decodes),
                          np.int32, nd)
        seq_ids[:nd] = R + rows
        qstart[R:R + nd] = rows
        sel[R:R + nd] = rows
        for j, req in enumerate(plan.decodes):
            slots = req.slot_array()
            m = min(np_bucket, slots.shape[0])
            bt[R + j, :m] = slots[:m]
        for i in range(k):
            act = i < iters                 # (nd,) live this iteration
            p = p0 + i
            positions[i, :nd] = np.where(act, p, 0)
            valid[i, :nd] = act
            qlen[i, R:R + nd] = act
            ctx[i, R:R + nd] = np.where(act, p + 1, 0)
            write_off[i, :nd] = np.where(act, p % bs, 0)
            for j, req in enumerate(plan.decodes):
                if act[j]:
                    tokens[i, j] = req.output_script[gen[j] - 1 + i]
                    write_slot[i, j] = req.slot_array()[p[j] // bs]
        nw = kv_write_capacity(e, t)
        write_page = np.zeros((k, nw), np.int32)
        write_row = np.zeros((k, t), np.int32)
        for i in range(k):
            write_page[i], write_row[i] = touched_pages(
                write_slot[i], valid[i], nw, e.num_pages)
        return dict(tokens=tokens, positions=positions, valid=valid,
                    write_slot=write_slot, write_off=write_off,
                    write_page=write_page, write_row=write_row,
                    seq_ids=seq_ids, sel=sel, qstart=qstart, qlen=qlen,
                    ctx=ctx, bt=bt)

    def _fold_page_ops(
            self, views: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Drain queued COW copies / host-tier swap-ins into padded index
        arrays for the jitted step (swap padding: dst == num_pages,
        dropped by the scatter), written in place into the packed
        buffer's ``views``; overflow past the static buckets goes
        eager."""
        if self.n_shards > 1:
            return self._fold_page_ops_sharded(views)
        e = self.ecfg
        C = e.max_instep_copies
        copies, self._pending_copies = self._pending_copies, []
        if len(copies) > C:
            # eager overflow fallback.  Eager copies run against the
            # pools BEFORE this step, so any queued swap-ins (which would
            # otherwise land inside the step, i.e. after the copy reads
            # its donor) must be flushed eagerly first — a same-round
            # swap-in may be the donor of one of these forks
            self._flush_swaps_eager()
            self.copy_pages(copies[C:])
            self.eager_copies += len(copies) - C
            copies = copies[:C]
        self.instep_copies += len(copies)
        # padding repeats the last real copy (idempotent: sources never
        # alias destinations) or is the identity 0 -> 0 on copy-free steps
        pad_src, pad_dst = copies[-1] if copies else (0, 0)
        copy_src, copy_dst = views["copy_src"], views["copy_dst"]
        copy_src[:] = pad_src
        copy_dst[:] = pad_dst
        for j, (src, dst) in enumerate(copies):
            copy_src[j] = src
            copy_dst[j] = dst

        out = dict(copy_src=copy_src, copy_dst=copy_dst)
        kq, self._pending_swap_k = self._pending_swap_k, []
        vq, self._pending_swap_v = self._pending_swap_v, []
        out.update(self._fold_swap_half("k", kq, views))
        out.update(self._fold_swap_half("v", vq, views))
        return out

    def _flush_swaps_eager(self) -> None:
        """Apply every queued swap-in half eagerly (pre-step), draining
        both split queues."""
        kq, self._pending_swap_k = self._pending_swap_k, []
        vq, self._pending_swap_v = self._pending_swap_v, []
        self.eager_swaps += len(kq) + len(vq)
        for slot, half in kq:
            self.swap_in(slot, (half, None))
        for slot, half in vq:
            self.swap_in(slot, (None, half))

    def _fold_swap_half(self, name: str, queue, views):
        """Fold one half's queued swap-ins (K or V) into its padded
        destination bucket + payload buffer.  The two halves are
        independent: a V-only swap-in (k-early prefetch's on-demand V
        stream) ships ZERO K bytes.  Quantized payload formats ship the
        int8 codes + (L, S, KH) f32 scales (or raw fp8 codes) and
        dequantize inside the step; ``swap_bytes_shipped`` counts the
        actual host->device payload bytes, which is what the offload
        benchmark's bytes-moved gate reads."""
        e = self.ecfg
        S, P = e.max_instep_swaps, e.num_pages
        if len(queue) > S:
            for slot, half in queue[S:]:          # eager overflow fallback
                self.swap_in(slot, (half, None) if name == "k"
                             else (None, half))
            self.eager_swaps += len(queue) - S
            queue = queue[:S]
        self.instep_swaps += len(queue)
        dst_name = f"swap_{name}_dst"
        dst = views[dst_name]
        dst[:] = P
        out = {dst_name: dst}
        key_p, key_s = f"swap_{name}", f"swap_{name}_scale"
        if not queue:
            # swap-free half (the common case): all destinations padded
            # out of range, so the payload content is irrelevant — reuse
            # the device-resident zero payload instead of allocating and
            # transferring fresh host buffers every step
            out[key_p] = self._zero_swap
            if self._payload_fmt == "q8":
                out[key_s] = self._zero_scale
            return out
        cfg = self.cfg
        page_shape = self.k_pools.shape[2:]
        buf = np.zeros((cfg.n_layers, S) + page_shape, self._payload_npdt)
        scale = (np.zeros((cfg.n_layers, S, page_shape[0]), np.float32)
                 if self._payload_fmt == "q8" else None)
        for j, (slot, half) in enumerate(queue):
            assert half.fmt == self._payload_fmt, (half.fmt,
                                                   self._payload_fmt)
            dst[j] = slot
            buf[:, j] = half.data
            if scale is not None:
                scale[:, j] = half.scale
        self.swap_bytes_shipped += buf.nbytes
        out[key_p] = buf
        if scale is not None:
            self.swap_bytes_shipped += scale.nbytes
            out[key_s] = scale
        return out

    def _fold_page_ops_sharded(
            self, views: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Per-shard routing of queued COW copies / swap-ins.

        Shard i's queue row holds shard-LOCAL page indices (what its
        ``shard_map`` slice can address).  Copies whose src/dst live on
        different shards are device-to-device transfers the local scatter
        cannot express; they — and per-shard overflow — run through the
        eager global-view fallback, same as the single-device overflow
        path (the block manager's shard-affine COW placement makes
        cross-shard forks rare, not impossible)."""
        e = self.ecfg
        ns = self.n_shards
        ploc = e.num_pages // ns
        C, S = e.max_instep_copies, e.max_instep_swaps
        copies, self._pending_copies = self._pending_copies, []
        per_c: List[List[Tuple[int, int]]] = [[] for _ in range(ns)]
        eager_c: List[Tuple[int, int]] = []
        for src, dst in copies:
            s1, s2 = src // ploc, dst // ploc
            if C > 0 and s1 == s2 and len(per_c[s1]) < C:
                per_c[s1].append((src - s1 * ploc, dst - s1 * ploc))
            else:
                eager_c.append((src, dst))
        self.instep_copies += len(copies) - len(eager_c)
        self.eager_copies += len(eager_c)
        if eager_c:
            # eager copies run against the pools BEFORE this step, while
            # queued swap-ins would land inside it (after the copy reads
            # its donor) — flush every swap eagerly first, as a same-round
            # swap-in may be the donor of one of these forks
            self._flush_swaps_eager()
            self.copy_pages(eager_c)
        copy_src = views["copy_src"].reshape(ns, C)
        copy_dst = views["copy_dst"].reshape(ns, C)
        for i in range(ns):
            # padding repeats the shard's last real local copy
            # (idempotent) or is the local identity 0 -> 0
            ps, pd = per_c[i][-1] if per_c[i] else (0, 0)
            copy_src[i, :] = ps
            copy_dst[i, :] = pd
            for j, (s_, d_) in enumerate(per_c[i]):
                copy_src[i, j] = s_
                copy_dst[i, j] = d_
        out: Dict[str, np.ndarray] = {}
        kq, self._pending_swap_k = self._pending_swap_k, []
        vq, self._pending_swap_v = self._pending_swap_v, []
        for name, queue in (("k", kq), ("v", vq)):
            dst = views[f"swap_{name}_dst"].reshape(ns, S)
            dst[:, :] = ploc         # out of local range -> dropped
            per: List[List[Tuple[int, object]]] = [[] for _ in range(ns)]
            for slot, half in queue:
                sh = slot // ploc
                if S > 0 and len(per[sh]) < S:
                    per[sh].append((slot - sh * ploc, half))
                    self.instep_swaps += 1
                else:                               # per-shard overflow
                    self.swap_in(slot, (half, None) if name == "k"
                                 else (None, half))
                    self.eager_swaps += 1
            if not any(per):
                out[f"swap_{name}"] = self._zero_swap
                continue
            buf = np.zeros((ns, self.cfg.n_layers, S, self.cfg.n_kv_heads,
                            e.page_size, self.cfg.head_dim),
                           self._payload_npdt)
            for i in range(ns):
                for j, (ls, half) in enumerate(per[i]):
                    dst[i, j] = ls
                    buf[i, :, j] = half.data
            self.swap_bytes_shipped += buf.nbytes
            out[f"swap_{name}"] = jax.device_put(buf, self._swap_sh)
        return out

    # -- copy-on-write page forks (cross-request prefix sharing) --------
    def queue_copies(self, pairs: List[Tuple[int, int]]) -> None:
        """Queue COW page copies ``src -> dst`` to be folded into the next
        dispatched step (before its attention reads the forked pages)."""
        self._pending_copies.extend(pairs)

    def copy_pages(self, pairs: List[Tuple[int, int]]) -> None:
        """Eager device-side K/V page copies ``src -> dst`` (all layers).

        Kept as the overflow fallback when a round queues more forks than
        ``max_instep_copies``; the pipelined path uses ``queue_copies``.

        Shared *full* blocks need no copying — the block manager hands the
        same slot to several requests and ``build_inputs`` simply maps that
        slot into each sequence's page table.  Copies are only needed at a
        divergence point: the destination page first receives the donor's
        K/V (valid for the common positions by causality), then the forking
        request overwrites the divergent tail as it computes it."""
        if not pairs:
            return
        src = jnp.asarray([p[0] for p in pairs], jnp.int32)
        dst = jnp.asarray([p[1] for p in pairs], jnp.int32)
        self.k_pools = self.k_pools.at[:, dst].set(self.k_pools[:, src])
        if not self.cfg.latent:
            self.v_pools = self.v_pools.at[:, dst].set(self.v_pools[:, src])

    # -- host-tier swaps (paper §7 hierarchical storage) ----------------
    @staticmethod
    def _pop_queued(queue, slot: int):
        """Remove and return the half queued for ``slot``, if any."""
        for i, (s, half) in enumerate(queue):
            if s == slot:
                del queue[i]
                return half
        return None

    def swap_out(self, slot: int, need_k: bool = True,
                 need_v: bool = True):
        """Copy one block's K/V (all layers) device -> host, per half.

        Returns ``(k, v)`` where each element is the half's payload (a
        queued :class:`HostHalf` or a raw pool ndarray) or ``None`` when
        that half was not requested.  The block manager passes
        ``need_k``/``need_v`` = False for halves the host tier already
        holds (clean spill: committed content is immutable, so the
        resident copy is still exact) — those halves move zero bytes and
        skip the synchronous pool read entirely.

        ``np.asarray`` waits for any in-flight step that writes the pool,
        so pipelined execution cannot hand out stale pages.  A swap-in
        still QUEUED for this slot (possible when a prefetched block's
        pin expires and it is re-evicted before any step dispatched — the
        payload never reached the pool) is returned directly AND removed
        from the queue: the queued payload IS the block's content, and
        letting it land later would clobber whatever the reallocated page
        holds by then.  Both split queues are ALWAYS purged, even for
        halves the caller does not need — that purge is the safety net.
        A latent cache has no V half: its ``v`` is always None."""
        kh = self._pop_queued(self._pending_swap_k, slot)
        vh = self._pop_queued(self._pending_swap_v, slot)
        out_k = out_v = None
        if need_k:
            out_k = kh if kh is not None \
                else np.asarray(self.k_pools[:, slot])
        if need_v and not self.cfg.latent:
            out_v = vh if vh is not None \
                else np.asarray(self.v_pools[:, slot])
        return out_k, out_v

    def _as_half(self, payload) -> HostHalf:
        """Normalize a raw ndarray payload (legacy callers / tests) into
        the :class:`HostHalf` wire form the split queues carry."""
        if isinstance(payload, HostHalf):
            return payload
        arr = np.asarray(payload)
        return HostHalf(data=arr, scale=None, nbytes=arr.nbytes, fmt="fp")

    def queue_swap_in(self, slot: int, payload) -> None:
        """Queue a host-tier payload ``(k_half, v_half)`` — either may be
        ``None`` (split residency) — to be scattered into ``slot`` inside
        the next dispatched step (the one whose attention first reads it).
        Falls back to the eager path when the in-step bucket is disabled."""
        if self.ecfg.max_instep_swaps <= 0:
            self.swap_in(slot, payload)
            return
        kh, vh = payload
        if kh is not None:
            self._pending_swap_k.append((slot, self._as_half(kh)))
        if vh is not None:
            self._pending_swap_v.append((slot, self._as_half(vh)))

    def swap_in(self, slot: int, payload) -> None:
        """Eager host -> device restore (overflow / bucket-disabled path).
        Quantized halves dequantize on the host with the same operand
        order as the in-step ``_dequant_payload``, so both paths land
        bit-identical pool bytes."""
        kh, vh = payload
        dt = np.dtype(self.cfg.dtype)
        if kh is not None:
            self.k_pools = self.k_pools.at[:, slot].set(
                jnp.asarray(dequantize_half(self._as_half(kh), dt)))
        if vh is not None:
            self.v_pools = self.v_pools.at[:, slot].set(
                jnp.asarray(dequantize_half(self._as_half(vh), dt)))

    # ------------------------------------------------------------------
    def perf_counters(self) -> Dict[str, object]:
        """Deterministic hot-path accounting: counts, not times (host
        wall clock is too noisy on shared containers)."""
        steps = max(self.steps_executed, 1)
        total = max(self.total_token_rows, 1)
        return {
            "attn_dispatches": self.attn_dispatches,
            "attn_dispatches_per_step": self.attn_dispatches / steps,
            "padded_token_fraction":
                1.0 - self.valid_token_rows / total,
            "bucket_counts": {f"T{t}xNP{n}": c for (t, n), c
                              in sorted(self.bucket_counts.items())},
            "instep_copies": self.instep_copies,
            "eager_copies": self.eager_copies,
            "instep_swaps": self.instep_swaps,
            "eager_swaps": self.eager_swaps,
            "swap_bytes_shipped": self.swap_bytes_shipped,
            # pool pages the fused steps' KV writes touched (per layer)
            "kv_pages_written": self.kv_pages_written,
            # work-list items of the Pallas steps, and those of one-token
            # runs (decode rows) among them
            "attn_items": self.attn_items,
            "attn_narrow_items": self.attn_narrow_items,
            # multi-token decode dispatch (schema frozen by
            # tests/test_perf_counters.py — benchmark gates read these)
            "engine_dispatches": self.steps_executed,
            "decode_only_dispatches": self.decode_only_dispatches,
            "decode_tokens_emitted": self.decode_tokens_emitted,
            "multi_token_dispatches": self.multi_token_dispatches,
            "multi_token_iterations": self.multi_token_iterations,
            "multi_token_rollbacks": self.multi_token_rollbacks,
            "k_counts": {f"k{k}": c for k, c
                         in sorted(self.k_counts.items())},
            # bytes one pool page holds over all layers (K and V halves;
            # a latent cache has only the K half)
            "kv_bytes_per_page": self.kv_bytes_per_page,
            # layers by "<attention>+<mlp>" kind, e.g. "latent+moe"
            "layers_by_kind": self.layers_by_kind(),
        }

    @property
    def kv_bytes_per_page(self) -> int:
        halves = 1 if self.cfg.latent else 2
        pools = self.k_pools
        return (halves * pools.shape[0] * int(np.prod(pools.shape[2:]))
                * pools.dtype.itemsize)

    def layers_by_kind(self) -> Dict[str, int]:
        cfg = self.cfg
        attn = "latent" if cfg.latent else "gqa"
        out: Dict[str, int] = {}
        for l in range(cfg.n_layers):
            kind = f"{attn}+{cfg.mlp_kind(l)}"
            out[kind] = out.get(kind, 0) + 1
        return out

    def reset_perf_counters(self) -> None:
        """Zero the deterministic accounting so a benchmark can measure
        one phase of a run in isolation (e.g. the decode-dominated
        segment the multi-token gates slice out).  The jit-cache state —
        ``jit_traces`` and ``buckets_used`` — is NOT reset: the
        compile-once-per-bucket invariant spans the engine's lifetime."""
        self.steps_executed = 0
        self.attn_dispatches = 0
        self.valid_token_rows = 0
        self.total_token_rows = 0
        self.bucket_counts = {}
        self.instep_copies = self.eager_copies = 0
        self.instep_swaps = self.eager_swaps = 0
        self.swap_bytes_shipped = 0
        self.kv_pages_written = 0
        self.attn_items = self.attn_narrow_items = 0
        self.decode_only_dispatches = 0
        self.decode_tokens_emitted = 0
        self.multi_token_dispatches = 0
        self.multi_token_iterations = 0
        self.multi_token_rollbacks = 0
        self.k_counts = {}

    def compiled_step(self, t_bucket: int, np_bucket: int,
                      w_bucket: int = 0):
        """Compile one step variant against the engine's live params and
        pools, outside the jit cache and the ``jit_traces`` count — for
        inspecting the program (HLO text, shardings, memory analysis)."""
        _, size = self.pack_layout(t_bucket, np_bucket, w_bucket)
        inp = {"pack": jnp.zeros((size,), jnp.int32),
               "swap_k": self._zero_swap, "swap_v": self._zero_swap}
        if self._payload_fmt == "q8":
            inp["swap_k_scale"] = self._zero_scale
            inp["swap_v_scale"] = self._zero_scale
        traces = self.jit_traces
        try:
            # lower() always retraces outside the jit cache; the trace
            # counter must keep meaning "compiled step variants executed"
            return self._step.lower(self.params, self.k_pools, self.v_pools,
                                    inp, t_bucket, np_bucket, w_bucket,
                                    1).compile()
        finally:
            self.jit_traces = traces

    def collective_counts(self, t_bucket: Optional[int] = None,
                          np_bucket: Optional[int] = None) -> Dict[str, int]:
        """Collective ops in one compiled step variant, by kind —
        deterministic accounting for the sharded engine (wall clock can't
        measure the merge cost on drifting shared hosts, HLO op counts
        can).  Counts the whole step: L per-layer LSE merges plus whatever
        GSPMD inserts for the sharded weights/logits."""
        from repro.roofline import parse_collectives
        t_b = t_bucket if t_bucket is not None else self.token_buckets[0]
        np_b = np_bucket if np_bucket is not None else self.np_buckets[0]
        coll = parse_collectives(self.compiled_step(t_b, np_b).as_text())
        return {kind: int(v["count"]) for kind, v in sorted(coll.items())}

    # ------------------------------------------------------------------
    def dispatch(self, plan: StepPlan) -> StepHandle:
        """Assemble and launch one step WITHOUT waiting for the device.

        Returns a :class:`StepHandle` over the device-side results; the
        pools advance immediately to the (asynchronous) step outputs, so a
        subsequent ``dispatch`` is ordered after this step by data
        dependency — the basis of the one-step-deep pipeline."""
        tr = self.tracer
        if tr is not None:
            tr.phase(ASSEMBLE)
        k = plan.decode_steps
        inp, (t_b, np_b, w_b) = self.build_inputs(plan)
        if tr is not None:
            tr.phase(LAUNCH)
        traces = self.jit_traces
        token_ids, pre_logits, self.k_pools, self.v_pools = self._step(
            self.params, self.k_pools, self.v_pools, inp, t_b, np_b, w_b, k)
        if tr is not None:
            tr.launched((t_b, np_b, w_b, k), self.jit_traces > traces)
        self.steps_executed += 1
        self.buckets_used.add((t_b, np_b, w_b, k))
        self.attn_dispatches += self.cfg.n_layers * k
        emitted = plan.emitted_tokens
        self.valid_token_rows += emitted
        self.total_token_rows += t_b * k
        key = (t_b, np_b)
        self.bucket_counts[key] = self.bucket_counts.get(key, 0) + 1
        if plan.decodes and not plan.prefills:
            self.decode_only_dispatches += 1
            self.decode_tokens_emitted += emitted
        if k > 1:
            self.multi_token_dispatches += 1
            self.multi_token_iterations += k
            self.multi_token_rollbacks += \
                k * len(plan.decodes) - sum(plan.decode_iters)
            self.k_counts[k] = self.k_counts.get(k, 0) + 1
        return StepHandle(token_ids=token_ids, prefill_logits=pre_logits,
                          full_logits=self.ecfg.return_full_logits)

    def execute(self, plan: StepPlan) -> StepHandle:
        """Synchronous convenience wrapper: dispatch + wait."""
        handle = self.dispatch(plan)
        handle.block()
        return handle
