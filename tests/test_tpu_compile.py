"""The fused MSA kernel and the engine's layer body compile for a TPU v5e.

No chip is needed: the TPU compiler is given a described ``v5e:2x2``
topology and compiles for one of its devices, so Mosaic refuses here what
it would refuse on the chip (block shapes off the (8, 128) tiling, VMEM
overuse).  Interpret-mode tests cannot see those faults.

The topology is described inside module-scoped fixtures of this one file
and never at import time: only one process may load the TPU library, and
every test worker imports every test file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.msa import WL_FIELDS
from repro.kernels.msa.latent import LatentLayout
from repro.kernels.msa.msa_fused import block_pages, msa_fused_pallas
from repro.serving.engine import write_and_attend

# Llama-3.1-8B attention widths; the engine's page size and q tile
H, KH, D, PAGE, Q_TILE = 32, 8, 128, 16, 128
POOL_PAGES, N_SEQS = 2048, 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiles(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (token bucket, work-list items, KV heads, layers): a decode-only bucket
# and the largest prefill bucket of the benchmark's cells (2064 tokens =
# 17 q tiles), at G=4 (8 KV heads) and at G=16 (2 KV heads, whose
# (G*TQ, D) = (2048, 128) operands hold the most VMEM); and the largest
# work-list the engine runs for ChatGLM3's 512-page bucket
# (``wl_capacity``), whose scalar-prefetched slots hold the most SMEM.
# layers 0: one layer's pool; else the engine's layer-stacked pools (the
# cells' 20 and 28 layers) and a layer index.
FUSED_CASES = [(16, 256, KH, 0), (2064, 512, KH, 0), (16, 256, 2, 0),
               (2064, 512, 2, 0), (2064, 4096, 2, 0),
               (16, 256, KH, 20), (2064, 512, KH, 20), (2064, 512, 2, 28),
               (2064, 4096, 2, 28)]


@pytest.mark.parametrize(
    "t,w,kh,layers", FUSED_CASES,
    ids=[f"{t}-{w}-{kh}" + (f"-stacked{n}" if n else "")
         for t, w, kh, n in FUSED_CASES])
def test_msa_fused_compiles_for_v5e(spec, no_compile_cache, t, w, kh,
                                    layers):
    pool = spec((layers,) * bool(layers) + (POOL_PAGES, kh, PAGE, D),
                jnp.bfloat16)
    slots = w * block_pages(PAGE)
    layer = (spec(()),) if layers else ()
    _compiles(lambda *a: msa_fused_pallas(*a, q_tile=Q_TILE),
              (spec((t, H, D), jnp.bfloat16), pool, pool,
               spec((N_SEQS,)), spec((N_SEQS,)), spec((t,)), spec((N_SEQS,)),
               spec((w,)), spec((w,)), spec((slots,)),
               *(spec((w,)) for _ in range(3)), *layer))


def _entry_ops(hlo: str):
    """(name, opcode, result element counts) of each instruction of the
    compiled module's entry computation."""
    lines = hlo[hlo.index("\nENTRY"):].splitlines()[1:]
    for line in lines:
        if line.startswith("}"):
            return
        if " = " not in line:
            continue
        name, rhs = line.strip().removeprefix("ROOT ").split(" = ", 1)
        if rhs.startswith("("):             # a tuple-shaped result
            depth = 0
            for end, ch in enumerate(rhs):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            rtype, rest = rhs[:end + 1], rhs[end + 1:]
        else:
            rtype, rest = rhs.split(" ", 1)
        sizes = [math.prod(int(x) for x in dims.split(",") if x)
                 for dims in re.findall(r"\w+\[([\d,]*)\]", rtype)]
        yield name.lstrip("%"), rest.strip().split("(", 1)[0], sizes


def test_engine_layer_writes_pools_in_place_for_v5e(spec, no_compile_cache):
    """Two of the engine's fused layer bodies (``write_and_attend``: the
    page write, then ``msa_fused`` on the stacked pools) over donated
    ChatGLM3-width pools of 5120 pages, in the 2064-token bucket.  Each
    layer's input depends on the layer before, as in the step.  No
    temporary the size of one layer's pool may appear, and no copy, slice
    or dynamic-update-slice of the stacked or one layer's pool: a row
    scatter straight into the pools, or a kernel operand sliced to one
    layer, puts whole-pool copies of that kind here (and on the chip on
    every layer of every step)."""
    n_layers, pages, kh, t, w, np_b, n = 2, 5120, 2, 2064, 512, 512, 18
    nw = 149                        # kv_write_capacity: 129 + 2 * 2 + 16

    def layers(k_pools, v_pools, q, k_new, v_new, f, wl):
        for layer in range(n_layers):
            k_pools, v_pools, attn = write_and_attend(
                k_pools, v_pools, layer, q, k_new, v_new, f, wl, window=0,
                softcap=0.0, q_tile=Q_TILE, impl="pallas")
            q, k_new, v_new = attn, attn[:, :kh], attn[:, kh:2 * kh]
        return k_pools, v_pools, q

    pool = spec((n_layers, pages, kh, PAGE, D), jnp.bfloat16)
    f = {"write_page": spec((nw,)), "write_row": spec((t,)),
         "write_off": spec((t,)), "valid": spec((t,), jnp.bool_),
         "bt": spec((n, np_b)), "ctx": spec((n,)), "positions": spec((t,)),
         "seq_ids": spec((t,)), "qstart": spec((n,)), "qlen": spec((n,))}
    wl = tuple(spec((w * block_pages(PAGE),) if name == "wl_slot" else (w,))
               for name in WL_FIELDS)
    rows = spec((t, kh, D), jnp.bfloat16)
    compiled = jax.jit(layers, donate_argnums=(0, 1)).lower(
        pool, pool, spec((t, H, D), jnp.bfloat16), rows, rows, f,
        wl).compile()
    layer_elems = pages * kh * PAGE * D
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * layer_elems
    copies = [(name, op) for name, op, sizes in _entry_ops(compiled.as_text())
              if max(sizes, default=0) >= layer_elems
              and any(k in (op if op != "fusion" else name)
                      for k in ("copy", "slice", "dynamic-update-slice"))]
    assert not copies, copies


# Moonlight-16B-A3B's latent attention: 16 query heads over one cached
# 576-lane vector per token (kv_lora_rank 512 + qk_rope 64), stored as a
# (72, 128) tile a page, the cell's q tile 64; the decode-only bucket
# and the largest prefill bucket at their largest work-lists
LATENT = LatentLayout(page=PAGE, rank=512, rope=64)
LATENT_CASES = [(32, 4096), (2080, 8192)]


@pytest.mark.parametrize("t,w", LATENT_CASES,
                         ids=[f"{t}-{w}" for t, w in LATENT_CASES])
def test_latent_msa_fused_compiles_for_v5e(spec, no_compile_cache, t, w):
    pool = spec((27, POOL_PAGES, 1, LATENT.rows, 128), jnp.bfloat16)
    _compiles(lambda *a: msa_fused_pallas(*a, q_tile=64, latent=LATENT),
              (spec((t, 16, LATENT.width), jnp.bfloat16), pool, None,
               spec((34,)), spec((34,)), spec((t,)), spec((34,)),
               spec((w,)), spec((w,)), spec((w * block_pages(PAGE),)),
               *(spec((w,)) for _ in range(3)), spec(())))


def test_latent_layer_writes_pool_in_place_for_v5e(spec, no_compile_cache):
    """Two latent layer bodies over a donated pool of 4096 latent pages in
    the 2080-token bucket: the touched page tiles open into rows, take
    the new rows and close again, and no temporary or copy the size of
    one layer's pool appears."""
    n_layers, pages, t, w, np_b, n = 2, 4096, 2080, 512, 512, 34
    nw = 166                        # kv_write_capacity: 130 + 2 * 2 + 32

    def layers(k_pools, q, k_new, f, wl):
        for layer in range(n_layers):
            k_pools, _, attn = write_and_attend(
                k_pools, None, layer, q, k_new, None, f, wl, window=0,
                softcap=0.0, q_tile=64, impl="pallas", latent=LATENT)
            k_new = k_new + attn[:, :1, :1]
        return k_pools, attn

    pool = spec((n_layers, pages, 1, LATENT.rows, 128), jnp.bfloat16)
    f = {"write_page": spec((nw,)), "write_row": spec((t,)),
         "write_off": spec((t,)), "valid": spec((t,), jnp.bool_),
         "bt": spec((n, np_b)), "ctx": spec((n,)), "positions": spec((t,)),
         "seq_ids": spec((t,)), "qstart": spec((n,)), "qlen": spec((n,))}
    wl = tuple(spec((w * block_pages(PAGE),) if name == "wl_slot" else (w,))
               for name in WL_FIELDS)
    compiled = jax.jit(layers, donate_argnums=(0,)).lower(
        pool, spec((t, 16, LATENT.width), jnp.bfloat16),
        spec((t, 1, LATENT.width), jnp.bfloat16), f, wl).compile()
    layer_elems = pages * LATENT.rows * 128
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * layer_elems
    copies = [(name, op) for name, op, sizes in _entry_ops(compiled.as_text())
              if max(sizes, default=0) >= layer_elems
              and any(k in (op if op != "fusion" else name)
                      for k in ("copy", "slice", "dynamic-update-slice"))]
    assert not copies, copies
