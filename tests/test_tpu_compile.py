"""The MSA kernels compile for a TPU v5e at Llama-3.1-8B widths.

No chip is needed: the TPU compiler is given a described ``v5e:2x2``
topology and compiles for one of its devices, so Mosaic refuses here what
it would refuse on the chip (block shapes off the (8, 128) tiling, VMEM
overuse).  Interpret-mode tests cannot see those faults.

The topology is described inside module-scoped fixtures of this one file
and never at import time: only one process may load the TPU library, and
every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.msa.msa_decode import msa_decode_pallas
from repro.kernels.msa.msa_fused import block_pages, msa_fused_pallas
from repro.kernels.msa.msa_prefill import msa_prefill_pallas

# Llama-3.1-8B attention widths; the engine's page size and q tile
H, KH, D, PAGE, Q_TILE = 32, 8, 128, 16, 128
POOL_PAGES, N_SEQS = 2048, 12
# split-layout batch: prefill rows x chunk, decode rows, pages per sequence
R, QP, B, NP = 2, 1024, 16, 128


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


# (token bucket, work-list items, KV heads): a decode-only bucket and the
# largest prefill bucket of the benchmark's cells (2064 tokens = 17 q
# tiles), at G=4 (8 KV heads) and at G=16 (2 KV heads, whose
# (G*TQ, D) = (2048, 128) operands hold the most VMEM); and the largest
# work-list the engine runs for ChatGLM3's 512-page bucket
# (``wl_capacity``), whose scalar-prefetched slots hold the most SMEM
@pytest.mark.parametrize("t,w,kh", [(16, 256, KH), (2064, 512, KH),
                                    (16, 256, 2), (2064, 512, 2),
                                    (2064, 4096, 2)])
def test_msa_fused_compiles_for_v5e(spec, no_compile_cache, t, w, kh):
    pool = spec((POOL_PAGES, kh, PAGE, D), jnp.bfloat16)
    slots = w * block_pages(PAGE)
    _compiles(lambda *a: msa_fused_pallas(*a, q_tile=Q_TILE),
              (spec((t, H, D), jnp.bfloat16), pool, pool,
               spec((N_SEQS,)), spec((N_SEQS,)), spec((t,)), spec((N_SEQS,)),
               spec((w,)), spec((w,)), spec((slots,)),
               *(spec((w,)) for _ in range(3))))


def test_msa_prefill_compiles_for_v5e(spec, no_compile_cache):
    """The split layout's prefill kernel (``attn_mode="split"``)."""
    pool = spec((POOL_PAGES, KH, PAGE, D), jnp.bfloat16)
    _compiles(lambda *a: msa_prefill_pallas(*a, q_tile=Q_TILE),
              (spec((R, QP, H, D), jnp.bfloat16), pool, pool,
               spec((R, NP)), spec((R,)), spec((R, QP)), spec((R,))))


def test_msa_decode_compiles_for_v5e(spec, no_compile_cache):
    """The split layout's decode kernel (``attn_mode="split"``)."""
    pool = spec((POOL_PAGES, KH, PAGE, D), jnp.bfloat16)
    _compiles(msa_decode_pallas,
              (spec((B, H, D), jnp.bfloat16), pool, pool,
               spec((B, NP)), spec((B,))))
