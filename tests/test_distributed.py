"""Distribution-layer tests.

Multi-device tests run in subprocesses (``conftest.run_devices``: jax
locks the host device count at first init, and the main pytest process
must keep seeing 1 CPU device for the smoke tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_devices
from repro.configs import ARCH_IDS, get_config
from repro.distributed.sharding import effective_config


def _run_devices(code: str, n_devices: int = 8) -> str:
    return run_devices(code, n_devices)


# ---------------------------------------------------------------------------
# effective_config hardware adaptation
# ---------------------------------------------------------------------------

def test_effective_config_vocab_padding():
    cfg = effective_config(get_config("granite-3-8b"))
    assert cfg.vocab_size % 256 == 0
    assert cfg.real_vocab == 49155


def test_effective_config_head_padding():
    cfg = effective_config(get_config("llava-next-34b"))
    assert cfg.n_heads == 64          # 56 -> 64 for TP16
    assert cfg.n_kv_heads == 8        # KV heads NOT padded (seq-sharded)


def test_effective_config_virtual_experts():
    cfg = effective_config(get_config("grok-1-314b"))
    assert cfg.moe.num_experts == 16          # 8 x split 2
    assert cfg.moe.expert_split == 2
    assert cfg.d_ff == 16384                  # 32768 / 2
    # param count preserved by the split
    assert abs(cfg.param_count() - get_config("grok-1-314b").param_count()) \
        < 0.01 * get_config("grok-1-314b").param_count()


def test_effective_config_kimi_unchanged():
    cfg = effective_config(get_config("kimi-k2-1t-a32b"))
    assert cfg.moe.num_experts == 384 and cfg.moe.expert_split == 1


def test_virtual_expert_split_exactness():
    """Column-split experts must reproduce the unsplit MoE exactly."""
    from repro.models.layers import moe_ffn_local
    key = jax.random.PRNGKey(0)
    t, d, e, f, k = 12, 16, 4, 32, 2
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (t, d))
    rw = jax.random.normal(ks[1], (d, e)) * 0.1
    we1 = jax.random.normal(ks[2], (e, d, f)) * 0.2
    we3 = jax.random.normal(ks[3], (e, d, f)) * 0.2
    we2 = jax.random.normal(ks[4], (e, f, d)) * 0.2
    base = moe_ffn_local(x, rw, we1, we3, we2, k, dropless=True)
    split = 2
    fs = f // split
    sp = lambda w: w.reshape(e, d, split, fs).transpose(0, 2, 1, 3).reshape(
        e * split, d, fs)
    we2s = we2.reshape(e, split, fs, d).reshape(e * split, fs, d)
    out = moe_ffn_local(x, rw, sp(we1), sp(we3), we2s, k, dropless=True,
                        expert_split=split)
    np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Multi-device correctness (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_moe_alltoall_matches_local():
    _run_devices("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config, scaled_config
        from repro.models import init_params, forward
        from repro.distributed.context import use_dist, DistContext
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = scaled_config(get_smoke_config("kimi-k2-1t-a32b"),
                            dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab_size)
        base = forward(params, cfg, {"tokens": toks})
        rules = {"batch": "data", "experts": "data", "expert_ffn": "model"}
        with use_dist(DistContext(mesh, rules, {"moe_alltoall": True})), mesh:
            dist = forward(params, cfg, {"tokens": toks})
        err = float(jnp.max(jnp.abs(base - dist))) / float(
            jnp.max(jnp.abs(base)))
        assert err < 1e-4, err
        print("OK", err)
    """)


@pytest.mark.slow
def test_flash_decode_matches_local():
    _run_devices("""
        import jax, jax.numpy as jnp
        from repro.distributed.context import use_dist, DistContext
        from repro.distributed.flash_decode import sharded_decode_attention
        from repro.models.layers import decode_attention
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        B, S, H, KH, D = 4, 32, 4, 2, 16
        q = jax.random.normal(ks[0], (B, H, D))
        k = jax.random.normal(ks[1], (B, S, KH, D))
        v = jax.random.normal(ks[2], (B, S, KH, D))
        kv_len = jnp.array([32, 17, 9, 1], jnp.int32)
        ref = decode_attention(q, k, v, kv_len)
        ctx = DistContext(mesh, {"batch": "data", "kv_seq": "model"}, {})
        with use_dist(ctx), mesh:
            out = sharded_decode_attention(q, k, v, kv_len)
        err = float(jnp.max(jnp.abs(ref - out)))
        assert err < 1e-5, err
        # replicated-KV degenerate case (whisper cross-attention, S=30
        # not divisible by 4 model shards)
        k2, v2 = k[:, :30], v[:, :30]
        ref2 = decode_attention(q, k2, v2, jnp.minimum(kv_len, 30))
        with use_dist(ctx), mesh:
            out2 = sharded_decode_attention(q, k2, v2,
                                            jnp.minimum(kv_len, 30))
        err2 = float(jnp.max(jnp.abs(ref2 - out2)))
        assert err2 < 1e-5, err2
        print("OK", err, err2)
    """)


@pytest.mark.slow
def test_dryrun_cell_small_mesh():
    """The dry-run machinery end-to-end on an 8-device mesh (structure
    identical to the 512-device production run)."""
    _run_devices("""
        import jax, jax.numpy as jnp, dataclasses
        import repro.launch.mesh as mesh_mod
        mesh_mod.make_production_mesh = lambda multi_pod=False: \
            mesh_mod.make_mesh(
                (2, 2, 2) if multi_pod else (4, 2),
                ("pod", "data", "model") if multi_pod else ("data", "model"))
        import repro.launch.dryrun as dr
        dr.make_production_mesh = mesh_mod.make_production_mesh
        import repro.configs.base as cb
        # shrink the shape grid for the test
        cb.SHAPE_BY_NAME["train_4k"] = dataclasses.replace(
            cb.SHAPE_BY_NAME["train_4k"], seq_len=64, global_batch=8)
        rec = dr.run_cell("chatglm3-6b", "train_4k", multi_pod=False,
                          out_dir="/tmp/dryrun_test", force=True)
        assert rec["status"] == "ok", rec
        assert rec["roofline"]["useful_ratio"] > 0
        print("OK", rec["roofline"]["bottleneck"])
    """, n_devices=8)


def test_banded_attention_model_equivalence():
    from repro.configs import get_smoke_config, scaled_config
    from repro.models import init_params, forward
    from repro.distributed.context import use_dist, DistContext
    from repro.launch.mesh import make_debug_mesh
    key = jax.random.PRNGKey(0)
    for arch in ("gemma3-12b", "granite-3-8b", "hymba-1.5b"):
        cfg = scaled_config(get_smoke_config(arch), dtype="float32")
        params = init_params(cfg, key)
        toks = jax.random.randint(key, (2, 64), 0, cfg.vocab_size)
        base = forward(params, cfg, {"tokens": toks})
        mesh = make_debug_mesh((1, 1))
        with use_dist(DistContext(mesh, {}, {"banded_attention": True})):
            banded = forward(params, cfg, {"tokens": toks})
        rel = float(jnp.max(jnp.abs(base - banded))) / float(
            jnp.max(jnp.abs(base)))
        assert rel < 1e-3, (arch, rel)


def test_sharding_rules_sanity():
    from repro.distributed.sharding import sharding_rules
    from jax.sharding import AbstractMesh
    # AbstractMesh carries axis sizes without requiring real devices
    mesh = AbstractMesh((2, 2), ("data", "model"))
    for arch in ARCH_IDS:
        cfg = effective_config(get_config(arch), tp=2, ep=2)
        for kind in ("train", "prefill", "decode"):
            rules = sharding_rules(cfg, mesh, kind, batch_size=8)
            assert rules["batch"] == "data"
            if kind == "decode" and cfg.family != "ssm":
                assert rules["kv_seq"] is not None
