"""The benchmark is data found by name: every configuration, traffic mix
and per-layer metric that BENCHMARK.json names has its file, and a new
cell needs only new files and a new entry (shown by adding one in a copy
of the benchmark and running it dry)."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_named_part_has_its_file():
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"] == f"bench/configs/{c['name']}.json"
    for w in SPEC["workloads"]:
        spec = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                          .read_text())
        assert (BENCH / "traffic" / f"{spec['kind']}.py").is_file()
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_program_block_matches_published_widths(name):
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    p = c["program"]
    keys = {  # program field: the config's own key for it
        "granite": {"d_model": "hidden_size", "d_ff": "intermediate_size",
                    "n_heads": "num_attention_heads",
                    "n_kv_heads": "num_key_value_heads",
                    "n_layers": "num_hidden_layers",
                    "vocab_size": "vocab_size", "norm_eps": "rms_norm_eps",
                    "tie_embeddings": "tie_word_embeddings"},
        "chatglm": {"d_model": "hidden_size", "d_ff": "ffn_hidden_size",
                    "n_heads": "num_attention_heads",
                    "n_kv_heads": "multi_query_group_num",
                    "n_layers": "num_layers", "head_dim": "kv_channels",
                    "vocab_size": "padded_vocab_size",
                    "norm_eps": "layernorm_epsilon",
                    "tie_embeddings": "tie_word_embeddings"},
    }[c["model_type"]]
    for field, key in keys.items():
        assert p[field] == c[key], (field, key)
    assert p["n_heads"] * p["head_dim"] == p["d_model"]
    assert set(c["reduced"]) <= set(c["published"])


def test_new_cell_needs_only_files_and_an_entry(tmp_path):
    """A single-turn unshared mix on granite, added as one traffic file
    and one BENCHMARK.json entry to a copy of the benchmark."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    spec = dict(SPEC)
    spec["workloads"] = SPEC["workloads"] + [{
        "name": "granite-3-8b-s20.single-paced",
        "config": "granite-3-8b-s20", "traffic": "single-paced",
        "chips": 1, "why": "single-turn unshared prompts"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "traffic" / "single-paced.json").write_text(
        json.dumps({
            "kind": "single", "rate": 0.5, "shape_seed": 0,
            "system_len": 0, "max_context": 4096,
            "prompt_len": {"median": 700, "sigma": 0.6, "min": 256,
                           "max": 2048},
            "output_len": {"median": 96, "sigma": 0.6, "min": 32,
                           "max": 256},
            "history_s": 0.0, "warm_pool_fill": 0.0, "ramp_s": 5.0,
            "drain_s": 30.0, "saturated": False, "prefetch": False}))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-3-8b-s20.single-paced", "--seed", "5", "--seconds", "4",
         "--dry"], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.rstrip().splitlines()[-1] == "dry run: no result line"
    assert '"correct": true' in r.stdout


def test_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "TPU" in r.stderr
