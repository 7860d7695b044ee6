"""The architecture contract: a configuration's ``arch`` names a module
that the harness loads by name, so a new architecture is new files only.
A test-only module (``data/shifted_gqa.py``) placed in a copy of the
benchmark runs through ``run.py --dry`` and its planted shift makes
``correct`` false; every kernel a module names gets device seconds from a
recorded trace and roofline seconds from the module's work counts."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

from bench.harness import serve as sv
from bench.harness import trace as trc
from bench.harness import work

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
DATA = BENCH / "tests" / "data"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
SINGLE = {
    "kind": "single", "rate": 0.5, "shape_seed": 0, "system_len": 0,
    "max_context": 4096,
    "prompt_len": {"median": 700, "sigma": 0.6, "min": 256, "max": 2048},
    "output_len": {"median": 96, "sigma": 0.6, "min": 32, "max": 256},
    "history_s": 0.0, "warm_pool_fill": 0.0, "ramp_s": 5.0,
    "drain_s": 30.0, "saturated": False, "prefetch": False}


def test_new_architecture_is_files_only_and_shift_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    shutil.copy(DATA / "shifted_gqa.py",
                tmp_path / "bench" / "models" / "shifted_gqa.py")
    cfg = json.loads((BENCH / "configs" / "granite-3-8b-s20.json")
                     .read_text())
    cfg.update(name="granite-shifted", arch="shifted_gqa")
    (tmp_path / "bench" / "configs" / "granite-shifted.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "single-paced.json").write_text(
        json.dumps(SINGLE))
    spec = dict(SPEC)
    spec["configs"] = SPEC["configs"] + [{
        "name": "granite-shifted", "source": cfg["source"],
        "file": "bench/configs/granite-shifted.json", "reduced": [],
        "why": "dense GQA with a planted shift in its reference"}]
    spec["workloads"] = SPEC["workloads"] + [{
        "name": "granite-shifted.single-paced", "config": "granite-shifted",
        "traffic": "single-paced", "chips": 1,
        "why": "single-turn unshared prompts"}]
    spec["per_layer"] = [
        dict(m, workloads=m["workloads"] + ["granite-shifted.single-paced"])
        if m["name"] == "host_loop_ms_per_step" else m
        for m in SPEC["per_layer"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-shifted.single-paced", "--seed", str(2**31 + 105),
         "--seconds", "4", "--trace", "1", "--dry"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 1, r.stderr[-2000:]
    assert r.stdout.rstrip().splitlines()[-1] == "dry run: no result line"
    rec = json.loads(re.search(r"^dry record: (.*)$", r.stdout,
                               re.M).group(1))
    assert rec["correct"] is False
    gap, limit = re.search(r"^logit_gap: (\S+) \(limit (\S+)\)$", r.stderr,
                           re.M).groups()
    assert float(gap) > float(limit)
    assert rec["program"]["steps"] > 0
    assert rec["metrics"]["host_loop_ms_per_step"]["value"] > 0


def test_every_named_kernel_gets_device_and_roofline_seconds():
    mod = sv.load_module(DATA / "shifted_gqa.py")
    run = sv.load_module(BENCH / "run.py")
    cfg = json.loads((BENCH / "configs" / "granite-3-8b-s20.json")
                     .read_text())
    p = sv.program_block(cfg, dry=False)
    ex = trc.extract(str(DATA / "tiny.xplane.pb"), sv.SPANS)

    class Chip:
        device_kind = "TPU v5 lite"

    steps = [([work.seq_of_positions(range(512)),
               work.seq_of_positions([3000])], 2)]
    red = run.reduce_trace(ex, mod, p, steps, [Chip()], dry=False)
    assert set(red["kernels"]) == set(mod.KERNELS) == {
        "msa_fused", mod.MLP_OP}
    for k in mod.KERNELS:
        assert red["kernels"][k]["device_s"] > 0
        assert red["kernels"][k]["roofline_s"] > 0
    att = red["kernels"]["msa_fused"]
    reader = sv.load_module(BENCH / "metrics" / "msa_fused_roofline.py")
    assert reader.read({"trace": red}) == \
        100.0 * att["roofline_s"] / att["device_s"]
    assert red["model_flops"] == mod.step_flops(*steps[0], p)
    assert red["traced_steps"] == 1
