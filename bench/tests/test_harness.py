"""The harness end to end on the CPU at smoke size (``--dry``): a sound run
is correct; the lower-precision control and each fault the served path
can have, planted under the timed path, make ``correct`` come out false.

Faults of a served cell: a step that returns its KV state unchanged, and
a token altered where it is produced.  (A batch mean and an exchange
between chips do not exist on a one-chip serving path.)
"""
import contextlib
import importlib.util
import io
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
CELLS = [c["name"] for c in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


def dry_run(workload, seed, *extra):
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "4", "--dry", *extra])
    text = out.getvalue()
    assert text.rstrip().splitlines()[-1] == "dry run: no result line"
    rec = json.loads(re.search(r"^dry record: (.*)$", text, re.M).group(1))
    checks = {m.group(1): (float(m.group(2)), float(m.group(3)))
              for m in re.finditer(r"^(\w+): (\S+) \(limit (\S+)\)$",
                                   err.getvalue(), re.M)}
    return rc, rec, checks


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell):
    """One window: the program's tokens pass; with the fp8 control put in
    the program's place, the same rule makes ``correct`` false."""
    rc, rec, checks = dry_run(cell, 2**31 + 101, "--control")
    assert rc == 1 and not rec["correct"]
    gap, limit = checks["logit_gap"]
    ctl, _ = checks["control_logit_gap"]
    assert gap <= limit < ctl
    assert rec["metrics"]["setup_s"]["value"] > 0
    assert rec["device"]["memory_peak_bytes"] == "not measured"
    rc, rec, checks = dry_run(cell, 2**31 + 101)
    assert rc == 0 and rec["correct"]
    assert checks["logit_gap"][0] == gap and "control_logit_gap" not in checks


def test_traced_dry_run_marks_device_metrics_not_measured():
    rc, rec, _ = dry_run(CELLS[0], 2**31 + 102, "--trace", "1")
    assert rc == 0
    for name, v in rec["metrics"].items():
        if name.startswith(("device_idle_share", "msa_fused_roofline",
                            "step_mfu")):
            assert v == "not measured"
    assert rec["metrics"]["decode_rows_per_step"]["value"] > 0


def test_state_left_unchanged_fails(monkeypatch):
    import repro.serving.engine as eng
    monkeypatch.setattr(eng, "write_kv_pages",
                        lambda kp, vp, *a, **k: (kp, vp))
    rc, rec, checks = dry_run(CELLS[0], 2**31 + 103)
    assert rc == 1 and not rec["correct"]
    gap, limit = checks["logit_gap"]
    assert gap > limit


def test_token_altered_where_produced_fails(monkeypatch):
    import repro.serving.engine as eng
    step = eng.Engine._step_impl

    def altered(self, *a, **k):
        ids, logits, kp, vp = step(self, *a, **k)
        return (ids + 1) % self.cfg.vocab_size, logits, kp, vp

    monkeypatch.setattr(eng.Engine, "_step_impl", altered)
    rc, rec, checks = dry_run(CELLS[-1], 2**31 + 104)
    assert rc == 1 and not rec["correct"]
    gap, limit = checks["logit_gap"]
    assert gap > limit
