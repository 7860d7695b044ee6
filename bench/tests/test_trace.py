"""Trace reduction: arithmetic on hand-made intervals, reading on a small
trace recorded on a TPU v5e (one ``msa_fused`` call and one matmul inside
the ``bench.window`` span); every kernel named gets its device seconds."""
from pathlib import Path

import pytest

from bench.harness import trace as trc

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_clip():
    assert trc.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trc.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]


def test_reduce_busy_kernel_and_gaps():
    ex = {
        "devices": {"/device:TPU:0": [
            ("fusion.1", 1.0, 2.0),
            ("msa_fused", 2.0, 3.5),
            ("msa_fused", 3.0, 3.6),        # overlaps: busy counts once
            ("fusion.2", 6.0, 7.0),
            ("fusion.3", 9.5, 12.0),        # runs past the window
        ]},
        "spans": [
            (trc.WINDOW_SPAN, 0.5, 10.0),
            ("engine.dispatch", 1.0, 1.2),   # first dispatch: no shift
            ("engine.dispatch", 3.0, 5.5),
            ("sched.schedule", 4.0, 5.0),    # innermost at t = 4.8
            ("server._retire", 7.0, 9.0),
        ],
    }
    r = trc.reduce(ex, ("msa_fused", "fusion.2", "absent"))
    assert r["window_s"] == pytest.approx(9.5)
    # busy: [1, 3.6] + [6, 7] + [9.5, 10]
    assert r["busy_s"] == pytest.approx(2.6 + 1.0 + 0.5)
    assert r["kernels"]["msa_fused"]["device_s"] == pytest.approx(1.5 + 0.6)
    assert r["kernels"]["fusion.2"]["device_s"] == pytest.approx(1.0)
    assert r["kernels"]["absent"]["device_s"] == 0
    assert r["device_ops"][0] == ["msa_fused", pytest.approx(2.1)]
    gaps = {(n, round(s, 9)) for n, s in r["idle_gaps"]}
    assert ("sched.schedule", 2.4) in gaps        # 3.6 .. 6
    assert ("server._retire", 2.5) in gaps        # 7 .. 9.5
    assert ("host idle", 0.5) in gaps             # 0.5 .. 1


def test_reduce_without_window_or_devices_reads_nothing():
    assert trc.reduce({"devices": {}, "spans": []}, ("msa_fused",)) == {}


def test_op_names():
    assert trc.op_name("%msa_fused.22 = bf16[8] custom-call(...)") == \
        "msa_fused"
    assert trc.op_name("%copy.407.remat2 = bf16[5120] copy(%x)") == "copy"
    assert trc.op_name("%maximum_bitcast_fusion.2 = f32[] fusion()") == \
        "maximum_bitcast_fusion"


def test_device_clock_aligned_to_first_dispatch():
    ex = {"devices": {"/device:TPU:0": [("a", 100.0, 101.0),
                                        ("b", 102.0, 103.0)]},
          "spans": [(trc.WINDOW_SPAN, 0.0, 10.0),
                    ("engine.dispatch", 1.0, 1.5)]}
    r = trc.reduce(ex, ("a",))
    assert r["busy_s"] == pytest.approx(2.0)
    assert r["kernels"]["a"]["device_s"] == pytest.approx(1.0)
    gaps = {(n, round(s, 9)) for n, s in r["idle_gaps"]}
    assert ("engine.dispatch", 0.0) not in gaps
    assert ("host idle", 1.0) in gaps and ("host idle", 6.0) in gaps


def test_recorded_trace():
    ex = trc.extract(str(DATA / "tiny.xplane.pb"),
                     ("engine.dispatch", "server._retire"))
    assert any(p.startswith("/device:TPU") for p in ex["devices"])
    r = trc.reduce(ex, ("msa_fused",))
    assert 0 < r["kernels"]["msa_fused"]["device_s"] < r["busy_s"] \
        <= r["window_s"]
    assert "msa_fused" in {n for n, _ in r["device_ops"]}
    assert {n for n, _ in r["idle_gaps"]} <= {
        "engine.dispatch", "server._retire", "host idle"}
