"""Timeline reduction: the run-id clock link on a trace recorded on a TPU
v5e, the arithmetic of host-caused idle on hand-made events, and the
scope map on the HLO text of a small jitted function compiled here."""
import re
from pathlib import Path

import pytest

from bench.harness import timeline as tl

DATA = Path(__file__).resolve().parent / "data"


def test_run_id_links_host_and_device_clocks():
    """The recorded trace has no program spans: its two
    ``engine.dispatch`` spans stand in for ``serve.launch``."""
    ex = tl.extract(str(DATA / "tiny.xplane.pb"),
                    launch_span="engine.dispatch")
    ln = tl.link(ex)
    assert [s["run_id"] for s in ln["steps"]] == [13, 14]
    assert ln["unmatched_launches"] == 0
    sh, = ln["shifts"].values()
    # the device clock runs 1.479-1.486 ms behind the host's
    assert -1.4863e-3 <= sh["shift_s"] <= -1.479e-3
    assert 0 <= sh["spread_s"] < 1e-5 and sh["linked"] == 2
    red = tl.reduce(ex)
    first, second = red["steps"]
    # the first module starts where its enqueue ended; the second waited
    # on the host: its enqueue ended 0.66 ms after the first module
    assert first["module_host"][0] == pytest.approx(
        ln["steps"][0]["enqueue_end"])
    assert "host_stall_s" not in first
    assert second["host_stall_s"] == pytest.approx(6.568e-4, abs=1e-6)
    assert red["stall_steps"] == 1 and red["linked_steps"] == 2


def _events():
    """One device whose clock runs 100 s behind the host's; three steps."""
    phases, launches, enqueues = [], [], []
    for step, (lo, q_end) in enumerate([(0.0, 0.5), (2.0, 3.2),
                                        (4.0, 4.5)], 1):
        phases += [("serve.assemble", step, lo, lo + 0.3),
                   ("serve.launch", step, lo + 0.3, q_end + 0.1)]
        launches.append(("serve.launch", step, lo + 0.3, q_end + 0.1))
        enqueues.append((10 + step, q_end - 0.05, q_end))
    phases.append(("serve.wait", 2, 3.3, 4.0))
    dev = {"modules": [(11, -99.5, -98.0), (12, -96.8, -96.5),
                       (13, -95.45, -94.0)],
           "ops": [("fusion.1", -99.5, -99.0), ("copy.2", -98.9, -98.0),
                   ("fusion.1", -96.8, -96.5), ("msa.3", -95.4, -94.0)]}
    return {"phases": phases, "launches": launches, "enqueues": enqueues,
            "devices": {"/device:TPU:0": dev}}


def test_host_stall_split_by_phase_and_scope_seconds():
    smap = {"fusion.1": "kv_write", "copy.2": "kv_write",
            "msa.3": "attention"}
    red = tl.reduce(_events(), lambda step: smap)
    sh = red["shifts"]["/device:TPU:0"]
    assert sh["shift_s"] == pytest.approx(-100.0)
    assert sh["spread_s"] == pytest.approx(0.05)
    s1, s2, s3 = red["steps"]
    assert s1["module_host"] == pytest.approx([0.5, 2.0])
    # step 2: idle from 2.0 to 3.2, all of it before its enqueue ended
    assert s2["idle_s"] == pytest.approx(1.2)
    assert s2["host_stall_s"] == pytest.approx(1.2)
    assert s2["stall_phases"] == {
        "serve.assemble": pytest.approx(0.3),
        "serve.launch": pytest.approx(0.9)}
    # step 3: idle 3.5 .. 4.55, the enqueue ended at 4.5
    assert s3["idle_s"] == pytest.approx(1.05)
    assert s3["host_stall_s"] == pytest.approx(1.0)
    assert red["host_stall_s"] == pytest.approx(2.2)
    assert red["stall_steps"] == 2
    assert red["scope_s"] == {"kv_write": pytest.approx(1.7),
                              "attention": pytest.approx(1.4)}


def test_launch_without_one_enqueue_is_not_linked():
    ex = _events()
    ex["enqueues"].append((99, 0.35, 0.4))      # a second enqueue in step 1
    red = tl.reduce(ex)
    assert red["unmatched_launches"] == 1 and red["linked_steps"] == 2
    assert "scope_s" not in red


def test_enqueue_after_its_launch_span_is_linked():
    """The runtime may enqueue a step whose inputs are still being
    computed after its launch span has closed: the one enqueue before the
    next launch is the step's, and the device idle until that enqueue
    ended is idle the host caused."""
    ex = _events()
    # step 2's enqueue moves out of its launch (2.3 .. 3.3) to 3.35 .. 3.4
    ex["enqueues"] = [q if q[0] != 12 else (12, 3.35, 3.4)
                      for q in ex["enqueues"]]
    ex["devices"]["/device:TPU:0"]["modules"][1] = (12, -96.55, -96.5)
    red = tl.reduce(ex)
    assert red["linked_steps"] == 3 and red["unmatched_launches"] == 0
    assert red["deferred_enqueues"] == 1
    s2 = red["steps"][1]
    assert s2["module_host"] == pytest.approx([3.45, 3.5])
    # idle 2.0 .. 3.45; the step was enqueued at 3.4
    assert s2["idle_s"] == pytest.approx(1.45)
    assert s2["host_stall_s"] == pytest.approx(1.4)


def test_reduce_without_devices_reads_nothing():
    ex = _events()
    ex["devices"] = {}
    assert tl.reduce(ex) == {}


def test_instruction_names():
    assert tl.instr_name(
        "%copy-start.1 = (s32[64]{0}, u32[]) copy-start(s32[64]{0} %c)") \
        == "copy-start.1"
    assert tl.instr_name("%msa_fused.1 = bf16[2]{0} custom-call(%a)") == \
        "msa_fused.1"


def test_scope_map_on_compiled_hlo():
    import jax
    import jax.numpy as jnp

    def f(x, w, pool):
        with jax.named_scope("qkv"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("kv_write"):
            pool = pool.at[1].set(h)
        with jax.named_scope("mlp"):
            y = jnp.sin(h) * 2.0
        return y.sum() + x.mean(), pool

    text = jax.jit(f, donate_argnums=2).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8)),
        jnp.ones((4, 8, 8))).compile().as_text()
    own, full = tl.own_scope_map(text), tl.scope_map(text)
    entry = text[text.index("\nENTRY"):]
    kinds = {}
    for line in entry.splitlines():
        m = tl._INSTR.match(line)
        if m:
            kinds[m.group(1)] = re.search(r" ([a-z][\w\-]*)\(",
                                          line[m.end() - 1:]).group(1)
    dots = [n for n, k in kinds.items() if k == "dot"]
    assert dots and all(full[n] == own[n] == "qkv" for n in dots)
    dus = [n for n in kinds if "dynamic-update-slice" in n]
    assert dus and all(full[n] == "kv_write" for n in dus)
    # parameters name no scope of their own; they flow into scoped ops
    params = [n for n, k in kinds.items() if k == "parameter"]
    assert params and all(own[n] == tl.UNSCOPED for n in params)
    assert all(full[n] != tl.UNSCOPED for n in params)
    assert tl.scope_of("jit(f)/jit(main)/attention/pallas_call") == \
        "attention"
    assert tl.scope_of("jit(f)/mlp/qkv/dot_general") == "qkv"
    assert tl.scope_of("jit(f)/add") is None
