import os
import subprocess
import sys
import textwrap

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-device subprocess tests (several minutes)")


def assert_drained(srv) -> None:
    """Shared drain audit: after a serve() run completes, the pool must
    hold zero leaked references, zero queued copies, and a consistent
    block-table/host-tier picture (BlockManager.check_invariants)."""
    bm = srv.bm
    bm.check_invariants()
    leaked = [i for i, b in enumerate(bm.blocks) if b.ref_count > 0]
    assert not leaked, f"leaked block refs at drain: {leaked}"
    assert not bm.pending_copies, \
        f"pending COW copies at drain: {bm.pending_copies}"
    assert not srv.sched.waiting and not srv.sched.running


def run_devices(code: str, n_devices: int) -> str:
    """Run ``code`` in a subprocess with ``n_devices`` forced CPU host
    devices (jax locks the device count at first init, and the main
    pytest process must keep seeing 1 CPU device for the smoke tests)."""
    env = dict(os.environ)
    # host devices only: never compete with a parent for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout
