"""Distributed-core correctness: runs repro.core.selfcheck in a
subprocess with 8 forced host devices (the main pytest process must keep
seeing exactly 1 device, so collectives are exercised out-of-process)."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_selfcheck(name: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.core.selfcheck", name],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"selfcheck {name} failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


pytestmark = pytest.mark.slow


@pytest.mark.parametrize("check", ["order", "phase1_z", "mm3d", "tri_inv",
                                   "rec_trsm",
                                   "it_inv_trsm", "doubling", "cholesky",
                                   "lu", "session", "bank", "overlap",
                                   "cyclic_serve", "residual_dense"])
def test_selfcheck(check):
    out = run_selfcheck(check)
    assert "FAIL" not in out
    assert "0 failures" in out
