"""Run the 8-device distributed executor checks in a subprocess.

The subprocess sets ``--xla_force_host_platform_device_count=8`` before
importing jax; the main pytest process keeps its single-device view.  The
children run on the CPU backend (``JAX_PLATFORMS=cpu``) so they never
wait on an accelerator the parent process holds.
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)


@pytest.mark.slow
def test_distributed_executors():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_distributed_checks.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL DISTRIBUTED CHECKS PASSED" in proc.stdout


@pytest.mark.slow
def test_distributed_models():
    """Sharded-vs-unsharded train step, GPipe, elastic re-mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_distributed_checks2.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL MODEL DISTRIBUTED CHECKS PASSED" in proc.stdout
