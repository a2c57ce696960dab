"""Multi-device (8 placeholder CPU devices) integration tests.

The worker runs in a subprocess because the device count is locked at
first jax init: the rest of the suite must keep seeing 1 device.
"""
import os
import subprocess
import sys

import pytest


@pytest.mark.timeout(600)
def test_distributed_pipeline_matches_single_device():
    worker = os.path.join(os.path.dirname(__file__),
                          "_distributed_worker.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, worker], env=env, capture_output=True, text=True,
        timeout=570)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count("ok:") == 7, out.stdout
