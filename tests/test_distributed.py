"""Multi-device checks in a subprocess (8 fake CPU devices), so the rest
of the suite keeps the default single device."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.slow
def test_distributed_checks():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "dist_checks.py")],
        capture_output=True, text=True, timeout=900, env=env)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "distributed checks failed"
    assert "ALL DISTRIBUTED CHECKS PASSED" in proc.stdout
