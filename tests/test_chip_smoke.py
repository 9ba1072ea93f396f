"""``chip_smoke.py`` on the CPU: it refuses to run there, and its phases
run end to end at a reduced size (kernels in interpret mode), the mesh
phase on four virtual devices."""
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402


def _cpu_env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"),
               **extra)
    return env


def test_refuses_without_tpu():
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=_cpu_env())
    assert proc.returncode != 0
    assert "found platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_serve_and_kernel_phases_reduced(monkeypatch):
    monkeypatch.setattr(chip_smoke, "MAX_LEN", 128)
    monkeypatch.setattr(chip_smoke, "KERNEL_SEQ", 256)
    cfg = get_config(chip_smoke.ARCH)
    chip_smoke.serve(["--arch", chip_smoke.ARCH, "--reduced"], 0,
                     cfg.reduced().vocab_size)
    found = chip_smoke.kernels(cfg, 0)
    assert set(found) == {"flash_attention", "decode_attention"}


def test_mesh_phase_on_four_virtual_devices():
    code = ("import chip_smoke\n"
            "from repro.configs import get_config\n"
            "cfg = get_config(chip_smoke.ARCH).reduced()\n"
            "chip_smoke.mesh_phase(cfg, 0, batch=8, seq=64)\n"
            "print('MESH PHASE OK')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0 and "MESH PHASE OK" in proc.stdout
    assert "[check] sharded and single-device losses agree" in proc.stdout
