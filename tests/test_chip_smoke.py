"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself refuses to run without a TPU; these tests call its
phase functions directly, with the reduced model and the Pallas kernels
in interpret mode, so a wrong path, argument or check shows up here
before it costs chip time.  The four-chip phase runs on four virtual CPU
devices in a subprocess.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_window_sync_phase_tiny(tmp_path):
    out = chip_smoke.window_sync_phase(str(tmp_path), nbytes=4 << 20,
                                       shard_pages=(37, 21, 11),
                                       impl="interpret")
    assert out["impl"] == "interpret" and out["bit_exact"]
    assert out["dirty_pages"] == int(1024 * 0.08)


@pytest.mark.parametrize("budget", [None, 200_000])
def test_train_phase_tiny(tmp_path, budget):
    """Offload training with async checkpoints, then a bit-exact restore --
    with the optimizer state all in memory, and spilled past a budget."""
    out = chip_smoke.train_phase(str(tmp_path), budget=budget, smoke=True,
                                 batch=2, seq=16)
    assert out["restore_bit_exact"] and out["checkpoints"] == 2
    assert len(out["step_s"]) == 4


def test_device_phase_refuses_cpu(tmp_path):
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.device_phase(str(tmp_path))


def test_script_fails_without_a_tpu(tmp_path):
    """Run as a user would, on a host without a TPU: no result line and
    exit != 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                          "--run-dir", str(tmp_path / "run")],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


_MESH = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[2])
import chip_smoke
out = chip_smoke.mesh_train_phase(sys.argv[1], smoke=True, batch=4, seq=16)
assert out["resume_bit_exact"] and out["mesh"] == {"data": 2, "model": 2}
print("OK")
"""


def test_mesh_train_phase_tiny(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO + "/src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _MESH, str(tmp_path), REPO],
                         capture_output=True, text=True, timeout=400,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("OK")
