import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
sys.path.insert(0, SRC)


def run_multidevice(script: str, n_devices: int = 8, timeout: int = 560):
    """Run a python snippet in a CPU subprocess with a forced host device
    count.

    Needed because jax locks the device count at first init — the main test
    process stays single-device (per the dry-run isolation rule). The child
    is held to the CPU so the suite never reaches for an accelerator."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"multidevice subprocess failed:\nSTDOUT:\n{proc.stdout[-4000:]}"
            f"\nSTDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture
def multidevice():
    return run_multidevice
