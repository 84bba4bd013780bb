"""Shared helpers of the benchmark's CPU tests: the benchmark's modules on
the path, cells cut to a CPU-sized smoke shape, and the four-chip mixes
whose cells wait for chip time.

The smoke shape keeps every cell's structure (workers, strategy flags,
exchange, limits) and shrinks only the model, to its family's `smoke` size,
and the batch, to 8 rows per worker.
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "bench")
SRC = os.path.join(REPO, "src")
for p in (BENCH, SRC):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_TRAFFIC = {"batch_per_worker": 8, "pool_batches": 4}
SEED = 2**31 + 17

# Four-chip mixes kept in bench/traffic/ whose cells are not in the manifest
# yet: each runs under dcgan32's configuration and the limits of its
# one-chip cell, so that their reference paths stay tested.
WAITING = {"dcgan32.q8.b64.w4": "q8.b64.w4",
           "dcgan32.exact.b64.w4": "exact.b64.w4"}
WAITING_BASE = "dcgan32.q8.b64"


def resolve(name: str) -> dict:
    """A manifest cell as `run.resolve` finds it, or a waiting mix."""
    import run

    if name not in WAITING:
        return run.resolve(REPO, name)
    spec = run.resolve(REPO, WAITING_BASE)
    spec["traffic"] = run.load_json(
        os.path.join(BENCH, "traffic", WAITING[name] + ".json"))
    spec["cell"] = dict(spec["cell"], name=name, traffic=WAITING[name],
                        chips=spec["traffic"]["workers"])
    return spec


def smoke(spec: dict) -> dict:
    """`spec` (from `run.resolve`) cut to the smoke shape, in place."""
    spec["config"] = spec["family"].smoke(spec["config"])
    spec["traffic"].update(SMOKE_TRAFFIC)
    return spec


def config(name: str):
    """A configuration file of `bench/configs/` and its family module, as
    `run.resolve` finds them."""
    import run

    path = os.path.join(BENCH, "configs", name + ".json")
    cfg = run.load_json(path)
    return cfg, run.load_family(REPO, cfg, path)


def run_subprocess(code: str, n_devices: int, timeout: int = 600) -> str:
    """Run python `code` on the CPU with `n_devices` forced host devices;
    return its standard output, or fail with both streams. Programs that
    compile alike within the run share a compile cache of its own."""
    with tempfile.TemporaryDirectory(prefix="bench-test-cache-") as cache:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count="
                   f"{n_devices}",
                   PYTHONPATH=os.pathsep.join([HERE, BENCH, SRC]),
                   JAX_COMPILATION_CACHE_DIR=cache,
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True,
                              timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{proc.stdout[-4000:]}\n"
                             f"{proc.stderr[-4000:]}")
    return proc.stdout
