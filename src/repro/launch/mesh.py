"""Production meshes + the XLA overlap-flag helper. Functions, not
module constants — importing this module never touches jax device
state."""
from __future__ import annotations

import os
import warnings

import jax
from jax.sharding import AxisType

# Per-platform XLA flags that let the compiler overlap the split-phase
# exchange (DESIGN.md §13) with field compute. Only flags verified to
# exist in the pinned jaxlib are listed — XLA aborts the process on an
# unknown --xla_* flag, so this table is allow-list, not wish-list.
#
#  gpu : async collectives are on by default; the latency-hiding
#        scheduler + a high-priority async stream make the -start/-done
#        pairs actually span the field compute.
#  cpu : XLA:CPU has NO async-collective lowering (collectives stay
#        sync thunks); the thunk runtime + concurrency-optimized
#        scheduler are the closest knobs — they let independent thunks
#        (which the delayed exchange's collectives are, see
#        obs.hlo.exchange_field_independence) run on the thread pool.
#  tpu : overlap is default XLA:TPU behavior; nothing to set.
OVERLAP_XLA_FLAGS = {
    "gpu": (
        "--xla_gpu_enable_latency_hiding_scheduler=true",
        "--xla_gpu_enable_highest_priority_async_stream=true",
    ),
    "cpu": (
        "--xla_cpu_use_thunk_runtime=true",
        "--xla_cpu_enable_concurrency_optimized_scheduler=true",
    ),
    "tpu": (),
}


def enable_overlap_flags(platform: str = "cpu") -> tuple:
    """Append the platform's overlap flags to ``XLA_FLAGS`` (the
    `set_platform` idiom: call BEFORE the first jax operation — XLA
    parses the env var once at backend init). Idempotent; returns the
    flags added. A no-op with a warning if the jax backend is already
    initialized, since the flags could no longer take effect."""
    flags = OVERLAP_XLA_FLAGS.get(platform)
    if flags is None:
        raise ValueError(
            f"unknown platform {platform!r}; have "
            f"{sorted(OVERLAP_XLA_FLAGS)}")
    monitoring = getattr(jax, "_src", None)
    backends = getattr(getattr(monitoring, "xla_bridge", None),
                       "_backends", None)
    if backends:
        warnings.warn(
            "enable_overlap_flags called after jax backend init — "
            "XLA_FLAGS already parsed; set the flags before the first "
            "jax call (or in the launch environment) for them to apply",
            stacklevel=2)
        return ()
    current = os.environ.get("XLA_FLAGS", "")
    added = tuple(f for f in flags if f not in current)
    if added:
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (current,) + added))
    return added


def make_production_mesh(*, multi_pod: bool = False, override: str = ""):
    """Single pod: (16,16) ('data','model') = 256 chips (v5e pod).
    Multi pod:  (2,16,16) ('pod','data','model') = 512 chips.
    `override` ("64,4" / "2,32,8") re-splits the same chips across the
    data/model axes — a §Perf sharding-scheme knob."""
    if override:
        shape = tuple(int(x) for x in override.split(","))
        assert len(shape) in (2, 3)
        axes = (("pod",) if len(shape) == 3 else ()) + ("data", "model")
        expected = 512 if multi_pod else 256
        assert (len(shape) == 3) == multi_pod
        total = 1
        for x in shape:
            total *= x
        assert total == expected, (shape, expected)
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for unit tests (requires xla_force_host_platform_device_count)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def worker_axes_for(layout: str, multi_pod: bool):
    """DQGAN worker axes by parameter layout (DESIGN.md §4):
    dp   -> every data-parallel rank is a paper-worker;
    fsdp -> each pod is a paper-worker (params sharded inside)."""
    if layout == "dp":
        return ("pod", "data") if multi_pod else ("data",)
    if layout == "fsdp":
        return ("pod",) if multi_pod else ()
    raise ValueError(layout)
