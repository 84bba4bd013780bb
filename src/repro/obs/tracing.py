"""Phase tracing: named spans for the jax profiler (DESIGN.md §11).

Three span flavors, all gated on `Observability.spans` so the default
build leaves the step graph and the host loop untouched:

- `step_span` — `jax.profiler.StepTraceAnnotation` around one launcher
  loop iteration, from which the profiler draws its Steps line.
- `host_span` — `jax.profiler.TraceAnnotation` around host-side phases
  (``data`` / ``dispatch`` / ``sync`` / ``eval``): ``dispatch`` is the
  call that enqueues the jitted step, ``sync`` the wait for its
  metrics. The launcher opens them through `StepProfiler.phase`, which
  also keeps their wall totals.
- `device_span` — `jax.named_scope` around in-jit phases, which names the
  HLO ops so profiler traces and HLO dumps attribute device time to the
  phase: ``lookahead`` (the OMD extrapolation to w_{t-1/2}), ``field``,
  ``exchange`` and, inside it, ``pack`` (bucket packing and unpacking)
  and ``compress`` (error feedback, the quantizer and its decode, never a
  collective), then ``apply``. Disabled spans return a `nullcontext`,
  keeping the traced graph byte-identical; enabled ones change only op
  metadata.

Span names are namespaced ``repro.obs/<phase>`` so they are greppable in
profiles next to user scopes.
"""
from __future__ import annotations

from contextlib import nullcontext

import jax

PREFIX = "repro.obs/"

# the canonical phase names (DESIGN.md §11 span naming)
STEP = "train"
HOST_PHASES = ("data", "dispatch", "sync", "eval")
DEVICE_PHASES = ("lookahead", "field", "exchange", "pack", "compress",
                 "apply")


def step_span(step: int, enabled: bool = True):
    """StepTraceAnnotation context for one training step (no-op when
    off)."""
    if not enabled:
        return nullcontext()
    return jax.profiler.StepTraceAnnotation(PREFIX + STEP,
                                            step_num=int(step))


def host_span(name: str, enabled: bool = True):
    """TraceAnnotation context for a host-side phase (no-op when off)."""
    if not enabled:
        return nullcontext()
    return jax.profiler.TraceAnnotation(PREFIX + name)


def device_span(name: str, enabled: bool = True):
    """named_scope context for an in-jit phase (no-op when off)."""
    if not enabled:
        return nullcontext()
    return jax.named_scope(PREFIX + name)
