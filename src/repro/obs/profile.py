"""Step profiler: the measured side of the wall clock (DESIGN.md §12.1).

`sched.clock` *models* the step time; this module *measures* it. A
`StepProfiler` watches the first ``--profile-steps N`` training steps:

* **step walls** — the launcher already brackets every step with
  ``jax.block_until_ready`` (PR 6's honest-timing fix), so the per-step
  wall it hands to `record_step` is a real device-synced measurement,
  not dispatch latency. The profiler keeps the whole window and reports
  mean/min/max/p50 (min ≈ the no-jitter compute+comm floor the
  calibration fit leans on).
* **host phases** — `phase(name, spans)` is the launcher's one context
  per host-side phase (``data`` / ``dispatch`` / ``sync`` / ``eval``):
  it opens the phase's `tracing.host_span` when spans are on and, while
  the window is open, accumulates its wall time, so a profile event and
  a captured profiler trace time and name each phase at one boundary.
* **device phases** — with spans on, the compiled step's optimized HLO
  carries ``repro.obs/<phase>`` scope names in op metadata;
  `launch.hlo_analysis.scope_costs` turns that into per-phase op counts
  and result bytes (lookahead / field / exchange / apply), a device-side
  cost attribution that needs no hardware profiler and runs on host CI.
  Nested scopes (``pack`` and ``compress`` inside ``exchange``) count
  under the outer one.
* **trace capture** — an optional ``jax.profiler.trace`` directory
  brackets the window for TensorBoard-grade attribution on real
  hardware.

The window closes after N recorded steps and `emit` writes ONE
versioned ``profile`` event (schema v2) into the run sink. Everything
here is host-side: profiling on/off cannot perturb the compiled step,
which is why `Observability.profile` stays outside `short_hash()` and
the bit-exactness tests pin the HLO equal either way.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from .tracing import DEVICE_PHASES, HOST_PHASES, PREFIX, host_span

DEFAULT_WINDOW = 32


def _stats(xs: List[float]) -> Dict[str, float]:
    ordered = sorted(xs)
    return {
        "mean": sum(xs) / len(xs),
        "min": ordered[0],
        "max": ordered[-1],
        "p50": ordered[len(ordered) // 2],
        "n": len(xs),
    }


def overlap_ratio(walls_on, walls_off, exchange_s: Optional[float] = None
                  ) -> Dict[str, float]:
    """Measured overlap from a paired A/B run: the same strategy timed
    with ``exchange.overlap`` on and off (DESIGN.md §13). ``hidden_s``
    is the step wall the split-phase lowering removed (p50-off minus
    p50-on, clamped at 0 — medians so one compile/jitter outlier cannot
    fake an overlap win). With ``exchange_s`` — the exposed exchange
    wall of the *off* run, e.g. ``t_off - t_compute`` from a
    calibration fit — the result also carries ``hidden_frac`` (the
    fraction of the exchange the scheduler hid; the CI smoke's gate)
    and ``exposed_s`` (what still sits on the critical path).

    Inputs are either full per-step wall lists (profile windows) or
    scalar means (timing events); pure function, no profiler state."""
    walls_on = [float(walls_on)] if isinstance(
        walls_on, (int, float)) else [float(w) for w in walls_on]
    walls_off = [float(walls_off)] if isinstance(
        walls_off, (int, float)) else [float(w) for w in walls_off]
    if not walls_on or not walls_off:
        raise ValueError("overlap_ratio: need at least one step wall "
                         "on each side of the A/B pair")
    t_on = _stats(walls_on)["p50"]
    t_off = _stats(walls_off)["p50"]
    hidden = max(t_off - t_on, 0.0)
    out = {"t_on_s": t_on, "t_off_s": t_off, "hidden_s": hidden}
    if exchange_s is not None and exchange_s > 0:
        out["exchange_s"] = float(exchange_s)
        out["exposed_s"] = max(float(exchange_s) - hidden, 0.0)
        out["hidden_frac"] = min(hidden / float(exchange_s), 1.0)
    return out


class StepProfiler:
    """Collects one profiled window of a training run.

    Life cycle: the launcher calls ``phase(name, spans)`` around its host
    phases and ``record_step(step, step_s, exchanged)`` once per step;
    after ``window`` recorded steps the profiler is `done` and further
    calls are no-ops. `emit(sink, hlo_text=...)` writes the window as a
    single ``profile`` event."""

    def __init__(self, window: int = DEFAULT_WINDOW, trace_dir: str = ""):
        if window < 1:
            raise ValueError(f"profile window must be >= 1, got {window}")
        self.window = int(window)
        self.trace_dir = trace_dir
        self.step_walls: List[float] = []
        self.first_step: Optional[int] = None
        self.exchange_steps = 0
        self.phase_s: Dict[str, List[float]] = {}   # name -> [total_s, n]
        self._tracing = False
        self._emitted = False

    # ------------------------------------------------------------------ #
    @property
    def active(self) -> bool:
        return len(self.step_walls) < self.window and not self._emitted

    @property
    def done(self) -> bool:
        return not self.active

    def phase(self, name: str, spans: bool = False):
        """A host phase (canonical names: tracing.HOST_PHASES): its
        TraceAnnotation when `spans`, and its wall time while the window
        is open."""
        if not self.active:
            return host_span(name, spans)
        return self._timed(name, spans)

    @contextmanager
    def _timed(self, name: str, spans: bool):
        with host_span(name, spans):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                rec = self.phase_s.setdefault(name, [0.0, 0])
                rec[0] += time.perf_counter() - t0
                rec[1] += 1

    def record_step(self, step: int, step_s: float,
                    exchanged: bool = True) -> None:
        """One synced per-step wall time. Starts the optional
        jax.profiler trace on the first recorded step and stops it when
        the window fills."""
        if not self.active:
            return
        if self.first_step is None:
            self.first_step = int(step)
            if self.trace_dir:
                import jax
                jax.profiler.start_trace(self.trace_dir)
                self._tracing = True
        self.step_walls.append(float(step_s))
        self.exchange_steps += bool(exchanged)
        if len(self.step_walls) >= self.window:
            self._stop_trace()

    def _stop_trace(self) -> None:
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False

    # ------------------------------------------------------------------ #
    def device_phase_costs(self, hlo_text: str) -> Dict[str, dict]:
        """Per-phase device cost attribution from the compiled step's
        optimized HLO — op counts + result bytes per `repro.obs/` scope
        (spans must have been on when the step was traced, or the
        metadata is absent and this returns {})."""
        from repro.launch.hlo_analysis import scope_costs
        known = set(DEVICE_PHASES)
        return {k: v for k, v in scope_costs(hlo_text, PREFIX).items()
                if k in known}

    def summary(self, hlo_text: str = "") -> Optional[dict]:
        """The window as a `profile` event payload, or None if no step
        was recorded."""
        if not self.step_walls:
            return None
        out = {
            "step0": self.first_step,
            "n_steps": len(self.step_walls),
            "exchange_steps": self.exchange_steps,
            "step_s": _stats(self.step_walls),
            "step_walls_s": [round(s, 6) for s in self.step_walls],
            "host_phases": {
                name: {"total_s": round(tot, 6), "n": n}
                for name, (tot, n) in sorted(self.phase_s.items())
            },
        }
        if hlo_text:
            dev = self.device_phase_costs(hlo_text)
            if dev:
                out["device_phases"] = dev
        if self.trace_dir:
            out["trace_dir"] = self.trace_dir
        return out

    def emit(self, sink, hlo_text: str = "") -> Optional[dict]:
        """Close the window (stopping any live trace) and write it as
        one schema-v2 ``profile`` event. Idempotent."""
        self._stop_trace()
        if self._emitted:
            return None
        payload = self.summary(hlo_text)
        if payload is None:
            return None
        self._emitted = True
        return sink.emit("profile", **payload)


class NullStepProfiler:
    """The off switch: same surface, every call a no-op — so the
    launcher's hot loop carries no conditionals."""

    window = 0
    active = False
    done = True
    step_walls: List[float] = []

    def phase(self, name: str, spans: bool = False):
        return host_span(name, spans)

    def record_step(self, step: int, step_s: float,
                    exchanged: bool = True) -> None:
        pass

    def device_phase_costs(self, hlo_text: str) -> Dict[str, dict]:
        return {}

    def summary(self, hlo_text: str = "") -> Optional[dict]:
        return None

    def emit(self, sink, hlo_text: str = "") -> Optional[dict]:
        return None


def make_profiler(enabled: bool, window: int = 0, trace_dir: str = ""):
    """Launcher factory: `StepProfiler` when profiling is on (via the
    Observability.profile strategy field or an explicit --profile-steps),
    else the no-op `NullStepProfiler`."""
    if not enabled:
        return NullStepProfiler()
    return StepProfiler(window=window or DEFAULT_WINDOW,
                        trace_dir=trace_dir)


# re-exported so profile consumers need not import tracing for the names
__all__ = [
    "DEFAULT_WINDOW",
    "DEVICE_PHASES",
    "HOST_PHASES",
    "NullStepProfiler",
    "StepProfiler",
    "make_profiler",
    "overlap_ratio",
]
