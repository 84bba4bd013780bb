"""repro.obs — on-device telemetry, structured run sinks, phase tracing
(DESIGN.md §11).

Three parts:

- `metrics` — a jit-static `MetricSpec` lattice ("off" ⊂ "wire" ⊂
  "full") collecting per-bucket gradient moments, empirical δ (from the
  already-materialized EF residual), EF norms and staleness histograms
  inside the jitted step, into fixed-shape buffers. ``off`` is
  contractually bit-identical to a build without this package.
- `sink` — a versioned JSONL event schema keyed by
  `Strategy.short_hash()`, with stdout / file / null backends
  (``--obs-sink`` on launch.train and benchmarks.run).
- `report` — ``python -m repro.obs report run.jsonl`` renders per-phase
  timing, the δ̂-vs-assumed-δ gap, bytes-vs-budget utilization and
  EF-residual growth from a sink file.

PR 7 adds the measured-vs-modeled layer (DESIGN.md §12):

- `profile` — a host-side `StepProfiler` turning the launcher's synced
  step walls + host/device phase attribution into schema-v2 ``profile``
  events (``--obs-profile`` / ``--profile-steps``).
- `hlo` — structural verification of the compiled step: collective
  ops/bytes from optimized HLO vs the `CommLedger`'s analytic bytes,
  plus schedule-shaped structure assertions.
- `calibrate` — ``python -m repro.obs calibrate run.jsonl`` fits
  `sched.clock` LinkModel + compute constants from recorded events and
  gates on modeled-vs-measured drift.
"""
from .metrics import (  # noqa: F401
    METRIC_SPECS,
    Collector,
    MetricSpec,
    NullCollector,
    ef_norms_sq,
    finalize,
    metric_keys,
    staleness_hist,
)
from .sink import (  # noqa: F401
    EVENT_KINDS,
    SCHEMA_VERSION,
    JsonlFileSink,
    NullSink,
    SchemaError,
    Sink,
    StdoutSink,
    TeeSink,
    make_sink,
    read_events,
    validate_event,
)
from .profile import (  # noqa: F401
    DEFAULT_WINDOW,
    NullStepProfiler,
    StepProfiler,
    make_profiler,
)
from .tracing import device_span, host_span, step_span  # noqa: F401
