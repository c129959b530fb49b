"""Unified telemetry: ONE metrics pipeline + span tracing for every layer.

The paper's platform treats observability as a first-class subsystem
(Traceml-style monitors, SURVEY.md §2); before this package the
reproduction had ad-hoc fragments — the trainer hand-rolled walltime
math, serving counted compiles in an instance attribute, the system
monitor wrote straight to the store. Everything now flows through:

- `MetricsRegistry` — thread-safe counters / gauges / fixed-bucket
  histograms with p50/p95/p99 summaries, rendered as a snapshot dict
  (`/statsz`) or Prometheus text exposition (`/metricsz`). Both surfaces
  read the SAME registry, so they cannot drift.
- `SpanTracer` — context-manager spans with parent/child nesting,
  exported in batches as JSONL into the run's artifacts dir next to the
  jax.profiler trace, and each one a `polyaxon.*` TraceAnnotation inside
  any such trace, on the device events' clock.
- `RequestTrace`/`TraceRing` (tracing.py) — the serving-side trace
  builder: explicit-parent spans that survive thread hops, plus a
  tail-sampling ring that always keeps errors/sheds/deadline-exceeded
  and the slowest tail. `/tracez` reads the ring.
- `SLOEngine`/`FlightRecorder` (slo.py) — multi-window burn rates over
  registry counters/histograms, `slo_burn_rate`/`slo_breached` gauges,
  and the breach-triggered post-mortem bundle under `<outputs>/debug/`.
- `compiles` — the one listener on JAX's compile events: `xla.programs`,
  `xla.traces`, `xla.lowerings` and their seconds, for `/statsz` `xla`
  and the trainer's log.
- `quantile` — the one exact-percentile implementation.
- `now()` — the sanctioned monotonic clock for metrics timing. No other
  module in the package may call `time.perf_counter()` directly
  (enforced by scripts/lint_telemetry.py and tests/test_telemetry.py).

Process-global `get_registry()`/`get_tracer()` serve cross-cutting
layers (run-store transitions, retry/backoff, chaos injections);
components that live one-per-process in production (Trainer,
ModelServer) default to a private registry so tests stay isolated.

Import cost is stdlib-only — safe to import from anywhere in the
package without cycles.
"""

from .detect import (
    DEFAULT_SERVING_RULES,
    RegressionRule,
    RegressionSentinel,
    build_rules,
)
from .federate import (
    PromSample,
    PromSnapshot,
    federate,
    parse_prometheus_text,
    queue_wait_delta_ms,
)
from .history import (
    HistorySampler,
    HistoryStore,
    queryz_payload,
)
from .registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    now,
    process_age,
)
from . import compiles
from .slo import (
    AvailabilityObjective,
    FlightRecorder,
    LatencyObjective,
    SLOEngine,
    build_objectives,
)
from .spans import SpanTracer, get_tracer
from .stats import (
    mfu,
    quantile,
    required_train_step_flops,
)
from .tracing import RequestTrace, TraceRing, new_trace_id, tracez_payload

__all__ = [
    "AvailabilityObjective",
    "Counter",
    "DEFAULT_SERVING_RULES",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "HistorySampler",
    "HistoryStore",
    "LatencyObjective",
    "MetricsRegistry",
    "RegressionRule",
    "RegressionSentinel",
    "PromSample",
    "PromSnapshot",
    "RequestTrace",
    "SLOEngine",
    "SpanTracer",
    "TraceRing",
    "build_objectives",
    "build_rules",
    "federate",
    "get_registry",
    "get_tracer",
    "new_trace_id",
    "parse_prometheus_text",
    "process_age",
    "queue_wait_delta_ms",
    "queryz_payload",
    "tracez_payload",
    "mfu",
    "now",
    "quantile",
    "required_train_step_flops",
]
