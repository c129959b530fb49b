"""Scenario engine (ISSUE 16): trace-driven traffic replay, chaos as
scenario ingredients, and a discrete-event serving twin.

Three layers, composed by `registry.Scenario`:

* `traces` — seeded, versioned JSONL traffic traces plus the generator
  zoo (diurnal curves, correlated bursts, heavy-tailed lengths, tenant
  mixes, adversarial floods, shared-prefix cohorts, mid-stream client
  disconnects).
* `driver` — open-loop HTTP replayer against the real router+replicas
  stack with a per-request outcome ledger and a hard zero-hung-requests
  invariant at drain.
* `twin` — a discrete-event serving twin on `scheduler.clock.SimClock`
  driven by measured per-phase costs, so million-user multi-hour soaks
  run in seconds on CI.

This package is deliberately free of raw clocks (`time.*`, `datetime.*`
— lint_telemetry rule 13): simulated time comes from SimClock, measured
time from `telemetry.now()`, and delays from `threading.Event.wait`.
"""

from .driver import Outcome, ReplayReport, replay
from .registry import SCENARIOS, Assertions, Scenario, run_scenario
from .traces import (
    TRACE_VERSION,
    GENERATORS,
    TraceRequest,
    body_for,
    generate,
    prompt_tokens,
    read_trace,
    write_trace,
)
from .twin import PhaseCosts, ServingTwin, TwinConfig

__all__ = [
    "TRACE_VERSION",
    "GENERATORS",
    "SCENARIOS",
    "Assertions",
    "Outcome",
    "PhaseCosts",
    "ReplayReport",
    "Scenario",
    "ServingTwin",
    "TraceRequest",
    "TwinConfig",
    "body_for",
    "generate",
    "prompt_tokens",
    "read_trace",
    "replay",
    "run_scenario",
    "write_trace",
]
