"""The scheduler's ONE time source: wall clock in production, a stepped
SimClock in the simulator and the tests.

Every piece of scheduling arithmetic (queue wait, reservation age, event
ordering in the simulator) reads `clock.time()` from an injected Clock —
never `time.time()` directly. That keeps the fleet scheduler fully
deterministic under simulation (`scheduler/sim.py` replays a seeded
workload through SimClock) and is enforced by
scripts/lint_telemetry.py: `time.time(`/`time.monotonic(` are forbidden
inside polyaxon_tpu/scheduler/ outside this module.

Timestamping (status conditions, metric rows in store/local.py) is NOT
scheduling math and keeps using time.time() — those are labels, not
quantities the scheduler computes with.
"""

from __future__ import annotations

import time as _time


class Clock:
    """Wall clock (the default). Subclass or swap for SimClock in tests."""

    def time(self) -> float:
        return _time.time()


class SimClock(Clock):
    """Manually advanced clock for deterministic scheduling simulation."""

    def __init__(self, start: float = 0.0):
        self._now = float(start)

    def time(self) -> float:
        return self._now

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError(f"cannot advance the clock backwards ({dt})")
        self._now += dt
        return self._now

    def advance_to(self, t: float) -> float:
        if t < self._now:
            raise ValueError(
                f"cannot rewind SimClock from {self._now} to {t}"
            )
        self._now = float(t)
        return self._now


WALL = Clock()
