"""Lightweight span tracing: context-manager spans with parent/child
nesting, a bounded in-memory ring, and streaming JSONL export.

A span is one timed region; nesting is tracked per-thread (a span opened
while another is active records it as parent), so trainer code like

    with tracer.span("step", step=i):
        with tracer.span("data_wait"):
            batch = feed.get()
        with tracer.span("compute"):
            ...

produces a two-level tree per step. Completed spans go to `spans.jsonl`
(one JSON object per line) when the tracer has a path — the trainer
points it into the run's artifacts dir next to the jax.profiler trace, so
both timing views travel with the run. Records are buffered and written
in batches: at `flush()` (the trainer's log points), at `close()`, and
when 256 of them have gathered — never one file open per span.
Instant `event()` records share the file with `"kind": "event"`.

Every span is also a `jax.profiler.TraceAnnotation` named
`<prefix><name>` (`polyaxon.train.compute`, `polyaxon.step.dispatch`), so
in any profiler capture the program's spans lie on the host plane on the
same clock as the device's `XLA Ops` and `XLA Modules` lines. With no
capture running an annotation costs one atomic load. This module never
imports jax: a process that has not imported it cannot be profiling, and
its spans skip the annotation.

A span can also be written once it is over (`SpanTracer.record_span`: name,
wall-clock start, duration, parent, attrs), for a region that no context
manager may stand around: the Trainer reads the clock before and after the
calls that trace and compile its step and writes `build` and `first_step`
with their children from those instants when the executable exists. Such
a span reaches the ring and `spans.jsonl` like any other and is NOT an
annotation (one cannot be opened in the past): in a capture the
`polyaxon.compile` marks of `compiles.py` lie at each compile's end.

Export schema per line:
    {"kind": "span"|"event", "name": str, "span_id": int,
     "parent_id": int|null, "ts": float (unix), "dur_s": float,
     "attrs": {...}}

Durations come from the monotonic metrics clock (registry.now); `ts` is
wall-clock so lines are correlatable with logs and store events. The
annotation opens just before the span's clock is read and closes just
after, so the two durations agree to a fraction of a microsecond.

Thread-local nesting is the right model ONLY for single-thread loops.
A serving request hops threads (HTTP handler → coalescer queue → decode
worker), so its trace is built with the explicit-parent
`RequestTrace`/`TraceRing` companions in tracing.py (re-exported here)
— same clock, no thread-local state, tail-sampled retention.
"""

from __future__ import annotations

import itertools
import json
import sys
import threading
import time
from collections import deque
from pathlib import Path
from typing import Optional

from .registry import now
from .tracing import RequestTrace, TraceRing, new_trace_id  # noqa: F401

__all__ = [
    "RequestTrace",
    "SpanTracer",
    "TraceRing",
    "get_tracer",
    "new_trace_id",
]

_BATCH = 256  # records that gather before `spans.jsonl` is written unasked


def _annotation_cls():
    """`jax.profiler.TraceAnnotation` where this process has imported jax,
    else None. Looked up, never imported: telemetry stays stdlib-only."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


class _SpanHandle:
    """Context manager for one in-flight span; attrs may be added while
    open via `set(...)`."""

    def __init__(self, tracer: "SpanTracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = next(tracer._ids)
        self.parent_id: Optional[int] = None
        self.ts = 0.0
        self._t0 = 0.0
        self._ann = None
        self.dur_s: Optional[float] = None

    def set(self, **attrs) -> "_SpanHandle":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_SpanHandle":
        stack = self.tracer._stack()
        self.parent_id = stack[-1].span_id if stack else None
        stack.append(self)
        self.ts = time.time()
        ann = _annotation_cls()
        if ann is not None:
            self._ann = ann(self.tracer.prefix + self.name)
            self._ann.__enter__()
        self._t0 = now()
        return self

    def __exit__(self, *exc) -> None:
        self.dur_s = now() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # defensive: mis-nested exit
            stack.remove(self)
        self.tracer._record(
            {
                "kind": "span",
                "name": self.name,
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                "ts": self.ts,
                "dur_s": self.dur_s,
                "attrs": self.attrs,
            }
        )


class SpanTracer:
    """Per-component tracer. `path=None` keeps spans only in the memory
    ring (`recent()`); with a path every completed record also goes to
    the JSONL file, `_BATCH` records at a time and at `flush()`/`close()`
    (parent dirs are made at the first record, so a path that cannot be
    written shows at once). Export failures are swallowed after the
    first — tracing is advisory and must never fail the traced work.
    `prefix` + a span's name is its name in a profiler capture."""

    def __init__(
        self,
        path: Optional[str] = None,
        capacity: int = 512,
        prefix: str = "polyaxon.",
    ):
        self._path = Path(path) if path else None
        self.prefix = prefix
        self._ring: deque = deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._write_lock = threading.Lock()
        self._unwritten: list[dict] = []
        self._dir_made = False
        self._broken = False

    @property
    def path(self) -> Optional[Path]:
        return self._path

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def span(self, name: str, **attrs) -> _SpanHandle:
        return _SpanHandle(self, name, attrs)

    def record_span(
        self, name: str, ts: float, dur_s: float,
        parent_id: Optional[int] = None, **attrs,
    ) -> int:
        """A span that is already over: the record `span()` writes at its
        exit, from a wall-clock start and a duration read elsewhere. Returns
        its id, for its children's `parent_id`. No annotation, and the
        calling thread's open spans are left alone."""
        span_id = next(self._ids)
        self._record(
            {
                "kind": "span",
                "name": name,
                "span_id": span_id,
                "parent_id": parent_id,
                "ts": ts,
                "dur_s": dur_s,
                "attrs": attrs,
            }
        )
        return span_id

    def event(self, name: str, **attrs) -> None:
        """Instant (zero-duration) record; a mark in a profiler capture."""
        ann = _annotation_cls()
        if ann is not None:
            with ann(self.prefix + name, **attrs):
                pass
        stack = self._stack()
        self._record(
            {
                "kind": "event",
                "name": name,
                "span_id": next(self._ids),
                "parent_id": stack[-1].span_id if stack else None,
                "ts": time.time(),
                "dur_s": 0.0,
                "attrs": attrs,
            }
        )

    def _record(self, rec: dict) -> None:
        self._ring.append(rec)
        if self._path is None or self._broken:
            return
        with self._write_lock:
            self._unwritten.append(rec)
            full = len(self._unwritten) >= _BATCH
            if not self._dir_made:
                try:
                    self._path.parent.mkdir(parents=True, exist_ok=True)
                    self._dir_made = True
                except OSError:
                    self._give_up()
        if full:
            self.flush()

    def _give_up(self) -> None:
        self._broken = True  # advisory: disk full must not kill training
        self._unwritten.clear()

    def flush(self) -> None:
        """Write what has gathered since the last flush: one open, one
        write."""
        with self._write_lock:
            if not self._unwritten or self._broken:
                return
            lines = "".join(json.dumps(r) + "\n" for r in self._unwritten)
            self._unwritten.clear()
            try:
                with self._path.open("a") as f:
                    f.write(lines)
            except OSError:
                self._give_up()

    close = flush  # nothing is held open between batches

    def recent(self, n: int = 50) -> list[dict]:
        """Most recent completed records, oldest first."""
        items = list(self._ring)
        return items[-n:]


_global = SpanTracer()


def get_tracer() -> SpanTracer:
    """Process-wide tracer (memory ring only) for cross-cutting events:
    XLA compilations (`compiles.py`; `/statsz` `xla.recent` reads them
    back). Components that export to a run's artifacts dir build their
    own `SpanTracer(path=...)`."""
    return _global
