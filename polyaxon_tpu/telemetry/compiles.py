"""XLA compilation as JAX itself reports it: one process-wide listener on
JAX's monitoring events.

`/statsz` `compile_count` counts misses of the server's own LRU of jitted
callables, which is neither XLA programs (one callable compiles once per
argument shape) nor time. JAX records three duration events around every
program it builds, each with the program's name (`fun_name`):

    /jax/core/compile/jaxpr_trace_duration           tracing to a jaxpr
    /jax/core/compile/jaxpr_to_mlir_module_duration  lowering to StableHLO
    /jax/core/compile/backend_compile_duration       XLA compile, or the
                                                     load from the persistent
                                                     cache (one per program)

The listener counts them into the process-global registry (`xla.programs`,
`xla.traces`, `xla.lowerings` and `xla.*_seconds`) and leaves a `compile`
event (`polyaxon.compile` in a profiler capture) with the program's name
and seconds in the process-wide span ring for every program. Trainer and
ModelServer call `install()` and read `snapshot()`; a listener cannot be
taken off again, so there is one, and its numbers are the process's. JAX
calls the listener on the thread that builds the program, so a thread can
ask for its own (`mine()`): the serving worker puts on a step's span what
that step built, not what a handler thread or a Trainer in the same
process compiled meanwhile.

Stdlib-only at import: jax is imported inside `install()`, which only
components that already run on jax call.
"""

from __future__ import annotations

import threading

from .registry import get_registry
from .spans import get_tracer

__all__ = ["install", "mine", "mirror", "recent", "snapshot"]

_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "program",
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
}
_WHAT = {
    "program": "compiled or loaded from the persistent cache",
    "trace": "traced to a jaxpr",
    "lowering": "lowered to StableHLO",
}
_lock = threading.Lock()
_series: dict = {}  # kind -> (count, seconds) counters; made by install()
_mine = threading.local()  # .programs: built on this thread


def _on_duration(event: str, duration: float, **kw) -> None:
    kind = _EVENTS.get(event)
    if kind is None:
        return
    count, seconds = _series[kind]
    count.inc()
    seconds.inc(max(0.0, float(duration)))
    if kind == "program":
        _mine.programs = getattr(_mine, "programs", 0) + 1
        get_tracer().event(
            "compile",
            program=str(kw.get("fun_name", "?")),
            seconds=round(float(duration), 6),
            thread=threading.get_ident(),
        )


def install() -> None:
    """Register the listener, once per process; the series exist from
    then on, at 0."""
    with _lock:
        if _series:
            return
        import jax.monitoring

        reg = get_registry()
        for kind, what in _WHAT.items():
            _series[kind] = (
                reg.counter(f"xla.{kind}s", help=f"XLA programs {what}"),
                reg.counter(
                    f"xla.{kind}_seconds",
                    help=f"Seconds spent on XLA programs {what}",
                ),
            )
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def mine() -> int:
    """XLA programs built or loaded so far on the calling thread."""
    return getattr(_mine, "programs", 0)


def recent(n: int = 8, mine: bool = False) -> list[dict]:
    """The newest programs still in the span ring, oldest first: name,
    seconds, the building thread, and the wall-clock time the compile (or
    cache load) ended. With `mine`, the calling thread's only."""
    me = threading.get_ident()
    events = [
        r for r in get_tracer().recent(512)
        if r["name"] == "compile" and (not mine or r["attrs"]["thread"] == me)
    ]
    return [dict(r["attrs"], ts=r["ts"]) for r in events[-n:]] if n > 0 else []


def mirror(registry) -> dict:
    """`snapshot()`, set as gauges of the same names in a component's own
    registry, so its scrape shows what `/statsz` `xla` shows. The
    process-global registry holds the counters themselves and is left
    alone."""
    xla = snapshot()
    if registry is not get_registry():
        for k, v in xla.items():
            registry.gauge(f"xla.{k}").set(v)
    return xla


def snapshot() -> dict:
    """{programs, traces, lowerings, program_seconds, trace_seconds,
    lowering_seconds}; empty before `install()`."""
    out = {}
    for kind, (count, seconds) in _series.items():
        out[f"{kind}s"] = int(count.value)
        out[f"{kind}_seconds"] = round(float(seconds.value), 6)
    return out
