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

Compiled or loaded is the difference between a set-up of half a minute and
one of three, and JAX says which where the persistent cache is on: two
more durations and, as plain events, what the cache answered

    /jax/compilation_cache/compile_requests_use_cache  the cache was asked (sent
                                                       with no directory set, too)
    /jax/compilation_cache/cache_hits                  and had the program
    /jax/compilation_cache/cache_misses                a compile was written to it
    /jax/compilation_cache/cache_retrieval_time_sec    what reading it took
    /jax/compilation_cache/compile_time_saved_sec      the compile a hit stood for

(a compile under the cache's floors of time and size is asked for and
never written: neither a hit nor a miss of JAX's).

The listeners count all of them into the process-global registry
(`xla.programs`, `xla.traces`, `xla.lowerings`, `xla.*_seconds`,
`xla.cache_hits`, `xla.cache_misses`, `xla.cache_retrieval_seconds`,
`xla.compile_seconds_saved`) and leave a `compile` event
(`polyaxon.compile` in a profiler capture) with the program's name, its
seconds and `cache` (`hit`, `miss`: asked and not there, or `off`) in the
process-wide span ring for every program. Trainer and ModelServer call
`install()` and read `snapshot()`; a listener cannot be taken off again,
so there is one of each kind, and their numbers are the process's. JAX
calls a listener on the thread that builds the program, so a thread can
ask for its own (`mine()`, `own()`): the serving worker puts on a step's
span what that step built, not what a handler thread or a Trainer in the
same process compiled meanwhile, and the Trainer's `init` and `compile`
spans carry what they themselves had built or loaded.

Stdlib-only at import: jax is imported inside `install()`, which only
components that already run on jax call.
"""

from __future__ import annotations

import sys
import threading

from .registry import get_registry
from .spans import get_tracer

__all__ = ["cache_since", "install", "mine", "mirror", "own", "recent", "snapshot"]

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
_CACHE = "/jax/compilation_cache/"
# JAX's name -> (the series here, what its help says); the first two are
# durations, the others plain events
_CACHE_EVENTS = {
    _CACHE + "cache_retrieval_time_sec": (
        "cache_retrieval_seconds", "Seconds spent reading the persistent compile cache"),
    _CACHE + "compile_time_saved_sec": (
        "compile_seconds_saved", "Compile seconds that hits of the persistent cache stood for"),
    _CACHE + "cache_hits": (
        "cache_hits", "XLA programs loaded from the persistent compile cache"),
    _CACHE + "cache_misses": (
        "cache_misses", "XLA programs compiled and written to the persistent compile cache"),
}
_CACHE_ASKED = _CACHE + "compile_requests_use_cache"
_lock = threading.Lock()
_series: dict = {}  # kind -> (count, seconds) counters; made by install()
_cache: dict = {}  # series name -> counter; made by install()
# .series: what this thread built, by snapshot()'s keys and `cache_asked`;
# .at_program: its cache counts when it last ended a program
_mine = threading.local()


def _own() -> dict:
    series = getattr(_mine, "series", None)
    if series is None:
        series = _mine.series = {}
    return series


def _add(name: str, amount: float = 1) -> None:
    own = _own()
    own[name] = own.get(name, 0) + amount


def _on_cache(event: str, amount: float = 1) -> None:
    name = _CACHE_EVENTS[event][0]
    _cache[name].inc(amount)
    _add(name, amount)


def _on_duration(event: str, duration: float, **kw) -> None:
    duration = max(0.0, float(duration))  # a hit can save less than nothing
    kind = _EVENTS.get(event)
    if kind is None:
        if event in _CACHE_EVENTS:
            _on_cache(event, duration)
        return
    count, seconds = _series[kind]
    count.inc()
    seconds.inc(duration)
    _add(f"{kind}s")
    _add(f"{kind}_seconds", duration)
    if kind == "program":
        get_tracer().event(
            "compile",
            program=str(kw.get("fun_name", "?")),
            seconds=round(duration, 6),
            cache=cache_since(getattr(_mine, "at_program", {})),
            thread=threading.get_ident(),
        )
        _mine.at_program = own()


def _on_event(event: str, **kw) -> None:
    if event in _CACHE_EVENTS:
        _on_cache(event)
    elif event == _CACHE_ASKED:  # the thread's own only: tells `miss` from `off`
        _add("cache_asked")


def install() -> None:
    """Register the listeners, once per process; the series exist from
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
        for name, what in _CACHE_EVENTS.values():
            _cache[name] = reg.counter(f"xla.{name}", help=what)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)


def mine() -> int:
    """XLA programs built or loaded so far on the calling thread."""
    return _own().get("programs", 0)


def own() -> dict:
    """The calling thread's own share of `snapshot()` so far (a series it
    has not moved is left out), with `cache_asked`: how often it asked the
    persistent cache for a program. A span takes one at its start and
    reads the difference at its end."""
    return dict(_own())


def cache_since(before: dict) -> str:
    """What the persistent cache answered the calling thread since it took
    `before` = `own()`: `hit` (a program came from it), `miss` (asked, and
    none did), or `off` (no cache directory, or nothing asked)."""
    own = _own()
    if own.get("cache_hits", 0) > before.get("cache_hits", 0):
        return "hit"
    config = getattr(sys.modules.get("jax"), "config", None)  # looked up, never imported
    if (
        own.get("cache_asked", 0) > before.get("cache_asked", 0)
        and config.jax_compilation_cache_dir
        and config.jax_enable_compilation_cache
    ):
        return "miss"
    return "off"


def recent(n: int = 8, mine: bool = False) -> list[dict]:
    """The newest programs still in the span ring, oldest first: name,
    seconds, what the persistent cache answered, the building thread, and
    the wall-clock time the compile (or cache load) ended. With `mine`,
    the calling thread's only."""
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
    lowering_seconds, cache_hits, cache_misses, cache_retrieval_seconds,
    compile_seconds_saved}; empty before `install()`."""
    out = {}
    for kind, (count, seconds) in _series.items():
        out[f"{kind}s"] = int(count.value)
        out[f"{kind}_seconds"] = round(float(seconds.value), 6)
    for name, counter in _cache.items():
        value = float(counter.value)
        out[name] = round(value, 6) if name.endswith(("seconds", "saved")) else int(value)
    return out
