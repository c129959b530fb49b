"""Thread-safe metrics registry: counters, gauges, fixed-bucket histograms.

One registry instance is one scrape surface. Metric names use dotted
namespaces (`serving.request_seconds`); the Prometheus renderer sanitizes
them to underscores and applies the exposition-format conventions
(counters grow `_total`, histograms emit `_bucket{le=...}`/`_sum`/
`_count`). `snapshot()` is the JSON-friendly view the `/statsz` handlers
and the CLI read — both views come from the same objects, so they cannot
disagree.

Histogram percentiles are ESTIMATED from bucket counts (linear
interpolation inside the bucket holding the target rank, clamped to the
observed min/max) — the registry never stores raw samples, so memory is
O(buckets) no matter how many observations land.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional, Sequence

# latency-shaped default buckets, in seconds: 1ms .. 60s
DEFAULT_BUCKETS: tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


def now() -> float:
    """The package's one monotonic metrics clock. Every duration
    measurement goes through here so the no-raw-perf_counter lint can
    hold everywhere else."""
    return time.perf_counter()


def process_age() -> Optional[float]:
    """Seconds since the kernel started this process: `/proc/uptime` less
    field 22 of `/proc/self/stat` (the start, in clock ticks since boot),
    so in steps of 10 ms. What ran before Python's first line (the
    interpreter's own start, an executor's `exec`) is in it. None where
    `/proc` does not say (off Linux)."""
    try:
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        with open("/proc/self/stat") as f:
            # the command's name (field 2) may hold spaces and brackets
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        return round(up - started / os.sysconf("SC_CLK_TCK"), 2)
    except (OSError, ValueError, IndexError):
        return None


class Counter:
    """Monotonically increasing count."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Last-written value (None until first set)."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._value: Optional[float] = None

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> Optional[float]:
        with self._lock:
            return self._value


class Histogram:
    """Fixed-bucket histogram. Buckets are ascending upper bounds; an
    implicit +inf bucket catches the overflow."""

    kind = "histogram"

    def __init__(
        self,
        name: str,
        buckets: Optional[Sequence[float]] = None,
        help: str = "",
    ):
        self.name = name
        self.help = help
        bounds = tuple(float(b) for b in (buckets or DEFAULT_BUCKETS))
        if not bounds or list(bounds) != sorted(set(bounds)):
            raise ValueError(
                f"histogram {name} buckets must be strictly ascending, "
                f"got {bounds}"
            )
        self.bounds = bounds
        self._lock = threading.Lock()
        self._counts = [0] * (len(bounds) + 1)  # last = +inf overflow
        self._sum = 0.0
        self._count = 0
        self._min: Optional[float] = None
        self._max: Optional[float] = None
        # last (value, trace_id) landing in each bucket — the exemplar
        # that lets a p99 spike link to a concrete request trace
        self._exemplars: list = [None] * (len(bounds) + 1)

    def observe(self, value: float, exemplar: Optional[str] = None) -> None:
        value = float(value)
        i = 0
        for i, b in enumerate(self.bounds):  # noqa: B007
            if value <= b:
                break
        else:
            i = len(self.bounds)
        with self._lock:
            self._counts[i] += 1
            self._sum += value
            self._count += 1
            if self._min is None or value < self._min:
                self._min = value
            if self._max is None or value > self._max:
                self._max = value
            if exemplar is not None:
                self._exemplars[i] = (value, str(exemplar))

    # ------------------------------------------------------------ reads
    def _state(self):
        with self._lock:
            return (
                list(self._counts), self._sum, self._count,
                self._min, self._max,
            )

    @property
    def count(self) -> int:
        return self._state()[2]

    @property
    def sum(self) -> float:
        return self._state()[1]

    def percentile(self, q: float) -> Optional[float]:
        """Estimate the q-quantile (q in [0, 1]) from bucket counts:
        linear interpolation across the bucket holding the target rank,
        clamped to the observed min/max so the estimate never leaves the
        data's range."""
        counts, _sum, total, vmin, vmax = self._state()
        if total == 0:
            return None
        target = q * total
        cum = 0.0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            lo = self.bounds[i - 1] if i > 0 else (vmin if vmin is not None else 0.0)
            hi = self.bounds[i] if i < len(self.bounds) else (vmax if vmax is not None else lo)
            if cum + c >= target:
                frac = (target - cum) / c
                est = lo + (hi - lo) * max(0.0, min(1.0, frac))
                if vmin is not None:
                    est = max(est, vmin)
                if vmax is not None:
                    est = min(est, vmax)
                return est
            cum += c
        return vmax

    def count_le(self, value: float) -> float:
        """Estimated cumulative count of observations <= value (linear
        interpolation inside the bucket the threshold falls in) — the
        latency-SLO 'good events' counter, from bucket counts only."""
        counts, _sum, total, vmin, vmax = self._state()
        if total == 0:
            return 0.0
        value = float(value)
        cum = 0.0
        for i, c in enumerate(counts):
            lo = (
                self.bounds[i - 1]
                if i > 0
                else (vmin if vmin is not None else 0.0)
            )
            hi = (
                self.bounds[i]
                if i < len(self.bounds)
                else (vmax if vmax is not None else lo)
            )
            if value >= hi:
                cum += c
                continue
            if value >= lo and hi > lo:
                cum += c * (value - lo) / (hi - lo)
            break
        return cum

    def exemplar(self, q: float = 0.99) -> Optional[dict]:
        """The exemplar nearest the q-quantile bucket: {'value',
        'trace_id'} of a request that actually landed there, or None."""
        with self._lock:
            counts = list(self._counts)
            total = self._count
            ex = list(self._exemplars)
        if total == 0:
            return None
        target = q * total
        cum = 0.0
        idx = len(counts) - 1
        for i, c in enumerate(counts):
            cum += c
            if cum >= target:
                idx = i
                break
        # the rank bucket may hold no exemplar (it landed before
        # exemplars were attached) — fall outward to the nearest
        for j in list(range(idx, len(ex))) + list(range(idx - 1, -1, -1)):
            if ex[j] is not None:
                return {"value": ex[j][0], "trace_id": ex[j][1]}
        return None

    def summary(self) -> dict:
        counts, total_sum, total, vmin, vmax = self._state()
        out = {
            "count": total,
            "sum": total_sum,
            "mean": (total_sum / total) if total else None,
            "min": vmin,
            "max": vmax,
        }
        for label, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
            out[label] = self.percentile(q)
        return out


class MetricsRegistry:
    """Get-or-create metric container. A name is bound to ONE metric
    kind for the registry's lifetime — re-registering with a different
    kind (or different histogram buckets) is a programming error and
    raises instead of silently splitting the series."""

    def __init__(self, default_buckets: Optional[Sequence[float]] = None):
        self._lock = threading.Lock()
        self._metrics: dict[str, object] = {}
        self._default_buckets = (
            tuple(default_buckets) if default_buckets else None
        )

    def _get_or_create(self, name: str, factory, kind: str):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = factory()
                self._metrics[name] = m
            elif m.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"not {kind}"
                )
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(name, lambda: Counter(name, help), "counter")

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(name, lambda: Gauge(name, help), "gauge")

    def histogram(
        self,
        name: str,
        buckets: Optional[Sequence[float]] = None,
        help: str = "",
    ) -> Histogram:
        h = self._get_or_create(
            name,
            lambda: Histogram(
                name, buckets or self._default_buckets, help
            ),
            "histogram",
        )
        if buckets is not None and tuple(float(b) for b in buckets) != h.bounds:
            raise ValueError(
                f"histogram {name!r} already registered with buckets "
                f"{h.bounds}"
            )
        return h

    def metrics(self) -> list:
        with self._lock:
            return sorted(self._metrics.values(), key=lambda m: m.name)

    # ------------------------------------------------------------ views
    def snapshot(self) -> dict:
        """JSON-friendly view: counters/gauges → value, histograms →
        their summary dict (count/sum/mean/min/max/p50/p95/p99)."""
        out = {}
        for m in self.metrics():
            if m.kind == "histogram":
                out[m.name] = m.summary()
            else:
                out[m.name] = m.value
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition format (0.0.4)."""
        lines: list[str] = []
        for m in self.metrics():
            name = _sanitize(m.name)
            if m.kind == "counter":
                name += "_total"
            if m.help:
                lines.append(f"# HELP {name} {m.help}")
            lines.append(f"# TYPE {name} {m.kind}")
            if m.kind == "counter":
                lines.append(f"{name} {_fmt(m.value)}")
            elif m.kind == "gauge":
                if m.value is not None:
                    lines.append(f"{name} {_fmt(m.value)}")
            else:  # histogram: cumulative le buckets + _sum/_count
                counts, total_sum, total, _, _ = m._state()
                cum = 0
                for bound, c in zip(m.bounds, counts):
                    cum += c
                    lines.append(
                        f'{name}_bucket{{le="{_fmt(bound)}"}} {cum}'
                    )
                lines.append(f'{name}_bucket{{le="+Inf"}} {total}')
                lines.append(f"{name}_sum {_fmt(total_sum)}")
                lines.append(f"{name}_count {total}")
        return "\n".join(lines) + ("\n" if lines else "")


def _sanitize(name: str) -> str:
    out = []
    for i, ch in enumerate(name):
        if ch.isalnum() or ch == "_" or (ch == ":" and i):
            out.append(ch)
        else:
            out.append("_")
    s = "".join(out)
    return ("_" + s) if s and s[0].isdigit() else s


def _fmt(v: float) -> str:
    # integers render without a trailing .0 (matches common exporters)
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


_global = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry for cross-cutting layers (run-store
    transitions, retries, chaos). Per-component surfaces (a ModelServer's
    `/metricsz`) use their own instance."""
    return _global
