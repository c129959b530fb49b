"""Shared timing helper for the benchmark scripts (one methodology:
warmup call excluded, mean over iters, device-synced).

Sync is a scalar FETCH: pulling one element of the output to the host
forces completion of the whole dependency chain. The fetch's own
round-trip is measured afterwards (everything already done) and
subtracted, so transfer latency doesn't bill against the kernel.
`chip_smoke.py` times one warm program both ways — ending in this fetch
and ending in `jax.block_until_ready` — so that the benchmark can keep one
method (ROADMAP Design item 5)."""

from __future__ import annotations

import time


def _sync(out) -> float:
    """Force completion of `out`'s computation: fetch one element.

    Assumes everything being timed flows into ONE jitted executable whose
    outputs include this leaf: the fetch barriers that executable's whole
    dependency chain because the device runs its program to completion
    before materializing any output. Work dispatched by OTHER executables
    (or donated-buffer side effects) is not ordered before this fetch — a
    benchmark that interleaves several jit calls must fetch from the last
    one, or fall back to jax.block_until_ready on all of them."""
    import jax

    leaf = jax.tree.leaves(out)[0]
    return float(leaf.ravel()[0])


def summarize(samples_s) -> dict:
    """Distribution summary (count/mean/p50/p95/p99) of per-call latency
    samples. Delegates to `polyaxon_tpu.telemetry.summarize` — the one
    percentile implementation, shared with the servers' /statsz — so the
    benches and the serving layer can never disagree on what a percentile
    means. Bench scripts run with the repo root on sys.path, so the
    package import resolves."""
    from polyaxon_tpu.telemetry import summarize as _summarize

    return _summarize(list(samples_s))


def time_call(fn, *args, iters: int = 20) -> float:
    """Mean wall time per call over `iters` calls; one warmup call runs
    first so compile time is excluded."""
    out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    dt = time.perf_counter() - t0
    # fetch round-trip with no pending work — pure transfer cost
    t0 = time.perf_counter()
    _sync(out)
    rtt = time.perf_counter() - t0
    return max(dt - rtt, 1e-9) / iters
