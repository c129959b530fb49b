"""Serving overload benchmark: deliberate 5x-capacity traffic.

Measures the resilience layer ISSUE 5 added to `serving/` the only way
that means anything — by overloading a live server and checking what it
does about it. The bench calibrates the server's decode capacity (one
full-batch group timed after warmup), then fires single-row requests at
`--overload` times that rate, every request carrying a deadline. A
healthy server under overload must:

  * hang nothing — every request gets SOME answer (200 / 503 / 504);
  * shed — over capacity, a bounded queue MUST refuse work (503 with
    Retry-After) or drop expired entries before dispatch (504);
  * keep admitted latency bounded — a request it chose to serve finishes
    within deadline + one group execution (it was dispatched before its
    deadline and decode takes one group), not after an unbounded queue
    wait.

Prints one JSON line in the same schema family as the other benches:

  {"metric": "serving_overload_goodput", "value": ..., "unit": "req/s",
   "offered_rps": ..., "capacity_rps": ..., "ok": ..., "shed_503": ...,
   "deadline_504": ..., "hung": 0, "shed_rate": ...,
   "admitted_p99_ms": ..., "deadline_ms": ..., "group_ms": ..., ...}

Exit 1 when any acceptance bound fails (hung requests, zero sheds, or
admitted p99 over the bound).

  python benchmarks/serving_overload_bench.py             # 150 requests
  python benchmarks/serving_overload_bench.py --smoke     # CI: 40
  python benchmarks/serving_overload_bench.py --metricsz-out /tmp/m.txt
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from polyaxon_tpu.telemetry import quantile  # noqa: E402 (needs sys.path)

MODEL_CFG = {
    "preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
    "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256,
}
PROMPT_LEN = 16   # one shape -> one bucket -> one compile; capacity is
MAX_NEW = 24      # then a pure decode-rate property, not a compile race.
                  # 24 new tokens keeps a group slow enough (~100ms on
                  # CPU) that offered load stresses the QUEUE, not the
                  # TCP accept path


def _post(url: str, body: dict, timeout: float) -> tuple[int, dict]:
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        try:
            payload = json.loads(e.read())
        except Exception:  # noqa: BLE001
            payload = {}
        return e.code, payload


def _bodies(trace_seed: int, n: int) -> list[dict]:
    """Request bodies from the scenario engine's seeded `single_shape`
    trace generator (ISSUE 16): one fixed shape — one bucket, one
    compile — so capacity stays a pure decode-rate property, and the
    workload is a replayable trace (`trace_seed` in the record)."""
    from polyaxon_tpu.scenarios.traces import body_for, single_shape

    return [
        body_for(rec, MODEL_CFG["vocab_size"])
        for rec in single_shape(
            trace_seed, n=n, prompt_len=PROMPT_LEN, max_new=MAX_NEW
        )
    ]


def build_server(max_batch: int, max_queue: int, breaker_threshold: int):
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import build_model
    from polyaxon_tpu.serving.batching import ServingConfig
    from polyaxon_tpu.serving.server import ModelServer

    bundle = build_model("transformer_lm", MODEL_CFG)
    params = bundle.module.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    return ModelServer(
        bundle.module,
        params,
        model_name="overload-bench",
        config=ServingConfig(
            max_batch=max_batch,
            max_wait_ms=2.0,
            max_queue=max_queue,
            # the deadline budget rides on each request body (deadlineMs)
            # — it is derived from the measured group time, which does
            # not exist yet at config time
            breaker_threshold=breaker_threshold,
            request_timeout_s=60.0,
        ),
    )


def calibrate(url: str, trace_seed: int, max_batch: int) -> float:
    """Seconds one full decode group takes, measured after the compile
    is warm: a max_batch-row body is exactly one coalesced group."""
    # a distinct trace stream so calibration prompts differ from the
    # driven ones (same role the shared rng draws played before)
    warm, body = _bodies(trace_seed + 999_331, n=2)
    _post(url, warm, timeout=300.0)  # pays the XLA compile
    body["tokens"] = body["tokens"] * max_batch
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        code, _ = _post(url, body, timeout=300.0)
        dt = time.perf_counter() - t0
        if code == 200:
            best = min(best, dt)
    if best == float("inf"):
        raise RuntimeError("calibration requests failed")
    return best


def drive(args) -> dict:
    server = build_server(
        args.max_batch, args.max_queue, args.breaker_threshold
    )
    # time every decode group the server actually runs: the latency bound
    # must be judged against the group times of THIS run, not a calibration
    # taken on an idle box — on a CI host the suite runs beside us and
    # stretches decode well past the calibrated figure
    group_times_s: list[float] = []
    recording = threading.Event()
    inner_execute = server._coalescer._execute

    def timed_execute(batch):
        t0 = time.perf_counter()
        try:
            return inner_execute(batch)
        finally:
            if recording.is_set():
                group_times_s.append(time.perf_counter() - t0)

    server._coalescer._execute = timed_execute
    port = server.start(port=0)
    url = f"http://127.0.0.1:{port}/generate"
    group_s = calibrate(url, args.seed, args.max_batch)
    recording.set()  # calibration/compile groups stay out of the sample
    capacity_rps = args.max_batch / group_s
    offered_rps = capacity_rps * args.overload
    # deadline: a few group-times of queueing allowed, then the request is
    # dead — floor keeps CPU-jitter from making every request stillborn
    deadline_ms = max(200.0, 3.0 * group_s * 1e3)

    bodies = [
        {**body, "deadlineMs": deadline_ms}
        for body in _bodies(args.seed, args.requests)
    ]
    offsets = [i / offered_rps for i in range(args.requests)]
    lock = threading.Lock()
    outcomes = {"ok": 0, "shed_503": 0, "deadline_504": 0,
                "hung": 0, "error": 0}
    ok_latency_ms: list[float] = []
    first_error: list[str] = []
    start = time.perf_counter() + 0.05  # common epoch for the schedule

    def fire(body: dict, offset: float):
        delay = start + offset - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t0 = time.perf_counter()
        try:
            code, _ = _post(url, body, timeout=deadline_ms / 1e3 + 30.0)
        except Exception as e:  # noqa: BLE001 — a hang IS the finding
            with lock:
                outcomes["hung"] += 1
                if not first_error:
                    first_error.append(f"{type(e).__name__}: {e}"[:200])
            return
        dt_ms = (time.perf_counter() - t0) * 1e3
        with lock:
            if code == 200:
                outcomes["ok"] += 1
                ok_latency_ms.append(dt_ms)
            elif code == 503:
                outcomes["shed_503"] += 1
            elif code == 504:
                outcomes["deadline_504"] += 1
            else:
                outcomes["error"] += 1
                if not first_error:
                    first_error.append(f"http {code}")

    threads = [
        threading.Thread(target=fire, args=(b, o), daemon=True)
        for b, o in zip(bodies, offsets)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    metricsz = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    stats = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/statsz", timeout=30
        ).read()
    )
    server.stop()
    if args.metricsz_out:
        Path(args.metricsz_out).write_text(metricsz)

    import jax

    device = jax.devices()[0]
    group_ms = group_s * 1e3
    # worst group this run actually executed — the honest decode cost
    # under whatever contention the host threw at us
    worst_group_ms = max(group_times_s) * 1e3 if group_times_s else group_ms
    shed = outcomes["shed_503"] + outcomes["deadline_504"]
    # admitted-and-served p99 bound: dispatched before deadline + one
    # group of decode (the worst one observed). The slack term absorbs
    # HTTP/thread scheduling jitter on top.
    bound_ms = deadline_ms + worst_group_ms + max(250.0, worst_group_ms)
    p99 = quantile(sorted(ok_latency_ms), 0.99) if ok_latency_ms else None
    rec = {
        "metric": "serving_overload_goodput",
        "value": round(outcomes["ok"] / wall, 2) if wall > 0 else 0.0,
        "unit": "req/s",
        "overload": args.overload,
        "offered_rps": round(offered_rps, 2),
        "capacity_rps": round(capacity_rps, 2),
        "requests": args.requests,
        **outcomes,
        "shed_rate": round(shed / args.requests, 3),
        "admitted_p50_ms": (
            round(quantile(sorted(ok_latency_ms), 0.5), 1)
            if ok_latency_ms else None
        ),
        "admitted_p99_ms": round(p99, 1) if p99 is not None else None,
        "deadline_ms": round(deadline_ms, 1),
        "group_ms": round(group_ms, 1),
        "worst_group_ms": round(worst_group_ms, 1),
        "bound_ms": round(bound_ms, 1),
        "worker_restarts": stats.get("worker_restarts"),
        "breaker": stats.get("breaker"),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "trace_seed": args.seed,
        "trace_generator": "single_shape",
    }
    if first_error:
        rec["first_error"] = first_error[0]

    failures = []
    if outcomes["hung"] or outcomes["error"]:
        failures.append(
            f"{outcomes['hung']} hung / {outcomes['error']} errored — "
            "overload must shed, never strand"
        )
    if shed == 0:
        failures.append(
            f"zero sheds at {args.overload}x capacity — the queue bound "
            "or deadline admission is not engaging"
        )
    if p99 is not None and p99 > bound_ms:
        failures.append(
            f"admitted p99 {p99:.0f}ms > bound {bound_ms:.0f}ms "
            "(deadline + worst observed group + slack) — queueing is "
            "unbounded"
        )
    rec["pass"] = not failures
    if failures:
        rec["failures"] = failures
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=150)
    ap.add_argument("--overload", type=float, default=5.0,
                    help="offered load as a multiple of calibrated capacity")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=8)
    ap.add_argument("--breaker-threshold", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI configuration (40 requests)")
    ap.add_argument("--metricsz-out", default=None,
                    help="write the server's final /metricsz text here "
                         "(CI gates grep it)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = min(args.requests, 40)

    # POLYAXON_JAX_PLATFORM / POLYAXON_NUM_CPU_DEVICES apply through
    # jax.config, so before the backend initializes
    from polyaxon_tpu.utils.jax_platform import apply_platform_env

    apply_platform_env()

    rec = drive(args)
    print(json.dumps(rec), flush=True)
    return 0 if rec["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
