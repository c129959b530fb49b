"""Serving resilience (ISSUE 5): bounded queues, deadlines, breaker, drain.

Tested at three levels:
  * pure units — CircuitBreaker state machine and the DecodeCoalescer's
    admission/shedding/watchdog behavior with fake executors (no jax);
  * chaos scenarios — seeded FaultPlans driving the serving.decode /
    serving.worker points through the REAL coalescer + server paths;
  * live HTTP — shed responses (503 + Retry-After), deadline drops (504),
    /readyz flipping during a graceful drain, and queued requests failed
    terminally when the drain budget runs out.

Plus the store durability satellites: fsync'd atomic JSON writes and
quarantine of undecodable files.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from polyaxon_tpu.serving.batching import (
    CircuitBreaker,
    DeadlineExceededError,
    DecodeCoalescer,
    GroupKey,
    PendingRequest,
    ServerClosingError,
    ServingConfig,
    ShedError,
    WorkerCrashError,
)

pytestmark = pytest.mark.serving


KEY = GroupKey(32, 16, 0.8, 40, None)


def _req(key=KEY, plen=3, seed=0, deadline_ms=None):
    deadline = (
        time.monotonic() + deadline_ms / 1e3 if deadline_ms is not None else None
    )
    return PendingRequest(
        tokens=[1] * plen, prompt_len=plen, max_new=4, seed=seed, key=key,
        deadline=deadline,
    )


def _ok_executor(batches=None):
    def execute(batch):
        if batches is not None:
            batches.append(batch)
        for r in batch:
            r.finish(result=list(r.tokens))

    return execute


def _blocking_executor(release: threading.Event, started=None):
    """Holds every batch until `release` is set — a decode in molasses."""

    def execute(batch):
        if started is not None:
            started.set()
        release.wait(10)
        for r in batch:
            r.finish(result=list(r.tokens))

    return execute


# ------------------------------------------------------- circuit breaker
def test_breaker_trips_after_consecutive_failures():
    b = CircuitBreaker(threshold=3, cooldown_s=60)
    for _ in range(2):
        b.record_failure()
    assert b.state == "closed" and b.allow()
    b.record_failure()
    assert b.state == "open" and not b.allow()


def test_breaker_success_resets_the_streak():
    b = CircuitBreaker(threshold=2, cooldown_s=60)
    b.record_failure()
    b.record_success()
    b.record_failure()
    assert b.state == "closed"  # failures were not consecutive


def test_breaker_half_open_probe_and_recovery():
    b = CircuitBreaker(threshold=1, cooldown_s=0.05)
    b.record_failure()
    assert b.state == "open" and not b.allow()
    time.sleep(0.06)
    assert b.allow()  # cooldown elapsed: ONE probe admitted
    assert b.state == "half_open"
    assert not b.allow()  # second caller inside the window: still shed
    b.record_success()
    assert b.state == "closed" and b.allow()


def test_breaker_failed_probe_reopens():
    b = CircuitBreaker(threshold=1, cooldown_s=0.05)
    b.record_failure()
    time.sleep(0.06)
    assert b.allow()
    b.record_failure()  # the probe failed
    assert b.state == "open" and not b.allow()


def test_breaker_unreported_probe_self_heals():
    # a probe that never reports (shed downstream, dropped on deadline)
    # must not wedge the breaker half-open forever
    b = CircuitBreaker(threshold=1, cooldown_s=0.05)
    b.record_failure()
    time.sleep(0.06)
    assert b.allow()  # probe 1 — never reports an outcome
    time.sleep(0.06)
    assert b.allow()  # one cooldown later another probe is admitted


def test_breaker_disabled_by_nonpositive_threshold():
    b = CircuitBreaker(threshold=0)
    for _ in range(10):
        b.record_failure()
    assert b.state == "closed" and b.allow()


def test_breaker_reports_state_changes():
    codes = []
    b = CircuitBreaker(threshold=1, cooldown_s=0.05, on_change=codes.append)
    b.record_failure()
    time.sleep(0.06)
    b.allow()
    b.record_success()
    assert codes == [1, 2, 0]  # open, half_open, closed


# --------------------------------------------------- admission / shedding
def test_coalescer_sheds_at_max_queue():
    release = threading.Event()
    c = DecodeCoalescer(
        _blocking_executor(release), max_batch=1, max_wait_ms=0, max_queue=2
    )
    r1, r2 = _req(seed=1), _req(seed=2)
    c.submit(r1)
    c.submit(r2)
    with pytest.raises(ShedError) as ei:
        c.submit(_req(seed=3))
    assert ei.value.reason == "queue_full"
    assert c.shed_total == 1 and c.depth == 2
    release.set()
    c.start()
    assert r1.done.wait(10) and r2.done.wait(10)
    c.stop()


def test_coalescer_sheds_expired_at_admission():
    c = DecodeCoalescer(_ok_executor(), max_batch=4, max_wait_ms=0)
    with pytest.raises(ShedError) as ei:
        c.submit(_req(deadline_ms=-1.0))  # already past
    assert ei.value.reason == "deadline"
    assert c.depth == 0  # never admitted


def test_coalescer_drops_expired_before_dispatch():
    # worker is wedged on group 1; a short-deadline request queued behind
    # it must be dropped WITHOUT spending a decode slot
    release = threading.Event()
    started = threading.Event()
    batches = []

    def execute(batch):
        batches.append([r.seed for r in batch])
        started.set()
        release.wait(10)
        for r in batch:
            r.finish(result=list(r.tokens))

    c = DecodeCoalescer(execute, max_batch=1, max_wait_ms=0)
    c.start()
    r1 = _req(seed=1)
    c.submit(r1)
    assert started.wait(10)
    r2 = _req(seed=2, deadline_ms=30.0)
    c.submit(r2)
    time.sleep(0.08)  # r2's deadline passes while the worker is wedged
    release.set()
    assert r1.done.wait(10) and r2.done.wait(10)
    c.stop()
    assert r1.result is not None
    assert isinstance(r2.error, DeadlineExceededError)
    assert batches == [[1]]  # r2 never reached the executor
    assert c.deadline_dropped == 1


def test_coalescer_evicts_expired_nonhead_while_coalescing():
    # ISSUE 14 regression: the coalesce wait used to be computed from the
    # HEAD row only, so a short-deadline row queued behind a deadline-less
    # head sat out the head's whole max_wait before its 504. The wait must
    # be capped at the earliest pending deadline: the non-head row fails
    # fast, spends no step tokens, and the head is NOT dispatched early.
    batches = []

    def execute(batch):
        batches.append([r.seed for r in batch])
        for r in batch:
            r.finish(result=list(r.tokens))

    c = DecodeCoalescer(execute, max_batch=4, max_wait_ms=1500.0)
    c.start()
    r1 = _req(seed=1)  # head: no deadline, coalescing for up to 1.5s
    c.submit(r1)
    time.sleep(0.02)
    r2 = _req(seed=2, deadline_ms=40.0)  # non-head, expires mid-coalesce
    c.submit(r2)
    assert r2.done.wait(0.75), "non-head row waited out the head's max_wait"
    assert isinstance(r2.error, DeadlineExceededError)
    assert c.deadline_dropped == 1
    # eviction must not have flushed the head before ITS max_wait
    assert not r1.done.is_set() and batches == []
    c.stop(drain_s=5.0)  # drain wakes the coalesce wait and flushes the head
    assert r1.done.is_set() and r1.result is not None
    assert batches == [[1]]  # r2 never reached the executor


def test_coalescer_breaker_opens_then_recovers():
    fail = {"n": 3}

    def execute(batch):
        if fail["n"] > 0:
            fail["n"] -= 1
            raise RuntimeError("decode outage")
        for r in batch:
            r.finish(result=list(r.tokens))

    breaker = CircuitBreaker(threshold=3, cooldown_s=0.05)
    c = DecodeCoalescer(execute, max_batch=1, max_wait_ms=0, breaker=breaker)
    c.start()
    for i in range(3):
        r = _req(seed=i)
        c.submit(r)
        assert r.done.wait(10)
        assert "outage" in str(r.error)
    assert breaker.state == "open"
    with pytest.raises(ShedError) as ei:
        c.submit(_req(seed=99))
    assert ei.value.reason == "breaker_open"
    assert ei.value.retry_after_s >= 1.0
    time.sleep(0.06)  # cooldown: next submit is the half-open probe
    probe = _req(seed=100)
    c.submit(probe)
    assert probe.done.wait(10)
    assert probe.result is not None
    assert breaker.state == "closed"
    c.stop()


def test_coalescer_watchdog_restarts_crashed_worker():
    from polyaxon_tpu.chaos.injector import active
    from polyaxon_tpu.chaos.plan import FaultPlan

    plan = FaultPlan.serving_worker_crash(seed=11, window=1)
    assert plan.params["crash_hit"] == 0
    c = DecodeCoalescer(_ok_executor(), max_batch=1, max_wait_ms=0)
    c.start()
    with active(plan):
        r1 = _req(seed=1)
        c.submit(r1)
        assert r1.done.wait(10)
        # the in-flight group failed FAST, not via request_timeout_s
        assert isinstance(r1.error, WorkerCrashError)
        # the restarted worker serves the next request normally
        r2 = _req(seed=2)
        c.submit(r2)
        assert r2.done.wait(10)
    c.stop()
    assert r2.result is not None
    assert c.worker_restarts == 1


def test_coalescer_drain_flushes_then_stop_fails_leftovers():
    release = threading.Event()
    started = threading.Event()
    c = DecodeCoalescer(
        _blocking_executor(release, started), max_batch=1, max_wait_ms=0
    )
    c.start()
    r1, r2 = _req(seed=1), _req(seed=2)
    c.submit(r1)
    assert started.wait(10)
    c.submit(r2)  # queued behind the wedged group
    t = threading.Thread(target=c.stop, kwargs={"drain_s": 0.15}, daemon=True)
    t.start()
    time.sleep(0.02)
    with pytest.raises(ServerClosingError):
        c.submit(_req(seed=3))  # admission closed the moment drain began
    time.sleep(0.2)  # let the drain budget lapse
    release.set()
    t.join(10)
    assert r1.done.is_set() and r2.done.is_set()
    assert r1.result is not None  # in-flight work finished
    # r2 missed the budget: terminal close, NOT a request_timeout_s hang
    assert isinstance(r2.error, ServerClosingError)
    assert c.idle


def test_coalescer_drain_with_budget_completes_everything():
    # 3 same-key rows in a max_batch=4 coalescer: a PARTIAL batch, which
    # normally sits out the 1s straggler window — draining flushes it
    c = DecodeCoalescer(_ok_executor(), max_batch=4, max_wait_ms=1000)
    c.start()
    rows = [_req(seed=i) for i in range(3)]
    for r in rows:
        c.submit(r)
    t0 = time.monotonic()
    c.stop(drain_s=5.0)
    assert time.monotonic() - t0 < 2.0
    assert all(r.result is not None for r in rows)


# ----------------------------------------------------------- chaos plans
@pytest.mark.chaos
def test_serving_fault_plans_are_seed_deterministic():
    from polyaxon_tpu.chaos.plan import FaultPlan

    for ctor, kwargs in (
        (FaultPlan.serving_flaky_decode, {"window": 20, "fails": 3}),
        (FaultPlan.serving_decode_outage, {"window": 20, "fails": 5}),
        (FaultPlan.serving_worker_crash, {"window": 20}),
        (FaultPlan.serving_brownout, {"window": 20, "slow": 2}),
    ):
        a = ctor(seed=7, **kwargs)
        b = ctor(seed=7, **kwargs)
        other = ctor(seed=8, **kwargs)
        assert a.params == b.params, ctor.__name__
        assert [vars(f) for f in a.faults] == [vars(f) for f in b.faults]
        assert a.params != other.params or a.seed != other.seed


@pytest.mark.chaos
def test_brownout_plan_sleeps_at_the_injection_site():
    from polyaxon_tpu.chaos.injector import active, inject
    from polyaxon_tpu.chaos.plan import FaultPlan

    plan = FaultPlan.serving_brownout(seed=3, window=4, slow=1, delay_ms=60.0)
    hit = plan.params["slow_start"]
    with active(plan):
        for i in range(4):
            t0 = time.monotonic()
            inject("serving.slow", rows=1)
            dt = time.monotonic() - t0
            if i == hit:
                assert dt >= 0.05, f"hit {i} did not stall ({dt * 1e3:.1f}ms)"
            else:
                assert dt < 0.05, f"hit {i} stalled unexpectedly"


# ------------------------------------------------------------- live HTTP
def _tiny_server(**cfg_overrides):
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import build_model
    from polyaxon_tpu.serving.server import ModelServer

    model_cfg = {
        "preset": "tiny", "seq_len": 64, "n_layers": 1, "dim": 32,
        "n_heads": 2, "n_kv_heads": 2, "vocab_size": 128,
    }
    bundle = build_model("transformer_lm", model_cfg)
    params = bundle.module.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    cfg = dict(max_batch=2, max_wait_ms=2.0, request_timeout_s=30.0)
    cfg.update(cfg_overrides)
    return ModelServer(
        bundle.module, params, model_name="resilience-test",
        config=ServingConfig(**cfg),
    )


def _post(port, body, timeout=30.0):
    """(status, payload, headers) — HTTP errors returned, not raised."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(port, path, timeout=10.0):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


BODY = {"tokens": [[1, 2, 3]], "maxNewTokens": 4, "temperature": 0.8,
        "topK": 10, "seed": 0}


def test_http_shed_maps_to_503_with_retry_after():
    server = _tiny_server(max_queue=1, max_batch=1, max_wait_ms=0)
    release = threading.Event()
    started = threading.Event()
    server._coalescer._execute = _blocking_executor(release, started)
    port = server.start(port=0)
    try:
        bg = threading.Thread(
            target=_post, args=(port, BODY), daemon=True
        )
        bg.start()
        assert started.wait(10)  # group 1 occupies the single slot...
        # ...but depth is 0 again once in-flight resolves, so wedge depth
        # by submitting while blocked: in-flight counts toward max_queue
        code, payload, headers = _post(port, BODY)
        assert code == 503
        assert payload["reason"] == "queue_full"
        assert int(headers["Retry-After"]) >= 1
        # the shed surfaced on /metricsz through the one pipeline
        _, text = _get(port, "/metricsz")
        assert "serving_shed_total 1" in text
        release.set()
        bg.join(10)
    finally:
        release.set()
        server.stop(drain_grace_s=0.5)


def test_http_expired_deadline_maps_to_504():
    server = _tiny_server(max_batch=1, max_wait_ms=0, max_queue=8)
    release = threading.Event()
    started = threading.Event()
    server._coalescer._execute = _blocking_executor(release, started)
    port = server.start(port=0)
    try:
        bg = threading.Thread(target=_post, args=(port, BODY), daemon=True)
        bg.start()
        assert started.wait(10)
        # queued behind the wedge with a 50ms budget: dropped, not decoded
        results = []
        t = threading.Thread(target=lambda: results.append(
            _post(port, {**BODY, "deadlineMs": 50.0})), daemon=True)
        t.start()
        time.sleep(0.15)
        release.set()
        t.join(10)
        bg.join(10)
        code, payload, _ = results[0]
        assert code == 504
        assert payload["reason"] == "deadline_exceeded"
        _, text = _get(port, "/metricsz")
        assert "serving_deadline_exceeded_total 1" in text
    finally:
        release.set()
        server.stop(drain_grace_s=0.5)


def test_http_already_expired_deadline_sheds_503():
    server = _tiny_server()
    port = server.start(port=0)
    try:
        code, payload, headers = _post(port, {**BODY, "deadlineMs": 1e-6})
        assert code == 503
        assert payload["reason"] == "deadline"
        assert "Retry-After" in headers
    finally:
        server.stop(drain_grace_s=0.5)


def test_http_invalid_deadline_is_400():
    server = _tiny_server()
    port = server.start(port=0)
    try:
        code, payload, _ = _post(port, {**BODY, "deadlineMs": -5})
        assert code == 400
        assert "deadlineMs" in payload["error"]
    finally:
        server.stop(drain_grace_s=0.5)


@pytest.mark.chaos
def test_http_decode_outage_trips_breaker_then_recovers():
    from polyaxon_tpu.chaos.injector import active
    from polyaxon_tpu.chaos.plan import FaultPlan

    # cooldown generous enough that HTTP roundtrip jitter cannot flip the
    # breaker half-open before the shed assertion runs
    server = _tiny_server(
        max_batch=1, max_wait_ms=0,
        breaker_threshold=2, breaker_cooldown_s=0.5,
    )
    port = server.start(port=0)
    try:
        # warm the compile OUTSIDE the outage so chaos hits decode, not XLA
        code, _, _ = _post(port, BODY, timeout=120.0)
        assert code == 200
        plan = FaultPlan.serving_decode_outage(seed=5, window=2, fails=2)
        assert plan.params == {"outage_start": 0, "outage_len": 2}
        with active(plan):
            for _ in range(2):  # the outage: chaos raises inside decode
                code, _, _ = _post(port, BODY)
                assert code == 500
            # 2 consecutive failures tripped the threshold-2 breaker
            code, payload, _ = _post(port, BODY)
            assert code == 503 and payload["reason"] == "breaker_open"
            _, text = _get(port, "/metricsz")
            assert "serving_breaker_state 1" in text
            time.sleep(0.6)  # cooldown: the next request is the probe
            code, _, _ = _post(port, BODY)
            assert code == 200  # outage spent; probe succeeds
        _, text = _get(port, "/metricsz")
        assert "serving_breaker_state 0" in text
        stats = json.loads(_get(port, "/statsz")[1])
        assert stats["breaker"] == "closed"
    finally:
        server.stop(drain_grace_s=0.5)


def test_http_graceful_drain_readyz_and_inflight():
    server = _tiny_server(max_batch=1, max_wait_ms=0, drain_grace_s=5.0)
    release = threading.Event()
    started = threading.Event()
    server._coalescer._execute = _blocking_executor(release, started)
    port = server.start(port=0)
    code, body = _get(port, "/readyz")
    assert code == 200 and json.loads(body)["ready"] is True

    results = []
    bg = threading.Thread(
        target=lambda: results.append(_post(port, BODY)), daemon=True
    )
    bg.start()
    assert started.wait(10)
    stopper = threading.Thread(target=server.stop, daemon=True)
    stopper.start()
    time.sleep(0.1)  # stop() has begun draining; httpd still answers
    code, body = _get(port, "/readyz")
    assert code == 503 and json.loads(body)["ready"] is False
    code, payload, _ = _post(port, BODY)
    # shed at admission mid-drain: "draining" (never queued, retryable
    # elsewhere) — "closing" is reserved for queued requests failed
    # terminally when the drain budget expires
    assert code == 503 and payload["reason"] == "draining"
    release.set()  # let the in-flight request finish inside the budget
    bg.join(10)
    stopper.join(10)
    code, payload, _ = results[0]
    assert code == 200 and payload["tokens"]


def test_http_drain_budget_fails_queued_terminally():
    server = _tiny_server(max_batch=1, max_wait_ms=0, drain_grace_s=0.05)
    release = threading.Event()
    started = threading.Event()
    server._coalescer._execute = _blocking_executor(release, started)
    port = server.start(port=0)
    results = []

    def fire():
        results.append(_post(port, BODY))

    t1 = threading.Thread(target=fire, daemon=True)
    t1.start()
    assert started.wait(10)
    t2 = threading.Thread(target=fire, daemon=True)  # queued behind wedge
    t2.start()
    time.sleep(0.1)
    stopper = threading.Thread(target=server.stop, daemon=True)
    stopper.start()
    time.sleep(0.2)  # budget (50ms) lapses with the worker still wedged
    release.set()
    t1.join(10)
    t2.join(10)
    stopper.join(10)
    codes = sorted(r[0] for r in results)
    # the wedged group finishes (200); the queued one is failed with a
    # terminal 503, NOT left to hang out request_timeout_s
    assert codes == [200, 503], results


def test_readiness_reflects_device_regression():
    server = _tiny_server()
    server.expected_devices = 9999  # conftest pins 8 fake CPU devices
    port = server.start(port=0)
    try:
        code, body = _get(port, "/readyz")
        assert code == 503
        assert "degraded slice" in json.loads(body)["reason"]
        _, text = _get(port, "/metricsz")
        assert "serving_ready 0" in text
    finally:
        server.stop(drain_grace_s=0.5)
    server2 = _tiny_server()
    server2.expected_devices = 8
    port = server2.start(port=0)
    try:
        code, body = _get(port, "/readyz")
        assert code == 200 and json.loads(body)["ready"] is True
    finally:
        server2.stop(drain_grace_s=0.5)


# ------------------------------------------------------------ spec schema
def test_serving_spec_resilience_fields_roundtrip():
    from polyaxon_tpu.schemas.run_kinds import V1ServingSpec

    spec = V1ServingSpec.model_validate({
        "maxQueue": 16, "defaultDeadlineMs": 250.0,
        "drainGraceS": 2.0, "breakerThreshold": 3,
    })
    cfg = spec.to_config()
    assert cfg.max_queue == 16
    assert cfg.default_deadline_ms == 250.0
    assert cfg.drain_grace_s == 2.0
    assert cfg.breaker_threshold == 3
    # defaults flow through untouched
    assert V1ServingSpec().to_config().max_queue == 64


@pytest.mark.parametrize("field,value", [
    ("maxQueue", 0),
    ("breakerThreshold", 0),
    ("defaultDeadlineMs", -1.0),
    ("drainGraceS", -0.5),
])
def test_serving_spec_rejects_bad_resilience_values(field, value):
    from pydantic import ValidationError

    from polyaxon_tpu.schemas.run_kinds import V1ServingSpec

    with pytest.raises(ValidationError):
        V1ServingSpec.model_validate({field: value})


def test_from_run_overrides_layer_over_spec_pins(tmp_home, tmp_path):
    # `polyaxon serve --max-queue 2` against a run whose spec pins
    # maxBatch must override ONLY max_queue — resetting the spec's other
    # pins to library defaults is the bug this guards against
    import jax
    import yaml

    from polyaxon_tpu.compiler import compile_operation
    from polyaxon_tpu.polyaxonfile import read_polyaxonfile
    from polyaxon_tpu.runtime import Executor
    from polyaxon_tpu.runtime.checkpoint import close_all
    from polyaxon_tpu.serving import ModelServer
    from polyaxon_tpu.store import RunStore

    spec = {
        "version": 1.1,
        "kind": "operation",
        "name": "pinned-serving",
        "component": {
            "kind": "component",
            "name": "pinned-serving",
            "run": {
                "kind": "jaxjob",
                "program": {
                    "model": {
                        "name": "transformer_lm",
                        "config": {
                            "preset": "tiny", "seq_len": 32, "n_layers": 1,
                            "dim": 32, "n_heads": 4, "n_kv_heads": 2,
                            "vocab_size": 64,
                        },
                    },
                    "data": {
                        "name": "synthetic_lm", "batchSize": 4,
                        "config": {"seq_len": 32, "vocab_size": 64},
                    },
                    "optimizer": {"name": "adamw", "learningRate": 0.001},
                    "train": {
                        "steps": 1, "logEvery": 1, "precision": "float32",
                        "checkpointEvery": 1,
                    },
                    "serving": {
                        "maxBatch": 3, "maxWaitMs": 7.0, "maxQueue": 11,
                        "breakerThreshold": 4,
                    },
                },
            },
        },
    }
    p = tmp_path / "pinned.yaml"
    p.write_text(yaml.safe_dump(spec))
    store = RunStore()
    compiled = compile_operation(read_polyaxonfile(str(p)))
    assert Executor(store, devices=jax.devices()[:1]).execute(compiled) == (
        "succeeded"
    )
    close_all()

    server = ModelServer.from_run(
        compiled.run_uuid, store=store,
        config_overrides={"max_queue": 2, "default_deadline_ms": 123.0},
    )
    assert server.config.max_queue == 2            # overridden
    assert server.config.default_deadline_ms == 123.0
    assert server.config.max_batch == 3            # spec pins survive
    assert server.config.max_wait_ms == 7.0
    assert server.config.breaker_threshold == 4

    # and with no overrides the spec config is used verbatim
    server2 = ModelServer.from_run(compiled.run_uuid, store=store)
    assert server2.config.max_queue == 11


# -------------------------------------------------------- store satellites
def test_write_json_survives_and_is_atomic(tmp_path):
    from polyaxon_tpu.store.local import _read_json, _write_json

    p = tmp_path / "status.json"
    _write_json(p, {"status": "running", "n": 1})
    assert _read_json(p) == {"status": "running", "n": 1}
    assert not p.with_suffix(".tmp").exists()  # no droppings
    _write_json(p, {"status": "succeeded", "n": 2})
    assert _read_json(p)["status"] == "succeeded"


def test_read_json_quarantines_corrupt_file(tmp_path, caplog):
    import logging

    from polyaxon_tpu.store.local import _read_json

    p = tmp_path / "status.json"
    p.write_text('{"status": "runni')  # torn write
    with caplog.at_level(logging.WARNING, logger="polyaxon_tpu.store.local"):
        assert _read_json(p) is None
    assert not p.exists()
    quarantined = tmp_path / "status.json.corrupt"
    assert quarantined.exists()
    assert quarantined.read_text() == '{"status": "runni'  # bytes preserved
    assert any("quarantined" in r.getMessage() for r in caplog.records)
    # a fresh status can now be written over the vacated name
    assert _read_json(p) is None


def test_read_json_quarantine_shields_run_status(tmp_home):
    # end to end: a torn status.json must not wedge get_status
    from polyaxon_tpu.store.local import RunStore

    store = RunStore()
    store.create_run("u1" * 16, "torn", "proj", {"component": {"name": "x"}})
    uuid = "u1" * 16
    (store.run_dir(uuid) / "status.json").write_text("\x00garbage\x00")
    status = store.get_status(uuid)  # would raise before the quarantine
    assert status == {}
    assert (store.run_dir(uuid) / "status.json.corrupt").exists()
