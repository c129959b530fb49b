"""ISSUE 9 coverage: per-request tracing, the SLO burn-rate engine, and
the slow-request flight recorder.

Unit layer: RequestTrace/TraceRing semantics under a fake clock,
histogram exemplars + count_le, burn-rate math at explicit evaluate
times (the breach edge fires exactly once, re-arms after recovery),
FlightRecorder bundle layout + dump limit, V1SLOSpec validation, and
the server's error->reason/status mapping for every shed class.

Live-HTTP layer (pytest.mark.serving, tiny models): X-Request-Id
round-trips every status class with the pinned structured error schema,
SSE frames carry the id, coalesced rows share a decode-group span id,
the /tracez span timeline sums to the observed latency (the 10%%
acceptance bound), the tail sampler keeps a deadline shed alive under
an ok flood with a 4-slot ring, a seeded overload flips /sloz and
writes a flight-recorder bundle, and `polyaxon stats --slo --traces` /
`polyaxon trace` read the live surfaces.
"""

import http.client
import json
import threading

import numpy as np
import pytest

from polyaxon_tpu.telemetry import (
    AvailabilityObjective,
    FlightRecorder,
    LatencyObjective,
    MetricsRegistry,
    RequestTrace,
    SLOEngine,
    TraceRing,
    build_objectives,
    new_trace_id,
)

# ---------------------------------------------------------------- unit


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt
        return self.t


def test_new_trace_id_shape():
    a, b = new_trace_id(), new_trace_id()
    assert a != b
    assert len(a) == 16 and int(a, 16) >= 0  # 16 hex chars


def test_request_trace_spans_groups_and_idempotent_finish():
    clk = FakeClock()
    tr = RequestTrace("abc", clock=clk, model="tiny", stream=False)
    clk.tick(0.25)
    tr.add("queue_wait", start=100.0, dur_s=0.25)
    tr.annotate("kv_plan", pages=3)  # zero-duration, stamped "now"
    tr.set_group(7)
    tr.set_group(7)  # de-duplicated
    clk.tick(0.75)
    tr.add("decode", start=100.25, dur_s=0.75, tokens=8)
    assert not tr.finished
    tr.finish()
    assert tr.finished and tr.dur_s == pytest.approx(1.0)
    tr.finish("error", error="late")  # first finish wins
    d = tr.to_dict()
    assert d["id"] == "abc" and d["status"] == "ok"
    assert "error" not in d
    assert d["dur_ms"] == pytest.approx(1000.0)
    assert d["group_span_ids"] == [7]
    assert d["attrs"] == {"model": "tiny", "stream": False}
    names = [s["name"] for s in d["spans"]]
    assert names == ["queue_wait", "kv_plan", "decode"]
    qw, plan, dec = d["spans"]
    assert qw["start_s"] == pytest.approx(0.0)
    assert qw["dur_s"] == pytest.approx(0.25)
    assert plan["start_s"] == pytest.approx(0.25) and plan["dur_s"] == 0.0
    assert plan["attrs"] == {"pages": 3}
    assert dec["start_s"] == pytest.approx(0.25)
    # offsets are clamped: a span can never start before the trace
    early = tr.add("early", start=0.0, dur_s=0.1)
    assert early["start_s"] == 0.0


def test_request_trace_error_status():
    clk = FakeClock()
    tr = RequestTrace("bad", clock=clk)
    clk.tick(0.1)
    tr.finish("shed:deadline", error="deadline already expired")
    d = tr.to_dict()
    assert d["status"] == "shed:deadline"
    assert d["error"] == "deadline already expired"


def _tdict(tid, status="ok", dur_ms=1.0):
    return {
        "id": tid, "status": status, "dur_ms": dur_ms,
        "group_span_ids": [], "attrs": {}, "spans": [],
    }


def test_trace_ring_tail_sampling_retention():
    ring = TraceRing(capacity=4, error_capacity=4, slow_capacity=2)
    ring.record(_tdict("err-1", status="shed:deadline", dur_ms=5.0))
    ring.record(_tdict("slow-1", dur_ms=999.0))
    for i in range(20):  # the ok flood that must NOT evict err/slow
        ring.record(_tdict(f"ok-{i}", dur_ms=1.0))
    assert ring.get("err-1")["status"] == "shed:deadline"
    assert ring.get("slow-1")["dur_ms"] == 999.0
    assert ring.get("ok-3") is None  # recent window slid past it
    recent = ring.list(4, sort="recent")
    assert [t["id"] for t in recent] == ["ok-19", "ok-18", "ok-17", "ok-16"]
    assert ring.list(1, sort="slowest")[0]["id"] == "slow-1"
    assert [t["id"] for t in ring.list(10, sort="errors")] == ["err-1"]
    with pytest.raises(ValueError):
        ring.list(5, sort="bogus")
    st = ring.stats()
    assert st["recorded"] == 22 and st["capacity"] == 4
    assert st["errors"] == 1
    assert st["retained"] == len(ring) == len(ring.dump())
    # every retained trace is reachable by id
    for t in ring.dump():
        assert ring.get(t["id"]) is not None


def test_trace_ring_records_live_traces():
    clk = FakeClock()
    ring = TraceRing(capacity=8)
    tr = RequestTrace("live", clock=clk)
    clk.tick(0.5)
    tr.finish()
    ring.record(tr)  # RequestTrace objects are admitted via to_dict
    assert ring.get("live")["dur_ms"] == pytest.approx(500.0)


def test_histogram_exemplars_and_count_le():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=[0.05, 0.1, 0.5])
    for _ in range(10):
        h.observe(0.01, exemplar="fast-req")
    for _ in range(10):
        h.observe(0.4, exemplar="slow-req")
    assert h.count == 20
    # interpolated cumulative count at the bucket edge is exact
    assert h.count_le(0.1) == pytest.approx(10.0)
    assert h.count_le(10.0) == pytest.approx(20.0)
    ex = h.exemplar(0.99)
    assert ex == {"value": 0.4, "trace_id": "slow-req"}


def test_availability_burn_math_and_breach_edge_fires_once():
    reg = MetricsRegistry()
    bad = reg.counter("bad")
    total = reg.counter("total")
    obj = AvailabilityObjective(
        "avail", 0.99, bad=[bad], total=[total], windows_s=(60.0, 300.0)
    )
    fired = []
    eng = SLOEngine([obj], reg, on_breach=fired.append, clock=lambda: 0.0)

    r = eng.evaluate(t=0.0)[0]  # baseline, no traffic
    assert r["burn_rate"] == 0.0 and not r["breached"]

    total.inc(100)
    r = eng.evaluate(t=30.0)[0]  # clean traffic burns nothing
    assert r["burn_rate"] == 0.0 and not fired

    bad.inc(5)
    total.inc(5)
    r = eng.evaluate(t=60.0)[0]
    # 5 bad / 105 total over a 1% budget -> ~4.76x in both windows
    assert r["burn_rate"] == pytest.approx(5 / 105 / 0.01)
    assert set(r["burn_rates"]) == {"60s", "300s"}
    assert r["breached"] is True
    assert len(fired) == 1 and fired[0]["name"] == "avail"

    snap = reg.snapshot()
    assert snap["slo.breached"] == 1.0
    assert snap["slo.burn_rate"] == pytest.approx(5 / 105 / 0.01)
    assert snap["slo.breached.avail"] == 1.0

    eng.evaluate(t=90.0)  # still breached: the edge must NOT re-fire
    assert len(fired) == 1

    # windows slide past the error burst -> recovery
    r = eng.evaluate(t=600.0)[0]
    assert not r["breached"]
    assert reg.snapshot()["slo.breached"] == 0.0

    bad.inc(2)
    total.inc(2)
    r = eng.evaluate(t=630.0)[0]  # a NEW burst re-arms the edge
    assert r["breached"] and len(fired) == 2


def test_breach_requires_every_window_and_real_traffic():
    reg = MetricsRegistry()
    bad = reg.counter("b")
    total = reg.counter("t")
    obj = AvailabilityObjective(
        "a", 0.99, bad=[bad], total=[total], windows_s=(10.0, 100.0)
    )
    eng = SLOEngine([obj], reg, clock=lambda: 0.0)
    eng.evaluate(t=0.0)
    bad.inc(10)
    total.inc(10)
    eng.evaluate(t=50.0)
    # short window slides clean while the long window still sees the
    # burst: effective burn = min across windows = 0 -> no breach
    r = eng.evaluate(t=70.0)[0]
    assert r["burn_rates"]["10s"] == 0.0
    assert r["burn_rates"]["100s"] > 1.0
    assert r["burn_rate"] == 0.0 and not r["breached"]


def test_latency_objective_counts_slow_requests():
    reg = MetricsRegistry()
    h = reg.histogram("req", buckets=[0.05, 0.1, 0.5])
    obj = LatencyObjective("p", 0.95, histogram=h, threshold_ms=100.0)
    for _ in range(10):
        h.observe(0.01)
    for _ in range(10):
        h.observe(0.4)
    b, t = obj.sample()
    assert (b, t) == (pytest.approx(10.0), 20.0)
    assert obj.describe()["threshold_ms"] == 100.0
    with pytest.raises(ValueError):
        LatencyObjective("x", 0.95, histogram=h, threshold_ms=0)


def test_objective_validation():
    reg = MetricsRegistry()
    c = reg.counter("c")
    with pytest.raises(ValueError):
        AvailabilityObjective("x", 1.5, bad=[c], total=[c])
    with pytest.raises(ValueError):
        AvailabilityObjective("x", 0.9, bad=[c], total=[c],
                              windows_s=(300.0, 60.0))
    with pytest.raises(ValueError):
        AvailabilityObjective("x", 0.9, bad=[c], total=[c],
                              burn_threshold=0.0)


def test_build_objectives_binds_kinds_and_rejects_unknown():
    reg = MetricsRegistry()
    bad, total = reg.counter("bad"), reg.counter("total")
    h = reg.histogram("lat")
    objs = build_objectives(
        [
            {"name": "avail", "kind": "availability", "objective": 0.999},
            {"name": "p99", "kind": "latency", "objective": 0.99,
             "threshold_ms": 250.0, "windows": [30.0, 120.0],
             "burn_threshold": 2.0},
        ],
        bad=[bad], total=[total], histogram=h,
    )
    assert isinstance(objs[0], AvailabilityObjective)
    assert isinstance(objs[1], LatencyObjective)
    assert objs[1].windows_s == (30.0, 120.0)
    assert objs[1].burn_threshold == 2.0
    with pytest.raises(ValueError):
        build_objectives(
            [{"name": "x", "kind": "throughput", "objective": 0.9}],
            bad=[bad], total=[total], histogram=h,
        )


def test_flight_recorder_bundle_layout_and_limit(tmp_path):
    ring = TraceRing(capacity=8)
    ring.record(_tdict("boom", status="error", dur_ms=50.0))
    ring.record(_tdict("fine", dur_ms=1.0))
    reg = MetricsRegistry()
    reg.counter("reqs").inc(3)
    fr = FlightRecorder(
        tmp_path, registry=reg, trace_ring=ring,
        state_fn=lambda: {"queue_depth": 2}, limit=2,
    )
    d = fr.dump({"name": "avail", "burn_rate": 7.0, "edge": True})
    assert d is not None and d.is_dir()
    breach = json.loads((d / "breach.json").read_text())
    assert breach["name"] == "avail" and "edge" not in breach
    # the picked trace is the most recent ERROR, linked from breach.json
    assert breach["trace_id"] == "boom"
    assert json.loads((d / "trace.json").read_text())["id"] == "boom"
    lines = (d / "traces.jsonl").read_text().splitlines()
    assert {json.loads(ln)["id"] for ln in lines} == {"boom", "fine"}
    assert json.loads((d / "metrics.json").read_text())["reqs"] == 3
    assert json.loads((d / "state.json").read_text()) == {"queue_depth": 2}
    assert fr.dump({"name": "avail"}) is not None
    assert fr.dump({"name": "avail"}) is None  # bounded per process
    assert len(fr.dumps) == 2


def test_v1_slo_spec_validation_and_to_config():
    from polyaxon_tpu.schemas.run_kinds import V1ObservabilitySpec, V1SLOSpec

    s = V1SLOSpec(name="availability")
    assert s.kind == "availability" and s.objective == 0.999
    cfg = s.to_config()
    assert cfg["name"] == "availability" and cfg["kind"] == "availability"
    assert "threshold_ms" not in cfg and "windows" not in cfg

    lat = V1SLOSpec.from_dict(
        {"name": "p99", "kind": "latency", "objective": 0.99,
         "thresholdMs": 250, "windows": [30, 120], "burnThreshold": 2}
    )
    cfg = lat.to_config()
    assert cfg["threshold_ms"] == 250 and cfg["windows"] == [30, 120]
    assert cfg["burn_threshold"] == 2

    with pytest.raises(ValueError):  # latency needs the split point
        V1SLOSpec(name="p", kind="latency")
    with pytest.raises(ValueError):  # thresholdMs is latency-only
        V1SLOSpec(name="a", threshold_ms=100)
    with pytest.raises(ValueError):
        V1SLOSpec(name="a", objective=1.2)
    with pytest.raises(ValueError):  # windows must ascend
        V1SLOSpec(name="a", windows=[300, 60])
    with pytest.raises(ValueError):
        V1SLOSpec(name="a", burn_threshold=0)

    obs = V1ObservabilitySpec.from_dict(
        {"slos": [{"name": "availability", "objective": 0.999}]}
    )
    assert obs.slos[0].name == "availability"


def test_error_reason_and_trace_status_cover_every_shed_class():
    from polyaxon_tpu.serving.batching import (
        DeadlineExceededError,
        ServerClosingError,
        ServingError,
        ShedError,
    )
    from polyaxon_tpu.serving.server import _error_reason, _trace_status

    for reason in ("queue_full", "breaker_open", "deadline", "draining",
                   "kv_pages"):
        e = ShedError("x", reason=reason)
        assert _error_reason(e) == reason
        assert _trace_status(e) == f"shed:{reason}"
    closing = ServerClosingError()
    assert _error_reason(closing) == "closing"
    assert _trace_status(closing) == "shed:closing"
    assert _error_reason(DeadlineExceededError("x")) == "deadline_exceeded"
    assert _trace_status(DeadlineExceededError("x")) == "deadline_exceeded"
    assert _error_reason(ServingError("x")) == "invalid_request"
    assert _trace_status(ServingError("x")) == "invalid_request"
    assert _error_reason(TimeoutError("x")) == "timeout"
    assert _trace_status(TimeoutError("x")) == "timeout"
    assert _error_reason(RuntimeError("x")) == "internal"
    assert _trace_status(RuntimeError("x")) == "error"
    assert _trace_status(None) == "ok"


# ----------------------------------------------------------- live HTTP

CFG = {
    "preset": "tiny", "seq_len": 64, "n_layers": 2, "dim": 64,
    "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128,
}

#: the structured error body every non-200 /generate response carries —
#: contract for log correlation; renaming a key silently breaks callers
ERROR_SCHEMA = {"error", "reason", "requestId"}


def _build():
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import build_model

    b = build_model("transformer_lm", CFG)
    params = b.module.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    return b.module, params


def _server(module, params, **kw):
    from polyaxon_tpu.serving.batching import ServingConfig
    from polyaxon_tpu.serving.server import ModelServer

    server_kw = {
        k: kw.pop(k)
        for k in ("slos", "debug_dir", "registry")
        if k in kw
    }
    cfg = ServingConfig(**{
        "max_batch": 4, "max_wait_ms": 2.0, "kv_page_tokens": 8,
        "stream_chunk_tokens": 3, **kw,
    })
    return ModelServer(
        module, params, model_name="tiny", config=cfg, **server_kw
    )


@pytest.fixture(scope="module")
def servers():
    module, params = _build()
    paged = _server(module, params, kv_pool_pages=64)
    port = paged.start(port=0)
    yield {"paged": port, "srv": paged, "module": module, "params": params}
    paged.stop()


def _post(port, body, headers=None, path="/generate", timeout=120):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", path, json.dumps(body), headers=headers or {})
    r = c.getresponse()
    raw = r.read()
    c.close()
    try:
        payload = json.loads(raw)
    except (ValueError, UnicodeDecodeError):
        payload = raw
    return r.status, payload, {k: v for k, v in r.getheaders()}


def _get(port, path, timeout=60):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("GET", path)
    r = c.getresponse()
    raw = r.read()
    c.close()
    try:
        return r.status, json.loads(raw)
    except (ValueError, UnicodeDecodeError):
        return r.status, raw


def _body(n_rows=1, max_new=6, seed=123):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, 100, size=12).tolist() for _ in range(n_rows)]
    return {
        "tokens": prompts, "maxNewTokens": max_new, "temperature": 0.0,
        "seed": seed,
    }


@pytest.mark.serving
def test_request_id_accept_or_assign(servers):
    # caller-supplied id is echoed in body AND header
    st, payload, hdrs = _post(
        servers["paged"], _body(), headers={"X-Request-Id": "my-req-1"}
    )
    assert st == 200, payload
    assert payload["requestId"] == "my-req-1"
    assert hdrs["X-Request-Id"] == "my-req-1"
    # no id supplied -> a fresh 16-hex id is assigned and echoed
    st, payload, hdrs = _post(servers["paged"], _body(seed=7))
    assert st == 200
    rid = hdrs["X-Request-Id"]
    assert len(rid) == 16 and int(rid, 16) >= 0
    assert payload["requestId"] == rid
    # the id resolves to a full span timeline on /tracez
    st, tr = _get(servers["paged"], "/tracez?id=my-req-1")
    assert st == 200 and tr["id"] == "my-req-1"
    assert tr["status"] == "ok" and tr["spans"]


@pytest.mark.serving
def test_structured_error_schema_400_503_504_500(servers, monkeypatch):
    port, srv = servers["paged"], servers["srv"]

    # 400 invalid: client error, pinned schema
    st, p, hdrs = _post(port, {"tokens": "nope"},
                        headers={"X-Request-Id": "bad-1"})
    assert st == 400 and set(p) == ERROR_SCHEMA, p
    assert p["reason"] == "invalid_request" and p["requestId"] == "bad-1"
    assert hdrs["X-Request-Id"] == "bad-1"

    # 503 deadline shed: Retry-After + reason from the shed class
    st, p, hdrs = _post(port, {**_body(), "deadlineMs": 1e-6},
                        headers={"X-Request-Id": "dead-1"})
    assert st == 503 and set(p) == ERROR_SCHEMA, p
    assert p["reason"] == "deadline" and p["requestId"] == "dead-1"
    assert int(hdrs["Retry-After"]) >= 1

    # 503 draining: admission closed while the server drains
    monkeypatch.setattr(srv, "_draining", True)
    st, p, _ = _post(port, _body())
    assert st == 503 and set(p) == ERROR_SCHEMA, p
    assert p["reason"] == "draining"
    monkeypatch.setattr(srv, "_draining", False)

    # 504 timeout and 500 internal: the handler looks handle_request up
    # on the server instance per call, so instance patching reaches it
    monkeypatch.setattr(
        srv, "handle_request",
        lambda body, request_id=None: (_ for _ in ()).throw(
            TimeoutError("decode timed out")
        ),
    )
    st, p, _ = _post(port, _body())
    assert st == 504 and set(p) == ERROR_SCHEMA, p
    assert p["reason"] == "timeout"

    monkeypatch.setattr(
        srv, "handle_request",
        lambda body, request_id=None: (_ for _ in ()).throw(
            RuntimeError("boom")
        ),
    )
    st, p, _ = _post(port, _body())
    assert st == 500 and set(p) == ERROR_SCHEMA, p
    assert p["reason"] == "internal" and "boom" in p["error"]


@pytest.mark.serving
def test_sse_frames_carry_request_id(servers):
    c = http.client.HTTPConnection("127.0.0.1", servers["paged"], timeout=120)
    c.request(
        "POST", "/generate?stream=1", json.dumps(_body(max_new=7)),
        headers={"X-Request-Id": "sse-1"},
    )
    r = c.getresponse()
    assert r.status == 200
    assert r.getheader("X-Request-Id") == "sse-1"
    events, buf = [], b""
    while True:
        data = r.read(64)
        if not data:
            break
        buf += data
        while b"\n\n" in buf:
            frame, buf = buf.split(b"\n\n", 1)
            events.append(json.loads(frame[len(b"data: "):]))
    c.close()
    assert events and events[-1].get("done") is True
    assert all(ev["requestId"] == "sse-1" for ev in events)
    st, tr = _get(servers["paged"], "/tracez?id=sse-1")
    assert st == 200 and tr["attrs"].get("stream") is True
    assert "stream_flush" in [s["name"] for s in tr["spans"]]


@pytest.mark.serving
def test_tracez_listing_and_errors(servers):
    st, data = _get(servers["paged"], "/tracez")
    assert st == 200 and data["traces"]
    assert {"recorded", "retained", "errors", "capacity"} <= data.keys()
    first = data["traces"][0]
    assert {"id", "status", "dur_ms", "spans"} <= first.keys()
    st, _ = _get(servers["paged"], "/tracez?id=no-such-trace")
    assert st == 404
    st, p = _get(servers["paged"], "/tracez?sort=bogus")
    assert st == 400 and "sort" in p["error"]
    st, data = _get(servers["paged"], "/tracez?n=1&sort=slowest")
    assert st == 200 and len(data["traces"]) == 1


@pytest.mark.serving
def test_span_timeline_sums_to_observed_latency(servers):
    st, _, _ = _post(servers["paged"], _body(seed=42, max_new=8),
                     headers={"X-Request-Id": "timeline-1"})
    assert st == 200
    st, tr = _get(servers["paged"], "/tracez?id=timeline-1")
    assert st == 200
    names = [s["name"] for s in tr["spans"]]
    for expected in ("admission", "queue_wait", "prefill", "decode",
                     "stream_flush"):
        assert expected in names, names
    # acceptance bound: the spans partition the request — their sum
    # lands within 10% of the latency the client observed
    span_ms = sum(s["dur_s"] for s in tr["spans"]) * 1e3
    assert tr["dur_ms"] > 0
    assert abs(span_ms - tr["dur_ms"]) <= 0.10 * tr["dur_ms"], (
        span_ms, tr["dur_ms"], names,
    )
    # every span starts inside the request window
    for s in tr["spans"]:
        assert 0.0 <= s["start_s"] * 1e3 <= tr["dur_ms"] + 1e-6


@pytest.mark.serving
def test_coalesced_rows_share_decode_group_span(servers):
    # a dedicated server with a generous coalescing window so two
    # concurrent single-row posts land in ONE decode group
    srv = _server(servers["module"], servers["params"],
                  kv_pool_pages=64, max_wait_ms=250.0)
    port = srv.start(port=0)
    try:
        results = {}

        def run(rid):
            body = _body(seed=9, max_new=5)
            results[rid] = _post(port, body,
                                 headers={"X-Request-Id": rid})

        threads = [
            threading.Thread(target=run, args=(rid,))
            for rid in ("co-a", "co-b")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results[r][0] == 200 for r in results), results
        groups = {}
        for rid in ("co-a", "co-b"):
            st, tr = _get(port, f"/tracez?id={rid}")
            assert st == 200
            groups[rid] = set(tr["group_span_ids"])
            assert groups[rid], tr
        assert groups["co-a"] & groups["co-b"], groups
    finally:
        srv.stop()


@pytest.mark.serving
def test_tail_sampler_keeps_deadline_shed_under_ok_flood(servers):
    # 4-slot recent window: the ok flood evicts ok traces, never the shed
    srv = _server(servers["module"], servers["params"],
                  kv_pool_pages=64, trace_ring=4)
    port = srv.start(port=0)
    try:
        st, p, _ = _post(port, {**_body(), "deadlineMs": 1e-6},
                         headers={"X-Request-Id": "shed-keep"})
        assert st == 503 and p["reason"] == "deadline"
        for i in range(10):
            st, _, _ = _post(port, _body(seed=i))
            assert st == 200
        st, tr = _get(port, "/tracez?id=shed-keep")
        assert st == 200, "tail sampler evicted the shed trace"
        assert tr["status"] == "shed:deadline"
        st, data = _get(port, "/tracez?sort=errors")
        assert st == 200
        assert "shed-keep" in [t["id"] for t in data["traces"]]
    finally:
        srv.stop()


@pytest.mark.serving
def test_slo_breach_flips_sloz_and_writes_flight_recorder(
    servers, tmp_path
):
    slos = [{"name": "availability", "kind": "availability",
             "objective": 0.999, "windows": [5.0, 30.0]}]
    srv = _server(servers["module"], servers["params"],
                  kv_pool_pages=64, slos=slos, debug_dir=str(tmp_path))
    port = srv.start(port=0)
    try:
        st, sloz = _get(port, "/sloz")  # baseline sample, nothing burning
        assert st == 200 and sloz["enabled"] and not sloz["breached"]
        st, _, _ = _post(port, _body())
        assert st == 200
        for _ in range(4):  # seeded overload: 4/5 requests shed
            st, p, _ = _post(port, {**_body(), "deadlineMs": 1e-6})
            assert st == 503 and p["reason"] == "deadline"
        st, sloz = _get(port, "/sloz")
        assert st == 200 and sloz["breached"] is True
        (s,) = sloz["slos"]
        assert s["name"] == "availability" and s["breached"]
        assert s["burn_rate"] > 1.0 and s["bad"] >= 4
        assert set(s["burn_rates"]) == {"5s", "30s"}
        # the gauges reach /metricsz for alerting
        st, text = _get(port, "/metricsz")
        text = text.decode()
        assert "slo_burn_rate" in text and "slo_breached 1" in text
        # the breach edge dumped a post-mortem bundle under debug/
        bundles = sorted(tmp_path.glob("slo-*-availability"))
        assert bundles, list(tmp_path.iterdir())
        assert (bundles[0] / "breach.json").exists()
        assert (bundles[0] / "traces.jsonl").read_text().strip()
        assert (bundles[0] / "metrics.json").exists()
        state = json.loads((bundles[0] / "state.json").read_text())
        assert "queue" in state or "kv" in state, state
        st, stats = _get(port, "/statsz")
        assert stats["slo"]["flight_recorder_dumps"] == [str(bundles[0])]
    finally:
        srv.stop()


@pytest.mark.serving
def test_cli_stats_and_trace_read_live_surfaces(servers):
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli

    st, _, _ = _post(servers["paged"], _body(seed=3),
                     headers={"X-Request-Id": "cli-req-1"})
    assert st == 200
    url = f"http://127.0.0.1:{servers['paged']}"
    runner = CliRunner()

    res = runner.invoke(
        cli, ["stats", "--url", url, "--slo", "--traces", "3"]
    )
    assert res.exit_code == 0, res.output
    assert "tracing: on" in res.output
    assert "cli-req-1" in res.output

    res = runner.invoke(cli, ["trace", "--url", url, "-n", "5"])
    assert res.exit_code == 0, res.output
    assert "cli-req-1" in res.output

    res = runner.invoke(cli, ["trace", "cli-req-1", "--url", url])
    assert res.exit_code == 0, res.output
    assert "trace cli-req-1" in res.output
    for name in ("queue_wait", "prefill", "decode"):
        assert name in res.output

    res = runner.invoke(cli, ["trace", "no-such-id", "--url", url])
    assert res.exit_code != 0  # 404 -> clean CLI error, not a traceback

    # --slo/--traces are live-surface flags: without --url they error
    res = runner.invoke(cli, ["stats", "--slo"])
    assert res.exit_code != 0
