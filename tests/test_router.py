"""Router + replica-fleet coverage (ISSUE 10), over live HTTP against
tiny models and scriptable fake upstreams:

  * JSQ/P2C balancing units: deterministic seeded picks, score ordering,
    the retry ladder;
  * the Prometheus scrape parser the balancer feeds on;
  * V1ServingSpec replicas/meshAxes validators, to_config plumbing, and
    the V1JAXJob meshAxes-vs-resources.chips cross-check;
  * replica child argv translation (fleet mode reuses `polyaxon serve`);
  * shed-retry on a sibling (and the deadline shed that must NOT retry),
    connection-failure retry, and mid-stream failover with exact per-row
    token trimming — against fake upstreams, so every branch is forced;
  * 2-replica live routing: byte-identical responses vs a direct replica
    (greedy and seeded-sampled, streamed and not), SSE X-Request-Id
    pass-through, router series on /metricsz, `polyaxon stats --url`;
  * chaos worker-kill mid-request: the router replays on the sibling and
    the client never sees the crash;
  * ReplicaSetManager: crash restart under the retry taxonomy, fleet
    reservations per slot, scale up/down, rolling redeploy with zero
    failed requests under concurrent traffic;
  * tensor-parallel decode: a batch×model mesh serves byte-identical
    tokens to single-device serving.
"""

import http.client
import json
import socket
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from polyaxon_tpu.serving.router import (
    AutoscalePolicy,
    P2CBalancer,
    ReplicaState,
    Router,
    parse_prometheus,
)
from polyaxon_tpu.telemetry import now

pytestmark = pytest.mark.serving

CFG = {
    "preset": "tiny", "seq_len": 64, "n_layers": 2, "dim": 64,
    "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128,
}


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


def _server(module, params, **overrides):
    from polyaxon_tpu.serving.batching import ServingConfig
    from polyaxon_tpu.serving.server import ModelServer

    cfg = ServingConfig(**{
        "max_batch": 4, "max_wait_ms": 2.0, "kv_page_tokens": 8,
        "kv_pool_pages": 64, "stream_chunk_tokens": 3, **overrides,
    })
    return ModelServer(module, params, model_name="tiny", config=cfg)


def _post(port, body, path="/generate", rid=None, timeout=120):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    headers = {"Content-Type": "application/json"}
    if rid is not None:
        headers["X-Request-Id"] = rid
    c.request("POST", path, body if isinstance(body, (bytes, str))
              else json.dumps(body), headers)
    r = c.getresponse()
    out = r.read()
    hdrs = dict(r.getheaders())
    c.close()
    return r.status, out, hdrs


def _get(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=60
    ).read()


def _frames(raw: bytes) -> list[dict]:
    return [
        json.loads(f[len(b"data: "):])
        for f in raw.split(b"\n\n")
        if f.startswith(b"data: ")
    ]


def _row_tokens(frames: list[dict]) -> dict[int, list[int]]:
    out: dict[int, list[int]] = {}
    for ev in frames:
        if "row" in ev and "tokens" in ev:
            out.setdefault(ev["row"], []).extend(ev["tokens"])
    return out


# --------------------------------------------------------------- units
def test_parse_prometheus():
    text = (
        "# HELP serving_queue_depth depth\n"
        "# TYPE serving_queue_depth gauge\n"
        "serving_queue_depth 3\n"
        "serving_queue_wait_seconds_sum 0.25\n"
        "serving_queue_wait_seconds_count 10\n"
        "bad line with words\n"
        "router_requests_total 7\n"
    )
    m = parse_prometheus(text)
    assert m["serving_queue_depth"] == 3.0
    assert m["serving_queue_wait_seconds_sum"] == 0.25
    assert m["router_requests_total"] == 7.0
    assert "bad" not in m


def _state(url, depth=0.0, wait=0.0, inflight=0):
    s = ReplicaState(url=url, slug=url[-2:], healthy=True)
    s.queue_depth, s.queue_wait_ms, s.inflight = depth, wait, inflight
    return s


def test_p2c_pick_prefers_shorter_queue():
    a = _state("http://a/r0", depth=5.0)
    b = _state("http://b/r1", depth=0.0)
    bal = P2CBalancer(seed=0)
    # <=2 candidates: pure JSQ, no sampling
    assert bal.pick([a, b]) is b
    # in-flight counts weigh the same as scraped depth
    b.inflight = 7
    assert bal.pick([a, b]) is a
    # queue-wait breaks depth ties
    b.inflight = 5
    b.queue_wait_ms, a.queue_wait_ms = 1.0, 9.0
    assert bal.pick([a, b]) is b


def test_p2c_seeded_sampling_deterministic():
    cands = [_state(f"http://x/r{i}", depth=float(i)) for i in range(5)]
    picks1 = [P2CBalancer(seed=42).pick(cands).url for _ in range(1)]
    picks2 = [P2CBalancer(seed=42).pick(cands).url for _ in range(1)]
    assert picks1 == picks2  # same seed, same sample
    # the P2C winner always beats at least one sampled loser: it can
    # never be the strictly worst of the sampled pair
    seq = [P2CBalancer(seed=7).pick(cands) for _ in range(20)]
    assert all(s is not None for s in seq)


def test_p2c_order_is_retry_ladder():
    cands = [_state(f"http://x/r{i}", depth=float(9 - i)) for i in range(4)]
    order = P2CBalancer(seed=3).order(cands)
    assert len(order) == 4 and len(set(id(s) for s in order)) == 4
    # after the P2C head, the rest are strictly score-sorted
    tail = order[1:]
    assert tail == sorted(tail, key=ReplicaState.score)
    assert P2CBalancer().order([]) == []


def test_retryable_matrix():
    r = Router([])
    shed = json.dumps({"error": "x", "reason": "queue"}).encode()
    deadline = json.dumps({"error": "x", "reason": "deadline"}).encode()
    assert r._retryable(503, shed) is True
    assert r._retryable(503, deadline) is False  # budget spent everywhere
    assert r._retryable(500, b"{}") is True  # decode is deterministic
    assert r._retryable(599, b"{}") is True  # synthetic connect failure
    assert r._retryable(502, b"{}") is True
    assert r._retryable(504, b"{}") is False  # deadline, by status
    assert r._retryable(400, b"{}") is False  # client error
    assert r._retryable(200, b"{}") is False
    assert r.stats()["upstream_shed"] == 2  # both 503s counted
    # no replicas at all: a clean 503, not an exception
    status, payload, _ = r.forward(b"{}", "rid-x")
    assert status == 503 and json.loads(payload)["reason"] == "no_replicas"


class _Scaler:
    def __init__(self, target):
        self.target = target
        self.calls = []

    def scale_to(self, n):
        self.calls.append(n)
        self.target = n


def test_autoscale_scale_up_cooldown_and_clamp():
    sc = _Scaler(target=1)
    r = Router(
        [], scaler=sc,
        autoscale=AutoscalePolicy(max_replicas=3, cooldown_s=3600.0),
    )
    assert r.slo_engine is not None  # shed-burn objective is armed
    # on the router's own clock, whose zero is the host's boot: 0.0 would
    # be "inside the cooldown" on any host up for less than an hour
    r._last_scale_t = now() - 2 * 3600.0
    r._scale_up({"slo": "router-upstream-shed"})
    assert sc.calls == [2]
    r._scale_up({})  # inside cooldown: ignored
    assert sc.calls == [2]
    sc.target = 3
    r._last_scale_t = -1e9  # cooldown long past
    r._scale_up({})  # already at max: clamped, no call
    assert sc.calls == [2]


def test_autoscale_calm_window_scales_down():
    sc = _Scaler(target=2)
    r = Router(
        ["http://127.0.0.1:9"], scaler=sc,
        autoscale=AutoscalePolicy(
            min_replicas=1, cooldown_s=0.0, calm_for_s=0.05,
        ),
    )
    r.states()[0].healthy = True  # idle, zero queue → calm
    r._last_scale_t = now() - 1.0
    r._autoscale_tick()  # opens the calm window
    assert sc.calls == []
    time.sleep(0.1)
    r._autoscale_tick()  # window elapsed → scale down to min
    assert sc.calls == [1]
    r._autoscale_tick()  # at min: stays
    assert sc.calls == [1]


# ------------------------------------------------------------- schemas
def test_serving_spec_replicas_and_mesh_axes():
    import pydantic

    from polyaxon_tpu.schemas.run_kinds import V1ServingSpec

    with pytest.raises(pydantic.ValidationError, match="replicas"):
        V1ServingSpec(replicas=0)
    with pytest.raises(pydantic.ValidationError, match="meshAxes"):
        V1ServingSpec(meshAxes={})
    with pytest.raises(pydantic.ValidationError, match="batch"):
        V1ServingSpec(meshAxes={"pipeline": 2})
    with pytest.raises(pydantic.ValidationError, match="meshAxes"):
        V1ServingSpec(meshAxes={"model": 0})
    with pytest.raises(pydantic.ValidationError, match="-1"):
        V1ServingSpec(meshAxes={"batch": -1, "model": -1})

    s = V1ServingSpec(replicas=2, meshAxes={"model": 2, "batch": 2})
    assert s.chips_needed() == 4
    assert s.to_config().mesh_axes == (("batch", 2), ("model", 2))
    # legacy axes are accepted (decode_mesh folds them into batch)
    assert V1ServingSpec(meshAxes={"data": 2, "model": 2}).chips_needed() == 4
    # all-1s canonicalize to no mesh; -1 defers sizing to the host
    assert V1ServingSpec(meshAxes={"model": 1}).to_config().mesh_axes is None
    assert V1ServingSpec(meshAxes={"model": -1}).chips_needed() is None
    # unresolved {{param}} interpolations must not break parse-time checks
    assert V1ServingSpec(meshAxes={"model": "{{tp}}"}).chips_needed() is None


def test_jaxjob_mesh_axes_vs_chips_crosscheck():
    import pydantic

    from polyaxon_tpu.schemas.run_kinds import V1JAXJob

    job = {
        "kind": "jaxjob",
        "program": {
            "model": {"name": "mlp"},
            "serving": {"meshAxes": {"model": 4}},
        },
        "environment": {"resources": {"chips": 2}},
    }
    with pytest.raises(pydantic.ValidationError, match="needs 4"):
        V1JAXJob.model_validate(job)
    job["environment"]["resources"]["chips"] = 4
    assert V1JAXJob.model_validate(job).program.serving.chips_needed() == 4
    # no resources declared → nothing to cross-check against
    del job["environment"]
    V1JAXJob.model_validate(job)


def test_serve_child_argv_translation():
    from polyaxon_tpu.cli.main import _serve_child_argv

    argv = _serve_child_argv(
        "uuid1234", 8301, {"batch": 2, "model": 2},
        {"max_batch": 8, "batching": False, "speculate": True,
         "prompt_buckets": (32, 64)},
        4,
    )
    assert argv[:4] == [sys.executable, "-m", "polyaxon_tpu.cli.main",
                        "serve"]
    text = " ".join(argv)
    assert "-uid uuid1234" in text
    assert "--port 8301" in text
    assert "--mesh batch=2,model=2" in text
    assert "--expected-devices 4" in text
    assert "--max-batch 8" in text
    assert "--no-batching" in text
    assert "--speculate" in text
    assert "--buckets 32,64" in text


# ------------------------------------------------- fake-upstream forcing
def _fake_upstream(generate, tracez=None):
    """An HTTP server that looks like a healthy replica (/readyz,
    /metricsz) whose POST /generate is the scriptable `generate(handler,
    body, query)`. With `tracez` (a `rid -> trace dict or None`
    callable), GET /tracez?id= answers the stitching fetch the way a
    real replica's ring would. Returns (httpd, base_url)."""

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _json(self, code, payload):
            data = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path.startswith("/readyz"):
                self._json(200, {"ready": True, "reason": "ok"})
            elif self.path.startswith("/metricsz"):
                data = b"serving_queue_depth 0\n"
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
            elif self.path.startswith("/tracez") and tracez is not None:
                rid = self.path.partition("id=")[2]
                t = tracez(rid)
                if t is None:
                    self._json(404, {"error": f"no trace {rid!r}"})
                else:
                    self._json(200, t)
            else:
                self._json(404, {"error": "no route"})

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            _, _, query = self.path.partition("?")
            generate(self, body, query)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def _json_reply(handler, code, payload, headers=None):
    data = json.dumps(payload).encode()
    handler.send_response(code)
    handler.send_header("Content-Type", "application/json")
    handler.send_header("Content-Length", str(len(data)))
    for k, v in (headers or {}).items():
        handler.send_header(k, v)
    handler.end_headers()
    handler.wfile.write(data)


def _sse_reply(handler, events, terminal=True):
    handler.send_response(200)
    handler.send_header("Content-Type", "text/event-stream")
    handler.send_header("Connection", "close")
    handler.end_headers()
    for ev in events:
        handler.wfile.write(b"data: " + json.dumps(ev).encode() + b"\n\n")
        handler.wfile.flush()
    if terminal:
        handler.wfile.write(
            b"data: " + json.dumps({"done": True}).encode() + b"\n\n"
        )
        handler.wfile.flush()


class _FixedOrder(P2CBalancer):
    """Force the retry ladder for tests: candidates in the given URL
    order, so 'the shedding replica is tried first' is deterministic."""

    def __init__(self, urls):
        super().__init__()
        self._pos = {u: i for i, u in enumerate(urls)}

    def order(self, candidates):
        return sorted(candidates, key=lambda s: self._pos.get(s.url, 99))


def _dead_url():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return f"http://127.0.0.1:{s.getsockname()[1]}"


def test_shed_retries_on_sibling():
    shedder, surl = _fake_upstream(
        lambda h, b, q: _json_reply(
            h, 503, {"error": "queue full", "reason": "queue"},
            headers={"Retry-After": "1"},
        )
    )
    ok, ourl = _fake_upstream(
        lambda h, b, q: _json_reply(h, 200, {"ok": True})
    )
    try:
        r = Router([surl, ourl], balancer=_FixedOrder([surl, ourl]))
        r.poll_once()
        status, payload, _ = r.forward(b"{}", "rid-1")
        assert status == 200 and json.loads(payload) == {"ok": True}
        st = r.stats()
        assert st["retries"] == 1 and st["upstream_shed"] == 1
        assert st["errors"] == 0
    finally:
        shedder.shutdown()
        ok.shutdown()


def test_deadline_shed_is_not_retried():
    shedder, surl = _fake_upstream(
        lambda h, b, q: _json_reply(
            h, 503, {"error": "budget spent", "reason": "deadline"}
        )
    )
    ok, ourl = _fake_upstream(
        lambda h, b, q: _json_reply(h, 200, {"ok": True})
    )
    try:
        r = Router([surl, ourl], balancer=_FixedOrder([surl, ourl]))
        r.poll_once()
        status, payload, _ = r.forward(b"{}", "rid-2")
        # the deadline is just as expired on the sibling: relay the 503
        assert status == 503
        assert json.loads(payload)["reason"] == "deadline"
        assert r.stats()["retries"] == 0
    finally:
        shedder.shutdown()
        ok.shutdown()


def test_connection_failure_retries_on_sibling():
    dead = _dead_url()
    ok, ourl = _fake_upstream(
        lambda h, b, q: _json_reply(h, 200, {"ok": True})
    )
    try:
        # no poll: cold-start fallback must try all candidates rather
        # than bounce the request
        r = Router([dead, ourl], balancer=_FixedOrder([dead, ourl]))
        status, payload, _ = r.forward(b"{}", "rid-3")
        assert status == 200 and json.loads(payload) == {"ok": True}
        assert r.stats()["retries"] == 1
    finally:
        ok.shutdown()


def test_midstream_failover_trims_delivered_tokens():
    # upstream A dies after delivering [1,2] then [3] for row 0 (no
    # terminal done); sibling B replays the full sequence — the client
    # must see each token exactly once, [4] arriving in a trimmed frame
    dying, durl = _fake_upstream(
        lambda h, b, q: _sse_reply(
            h,
            [{"row": 0, "tokens": [1, 2]}, {"row": 0, "tokens": [3]}],
            terminal=False,
        )
    )
    full, furl = _fake_upstream(
        lambda h, b, q: _sse_reply(
            h,
            [
                {"row": 0, "tokens": [1, 2]},
                {"row": 0, "tokens": [3, 4]},
                {"row": 0, "tokens": [5]},
                {"row": 0, "done": True},
            ],
        )
    )
    try:
        r = Router([durl, furl], balancer=_FixedOrder([durl, furl]))
        r.poll_once()
        frames = [
            _frames(f)[0] for f in r.forward_stream(b"{}", "rid-4")
        ]
        assert _row_tokens(frames) == {0: [1, 2, 3, 4, 5]}
        # the overlap frame was re-serialized down to the fresh suffix
        assert {"row": 0, "tokens": [4]} in frames
        assert frames[-1] == {"done": True}
        assert sum(1 for f in frames if f.get("row") == 0 and f.get("done")) == 1
        assert not any("error" in f for f in frames)
        assert r.stats()["retries"] == 1
    finally:
        dying.shutdown()
        full.shutdown()


def test_row_error_frame_triggers_failover():
    # a worker crash scatters {"row": i, "error": ...} to every row —
    # the router must fail over, not relay the error to the client
    crashing, curl = _fake_upstream(
        lambda h, b, q: _sse_reply(
            h, [{"row": 0, "error": "decode worker crashed"}]
        )
    )
    full, furl = _fake_upstream(
        lambda h, b, q: _sse_reply(
            h, [{"row": 0, "tokens": [7, 8]}, {"row": 0, "done": True}]
        )
    )
    try:
        r = Router([curl, furl], balancer=_FixedOrder([curl, furl]))
        r.poll_once()
        frames = [
            _frames(f)[0] for f in r.forward_stream(b"{}", "rid-5")
        ]
        assert _row_tokens(frames) == {0: [7, 8]}
        assert not any("error" in f for f in frames)
        assert r.stats()["retries"] == 1
    finally:
        crashing.shutdown()
        full.shutdown()


# ---------------------------------------------------- live 2-replica rig
@pytest.fixture(scope="module")
def model():
    return _build()


@pytest.fixture(scope="module")
def rig(model):
    from polyaxon_tpu.retry import RetryPolicy
    from polyaxon_tpu.serving.replicas import (
        InProcessReplica,
        ReplicaSetManager,
    )

    module, params = model
    mgr = ReplicaSetManager(
        lambda i: InProcessReplica(lambda: _server(module, params)),
        replicas=2,
        retry=RetryPolicy(max_retries=3, backoff=0.05),
        monitor_interval_s=0.1,
    )
    router = Router(
        mgr.endpoints, balancer=P2CBalancer(seed=7), poll_interval_s=0.2
    )
    mgr.attach_router(router)
    mgr.start()
    rport = router.start("127.0.0.1", 0)
    direct = _server(module, params)
    dport = direct.start(port=0)
    yield {
        "mgr": mgr, "router": router, "rport": rport,
        "direct": direct, "dport": dport,
    }
    router.stop()
    mgr.stop()
    direct.stop()


def _bodies():
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 100, size=12).tolist() for _ in range(2)]
    greedy = {"tokens": prompts, "maxNewTokens": 8}
    sampled = {
        "tokens": prompts, "maxNewTokens": 8, "temperature": 0.8,
        "topK": 40, "seed": 123,
    }
    return greedy, sampled


def test_router_byte_identity_nonstream(rig):
    for i, body in enumerate(_bodies()):
        rid = f"rid-identity-{i}"
        raw = json.dumps(body)
        s1, o1, _ = _post(rig["dport"], raw, rid=rid)
        s2, o2, h2 = _post(rig["rport"], raw, rid=rid)
        assert s1 == 200 and s2 == 200, (s1, s2, o1, o2)
        assert o1 == o2  # bytes, not just tokens
        assert h2.get("X-Request-Id") == rid


def test_router_stream_byte_identity_and_rid(rig):
    _, sampled = _bodies()
    rid = "rid-stream-1"
    raw = json.dumps(sampled)
    s1, o1, h1 = _post(rig["dport"], raw, path="/generate?stream=1", rid=rid)
    s2, o2, h2 = _post(rig["rport"], raw, path="/generate?stream=1", rid=rid)
    assert s1 == 200 and s2 == 200
    assert o1 == o2  # frames relayed verbatim
    assert h1.get("X-Request-Id") == rid and h2.get("X-Request-Id") == rid
    frames = _frames(o2)
    assert frames and frames[-1]["done"] is True
    assert all(f["requestId"] == rid for f in frames)
    # stream suffix equals the non-stream result's new tokens
    s3, o3, _ = _post(rig["rport"], raw, rid=rid)
    assert s3 == 200
    whole = json.loads(o3)["tokens"]
    got = _row_tokens(frames)
    for i, row in enumerate(whole):
        assert got[i] == row[len(sampled["tokens"][i]):]


def test_router_observability_surfaces(rig):
    rig["router"].poll_once()
    metrics = parse_prometheus(_get(rig["rport"], "/metricsz").decode())
    for name in (
        "router_requests_total", "router_retries_total",
        "router_upstream_shed_total", "router_errors_total",
        "router_replicas_routable", "router_replica_healthy_r0",
        "router_replica_healthy_r1", "router_replica_queue_wait_ms_r0",
        "router_replica_queue_depth_r0", "router_request_seconds_count",
    ):
        assert name in metrics, name
    assert metrics["router_replicas_routable"] == 2.0
    assert metrics["router_replica_healthy_r0"] == 1.0
    st = json.loads(_get(rig["rport"], "/statsz"))
    assert st["role"] == "router" and st["routable"] == 2
    assert len(st["replicas"]) == 2
    assert st["replicas"][0]["slug"] == "r0"
    assert st["autoscale"]["enabled"] is False
    ready = json.loads(_get(rig["rport"], "/readyz"))
    assert ready["ready"] is True
    health = json.loads(_get(rig["rport"], "/healthz"))
    assert health["role"] == "router" and health["replicas"] == 2
    slo = json.loads(_get(rig["rport"], "/sloz"))
    assert slo["enabled"] is False


def test_cli_stats_against_router(rig):
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli

    res = CliRunner().invoke(
        cli, ["stats", "--url", f"http://127.0.0.1:{rig['rport']}"]
    )
    assert res.exit_code == 0, res.output
    assert '"role": "router"' in res.output
    assert '"routable"' in res.output


def test_chaos_worker_kill_fails_over_midstream(rig):
    from polyaxon_tpu.chaos.injector import active
    from polyaxon_tpu.chaos.plan import Fault, FaultPlan

    _, sampled = _bodies()
    raw = json.dumps(sampled)
    rid = "rid-chaos-1"
    # reference first, outside the armed window
    s0, o0, _ = _post(rig["dport"], raw, path="/generate?stream=1", rid=rid)
    assert s0 == 200
    want = _row_tokens(_frames(o0))
    retries_before = rig["router"].stats()["retries"]
    # the first decode batch dispatched while armed dies with the worker
    # thread (count=1: the sibling's replay must survive)
    with active(FaultPlan([Fault("serving.worker", "kill", at=0)])):
        s1, o1, _ = _post(
            rig["rport"], raw, path="/generate?stream=1", rid=rid
        )
    assert s1 == 200
    frames = _frames(o1)
    assert not any("error" in f for f in frames), frames
    assert frames[-1]["done"] is True
    assert _row_tokens(frames) == want
    assert rig["router"].stats()["retries"] >= retries_before + 1


def test_replica_crash_restart_keeps_slot(rig):
    mgr, router = rig["mgr"], rig["router"]
    before = mgr.endpoints()
    restarts0 = int(mgr._m_restarts.value)
    mgr.replica(0).kill()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and mgr.live() < 2:
        time.sleep(0.1)
    assert mgr.live() == 2
    assert int(mgr._m_restarts.value) >= restarts0 + 1
    after = mgr.endpoints()
    assert len(after) == 2
    assert after[1] == before[1]  # the sibling never moved
    router.poll_once()
    assert sum(1 for s in router.states() if s.routable) == 2
    # slugs are positional: the restarted replica keeps r0
    assert [s.slug for s in router.states()] == ["r0", "r1"]


def test_rolling_redeploy_zero_downtime(rig):
    mgr = rig["mgr"]
    results, errors = [], []
    stop = threading.Event()
    body = json.dumps({"tokens": [[5, 6, 7]], "maxNewTokens": 2})

    def client():
        while not stop.is_set():
            try:
                status, payload, _ = _post(rig["rport"], body, timeout=60)
                results.append((status, payload))
            except Exception as e:  # noqa: BLE001 — any failure is the bug
                errors.append(repr(e))

    t = threading.Thread(target=client)
    t.start()
    try:
        before = set(mgr.endpoints())
        mgr.rolling_redeploy()
        after = set(mgr.endpoints())
    finally:
        stop.set()
        t.join(timeout=60)
    assert not errors, errors[:3]
    assert results, "no traffic flowed during the redeploy"
    bad = [(s, p) for s, p in results if s != 200]
    assert not bad, bad[:3]
    assert before.isdisjoint(after)  # every replica was replaced
    rig["router"].poll_once()
    assert rig["router"].readiness() == (True, "ok")


# ----------------------------------------------- manager + fleet ledger
class _FakeFleet:
    configured = True

    def __init__(self, capacity):
        self.capacity = capacity
        self.reserved = {}

    def reserve(self, uuid, *, chips, queue=None):
        if sum(self.reserved.values()) + chips > self.capacity:
            return None
        self.reserved[uuid] = chips
        return {"uuid": uuid, "chips": chips, "queue": queue}

    def release(self, uuid):
        self.reserved.pop(uuid, None)


class _NullReplica:
    _n = 0

    def __init__(self):
        self._alive = False
        _NullReplica._n += 1
        self.url = f"http://127.0.0.1:{10000 + _NullReplica._n}"

    def start(self):
        self._alive = True
        return self.url

    def alive(self):
        return self._alive

    def stop(self, drain_grace_s=None):
        self._alive = False

    def kill(self):
        self._alive = False


def test_manager_fleet_reservations_and_scale():
    from polyaxon_tpu.retry import RetryPolicy
    from polyaxon_tpu.serving.replicas import ReplicaSetManager

    fleet = _FakeFleet(capacity=4)
    mgr = ReplicaSetManager(
        lambda i: _NullReplica(), replicas=2, fleet=fleet,
        chips_per_replica=2, name="t",
        retry=RetryPolicy(max_retries=2, backoff=0.01),
        monitor_interval_s=999.0,  # supervise manually via monitor_once
    )
    try:
        urls = mgr.start()
        assert len(urls) == 2 and mgr.live() == 2
        assert fleet.reserved == {"t-r0": 2, "t-r1": 2}
        # no capacity for a third: the grow is absorbed, not fatal
        mgr.scale_to(3)
        assert mgr.live() == 2 and mgr.target == 3
        assert len(mgr.endpoints()) == 2
        # shrink releases the highest slot's reservation
        mgr.scale_to(1)
        assert mgr.live() == 1
        assert fleet.reserved == {"t-r0": 2}
        assert len(mgr.endpoints()) == 1
        # crash restart rides the retry taxonomy and re-reserves
        mgr.replica(0).kill()
        assert mgr.live() == 0
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and mgr.live() < 1:
            mgr.monitor_once()
            time.sleep(0.02)
        assert mgr.live() == 1
        assert "t-r0" in fleet.reserved
    finally:
        mgr.stop()
    assert fleet.reserved == {}  # every slot released on stop


def test_manager_gives_up_after_retry_budget():
    from polyaxon_tpu.retry import RetryPolicy
    from polyaxon_tpu.serving.replicas import ReplicaSetManager

    class _Crasher(_NullReplica):
        def start(self):
            raise RuntimeError("boom")

    mgr = ReplicaSetManager(
        lambda i: _Crasher(), replicas=1,
        retry=RetryPolicy(max_retries=2, backoff=0.0, jitter=0.0),
        monitor_interval_s=999.0,
    )
    with pytest.raises(RuntimeError, match="boom"):
        mgr.start()
    for _ in range(10):
        mgr.monitor_once()
        time.sleep(0.01)
    # attempts are capped: the slot stays down instead of crash-looping
    assert mgr._attempts[0] > mgr.retry.max_retries
    assert mgr.live() == 0
    mgr.stop()


# ------------------------------------------------ tensor-parallel decode
def test_mesh_sharded_decode_byte_identity(model):
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices (conftest forces 8)")
    from polyaxon_tpu.models.transformer import TRANSFORMER_RULES
    from polyaxon_tpu.serving.batching import (
        ServingConfig,
        normalize_mesh_axes,
    )
    from polyaxon_tpu.serving.server import ModelServer

    module, params = model
    ref = ModelServer(
        module, params, model_name="tiny",
        config=ServingConfig(max_batch=4, max_wait_ms=1.0),
    )
    tp = ModelServer(
        module, params, model_name="tiny",
        config=ServingConfig(
            max_batch=4, max_wait_ms=1.0,
            mesh_axes=normalize_mesh_axes({"batch": 2, "model": 2}),
        ),
        sharding_rules=TRANSFORMER_RULES,
    )
    st = tp.stats()["mesh"]
    assert st["enabled"] and st["devices"] == 4
    assert st["axes"] == {"batch": 2, "model": 2}
    assert tp.stats()["mesh"] != ref.stats()["mesh"]
    assert ref.stats()["mesh"]["enabled"] is False
    greedy, sampled = _bodies()
    for body in (greedy, sampled):
        assert tp.generate(body)["tokens"] == ref.generate(body)["tokens"]
    # single-row prefill-only path through the sharded kernels
    one = dict(greedy, tokens=greedy["tokens"][:1], maxNewTokens=1)
    assert tp.generate(one)["tokens"] == ref.generate(one)["tokens"]


# --------------------------- cluster observability plane (ISSUE 13):
# cross-process trace stitching + metrics federation on the router
def _get_trace(rport, rid, timeout=8.0):
    """Poll router /tracez?id= until the trace lands in the ring (it is
    recorded in the handler's finally, a beat after the response)."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            return json.loads(_get(rport, f"/tracez?id={rid}"))
        except urllib.error.HTTPError as e:
            if e.code != 404 or time.monotonic() > deadline:
                raise
            time.sleep(0.05)


def _attempt_spans(t):
    return [s for s in t["spans"] if s["name"] == "upstream_attempt"]


def _local_span_ms(t):
    """Router-side (non-grafted) span durations, ms. Spans are
    sequential, so their sum must reconcile with the trace duration."""
    return 1000.0 * sum(
        s["dur_s"] for s in t["spans"] if not s["attrs"].get("remote")
    )


def _remote_trace(rid, status, spans, dur_ms=1.0):
    """What a replica's /tracez?id= would answer: offsets relative to
    the REMOTE trace start — stitching must re-anchor them."""
    return {
        "id": rid, "status": status, "dur_ms": dur_ms, "error": None,
        "attrs": {}, "spans": spans,
    }


def test_router_tracez_contract(rig):
    rid = "rid-contract-1"
    body = json.dumps({"tokens": [[5, 6, 7]], "maxNewTokens": 2})
    s, _, _ = _post(rig["rport"], body, rid=rid)
    assert s == 200
    t = _get_trace(rig["rport"], rid)
    assert t["id"] == rid and t["status"] == "ok"

    for sort in ("recent", "slowest", "errors"):
        page = json.loads(_get(rig["rport"], f"/tracez?sort={sort}"))
        assert "traces" in page and page["capacity"] > 0
    assert any(
        tr["id"] == rid
        for tr in json.loads(_get(rig["rport"], "/tracez"))["traces"]
    )

    with pytest.raises(urllib.error.HTTPError) as err:
        _get(rig["rport"], "/tracez?sort=bogus")
    assert err.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(rig["rport"], "/tracez?id=never-seen")
    assert err.value.code == 404

    st = json.loads(_get(rig["rport"], "/statsz"))
    assert st["tracing"]["enabled"] and st["tracing"]["stitch"]
    assert st["tracing"]["recorded"] >= 1


def test_stitched_shed_retry_fake_upstreams():
    """A shed-retry crosses two replicas: the router trace must carry
    BOTH upstream_attempt subtrees, each grafted with that replica's own
    timeline, under one request id."""
    rid = "rid-stitch-fake"
    shedder, surl = _fake_upstream(
        lambda h, b, q: _json_reply(
            h, 503, {"error": "queue full", "reason": "queue_full"}
        ),
        tracez=lambda r: _remote_trace(
            r, "shed",
            [{"name": "admission", "start_s": 0.0, "dur_s": 0.001,
              "attrs": {}}],
        ),
    )
    ok, ourl = _fake_upstream(
        lambda h, b, q: _json_reply(h, 200, {"ok": True}),
        tracez=lambda r: _remote_trace(
            r, "ok",
            [{"name": "admission", "start_s": 0.0, "dur_s": 0.001,
              "attrs": {}},
             {"name": "decode", "start_s": 0.001, "dur_s": 0.02,
              "attrs": {"group": 1}}],
            dur_ms=21.0,
        ),
    )
    r = Router([surl, ourl], balancer=_FixedOrder([surl, ourl]))
    rport = r.start("127.0.0.1", 0)
    try:
        r.poll_once()
        s, out, _ = _post(rport, "{}", rid=rid)
        assert s == 200 and json.loads(out) == {"ok": True}

        t = _get_trace(rport, rid)
        assert t["id"] == rid
        assert t["attrs"]["attempts"] == 2 and t["attrs"]["stitched"] == 2
        att = _attempt_spans(t)
        assert [a["attrs"]["attempt"] for a in att] == [0, 1]
        assert att[0]["attrs"]["remote_status"] == "shed"
        assert att[1]["attrs"]["remote_status"] == "ok"
        assert all(a["attrs"]["stitched"] for a in att)

        # grafted spans are re-anchored at their attempt's start and
        # carry the replica/attempt identity plus remote: True
        remote = [s_ for s_ in t["spans"] if s_["attrs"].get("remote")]
        assert sorted(s_["name"] for s_ in remote) == [
            "admission", "admission", "decode",
        ]
        decode = next(s_ for s_ in remote if s_["name"] == "decode")
        assert decode["attrs"]["attempt"] == 1
        assert decode["attrs"]["replica"] == att[1]["attrs"]["replica"]
        assert decode["start_s"] >= att[1]["start_s"]

        # the graft is cached in the ring: a second read re-stitches
        # nothing (the stitched counter holds still)
        stitched0 = json.loads(
            _get(rport, "/statsz")
        )["tracing"]["stitched"]
        again = _get_trace(rport, rid)
        assert len(again["spans"]) == len(t["spans"])
        assert json.loads(
            _get(rport, "/statsz")
        )["tracing"]["stitched"] == stitched0
    finally:
        r.stop()
        shedder.shutdown()
        ok.shutdown()


def test_stitch_miss_is_counted_not_fatal():
    """A replica that cannot answer the trace fetch (sampler dropped it,
    or it died) must leave a visible miss, not a broken trace."""
    rid = "rid-stitch-miss"
    ok, ourl = _fake_upstream(
        lambda h, b, q: _json_reply(h, 200, {"ok": True}),
        tracez=lambda r: None,  # 404 every time
    )
    r = Router([ourl])
    rport = r.start("127.0.0.1", 0)
    try:
        r.poll_once()
        s, _, _ = _post(rport, "{}", rid=rid)
        assert s == 200
        t = _get_trace(rport, rid)
        assert t["attrs"]["attempts"] == 1 and t["attrs"]["stitched"] == 0
        assert _attempt_spans(t)[0]["attrs"]["stitched"] is False
        assert not any(s_["attrs"].get("remote") for s_ in t["spans"])
        assert json.loads(
            _get(rport, "/statsz")
        )["tracing"]["stitch_misses"] >= 1
    finally:
        r.stop()
        ok.shutdown()


def test_live_shed_retry_one_stitched_trace(model):
    """Acceptance (ISSUE 13): a real shed-retry across two live replicas
    produces ONE router trace whose two upstream_attempt subtrees share
    the request id, with the replicas' own spans grafted in and span
    sums reconciling with the trace duration within 10%."""
    module, params = model
    # replica A admits exactly one request at a time: while a slow
    # request is in its custody, the next one sheds queue_full
    a = _server(module, params, max_queue=1)
    b = _server(module, params)
    aport = a.start(port=0)
    bport = b.start(port=0)
    urls = [f"http://127.0.0.1:{aport}", f"http://127.0.0.1:{bport}"]
    r = Router(urls, balancer=_FixedOrder(urls))
    rport = r.start("127.0.0.1", 0)
    rid = "rid-stitch-live"
    slow = json.dumps({
        "tokens": [list(range(1, 13))], "maxNewTokens": 48,
    })
    body = json.dumps({
        "tokens": [list(range(1, 13))], "maxNewTokens": 16,
    })
    try:
        r.poll_once()
        shed = False
        for _ in range(5):  # saturation is timing-based: retry the setup
            hog = threading.Thread(
                target=lambda: _post(aport, slow, timeout=120)
            )
            hog.start()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                depth = json.loads(_get(aport, "/statsz"))["queue_depth"]
                if depth >= 1:
                    break
                time.sleep(0.01)
            s, _, _ = _post(rport, body, rid=rid)
            hog.join(timeout=120)
            assert s == 200
            t = _get_trace(rport, rid)
            if t["attrs"]["attempts"] == 2:
                shed = True
                break
        assert shed, "replica A never shed: trace shows one attempt"

        att = _attempt_spans(t)
        assert att[0]["attrs"]["status"] == 503
        assert att[1]["attrs"]["status"] == 200
        assert t["attrs"]["stitched"] == 2, t["attrs"]
        assert att[0]["attrs"]["remote_status"].startswith("shed")
        assert att[1]["attrs"]["remote_status"] == "ok"
        # the replica-side decode really happened inside attempt 2
        decode = [
            s_ for s_ in t["spans"]
            if s_["name"] == "decode" and s_["attrs"].get("remote")
        ]
        assert decode and all(
            s_["attrs"]["attempt"] == 1 for s_ in decode
        )
        # router-side spans are sequential and cover the request: their
        # sum reconciles with the end-to-end duration within 10%
        assert _local_span_ms(t) >= 0.9 * t["dur_ms"], (
            _local_span_ms(t), t["dur_ms"],
        )
        assert _local_span_ms(t) <= 1.1 * t["dur_ms"]
        assert r.stats()["retries"] >= 1
    finally:
        r.stop()
        a.stop()
        b.stop()


def test_chaos_failover_one_stitched_trace(rig):
    """Acceptance (ISSUE 13): a mid-stream worker kill fails over to the
    sibling and still yields ONE router trace with both attempts under
    the same request id."""
    from polyaxon_tpu.chaos.injector import active
    from polyaxon_tpu.chaos.plan import Fault, FaultPlan

    _, sampled = _bodies()
    raw = json.dumps(sampled)
    rid = "rid-chaos-trace"
    with active(FaultPlan([Fault("serving.worker", "kill", at=0)])):
        s1, o1, _ = _post(
            rig["rport"], raw, path="/generate?stream=1", rid=rid
        )
    assert s1 == 200
    assert _frames(o1)[-1]["done"] is True

    t = _get_trace(rig["rport"], rid)
    assert t["id"] == rid and t["status"] == "ok"
    att = _attempt_spans(t)
    assert len(att) == 2, [a["attrs"] for a in att]
    assert att[0]["attrs"]["status"] in (500, 502)
    assert att[1]["attrs"]["status"] == 200
    assert all(a["attrs"]["streamed"] for a in att)
    # the replay on the sibling is annotated (failover if frames had
    # already flowed, retry when the worker died pre-stream)
    assert any(s_["name"] in ("failover", "retry") for s_ in t["spans"])
    # the surviving attempt carries the sibling's own decode spans
    assert any(
        s_["name"] == "decode"
        and s_["attrs"].get("remote")
        and s_["attrs"]["attempt"] == 1
        for s_ in t["spans"]
    ), [s_["name"] for s_ in t["spans"]]
    assert _local_span_ms(t) >= 0.9 * t["dur_ms"]
    assert _local_span_ms(t) <= 1.1 * t["dur_ms"]


def test_router_metricsz_federates_replicas(rig):
    """One router scrape answers for the fleet: every replica's series
    re-labeled replica="r<N>", plus cluster:...:sum/:max rollups."""
    from polyaxon_tpu.telemetry.federate import parse_prometheus_text

    body = json.dumps({"tokens": [[5, 6, 7]], "maxNewTokens": 2})
    s, _, _ = _post(rig["rport"], body, rid="rid-fed-1")
    assert s == 200
    rig["router"].poll_once()  # capture fresh /metricsz texts
    snap = parse_prometheus_text(_get(rig["rport"], "/metricsz").decode())

    assert snap.get("federation_source_up", replica="r0") == 1.0
    assert snap.get("federation_source_up", replica="r1") == 1.0
    for slug in ("r0", "r1"):
        assert snap.get("serving_requests_total", replica=slug) is not None
        assert snap.get("serving_queue_depth", replica=slug) is not None
    # cluster rollups: sums for counters, max only for gauge-shaped
    assert snap.get("cluster:serving_requests_total:sum") >= 1.0
    assert snap.get("cluster:serving_queue_depth:sum") is not None
    assert snap.get("cluster:serving_queue_depth:max") is not None
    assert snap.get("cluster:serving_requests_total:max") is None
    # the router's own series stay label-less (local, not federated)
    assert snap.get("router_requests_total") is not None
    st = json.loads(_get(rig["rport"], "/statsz"))
    assert st["cluster"]["federation"] is True
    assert st["cluster"]["scraped"] == 2
    assert st["cluster"]["serving_requests"] >= 1.0


def test_cli_trace_and_stats_against_router(rig):
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli

    rid = "rid-cli-trace"
    body = json.dumps({"tokens": [[5, 6, 7]], "maxNewTokens": 2})
    s, _, _ = _post(rig["rport"], body, rid=rid)
    assert s == 200
    _get_trace(rig["rport"], rid)  # wait for the ring to catch up
    url = f"http://127.0.0.1:{rig['rport']}"

    res = CliRunner().invoke(cli, ["trace", "--url", url])
    assert res.exit_code == 0, res.output
    assert "traces:" in res.output and rid in res.output

    res = CliRunner().invoke(cli, ["trace", rid, "--url", url])
    assert res.exit_code == 0, res.output
    assert f"trace {rid}" in res.output
    assert "upstream_attempt" in res.output
    assert "admission" in res.output

    res = CliRunner().invoke(
        cli, ["stats", "--url", url, "--traces", "5"]
    )
    assert res.exit_code == 0, res.output
    assert "traces:" in res.output
