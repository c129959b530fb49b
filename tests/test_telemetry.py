"""Unified telemetry: registry, spans, /metricsz, and the trainer's
span-accounting invariant.

The contract under test: ONE metrics pipeline per process. /statsz and
/metricsz render from the same Histogram/Counter objects (they cannot
disagree), the trainer's per-step data_wait + compute spans cover the
step body (they sum to the step walltime), and no module outside
polyaxon_tpu/telemetry hand-rolls a perf_counter timing loop."""

import json
import subprocess
import sys
import threading
import urllib.request
from pathlib import Path

import pytest

from polyaxon_tpu.telemetry import (
    MetricsRegistry,
    SpanTracer,
    quantile,
)

pytestmark = pytest.mark.telemetry

REPO_ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------- registry
def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("runs.retries", help="x")
    c.inc()
    c.inc(2.0)
    assert c.value == 3.0
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("train.loss")
    assert g.value is None  # unset gauge reports None, not 0
    g.set(0.25)
    assert g.value == 0.25
    # same name → same object; different kind → error, not a split series
    assert reg.counter("runs.retries") is c
    with pytest.raises(ValueError):
        reg.gauge("runs.retries")


def test_registry_concurrent_increments_exact():
    """N threads hammering one counter + one histogram lose no updates."""
    reg = MetricsRegistry()
    c = reg.counter("hits")
    h = reg.histogram("lat", buckets=(0.5, 1.0))
    threads_n, iters = 8, 500

    def work(tid):
        for i in range(iters):
            c.inc()
            h.observe((tid + i) % 2)  # alternates buckets

    threads = [
        threading.Thread(target=work, args=(t,)) for t in range(threads_n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == threads_n * iters
    assert h.count == threads_n * iters
    assert h.sum == sum((t + i) % 2 for t in range(threads_n) for i in range(iters))


def test_histogram_bucket_boundaries():
    """Values AT an upper bound land in that bucket (le semantics); above
    the last bound they land in +Inf only."""
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 9.0):
        h.observe(v)
    text = reg.render_prometheus()
    assert 'h_bucket{le="1"} 2' in text  # 0.5, 1.0
    assert 'h_bucket{le="2"} 4' in text  # + 1.5, 2.0  (cumulative)
    assert 'h_bucket{le="4"} 5' in text  # + 4.0
    assert 'h_bucket{le="+Inf"} 6' in text
    assert "h_sum 18" in text
    assert "h_count 6" in text
    # mismatched re-registration is a programming error
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(1.0, 2.0))


def test_histogram_percentiles_clamped_and_sane():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for _ in range(100):
        h.observe(0.05)
    s = h.summary()
    assert s["count"] == 100 and s["min"] == s["max"] == 0.05
    # all mass in one bucket: estimates must clamp to observed range
    for q in ("p50", "p95", "p99"):
        assert s[q] == pytest.approx(0.05)
    assert reg.histogram("empty").percentile(0.5) is None


def test_prometheus_rendering_conventions():
    reg = MetricsRegistry()
    reg.counter("serving.requests", help="Total requests").inc(3)
    reg.gauge("train.loss").set(0.5)
    reg.gauge("never.set")  # must NOT render a sample line
    text = reg.render_prometheus()
    assert "# HELP serving_requests_total Total requests" in text
    assert "# TYPE serving_requests_total counter" in text
    assert "serving_requests_total 3" in text  # dots sanitized, _total suffix
    assert "train_loss 0.5" in text
    assert "never_set" not in text.replace("# TYPE never_set gauge", "")
    assert text.endswith("\n")


def test_snapshot_matches_prometheus_view():
    """snapshot() (the /statsz side) and render_prometheus() (the
    /metricsz side) read the same objects."""
    reg = MetricsRegistry()
    reg.counter("a").inc(7)
    h = reg.histogram("b", buckets=(1.0,))
    h.observe(0.5)
    h.observe(2.0)
    snap = reg.snapshot()
    text = reg.render_prometheus()
    assert snap["a"] == 7 and "a_total 7" in text
    assert snap["b"]["count"] == 2 and "b_count 2" in text
    assert snap["b"]["sum"] == 2.5 and "b_sum 2.5" in text


# ------------------------------------------------------------ exact stats
def test_exact_quantile_type7():
    vals = [1.0, 2.0, 3.0, 4.0]
    assert quantile(vals, 0.0) == 1.0
    assert quantile(vals, 1.0) == 4.0
    assert quantile(vals, 0.5) == 2.5  # numpy-default interpolation
    assert quantile([], 0.5) is None
    with pytest.raises(ValueError):
        quantile(vals, 1.5)


# ----------------------------------------------------------------- spans
def test_span_nesting_and_jsonl_export(tmp_path):
    path = tmp_path / "t" / "spans.jsonl"
    tr = SpanTracer(path=str(path))
    with tr.span("step", step=3) as outer:
        with tr.span("data_wait"):
            pass
        with tr.span("compute") as inner:
            inner.set(tokens=128)
        tr.event("checkpoint", step=3)
    assert not path.exists()  # batched: nothing is written per span
    tr.close()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    by_name = {r["name"]: r for r in recs}
    assert [r["name"] for r in recs] == [
        "data_wait", "compute", "checkpoint", "step",  # completion order
    ]
    assert by_name["data_wait"]["parent_id"] == outer.span_id
    assert by_name["compute"]["parent_id"] == outer.span_id
    assert by_name["compute"]["attrs"] == {"tokens": 128}
    assert by_name["checkpoint"]["kind"] == "event"
    assert by_name["checkpoint"]["parent_id"] == outer.span_id
    assert by_name["step"]["parent_id"] is None
    assert by_name["step"]["attrs"] == {"step": 3}
    assert all(r["dur_s"] >= 0 for r in recs)
    assert tr.recent(2) == recs[-2:]  # memory ring mirrors the file


def test_span_nesting_is_per_thread():
    tr = SpanTracer()
    parents = {}

    def work(name):
        with tr.span(name) as s:
            parents[name] = s.parent_id

    with tr.span("main"):
        t = threading.Thread(target=work, args=("other-thread",))
        t.start()
        t.join()
        work("same-thread")
    assert parents["other-thread"] is None  # no cross-thread adoption
    assert parents["same-thread"] is not None


def test_tracer_export_failure_is_advisory(tmp_path):
    blocked = tmp_path / "file"
    blocked.write_text("")  # a FILE where a parent dir is needed
    tr = SpanTracer(path=str(blocked / "spans.jsonl"))
    with tr.span("s"):
        pass  # must not raise
    assert tr._broken and tr.recent()  # ring still records


# ------------------------------------------------- trainer span accounting
def _mlp_program(observability=None, **train_overrides):
    from polyaxon_tpu.schemas.run_kinds import V1Program

    train = {"steps": 8, "logEvery": 4, "precision": "float32", "seed": 0}
    train.update(train_overrides)
    spec = {
        "model": {
            "name": "mlp",
            "config": {"hidden": [32], "input_dim": 16, "num_classes": 4},
        },
        "data": {
            "name": "synthetic",
            "batchSize": 32,
            "config": {"shape": [16], "num_classes": 4},
        },
        "optimizer": {"name": "adamw", "learningRate": 0.01},
        "train": train,
    }
    if observability is not None:
        spec["observability"] = observability
    return V1Program.model_validate(spec)


def test_trainer_spans_account_for_step_walltime(tmp_path):
    """The acceptance invariant: a CPU run writes spans.jsonl into the
    artifacts dir, and per step the data_wait + compute child spans sum
    to the step span's walltime (within 10% in aggregate — the only
    uncovered work in the step body is a preemption-flag check)."""
    import jax

    from polyaxon_tpu.runtime.trainer import Trainer

    t = Trainer(
        _mlp_program(),
        mesh_axes={"data": 1},
        devices=jax.devices()[:1],
        artifacts_dir=str(tmp_path),
    )
    result = t.run()
    assert result.history[-1]["loss"] < result.history[0]["loss"]

    span_file = tmp_path / "telemetry" / "spans.jsonl"
    assert span_file.exists()
    recs = [json.loads(line) for line in span_file.read_text().splitlines()]
    steps = {r["span_id"]: r for r in recs if r["name"] == "step"}
    assert len(steps) == 8
    covered = {sid: 0.0 for sid in steps}
    for r in recs:
        if r["name"] in ("data_wait", "compute"):
            covered[r["parent_id"]] += r["dur_s"]
    total_step = sum(r["dur_s"] for r in steps.values())
    total_children = sum(covered.values())
    assert total_children <= total_step + 1e-6
    assert total_children >= 0.9 * total_step, (
        f"children cover {total_children:.6f}s of {total_step:.6f}s"
    )
    # per-step: children never exceed the parent, and cover it up to a
    # small absolute slack (sub-ms steps make pure ratios noisy)
    for sid, rec in steps.items():
        assert covered[sid] <= rec["dur_s"] + 1e-6
        assert covered[sid] >= 0.9 * rec["dur_s"] - 2e-3

    # the same run fed the registry: step histogram saw every step and
    # wait+compute histogram sums bracket the step histogram sum
    snap = t.telemetry.snapshot()
    assert snap["trainer.step_seconds"]["count"] == 8
    assert snap["trainer.steps"] == 8
    assert (
        snap["trainer.data_wait_seconds"]["sum"]
        + snap["trainer.compute_seconds"]["sum"]
        <= snap["trainer.step_seconds"]["sum"] + 1e-6
    )
    # derived throughput gauges landed in history at log points
    assert "data_wait_frac" in result.history[0]
    assert 0.0 <= result.history[0]["data_wait_frac"] <= 1.0


def test_trainer_trace_opt_out(tmp_path):
    """observability.trace: false suppresses the spans file (the spans
    still exist in memory for /statsz-style surfaces)."""
    import jax

    from polyaxon_tpu.runtime.trainer import Trainer

    t = Trainer(
        _mlp_program(observability={"trace": False}, steps=2, logEvery=1),
        mesh_axes={"data": 1},
        devices=jax.devices()[:1],
        artifacts_dir=str(tmp_path),
    )
    t.run()
    assert not (tmp_path / "telemetry" / "spans.jsonl").exists()
    assert t.tracer.recent()  # memory ring still populated


# -------------------------------------------- serving /statsz ↔ /metricsz
def _tiny_server():
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import build_model
    from polyaxon_tpu.serving.batching import ServingConfig
    from polyaxon_tpu.serving.server import ModelServer

    cfg = {
        "preset": "tiny", "seq_len": 64, "n_layers": 2, "dim": 64,
        "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128,
    }
    b = build_model("transformer_lm", cfg)
    params = b.module.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((2, 64), jnp.int32),
        train=False,
    )["params"]
    return ModelServer(
        b.module, params, config=ServingConfig(max_batch=4, max_wait_ms=30.0)
    )


def _parse_prom(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        key, val = line.rsplit(" ", 1)
        out[key] = float(val)
    return out


@pytest.mark.serving
def test_statsz_and_metricsz_report_the_same_pipeline(tmp_home):
    """Drive real requests over HTTP, then check the JSON and Prometheus
    surfaces agree — both render from the same registry objects."""
    server = _tiny_server()
    port = server.start(port=0)
    try:
        def post(i):
            body = {
                "tokens": [[(i + j) % 128 for j in range(4)]],
                "maxNewTokens": 3, "temperature": 0.5, "topK": 10, "seed": i,
            }
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=300) as r:
                json.loads(r.read())

        threads = [
            threading.Thread(target=post, args=(i,), daemon=True)
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)

        stats = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statsz", timeout=30
            ).read()
        )
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metricsz", timeout=30
        ) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            prom_text = r.read().decode()
        prom = _parse_prom(prom_text)

        # required series exist (scrapers know them by these names)
        assert 'serving_request_seconds_bucket{le="+Inf"}' in prom
        assert "serving_compile_cache_misses_total" in prom
        assert "serving_compile_cache_hits_total" in prom

        # cross-surface agreement: counters
        assert prom["serving_requests_total"] == stats["requests"] == 4
        assert prom["serving_compile_cache_hits_total"] == stats["compile_cache"]["hits"]
        assert prom["serving_compile_cache_misses_total"] == stats["compile_cache"]["misses"]
        assert stats["compile_cache"]["misses"] == stats["compile_count"] >= 1
        # cross-surface agreement: the latency histogram
        assert prom["serving_request_seconds_count"] == 4
        assert prom['serving_request_seconds_bucket{le="+Inf"}'] == 4
        lat = stats["latency_ms"]
        assert lat["p50"] is not None and lat["p50"] <= lat["p95"] <= lat["p99"]
        assert lat["p99"] * 1e-3 <= prom["serving_request_seconds_sum"] + 1e-9
        # queue-wait and occupancy measured on the batched path
        assert stats["queue_wait_ms"]["p50"] is not None
        assert prom["serving_batches_total"] >= 1
        assert prom['serving_batch_occupancy_bucket{le="+Inf"}'] >= 1
    finally:
        server.stop()


# ------------------------------------------------------- cross-cutting
def test_store_transitions_and_retries_hit_global_registry(tmp_home):
    from polyaxon_tpu.retry import RetryPolicy, TransientError
    from polyaxon_tpu.store.local import RunStore
    from polyaxon_tpu.telemetry import get_registry

    reg = get_registry()
    base_t = reg.counter("runs.transitions").value
    base_r = reg.counter("retry.attempts").value

    store = RunStore()
    store.create_run("feedbeef0001", "t", "default", {"kind": "test"})
    for st in ("compiled", "scheduled", "running", "succeeded"):
        store.set_status("feedbeef0001", st)
    assert reg.counter("runs.transitions").value >= base_t + 4
    assert reg.counter("runs.transitions.succeeded").value >= 1

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("blip")
        return "ok"

    policy = RetryPolicy(max_retries=5)  # backoff=0 → immediate retries
    assert policy.call(flaky) == "ok"
    assert reg.counter("retry.attempts").value == base_r + 2


def test_streams_metricsz_route(tmp_home):
    from polyaxon_tpu.store.local import RunStore
    from polyaxon_tpu.streams import BackgroundServer

    store = RunStore()
    store.create_run("feedbeef0002", "t", "default", {"kind": "test"})
    for st in ("compiled", "scheduled", "running"):
        store.set_status("feedbeef0002", st)
    with BackgroundServer(store) as srv:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metricsz", timeout=30
        ) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
    assert "runs_transitions_total" in text


# ------------------------------------------------------------------ lint
def test_no_raw_perf_counter_outside_telemetry():
    """polyaxon_tpu.telemetry.now() is the one metrics clock; any other
    module timing with perf_counter is growing a second pipeline."""
    res = subprocess.run(
        [sys.executable, str(REPO_ROOT / "scripts" / "lint_telemetry.py")],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 0, res.stderr


# ---------------------------------------------------------------- schema
def test_observability_schema():
    from polyaxon_tpu.schemas.run_kinds import V1ObservabilitySpec

    spec = V1ObservabilitySpec.model_validate(
        {"sampleInterval": 2.5, "histogramBuckets": [0.01, 0.1, 1.0]}
    )
    assert spec.sample_interval == 2.5 and spec.trace is True
    # templated value survives validation (resolved downstream)
    V1ObservabilitySpec.model_validate({"sampleInterval": "{{ interval }}"})
    with pytest.raises(Exception):
        V1ObservabilitySpec.model_validate({"sampleInterval": -1})
    with pytest.raises(Exception):
        V1ObservabilitySpec.model_validate({"histogramBuckets": [1.0, 1.0]})
    with pytest.raises(Exception):
        V1ObservabilitySpec.model_validate({"histogramBuckets": [2.0, 1.0]})


def test_stats_cli_renders_run(tmp_home, tmp_path):
    """`polyaxon stats <run>` prints status, latest metrics, and events."""
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli
    from polyaxon_tpu.store.local import RunStore

    store = RunStore()
    uuid = "feedbeef0003"
    store.create_run(uuid, "t", "default", {"kind": "test"})
    for st in ("compiled", "scheduled", "running"):
        store.set_status(uuid, st)
    store.log_metrics(uuid, 5, {"loss": 0.5, "tokens_per_sec": 1234.0})
    store.log_event(uuid, "artifact", {"kind": "profile", "path": "profile"})
    out_dir = Path(store.outputs_dir(uuid)) / "telemetry"
    out_dir.mkdir(parents=True, exist_ok=True)
    tr = SpanTracer(path=str(out_dir / "spans.jsonl"))
    with tr.span("step", step=5):
        with tr.span("compute"):
            pass
    tr.close()
    store.set_status(uuid, "succeeded")

    res = CliRunner().invoke(cli, ["stats", uuid])
    assert res.exit_code == 0, res.output
    assert "succeeded" in res.output
    assert "tokens_per_sec" in res.output and "1234" in res.output
    assert "step" in res.output and "compute" in res.output
    assert "profile" in res.output

    res = CliRunner().invoke(cli, ["stats", "nope"])
    assert res.exit_code != 0
