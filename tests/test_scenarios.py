"""Scenario engine coverage (ISSUE 16).

  * trace format: seeded generator determinism (same (generator, seed,
    params) → byte-identical records, PYTHONHASHSEED-independent),
    JSONL round-trip with version validation, shared-prefix cohorts
    that share real bytes;
  * discrete-event twin: deterministic reports, structural invariants
    (zero hung, zero leaked pages) under overload / chaos / disconnect
    ingredients, PhaseCosts fitting from /metricsz text;
  * registry: every real+twin scenario passes its declarative
    assertions in twin mode; `polyaxon scenario run --smoke` pins the
    million-user soak through the CLI;
  * satellite 1 end to end: a streamed client that vanishes mid-stream
    is detected (serving_client_disconnects_total), its rows cancelled,
    its KV pages released promptly, and the server keeps serving;
  * slow tier: disconnect storm + replica-kill chaos scenarios against
    a live 2-replica router rig (zero hung, zero leaked).
"""

import http.client
import json
import threading

import pytest

from polyaxon_tpu.scenarios import traces as tr
from polyaxon_tpu.scenarios.twin import PhaseCosts, ServingTwin, TwinConfig

pytestmark = pytest.mark.serving


# ------------------------------------------------------------------ traces
SMALL = {
    "diurnal": dict(n=24, duration_s=4.0),
    "bursts": dict(n=24, duration_s=4.0),
    "flood": dict(n=24),
    "shared_prefix": dict(n=24),
    "disconnect_storm": dict(n=24),
}


def test_every_generator_is_deterministic_per_seed():
    for name, params in SMALL.items():
        a = list(tr.generate(name, 3, **params))
        b = list(tr.generate(name, 3, **params))
        c = list(tr.generate(name, 4, **params))
        assert a == b, f"{name}: same seed must reproduce byte-identically"
        assert a != c, f"{name}: a different seed must change the trace"
        # structural invariants every generator keeps
        assert [r.i for r in a] == list(range(len(a)))
        assert all(r.at >= 0 for r in a)
        assert all(x.at <= y.at for x, y in zip(a, a[1:])), name
        assert all(r.prompt_len >= 1 and r.max_new >= 1 for r in a)


def test_prompt_tokens_deterministic_and_cohorts_share_bytes():
    recs = list(tr.generate("shared_prefix", 5, n=40, cohorts=2))
    by_cohort = {}
    for r in recs:
        by_cohort.setdefault(r.prefix_group, []).append(r)
    assert len(by_cohort) == 2
    for group, members in by_cohort.items():
        toks = [tr.prompt_tokens(r, 256) for r in members[:4]]
        plen = max(1, (3 * members[0].prompt_len) // 4)
        for t in toks[1:]:
            assert t[:plen] == toks[0][:plen], "cohort must share its prefix"
    # derivation is pure: same record, same tokens
    r0 = recs[0]
    assert tr.prompt_tokens(r0, 256) == tr.prompt_tokens(r0, 256)
    # low-entropy prompts are cyclic (speculation-friendly by design)
    low = tr.TraceRequest(i=0, at=0.0, prompt_len=8, max_new=4,
                          prompt_seed=10, entropy="low")
    toks = tr.prompt_tokens(low, 128)
    assert toks == [(10 + j) % 128 for j in range(8)]


def test_trace_jsonl_roundtrip(tmp_path):
    path = tmp_path / "t.jsonl"
    recs = list(tr.generate("disconnect_storm", 7, n=12))
    n = tr.write_trace(path, {"name": "dc", "seed": 7,
                              "generator": "disconnect_storm"}, recs)
    assert n == 12
    head, back = tr.read_trace(path)
    assert head["trace_version"] == tr.TRACE_VERSION
    assert head["count"] == 12 and head["name"] == "dc"
    assert back == recs  # None-field omission must round-trip losslessly

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"trace_version": 999}\n')
    with pytest.raises(ValueError, match="version"):
        tr.read_trace(bad)


def test_body_for_carries_request_contract():
    rec = tr.TraceRequest(i=1, at=0.0, prompt_len=6, max_new=4, seed=9,
                          prompt_seed=2, deadline_ms=250.0)
    body = tr.body_for(rec, 64)
    assert len(body["tokens"][0]) == 6
    assert all(0 <= t < 64 for t in body["tokens"][0])
    assert body["maxNewTokens"] == 4 and body["seed"] == 9
    assert body["topK"] == 40 and body["deadlineMs"] == 250.0
    no_dl = tr.body_for(tr.TraceRequest(i=0, at=0.0, prompt_len=4,
                                        max_new=2, top_k=None), 64)
    assert "deadlineMs" not in no_dl and "topK" not in no_dl

    with pytest.raises(ValueError, match="unknown trace generator"):
        tr.generate("nope", 0)


# -------------------------------------------------------------------- twin
def _twin(cfg=None, **kw):
    return ServingTwin(cfg or TwinConfig(), PhaseCosts(), **kw)


def test_twin_is_deterministic_and_structurally_sound():
    recs = lambda: tr.generate("diurnal", 11, n=2000, duration_s=30.0,  # noqa: E731
                               base_rps=80.0)
    a = _twin().run(recs())
    b = _twin().run(recs())
    assert a == b, "same trace + config must reproduce the same report"
    assert a["hung"] == 0 and a["kv_pages_leaked"] == 0
    assert a["offered"] == 2000
    assert a["ok"] + a["shed"] + a["deadline_504"] + a["disconnected"] \
        + a["error"] == 2000


def test_twin_sheds_queue_and_kv_pressure():
    cfg = TwinConfig(replicas=1, max_batch=2, max_queue=4,
                     kv_pool_pages=12, kv_page_tokens=8)
    out = ServingTwin(cfg, PhaseCosts(decode_step_ms=5.0)).run(
        tr.generate("flood", 2, n=300, rps=5000.0)
    )
    assert out["shed"] > 0
    assert set(out["shed_reasons"]) <= {"queue_full", "kv_pages"}
    assert out["hung"] == 0 and out["kv_pages_leaked"] == 0


def test_twin_replica_down_fails_over_without_hangs():
    out = ServingTwin(
        TwinConfig(replicas=2, kv_pool_pages=64),
        PhaseCosts(),
        faults=[{"kind": "replica_down", "replica": 0, "at_s": 1.0,
                 "duration_s": 2.0}],
    ).run(tr.generate("diurnal", 3, n=500, duration_s=10.0, base_rps=60.0))
    assert out["hung"] == 0 and out["kv_pages_leaked"] == 0
    assert out["ok"] > 0

    with pytest.raises(ValueError, match="unknown twin fault"):
        ServingTwin(TwinConfig(), PhaseCosts(),
                    faults=[{"kind": "meteor_strike"}])


def test_twin_prefix_directory_models_affinity_and_hit_rate():
    # ISSUE 17: each twin replica keeps a prefix directory; affinity
    # steers cohort repeats to the replica that already prefilled the
    # shared prefix, so only a handful of cold prefills happen
    # near-simultaneous arrivals: queues build, so JSQ genuinely spreads
    # rows across both replicas and affinity has a decision to make
    recs = lambda: tr.generate("shared_prefix", 5, n=60, rps=2000.0,  # noqa: E731
                               cohorts=3)
    on = _twin(TwinConfig(replicas=2, prefix_cache=True,
                          kv_pool_pages=64)).run(recs())
    p = on["prefix"]
    assert p["lookups"] > 0 and p["hits"] > 0
    assert p["hit_rate"] >= 0.5, p
    assert on["hung"] == 0 and on["kv_pages_leaked"] == 0
    # without affinity, JSQ spreads each cohort across BOTH replicas —
    # every replica pays its own cold prefill, so strictly fewer hits
    off = _twin(TwinConfig(replicas=2, prefix_cache=True,
                           kv_pool_pages=64, prefix_affinity=False)).run(
        recs())
    assert off["prefix"]["hits"] < p["hits"], (off["prefix"], p)
    # a replica death empties its directory with its pages
    dead = _twin(
        TwinConfig(replicas=2, prefix_cache=True, kv_pool_pages=64),
        faults=[{"kind": "replica_down", "replica": 0, "at_s": 0.5,
                 "duration_s": 0.5}],
    ).run(recs())
    assert dead["hung"] == 0 and dead["kv_pages_leaked"] == 0
    # prefix off: the ledger stays empty and hit_rate is None
    plain = _twin(TwinConfig(replicas=2, kv_pool_pages=64)).run(recs())
    assert plain["prefix"] == {"lookups": 0, "hits": 0, "hit_rate": None}


def test_twin_counts_disconnects_and_truncates_their_latency():
    out = _twin().run(tr.generate("disconnect_storm", 6, n=60, rps=30.0))
    assert out["disconnected"] > 0
    assert out["hung"] == 0 and out["kv_pages_leaked"] == 0


def test_phase_costs_fit_from_metricsz_text():
    # 10 requests: TTFT 40ms each (5ms of it queue wait), total 100ms
    text = "\n".join([
        "serving_ttft_ms_sum 400.0",
        "serving_ttft_ms_count 10",
        "serving_request_seconds_sum 1.0",
        "serving_request_seconds_count 10",
        "serving_queue_wait_seconds_sum 0.05",
        "serving_queue_wait_seconds_count 10",
    ])
    c = PhaseCosts.fit(text, mean_prompt_tokens=20.0, mean_new_tokens=7.0)
    # prefill region = 40 - 5 = 35ms → 80/20 split over 20 tokens
    assert c.prefill_ms_per_token == pytest.approx(0.8 * 35.0 / 20.0)
    assert c.batch_overhead_ms == pytest.approx(0.2 * 35.0)
    # decode region = 100 - 40 = 60ms over 6 steps
    assert c.decode_step_ms == pytest.approx(10.0)

    # a warmup baseline is subtracted sum-and-count-wise
    base = "\n".join([
        "serving_ttft_ms_sum 200.0",
        "serving_ttft_ms_count 2",
        "serving_request_seconds_sum 0.5",
        "serving_request_seconds_count 2",
    ])
    text2 = "\n".join([
        "serving_ttft_ms_sum 520.0",
        "serving_ttft_ms_count 10",
        "serving_request_seconds_sum 1.3",
        "serving_request_seconds_count 10",
    ])
    c2 = PhaseCosts.fit(text2, 20.0, 7.0, baseline_texts=base)
    assert c2.prefill_ms_per_token == pytest.approx(0.8 * 40.0 / 20.0)

    with pytest.raises(ValueError, match="no serving_ttft_ms"):
        PhaseCosts.fit("", 10.0, 5.0)


# ---------------------------------------------------------------- registry
def test_registry_twin_mode_passes_every_scenario():
    from polyaxon_tpu.scenarios.registry import SCENARIOS, run_twin

    for name, scn in SCENARIOS.items():
        if scn.twin_only:
            continue  # the 1M soak is pinned via the CLI test below
        res = run_twin(scn, smoke=True)
        assert res["pass"], (name, res["assertions"])
        assert res["summary"]["hung"] == 0
        assert res["summary"]["kv_pages_leaked"] == 0
        # twin runs are deterministic per (scenario, seed)
        assert run_twin(scn, smoke=True)["summary"] == res["summary"]


def test_registry_rejects_unknowns():
    from polyaxon_tpu.scenarios.registry import SCENARIOS, run_scenario

    with pytest.raises(ValueError, match="unknown scenario"):
        run_scenario("nope", mode="twin")
    with pytest.raises(ValueError, match="twin-only"):
        run_scenario("million_user_soak", mode="real")
    assert len(SCENARIOS) >= 6


def test_cli_scenario_ls_and_million_user_twin_soak_pin():
    """`polyaxon scenario run million_user_soak --smoke` IS the CI pin:
    a million-request diurnal soak through the twin, zero hung requests,
    zero leaked pages, inside the per-test watchdog budget."""
    from click.testing import CliRunner

    from polyaxon_tpu.cli.main import cli

    runner = CliRunner()
    ls = runner.invoke(cli, ["scenario", "ls"])
    assert ls.exit_code == 0, ls.output
    rows = [json.loads(l) for l in ls.output.splitlines() if l.strip()]
    assert {r["name"] for r in rows} >= {
        "diurnal_soak", "burst_overload", "high_entropy_flood",
        "replica_kill_midsoak", "disconnect_storm", "million_user_soak",
    }

    run = runner.invoke(
        cli, ["scenario", "run", "million_user_soak", "--smoke"]
    )
    assert run.exit_code == 0, run.output
    head = json.loads(run.output.splitlines()[0])
    assert head["pass"] is True and head["mode"] == "twin"
    assert head["offered"] == 1_000_000 and head["hung"] == 0


# --------------------------------------------- satellite 1: disconnect e2e
CFG = {
    "preset": "tiny", "seq_len": 64, "n_layers": 2, "dim": 64,
    "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128,
}


def _mini_server():
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import build_model
    from polyaxon_tpu.serving.batching import ServingConfig
    from polyaxon_tpu.serving.server import ModelServer

    b = build_model("transformer_lm", CFG)
    params = b.module.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    return ModelServer(
        b.module, params, model_name="dc-e2e",
        config=ServingConfig(
            max_batch=2, max_wait_ms=2.0, kv_page_tokens=8,
            kv_pool_pages=32, stream_chunk_tokens=3,
            prefix_cache=False, request_timeout_s=60.0,
        ),
    )


def _metric(port: int, name: str) -> float:
    import urllib.request

    from polyaxon_tpu.telemetry import parse_prometheus_text

    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    return parse_prometheus_text(text).value(name, 0.0)


def test_midstream_disconnect_cancels_rows_and_releases_pages():
    """A streamed client that closes its socket after the first chunk
    must be counted on serving_client_disconnects_total, its rows
    cancelled (decode ends early), its KV pages released promptly — and
    the server must keep serving afterwards."""
    server = _mini_server()
    port = server.start(port=0)
    body = {"tokens": [[7] * 8], "maxNewTokens": 40, "temperature": 0.8,
            "topK": 40, "seed": 1}
    try:
        # warm the compile so the stream below is steady-state
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        c.request("POST", "/generate", json.dumps(body),
                  {"Content-Type": "application/json"})
        assert c.getresponse().status == 200
        c.close()

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/generate?stream=1", json.dumps(body),
                     {"Content-Type": "application/json",
                      "X-Request-Id": "dc-e2e-1"})
        resp = conn.getresponse()
        assert resp.status == 200
        got = 0
        for raw in resp:
            if raw.startswith(b"data: "):
                got += 1
                break  # first token frame seen: vanish mid-stream
        assert got, "stream produced no frames"
        # abrupt close — what a vanished client looks like to the server
        # (the connection handed its socket to the response: Connection:
        # close, so conn.sock is already None — close the response's fp)
        resp.close()
        conn.close()

        # the server notices at its next write, cancels, releases
        waiter = threading.Event()
        for _ in range(200):
            if (
                _metric(port, "serving_client_disconnects_total") >= 1.0
                and _metric(port, "serving_kv_pages_used") <= 1.0
            ):
                break
            waiter.wait(0.1)
        assert _metric(port, "serving_client_disconnects_total") >= 1.0
        # <= 1: only the KV manager's permanent scratch page may remain
        assert _metric(port, "serving_kv_pages_used") <= 1.0

        # and the server still serves: no leaked decode slot or queue depth
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        c.request("POST", "/generate", json.dumps({**body, "seed": 2}),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        assert r.status == 200
        out = json.loads(r.read())
        assert len(out["tokens"][0]) == 8 + 40
        c.close()
    finally:
        server.stop()


def test_cancel_marks_only_unfinished_rows():
    from polyaxon_tpu.serving.batching import PendingRequest

    r = PendingRequest(tokens=[1], prompt_len=1, max_new=1, seed=0, key=None)
    r.cancel()
    assert r.cancelled
    done = PendingRequest(tokens=[1], prompt_len=1, max_new=1, seed=0,
                          key=None)
    done.finish(result=[1, 2])
    done.cancel()
    assert not done.cancelled, "a resolved row must not flip to cancelled"


# ------------------------------------------------- slow tier: live 2-replica
@pytest.fixture(scope="module")
def rig():
    from polyaxon_tpu.scenarios.registry import build_rig

    r = build_rig(replicas=2)
    yield r
    r.stop()


@pytest.mark.slow
@pytest.mark.chaos
def test_real_disconnect_storm_scenario(rig):
    from polyaxon_tpu.scenarios.registry import SCENARIOS, run_real

    res = run_real(SCENARIOS["disconnect_storm"], smoke=True, rig=rig)
    assert res["pass"], res["assertions"]
    assert res["summary"]["hung"] == 0
    assert res["metrics"]["kv_pages_leaked"] == 0
    assert res["metrics"]["client_disconnects"] >= 1


@pytest.mark.slow
@pytest.mark.chaos
def test_real_replica_kill_midsoak_scenario(rig):
    from polyaxon_tpu.scenarios.registry import SCENARIOS, run_real

    res = run_real(SCENARIOS["replica_kill_midsoak"], smoke=True, rig=rig)
    assert res["pass"], res["assertions"]
    assert res["chaos"] and "kill_tick" in res["chaos"]
    assert res["summary"]["hung"] == 0
    assert res["metrics"]["kv_pages_leaked"] == 0


@pytest.mark.slow
def test_real_prefix_storm_scenario():
    # own rig: prefix_storm needs prefix_cache + spill overrides the
    # shared fixture rig does not carry
    from polyaxon_tpu.scenarios.registry import SCENARIOS, run_real

    res = run_real(SCENARIOS["prefix_storm"], smoke=True)
    assert res["pass"], res["assertions"]
    assert res["summary"]["hung"] == 0
    # warm pages are NOT leaks: the prefix_held gauge discounts them
    assert res["metrics"]["kv_pages_leaked"] == 0
    assert res["metrics"]["prefix_hit_rate"] >= 0.25
