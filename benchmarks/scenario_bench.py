"""Scenario engine bench: named-scenario records + twin calibration.

Emits one JSON line per named scenario (twin mode: shed rate, p99,
hung, leaked pages — the fast, deterministic view of every scenario in
the registry), then validates the twin against the REAL serving stack:
a live 2-replica router rig replays a fixed-shape calibration trace,
`PhaseCosts.fit` extracts per-phase costs from the replicas' /metricsz
scrapes (warmup compiles subtracted via a baseline scrape), the twin
re-runs the same trace on those costs, and the disagreement is pinned:

  {"metric": "sim_vs_real_calibration_error", "value": ...,
   "pass": value <= 0.25, ...}

Finally the acceptance headliner: a million-request diurnal soak
through the twin, wall-clock pinned under 60 seconds on the 1-core CI
box.

  python benchmarks/scenario_bench.py            # full configuration
  python benchmarks/scenario_bench.py --smoke    # CI configuration
  python benchmarks/scenario_bench.py --smoke --twin-only  # no rig
  python benchmarks/scenario_bench.py --metricsz-out /tmp/m.txt
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from polyaxon_tpu.telemetry import parse_prometheus_text  # noqa: E402

CAL_PROMPT_LEN = 24  # one shape -> one bucket -> one compile pair, so
CAL_MAX_NEW = 12     # the fitted costs are steady-state, not compile noise


def twin_records(smoke: bool) -> list[dict]:
    """One record per named scenario, twin mode — deterministic, fast."""
    from polyaxon_tpu.scenarios.registry import SCENARIOS, run_twin

    out = []
    for name, scn in SCENARIOS.items():
        if scn.twin_only:
            continue  # the soak record below IS its record (wall pinned)
        t0 = time.perf_counter()
        res = run_twin(scn, smoke=smoke)
        wall = time.perf_counter() - t0
        s = res["summary"]
        rec = {
            "metric": "scenario_twin",
            "scenario": name,
            "value": s["shed_rate"],
            "unit": "shed_rate",
            "offered": s["offered"],
            "ok": s["ok"],
            "shed": s["shed"],
            "disconnected": s["disconnected"],
            "error": s["error"],
            "hung": s["hung"],
            "kv_pages_leaked": s["kv_pages_leaked"],
            "p99_ms": s["latency_ms"]["p99"],
            "slo_burn": None,  # twin models no SLO engine; real runs do
            "sim_duration_s": s["sim_duration_s"],
            "wall_s": round(wall, 2),
            "trace_seed": res["seed"],
            "pass": res["pass"],
        }
        if not res["pass"]:
            rec["failures"] = [
                v["detail"] for v in res["assertions"] if not v["ok"]
            ]
        out.append(rec)
    return out


def calibrate(smoke: bool, metricsz_out: str | None) -> list[dict]:
    """Real-stack calibration: replay a fixed-shape trace against a live
    2-replica rig, fit PhaseCosts from the scrapes, re-run the twin on
    the same trace, pin the disagreement."""
    from polyaxon_tpu.scenarios.driver import replay
    from polyaxon_tpu.scenarios.registry import (
        RIG_MODEL_CFG, _wait_drained, build_rig, calibration_error,
    )
    from polyaxon_tpu.scenarios.traces import body_for, flood
    from polyaxon_tpu.scenarios.twin import PhaseCosts, ServingTwin, TwinConfig

    n = 16 if smoke else 60
    rps = 4.0 if smoke else 8.0
    vocab = RIG_MODEL_CFG["vocab_size"]
    rig = build_rig(replicas=2)
    try:
        # warm EVERY replica's compile cache with the calibration shape,
        # then scrape the baseline so fit() sees only steady-state costs
        warm = next(iter(flood(
            99, n=1, rps=1.0, prompt_len=CAL_PROMPT_LEN, max_new=CAL_MAX_NEW
        )))
        for url in rig.mgr.endpoints():
            req = urllib.request.Request(
                url + "/generate",
                data=json.dumps(body_for(warm, vocab)).encode(),
                headers={"Content-Type": "application/json"},
            )
            urllib.request.urlopen(req, timeout=300.0).read()
        baseline = rig.replica_metricsz()

        records = list(flood(
            1, n=n, rps=rps, prompt_len=CAL_PROMPT_LEN, max_new=CAL_MAX_NEW
        ))
        report = replay(records, rig.url, vocab_size=vocab,
                        rid_prefix="cal")
        texts = [t for t in _wait_drained(rig) if t]
        if metricsz_out:
            Path(metricsz_out).write_text("\n".join(texts))
        real = report.summary()
        slo_burn = max(
            (parse_prometheus_text(t).value("slo_burn_rate", 0.0)
             for t in texts),
            default=0.0,
        )
        costs = PhaseCosts.fit(
            texts,
            mean_prompt_tokens=CAL_PROMPT_LEN,
            mean_new_tokens=CAL_MAX_NEW,
            baseline_texts=baseline,
        )
        # the twin models the SERVER: hold it to the server-measured
        # latency (delta over the warmup baseline), not the client-side
        # ledger mean, which adds HTTP + client-thread scheduling
        # overhead the twin deliberately does not simulate
        def _delta(name: str) -> float:
            return (
                sum(parse_prometheus_text(t).value(name) for t in texts)
                - sum(parse_prometheus_text(t).value(name) for t in baseline)
            )

        lat_n = _delta("serving_request_seconds_count")
        server_mean_ms = (
            _delta("serving_request_seconds_sum") / lat_n * 1e3
            if lat_n else None
        )
    finally:
        rig.stop()

    twin = ServingTwin(
        TwinConfig(replicas=2, max_batch=4, max_queue=64,
                   kv_pool_pages=96, kv_page_tokens=8),
        costs,
    ).run(iter(records))
    real_cmp = dict(real)
    if server_mean_ms is not None:
        real_cmp["latency_ms"] = {**real["latency_ms"], "mean": server_mean_ms}
    err = calibration_error(twin, real_cmp)
    real_rec = {
        "metric": "scenario_real",
        "scenario": "flood_calibration",
        "value": real["shed_rate"],
        "unit": "shed_rate",
        "offered": real["offered"],
        "ok": real["ok"],
        "shed": real["shed"],
        "error": real["error"],
        "hung": real["hung"],
        "p50_ms": real["latency_ms"]["p50"],
        "p99_ms": real["latency_ms"]["p99"],
        "mean_ms": real["latency_ms"]["mean"],
        "slo_burn": round(slo_burn, 3),
        "trace_seed": 1,
        "pass": real["hung"] == 0 and real["error"] == 0,
    }
    cal_rec = {
        "metric": "sim_vs_real_calibration_error",
        "value": round(err, 4),
        "unit": "max(|shed gap|, rel server-side mean-latency gap)",
        "requests": n,
        "twin_mean_ms": twin["latency_ms"]["mean"],
        "real_server_mean_ms": server_mean_ms,
        "real_client_mean_ms": real["latency_ms"]["mean"],
        "twin_shed_rate": twin["shed_rate"],
        "real_shed_rate": real["shed_rate"],
        "costs": {
            "prefill_ms_per_token": round(costs.prefill_ms_per_token, 4),
            "decode_step_ms": round(costs.decode_step_ms, 4),
            "batch_overhead_ms": round(costs.batch_overhead_ms, 4),
        },
        "pass": err <= 0.25,
    }
    return [real_rec, cal_rec]


def soak_record() -> dict:
    """The acceptance headliner: 1M requests through the twin, <60s."""
    from polyaxon_tpu.scenarios.registry import SCENARIOS, run_twin

    t0 = time.perf_counter()
    res = run_twin(SCENARIOS["million_user_soak"])
    wall = time.perf_counter() - t0
    s = res["summary"]
    return {
        "metric": "scenario_twin_soak_wall_s",
        "value": round(wall, 2),
        "unit": "s",
        "requests": s["offered"],
        "sim_hours": round(s["sim_duration_s"] / 3600.0, 2),
        "hung": s["hung"],
        "kv_pages_leaked": s["kv_pages_leaked"],
        "shed_rate": s["shed_rate"],
        "pass": wall < 60.0 and res["pass"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small CI configuration")
    ap.add_argument("--twin-only", action="store_true",
                    help="skip the real-rig calibration (no jax, no "
                         "compiles): twin records + the soak pin only")
    ap.add_argument("--metricsz-out", default=None,
                    help="write the calibration rig's final /metricsz "
                         "scrapes here (CI gates grep it)")
    args = ap.parse_args(argv)

    recs = twin_records(args.smoke)
    if not args.twin_only:
        # POLYAXON_JAX_PLATFORM / POLYAXON_NUM_CPU_DEVICES apply through
        # jax.config, so before the backend initializes
        from polyaxon_tpu.utils.jax_platform import apply_platform_env

        apply_platform_env()
        recs.extend(calibrate(args.smoke, args.metricsz_out))
    recs.append(soak_record())
    ok = True
    for rec in recs:
        print(json.dumps(rec), flush=True)
        ok = ok and rec.get("pass", True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
