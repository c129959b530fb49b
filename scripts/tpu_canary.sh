#!/bin/bash
# TPU canary: poll for a TPU; the moment a real one answers, capture
# the full perf evidence chain and commit it.
#
#   bench.py                      -> tpu_results/bench_tpu.json  (+ BENCH line)
#   benchmarks/run_baselines.py   -> BASELINE.md rows (TPU-measured section)
#   benchmarks/decode_bench.py    -> tpu_results/decode_tpu.json
#
# Results land in tpu_results/ inside the repo (so an end-of-round snapshot
# always picks them up) and are committed under a flock on .git so a canary
# commit can never interleave with an interactive one.
#
# Usage: nohup scripts/tpu_canary.sh >/dev/null 2>&1 &
# Log:   tpu_results/canary.log

set -u
cd "$(dirname "$0")/.."
mkdir -p tpu_results
log=tpu_results/canary.log
echo "canary start $(date -u +%F' '%T)" >> "$log"

while true; do
  if timeout 90 python -c "import jax; d=jax.devices()[0]; assert d.platform=='tpu'; print('probe-ok', d.device_kind)" >> "$log" 2>&1; then
    echo "tpu up at $(date -u +%T); running bench" >> "$log"
    POLYAXON_BENCH_TIMEOUT=1500 timeout 1800 python bench.py > tpu_results/bench_tpu.json 2>> "$log"
    echo "bench rc=$? $(date -u +%T)" >> "$log"
    cat tpu_results/bench_tpu.json >> "$log"
    if ! grep -q '"platform": "tpu"' tpu_results/bench_tpu.json; then
      echo "bench fell back to cpu; retrying loop" >> "$log"
      # never leave CPU numbers on disk under a _tpu filename — an
      # end-of-round snapshot must not mistake them for chip evidence
      rm -f tpu_results/bench_tpu.json
      sleep 90
      continue
    fi
    echo "running baselines $(date -u +%T)" >> "$log"
    timeout 4000 python benchmarks/run_baselines.py --update-baseline \
      > tpu_results/baselines_tpu.out 2>> "$log"
    echo "baselines rc=$? $(date -u +%T)" >> "$log"
    echo "running decode bench $(date -u +%T)" >> "$log"
    timeout 1200 python benchmarks/decode_bench.py \
      > tpu_results/decode_tpu.json 2>> "$log"
    echo "decode rc=$? $(date -u +%T)" >> "$log"
    # telemetry gate: after the smoke traffic, /metricsz must expose the
    # required series — a capture whose metrics pipeline is dark is not
    # usable perf evidence, so a missing series FAILS the canary.
    echo "running metricsz smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.server import ModelServer

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]
server = ModelServer(
    b.module, params, config=ServingConfig(max_batch=4, max_wait_ms=10.0)
)
port = server.start(port=0)
try:
    body = {"tokens": [[1, 2, 3]], "maxNewTokens": 4,
            "temperature": 0.5, "topK": 10, "seed": 0}
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    urllib.request.urlopen(req, timeout=300).read()
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
finally:
    server.stop()
with open("tpu_results/metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "serving_request_seconds_bucket",
    "serving_requests_total",
    "serving_compile_cache_hits_total",
    "serving_compile_cache_misses_total",
    "serving_queue_wait_seconds_bucket",
    "serving_batch_occupancy_bucket",
)
missing = [s for s in required if s not in text]
if missing:
    print("metricsz smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
print(f"metricsz smoke: ok ({len(required)} required series present)")
PY
    then
      echo "METRICSZ-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # fleet scheduler gate: drive a deterministic admission scenario
    # (fill fleet -> high-priority preemption -> over-quota rejection)
    # through the REAL simulator and require the fleet.*/scheduler.*
    # series on /metricsz. A scheduler whose telemetry is dark would
    # ship blind capacity decisions, so a missing series FAILS the run.
    echo "running fleet metricsz smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import sys
import urllib.request

sys.path.insert(0, ".")
from polyaxon_tpu.schemas import V1QuotaSpec
from polyaxon_tpu.scheduler.sim import FleetSimulator, SimJob
from polyaxon_tpu.streams.server import make_server

jobs = [
    # fills the 2x2 fleet, then gets evicted by the priority-10 arrival
    SimJob(name="wide", duration=100, arrival=0, chips=4, project="alpha"),
    SimJob(name="hot", duration=20, arrival=10, chips=2, priority=10,
           project="alpha"),
    # capped at 2 chips -> asking 4 can NEVER fit -> admission.rejected
    SimJob(name="greedy", duration=5, arrival=5, chips=4, project="capped"),
]
sim = FleetSimulator(
    jobs,
    topology="2x2",
    quotas=[V1QuotaSpec(scope="capped", max_chips=2)],
    invariant_fn=lambda s: s.check_invariants(),
)
report = sim.run()
assert report["preemptions"] >= 1, report
assert report["unschedulable"] == 1, report

server = make_server(sim.store, port=0)
port = server.server_address[1]
import threading

threading.Thread(target=server.serve_forever, daemon=True).start()
try:
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    fleetz = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/fleetz", timeout=30
    ).read().decode()
finally:
    server.shutdown()
with open("tpu_results/fleet_metricsz_tpu.txt", "w") as f:
    f.write(text)
with open("tpu_results/fleetz_tpu.json", "w") as f:
    f.write(fleetz)
required = (
    "fleet_chips_total",
    "fleet_chips_reserved",
    "scheduler_queue_wait_ms_bucket",
    "scheduler_preemptions_total",
    "admission_rejected_total",
)
missing = [s for s in required if s not in text]
if missing:
    print("fleet metricsz smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
print(f"fleet metricsz smoke: ok ({len(required)} required series present)")
PY
    then
      echo "FLEET-METRICSZ-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # overload resilience gate: drive the serving stack at 5x its
    # calibrated capacity (benchmarks/serving_overload_bench.py --smoke
    # asserts zero hung requests, a positive shed rate, and bounded
    # admitted latency) and require the resilience series in the
    # /metricsz text it captured. A server that strands requests under
    # overload — or sheds invisibly — FAILS the canary.
    echo "running overload smoke $(date -u +%T)" >> "$log"
    if ! timeout 900 python benchmarks/serving_overload_bench.py --smoke \
        --metricsz-out tpu_results/overload_metricsz_tpu.txt \
        > tpu_results/overload_tpu.json 2>> "$log"; then
      echo "OVERLOAD-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      cat tpu_results/overload_tpu.json >> "$log" 2>/dev/null
      exit 1
    fi
    cat tpu_results/overload_tpu.json >> "$log"
    for series in serving_shed_total serving_deadline_exceeded_total \
        serving_breaker_state serving_worker_restarts_total serving_ready; do
      if ! grep -q "$series" tpu_results/overload_metricsz_tpu.txt; then
        echo "OVERLOAD-SMOKE-FAILED: missing series $series $(date -u +%T)" >> "$log"
        exit 1
      fi
    done
    echo "overload smoke: ok $(date -u +%T)" >> "$log"
    # scenario gate (ISSUE 16): the scenario engine end to end. First
    # benchmarks/scenario_bench.py --smoke — every named scenario
    # through the twin, the million-request soak under its 60s wall
    # pin, and the twin-vs-real calibration against a live 2-replica
    # rig (sim_vs_real_calibration_error <= 0.25, exit 1 past it).
    # Then the disconnect_storm scenario for real via the CLI so the
    # mid-stream-cancellation path actually fires on this hardware,
    # and require the resilience series (above all
    # serving_client_disconnects_total) in the rig's /metricsz text.
    # A twin that drifts from the stack it predicts — or a server that
    # cannot account for vanished clients — FAILS the canary.
    echo "running scenario smoke $(date -u +%T)" >> "$log"
    if ! timeout 900 python benchmarks/scenario_bench.py --smoke \
        --metricsz-out tpu_results/scenario_metricsz_tpu.txt \
        > tpu_results/scenario_tpu.json 2>> "$log"; then
      echo "SCENARIO-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      cat tpu_results/scenario_tpu.json >> "$log" 2>/dev/null
      exit 1
    fi
    cat tpu_results/scenario_tpu.json >> "$log"
    if ! timeout 600 python -m polyaxon_tpu.cli.main scenario run \
        disconnect_storm --smoke \
        --out tpu_results/scenario_disconnect_tpu.json >> "$log" 2>&1; then
      echo "SCENARIO-SMOKE-FAILED: disconnect_storm $(date -u +%T)" >> "$log"
      cat tpu_results/scenario_disconnect_tpu.json >> "$log" 2>/dev/null
      exit 1
    fi
    for series in serving_client_disconnects_total serving_shed_total \
        serving_kv_pages_used serving_queue_depth; do
      if ! grep -q "$series" tpu_results/scenario_metricsz_tpu.txt; then
        echo "SCENARIO-SMOKE-FAILED: missing series $series $(date -u +%T)" >> "$log"
        exit 1
      fi
    done
    echo "scenario smoke: ok $(date -u +%T)" >> "$log"
    # paged-KV gate: drive warm traffic (same prompt twice -> prefix
    # reuse) plus a streamed request through a pool-backed server and
    # require the KV/TTFT series on /metricsz. A paged deployment whose
    # pool occupancy, prefix hit rate, or TTFT is dark cannot be
    # capacity-planned, so a missing series FAILS the canary.
    echo "running kv metricsz smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.server import ModelServer

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]
server = ModelServer(
    b.module, params,
    config=ServingConfig(max_batch=4, max_wait_ms=10.0,
                         kv_pool_pages=64, kv_page_tokens=8,
                         stream_chunk_tokens=4),
)
port = server.start(port=0)
try:
    body = json.dumps({
        "tokens": [list(range(1, 21))], "maxNewTokens": 6,
        "temperature": 0.5, "topK": 10, "seed": 0,
    }).encode()
    for path in ("/generate", "/generate", "/generate?stream=1"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=body,
            headers={"Content-Type": "application/json"},
        )
        urllib.request.urlopen(req, timeout=300).read()
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statsz", timeout=30
    ).read())
finally:
    server.stop()
with open("tpu_results/kv_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "serving_kv_pages_total",
    "serving_kv_pages_used",
    "serving_prefix_cache_hits_total",
    "serving_prefix_cache_misses_total",
    "serving_ttft_ms",
)
missing = [s for s in required if s not in text]
if missing:
    print("kv metricsz smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
kv = stats["kv"]
if kv["prefix"]["hits"] < 1:
    print("kv metricsz smoke: warm re-post produced no prefix hit", kv)
    sys.exit(1)
print(f"kv metricsz smoke: ok ({len(required)} required series present, "
      f"{kv['prefix']['hits']} prefix hits)")
PY
    then
      echo "KV-METRICSZ-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # fast-decode gate: warm greedy traffic (a cyclic prompt posted
    # twice) through a speculative + int8-quantized paged server, then
    # require the spec/quant series on /metricsz AND at least one
    # ACCEPTED draft token on /statsz. A speculation deployment that
    # never accepts is pure verify overhead, and a dark accept-rate
    # cannot be tuned, so either FAILS the canary.
    echo "running spec/quant metricsz smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.server import ModelServer

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]
server = ModelServer(
    b.module, params,
    config=ServingConfig(max_batch=4, max_wait_ms=10.0,
                         kv_pool_pages=64, kv_page_tokens=8,
                         stream_chunk_tokens=4,
                         speculate=True, draft_tokens=4, quantize=True),
)
port = server.start(port=0)
try:
    # a repetitive prompt is the n-gram drafter's home turf: greedy
    # decode revisits prompt n-grams, so drafts get accepted
    body = json.dumps({
        "tokens": [list(range(1, 9)) * 3], "maxNewTokens": 24,
        "temperature": 0.0, "seed": 0,
    }).encode()
    for _ in range(2):  # second post rides the warm prefix pages
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        urllib.request.urlopen(req, timeout=300).read()
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statsz", timeout=30
    ).read())
finally:
    server.stop()
with open("tpu_results/spec_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "serving_spec_proposed_total",
    "serving_spec_accepted_total",
    "serving_spec_rollback_total",
    "serving_quant_bytes_saved",
)
missing = [s for s in required if s not in text]
if missing:
    print("spec/quant metricsz smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
sp = stats["speculation"]
if sp["accepted"] < 1:
    print("spec/quant metricsz smoke: no draft token accepted on warm "
          "repetitive traffic", sp)
    sys.exit(1)
if stats["quant"]["bytes_saved"] <= 0:
    print("spec/quant metricsz smoke: quantize-on-load saved no bytes",
          stats["quant"])
    sys.exit(1)
print(f"spec/quant metricsz smoke: ok ({len(required)} required series "
      f"present, {sp['accepted']} draft tokens accepted, "
      f"accept_rate={sp['accept_rate']}, "
      f"{stats['quant']['bytes_saved']} bytes saved)")
PY
    then
      echo "SPEC-QUANT-METRICSZ-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # adaptive-spec gate (ISSUE 15): warm HIGH-ENTROPY traffic through an
    # adaptive speculative server must drive serving_spec_effective_k
    # down from the configured K — a shrink or a full auto-disable — with
    # ZERO failed requests (adaptation is a perf decision, never a
    # correctness event), and an int8-KV server must serve byte-identical
    # greedy output one-shot vs chunked on the quantized pool. A
    # controller that lets losing speculation run unbounded, or a
    # quantized pool that changes bytes with write order, FAILS.
    echo "running adaptive-spec smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.server import ModelServer

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]


def post(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        return r.status, json.loads(r.read())


K0 = 4
server = ModelServer(
    b.module, params,
    config=ServingConfig(max_batch=4, max_wait_ms=10.0,
                         kv_pool_pages=64, kv_page_tokens=8,
                         speculate=True, draft_tokens=K0,
                         adaptive_draft=True),
)
port = server.start(port=0)
failed = 0
try:
    rng = np.random.RandomState(0)
    for i in range(4):  # high-entropy: the n-gram drafter gets nothing
        body = {
            "tokens": [rng.randint(1, 256, size=12).tolist()
                       for _ in range(4)],
            "maxNewTokens": 24, "temperature": 0.0,
        }
        status, _ = post(port, body)
        if status != 200:
            failed += 1
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statsz", timeout=30
    ).read())
finally:
    server.stop()
with open("tpu_results/adaptive_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = ("serving_spec_effective_k", "serving_spec_truncated_total")
missing = [s for s in required if s not in text]
if missing:
    print("adaptive-spec smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
if failed:
    print(f"adaptive-spec smoke: {failed} failed requests during adaptation")
    sys.exit(1)
sp = stats["speculation"]
eff, dis = sp["effective_k"], sp["auto_disabled"]
if not (dis or eff < K0):
    print("adaptive-spec smoke: high-entropy traffic left K unbounded",
          {"effective_k": eff, "auto_disabled": dis})
    sys.exit(1)

# int8-KV byte identity: one-shot vs chunked prefill on the QUANTIZED
# pool must agree bit for bit (quantize-on-write is per-slot, so bytes
# never depend on which chunk wrote them)
kv_kw = dict(max_batch=4, max_wait_ms=10.0, kv_pool_pages=64,
             kv_page_tokens=8, kv_quant="int8")
one = ModelServer(b.module, params, config=ServingConfig(**kv_kw))
two = ModelServer(b.module, params, config=ServingConfig(
    **kv_kw, chunked_prefill=True, prefill_chunk_tokens=16,
    max_step_tokens=64))
p1, p2 = one.start(port=0), two.start(port=0)
try:
    body = {"tokens": [list(range(1, 41)), list(range(7, 47))],
            "maxNewTokens": 12, "temperature": 0.0}
    s1, o1 = post(p1, body)
    s2, o2 = post(p2, body)
    kv_stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{p1}/statsz", timeout=30
    ).read())["kv"]
finally:
    one.stop()
    two.stop()
if s1 != 200 or s2 != 200:
    print("adaptive-spec smoke: int8-KV request failed", s1, s2)
    sys.exit(1)
if o1["tokens"] != o2["tokens"]:
    print("adaptive-spec smoke: int8-KV greedy output diverged "
          "one-shot vs chunked", o1["tokens"], o2["tokens"])
    sys.exit(1)
if kv_stats.get("kv_quant") != "int8" or kv_stats.get("kv_pool_bytes", 0) <= 0:
    print("adaptive-spec smoke: quantized pool accounting dark", kv_stats)
    sys.exit(1)
print(f"adaptive-spec smoke: ok (effective_k {K0} -> {eff}, "
      f"auto_disabled={dis}, zero failed requests, int8-KV byte-identical, "
      f"kv_pool_bytes={kv_stats['kv_pool_bytes']})")
PY
    then
      echo "ADAPTIVE-SPEC-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # chunked-prefill gate: fire one long-prompt/long-decode request and,
    # while it is in flight, a short streamed request against a
    # chunkedPrefill server. The short request's first token must land
    # BEFORE the long request finishes (the step scheduler's whole point
    # — no head-of-line blocking), and the three new series must be on
    # /metricsz. A chunked deployment whose step telemetry is dark
    # cannot be tuned, so either failure FAILS the canary.
    echo "running chunked-prefill smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import threading
import time
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.server import ModelServer

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]
server = ModelServer(
    b.module, params,
    config=ServingConfig(max_batch=4, max_wait_ms=5.0,
                         kv_pool_pages=64, kv_page_tokens=8,
                         stream_chunk_tokens=2, chunked_prefill=True,
                         prefill_chunk_tokens=16, max_step_tokens=64),
)
port = server.start(port=0)
base = f"http://127.0.0.1:{port}"


def post(body, path="/generate"):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=300)


long_body = {"tokens": [list(range(1, 97))], "maxNewTokens": 24,
             "temperature": 0.5, "topK": 10, "seed": 0}
short_body = {"tokens": [list(range(1, 9))], "maxNewTokens": 4,
              "temperature": 0.5, "topK": 10, "seed": 1}
try:
    # warm both shapes so compiles don't land in the timed race
    post(long_body).read()
    post(short_body).read()

    long_done_at = [None]

    def fire_long():
        post(long_body).read()
        long_done_at[0] = time.perf_counter()

    t = threading.Thread(target=fire_long, daemon=True)
    t.start()
    time.sleep(0.02)  # let the long prefill enter the step loop
    resp = post(short_body, "/generate?stream=1")
    short_first_at = None
    buf = b""
    while True:
        chunk = resp.read(64)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            frame, buf = buf.split(b"\n\n", 1)
            ev = json.loads(frame[len(b"data: "):])
            if "tokens" in ev and short_first_at is None:
                short_first_at = time.perf_counter()
    t.join(timeout=300)
    text = urllib.request.urlopen(f"{base}/metricsz", timeout=30
                                  ).read().decode()
    stats = json.loads(urllib.request.urlopen(f"{base}/statsz", timeout=30
                                              ).read())
finally:
    server.stop()
with open("tpu_results/chunked_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "serving_prefill_chunks_total",
    "serving_step_tokens",
    "serving_prefill_queue_depth",
)
missing = [s for s in required if s not in text]
if missing:
    print("chunked-prefill smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
ch = stats["chunked"]
if not ch.get("enabled") or ch.get("prefill_chunks", 0) < 2:
    print("chunked-prefill smoke: step scheduler did not chunk", ch)
    sys.exit(1)
if short_first_at is None or long_done_at[0] is None:
    print("chunked-prefill smoke: race did not complete")
    sys.exit(1)
if short_first_at >= long_done_at[0]:
    print("chunked-prefill smoke: short TTFT waited out the long request "
          f"(short first token {short_first_at:.3f} vs long done "
          f"{long_done_at[0]:.3f}) — head-of-line blocking is back")
    sys.exit(1)
print(f"chunked-prefill smoke: ok ({len(required)} required series "
      f"present, {ch['prefill_chunks']} chunks over {ch['steps']} steps, "
      f"short first token {long_done_at[0] - short_first_at:.3f}s before "
      "long finish)")
PY
    then
      echo "CHUNKED-PREFILL-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # elastic gate: a seeded preempt-shrink-resume through the REAL stack
    # (two-tier checkpoints, eviction at peak, halving-ladder re-admission
    # on a half-stolen fleet), then require the elastic series on
    # /metricsz. A resize path whose telemetry is dark would hide both
    # checkpoint stalls and silent capacity downgrades, so a missing
    # series FAILS the run.
    echo "running elastic metricsz smoke $(date -u +%T)" >> "$log"
    if ! timeout 900 python - >> "$log" 2>&1 <<'PY'
import os
import sys
import tempfile
import threading
import urllib.request

# the shrink must be a REAL mesh reduction: off-TPU (local dry runs) the
# host would expose a single CPU device and the 2->1 grant would no-op
if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )

sys.path.insert(0, ".")
from polyaxon_tpu.scheduler.agent import Agent
from polyaxon_tpu.scheduler.fleet import Fleet
from polyaxon_tpu.schemas.operation import V1Operation
from polyaxon_tpu.store import RunStore
from polyaxon_tpu.streams.server import make_server

home = tempfile.mkdtemp(prefix="canary-elastic-")
local = tempfile.mkdtemp(prefix="canary-elastic-fast-")
EVICT_AT, STEPS = 4, 6


class EvictAtPeak(RunStore):
    target = None

    def log_metrics(self, run_uuid, step, metrics):
        super().log_metrics(run_uuid, step, metrics)
        if run_uuid == self.target and step == EVICT_AT:
            meta = (self.get_status(run_uuid) or {}).get("meta") or {}
            if not meta.get("preempt_restarts"):
                self.set_meta(run_uuid, preempt_requested=True)


store = EvictAtPeak(home)
Fleet(store).configure(chips=2)
agent = Agent(store=store)
op = V1Operation.model_validate({
    "kind": "operation",
    "name": "canary-elastic",
    "environment": {"resources": {"chips": 2, "minChips": 1}},
    "component": {
        "kind": "component",
        "name": "c",
        "termination": {"maxRetries": 0},
        "run": {
            "kind": "jaxjob",
            "program": {
                "model": {"name": "mlp", "config": {
                    "input_dim": 8, "num_classes": 2, "hidden": [4]}},
                "data": {"name": "synthetic", "batchSize": 8,
                         "config": {"shape": [8], "num_classes": 2}},
                "optimizer": {"name": "sgd", "learningRate": 0.01},
                "train": {"steps": STEPS, "logEvery": 1,
                          "checkpointEvery": 2, "precision": "float32",
                          "checkpointLocalDir": local},
            },
        },
    },
})
uid = agent.submit(op)
store.target = uid

# the instant the evicted run frees its 2 chips, 1 is stolen — the full
# block can never re-place, so re-admission MUST take the smaller rung
hogged = []
real_release = Fleet.release


def release_and_hog(self, run_uuid):
    rec = real_release(self, run_uuid)
    if run_uuid == uid and not hogged:
        hogged.append(1)
        assert self.reserve("hog", chips=1, project="hog") is not None
    return rec


Fleet.release = release_and_hog
agent.drain()
status = store.get_status(uid)
assert getattr(status["status"], "value", status["status"]) == "succeeded"
meta = status["meta"]
assert meta["granted_chips"] == 1 and meta["requested_chips"] == 2, meta
resumed = [e for e in store.read_events(uid) if e["kind"] == "resumed"]
assert resumed and resumed[0]["step"] >= EVICT_AT, resumed
assert store.read_metrics(uid)[-1]["step"] == STEPS

server = make_server(store, port=0)
port = server.server_address[1]
threading.Thread(target=server.serve_forever, daemon=True).start()
try:
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
finally:
    server.shutdown()
with open("tpu_results/elastic_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "trainer_checkpoint_stall_ms",
    "checkpoint_tier_writes_total",
    "trainer_elastic_resizes_total",
    "scheduler_elastic_shrinks_total",
)
missing = [s for s in required if s not in text]
if missing:
    print("elastic metricsz smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
print(f"elastic metricsz smoke: ok ({len(required)} required series "
      f"present, resumed at step {resumed[0]['step']} on 1 chip)")
PY
    then
      echo "ELASTIC-METRICSZ-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # slo/trace gate: mixed traffic (good decodes + deterministic
    # deadline sheds) through a server armed with a tight availability
    # SLO, then require the burn-rate gauges on /metricsz, a COMPLETE
    # trace on /tracez (nonzero queue_wait + decode spans — a timeline
    # with dark gaps cannot explain a p99), and a flight-recorder
    # bundle on the breach. Dark burn rates or hollow traces FAIL.
    echo "running slo/trace metricsz smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import pathlib
import sys
import tempfile
import urllib.error
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.server import ModelServer

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]
debug_dir = tempfile.mkdtemp(prefix="slo-canary-")
server = ModelServer(
    b.module, params,
    config=ServingConfig(max_batch=4, max_wait_ms=10.0,
                         kv_pool_pages=64, kv_page_tokens=8),
    slos=[{"name": "availability", "kind": "availability",
           "objective": 0.999, "windows": [5.0, 30.0]}],
    debug_dir=debug_dir,
)
port = server.start(port=0)
try:
    def post(body, rid=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     **({"X-Request-Id": rid} if rid else {})},
        )
        try:
            r = urllib.request.urlopen(req, timeout=300)
            return r.status, json.loads(r.read()), dict(r.headers)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read()), dict(e.headers)

    good = {"tokens": [list(range(1, 9))], "maxNewTokens": 8,
            "temperature": 0.8, "topK": 40, "seed": 0}
    st, out, hdr = post(good, rid="canary-good")
    if st != 200 or hdr.get("X-Request-Id") != "canary-good":
        print("slo/trace smoke: good request lost its id", st, hdr)
        sys.exit(1)
    # deterministic 503s: an already-expired deadline sheds at admission
    for i in range(4):
        st, out, _ = post({**good, "deadlineMs": 1e-6, "seed": i + 1})
        if st != 503 or out.get("reason") != "deadline" or not out.get("requestId"):
            print("slo/trace smoke: shed shape wrong", st, out)
            sys.exit(1)
    server.slo_engine.evaluate()  # don't wait for the background cadence
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    trace = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/tracez?id=canary-good", timeout=30
    ).read())
    sloz = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/sloz", timeout=30
    ).read())
finally:
    server.stop()
with open("tpu_results/slo_trace_tpu.txt", "w") as f:
    f.write(text)
    f.write("\n--- tracez?id=canary-good ---\n")
    f.write(json.dumps(trace, indent=1))
    f.write("\n--- sloz ---\n")
    f.write(json.dumps(sloz, indent=1))
required = ("slo_burn_rate", "slo_breached",
            "serving_http_requests_total", "serving_http_errors_total")
missing = [s for s in required if s not in text]
if missing:
    print("slo/trace smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
spans = {s["name"]: s for s in trace.get("spans", [])}
if "queue_wait" not in spans or "decode" not in spans:
    print("slo/trace smoke: trace missing queue_wait/decode spans:",
          sorted(spans))
    sys.exit(1)
if spans["queue_wait"]["dur_s"] <= 0 or spans["decode"]["dur_s"] <= 0:
    print("slo/trace smoke: zero-duration queue_wait/decode spans", spans)
    sys.exit(1)
if not sloz.get("breached"):
    print("slo/trace smoke: 4/5 sheds did not breach the 99.9% "
          "availability SLO", sloz)
    sys.exit(1)
bundles = sorted(pathlib.Path(debug_dir).glob("slo-*/breach.json"))
if not bundles:
    print("slo/trace smoke: breach fired but no flight-recorder bundle "
          f"under {debug_dir}")
    sys.exit(1)
print(f"slo/trace metricsz smoke: ok ({len(required)} required series "
      f"present, trace has {len(spans)} span kinds, breach bundle at "
      f"{bundles[0].parent})")
PY
    then
      echo "SLO-TRACE-METRICSZ-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # router gate: 2 replicas behind the fleet router, warm traffic,
    # then a worker kill injected mid-stream. The router must fail the
    # stream over to the sibling with ZERO client-visible failures
    # (byte-complete greedy tokens, no error frames) and count at least
    # one retry; the router_* series must be live on /metricsz. A
    # horizontal deployment whose failover or telemetry is dark FAILS.
    echo "running router failover smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import time
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.chaos.injector import active
from polyaxon_tpu.chaos.plan import Fault, FaultPlan
from polyaxon_tpu.models import build_model
from polyaxon_tpu.retry import RetryPolicy
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.replicas import InProcessReplica, ReplicaSetManager
from polyaxon_tpu.serving.router import P2CBalancer, Router
from polyaxon_tpu.serving.server import ModelServer
from polyaxon_tpu.telemetry import MetricsRegistry

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]


def make_server():
    return ModelServer(
        b.module, params,
        config=ServingConfig(max_batch=4, max_wait_ms=10.0,
                             kv_pool_pages=64, kv_page_tokens=8,
                             stream_chunk_tokens=3),
    )


# one registry: the manager's replica-fleet gauges and the router's
# routing series land on the SAME /metricsz the gate scrapes
reg = MetricsRegistry()
mgr = ReplicaSetManager(
    lambda i: InProcessReplica(make_server), replicas=2,
    retry=RetryPolicy(max_retries=3, backoff=0.1),
    registry=reg, monitor_interval_s=0.2,
)
router = Router(
    mgr.endpoints, registry=reg, balancer=P2CBalancer(seed=7),
    poll_interval_s=0.2,
)
mgr.attach_router(router)
mgr.start()
port = router.start("127.0.0.1", 0)
failures = []
try:
    router.poll_once()

    def post(body, path="/generate"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "canary-router"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            if r.status != 200:
                failures.append((path, r.status))
            return r.read()

    greedy = {"tokens": [list(range(1, 13))], "maxNewTokens": 8,
              "temperature": 0.0, "seed": 0}
    sampled = {**greedy, "temperature": 0.8, "topK": 40}

    def stream_tokens(raw):
        toks, errs = [], []
        for frame in raw.split(b"\n\n"):
            if not frame.startswith(b"data: "):
                continue
            ev = json.loads(frame[len(b"data: "):])
            if "error" in ev:
                errs.append(ev)
            if "tokens" in ev and ev.get("row") == 0:
                toks.extend(ev["tokens"])
        return toks, errs

    # warm traffic: both paths, both replicas compile their buckets
    for _ in range(4):
        post(greedy)
        post(sampled)
    reference, errs = stream_tokens(post(greedy, "/generate?stream=1"))
    if errs or not reference:
        print("router smoke: warm stream failed", errs)
        sys.exit(1)

    retries_before = router._m_retries.value
    # the injected worker kill crashes whichever replica the stream
    # landed on mid-decode; the router must replay on the sibling
    with active(FaultPlan([Fault("serving.worker", "kill", at=0)])):
        failed_over, errs = stream_tokens(post(greedy, "/generate?stream=1"))
    retries = router._m_retries.value - retries_before
    if errs:
        print("router smoke: client saw error frames through failover", errs)
        sys.exit(1)
    if failed_over != reference:
        print("router smoke: failover stream diverged",
              failed_over, reference)
        sys.exit(1)
    if retries < 1:
        print("router smoke: worker kill produced no router retry")
        sys.exit(1)

    # crashed-replica recovery: kill a replica outright; the manager
    # must relaunch it into the same slot while the router keeps serving
    mgr.replica(0).kill()
    post(greedy)  # served by the survivor
    deadline = time.monotonic() + 60
    while mgr.live() < 2 and time.monotonic() < deadline:
        time.sleep(0.2)
    if mgr.live() != 2:
        print("router smoke: killed replica was not relaunched")
        sys.exit(1)

    if failures:
        print("router smoke: non-200 responses", failures)
        sys.exit(1)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
finally:
    router.stop()
    mgr.stop()
with open("tpu_results/router_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "router_requests_total",
    "router_retries_total",
    "router_upstream_shed_total",
    "router_errors_total",
    "router_replicas_routable",
    "router_request_seconds_bucket",
    "serving_replica_restarts_total",
)
missing = [s for s in required if s not in text]
if missing:
    print("router smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
print(f"router failover smoke: ok ({len(required)} required series "
      f"present, {retries} retries, zero failed requests, "
      f"replica relaunched)")
PY
    then
      echo "ROUTER-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # federation gate (ISSUE 13): 2 replicas behind the router, warm
    # traffic, then ONE router scrape must answer for the fleet —
    # replica-labeled serving_* series plus cluster:...:sum/:max
    # aggregates on /metricsz — and ONE router /tracez read must show a
    # stitched router→replica timeline (the replica's own decode span
    # grafted under the router's upstream_attempt). An observability
    # plane that cannot see across processes FAILS.
    echo "running metrics federation smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import time
import urllib.error
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.models import build_model
from polyaxon_tpu.retry import RetryPolicy
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.replicas import InProcessReplica, ReplicaSetManager
from polyaxon_tpu.serving.router import P2CBalancer, Router
from polyaxon_tpu.serving.server import ModelServer
from polyaxon_tpu.telemetry import MetricsRegistry
from polyaxon_tpu.telemetry.federate import parse_prometheus_text

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]


def make_server():
    return ModelServer(
        b.module, params,
        config=ServingConfig(max_batch=4, max_wait_ms=10.0,
                             kv_pool_pages=64, kv_page_tokens=8,
                             stream_chunk_tokens=3),
    )


reg = MetricsRegistry()
mgr = ReplicaSetManager(
    lambda i: InProcessReplica(make_server), replicas=2,
    retry=RetryPolicy(max_retries=3, backoff=0.1),
    registry=reg, monitor_interval_s=0.2,
)
router = Router(
    mgr.endpoints, registry=reg, balancer=P2CBalancer(seed=7),
    poll_interval_s=0.2,
)
mgr.attach_router(router)
mgr.start()
port = router.start("127.0.0.1", 0)
try:
    router.poll_once()
    body = json.dumps({"tokens": [list(range(1, 13))],
                       "maxNewTokens": 8}).encode()
    warm = 6
    for i in range(warm):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json",
                     "X-Request-Id": f"canary-fed-{i}"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            if r.status != 200:
                print("federation smoke: request failed", r.status)
                sys.exit(1)
            r.read()
    router.poll_once()  # re-scrape: replica texts include the traffic

    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    snap = parse_prometheus_text(text)
    problems = []
    for slug in ("r0", "r1"):
        if snap.get("federation_source_up", replica=slug) != 1.0:
            problems.append(f"federation_source_up missing for {slug}")
        if snap.get("serving_requests_total", replica=slug) is None:
            problems.append(f"serving_requests_total not labeled {slug}")
    total = snap.get("cluster:serving_requests_total:sum")
    if total is None or total < warm:
        problems.append(f"cluster requests sum {total} < warm {warm}")
    if snap.get("cluster:serving_queue_depth:max") is None:
        problems.append("cluster:serving_queue_depth:max missing")
    if problems:
        print("federation smoke:", "; ".join(problems))
        sys.exit(1)

    trace = None
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            trace = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/tracez?id=canary-fed-0",
                timeout=30,
            ).read())
            break
        except urllib.error.HTTPError as e:
            if e.code != 404:
                raise
            time.sleep(0.1)
    if trace is None:
        print("federation smoke: router trace never recorded")
        sys.exit(1)
    if not trace["attrs"].get("stitched"):
        print("federation smoke: trace not stitched", trace["attrs"])
        sys.exit(1)
    decode = [s for s in trace["spans"]
              if s["name"] == "decode" and s["attrs"].get("remote")]
    if not decode:
        print("federation smoke: no replica-side decode span grafted",
              [s["name"] for s in trace["spans"]])
        sys.exit(1)
finally:
    router.stop()
    mgr.stop()
with open("tpu_results/router_federated_metricsz_tpu.txt", "w") as f:
    f.write(text)
with open("tpu_results/router_stitched_trace_tpu.json", "w") as f:
    json.dump(trace, f, indent=2)
print(f"metrics federation smoke: ok (cluster sum {total:g} requests "
      f"across 2 replicas, stitched trace with {len(decode)} remote "
      f"decode span(s))")
PY
    then
      echo "FEDERATION-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # cluster-KV gate (ISSUE 17): a warm shared-prefix cohort through a
    # 2-replica router must STICK to the replica holding its prefix KV
    # (>= 1 affinity hit), survive eviction through a spill -> restore
    # cycle with byte-identical output, and the affinity + spill series
    # (router_affinity_hits_total, serving_kv_spill_*_total, the
    # cluster prefix-hit aggregate) must be live on one router scrape.
    # A fleet whose warm traffic scatters or whose spill tier is dark
    # FAILS.
    echo "running cluster-KV affinity smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import time
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

from polyaxon_tpu.models import build_model
from polyaxon_tpu.retry import RetryPolicy
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.replicas import InProcessReplica, ReplicaSetManager
from polyaxon_tpu.serving.router import P2CBalancer, Router
from polyaxon_tpu.serving.server import ModelServer
from polyaxon_tpu.telemetry import MetricsRegistry
from polyaxon_tpu.telemetry.federate import parse_prometheus_text

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]


def make_server():
    # pool sized so ~4 distinct cached prompts force harvest to demote
    # (each 49-token prompt caches 6 pages of 8 tokens; pool holds 24)
    return ModelServer(
        b.module, params,
        config=ServingConfig(max_batch=4, max_wait_ms=10.0,
                             kv_pool_pages=24, kv_page_tokens=8,
                             spill_ram_bytes=32 << 20),
    )


reg = MetricsRegistry()
mgr = ReplicaSetManager(
    lambda i: InProcessReplica(make_server), replicas=2,
    retry=RetryPolicy(max_retries=3, backoff=0.1),
    registry=reg, monitor_interval_s=0.2,
)
router = Router(
    mgr.endpoints, registry=reg, balancer=P2CBalancer(seed=7),
    poll_interval_s=0.2,
)
mgr.attach_router(router)
mgr.start()
port = router.start("127.0.0.1", 0)
try:
    router.poll_once()

    def post(tokens):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"tokens": [list(tokens)], "maxNewTokens": 6,
                             "temperature": 0.0, "seed": 0}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            if r.status != 200:
                print("cluster-kv smoke: request failed", r.status)
                sys.exit(1)
            return json.loads(r.read())["tokens"]

    rng = np.random.RandomState(0)
    # the flood splits across both replicas, so it is sized for the
    # HOLDER's share alone to overflow its pool (24 pages, 6 per prompt)
    target, *flood = [rng.randint(1, 100, size=49).tolist()
                      for _ in range(17)]

    cold = post(target)  # harvests the target prefix on one replica
    deadline = time.monotonic() + 10
    while router.directory.empty and time.monotonic() < deadline:
        time.sleep(0.1)
        router.poll_once()  # pick up the /kvz advertisement
    if router.directory.empty:
        print("cluster-kv smoke: no replica ever advertised a prefix")
        sys.exit(1)

    hits_before = router._m_affinity_hits.value
    warm = post(target)  # must stick to the holder: affinity + KV hit
    if router._m_affinity_hits.value <= hits_before:
        print("cluster-kv smoke: warm repeat produced no affinity hit")
        sys.exit(1)
    if warm != cold:
        print("cluster-kv smoke: warm bytes diverged", warm, cold)
        sys.exit(1)

    # churn both pools with distinct prompts so the target's cached
    # pages demote to the RAM spill tier, then repeat the target: the
    # hit must RESTORE from spill, still byte-identical
    for f in flood:
        post(f)
    router.poll_once()
    restored = post(target)
    if restored != cold:
        print("cluster-kv smoke: restored bytes diverged", restored, cold)
        sys.exit(1)

    router.poll_once()  # re-scrape: replica texts include the cycle
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
finally:
    router.stop()
    mgr.stop()
with open("tpu_results/cluster_kv_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "router_affinity_hits_total",
    "serving_kv_spill_bytes_total",
    "serving_kv_spill_restores_total",
    "serving_kv_spill_quarantined_total",
    "cluster:serving_prefix_cache_hits_total:sum",
)
missing = [s for s in required if s not in text]
if missing:
    print("cluster-kv smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
snap = parse_prometheus_text(text)
spilled = snap.get("cluster:serving_kv_spill_bytes_total:sum") or 0
restores = snap.get("cluster:serving_kv_spill_restores_total:sum") or 0
kv_hits = snap.get("cluster:serving_prefix_cache_hits_total:sum") or 0
problems = []
if spilled <= 0:
    problems.append(f"no bytes ever spilled ({spilled})")
if restores < 1:
    problems.append(f"no spill restore fired ({restores})")
if kv_hits < 1:
    problems.append(f"no cluster prefix-cache hit ({kv_hits})")
if problems:
    print("cluster-kv smoke:", "; ".join(problems))
    sys.exit(1)
print(f"cluster-KV affinity smoke: ok ({len(required)} required series "
      f"present, {int(router._m_affinity_hits.value)} affinity hits, "
      f"{int(spilled)} bytes spilled, {int(restores)} restore(s), "
      f"{int(kv_hits)} cluster prefix hit(s), byte-identical warm + "
      f"restored output)")
PY
    then
      echo "CLUSTER-KV-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # event-log crash gate: a REAL run through the Agent/Fleet stack,
    # then the store writer takes a real SIGKILL mid-append (seeded
    # garbage lands on the live segment first — the torn tail a power
    # cut leaves). A fresh process must recover ZERO lost committed
    # transitions (byte-identical history), count the truncation in
    # store_recovered_tails_total, and resume the pre-kill watch cursor
    # with no gaps and no duplicates. Any lost transition FAILS.
    echo "running event-log crash smoke $(date -u +%T)" >> "$log"
    if ! timeout 900 python - >> "$log" 2>&1 <<'PY'
import json
import subprocess
import sys
import tempfile
import threading
import urllib.request

sys.path.insert(0, ".")

home = tempfile.mkdtemp(prefix="canary-eventlog-")

# the child IS the store writer: it drives the run end-to-end through
# the real Agent/Fleet stack, records what the log acknowledged, then
# dies by real SIGKILL the instant the chaos plan tears its next append
CHILD = r"""
import json, os, signal, sys
sys.path.insert(0, ".")
home = sys.argv[1]
from polyaxon_tpu.chaos.injector import SimulatedKill, active
from polyaxon_tpu.chaos.plan import FaultPlan
from polyaxon_tpu.scheduler.agent import Agent
from polyaxon_tpu.scheduler.fleet import Fleet
from polyaxon_tpu.schemas.operation import V1Operation
from polyaxon_tpu.store import RunStore

store = RunStore(home)
Fleet(store).configure(chips=2)
agent = Agent(store=store)
op = V1Operation.model_validate({
    "kind": "operation",
    "name": "canary-eventlog",
    "environment": {"resources": {"chips": 2}},
    "component": {
        "kind": "component",
        "name": "c",
        "termination": {"maxRetries": 0},
        "run": {
            "kind": "jaxjob",
            "program": {
                "model": {"name": "mlp", "config": {
                    "input_dim": 8, "num_classes": 2, "hidden": [4]}},
                "data": {"name": "synthetic", "batchSize": 8,
                         "config": {"shape": [8], "num_classes": 2}},
                "optimizer": {"name": "sgd", "learningRate": 0.01},
                "train": {"steps": 3, "logEvery": 1,
                          "precision": "float32"},
            },
        },
    },
})
uid = agent.submit(op)
agent.drain()
status = store.get_status(uid)
assert getattr(status["status"], "value", status["status"]) == "succeeded"
# everything committed so far: append() returned, so this set is the
# gate's "zero lost transitions" contract after the kill
with open(os.path.join(home, "acked.json"), "w") as f:
    json.dump({
        "uuid": uid,
        "history": store.get_history(uid),
        "cursor": store.head_cursor(),
    }, f, default=str)
    f.flush()
    os.fsync(f.fileno())
plan = FaultPlan.scrambled_tail(seed=7, window=1)  # the NEXT append
try:
    with active(plan):
        store.eventlog.append(uid, "event", {"event": {"torn": True}})
except SimulatedKill:
    os.kill(os.getpid(), signal.SIGKILL)  # page cache keeps the garbage
print("eventlog child: scrambled-tail fault never fired")
sys.exit(3)
"""
rc = subprocess.call([sys.executable, "-c", CHILD, home])
if rc != -9:
    print(f"eventlog smoke: child exited rc={rc}, expected SIGKILL (-9)")
    sys.exit(1)
with open(f"{home}/acked.json") as f:
    acked = json.load(f)
uid = acked["uuid"]

from polyaxon_tpu.store import RunStore
from polyaxon_tpu.streams.server import make_server
from polyaxon_tpu.telemetry import get_registry

store = RunStore(home)  # the restarted writer
store.recover()
tails = get_registry().counter("store.recovered_tails").value
if tails < 1:
    print("eventlog smoke: recovery truncated no torn tail", tails)
    sys.exit(1)

norm = lambda h: json.dumps(h, sort_keys=True, default=str)
recovered = store.get_history(uid)
if norm(recovered) != norm(acked["history"]):
    print("eventlog smoke: committed history diverged after crash")
    print(" acked:", norm(acked["history"])[:2000])
    print(" recovered:", norm(recovered)[:2000])
    sys.exit(1)

# cursor integrity: the full replay is gap-free and duplicate-free, and
# the child's pre-kill cursor resumes cleanly — the torn (unacked)
# append must NOT appear, the first post-recovery commit must
entries, _ = store.read_events_since("0:0")
seqs = [e["seq"] for e in entries]
if seqs != sorted(seqs) or len(set(seqs)) != len(seqs):
    print("eventlog smoke: replay has gaps or duplicates", seqs)
    sys.exit(1)
resumed, cur = store.read_events_since(acked["cursor"])
if [e for e in resumed if e.get("kind") != "log"]:
    print("eventlog smoke: unacked events resurfaced after the cursor",
          resumed)
    sys.exit(1)
store.eventlog.append(uid, "event", {"event": {"post_recovery": True}})
fresh, _ = store.read_events_since(cur)
if [e["event"] for e in fresh if e["kind"] == "event"] != [
    {"post_recovery": True}
]:
    print("eventlog smoke: resumed cursor missed the first post-recovery "
          "commit", fresh)
    sys.exit(1)

server = make_server(store, port=0)
port = server.server_address[1]
threading.Thread(target=server.serve_forever, daemon=True).start()
try:
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
finally:
    server.shutdown()
with open("tpu_results/eventlog_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "store_appends_total",
    "store_recovered_tails_total",
    "store_fsync_ms_bucket",
    "store_compactions_total",
    "store_watch_cursor_lag",
)
missing = [s for s in required if s not in text]
if missing:
    print("eventlog smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
print(f"event-log crash smoke: ok ({len(required)} required series "
      f"present, {int(tails)} torn tail(s) recovered, "
      f"{len(recovered)} committed records intact, cursor resumed clean)")
PY
    then
      echo "EVENTLOG-CRASH-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # metrics-history gate (ISSUE 18): a server armed with the history
    # sampler + a latency regression rule, warm fast traffic, then a
    # chaos-injected decode slowdown (serving.slow sleeps). The sentinel
    # must flip regression_active on the REAL latency surge, land a
    # perf_regression event in the run's event log, and leave a
    # flight-recorder bundle with the offending series window; the
    # history series must be live on /metricsz and /queryz must answer
    # with the recorded points. A regression detector that sleeps
    # through a 10x slowdown — or a history plane that is dark — FAILS.
    echo "running metrics-history smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import pathlib
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.chaos.injector import active
from polyaxon_tpu.chaos.plan import Fault, FaultPlan
from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.server import ModelServer
from polyaxon_tpu.store import RunStore

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]
home = tempfile.mkdtemp(prefix="canary-history-store-")
store = RunStore(home)
uid = "canaryhist0001"
store.create_run(uid, "canary-history", "default", {"kind": "test"})
hist_dir = tempfile.mkdtemp(prefix="canary-history-")
debug_dir = tempfile.mkdtemp(prefix="canary-history-debug-")
server = ModelServer(
    b.module, params,
    config=ServingConfig(max_batch=4, max_wait_ms=10.0),
    history={"dir": hist_dir, "interval_s": 0.05},
    regression_rules=[{
        "name": "latency-surge", "series": "serving.request_seconds",
        "kind": "window_ratio", "agg": "p95", "window_s": 2.0,
        "threshold": 2.0, "min_samples": 4,
    }],
    debug_dir=debug_dir,
    event_sink=lambda kind, body: store.log_event(uid, kind, body),
)
port = server.start(port=0)
try:
    body = json.dumps({"tokens": [[1, 2, 3, 4]], "maxNewTokens": 4,
                       "temperature": 0.5, "topK": 10, "seed": 0}).encode()

    def post():
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        urllib.request.urlopen(req, timeout=300).read()

    # warm window: fast requests fill the baseline half of the ratio
    post()  # compile out of the way first
    t0 = time.monotonic()
    while time.monotonic() - t0 < 2.0:
        post()
        time.sleep(0.02)
    server.sentinel.evaluate()
    if any(r["active"] for r in server.sentinel.last):
        print("history smoke: rule fired on the WARM baseline",
              server.sentinel.last)
        sys.exit(1)
    # surge window: every decode batch stalls 150ms under chaos — the
    # p95 of the recent window must dwarf the warm window's
    with active(FaultPlan([Fault("serving.slow", "sleep", at=0,
                                 count=10_000, delay_ms=150.0)])):
        t0 = time.monotonic()
        while time.monotonic() - t0 < 2.2:
            post()
    results = server.sentinel.evaluate()
    fired = [r for r in results if r["active"]]
    if not fired:
        print("history smoke: 150ms chaos slowdown never flipped "
              "regression_active", results)
        sys.exit(1)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
    q = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/queryz?series=serving.request_seconds"
        "&agg=p95&last=10&step=2", timeout=30,
    ).read())
finally:
    server.stop()
with open("tpu_results/history_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = ("history_samples_total", "history_bytes", "regression_active")
missing = [s for s in required if s not in text]
if missing:
    print("history smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
active_lines = [l for l in text.splitlines()
                if l.startswith("regression_active ")]
if not active_lines or float(active_lines[0].split()[1]) < 1:
    print("history smoke: regression_active gauge not >= 1 after the "
          "edge", active_lines)
    sys.exit(1)
if not any(v is not None for _, v in q.get("points", [])):
    print("history smoke: /queryz returned no recorded points", q)
    sys.exit(1)
events = [e for e in store.read_events(uid)
          if e.get("kind") == "perf_regression"]
if not events:
    print("history smoke: no perf_regression event in the run log")
    sys.exit(1)
if not events[0].get("history_window"):
    print("history smoke: perf_regression event carries no series window",
          events[0])
    sys.exit(1)
bundles = sorted(pathlib.Path(debug_dir).glob("slo-*/breach.json"))
if not bundles:
    print("history smoke: regression edge left no flight-recorder bundle "
          f"under {debug_dir}")
    sys.exit(1)
burst = json.loads(bundles[0].read_text())
if not burst.get("history_window"):
    print("history smoke: breach bundle missing history_window", burst)
    sys.exit(1)
print(f"metrics-history smoke: ok ({len(required)} required series "
      f"present, rule {fired[0]['name']!r} fired at ratio "
      f"{fired[0].get('ratio'):.1f}, perf_regression event landed, "
      f"bundle at {bundles[0].parent})")
PY
    then
      echo "HISTORY-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # tenancy gate (ISSUE 19): one multi-tenant server, two LoRA
    # adapters squeezed through ONE hot slot plus quota'd tenants.
    # Alternating tenants must force a real evict -> spill -> restore
    # cycle that stays byte-identical (and identical to a solo
    # single-adapter server), a capped noisy tenant's flood must shed
    # with reason tenant_quota while the victim tenant completes every
    # request, and the serving_adapter_* + per-tenant series must be
    # live on /metricsz. A multiplexer that corrupts a restored
    # adapter, sheds the wrong tenant, or serves dark FAILS.
    echo "running tenancy smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import threading
import urllib.error
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.server import ModelServer
from polyaxon_tpu.serving.tenancy import normalize_adapters, normalize_tenants

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128, "lora_rank": 4}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((2, 128), jnp.int32), train=False,
)["params"]


def serve(adapters, tenants, slots=0):
    return ModelServer(
        b.module, params,
        config=ServingConfig(
            max_batch=2, max_wait_ms=30.0,
            adapters=normalize_adapters(adapters),
            tenants=normalize_tenants(tenants),
            adapter_slots=slots,
        ),
    )


def post(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


GREEDY = {"tokens": [[1, 2, 3, 4, 5]], "maxNewTokens": 8,
          "temperature": 0.0}
server = serve(
    {"acme": "seed:1", "globex": "seed:2"},
    [{"name": "acme", "adapter": "acme"},
     {"name": "globex", "adapter": "globex"},
     {"name": "noisy", "max_outstanding": 1},
     {"name": "victim"}],
    slots=1,
)
port = server.start(port=0)
try:
    # 1) evict/restore byte identity: 2 adapters through 1 hot slot —
    # every alternation swaps, the comeback must reproduce exact tokens
    a1 = post(port, dict(GREEDY, tenant="acme"))[1]["tokens"]
    g1 = post(port, dict(GREEDY, tenant="globex"))[1]["tokens"]
    a2 = post(port, dict(GREEDY, tenant="acme"))[1]["tokens"]
    if a1 != a2:
        print("tenancy smoke: restored adapter diverged", a1, a2)
        sys.exit(1)
    if a1 == g1:
        print("tenancy smoke: adapters did not diverge (vacuous)", a1)
        sys.exit(1)
    reg = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statsz", timeout=30
    ).read())["tenancy"]["adapters"]
    if reg["evictions"] < 1 or reg["restores"] < 1:
        print("tenancy smoke: no real evict/restore cycle", reg)
        sys.exit(1)
    # 2) noisy flood sheds tenant_quota alone; victim completes all
    results = []
    lock = threading.Lock()

    def noisy(i):
        s, p = post(port, {"tokens": [[1, 2]], "maxNewTokens": 16,
                           "tenant": "noisy", "seed": i,
                           "temperature": 0.5, "topK": 10})
        with lock:
            results.append((s, p.get("reason")))

    threads = [threading.Thread(target=noisy, args=(i,), daemon=True)
               for i in range(5)]
    for t in threads:
        t.start()
    for i in range(3):
        s, p = post(port, {"tokens": [[3, 4, 5]], "maxNewTokens": 4,
                           "tenant": "victim"})
        if s != 200:
            print("tenancy smoke: victim request failed", s, p)
            sys.exit(1)
    for t in threads:
        t.join(300)
    sheds = [r for r in results if r[0] == 503]
    if not sheds:
        print("tenancy smoke: flood never overran the cap", results)
        sys.exit(1)
    if any(r[1] != "tenant_quota" for r in sheds):
        print("tenancy smoke: shed with wrong reason", results)
        sys.exit(1)
    ten = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/statsz", timeout=30
    ).read())["tenancy"]["tenants"]
    if ten["victim"]["shed"] != 0 or ten["noisy"]["shed"] != len(sheds):
        print("tenancy smoke: shed ledger misattributed", ten)
        sys.exit(1)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metricsz", timeout=30
    ).read().decode()
finally:
    server.stop()
# 3) mixed-tenant output matches a solo single-adapter server
solo = serve({"acme": "seed:1"}, [{"name": "acme", "adapter": "acme"}])
sport = solo.start(port=0)
try:
    s1 = post(sport, dict(GREEDY, tenant="acme"))[1]["tokens"]
finally:
    solo.stop()
if s1 != a1:
    print("tenancy smoke: mixed-tenant output != solo server", a1, s1)
    sys.exit(1)
with open("tpu_results/tenancy_metricsz_tpu.txt", "w") as f:
    f.write(text)
required = (
    "serving_adapter_resident",
    "serving_adapter_loads_total",
    "serving_adapter_evictions_total",
    "serving_adapter_restores_total",
    "serving_adapter_load_ms",
    "serving_tenant_queue_wait_seconds",
    "serving_shed_by_tenant_noisy_total",
    "serving_queue_wait_by_tenant_victim",
)
missing = [s for s in required if s not in text]
if missing:
    print("tenancy smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
print(f"tenancy smoke: ok ({len(required)} required series present, "
      f"{reg['evictions']} evictions / {reg['restores']} restores "
      f"byte-identical, {len(sheds)} noisy sheds all tenant_quota, "
      f"victim untouched, solo-identity holds)")
PY
    then
      echo "TENANCY-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    # handoff gate (ISSUE 20): a disaggregated prefill+decode pair behind
    # the router. One request must complete over a REAL live KV handoff
    # (export -> /kv_import -> adopt, zero fallbacks for it), then a
    # decode-side crash injected mid-import and finally a hard decode
    # kill must both complete via retry-or-fallback — zero failed
    # requests, byte-identical tokens on all three paths — and the
    # serving_kv_handoff_* series must be live on /metricsz. A handoff
    # that silently falls back on the clean path, drops a request when
    # the decode pool dies, or serves dark FAILS.
    echo "running handoff smoke $(date -u +%T)" >> "$log"
    if ! timeout 600 python - >> "$log" 2>&1 <<'PY'
import json
import sys
import time
import urllib.request

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from polyaxon_tpu.chaos.injector import active
from polyaxon_tpu.chaos.plan import Fault, FaultPlan
from polyaxon_tpu.models import build_model
from polyaxon_tpu.serving.batching import ServingConfig
from polyaxon_tpu.serving.router import P2CBalancer, Router, parse_prometheus
from polyaxon_tpu.serving.server import ModelServer

cfg = {"preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
       "n_heads": 4, "n_kv_heads": 2, "vocab_size": 128}
b = build_model("transformer_lm", cfg)
params = b.module.init(
    {"params": jax.random.PRNGKey(0)},
    jnp.zeros((1, 8), jnp.int32), train=False,
)["params"]


def server(role):
    return ModelServer(b.module, params, config=ServingConfig(
        max_batch=2, max_wait_ms=10.0, kv_page_tokens=8, kv_pool_pages=64,
        chunked_prefill=True, prefix_cache=True, role=role,
    ))


def post(port, rid):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps({"tokens": [list(range(1, 15))],
                         "maxNewTokens": 8, "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json", "X-Request-Id": rid},
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        return r.status, json.loads(r.read())


pre, dec = server("prefill"), server("decode")
pp, dp = pre.start(port=0), dec.start(port=0)
router = Router([f"http://127.0.0.1:{pp}", f"http://127.0.0.1:{dp}"],
                balancer=P2CBalancer(seed=7), poll_interval_s=0.1)
rp = router.start("127.0.0.1", 0)
try:
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        router.poll_once()
        reps = router.stats()["replicas"]
        if len(reps) == 2 and all(r["healthy"] for r in reps):
            break
        time.sleep(0.1)
    else:
        print("handoff smoke: pooled replicas never came healthy")
        sys.exit(1)
    # 1) clean path: a real export -> import -> adopt, no fallback
    s1, p1 = post(rp, "canary-h1")
    ho = pre.stats()["handoff"]
    im = dec.stats()["handoff"]
    if s1 != 200 or ho["exports"] < 1 or im["imports"] < 1:
        print("handoff smoke: no live handoff on the clean path",
              s1, ho, im)
        sys.exit(1)
    if ho["fallbacks"] != 0:
        print("handoff smoke: clean path fell back monolithic", ho)
        sys.exit(1)
    # 2) decode-side crash mid-import: retry-or-fallback, never a 5xx
    with active(FaultPlan([Fault("serving.kv_import", "raise", at=0)])):
        s2, p2 = post(rp, "canary-h1")
    # 3) hard decode kill: the pool is gone, the request still lands
    dec_text = urllib.request.urlopen(
        f"http://127.0.0.1:{dp}/metricsz", timeout=30).read().decode()
    dec.stop()
    s3, p3 = post(rp, "canary-h1")
    if s2 != 200 or s3 != 200:
        print("handoff smoke: request failed under decode loss", s2, s3)
        sys.exit(1)
    if not (p1["tokens"] == p2["tokens"] == p3["tokens"]):
        print("handoff smoke: fallback paths diverged",
              p1["tokens"], p2["tokens"], p3["tokens"])
        sys.exit(1)
    if pre.stats()["handoff"]["fallbacks"] < 1:
        print("handoff smoke: injected import crash never counted a "
              "fallback", pre.stats()["handoff"])
        sys.exit(1)
    # drain honesty: no leaked pages, no export stuck in flight
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        m = parse_prometheus(urllib.request.urlopen(
            f"http://127.0.0.1:{pp}/metricsz", timeout=30).read().decode())
        used = m.get("serving_kv_pages_used", 0.0)
        held = m.get("serving_kv_pages_prefix_held", 0.0)
        if used <= 1 + held and m.get("serving_kv_handoff_inflight") == 0:
            break
        time.sleep(0.1)
    else:
        print("handoff smoke: pages leaked or export stuck", m)
        sys.exit(1)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{pp}/metricsz", timeout=30).read().decode()
finally:
    router.stop()
    pre.stop()
    dec.stop()
with open("tpu_results/handoff_metricsz_tpu.txt", "w") as f:
    f.write(text + dec_text)
required = (
    "serving_kv_handoff_ms_bucket",
    "serving_kv_handoff_exports_total",
    "serving_kv_handoff_fallbacks_total",
    "serving_kv_handoff_inflight",
    "serving_kv_pages_handoff_held",
)
missing = [s for s in required if s not in text]
if "serving_kv_handoff_imports_total" not in dec_text:
    missing = list(missing) + ["serving_kv_handoff_imports_total (decode)"]
if missing:
    print("handoff smoke: MISSING series:", ", ".join(missing))
    sys.exit(1)
print(f"handoff smoke: ok ({len(required) + 1} required series present, "
      f"{ho['exports']} exports / {im['imports']} imports clean, "
      f"import-crash and decode-kill both completed byte-identically)")
PY
    then
      echo "HANDOFF-SMOKE-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    fi
    python scripts/lint_telemetry.py >> "$log" 2>&1 || {
      echo "TELEMETRY-LINT-FAILED $(date -u +%T); aborting capture" >> "$log"
      exit 1
    }
    touch tpu_results/COMPLETE
    (
      flock 9
      git add tpu_results BASELINE.md BASELINE.json 2>> "$log"
      # pathspec'd commit: only the canary's paths, never concurrently
      # staged interactive WIP
      git commit -m "Record TPU-measured bench results (canary capture)" \
        -- tpu_results BASELINE.md BASELINE.json >> "$log" 2>&1
    ) 9>.git/canary.lock
    echo "CANARY-COMPLETE $(date -u +%T)" >> "$log"
    break
  else
    echo "probe fail $(date -u +%T)" >> "$log"
  fi
  sleep 90
done
