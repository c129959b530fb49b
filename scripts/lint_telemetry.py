#!/usr/bin/env python
"""Telemetry lint: exactly ONE metrics clock in the package.

Every duration measurement in `polyaxon_tpu/` must go through
`polyaxon_tpu.telemetry.now()` (or a span) so all latency numbers share
one clock and land in one registry. This script fails CI when any module
outside `polyaxon_tpu/telemetry/` calls `time.perf_counter` — the
tell-tale of a hand-rolled timing loop growing a second metrics
pipeline. `time.monotonic` stays allowed: the serving queue uses it for
deadlines (scheduling, not metrics).

Second rule, same spirit: exactly ONE scheduling clock in the fleet
scheduler. Everything under `polyaxon_tpu/scheduler/` must take time
from an injected `Clock` (`polyaxon_tpu/scheduler/clock.py`) so the
simulator/benchmark can replace it with `SimClock` and replay a workload
deterministically. A raw `time.time()`/`time.monotonic()` there would be
invisible to the simulated clock and silently skew queue-wait math, so
both are forbidden outside `scheduler/clock.py`.

Third rule: ONE deadline clock in serving. Deadline math in
`polyaxon_tpu/serving/` must use `time.monotonic()` — a `time.time()`
deadline jumps with NTP steps and DST, silently shedding live requests
(or keeping dead ones), so raw `time.time()` is forbidden there.

Fourth rule: NO clock at all in page-pool accounting. The paged-KV
modules (`polyaxon_tpu/models/kv_pages.py`, `polyaxon_tpu/serving/kv.py`)
order LRU eviction by a logical tick and observe durations (TTFT) only
through the telemetry clock helpers in the server layer — a raw
`time.*()` read inside the pool accounting would couple eviction order
and occupancy math to the host clock, making paged-vs-dense replay
nondeterministic and TTFT double-clocked. Any `time.time/monotonic/
perf_counter` (and `_ns` variants) there is forbidden.

Fifth rule: NO raw clock in checkpoint-tier/elastic accounting. The
tiered-checkpoint module (`polyaxon_tpu/runtime/checkpoint.py`) orders
saves/uploads/restores purely by step number, and the one duration that
matters — the step-loop checkpoint stall — is measured by the trainer's
span tree on the telemetry clock (`trainer_checkpoint_stall_ms`). A raw
`time.*()` read inside the tier machinery would grow a second stall
clock that can disagree with the histogram /metricsz serves, so any
`time.time/monotonic/perf_counter` (and `_ns` variants) there is
forbidden.

Sixth rule: NO raw clock in the fast-decode modules. Speculative
decoding (`polyaxon_tpu/models/spec_decode.py`) orders drafting, verify
and commit purely by logical generation index — the per-row key
schedule `fold_in(key, g)` is what makes speculative output
byte-identical to plain decode, and a wall-clock read anywhere in that
path is a tell that something (drafter pruning, window sizing) has been
coupled to host timing and replay just broke. Weight-only quantization
(`polyaxon_tpu/models/quant.py`) is a load-time tree transform with no
duration of its own; its one observable (bytes saved) is a counter, not
a latency. Any `time.time/monotonic/perf_counter` (and `_ns` variants)
in either module is forbidden — logical generation index only.

Seventh rule: the SLO/trace layer itself uses only the injected
telemetry clock. `polyaxon_tpu/telemetry/slo.py` (burn-rate windows)
and `polyaxon_tpu/telemetry/tracing.py` (request span timelines) are
the modules whose OUTPUT alerting reads; a raw `time.*()` read
there would mix wall-clock (NTP steps, DST) into burn windows and span
durations — the exact drift this lint exists to prevent. They must take
time from `registry.now` (or an injected `clock=` callable), so any
direct `time.time/monotonic/perf_counter` (and `_ns` variants) call in
those two files is forbidden. The rest of `polyaxon_tpu/telemetry/`
stays exempt (registry.py DEFINES the clock; spans.py stamps wall-clock
`ts` for log correlation by design).

Eighth rule: NO raw clock in the serving router. The router
(`polyaxon_tpu/serving/router.py`) balances on queue-wait deltas it
scrapes off replica /metricsz and feeds its own latency histogram and
the autoscale burn engine — all of which live on the telemetry clock
(`registry.now`). A `time.time()`/`datetime.now()` (or `time.monotonic`
outside the sanctioned helper) read there would mix a second clock into
the balancing signal and the burn windows: NTP steps would reorder
replicas and flap the autoscaler. The router must take time ONLY from
`telemetry.now()`, so any direct `time.*` / `datetime.now/utcnow/today`
call in that file is forbidden.

Ninth rule: NO raw clock in the event-log store. The run event log
(`polyaxon_tpu/store/eventlog.py`) is the control plane's single
ordering authority: replay, watch cursors, and crash recovery all order
by monotonic sequence number, and the two timestamps it does emit
(record `ts`, fsync latency) come from INJECTED callables (`wall=`,
`mono=` passed by the store layer). A direct `time.*()` /
`datetime.now()` read there would couple replay to the host clock —
chaos tests could no longer replay byte-identical histories — and
`time.sleep` would hide a missing commit-notification path. Any direct
`time.time/monotonic/perf_counter/sleep` (and `_ns` variants) or
`datetime.now/utcnow/today` call in that file is forbidden: order by
sequence number, take clocks through the constructor.

Tenth rule: NO clock at all in metrics federation or timeline folding.
Federation (`polyaxon_tpu/telemetry/federate.py`) is a pure text
transform — parse N scraped expositions, re-label, aggregate — and the
run timeline (`polyaxon_tpu/store/timeline.py`) is a pure fold over
committed event-log records whose ordering authority is the sequence
number. A raw `time.*()` / `datetime.now()` read in either would smuggle
a time axis into layers whose whole correctness story is that they have
none (federated aggregates must be reproducible from the same scrape
texts; timelines must replay byte-identical from the same log). Any
direct `time.time/monotonic/perf_counter/sleep` (and `_ns` variants) or
`datetime.now/utcnow/today` call in those files is forbidden.

Eleventh rule: NO raw clock in the step scheduler. The chunked-prefill
step loop (`polyaxon_tpu/serving/steps.py`) decides what each device
step runs purely from logical state — token budgets, chunk offsets,
row phases — and delegates every time-touching concern outward: row
deadlines are evaluated by `PendingRequest.expired()` (the monotonic
clock lives in batching.py, rule 3), and every duration the operator
sees (TTFT, step tokens, queue wait) is observed by the server's
engine on the telemetry clock. A raw `time.*()` / `datetime.now()`
read inside the scheduler would couple step composition to host timing
— the same request mix could schedule differently across runs, and
the byte-identity story (chunked ≡ one-shot) would no longer be
testable by replay. Any direct `time.time/monotonic/perf_counter/
sleep` (and `_ns` variants) or `datetime.now/utcnow/today` call in
that file is forbidden: schedule on logical state, take time through
injected collaborators.

Twelfth rule: NO raw clock in adaptive speculation. The draft model
(`polyaxon_tpu/models/draft.py`) keys its cache frontier and its sampling
schedule purely on the logical generation index — the same
`fold_in(key, g)` discipline rule 6 pins for spec_decode — and the
accept-rate controller (`polyaxon_tpu/serving/adaptive.py`) windows its
K decisions on PROPOSED-TOKEN counts and re-probes on logical plain-step
ticks. A wall-clock read in either would couple the draft width (and so
the entire serving batch composition) to host scheduling jitter: the
same traffic would speculate differently across runs and the
byte-identity replays the tests pin would stop being replays. Any
`time.time/monotonic/perf_counter/sleep` (and `_ns` variants) or
`datetime.now/utcnow/today` call in those two files is forbidden: count
proposals and logical steps, never seconds.

Thirteenth rule: NO raw clock in the scenario engine. Everything under
`polyaxon_tpu/scenarios/` — trace generation, the open-loop replay
driver, the discrete-event twin, the scenario registry — must take
measurements from `telemetry.now()` and schedule waits through
`threading.Event.wait`. The whole point of the engine is replayability:
a trace is a pure function of (generator, seed, params), the twin runs
on the injectable SimClock, and the driver's ledger is what the
calibration gate (`sim_vs_real_calibration_error`) diffs against the
twin. A raw `time.*()` / `datetime.now()` / `time.sleep` read anywhere
in there would couple a scenario's story to the host clock — the same
seed would stop replaying the same soak. Any direct `time.time/
monotonic/perf_counter/sleep` (and `_ns` variants) or
`datetime.now/utcnow/today` call in that directory is forbidden.

Fourteenth rule: NO raw clock in the tiered-KV spill/directory modules.
The spill store (`polyaxon_tpu/serving/spill.py`) orders its RAM-tier
LRU by insertion order and its disk tier by segment sequence number,
and the router-side prefix directory
(`polyaxon_tpu/serving/affinity.py`) is a pure map from poll-loop
advertisements to candidate ordering — freshness is "whatever the last
poll wrote", never an age in seconds. A raw `time.*()` /
`datetime.now()` read in either would couple spill/restore order and
affinity decisions to the host clock: the chaos replays (kill mid-
spill, corrupt-segment quarantine) and the scenario twin's prefix
model would stop reproducing. Any direct `time.time/monotonic/
perf_counter/sleep` (and `_ns` variants) or `datetime.now/utcnow/
today` call in those two files is forbidden: order by logical
sequence, measure in the server layer on the telemetry clock.

Fifteenth rule: NO raw clock in the metrics-history store or the
regression sentinel. The history store (`polyaxon_tpu/telemetry/
history.py`) timestamps nothing itself — every sample's `t` comes from
the caller (the sampler's injected clock), which is what lets the tests
replay deterministic histories and the downsampler/retention math stay
reproducible. The sentinel (`polyaxon_tpu/telemetry/detect.py`)
evaluates rules at an injected `clock=` time for the same reason: a raw
`time.*()` read in either would couple stored timestamps and rule
windows to the host clock, so `rate()` and EWMA baselines could not be
pinned against exact references. Any direct `time.time/monotonic/
perf_counter/sleep` (and `_ns` variants) or `datetime.now/utcnow/today`
call in those two files is forbidden: timestamps come in through
`append(sample)`, evaluation time through the injected clock.

Sixteenth rule: NO raw clock in tenancy admission or adapter
residency. The per-tenant admission ledger
(`polyaxon_tpu/serving/tenancy.py`) counts outstanding rows and queued
tokens — pure occupancy, no ages — and the adapter registry
(`polyaxon_tpu/serving/adapters.py`) orders LRU recency by a logical
sequence counter, exactly like the spill tiers it demotes into (rule
14). A raw `time.*()` / `datetime.now()` read in either would couple
shed decisions and eviction order to host timing: the same tenant storm
would shed different requests across runs, and the chaos replay (kill
mid-restore → zero leak) would stop reproducing. Every duration the
operator sees — per-tenant queue wait, adapter load time — is observed
by the server layer on the telemetry clock. Any direct
`time.time/monotonic/perf_counter/sleep` (and `_ns` variants) or
`datetime.now/utcnow/today` call in those two files is forbidden.

Seventeenth rule: NO raw clock in the KV handoff module. The
prefill→decode transfer layer (`polyaxon_tpu/serving/handoff.py`) —
lease table, wire codec, transfer client — is pure protocol state:
epochs are logical integers, retry backoff sleeps ride
`threading.Event.wait` on the shared `RetryPolicy` curve, and the only
deadline is the per-attempt socket timeout. A raw `time.*()` /
`datetime.now()` read there would couple lease outcomes and retry
schedules to host timing: the seeded chaos replays (kill at export/
import/adopt → zero leak, clean retry or clean fallback) and the
stale-epoch rejection tests would stop reproducing. The handoff
latency the operator sees (`serving_kv_handoff_ms`) is observed by the
server layer on the telemetry clock. Any direct `time.time/monotonic/
perf_counter/sleep` (and `_ns` variants) or `datetime.now/utcnow/
today` call in that file is forbidden.

Scope is the package only. The benchmark (`cellbench/`), tests and
top-level scripts own their methodology and are exempt.

    python scripts/lint_telemetry.py        # exit 0 clean, 1 with hits
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

PATTERN = re.compile(r"\bperf_counter\b")
SCHED_PATTERN = re.compile(r"\btime\.(?:time|monotonic)\s*\(")
SERVING_PATTERN = re.compile(r"\btime\.time\s*\(")
KV_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter)(?:_ns)?\s*\("
)
KV_MODULES = (
    ("polyaxon_tpu", "models", "kv_pages.py"),
    ("polyaxon_tpu", "serving", "kv.py"),
)
CKPT_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter)(?:_ns)?\s*\("
)
CKPT_MODULES = (
    ("polyaxon_tpu", "runtime", "checkpoint.py"),
)
SPEC_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter)(?:_ns)?\s*\("
)
SPEC_MODULES = (
    ("polyaxon_tpu", "models", "spec_decode.py"),
    ("polyaxon_tpu", "models", "quant.py"),
)
SLO_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter)(?:_ns)?\s*\("
)
SLO_MODULES = (
    ("polyaxon_tpu", "telemetry", "slo.py"),
    ("polyaxon_tpu", "telemetry", "tracing.py"),
)
ROUTER_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
ROUTER_MODULES = (
    ("polyaxon_tpu", "serving", "router.py"),
)
STORE_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
STORE_MODULES = (
    ("polyaxon_tpu", "store", "eventlog.py"),
)
PURE_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
#: clock-free pure transforms: federation text rewriting and the
#: event-log timeline fold (rule 10)
PURE_MODULES = (
    ("polyaxon_tpu", "telemetry", "federate.py"),
    ("polyaxon_tpu", "store", "timeline.py"),
)
STEPS_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
#: the chunked-prefill step scheduler schedules on logical state only
#: (rule 11); clocks live in its collaborators
STEPS_MODULES = (
    ("polyaxon_tpu", "serving", "steps.py"),
)
ADAPTIVE_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
#: adaptive speculation counts proposals and logical steps, never
#: seconds (rule 12): drafting and K control must replay deterministically
ADAPTIVE_MODULES = (
    ("polyaxon_tpu", "models", "draft.py"),
    ("polyaxon_tpu", "serving", "adaptive.py"),
)
SCENARIO_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
#: the scenario engine replays: traces are pure functions of their seed,
#: the twin rides SimClock, the driver measures on telemetry.now() and
#: waits on threading.Event (rule 13)
SPILL_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
#: tiered-KV spill orders by logical sequence, the prefix directory by
#: the last poll's advertisement — no time axis (rule 14)
SPILL_MODULES = (
    ("polyaxon_tpu", "serving", "spill.py"),
    ("polyaxon_tpu", "serving", "affinity.py"),
)
HISTORY_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
#: the metrics-history store timestamps nothing (sample `t` comes from
#: the caller) and the regression sentinel evaluates at an injected
#: clock — both must replay deterministic histories (rule 15)
HISTORY_MODULES = (
    ("polyaxon_tpu", "telemetry", "history.py"),
    ("polyaxon_tpu", "telemetry", "detect.py"),
)
TENANCY_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
#: tenancy admission ledgers count outstanding rows/tokens and the
#: adapter registry orders recency by a logical seq counter — no time
#: axis, so per-tenant chaos replays stay deterministic (rule 16)
TENANCY_MODULES = (
    ("polyaxon_tpu", "serving", "tenancy.py"),
    ("polyaxon_tpu", "serving", "adapters.py"),
)
HANDOFF_PATTERN = re.compile(
    r"\btime\.(?:time|monotonic|perf_counter|sleep)(?:_ns)?\s*\("
    r"|\bdatetime\.(?:now|utcnow|today)\s*\("
)
#: the KV handoff layer is pure protocol state — logical epochs, Event-
#: based backoff, socket-timeout deadlines — so seeded chaos replays
#: reproduce (rule 17); the latency histogram is the server layer's
HANDOFF_MODULES = (
    ("polyaxon_tpu", "serving", "handoff.py"),
)


def violations(repo_root: Path) -> list[str]:
    pkg = repo_root / "polyaxon_tpu"
    out = []
    for py in sorted(pkg.rglob("*.py")):
        rel = py.relative_to(repo_root)
        if rel.parts[:2] == ("polyaxon_tpu", "telemetry"):
            # the telemetry package owns the clock — exempt from rules
            # 1-6, but the SLO/trace modules must take time via
            # registry.now / an injected clock, never directly
            if rel.parts in SLO_MODULES:
                for i, line in enumerate(
                    py.read_text().splitlines(), 1
                ):
                    code = line.split("#", 1)[0]
                    if SLO_PATTERN.search(code):
                        out.append(
                            f"{rel}:{i}: raw clock in the SLO/trace "
                            f"layer — inject the telemetry clock "
                            f"(registry.now): {line.strip()}"
                        )
            if rel.parts in PURE_MODULES:
                for i, line in enumerate(
                    py.read_text().splitlines(), 1
                ):
                    code = line.split("#", 1)[0]
                    if PURE_PATTERN.search(code):
                        out.append(
                            f"{rel}:{i}: clock in a pure transform — "
                            f"federation/timeline code has no time "
                            f"axis: {line.strip()}"
                        )
            if rel.parts in HISTORY_MODULES:
                for i, line in enumerate(
                    py.read_text().splitlines(), 1
                ):
                    code = line.split("#", 1)[0]
                    if HISTORY_PATTERN.search(code):
                        out.append(
                            f"{rel}:{i}: raw clock in the metrics "
                            f"history/sentinel layer — timestamps come "
                            f"from callers, evaluation time from the "
                            f"injected clock: {line.strip()}"
                        )
            continue
        in_scheduler = rel.parts[:2] == ("polyaxon_tpu", "scheduler")
        clock_exempt = in_scheduler and rel.name == "clock.py"
        in_serving = rel.parts[:2] == ("polyaxon_tpu", "serving")
        in_kv = rel.parts in KV_MODULES
        in_ckpt = rel.parts in CKPT_MODULES
        in_spec = rel.parts in SPEC_MODULES
        in_router = rel.parts in ROUTER_MODULES
        in_store = rel.parts in STORE_MODULES
        in_pure = rel.parts in PURE_MODULES
        in_steps = rel.parts in STEPS_MODULES
        in_adaptive = rel.parts in ADAPTIVE_MODULES
        in_scenarios = rel.parts[:2] == ("polyaxon_tpu", "scenarios")
        in_spill = rel.parts in SPILL_MODULES
        in_tenancy = rel.parts in TENANCY_MODULES
        in_handoff = rel.parts in HANDOFF_MODULES
        for i, line in enumerate(py.read_text().splitlines(), 1):
            code = line.split("#", 1)[0]
            if PATTERN.search(code):
                out.append(f"{rel}:{i}: {line.strip()}")
            if in_scheduler and not clock_exempt and SCHED_PATTERN.search(
                code
            ):
                out.append(
                    f"{rel}:{i}: raw wall clock in scheduler/ "
                    f"(use scheduler.clock.Clock): {line.strip()}"
                )
            if in_serving and SERVING_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: time.time() in serving/ — deadlines "
                    f"must use time.monotonic(): {line.strip()}"
                )
            if in_kv and KV_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in page-pool accounting — "
                    f"use a logical tick or the telemetry clock "
                    f"helpers: {line.strip()}"
                )
            if in_ckpt and CKPT_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in checkpoint-tier/elastic "
                    f"accounting — order by step number; durations go "
                    f"through the trainer's telemetry spans: {line.strip()}"
                )
            if in_spec and SPEC_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in the fast-decode path — "
                    f"speculation/quant order by logical generation "
                    f"index only: {line.strip()}"
                )
            if in_router and ROUTER_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in the serving router — "
                    f"balancing and autoscale burn must ride "
                    f"telemetry.now() only: {line.strip()}"
                )
            if in_store and STORE_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in the event-log store — "
                    f"order by sequence number; clocks are injected "
                    f"(wall=/mono= ctor args): {line.strip()}"
                )
            if in_pure and PURE_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: clock in a pure transform — "
                    f"federation/timeline code has no time "
                    f"axis: {line.strip()}"
                )
            if in_steps and STEPS_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in the step scheduler — "
                    f"schedule on logical state; deadlines and "
                    f"durations belong to its collaborators: "
                    f"{line.strip()}"
                )
            if in_adaptive and ADAPTIVE_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in adaptive speculation — "
                    f"drafting and K control count proposals and "
                    f"logical steps, never seconds: {line.strip()}"
                )
            if in_scenarios and SCENARIO_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in the scenario engine — "
                    f"traces replay from their seed, the twin rides "
                    f"SimClock; measure via telemetry.now(), wait via "
                    f"threading.Event.wait: {line.strip()}"
                )
            if in_spill and SPILL_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in tiered-KV spill/affinity "
                    f"— spill orders by logical sequence, the prefix "
                    f"directory by the last poll's advertisement; "
                    f"durations belong to the server layer: "
                    f"{line.strip()}"
                )
            if in_tenancy and TENANCY_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in tenancy/adapter "
                    f"residency — admission counts rows and tokens, "
                    f"the registry orders recency by its logical seq; "
                    f"queue-wait timing belongs to the server layer: "
                    f"{line.strip()}"
                )
            if in_handoff and HANDOFF_PATTERN.search(code):
                out.append(
                    f"{rel}:{i}: raw clock in the KV handoff layer — "
                    f"epochs are logical, backoff rides "
                    f"threading.Event.wait, deadlines are socket "
                    f"timeouts; handoff latency belongs to the server "
                    f"layer: {line.strip()}"
                )
    return out


def main() -> int:
    root = Path(__file__).resolve().parent.parent
    hits = violations(root)
    if hits:
        print(
            "telemetry lint: raw time.perf_counter outside "
            "polyaxon_tpu/telemetry/ — route timing through "
            "polyaxon_tpu.telemetry.now() / spans instead:",
            file=sys.stderr,
        )
        for h in hits:
            print(f"  {h}", file=sys.stderr)
        return 1
    print("telemetry lint: ok (one metrics clock)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
