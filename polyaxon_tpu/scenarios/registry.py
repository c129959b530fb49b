"""Named scenarios: trace + chaos ingredients + declarative assertions.

A `Scenario` composes
  * a seeded trace (a `traces.GENERATORS` entry plus params, with a
    smaller `smoke_params` overlay for CI),
  * an optional chaos ingredient (a `FaultPlan` against the scenario
    runner's own injection point, e.g. `scenario.replica_kill`, or the
    process-global serving points the replicas already instrument),
  * serving-config overrides (e.g. a tiny `kv_pool_pages` pool is the
    KV-exhaustion ingredient, a small `max_queue` the overload one),
  * declarative `Assertions` (max shed rate, p99 bound, SLO burn, zero
    hung requests, zero leaked KV pages).

`run_scenario(name, mode="real"|"twin")` replays the scenario either
against a live router+replicas rig (built here exactly like
tests/test_router.py builds one, or passed in for reuse) or through the
discrete-event twin — same trace, same seed, same assertion schema, so
a twin run and a real run of one scenario can be read side by side.

Rule 13: no raw clocks — waits go through `threading.Event.wait`,
measurements through `telemetry.now()`.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import urllib.request
from typing import Optional

from ..chaos.plan import FaultPlan
from ..telemetry import parse_prometheus_text
from .driver import replay
from .traces import generate
from .twin import PhaseCosts, ServingTwin, TwinConfig

# the rig's model: tiny transformer, seq_len 128 so prompt+new always
# fits, vocab 256 (trace prompt ids derive mod vocab_size)
RIG_MODEL_CFG = {
    "preset": "tiny", "seq_len": 128, "n_layers": 2, "dim": 64,
    "n_heads": 4, "n_kv_heads": 2, "vocab_size": 256,
}
_CHAOS_TICK_S = 0.1  # the scenario runner's chaos-clock granularity


@dataclasses.dataclass(frozen=True)
class Assertions:
    """Declarative pass/fail bounds, evaluated identically for real and
    twin runs (None disables a bound). `zero_hung` and
    `zero_leaked_pages` are the two hard invariants every scenario
    keeps on."""

    max_shed_rate: float = 1.0
    p99_ms: Optional[float] = None
    ttft_p50_ms: Optional[float] = None
    max_error_rate: Optional[float] = None
    max_slo_burn: Optional[float] = None
    min_completed: int = 1
    min_disconnects: int = 0
    min_prefix_hit_rate: Optional[float] = None
    zero_hung: bool = True
    zero_leaked_pages: bool = True
    # history predicates (ISSUE 18), evaluated over arrival-ordered
    # per-request series ("latency_ms", "ttft_ms", "ok") built from the
    # replay ledger (real) or the twin's TrendTapes — ONE schema for
    # both modes. `max_metric_trend` bounds mean(second half) /
    # mean(first half); `min_metric_floor` bounds the mean of EACH half
    # from below (a floor that must hold across the whole story).
    max_metric_trend: Optional[dict] = None
    min_metric_floor: Optional[dict] = None
    # tenancy predicates (ISSUE 19), read off the summary's `by_tenant`
    # block. `min_shed_share` binds tenant → min fraction of ALL sheds
    # attributed to that tenant (the noisy neighbor must absorb its own
    # flood — and implicitly, nobody else's sheds may grow). `tenant_p99_ms`
    # binds tenant → p99 latency ceiling (the victim's tail must stay
    # flat under the storm).
    min_shed_share: Optional[dict] = None
    tenant_p99_ms: Optional[dict] = None


@dataclasses.dataclass(frozen=True)
class Scenario:
    name: str
    description: str
    generator: str
    params: dict
    assertions: Assertions
    smoke_params: dict = dataclasses.field(default_factory=dict)
    serving_overrides: dict = dataclasses.field(default_factory=dict)
    chaos: Optional[str] = None  # "replica_kill" | "prefill_pool_kill"
    # disaggregated pools (ISSUE 20): (n_prefill, n_decode). The rig's
    # first `n_prefill` slots run role="prefill" (chunked prefill +
    # prefix cache, shipping finished page sets over /kv_import) and
    # the rest role="decode". None = monolithic replicas.
    pools: Optional[tuple] = None
    twin_config: dict = dataclasses.field(default_factory=dict)
    twin_only: bool = False
    # stamp each record's tenant into its request body (requires the
    # rig's servers to declare those tenants via serving_overrides)
    tenancy: bool = False
    seed: int = 0
    time_scale: float = 1.0


SCENARIOS: dict[str, Scenario] = {}


def _register(s: Scenario) -> Scenario:
    SCENARIOS[s.name] = s
    return s


_register(Scenario(
    name="diurnal_soak",
    description="Sinusoidal diurnal load with heavy-tailed lengths and "
                "a skewed tenant mix — the long-soak baseline.",
    generator="diurnal",
    params=dict(n=240, duration_s=24.0, base_rps=10.0, max_prompt=24),
    smoke_params=dict(n=32, duration_s=4.0, base_rps=8.0, max_prompt=24),
    assertions=Assertions(
        # p99 bound tolerates the trace's cold head: the first arrivals
        # pay XLA compiles (~20s on the 1-core CI box), which is host
        # speed, not serving behavior — the bound catches unbounded
        # queue waits, not compile time
        max_shed_rate=0.2, p99_ms=30_000.0, max_error_rate=0.0,
        max_slo_burn=20.0, min_completed=8,
        # history predicates (ISSUE 18): latency must not drift across
        # the soak (the generous ratio absorbs the CI box's compile
        # head landing in the FIRST half, which makes it look slow) and
        # the completion rate must hold in BOTH halves
        max_metric_trend={"latency_ms": 3.0},
        min_metric_floor={"ok": 0.5},
    ),
))

_register(Scenario(
    name="burst_overload",
    description="Correlated thundering-herd bursts over a Poisson base "
                "against a deliberately small admission queue — sheds "
                "are expected, hangs are not.",
    generator="bursts",
    params=dict(n=200, duration_s=12.0, base_rps=10.0, burst_factor=10.0,
                n_bursts=3, burst_len_s=1.5, max_prompt=24),
    smoke_params=dict(n=32, duration_s=3.0, base_rps=8.0, burst_factor=10.0,
                      n_bursts=1, burst_len_s=1.0, max_prompt=24),
    serving_overrides=dict(max_queue=8),
    assertions=Assertions(
        max_shed_rate=0.9, max_error_rate=0.0, min_completed=4,
    ),
    twin_config=dict(max_queue=8),
))

_register(Scenario(
    name="high_entropy_flood",
    description="Adversarial flood of unique uniform-random prompts at "
                "over-capacity rate plus a starved KV pool — exercises "
                "queue AND kv_pages shedding; goodput over throughput.",
    generator="flood",
    params=dict(n=160, rps=50.0, prompt_len=24, max_new=12),
    smoke_params=dict(n=28, rps=40.0, prompt_len=24, max_new=12),
    serving_overrides=dict(max_queue=8, kv_pool_pages=48),
    assertions=Assertions(
        max_shed_rate=0.95, max_error_rate=0.0, min_completed=2,
    ),
    twin_config=dict(max_queue=8, kv_pool_pages=48),
))

_register(Scenario(
    name="replica_kill_midsoak",
    description="A seed-chosen replica dies mid-soak; the monitor "
                "restarts it and the router retries around the outage — "
                "zero hung requests, zero leaked pages, no client-visible "
                "errors.",
    generator="diurnal",
    params=dict(n=160, duration_s=16.0, base_rps=10.0, max_prompt=24),
    smoke_params=dict(n=36, duration_s=6.0, base_rps=6.0, max_prompt=24),
    chaos="replica_kill",
    assertions=Assertions(
        max_shed_rate=0.5, max_error_rate=0.10, min_completed=8,
    ),
))

_register(Scenario(
    name="prefill_pool_outage",
    description="Disaggregated 1+1 pools (prefill ships live KV to "
                "decode over /kv_import); the WHOLE prefill pool dies "
                "mid-soak — the router degrades to monolithic decode-"
                "pool serving, in-flight handoffs fall back or retry, "
                "and nothing hangs or leaks on either side.",
    generator="diurnal",
    params=dict(n=160, duration_s=16.0, base_rps=10.0, max_prompt=24),
    smoke_params=dict(n=36, duration_s=6.0, base_rps=6.0, max_prompt=24),
    chaos="prefill_pool_kill",
    pools=(1, 1),
    assertions=Assertions(
        # the kill window costs at most the in-flight requests on the
        # dying prefill replica (same tolerance replica_kill_midsoak
        # carries); everything after degrades to the decode pool
        max_shed_rate=0.5, max_error_rate=0.10, min_completed=8,
    ),
    # twin mirror: the two-pool handoff-cost model (prefill pool
    # services prefill only, one handoff_ms per row to the decode pool,
    # local fallback when the decode pool cannot adopt)
    twin_config=dict(pools=(1, 1)),
))

_register(Scenario(
    name="disconnect_storm",
    description="Long streamed generations where half the clients vanish "
                "mid-stream — the server must cancel the rows, release "
                "their pages promptly, and count the disconnects.",
    generator="disconnect_storm",
    params=dict(n=60, rps=8.0, disconnect_frac=0.5, max_new=48),
    smoke_params=dict(n=16, rps=5.0, disconnect_frac=0.5, max_new=48),
    assertions=Assertions(
        max_shed_rate=0.3, max_error_rate=0.0, min_completed=4,
        min_disconnects=1,
    ),
))

_register(Scenario(
    name="prefix_storm",
    description="Shared-prefix cohorts hammered through the affinity "
                "router against a starved pool with a RAM spill tier — "
                "evictions demote to spill, cohort repeats restore, and "
                "the cluster-wide prefix hit rate is the gate alongside "
                "warm TTFT.",
    generator="shared_prefix",
    params=dict(n=200, rps=8.0, cohorts=4, prompt_len=24, max_new=8),
    # smoke arrivals spread WELL past the 1-core CI box's ~15s compile
    # head: prefix lookups happen at admission, so every request that
    # arrives before the first cohort member harvests is a structural
    # miss — a bunched trace would measure compile time, not the cache
    smoke_params=dict(n=24, rps=0.75, cohorts=3, prompt_len=24, max_new=8),
    serving_overrides=dict(prefix_cache=True, kv_pool_pages=64,
                           spill_ram_bytes=32 << 20),
    assertions=Assertions(
        # ttft_p50 binds in twin mode (the replay posts unstreamed, so
        # real-mode TTFT is absent and the bound is vacuous there); the
        # hit-rate gate is what must hold on the real stack
        max_shed_rate=0.2, max_error_rate=0.0, min_completed=8,
        min_prefix_hit_rate=0.25, ttft_p50_ms=30_000.0,
        # warm cohort repeats must not make the tail of the storm
        # slower than its head (prefix reuse should do the opposite)
        max_metric_trend={"latency_ms": 3.0},
        min_metric_floor={"ok": 0.5},
    ),
    twin_config=dict(prefix_cache=True, kv_pool_pages=64),
))

_register(Scenario(
    name="tenant_storm",
    description="A noisy tenant floods at ~10x the victim's rate into "
                "per-tenant admission caps — the flood sheds as "
                "tenant_quota against the noisy tenant alone while the "
                "victim tenant's steady trickle completes with a flat "
                "tail (the noisy-neighbor isolation story).",
    generator="tenant_storm",
    params=dict(n=240, noisy_frac=0.85, victim_rps=4.0, noisy_rps=40.0,
                prompt_len=16, max_new=8),
    smoke_params=dict(n=48, noisy_frac=0.75, victim_rps=1.5,
                      noisy_rps=25.0, prompt_len=16, max_new=8),
    tenancy=True,
    # the rig's replicas each cap the noisy tenant at 3 outstanding
    # rows; the victim rides uncapped (weights only matter under
    # contention for the batch head, which this trace never reaches)
    serving_overrides=dict(tenants=[
        dict(name="noisy", max_outstanding=3),
        dict(name="victim"),
    ]),
    assertions=Assertions(
        max_shed_rate=0.9, max_error_rate=0.0, min_completed=8,
        min_shed_share={"noisy": 0.95},
        # generous ceiling for the same reason diurnal_soak's p99 is:
        # the 1-core CI box's compile head is host speed, not isolation
        tenant_p99_ms={"victim": 45_000.0},
    ),
    # the twin's measured costs are steady-state (no compile head), so
    # at trace rates the default batched service would never accumulate
    # outstanding rows — serial batches and a tight fleet-wide cap
    # reproduce the contention the real rig reaches through its much
    # slower cold service
    twin_config=dict(tenants={"noisy": 1}, max_batch=1),
))

_register(Scenario(
    name="million_user_soak",
    description="A million-request, two-hour diurnal soak through the "
                "discrete-event twin — seconds of wall time on the CI "
                "box, impossible to drive for real there.",
    generator="diurnal",
    params=dict(n=1_000_000, duration_s=7200.0, base_rps=160.0,
                max_prompt=24),
    smoke_params=dict(n=1_000_000, duration_s=7200.0, base_rps=160.0,
                      max_prompt=24),
    twin_only=True,
    twin_config=dict(replicas=8, max_batch=8, max_queue=64,
                     kv_pool_pages=256, kv_page_tokens=8),
    assertions=Assertions(max_shed_rate=0.05, min_completed=500_000),
))


# ------------------------------------------------------------------ rig
class Rig:
    """A live 2+-replica router rig, shaped exactly like the
    tests/test_router.py fixture. Build once, reuse across scenarios;
    `stop()` tears the whole stack down."""

    def __init__(self, mgr, router, port: int, replicas: int):
        self.mgr = mgr
        self.router = router
        self.port = port
        self.replicas = replicas

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def replica_metricsz(self) -> list[str]:
        out = []
        for url in self.mgr.endpoints():
            try:
                out.append(_http_text(url + "/metricsz"))
            except Exception:  # noqa: BLE001 — a dead replica scrapes empty
                out.append("")
        return out

    def stop(self) -> None:
        self.router.stop()
        self.mgr.stop()


def build_rig(replicas: int = 2, overrides: Optional[dict] = None,
              slos: Optional[list] = None,
              pools: Optional[tuple] = None) -> Rig:
    import jax
    import jax.numpy as jnp

    from ..models import build_model
    from ..retry import RetryPolicy
    from ..serving.batching import ServingConfig
    from ..serving.replicas import InProcessReplica, ReplicaSetManager
    from ..serving.router import P2CBalancer, Router
    from ..serving.server import ModelServer

    bundle = build_model("transformer_lm", RIG_MODEL_CFG)
    params = bundle.module.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    overrides = dict(overrides or {})
    if overrides.get("tenants"):
        # scenario defs carry tenants as plain dicts; the config wants
        # the canonical pair-tuples
        from ..serving.tenancy import normalize_tenants

        overrides["tenants"] = normalize_tenants(overrides["tenants"])
    cfg = ServingConfig(**{
        "max_batch": 4, "max_wait_ms": 2.0, "kv_page_tokens": 8,
        "kv_pool_pages": 96, "stream_chunk_tokens": 4,
        # prefix_cache off by default so `serving_kv_pages_used == 1` at
        # drain IS the zero-leak invariant; scenarios that turn it on
        # (prefix_storm) have their warm pages discounted through the
        # serving_kv_pages_prefix_held gauge instead
        "prefix_cache": False,
        "request_timeout_s": 60.0,
        **overrides,
    })
    if slos is None:
        slos = [{"name": "availability", "kind": "availability",
                 "objective": 0.99}]
    # disaggregated pools (ISSUE 20): slots [0, n_prefill) run
    # role="prefill" (which requires chunked prefill + the prefix cache
    # — the handoff unit is the page-aligned prefix chain), the rest
    # role="decode" (prefix cache on so /kv_import has somewhere to
    # adopt pages). The slot-indexed factory keeps roles stable across
    # monitor restarts.
    if pools is not None:
        n_prefill = max(1, int(pools[0]))
        replicas = n_prefill + max(1, int(pools[1]))

    def _cfg_for(slot: int) -> ServingConfig:
        if pools is None:
            return cfg
        if slot < n_prefill:
            return dataclasses.replace(
                cfg, role="prefill", chunked_prefill=True,
                prefix_cache=True,
            )
        return dataclasses.replace(cfg, role="decode", prefix_cache=True)

    def _server(slot: int = -1):
        return ModelServer(
            bundle.module, params, model_name="scenario-rig",
            config=_cfg_for(slot), slos=slos,
        )

    mgr = ReplicaSetManager(
        lambda i: InProcessReplica(lambda slot=i: _server(slot)),
        replicas=replicas,
        retry=RetryPolicy(max_retries=3, backoff=0.05),
        monitor_interval_s=0.1,
    )
    router = Router(
        mgr.endpoints, balancer=P2CBalancer(seed=7), poll_interval_s=0.2
    )
    mgr.attach_router(router)
    mgr.start()
    port = router.start("127.0.0.1", 0)
    return Rig(mgr, router, port, replicas)


def _http_text(url: str, timeout: float = 10.0) -> str:
    return urllib.request.urlopen(url, timeout=timeout).read().decode()


def _sum_metric(texts: list[str], name: str) -> float:
    return sum(parse_prometheus_text(t).value(name, 0.0) for t in texts)


def _wait_drained(rig: Rig, budget_s: float = 20.0) -> list[str]:
    """Poll the replicas until queues are empty and every KV page is
    back (or the budget runs out); returns the final scrapes. A fully
    drained replica still reports one used page — the KV manager's
    permanently-allocated scratch page."""
    waiter = threading.Event()
    texts: list[str] = []
    for _ in range(max(1, int(budget_s / 0.2))):
        texts = rig.replica_metricsz()
        busy = False
        for t in texts:
            if not t:
                continue
            snap = parse_prometheus_text(t)
            # pages the prefix cache keeps on purpose are warm state,
            # not in-flight work — a warm rig still counts as drained
            held = snap.value("serving_kv_pages_prefix_held", 0.0)
            # an export in flight is work, not warmth: a prefill replica
            # mid-handoff must never report drained (ISSUE 20)
            if (
                snap.value("serving_queue_depth", 0.0) > 0
                or snap.value("serving_kv_handoff_inflight", 0.0) > 0
                or snap.value("serving_kv_pages_used", 0.0) > 1 + held
            ):
                busy = True
                break
        if not busy and any(texts):
            break
        waiter.wait(0.2)
    return texts


# ------------------------------------------------------------ evaluation
def half_means(values) -> tuple[Optional[float], Optional[float]]:
    """Mean of each half of an ordered value series (None, None when
    fewer than 4 points — too thin for a trend verdict). Pure; the
    history predicates in both real and twin modes ride this."""
    vals = [float(v) for v in values if v is not None]
    if len(vals) < 4:
        return None, None
    mid = len(vals) // 2
    return sum(vals[:mid]) / mid, sum(vals[mid:]) / (len(vals) - mid)


def evaluate(a: Assertions, summary: dict, metrics: dict,
             history: Optional[dict] = None) -> list[dict]:
    """Assertion verdicts for one run; identical schema for real and
    twin modes so calibration can diff them. `history` maps series
    name → arrival-ordered values for the ISSUE 18 trend/floor
    predicates."""
    out = []

    def check(name: str, ok: bool, detail: str) -> None:
        out.append({"assertion": name, "ok": bool(ok), "detail": detail})

    for series, max_ratio in sorted((a.max_metric_trend or {}).items()):
        first, second = half_means((history or {}).get(series, ()))
        if first is None:
            # too thin to call a drift — vacuous, but say so
            check(f"max_metric_trend:{series}", True,
                  f"insufficient samples for {series!r}, trend vacuous")
            continue
        ratio = (second / first) if first > 0 else None
        check(
            f"max_metric_trend:{series}",
            ratio is None or ratio <= max_ratio,
            f"trend={None if ratio is None else round(ratio, 4)} "
            f"<= {max_ratio} (halves {round(first, 3)} -> "
            f"{round(second, 3)})",
        )
    for series, floor in sorted((a.min_metric_floor or {}).items()):
        first, second = half_means((history or {}).get(series, ()))
        if first is None:
            check(f"min_metric_floor:{series}", False,
                  f"no samples for floor on {series!r}")
            continue
        low = min(first, second)
        check(
            f"min_metric_floor:{series}",
            low >= floor,
            f"floor={round(low, 4)} >= {floor} (halves "
            f"{round(first, 4)} / {round(second, 4)})",
        )

    by_tenant = summary.get("by_tenant") or {}
    for tenant, share in sorted((a.min_shed_share or {}).items()):
        total = summary.get("shed", 0) or sum(
            st.get("shed", 0) for st in by_tenant.values()
        )
        mine = by_tenant.get(tenant, {}).get("shed", 0)
        frac = (mine / total) if total else None
        check(
            f"min_shed_share:{tenant}",
            frac is not None and frac >= share,
            f"shed_share={None if frac is None else round(frac, 4)} "
            f">= {share} ({mine}/{total} sheds on {tenant!r})",
        )
    for tenant, bound in sorted((a.tenant_p99_ms or {}).items()):
        p99 = by_tenant.get(tenant, {}).get("latency_ms", {}).get("p99")
        check(f"tenant_p99_ms:{tenant}", p99 is None or p99 <= bound,
              f"p99={p99} <= {bound} for {tenant!r}")
    if a.zero_hung:
        check("zero_hung", summary["hung"] == 0,
              f"hung={summary['hung']}")
    if a.zero_leaked_pages:
        leaked = metrics.get("kv_pages_leaked", 0)
        check("zero_leaked_kv_pages", leaked == 0, f"leaked={leaked}")
    check("max_shed_rate", summary["shed_rate"] <= a.max_shed_rate,
          f"shed_rate={summary['shed_rate']} <= {a.max_shed_rate}")
    if a.p99_ms is not None:
        p99 = summary["latency_ms"]["p99"]
        check("p99_ms", p99 is None or p99 <= a.p99_ms,
              f"p99={p99} <= {a.p99_ms}")
    if a.ttft_p50_ms is not None:
        t50 = summary.get("ttft_ms", {}).get("p50")
        check("ttft_p50_ms", t50 is None or t50 <= a.ttft_p50_ms,
              f"ttft_p50={t50} <= {a.ttft_p50_ms}")
    if a.min_prefix_hit_rate is not None:
        rate = metrics.get("prefix_hit_rate")
        check(
            "min_prefix_hit_rate",
            rate is not None and rate >= a.min_prefix_hit_rate,
            f"prefix_hit_rate={rate} >= {a.min_prefix_hit_rate}",
        )
    if a.max_error_rate is not None:
        rate = summary["error"] / max(1, summary["offered"])
        check("max_error_rate", rate <= a.max_error_rate,
              f"error_rate={round(rate, 4)} <= {a.max_error_rate}")
    if a.max_slo_burn is not None and metrics.get("slo_burn") is not None:
        check("max_slo_burn", metrics["slo_burn"] <= a.max_slo_burn,
              f"burn={metrics['slo_burn']} <= {a.max_slo_burn}")
    completed = summary.get("ok", 0) + summary.get("disconnected", 0)
    check("min_completed", completed >= a.min_completed,
          f"completed={completed} >= {a.min_completed}")
    if a.min_disconnects:
        dc = metrics.get("client_disconnects", summary.get("disconnected", 0))
        check("min_disconnects", dc >= a.min_disconnects,
              f"disconnects={dc} >= {a.min_disconnects}")
    return out


# ------------------------------------------------------------------ run
def _records(scn: Scenario, smoke: bool, seed: Optional[int]):
    params = dict(scn.params)
    if smoke:
        params.update(scn.smoke_params)
    return generate(
        scn.generator, scn.seed if seed is None else seed, **params
    ), params


def _twin_faults(scn: Scenario, seed: int, duration_s: float,
                 replicas: int) -> list[dict]:
    window = max(2, int(duration_s / _CHAOS_TICK_S))
    if scn.chaos == "replica_kill":
        plan = FaultPlan.replica_kill_midsoak(seed, window=window,
                                              replicas=replicas)
        return [{
            "kind": "replica_down",
            "replica": plan.params["kill_slot"],
            "at_s": plan.params["kill_tick"] * _CHAOS_TICK_S,
            # the monitor's restart latency, scaled into sim time
            "duration_s": 1.0,
        }]
    if scn.chaos == "prefill_pool_kill":
        # the whole prefill pool dies at one seed-chosen tick (ISSUE 20)
        n_prefill = max(1, int((scn.pools or (1, 1))[0]))
        plan = FaultPlan.replica_kill_midsoak(seed, window=window,
                                              replicas=n_prefill)
        at_s = plan.params["kill_tick"] * _CHAOS_TICK_S
        return [
            {"kind": "replica_down", "replica": slot, "at_s": at_s,
             "duration_s": 1.0}
            for slot in range(n_prefill)
        ]
    return []


def run_twin(scn: Scenario, *, smoke: bool = False,
             seed: Optional[int] = None,
             costs: Optional[PhaseCosts] = None) -> dict:
    records, params = _records(scn, smoke, seed)
    use_seed = scn.seed if seed is None else seed
    cfg = TwinConfig(**{
        "replicas": 2, "max_batch": 4, "max_queue": 64,
        "kv_pool_pages": 96, "kv_page_tokens": 8,
        **scn.twin_config,
    })
    horizon = float(params.get("duration_s") or 0.0)
    if not horizon:
        n, rps = params.get("n", 0), params.get("rps", 0)
        horizon = (n / rps) if rps else 0.0
    twin = ServingTwin(
        cfg, costs or PhaseCosts(),
        faults=_twin_faults(scn, use_seed, horizon, cfg.replicas),
        seed=use_seed,
    )
    summary = twin.run(records)
    metrics = {
        "kv_pages_leaked": summary["kv_pages_leaked"],
        "prefix_hit_rate": summary.get("prefix", {}).get("hit_rate"),
    }
    history = {k: list(t.points) for k, t in twin.tapes.items()}
    verdicts = evaluate(scn.assertions, summary, metrics, history)
    return {
        "scenario": scn.name,
        "mode": "twin",
        "seed": use_seed,
        "params": params,
        "summary": summary,
        "assertions": verdicts,
        "pass": all(v["ok"] for v in verdicts),
    }


def run_real(scn: Scenario, *, smoke: bool = False,
             seed: Optional[int] = None, rig: Optional[Rig] = None,
             replicas: int = 2, time_scale: Optional[float] = None) -> dict:
    if scn.twin_only:
        raise ValueError(f"scenario {scn.name} is twin-only")
    records, params = _records(scn, smoke, seed)
    records = list(records)
    use_seed = scn.seed if seed is None else seed
    own_rig = rig is None
    if own_rig:
        rig = build_rig(replicas=replicas, overrides=scn.serving_overrides,
                        pools=scn.pools)
    stop_chaos = threading.Event()
    chaos_thread = None
    chaos_params = {}
    try:
        if scn.chaos in ("replica_kill", "prefill_pool_kill"):
            horizon = float(params.get("duration_s", 10.0))
            window = max(2, int(horizon / _CHAOS_TICK_S))
            if scn.chaos == "replica_kill":
                plan = FaultPlan.replica_kill_midsoak(
                    use_seed, window=window, replicas=rig.replicas,
                )
                kill_slots = [plan.params["kill_slot"]]
            else:
                # the WHOLE prefill pool dies together (ISSUE 20): the
                # seed picks the tick, the pool picks the slots
                n_prefill = max(1, int((scn.pools or (1, 1))[0]))
                plan = FaultPlan.replica_kill_midsoak(
                    use_seed, window=window, replicas=n_prefill,
                )
                kill_slots = list(range(n_prefill))
            chaos_params = dict(plan.params, kill_slots=kill_slots)

            def _tick():
                while not stop_chaos.wait(_CHAOS_TICK_S):
                    fault = plan.fire("scenario.replica_kill")
                    if fault is not None and fault.action == "kill":
                        for slot in kill_slots:
                            try:
                                rig.mgr.replica(slot).kill()
                            except Exception:  # noqa: BLE001 — already dead is fine
                                pass

            chaos_thread = threading.Thread(target=_tick, daemon=True)
            chaos_thread.start()
        report = replay(
            records, rig.url,
            vocab_size=RIG_MODEL_CFG["vocab_size"],
            time_scale=time_scale or scn.time_scale,
            timeout_s=60.0,
            rid_prefix=scn.name,
            tenancy=scn.tenancy,
        )
        stop_chaos.set()
        texts = _wait_drained(rig)
        summary = report.summary()
        live_texts = [t for t in texts if t]
        prefix_hits = _sum_metric(live_texts,
                                  "serving_prefix_cache_hits_total")
        prefix_misses = _sum_metric(live_texts,
                                    "serving_prefix_cache_misses_total")
        metrics = {
            # every live replica permanently holds exactly one page (the
            # KV manager's scratch page) plus whatever distinct pages the
            # prefix cache holds on purpose (serving_kv_pages_prefix_held)
            # — anything above that at drain is a leak
            "kv_pages_leaked": int(sum(
                max(0.0,
                    parse_prometheus_text(t).value("serving_kv_pages_used",
                                                   0.0)
                    - 1.0
                    - parse_prometheus_text(t).value(
                        "serving_kv_pages_prefix_held", 0.0))
                for t in live_texts
            )),
            "prefix_hit_rate": (
                round(prefix_hits / (prefix_hits + prefix_misses), 4)
                if (prefix_hits + prefix_misses) > 0 else None
            ),
            "client_disconnects": int(
                _sum_metric(live_texts, "serving_client_disconnects_total")
            ),
            "slo_burn": (
                max(
                    (parse_prometheus_text(t).value("slo_burn_rate", 0.0)
                     for t in live_texts),
                    default=0.0,
                )
                if live_texts else None
            ),
        }
        # the same history series the twin tapes, rebuilt off the
        # replay ledger in arrival order (ISSUE 18)
        outs = sorted(report.outcomes, key=lambda o: o.i)
        history = {
            "latency_ms": [
                o.latency_ms for o in outs
                if o.status == 200 and o.latency_ms is not None
            ],
            "ttft_ms": [
                o.ttft_ms for o in outs if o.ttft_ms is not None
            ],
            "ok": [
                1.0 if (o.status == 200 or o.disconnected) else 0.0
                for o in outs
            ],
        }
        verdicts = evaluate(scn.assertions, summary, metrics, history)
        return {
            "scenario": scn.name,
            "mode": "real",
            "seed": use_seed,
            "params": params,
            "chaos": chaos_params or None,
            "summary": summary,
            "metrics": metrics,
            "replica_metricsz": live_texts,
            "assertions": verdicts,
            "pass": all(v["ok"] for v in verdicts),
        }
    finally:
        stop_chaos.set()
        if chaos_thread is not None:
            chaos_thread.join(2.0)
        if own_rig:
            rig.stop()


def run_scenario(name: str, *, mode: str = "real", smoke: bool = False,
                 seed: Optional[int] = None, rig: Optional[Rig] = None,
                 replicas: int = 2,
                 costs: Optional[PhaseCosts] = None) -> dict:
    try:
        scn = SCENARIOS[name]
    except KeyError:
        raise ValueError(
            f"unknown scenario {name!r} "
            f"(have: {', '.join(sorted(SCENARIOS))})"
        ) from None
    if mode == "twin" or (scn.twin_only and mode != "real"):
        return run_twin(scn, smoke=smoke, seed=seed, costs=costs)
    if mode != "real":
        raise ValueError(f"mode must be real|twin, got {mode!r}")
    return run_real(scn, smoke=smoke, seed=seed, rig=rig, replicas=replicas)


def scenario_table() -> list[dict]:
    """`polyaxon scenario ls` rows."""
    return [
        {
            "name": s.name,
            "generator": s.generator,
            "chaos": s.chaos or "-",
            "mode": "twin-only" if s.twin_only else "real+twin",
            "description": s.description,
        }
        for s in SCENARIOS.values()
    ]
