"""Discrete-event serving twin: million-user soaks in seconds on CI.

The twin replays a trace through a simulated router + replica set on
the injectable `scheduler.clock.SimClock` — the same sim-validates-real
idiom `scheduler/sim.py` proved for the fleet scheduler, pointed at the
serving stack. Simulated replicas are driven by measured per-phase
costs (`PhaseCosts`: prefill-per-token, decode-step, per-batch
overhead) fitted from real `/metricsz` scrapes, so a multi-hour
million-request soak runs in seconds of wall time. How far its
shed-rate and latency predictions are from the real stack's is not
measured by anything in the tree.

What the twin models — deliberately at batch granularity, the level the
measured costs live at:

* JSQ routing with shed-retry on a sibling (the router's 503 retry);
* bounded per-replica queues (`max_queue`) shedding `queue_full`;
* KV page reservation at admission (`kv_pool_pages`) shedding
  `kv_pages` when the pool cannot fit the row — the exhaustion
  ingredient;
* batched service: up to `max_batch` rows prefill together and decode
  in lockstep for max-of-row steps (the coalescer's group shape);
* deadline purge at dispatch (504s without spending step budget);
* mid-stream client disconnects truncating a row's decode steps (the
  satellite-1 cancellation path);
* chaos ingredients: replica-down windows (queued + in-flight rows
  fail over to siblings; the dead replica's pages drop with it, the
  monitor brings it back empty).

Invariants checked structurally at drain: every offered request has
exactly one outcome (zero hung) and every page is back in the pool
(zero leaked). No raw clocks anywhere (lint_telemetry rule 13) — the
wall-clock timing of a twin run is the CALLER's business.
"""

from __future__ import annotations

import dataclasses
import heapq
import math
import random
from collections import deque
from typing import Iterable, Optional

from ..scheduler.clock import SimClock
from ..telemetry import parse_prometheus_text, quantile
from .traces import TraceRequest

_RESERVOIR = 200_000  # latency samples kept for quantiles (seeded reservoir)


@dataclasses.dataclass(frozen=True)
class PhaseCosts:
    """Measured per-phase serving costs, milliseconds."""

    prefill_ms_per_token: float = 0.08
    decode_step_ms: float = 2.0
    batch_overhead_ms: float = 4.0
    # disaggregated pools (ISSUE 20): per-row prefill→decode KV
    # transfer cost (serialize + POST /kv_import + verify + adopt) —
    # the serving_kv_handoff_ms histogram is its real-stack mirror
    handoff_ms: float = 1.5

    @classmethod
    def fit(cls, metricsz_texts, mean_prompt_tokens: float,
            mean_new_tokens: float, baseline_texts=None) -> "PhaseCosts":
        """Fit costs from real `/metricsz` scrapes (one text per replica;
        sums and counts aggregate across them) plus the trace's mean
        shape. TTFT is anchored at admission, so queue wait is
        subtracted before attributing the remainder to prefill; the
        decode region is mean latency minus mean TTFT spread over the
        remaining steps. The 80/20 prefill/overhead split is a
        convention — at calibration scale the two are not separable
        from means alone, and the twin only ever uses their sum plus
        the per-token slope.

        `baseline_texts` (scrapes taken BEFORE the measured run, same
        replica order) are subtracted so warmup traffic — above all the
        XLA compiles it pays for — does not pollute the steady-state
        costs."""
        if isinstance(metricsz_texts, str):
            metricsz_texts = [metricsz_texts]
        if isinstance(baseline_texts, str):
            baseline_texts = [baseline_texts]
        tt_sum = tt_n = lat_sum = lat_n = qw_sum = qw_n = 0.0
        for text in metricsz_texts:
            snap = parse_prometheus_text(text)
            tt_sum += snap.value("serving_ttft_ms_sum")
            tt_n += snap.value("serving_ttft_ms_count")
            lat_sum += snap.value("serving_request_seconds_sum")
            lat_n += snap.value("serving_request_seconds_count")
            qw_sum += snap.value("serving_queue_wait_seconds_sum")
            qw_n += snap.value("serving_queue_wait_seconds_count")
        for text in baseline_texts or ():
            snap = parse_prometheus_text(text)
            tt_sum -= snap.value("serving_ttft_ms_sum")
            tt_n -= snap.value("serving_ttft_ms_count")
            lat_sum -= snap.value("serving_request_seconds_sum")
            lat_n -= snap.value("serving_request_seconds_count")
            qw_sum -= snap.value("serving_queue_wait_seconds_sum")
            qw_n -= snap.value("serving_queue_wait_seconds_count")
        if not tt_n or not lat_n:
            raise ValueError(
                "cannot fit PhaseCosts: no serving_ttft_ms/"
                "serving_request_seconds samples in the scrapes"
            )
        ttft_ms = tt_sum / tt_n
        lat_ms = (lat_sum / lat_n) * 1e3
        qw_ms = (qw_sum / qw_n) * 1e3 if qw_n else 0.0
        prefill_ms = max(0.05, ttft_ms - qw_ms)
        decode_ms = max(0.0, lat_ms - ttft_ms)
        steps = max(1.0, mean_new_tokens - 1.0)
        return cls(
            prefill_ms_per_token=0.8 * prefill_ms / max(1.0, mean_prompt_tokens),
            decode_step_ms=max(0.01, decode_ms / steps),
            batch_overhead_ms=0.2 * prefill_ms,
        )


@dataclasses.dataclass(frozen=True)
class TwinConfig:
    """The slice of ServingConfig the twin models."""

    replicas: int = 2
    max_batch: int = 4
    max_queue: int = 64
    kv_pool_pages: Optional[int] = None
    kv_page_tokens: int = 8
    retry_on_shed: bool = True  # the router's sibling retry
    # ISSUE 17: each replica keeps a prefix DIRECTORY — the set of
    # cohort ids it has served. Admission prefers the replica already
    # holding a row's cohort (the router's prefix-affinity hint), and a
    # directory hit discounts the row's prefill to its unshared quarter
    # (cohorts share 3/4 of their prompt; traces.prompt_tokens). Page
    # accounting stays per-request — the twin models the LATENCY and
    # PLACEMENT effects of the cache, not its pool residency.
    prefix_cache: bool = False
    prefix_affinity: bool = True
    # ISSUE 19: per-tenant admission — tenant name → cap on FLEET-wide
    # outstanding rows (queued + in flight). An over-cap arrival sheds
    # `tenant_quota` before routing, exactly like the real servers'
    # TenantAdmission (whose caps are per replica — a twin modeling an
    # N-replica rig should multiply accordingly). None/missing = uncapped.
    tenants: Optional[dict] = None
    # ISSUE 20: disaggregated pools — (n_prefill, n_decode). When set
    # the replica list is prefill slots then decode slots and `replicas`
    # is ignored: fresh rows admit to the prefill pool (decode pool as
    # monolithic fallback when every prefill replica is down), a
    # prefill batch services ONLY its prefill region, then every row
    # pays `handoff_ms` to move its page set to the least-loaded decode
    # replica; a decode pool that cannot adopt (down, queue-full, or
    # page-starved) sends the row back to the prefill pool for local
    # monolithic decode — the real stack's kv_handoff fallback.
    pools: Optional[tuple] = None


class _Row:
    __slots__ = ("i", "arrive_t", "prompt_len", "max_new", "deadline",
                 "disconnect_after_ms", "pages", "attempts", "prefix_group",
                 "tenant", "decode_phase", "ttft_ms")

    def __init__(self, rec: TraceRequest, arrive_t: float, pages: int):
        # disaggregated handoff (ISSUE 20): True once the row's prefill
        # finished on a prefill replica (TTFT recorded then) — only the
        # decode region remains wherever it lands next
        self.decode_phase = False
        self.ttft_ms: Optional[float] = None
        self.i = rec.i
        self.tenant = rec.tenant or "default"
        self.arrive_t = arrive_t
        self.prompt_len = rec.prompt_len
        self.max_new = rec.max_new
        self.deadline = (
            arrive_t + rec.deadline_ms / 1e3
            if rec.deadline_ms is not None else None
        )
        self.disconnect_after_ms = rec.disconnect_after_ms
        self.pages = pages
        self.attempts = 0
        self.prefix_group = rec.prefix_group


class TrendTape:
    """Order-preserving, bounded per-request value tape feeding the
    history assertion predicates (`max_metric_trend`/`min_metric_floor`,
    ISSUE 18). When full, every other point is dropped and the sampling
    stride doubles — halves stay halves at million-request scale while
    memory stays O(cap)."""

    def __init__(self, cap: int = 4096):
        self.cap = max(8, int(cap))
        self.stride = 1
        self._i = 0
        self.points: list[float] = []

    def add(self, v: float) -> None:
        if self._i % self.stride == 0:
            self.points.append(float(v))
            if len(self.points) >= self.cap:
                self.points = self.points[::2]
                self.stride *= 2
        self._i += 1


class _Replica:
    __slots__ = ("up", "queue", "batch", "pages_used", "prefix_groups")

    def __init__(self):
        self.up = True
        self.queue: deque[_Row] = deque()
        self.batch: Optional[list[_Row]] = None
        self.pages_used = 0
        # the per-replica prefix directory (ISSUE 17): cohort ids whose
        # shared prefix this replica has prefilled and still holds
        self.prefix_groups: set = set()

    def depth(self) -> int:
        return len(self.queue) + (len(self.batch) if self.batch else 0)


class ServingTwin:
    """One twin run: `run(records)` consumes a (lazy) record stream and
    returns the aggregate report. Faults are dicts —
    `{"kind": "replica_down", "replica": r, "at_s": t, "duration_s": d}`
    — usually derived from the same seed as the scenario's real-stack
    FaultPlan so twin and rig replay the same story."""

    def __init__(self, cfg: TwinConfig, costs: PhaseCosts, *,
                 faults: Iterable[dict] = (), seed: int = 0):
        self.cfg = cfg
        self.costs = costs
        self.clock = SimClock()
        # disaggregated pools (ISSUE 20): prefill slots first, then
        # decode slots; n_prefill == 0 means monolithic replicas
        if cfg.pools is not None:
            self.n_prefill = max(1, int(cfg.pools[0]))
            n_replicas = self.n_prefill + max(1, int(cfg.pools[1]))
        else:
            self.n_prefill = 0
            n_replicas = cfg.replicas
        self.replicas = [_Replica() for _ in range(n_replicas)]
        self.handoffs = 0
        self.handoff_fallbacks = 0
        self._events: list[tuple[float, int, str, object]] = []
        self._seq = 0
        for f in faults:
            if f.get("kind") != "replica_down":
                raise ValueError(f"unknown twin fault kind: {f!r}")
            r = int(f["replica"]) % n_replicas
            t = float(f["at_s"])
            self._push(t, "down", r)
            self._push(t + float(f.get("duration_s", 1.0)), "up", r)
        # outcome ledger (aggregates + seeded latency reservoirs)
        self.counts = {
            "ok": 0, "shed": 0, "deadline_504": 0, "disconnected": 0,
            "error": 0,
        }
        self.shed_reasons: dict[str, int] = {}
        self._lat_res: list[float] = []
        self._ttft_res: list[float] = []
        self._lat_sum = 0.0
        self._lat_n = 0
        self._rng = random.Random(f"twin-reservoir:{seed}")
        self.offered = 0
        self.resolved = 0
        # prefix-directory ledger (ISSUE 17)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        # tenancy ledger (ISSUE 19): fleet-wide outstanding per tenant
        # plus the per-tenant outcome breakdown the assertions read
        self._tenant_out: dict[str, int] = {}
        self._tenant_stats: dict[str, dict] = {}
        # arrival-ordered value tapes for the history predicates
        # (ISSUE 18): same series names run_real builds off the ledger
        self.tapes = {
            "latency_ms": TrendTape(),
            "ttft_ms": TrendTape(),
            "ok": TrendTape(),
        }

    # ------------------------------------------------------------ events
    def _push(self, t: float, kind: str, data) -> None:
        self._seq += 1
        heapq.heappush(self._events, (t, self._seq, kind, data))

    # ---------------------------------------------------------- tenancy
    def _tstat(self, tenant: str) -> dict:
        return self._tenant_stats.setdefault(tenant, {
            "offered": 0, "ok": 0, "shed": 0, "error": 0,
            "shed_reasons": {}, "_lat": [],
        })

    def _tenant_shed(self, tenant: str, reason: str) -> None:
        st = self._tstat(tenant)
        st["shed"] += 1
        st["shed_reasons"][reason] = st["shed_reasons"].get(reason, 0) + 1

    def _tenant_done(self, tenant: str) -> None:
        if self._tenant_out.get(tenant):
            self._tenant_out[tenant] -= 1

    # ---------------------------------------------------------- routing
    def _role_up(self, prefill: bool) -> list[int]:
        """Live slot indices of one pool (pooled mode only)."""
        rng = (range(self.n_prefill) if prefill
               else range(self.n_prefill, len(self.replicas)))
        return [i for i in rng if self.replicas[i].up]

    def _route_order(self) -> list[int]:
        """JSQ candidate order. Pooled mode sends fresh rows to the
        prefill pool; a fully-dead prefill pool degrades to routing the
        decode pool monolithically (the router's role-aware reorder)."""
        if self.n_prefill:
            pool = self._role_up(True) or self._role_up(False)
        else:
            pool = [i for i, r in enumerate(self.replicas) if r.up]
        return sorted(pool, key=lambda i: self.replicas[i].depth())

    def _admit(self, rec: TraceRequest, now: float) -> None:
        self.offered += 1
        tenant = rec.tenant or "default"
        self._tstat(tenant)["offered"] += 1
        cap = (self.cfg.tenants or {}).get(tenant)
        if cap is not None and self._tenant_out.get(tenant, 0) >= cap:
            # over-cap arrival: shed against THIS tenant before routing,
            # the real stack's TenantAdmission.admit
            self.counts["shed"] += 1
            self.shed_reasons["tenant_quota"] = (
                self.shed_reasons.get("tenant_quota", 0) + 1
            )
            self._tenant_shed(tenant, "tenant_quota")
            self.tapes["ok"].add(0.0)
            self.resolved += 1
            return
        pages = 0
        if self.cfg.kv_pool_pages:
            pages = -(-(rec.prompt_len + rec.max_new) // self.cfg.kv_page_tokens)
        row = _Row(rec, now, pages)
        order = self._route_order()
        # prefix affinity (ISSUE 17): a row whose cohort some replica's
        # directory already holds goes there first — the twin models the
        # router's stickiness without the imbalance yield (at twin scale
        # JSQ keeps depths within one batch of each other anyway)
        if (
            self.cfg.prefix_cache
            and self.cfg.prefix_affinity
            and row.prefix_group is not None
        ):
            order.sort(
                key=lambda i: row.prefix_group
                not in self.replicas[i].prefix_groups
            )
        if not self.cfg.retry_on_shed:
            order = order[:1]
        reason = "unavailable"
        for i in order:
            rep = self.replicas[i]
            if rep.depth() >= self.cfg.max_queue:
                reason = "queue_full"
                continue
            if (
                self.cfg.kv_pool_pages
                and rep.pages_used + pages > self.cfg.kv_pool_pages
            ):
                reason = "kv_pages"
                continue
            rep.pages_used += pages
            rep.queue.append(row)
            self._tenant_out[tenant] = self._tenant_out.get(tenant, 0) + 1
            self._maybe_start(i, now)
            return
        self.counts["shed"] += 1
        self.shed_reasons[reason] = self.shed_reasons.get(reason, 0) + 1
        self._tenant_shed(tenant, reason)
        self.tapes["ok"].add(0.0)
        self.resolved += 1

    def _requeue(self, row: _Row, now: float) -> None:
        """Failover: a dying replica's row retries on a sibling, keeping
        its original arrival time (the client pays for the redo). The
        retry is a FULL replay — a decode-phase row's adopted pages died
        with the replica, so the new owner prefills from scratch, just
        like the router re-posting the whole body."""
        row.attempts += 1
        row.decode_phase = False
        row.ttft_ms = None
        order = self._route_order()
        for i in order:
            rep = self.replicas[i]
            if rep.depth() >= self.cfg.max_queue:
                continue
            if (
                self.cfg.kv_pool_pages
                and rep.pages_used + row.pages > self.cfg.kv_pool_pages
            ):
                continue
            rep.pages_used += row.pages
            rep.queue.append(row)
            self._maybe_start(i, now)
            return
        self.counts["error"] += 1
        self._tstat(row.tenant)["error"] += 1
        self._tenant_done(row.tenant)
        self.resolved += 1

    # ---------------------------------------------------------- service
    def _maybe_start(self, i: int, now: float) -> None:
        rep = self.replicas[i]
        if not rep.up or rep.batch is not None or not rep.queue:
            return
        c = self.costs
        # deadline purge at dispatch: 504 without spending step budget
        while rep.queue:
            head = rep.queue[0]
            if head.deadline is not None and head.deadline <= now:
                rep.queue.popleft()
                rep.pages_used -= head.pages
                self.counts["deadline_504"] += 1
                self._tenant_shed(head.tenant, "deadline")
                self._tenant_done(head.tenant)
                self.resolved += 1
                continue
            break
        if not rep.queue:
            return
        # phase-uniform batches (ISSUE 20): decode-phase continuations
        # (adopted handoffs, local fallbacks) never share a batch with
        # fresh prefills — the real step engine separates the phases too
        head_phase = rep.queue[0].decode_phase
        batch = []
        while (
            rep.queue
            and len(batch) < self.cfg.max_batch
            and rep.queue[0].decode_phase == head_phase
        ):
            batch.append(rep.queue.popleft())
        steps = 0
        for row in batch:
            eff = row.max_new
            if row.disconnect_after_ms is not None:
                # a disconnected client's row is cancelled promptly
                # (satellite 1): it decodes only until the disconnect
                eff = min(
                    eff,
                    1 + math.ceil(row.disconnect_after_ms / c.decode_step_ms),
                )
            steps = max(steps, eff - 1)
        if head_phase:
            # prompt KV already resident (adopted or locally warm):
            # only the decode region runs here
            service_s = (c.batch_overhead_ms + steps * c.decode_step_ms) / 1e3
            rep.batch = batch
            self._push(now + service_s, "finish", (i, now))
            return
        prefill_tokens = 0
        for row in batch:
            toks = row.prompt_len
            if self.cfg.prefix_cache and row.prefix_group is not None:
                self.prefix_lookups += 1
                if row.prefix_group in rep.prefix_groups:
                    # directory hit: only the unshared quarter prefills
                    # (cohorts share 3/4 of their prompt bytes)
                    self.prefix_hits += 1
                    toks = row.prompt_len - (3 * row.prompt_len) // 4
                else:
                    rep.prefix_groups.add(row.prefix_group)
            prefill_tokens = max(prefill_tokens, toks)
        prefill_ms = (
            c.batch_overhead_ms + c.prefill_ms_per_token * prefill_tokens
        )
        if self.n_prefill and i < self.n_prefill and self._role_up(False):
            # two-pool path (ISSUE 20): this batch runs ONLY its prefill
            # region; each finished row's page set then ships to the
            # decode pool (one handoff_ms per row, the "export" event)
            rep.batch = batch
            self._push(now + prefill_ms / 1e3, "export",
                       (i, now + prefill_ms / 1e3))
            return
        service_s = (prefill_ms + steps * c.decode_step_ms) / 1e3
        rep.batch = batch
        self._push(now + service_s, "finish", (i, now + prefill_ms / 1e3))

    def _export(self, i: int, first_token_t: float, now: float) -> None:
        """A prefill replica's batch finished its prefill region: emit
        the first token (TTFT pins here, like the real `_emit`), release
        the exporter's pages, and ship each row's page set to the decode
        pool after `handoff_ms` of transfer time."""
        rep = self.replicas[i]
        batch, rep.batch = rep.batch, None
        for row in batch or ():
            rep.pages_used -= row.pages
            row.ttft_ms = (first_token_t - row.arrive_t) * 1e3
            row.decode_phase = True
            self.handoffs += 1
            self._push(now + self.costs.handoff_ms / 1e3, "adopt", row)
        self._maybe_start(i, now)

    def _adopt(self, row: "_Row", now: float) -> None:
        """A shipped page set lands: the least-loaded decode replica
        that can hold it adopts; when none can (down, queue-full, or
        page-starved — the import shed), the row falls back to the
        prefill pool for local monolithic decode. Only a fully-dead
        fleet errors the row."""
        decode = self._role_up(False)
        prefill = self._role_up(True)
        for candidates, fallback in ((decode, False), (prefill, True)):
            order = sorted(candidates,
                           key=lambda i: self.replicas[i].depth())
            for i in order:
                rep = self.replicas[i]
                if rep.depth() >= self.cfg.max_queue:
                    continue
                if (
                    self.cfg.kv_pool_pages
                    and rep.pages_used + row.pages > self.cfg.kv_pool_pages
                ):
                    continue
                if fallback:
                    self.handoff_fallbacks += 1
                rep.pages_used += row.pages
                rep.queue.append(row)
                self._maybe_start(i, now)
                return
        self.counts["error"] += 1
        self._tstat(row.tenant)["error"] += 1
        self._tenant_done(row.tenant)
        self.resolved += 1

    def _finish(self, i: int, first_token_t: float, now: float) -> None:
        rep = self.replicas[i]
        batch, rep.batch = rep.batch, None
        for row in batch or ():
            rep.pages_used -= row.pages
            ttft_ms = (
                row.ttft_ms if row.ttft_ms is not None
                else (first_token_t - row.arrive_t) * 1e3
            )
            if row.disconnect_after_ms is not None:
                end = first_token_t + row.disconnect_after_ms / 1e3
                self.counts["disconnected"] += 1
                self._observe(min(end, now) - row.arrive_t, ttft_ms)
                self._tstat(row.tenant)["ok"] += 1
            else:
                self.counts["ok"] += 1
                self._observe(now - row.arrive_t, ttft_ms)
                st = self._tstat(row.tenant)
                st["ok"] += 1
                if len(st["_lat"]) < _RESERVOIR:
                    st["_lat"].append((now - row.arrive_t) * 1e3)
            self._tenant_done(row.tenant)
            self.resolved += 1
        self._maybe_start(i, now)

    def _observe(self, latency_s: float, ttft_ms: float) -> None:
        lat_ms = latency_s * 1e3
        self.tapes["latency_ms"].add(lat_ms)
        self.tapes["ttft_ms"].add(ttft_ms)
        self.tapes["ok"].add(1.0)
        self._lat_sum += lat_ms
        self._lat_n += 1
        for res, v in ((self._lat_res, lat_ms), (self._ttft_res, ttft_ms)):
            if len(res) < _RESERVOIR:
                res.append(v)
            else:
                j = self._rng.randrange(self._lat_n)
                if j < _RESERVOIR:
                    res[j] = v

    # ------------------------------------------------------------- chaos
    def _down(self, i: int, now: float) -> None:
        rep = self.replicas[i]
        rep.up = False
        # the process died: its pages die with it, its rows fail over
        orphans = list(rep.batch or []) + list(rep.queue)
        rep.batch = None
        rep.queue.clear()
        rep.pages_used = 0
        # warm KV died with the process; the directory empties with it
        rep.prefix_groups.clear()
        for row in orphans:
            self._requeue(row, now)

    def _up(self, i: int, now: float) -> None:
        # the monitor restarted it: empty queue, empty pool
        self.replicas[i].up = True
        self._maybe_start(i, now)

    # -------------------------------------------------------------- run
    def run(self, records: Iterable[TraceRequest]) -> dict:
        arrivals = iter(records)
        nxt = next(arrivals, None)
        while nxt is not None or self._events:
            if nxt is not None and (
                not self._events or nxt.at <= self._events[0][0]
            ):
                now = self.clock.advance_to(max(self.clock.time(), nxt.at))
                self._admit(nxt, now)
                nxt = next(arrivals, None)
                continue
            t, _, kind, data = heapq.heappop(self._events)
            now = self.clock.advance_to(max(self.clock.time(), t))
            if kind == "finish":
                i, first_t = data
                self._finish(i, first_t, now)
            elif kind == "export":
                i, first_t = data
                self._export(i, first_t, now)
            elif kind == "adopt":
                self._adopt(data, now)
            elif kind == "down":
                self._down(data, now)
            elif kind == "up":
                self._up(data, now)
        return self.report()

    def report(self) -> dict:
        hung = self.offered - self.resolved
        leaked = sum(r.pages_used for r in self.replicas)
        lat = sorted(self._lat_res)
        ttft = sorted(self._ttft_res)
        shed = self.counts["shed"]
        return {
            "mode": "twin",
            "offered": self.offered,
            **self.counts,
            "shed_reasons": dict(self.shed_reasons),
            "shed_rate": round(shed / self.offered, 4) if self.offered else 0.0,
            "hung": hung,
            "kv_pages_leaked": leaked,
            "latency_ms": {
                "p50": quantile(lat, 0.5),
                "p99": quantile(lat, 0.99),
                "mean": (self._lat_sum / self._lat_n) if self._lat_n else None,
            },
            "ttft_ms": {
                "p50": quantile(ttft, 0.5),
                "p99": quantile(ttft, 0.99),
            },
            "handoff": {
                "handoffs": self.handoffs,
                "fallbacks": self.handoff_fallbacks,
            },
            "prefix": {
                "lookups": self.prefix_lookups,
                "hits": self.prefix_hits,
                "hit_rate": (
                    round(self.prefix_hits / self.prefix_lookups, 4)
                    if self.prefix_lookups else None
                ),
            },
            "by_tenant": self._by_tenant(),
            "sim_duration_s": round(self.clock.time(), 3),
        }

    def _by_tenant(self) -> dict:
        """The same per-tenant breakdown ReplayReport.summary builds —
        empty unless the trace actually named tenants."""
        if not (set(self._tenant_stats) - {"default"}):
            return {}
        out = {}
        for t, st in sorted(self._tenant_stats.items()):
            lat = sorted(st["_lat"])
            out[t] = {
                "offered": st["offered"], "ok": st["ok"],
                "shed": st["shed"], "error": st["error"],
                "shed_reasons": dict(st["shed_reasons"]),
                "latency_ms": {
                    "p50": quantile(lat, 0.5),
                    "p99": quantile(lat, 0.99),
                },
            }
        return out
