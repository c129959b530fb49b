"""Model serving: load a finished run's checkpoint, serve generation.

The reference's `service` run kind serves user containers (dashboards,
notebooks); this module gives the native LM family its inference surface —
a checkpointed `transformer_lm` run becomes an HTTP endpoint in one
command:

    polyaxon serve --uid <run> --port 8601
    curl -X POST localhost:8601/generate -d '{"tokens": [[1,2,3]], "maxNewTokens": 16}'

Endpoints:
  GET  /healthz           → {"status": "ok", "model": ..., "step": N}
  GET  /readyz            → 200 {"ready": true} while accepting; 503 while
                             draining or when --expected-devices detects a
                             degraded slice (runtime/health.check_slice)
  GET  /statsz            → {"compile_count": N, "requests": N,
                             "batches": N, "mean_batch_occupancy": x,
                             "latency_ms": {p50/p95/p99}, "shed": N,
                             "deadline_exceeded": N, "breaker": "closed",
                             "queue_depth": N, ...}
  GET  /metricsz          → Prometheus text format, rendered from the
                             same telemetry registry as /statsz
  POST /generate          → {"tokens": [[...]]}
     body: {"tokens": [[int]], "maxNewTokens": int, "temperature": float,
            "topK": int?, "eosId": int?, "seed": int?, "deadlineMs": float?,
            "numBeams": int? (beam search when > 1), "lengthPenalty": float?}
     errors: 400 validation; 503 + Retry-After shed (queue full, breaker
     open, expired at admission, KV page pool exhausted, draining — never
     queued, retry later); 504 deadline exceeded while queued (dropped
     before dispatch).
  POST /generate?stream=1 → Server-Sent Events (`data: <json>` frames):
     {"row": i, "tokens": [...]} per decoded chunk (generated tokens only;
     prompt + concatenated chunks == the non-streamed row), then
     {"row": i, "done": true} per row, then {"done": true}. Requires the
     paged KV pool (serving.kvPoolPages) for incremental delivery;
     otherwise each row arrives as one terminal chunk.

Design — the serving fast path (serving/batching.py):

  * Shape bucketing: prompts are LEFT-padded up to a geometric ladder of
    widths and `maxNewTokens` rounds up the same way, so rows of different
    true lengths share ONE compiled decode program (generate() masks pad
    out of attention and offsets rotary positions per row). Compile count
    is O(#buckets), not O(#distinct request shapes).
  * Continuous batching: HTTP handler threads are producers only; a single
    decode worker coalesces same-signature requests (per-row seed is a [B]
    runtime argument) into one batched dispatch of up to `max_batch` rows,
    waiting at most `max_wait_ms`, and scatters rows back to the waiting
    handlers. jax tracing/execution is single-threaded by construction.

`ServingConfig(batching=False)` restores the legacy per-request path (one
exact-shape jitted program per signature, LRU of 32) — beam-search
requests always use it. Serving is read-only — params are restored once
at startup.
"""

from __future__ import annotations

import dataclasses
import contextlib
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


class _Httpd(ThreadingHTTPServer):
    # socketserver's default accept backlog is 5 — an overload burst then
    # gets TCP RSTs before the shed logic ever sees it. A server whose
    # whole job under pressure is answering 503 fast must accept the
    # connection to say so.
    request_queue_size = 128
from typing import Optional

from ..chaos.injector import inject
from ..store.local import RunStore
from ..telemetry import (
    DEFAULT_SERVING_RULES,
    FlightRecorder,
    HistorySampler,
    HistoryStore,
    MetricsRegistry,
    RegressionSentinel,
    RequestTrace,
    SLOEngine,
    SpanTracer,
    TraceRing,
    build_objectives,
    build_rules,
    compiles,
    new_trace_id,
    now as _now,
    queryz_payload,
)
from .batching import (
    CircuitBreaker,
    DeadlineExceededError,
    DecodeCoalescer,
    GroupKey,
    PendingRequest,
    ServerClosingError,
    ServingConfig,
    ServingError,
    ShedError,
    batch_bucket,
    choose_buckets,
)
from .kv import KVCacheManager


#: phases of one scheduler step, in order (`/statsz` `chunked.phase_s`):
#: the span's name and what the phase's seconds are spent on
_STEP_PHASES = {
    "intake": ("sched.intake", "draining the queue, deadline sweeps, "
               "admission and composing the step"),
    "prepare": ("step.prepare", "assembling a program's host arguments "
                "(numpy, KV pages and tables)"),
    "dispatch": ("step.dispatch", "the server lock, host-to-device copies "
                 "and the program call (tracing and compiling it when it "
                 "is new)"),
    "fetch": ("step.fetch", "the blocking read of the program's result"),
    "emit": ("step.emit", "frames to clients, finished rows and the prefix "
             "cache's harvest"),
}


def _trace_status(error: Optional[BaseException]) -> str:
    """Trace status string for the tail sampler: everything that is not
    a clean completion is retained preferentially."""
    if error is None:
        return "ok"
    if isinstance(error, ShedError):
        return f"shed:{error.reason}"
    if isinstance(error, DeadlineExceededError):
        return "deadline_exceeded"
    if isinstance(error, ServingError):
        return "invalid_request"
    if isinstance(error, TimeoutError):
        return "timeout"
    return "error"


def _error_reason(error: BaseException) -> str:
    """The structured `reason` field every error body carries (satellite:
    consistent across all shed reasons AND the 400/500/504 classes)."""
    if isinstance(error, ShedError):
        return error.reason
    if isinstance(error, DeadlineExceededError):
        return "deadline_exceeded"
    if isinstance(error, ServingError):
        return "invalid_request"
    if isinstance(error, TimeoutError):
        return "timeout"
    return "internal"


class _HandoffPrefillDone(Exception):
    """Sentinel resolving a prefill-role row (ISSUE 20): the first token
    is out and the finished page set is exported, but the transfer has
    NOT run — the HTTP handler thread must ship it (network I/O never
    rides the decode worker). Callers convert this into either a
    retryable failover (shipped) or a local monolithic re-run (not)."""

    def __init__(self, first_token: int):
        super().__init__("prefill complete: KV handoff pending")
        self.first_token = int(first_token)


def _restore_params_subtree(ckpt_dir: str, abstract_params):
    """Read ONLY the params subtree of a saved TrainState (Orbax partial
    restore) into the shardings carried by `abstract_params`.

    Uses a fresh read-only CheckpointManager rather than the runtime's
    per-directory cache (runtime/checkpoint.py): the cached manager's
    handler registry is pinned to Standard save/restore by training, and a
    serving process must not pin retention options for a trainer that may
    later resume in-process."""
    import orbax.checkpoint as ocp

    mgr = ocp.CheckpointManager(ckpt_dir)
    try:
        step = mgr.latest_step()
        if step is None:
            raise ServingError(f"no restorable checkpoint in {ckpt_dir}")
        # explicit restore args: arrays land on THIS topology's shardings
        # (serving mesh), not the sharding recorded at save time —
        # train-on-8-hosts/serve-on-1 must work
        restore_args = {
            "params": ocp.checkpoint_utils.construct_restore_args(
                abstract_params
            )
        }
        args = ocp.args.PyTreeRestore(
            {"params": abstract_params},
            restore_args=restore_args,
            partial_restore=True,
        )
        out = mgr.restore(step, args=args)
        return out["params"], step
    finally:
        mgr.close()


class ModelServer:
    def __init__(
        self,
        module,
        params,
        *,
        model_name: str = "?",
        step: int = 0,
        config: Optional[ServingConfig] = None,
        registry: Optional[MetricsRegistry] = None,
        expected_devices: Optional[int] = None,
        slos: Optional[list] = None,
        debug_dir: Optional[str] = None,
        slo_profile_s: float = 0.0,
        sharding_rules: tuple = (),
        mesh=None,
        history: Optional[dict] = None,
        regression_rules: Optional[list] = None,
        event_sink=None,
    ):
        self.config = config or ServingConfig()
        # the run-spec path validates these combos in V1ServingSpec, but
        # CLI overrides and direct construction land here unchecked — and
        # a silently ignored kv_quant means an operator who asked for a
        # halved pool is capacity-planning on memory they don't have
        if (
            self.config.kv_quant not in (None, "none")
            and not self.config.kv_pool_pages
        ):
            raise ValueError(
                "kv_quant requires the paged KV pool (set kv_pool_pages)"
            )
        if (
            self.config.adaptive_draft or self.config.draft_model is not None
        ) and not self.config.speculate:
            raise ValueError(
                "draft_model/adaptive_draft require speculate=True"
            )
        if (self.config.spill_ram_bytes or self.config.spill_dir) and not (
            self.config.kv_pool_pages and self.config.prefix_cache
        ):
            raise ValueError(
                "spill_ram_bytes/spill_dir require the paged KV pool with "
                "the prefix cache (set kv_pool_pages, keep prefix_cache on)"
            )
        # disaggregated pools (ISSUE 20): the handoff unit is the
        # page-aligned prefix-cache chain a chunked prefill leaves
        # behind, so a prefill-role replica needs all three ingredients
        if self.config.role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', "
                f"got {self.config.role!r}"
            )
        if self.config.role == "prefill" and not (
            self.config.chunked_prefill
            and self.config.kv_pool_pages
            and self.config.prefix_cache
        ):
            raise ValueError(
                "role='prefill' requires chunked_prefill + kv_pool_pages "
                "+ prefix_cache (the handoff ships the page-aligned "
                "prefix chain chunked prefill leaves in the cache)"
            )
        # int8 quantize-on-load (ISSUE 8): rebuild the module with the
        # Int8Dense projection path and transform the restored fp params
        # BEFORE anything captures them — the dense projection kernels
        # are never resident past this constructor
        self._quant_bytes_saved = 0
        if self.config.quantize:
            from ..models.quant import quantize_module

            module, params, self._quant_bytes_saved = quantize_module(
                module, params
            )
        # multi-tenant adapter multiplexing (ISSUE 19): stack the restored
        # checkpoint's LoRA params to [slots, ...] — slot 0 keeps the
        # checkpoint's own adapter, slots 1..N start zero for the registry
        # to hot-swap. Runs AFTER quantize (int8 base + fp adapters
        # compose) and BEFORE the mesh device_put (the slot axis must land
        # replicated: the per-row gather must not become a collective).
        self._tenancy = None
        self._adapter_registry = None
        self._adapter_spill = None
        self._adapter_sources = dict(self.config.adapters or ())
        self._adapter_slots_active = False
        self._adapter_n_hot = 0
        sharding_rules = tuple(sharding_rules or ())
        if self._adapter_sources or self.config.adapter_slots:
            if getattr(module.cfg, "lora_rank", 0) <= 0:
                raise ValueError(
                    "serving adapters require a LoRA model (lora_rank > 0): "
                    "this checkpoint has no adapter params to multiplex"
                )
            n_hot = int(self.config.adapter_slots) or len(self._adapter_sources)
            if n_hot < 1:
                raise ValueError(
                    "adapter_slots must be >= 1 when adapters are configured"
                )
            from .adapters import stack_adapter_params

            module, params = stack_adapter_params(
                module, params, slots=n_hot + 1
            )
            self._adapter_slots_active = True
            self._adapter_n_hot = n_hot
            # mirror build_transformer's rule rewrite: prepend the slot
            # axis (replicated) to every lora_* sharding rule, since
            # _spec_for applies axes positionally from dim 0
            sharding_rules = tuple(
                (pat, (None, *axes)) if "lora_" in pat else (pat, axes)
                for pat, axes in sharding_rules
            )
        if self.config.tenants or self._adapter_sources:
            from .tenancy import TenantAdmission, TenantSpec

            self._tenancy = TenantAdmission(self.config.tenants)
            for pairs in self.config.tenants or ():
                spec = TenantSpec.from_pairs(pairs)
                if spec.adapter and spec.adapter not in self._adapter_sources:
                    raise ValueError(
                        f"tenant {spec.name!r} binds adapter "
                        f"{spec.adapter!r}, which is not configured"
                    )
        # tensor-parallel decode (ISSUE 10): a named 2-D `batch`×`model`
        # mesh. from_run passes the mesh it restored onto (params already
        # land sharded); direct construction builds one from
        # config.mesh_axes and shards the given params here. device_put
        # onto an already-matching sharding is a no-op, so both paths
        # share this block.
        self._sharding_rules = tuple(sharding_rules or ())
        self._mesh = mesh
        if self._mesh is None and self.config.mesh_axes:
            from ..parallel.mesh import decode_mesh

            self._mesh = decode_mesh(dict(self.config.mesh_axes))
        if self._mesh is not None:
            import jax

            from ..parallel.ring import set_current_mesh
            from ..parallel.sharding import param_shardings

            set_current_mesh(self._mesh)
            params = jax.device_put(
                params,
                param_shardings(params, self._sharding_rules, self._mesh),
            )
        self.module = module
        self.params = params
        # the devices this server decodes on (no mesh = device 0, the
        # single-chip path) — /statsz and the startup line name them
        import jax as _jax

        from ..utils.jax_platform import device_report

        self._devices = (
            list(self._mesh.devices.flat)
            if self._mesh is not None
            else _jax.devices()[:1]
        )
        self._device_report = device_report(self._devices, module.cfg)
        # adaptive speculation (ISSUE 15): an optional real draft model
        # (weights derived by layer truncation of the SERVED tree — after
        # quantize/mesh, so the draft rides the same int8/sharded params)
        # and an accept-rate controller that steers the per-group draft
        # width K, down to disabling speculation entirely
        self._draft_module = None
        self._draft_params = None
        self._draft_derived = False
        self._draft_propose_fns: dict = {}  # shared across groups/drafters
        if self.config.draft_model is not None:
            from ..models.draft import build_draft

            (
                self._draft_module,
                self._draft_params,
                self._draft_derived,
            ) = build_draft(
                module, params, overrides=dict(self.config.draft_model)
            )
        self._spec_controller = None
        if self.config.adaptive_draft and self.config.speculate:
            from .adaptive import AdaptiveSpecController

            k0 = max(1, int(self.config.draft_tokens))
            self._spec_controller = AdaptiveSpecController(
                k_init=k0, k_min=1, k_max=max(k0, 8)
            )
        self.model_name = model_name
        self.step = step
        # readiness: /readyz reports 503 while draining, and — when
        # `expected_devices` is set — when the visible device count
        # regresses below it (degraded slice; runtime/health.check_slice)
        self.expected_devices = expected_devices
        self._draining = False
        self._health_cache: Optional[tuple[float, bool, str]] = None
        # ONE metrics pipeline: /statsz and /metricsz both render from
        # this registry, so the two surfaces cannot drift (pinned by
        # tests/test_telemetry.py). A server defaults to its own registry
        # — one server per process in production, isolated in tests.
        self.telemetry = registry or MetricsRegistry()
        self._m_requests = self.telemetry.counter(
            "serving.requests", help="Generation rows served"
        )
        self._m_batches = self.telemetry.counter(
            "serving.batches", help="Decode batches dispatched"
        )
        self._m_cache_hits = self.telemetry.counter(
            "serving.compile_cache_hits", help="Compiled-program cache hits"
        )
        self._m_cache_misses = self.telemetry.counter(
            "serving.compile_cache_misses",
            help="Compiled-program cache misses (programs built)",
        )
        self._m_latency = self.telemetry.histogram(
            "serving.request_seconds",
            help="End-to-end request latency, seconds",
        )
        self._m_queue_wait = self.telemetry.histogram(
            "serving.queue_wait_seconds",
            help="Submit-to-dispatch wait in the coalescer queue, seconds",
        )
        self._m_occupancy = self.telemetry.histogram(
            "serving.batch_occupancy",
            buckets=(1, 2, 4, 8, 16, 32, 64),
            help="Rows per dispatched decode batch",
        )
        # resilience series — registered (and rendered) from startup so a
        # scrape can alert on them before the first overload event
        self._m_shed = self.telemetry.counter(
            "serving.shed",
            help="Requests shed at admission "
            "(queue full / breaker open / expired / draining)",
        )
        self._m_deadline = self.telemetry.counter(
            "serving.deadline_exceeded",
            help="Requests that missed their deadline (shed at admission "
            "or dropped before dispatch)",
        )
        self._m_worker_restarts = self.telemetry.counter(
            "serving.worker_restarts",
            help="Decode worker watchdog restarts",
        )
        self._m_breaker = self.telemetry.gauge(
            "serving.breaker_state",
            help="Decode circuit breaker: 0 closed, 1 open, 2 half-open",
        )
        self._m_breaker.set(0)
        self._m_ready = self.telemetry.gauge(
            "serving.ready",
            help="Readiness (/readyz): 1 accepting, 0 draining/degraded",
        )
        self._m_ready.set(0)
        # router balancing signal (ISSUE 10): unfinished requests admitted
        # to the coalescer, refreshed at scrape time — join-shortest-queue
        # reads this off /metricsz
        self._m_queue_depth = self.telemetry.gauge(
            "serving.queue_depth",
            help="Unfinished requests admitted to the coalescer queue",
        )
        self._m_mesh_devices = self.telemetry.gauge(
            "serving.mesh_devices",
            help="Devices in this replica's decode mesh (1 = single-chip)",
        )
        self._m_mesh_model = self.telemetry.gauge(
            "serving.mesh_model",
            help="Tensor-parallel (`model` axis) degree of the decode mesh",
        )
        self._m_mesh_devices.set(self._mesh.devices.size if self._mesh is not None else 1)
        self._m_mesh_model.set(
            self._mesh.shape.get("model", 1) if self._mesh is not None else 1
        )
        # paged KV + streaming series (ISSUE 6) — registered from startup
        # (zeros when the pool is off) so a scraper finds them either way
        self._m_kv_total = self.telemetry.gauge(
            "serving.kv_pages_total",
            help="KV page pool capacity (0 = dense per-group caches)",
        )
        self._m_kv_used = self.telemetry.gauge(
            "serving.kv_pages_used",
            help="KV pages currently allocated (incl. scratch + prefix cache)",
        )
        self._m_kv_prefix_held = self.telemetry.gauge(
            "serving.kv_pages_prefix_held",
            help="Distinct KV pages held only on behalf of the prefix "
            "cache — warm state, not a leak; drain accounting subtracts "
            "this from kv_pages_used",
        )
        self._m_prefix_hits = self.telemetry.counter(
            "serving.prefix_cache_hits",
            help="Requests whose prompt prefix was served from cached KV",
        )
        self._m_prefix_misses = self.telemetry.counter(
            "serving.prefix_cache_misses",
            help="Requests that found no cached KV prefix",
        )
        # tiered prefix spill series (ISSUE 17) — registered from startup
        # (zeros when spill is off) so a scraper finds them either way
        self._m_spill_bytes = self.telemetry.counter(
            "serving.kv_spill_bytes",
            help="Bytes of evicted KV prefixes accepted into the spill "
            "tiers (host RAM / disk) instead of being discarded",
        )
        self._m_spill_restores = self.telemetry.counter(
            "serving.kv_spill_restores",
            help="Spilled prefixes restored into the page pool on a hit "
            "(each one is a prefill the cluster did not repeat)",
        )
        self._m_spill_quarantined = self.telemetry.counter(
            "serving.kv_spill_quarantined",
            help="Corrupt spill segments quarantined to <seg>.corrupt and "
            "served as clean misses",
        )
        # live KV handoff series (ISSUE 20) — registered from startup
        # (zeros when pools are off) so a scraper finds them either way
        self._m_handoff_ms = self.telemetry.histogram(
            "serving.kv_handoff_ms",
            buckets=(1, 2, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000),
            help="Prefill→decode KV handoff wall time, milliseconds "
            "(payload capture through import acknowledgement)",
        )
        self._m_handoff_exports = self.telemetry.counter(
            "serving.kv_handoff_exports",
            help="Page sets this replica exported to a decode replica "
            "over POST /kv_import (acknowledged adoptions)",
        )
        self._m_handoff_imports = self.telemetry.counter(
            "serving.kv_handoff_imports",
            help="Page sets this replica adopted from a prefill replica "
            "via POST /kv_import",
        )
        self._m_handoff_rejected = self.telemetry.counter(
            "serving.kv_handoff_rejected",
            help="Imports refused: stale lease epoch (409), CRC/hash "
            "verification failure (400), or headroom shed (503)",
        )
        self._m_handoff_fallbacks = self.telemetry.counter(
            "serving.kv_handoff_fallbacks",
            help="Prefill-role requests that completed by LOCAL "
            "monolithic decode because no decode replica could adopt "
            "(no target routable, import shed, retries exhausted)",
        )
        self._m_handoff_inflight = self.telemetry.gauge(
            "serving.kv_handoff_inflight",
            help="Handoff exports in flight (captured, not yet "
            "acknowledged or fallen back) — drain waits on zero",
        )
        self._m_kv_handoff_held = self.telemetry.gauge(
            "serving.kv_pages_handoff_held",
            help="KV pages held by adopted-but-not-yet-flushed handoff "
            "imports — in-transit state, not a leak; mirrors "
            "kv_pages_prefix_held in drain accounting",
        )
        # fast-decode series (ISSUE 8) — registered from startup (zeros
        # when speculation/quant are off) so a scraper finds them either
        # way
        self._m_spec_proposed = self.telemetry.counter(
            "serving.spec_proposed",
            help="Draft tokens proposed to speculative verify windows",
        )
        self._m_spec_accepted = self.telemetry.counter(
            "serving.spec_accepted",
            help="Draft tokens accepted (committed without their own "
            "forward pass); accept rate = accepted / proposed",
        )
        self._m_spec_rollback = self.telemetry.counter(
            "serving.spec_rollback",
            help="Draft tokens rejected and rolled back (their KV slots "
            "are masked dead and rewritten by the next window)",
        )
        self._m_spec_truncated = self.telemetry.counter(
            "serving.spec_truncated",
            help="Accepted drafts the remaining-budget clamp kept out of "
            "the commit (judged accepted, not committed) — the gap "
            "between the raw and corrected accept rates",
        )
        self._m_spec_effective_k = self.telemetry.gauge(
            "serving.spec_effective_k",
            help="Current speculative draft width K (0 = speculation "
            "auto-disabled or off; static draft_tokens without "
            "adaptiveDraft)",
        )
        self._m_spec_effective_k.set(
            int(self.config.draft_tokens) if self.config.speculate else 0
        )
        self._m_quant_saved = self.telemetry.gauge(
            "serving.quant_bytes_saved",
            help="HBM bytes saved by int8 weight-only quantization "
            "(0 = full-precision kernels)",
        )
        self._m_quant_saved.set(self._quant_bytes_saved)
        self._m_ttft = self.telemetry.histogram(
            "serving.ttft_ms",
            buckets=(1, 2, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000),
            help="Time to first token, milliseconds (admission → first "
            "sampled token; whole-decode on the dense path)",
        )
        # chunked prefill + step scheduling series (ISSUE 14) — registered
        # from startup (zeros when chunking is off) so a scraper finds
        # them either way
        self._m_prefill_chunks = self.telemetry.counter(
            "serving.prefill_chunks",
            help="Prefill slices executed by the step scheduler "
            "(chunked prefill)",
        )
        self._m_step_tokens = self.telemetry.histogram(
            "serving.step_tokens",
            buckets=(8, 16, 32, 64, 128, 256, 512, 1024),
            help="Tokens touched per device step (all decode rows plus at "
            "most one prefill slice; bounded by maxStepTokens)",
        )
        self._m_prefill_queue = self.telemetry.gauge(
            "serving.prefill_queue_depth",
            help="Rows admitted but not yet past prefill (pending + "
            "mid-prefill), refreshed at scrape time",
        )
        # where a scheduler step's wall time goes (ISSUE 27): one span a
        # phase in the ring below (`polyaxon.sched.intake`,
        # `polyaxon.step.<phase>` in a profiler capture) and cumulative
        # seconds beside the step count, so /statsz `chunked.phase_s`
        # over `chunked.steps` is the host's own time per step
        self.spans = SpanTracer(prefix="polyaxon.", capacity=2048)
        self._m_phase = {
            phase: self.telemetry.counter(
                f"serving.step_phase_seconds.{phase}",
                help=f"Cumulative seconds of scheduler steps spent in {what}",
            )
            for phase, (_, what) in _STEP_PHASES.items()
        }
        # XLA programs as JAX counts them, process-wide (compile_count
        # below counts misses of this server's own LRU of callables)
        compiles.install()
        # per-request tracing (ISSUE 9): HTTP-level availability counters
        # (request attempts and 5xx-class failures — the SLO engine's
        # availability numerator/denominator), the tail-sampling trace
        # ring behind /tracez, and a per-process decode-group id sequence
        # so the B member rows of one coalesced batch share a group span
        self._m_http = self.telemetry.counter(
            "serving.http_requests",
            help="HTTP /generate attempts (any outcome)",
        )
        self._m_http_err = self.telemetry.counter(
            "serving.http_errors",
            help="HTTP /generate 5xx-class failures (500/503/504)",
        )
        # mid-stream client disconnects (ISSUE 16): streamed requests whose
        # socket broke before the stream finished — their rows are
        # cancelled and their KV pages released promptly
        self._m_client_disconnects = self.telemetry.counter(
            "serving.client_disconnects",
            help="Streamed /generate requests whose client vanished "
            "mid-stream (broken pipe); rows cancelled, pages released",
        )
        # multi-tenant observability (ISSUE 19): adapter-swap cost +
        # per-tenant queue-wait, registered from startup so the
        # regressionRules (tenant-queue-wait-trend, adapter-thrash-surge)
        # always have their series
        self._m_tenant_queue_wait = self.telemetry.histogram(
            "serving.tenant_queue_wait_seconds",
            help="Submit-to-dispatch wait for rows of NAMED tenants, "
            "seconds (the tenant-fairness signal; per-tenant splits in "
            "serving.queue_wait_by_tenant.*)",
        )
        self._m_adapter_load = self.telemetry.histogram(
            "serving.adapter_load_ms",
            buckets=(1, 5, 10, 25, 50, 100, 250, 500, 1000, 5000),
            help="Wall time to materialize an adapter into its slot on "
            "acquire (cold load or spill restore), milliseconds",
        )
        if self._tenancy is not None:
            for _t in self._tenancy.known():
                self._tenant_series(_t)
        self.traces = TraceRing(capacity=int(self.config.trace_ring))
        import itertools

        self._group_seq = itertools.count(1)
        # live streamed requests by request id, so a broken pipe in the
        # HTTP layer can cancel the right rows (ISSUE 16 satellite)
        self._stream_rows: dict = {}
        # SLO engine + flight recorder (ISSUE 9): objectives come from
        # observability.slos in the run spec (from_run) or the `slos`
        # ctor arg (dicts shaped like V1SLOSpec.to_config()); a breach
        # edge dumps a post-mortem bundle under <debug_dir>/
        self.slo_engine: Optional[SLOEngine] = None
        self.flight_recorder: Optional[FlightRecorder] = None
        # the recorder serves both breach sources: SLO burn edges and the
        # ISSUE 18 regression sentinel's perf_regression edges
        if debug_dir is not None and (slos or regression_rules):
            self.flight_recorder = FlightRecorder(
                debug_dir,
                registry=self.telemetry,
                trace_ring=self.traces,
                state_fn=self._occupancy_state,
                trace_fn=self._breach_trace,
                profile_s=slo_profile_s,
            )
        if slos:
            objectives = build_objectives(
                slos,
                bad=[self._m_http_err],
                total=[self._m_http],
                histogram=self._m_latency,
            )
            # per-tenant SLOs (ISSUE 19): every latency objective is also
            # tracked per tenant against that tenant's own latency
            # histogram, named "<slo>@<tenant>" — a noisy neighbor burning
            # only its own budget shows up as ITS breach, not the fleet's
            if self._tenancy is not None:
                lat_specs = [
                    s
                    for s in slos
                    if s.get("kind", "availability") == "latency"
                ]
                for t in self._tenancy.known():
                    if not lat_specs:
                        break
                    objectives += build_objectives(
                        [
                            {**s, "name": f"{s.get('name', 'slo')}@{t}"}
                            for s in lat_specs
                        ],
                        bad=[self._m_http_err],
                        total=[self._m_http],
                        histogram=self._tenant_series(t)[1],
                    )
            self.slo_engine = SLOEngine(
                objectives,
                self.telemetry,
                on_breach=(
                    self.flight_recorder.dump
                    if self.flight_recorder is not None
                    else None
                ),
            )
        # metrics history + regression sentinel (ISSUE 18): a background
        # sampler snapshots THIS registry into a crash-consistent tiered
        # store under <outputs>/telemetry/history/, /queryz reads it, and
        # declarative rules over its windows fire edge-triggered
        # perf_regression events (event_sink → run event log) plus
        # flight-recorder bundles. `history` is a dict shaped like
        # V1HistorySpec.to_config(): dir (required), interval_s,
        # max_bytes, segment_bytes.
        self.history: Optional[HistoryStore] = None
        self.history_sampler: Optional[HistorySampler] = None
        self.sentinel: Optional[RegressionSentinel] = None
        if history is not None and history.get("dir"):
            self.history = HistoryStore(
                history["dir"],
                max_bytes=int(
                    history.get("max_bytes") or HistoryStore.DEFAULT_MAX_BYTES
                ),
                segment_bytes=int(
                    history.get("segment_bytes")
                    or HistoryStore.DEFAULT_SEGMENT_BYTES
                ),
            )
            self.history_sampler = HistorySampler(
                self.telemetry,
                self.history,
                interval_s=float(history.get("interval_s") or 1.0),
            )
        if regression_rules and self.history is not None:
            self.sentinel = RegressionSentinel(
                self.history,
                self.telemetry,
                build_rules(regression_rules),
                on_event=event_sink,
                recorder=self.flight_recorder,
            )
        self._prompt_ladder, self._new_ladder = self.config.ladders(
            int(module.cfg.seq_len)
        )
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        # one jitted decode program per (shape, sampling) signature — seed
        # is a runtime argument so same-shape requests reuse the compile.
        # On the bucketed path shapes are ladder-quantized, so the count is
        # bounded by the ladder product; the legacy path embeds client-
        # controlled exact shapes, so the dict stays LRU-bounded to keep a
        # novel-shape request stream from leaking compiled XLA programs.
        # Guarded by _lock: jax tracing is not re-entrant, and execution
        # comes from both the decode worker and direct generate() callers.
        import collections

        self._compiled: collections.OrderedDict = collections.OrderedDict()
        self._compiled_max = 32
        self._lock = threading.Lock()
        # adapter registry (ISSUE 19): named LoRA adapters managed like KV
        # pages — refcounted residency in the stacked slots, LRU evict of
        # idle adapters through a dedicated SpillManager RAM tier (+ disk
        # when spill_dir is configured), restore-on-request. The registry
        # lock serializes the (not thread-safe) SpillManager; slot
        # reads/writes take self._lock inside it (consistent order, and
        # finish()-driven release never runs under self._lock).
        if self._adapter_slots_active:
            from .adapters import AdapterRegistry, adapter_template
            from .spill import SpillManager

            self._adapter_template = adapter_template(params)
            self._adapter_spill = SpillManager(
                ram_bytes=256 << 20,
                dir_path=(
                    str(self.config.spill_dir).rstrip("/") + "/adapters"
                    if self.config.spill_dir
                    else None
                ),
                dir_bytes=self.config.spill_dir_bytes,
            )
            self._adapter_registry = AdapterRegistry(
                slots=self._adapter_n_hot,
                sources=self._adapter_sources,
                template=self._adapter_template,
                read_slot=self._adapter_read_slot,
                write_slot=self._adapter_write_slot,
                spill=self._adapter_spill,
                telemetry=self.telemetry,
            )
        self._coalescer: Optional[DecodeCoalescer] = None
        if self.config.batching:
            self._coalescer = self._make_coalescer()
        # block-paged KV cache (ISSUE 6): one fixed pool replaces the dense
        # per-group cache allocations; admission reserves pages instead of
        # worst-case seq_len rows. Only meaningful on the coalesced path.
        self._kv: Optional[KVCacheManager] = None
        if self.config.batching and self.config.kv_pool_pages:
            self._kv = KVCacheManager(
                module,
                params,
                pool_pages=int(self.config.kv_pool_pages),
                page_tokens=int(self.config.kv_page_tokens),
                prefix_cache=bool(self.config.prefix_cache),
                observer=self._kv_observe,
                kv_quant=str(self.config.kv_quant or "none"),
                spill_ram_bytes=self.config.spill_ram_bytes,
                spill_dir=self.config.spill_dir,
                spill_dir_bytes=self.config.spill_dir_bytes,
            )
            self._m_kv_total.set(self._kv.pool.n_pages)
            self._m_kv_used.set(self._kv.pool.used)
        # live KV handoff state (ISSUE 20). The lease table guards the
        # decode side (single-owner adoption per request id, monotonic
        # epochs); the client ships exports from the prefill side with
        # RetryPolicy-driven retries. Exports-in-flight gates drain: a
        # replica must not report idle while a page set is on the wire.
        from .handoff import HandoffClient, LeaseTable

        self._lease_table = LeaseTable()
        self._handoff_client = HandoffClient()
        self._handoff_lock = threading.Lock()
        self._handoff_inflight = 0
        self._handoff_idle = threading.Event()
        self._handoff_idle.set()

    def _handoff_begin(self) -> None:
        with self._handoff_lock:
            self._handoff_inflight += 1
            self._handoff_idle.clear()
            self._m_handoff_inflight.set(self._handoff_inflight)

    def _handoff_end(self) -> None:
        with self._handoff_lock:
            self._handoff_inflight -= 1
            self._m_handoff_inflight.set(self._handoff_inflight)
            if self._handoff_inflight <= 0:
                self._handoff_idle.set()

    def _handoff_ship(self, r: PendingRequest) -> bool:
        """POST the exported page set to the router-named decode replica.
        Handler-thread only. True → the decode side adopted the pages
        (the caller converts the row into a retryable failover so the
        router replays on that replica); False → the caller falls back
        to local monolithic decode. Never raises: every transport and
        protocol failure is a structured HandoffResult reason."""
        if not r.handoff_payload or not r.handoff_target:
            return False
        t0 = _now()
        self._handoff_begin()
        try:
            res = self._handoff_client.send(
                r.handoff_target,
                r.request_id or new_trace_id(),
                r.handoff_payload,
                base_epoch=int(r.handoff_epoch),
            )
        finally:
            self._handoff_end()
            self._m_handoff_ms.observe((_now() - t0) * 1e3)
        if res.ok:
            self._m_handoff_exports.inc()
            if r.trace is not None:
                r.trace.add(
                    "kv_handoff", start=t0, dur_s=_now() - t0, row=r.row,
                    pages=res.adopted_pages, epoch=res.epoch,
                    attempts=res.attempts,
                )
            return True
        self._m_handoff_rejected.inc()
        self._observe(
            "kv_handoff_failed", reason=res.reason, attempts=res.attempts,
        )
        return False

    def _handoff_rerun(self, req: dict, row_idx: int) -> PendingRequest:
        """Monolithic fallback after a failed handoff: re-run one row of
        the validated request locally, with the handoff target cleared.
        The finished prefix is already warm in this replica's cache, so
        the re-run skips straight to decode. Returns the resolved row;
        raises its error (shed/timeout) for the HTTP taxonomy."""
        self._m_handoff_fallbacks.inc()
        sub = dict(req)
        sub["arr"] = req["arr"][row_idx : row_idx + 1]
        # _make_requests seeds row i as seed+i; keep the original row's
        # stream so the fallback stays byte-identical to a monolithic run
        sub["seed"] = int(req["seed"]) + row_idx
        sub["handoff_target"] = ""
        rows = self._make_requests(sub)
        r2 = rows[0]
        r2.row = row_idx
        r2.submitted_t = _now()
        try:
            self._coalescer.submit(r2)
        except BaseException:
            self._release_row(r2)
            raise
        if not r2.done.wait(self.config.request_timeout_s):
            raise TimeoutError(
                f"handoff fallback did not complete within "
                f"{self.config.request_timeout_s:.0f}s"
            )
        if r2.error is not None:
            raise r2.error
        return r2

    def _handoff_stream_resolve(self, req: dict, r: PendingRequest) -> list:
        """Terminal events for a streamed row whose prefill finished with
        a pending handoff. Shipped → one in-band error frame the
        router's failover machinery treats as retryable (it replays the
        stream on the decode replica and trims the already-sent first
        token). Not shipped → local monolithic fallback: the remaining
        tokens stream as one chunk (the first is already on the wire),
        then done."""
        i = r.row
        if self._handoff_ship(r):
            return [{
                "row": i,
                "error": "kv_handoff_done: decode replica owns the stream",
            }]
        try:
            r2 = self._handoff_rerun(req, i)
        except BaseException as e:  # noqa: BLE001 — in-band taxonomy
            return [{"row": i, "error": str(e)}]
        out = []
        rest = r2.result[r2.prompt_len + 1 :]
        if rest:
            out.append({"row": i, "tokens": [int(t) for t in rest]})
        out.append({"row": i, "done": True})
        return out

    def _make_coalescer(self) -> DecodeCoalescer:
        breaker = CircuitBreaker(
            threshold=self.config.breaker_threshold,
            cooldown_s=self.config.breaker_cooldown_s,
            on_change=self._m_breaker.set,
        )
        if self.config.chunked_prefill and self.config.kv_pool_pages:
            # chunked prefill + token-budget step loop (ISSUE 14): only
            # meaningful on the paged path — page tables are what let a
            # half-prefilled row persist across steps. The classic
            # _dispatch_group stays as the blocking fallback for rows the
            # engine cannot step (beam search).
            from .steps import StepScheduler

            return StepScheduler(
                self._dispatch_group,
                _StepEngine(self),
                prefill_chunk_tokens=self.config.prefill_chunk_tokens,
                max_step_tokens=self.config.max_step_tokens,
                max_batch=self.config.max_batch,
                max_wait_ms=self.config.max_wait_ms,
                max_queue=self.config.max_queue,
                breaker=breaker,
                observer=self._observe,
                tenancy=self._tenancy,
            )
        return DecodeCoalescer(
            self._dispatch_group,
            max_batch=self.config.max_batch,
            max_wait_ms=self.config.max_wait_ms,
            max_queue=self.config.max_queue,
            breaker=breaker,
            observer=self._observe,
            tenancy=self._tenancy,
        )

    def _observe(self, event: str, **ctx) -> None:
        """Coalescer → registry bridge: every resilience event lands on
        /metricsz (and /statsz) through the one telemetry pipeline."""
        if event == "shed":
            self._m_shed.inc()
            reason = ctx.get("reason", "overload")
            self.telemetry.counter(
                f"serving.shed.{reason}",
                help=f"Requests shed at admission: {reason}",
            ).inc()
            # per-tenant shed attribution (ISSUE 19): only for tenants the
            # operator configured — unknown names 400 before admission, so
            # clients can't mint unbounded metric series
            tenant = ctx.get("tenant")
            if (
                tenant
                and self._tenancy is not None
                and tenant in self._tenancy.known()
            ):
                self._tenant_series(tenant)[0].inc()
            if reason == "deadline":
                self._m_deadline.inc()
        elif event == "deadline_dropped":
            self._m_deadline.inc()
        elif event == "worker_restart":
            self._m_worker_restarts.inc()
        elif event == "decode_error":
            self.telemetry.counter(
                "serving.decode_errors", help="Decode batch failures"
            ).inc()
        elif event == "step":
            # one device step of the step scheduler: its token budget
            # spend and its row occupancy (same histogram the classic
            # group path feeds, so occupancy dashboards keep working)
            self._m_step_tokens.observe(float(ctx.get("tokens", 0)))
            rows = int(ctx.get("rows", 0))
            if rows:
                self._m_occupancy.observe(rows)
            self._m_batches.inc()

    @contextlib.contextmanager
    def _phase(self, phase: str, **attrs):
        """One phase of a scheduler step: a span and its seconds. A span
        inside which this thread had XLA build (or load) a program says
        so, and which: a new lane width in `dispatch`, a new harvest shape
        in `emit`."""
        span = self.spans.span(_STEP_PHASES[phase][0], **attrs)
        before = compiles.mine()
        try:
            with span:
                yield span
                built = compiles.mine() - before
                if built:
                    span.set(
                        compiled=True,
                        programs=compiles.recent(built, mine=True),
                    )
        finally:
            self._m_phase[phase].inc(span.dur_s or 0.0)

    def _kv_observe(self, event: str, **ctx) -> None:
        """KVCacheManager → registry bridge (same pipeline as _observe)."""
        if event == "kv_pages":
            self._m_kv_used.set(ctx["used"])
            self._m_kv_prefix_held.set(ctx.get("prefix_held", 0))
            self._m_kv_handoff_held.set(ctx.get("handoff_held", 0))
        elif event == "kv_handoff_adopt":
            self._m_handoff_imports.inc()
        elif event == "prefix_hit":
            self._m_prefix_hits.inc()
        elif event == "prefix_miss":
            self._m_prefix_misses.inc()
        elif event == "prefix_evict":
            self.telemetry.counter(
                "serving.prefix_cache_evictions",
                help="Prefix-cache entries LRU-evicted to admit new requests",
            ).inc()
        elif event == "kv_spill":
            self._m_spill_bytes.inc(int(ctx.get("bytes", 0)))
        elif event == "kv_spill_restore":
            self._m_spill_restores.inc()
        elif event == "kv_spill_quarantined":
            self._m_spill_quarantined.inc(int(ctx.get("n", 1)))
        elif event == "shed":
            self._observe("shed", **ctx)

    # ------------------------------------------------------------ tenancy
    def _tenant_series(self, tenant: str):
        """Get-or-create the per-tenant series triple: (shed counter,
        request-latency histogram, queue-wait histogram). Only called for
        operator-configured tenant names — cardinality is bounded by the
        run spec, never by clients."""
        reg = self.telemetry
        return (
            reg.counter(
                f"serving.shed_by_tenant.{tenant}",
                help=f"Requests shed at admission for tenant {tenant!r}",
            ),
            reg.histogram(
                f"serving.request_seconds_by_tenant.{tenant}",
                help=f"End-to-end latency for tenant {tenant!r}, seconds",
            ),
            reg.histogram(
                f"serving.queue_wait_by_tenant.{tenant}",
                help=f"Submit-to-dispatch wait for tenant {tenant!r}, "
                "seconds",
            ),
        )

    def _observe_queue_wait(self, r, wait: float) -> None:
        """One row's submit→dispatch wait, fanned to the global histogram
        plus — for named tenants — the fairness signal and the tenant's
        own split."""
        self._m_queue_wait.observe(wait)
        tenant = getattr(r, "tenant", "") or ""
        if self._tenancy is None or not tenant:
            return
        if tenant not in self._tenancy.known():
            return
        self._tenant_series(tenant)[2].observe(wait)
        from .tenancy import DEFAULT_TENANT

        if tenant != DEFAULT_TENANT:
            # the aggregate fairness-trend signal tracks NAMED tenants
            # only — default traffic has no contract to regress against
            self._m_tenant_queue_wait.observe(wait)

    def _observe_tenant_latency(self, tenant: str, dur: float) -> None:
        if self._tenancy is None or not tenant:
            return
        if tenant not in self._tenancy.known():
            return
        self._tenant_series(tenant)[1].observe(dur)

    def _observe_body_latency(self, body, dur: float) -> None:
        """End-to-end latency split by the request body's tenant — feeds
        the per-tenant latency histograms the per-tenant SLO objectives
        burn against."""
        if self._tenancy is None:
            return
        try:
            name = self._tenancy.resolve(
                str((body or {}).get("tenant") or "")
            ).name
        except Exception:  # noqa: BLE001 — unknown tenants 400 elsewhere
            return
        self._observe_tenant_latency(name, dur)

    def _adapter_read_slot(self, slot: int) -> list:
        """Host copies of every LoRA leaf's [slot] slice, in the
        registry's sorted-template-path order — the spill payload for a
        demoted adapter."""
        import numpy as np

        wanted = set(self._adapter_template)
        found: dict = {}

        def walk(node, prefix):
            if isinstance(node, dict):
                for k, v in node.items():
                    walk(v, f"{prefix}/{k}" if prefix else k)
            elif prefix in wanted:
                found[prefix] = np.asarray(node[..., slot, :, :])

        with self._lock:
            walk(self.params, "")
        return [found[p] for p in sorted(self._adapter_template)]

    def _adapter_write_slot(self, slot: int, adapter: dict) -> None:
        """Install one adapter (slash-joined path → array) into stacked
        slot `slot` via functional .at[].set — under self._lock because
        dispatches snapshot self.params under that same lock before
        launching their compiled programs."""
        import jax.numpy as jnp

        def walk(node, prefix):
            if isinstance(node, dict):
                return {
                    k: walk(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in node.items()
                }
            if prefix in adapter:
                arr = jnp.asarray(adapter[prefix], node.dtype)
                return node.at[..., slot, :, :].set(arr)
            return node

        with self._lock:
            self.params = walk(self.params, "")

    def _adapter_ix(self, batch, bb: int):
        """[bb] int32 adapter-slot gather indices for one dispatch, or
        None when this server has no stacked slots. Pad rows ride slot 0
        (the checkpoint's own adapter) — inert and always resident."""
        if not self._adapter_slots_active:
            return None
        import numpy as np

        ix = np.zeros((bb,), np.int32)
        for i, r in enumerate(batch):
            ix[i] = int(getattr(r, "adapter_slot", 0))
        return ix

    # ------------------------------------------------------------ tracing
    def _new_trace(self, rid: str, **attrs) -> Optional[RequestTrace]:
        """A RequestTrace for this request id, or None when tracing is
        off (config.trace=False)."""
        if not self.config.trace:
            return None
        return RequestTrace(rid, **attrs)

    def _finish_trace(
        self, trace: Optional[RequestTrace], error: Optional[BaseException]
    ) -> None:
        """Close the root span and hand the trace to the tail sampler."""
        if trace is None:
            return
        trace.finish(
            status=_trace_status(error),
            error=None if error is None else str(error),
        )
        self.traces.record(trace)

    def _trace_group(self, batch) -> tuple[int, float]:
        """Open one decode group: a fresh group span id shared by every
        member row's trace, plus each row's queue_wait span (submit →
        dispatch on the telemetry clock). Returns (group_id, dispatch_t)
        so the execute path can anchor its prefill/decode spans."""
        gid = next(self._group_seq)
        td = _now()
        for r in batch:
            if r.trace is None:
                continue
            r.trace.set_group(gid)
            start = r.submitted_t if r.submitted_t is not None else r.trace.t0
            r.trace.add(
                "queue_wait",
                start=start,
                dur_s=td - start,
                group=gid,
                row=r.row,
            )
        return gid, td

    def _occupancy_state(self) -> dict:
        """Queue/KV occupancy snapshot for the flight-recorder bundle."""
        out: dict = {"draining": self._draining}
        c = self._coalescer
        if c is not None:
            out["queue"] = {
                "depth": c.depth,
                "breaker": c.breaker.state if c.breaker else None,
            }
        if self._kv is not None:
            out["kv"] = self._kv.stats()
        return out

    def _breach_trace(self, breach: dict) -> Optional[dict]:
        """The trace that explains a breach: for latency objectives the
        p99 exemplar (the histogram observation that carried a trace id
        near the spike); availability falls back to the ring's errors."""
        if breach.get("kind") == "latency":
            ex = self._m_latency.exemplar(0.99)
            if ex is not None:
                return self.traces.get(ex["trace_id"])
        return None

    @property
    def compile_count(self) -> int:
        """Programs BUILT (cache misses), ever — the bound the
        bucket-sweep test pins."""
        return int(self._m_cache_misses.value)

    @property
    def requests_served(self) -> int:
        return int(self._m_requests.value)

    # ------------------------------------------------------- compiled cache
    def _cached(self, key, build):
        """LRU lookup/insert; counts hits/misses into the registry (a miss
        is a program build — the compile-count telemetry the bucket-sweep
        test pins). Callers hold _lock."""
        fn = self._compiled.get(key)
        if fn is not None:
            self._compiled.move_to_end(key)
            self._m_cache_hits.inc()
            return fn
        fn = build()
        self._m_cache_misses.inc()
        self._compiled[key] = fn
        while len(self._compiled) > self._compiled_max:
            self._compiled.popitem(last=False)
        return fn

    def _decode_fn(
        self, batch, prompt_len, max_new, temperature, top_k, eos_id,
        num_beams=1, length_penalty=1.0,
    ):
        """Legacy exact-shape program: sampling per (batch, P, new,
        sampling) signature, or beam search (which ignores temperature/
        top_k; sampling ignores length_penalty — normalize the key so
        equivalent requests don't compile duplicate programs)."""
        import jax

        from ..models.generate import beam_search, generate

        if num_beams > 1:
            temperature, top_k = 0.0, None
        else:
            length_penalty = 1.0
        key = (
            "exact", batch, prompt_len, max_new, temperature, top_k, eos_id,
            num_beams, length_penalty,
        )

        def generate_beam(params, prompt, seed):
            return beam_search(
                self.module,
                params,
                prompt,
                max_new_tokens=max_new,
                num_beams=num_beams,
                length_penalty=length_penalty,
                eos_id=eos_id,
            )

        def generate_exact(params, prompt, seed):
            return generate(
                self.module,
                params,
                prompt,
                max_new_tokens=max_new,
                temperature=temperature,
                top_k=top_k,
                eos_id=eos_id,
                seed=seed,
            )

        def build():
            return jax.jit(generate_beam if num_beams > 1 else generate_exact)

        return self._cached(key, build)

    def _bucketed_fn(self, batch, prompt_bucket, new_bucket, temperature, top_k, eos_id):
        """Bucketed program: prompt_lengths and per-row seeds are runtime
        [B] arguments, so every true length/seed mix in the bucket reuses
        this one compile."""
        import jax

        from ..models.generate import generate

        key = (
            "bucket", batch, prompt_bucket, new_bucket, temperature, top_k,
            eos_id, self._adapter_slots_active,
        )

        def generate_bucketed(params, prompt, lengths, seeds, adapter_ix=None):
            return generate(
                self.module,
                params,
                prompt,
                max_new_tokens=new_bucket,
                temperature=temperature,
                top_k=top_k,
                eos_id=eos_id,
                seed=seeds,
                prompt_lengths=lengths,
                adapter_ix=adapter_ix,
            )

        def build():
            return jax.jit(generate_bucketed)

        return self._cached(key, build)

    # ------------------------------------------------------------ loading
    @classmethod
    def from_run(
        cls,
        run_ref: str,
        store: Optional[RunStore] = None,
        mesh_axes: Optional[dict] = None,
        config: Optional[ServingConfig] = None,
        config_overrides: Optional[dict] = None,
        expected_devices: Optional[int] = None,
    ):
        """Restore the latest checkpoint of a `transformer_lm` jaxjob run.

        Serving-shaped restore — NOT a Trainer: the model bundle and mesh
        are built directly from the stored spec, and only the `params`
        subtree of the saved TrainState is read back (Orbax partial
        restore). No data pipeline is constructed (the training corpus
        need not exist on the serving host, no prefetch threads spin up)
        and the Adam moments never touch HBM, so serving holds params-sized
        memory instead of the ~3x TrainState.

        `mesh_axes` (e.g. {"model": 4}) shards the restored params over a
        device mesh for models too big for one chip — decode is unchanged,
        XLA inserts the collectives from the param shardings (parity with
        single-device decoding is tested).

        `config` replaces the batching knobs wholesale; absent, the stored
        spec's `program.serving` section (schemas.run_kinds.V1ServingSpec)
        provides defaults so a run can pin its own serving shape.
        `config_overrides` (field-name → value) layers individual knobs
        over that base — a CLI `--max-queue 2` must not silently reset the
        spec's `maxBatch` pin back to the library default."""
        import jax

        from ..models import build_model
        from ..parallel.mesh import decode_mesh
        from ..parallel.ring import set_current_mesh
        from ..parallel.sharding import param_shardings
        from ..runtime.trainer import make_param_init, param_dtype_for
        from ..schemas.run_kinds import V1JAXJob

        store = store or RunStore()
        uuid = store.resolve(run_ref)
        spec = store.read_spec(uuid)
        run = (spec.get("component") or {}).get("run") or {}
        if run.get("kind") != "jaxjob" or not run.get("program"):
            raise ServingError(
                f"run {uuid[:8]} is not a native jaxjob program run"
            )
        run_spec = V1JAXJob.model_validate(run)
        from ..utils.jax_platform import require_declared_tpu

        # before the restore: a TPU run served from a silent CPU fallback
        # fails here, not after reading back a chip-sized checkpoint
        require_declared_tpu(run_spec, jax.devices()[0].platform)
        program = run_spec.program
        if program.model.name not in ("transformer_lm",):
            raise ServingError(
                f"serving supports the LM family (transformer_lm), run "
                f"{uuid[:8]} trained {program.model.name!r}"
            )
        if config is None and program.serving is not None:
            config = program.serving.to_config()
        if config_overrides:
            config = dataclasses.replace(
                config if config is not None else ServingConfig(),
                **config_overrides,
            )
        if mesh_axes:
            # the CLI --mesh flag is an override like any other knob: it
            # layers over the spec's meshAxes without resetting it to None
            from .batching import normalize_mesh_axes

            config = dataclasses.replace(
                config if config is not None else ServingConfig(),
                mesh_axes=normalize_mesh_axes(mesh_axes),
            )
        # absolute: orbax's CheckpointManager rejects relative paths, and a
        # store rooted at a relative POLYAXON_HOME (CLI run from the store's
        # parent dir) would otherwise fail only at serve time
        ckpt_dir = (store.outputs_dir(uuid) / "checkpoints").resolve()
        if not ckpt_dir.is_dir():
            raise ServingError(
                f"run {uuid[:8]} has no checkpoints under its outputs — "
                "train with train.checkpointEvery set"
            )
        from ..utils.jax_platform import apply_compilation_cache

        apply_compilation_cache()  # serve restarts reuse training compiles
        bundle = build_model(program.model.name, program.model.config)
        tspec = program.train
        seed = int(tspec.seed) if tspec else 0
        precision = tspec.precision if tspec else "mixed"
        axes = config.mesh_axes if config is not None else None
        # the named 2-D serving mesh (`batch`×`model`); no axes = the
        # single-chip path on device 0, exactly the pre-mesh behaviour
        mesh = decode_mesh(dict(axes) if axes else None)
        set_current_mesh(mesh)  # decode-time sharding constraints need it
        # the trainer's own init recipe → identical abstract tree, no drift
        init_fn = make_param_init(
            bundle, param_dtype_for(precision), bundle.example_inputs(1)
        )
        abstract_params, _ = jax.eval_shape(
            init_fn, jax.random.PRNGKey(seed)
        )
        p_shard = param_shardings(
            abstract_params, bundle.sharding_rules, mesh
        )
        abstract = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            abstract_params,
            p_shard,
        )
        params, step = _restore_params_subtree(str(ckpt_dir), abstract)
        # the run's own SLOs (spec observability.slos) arm the burn-rate
        # engine; breach bundles land next to the checkpoints it serves.
        # observability.history arms the metrics-history sampler under
        # <outputs>/telemetry/history/ and observability.regressionRules
        # the sentinel — whose perf_regression edges land in THIS run's
        # event log (ISSUE 18)
        slos = None
        history = None
        rules = None
        obs = program.observability
        if obs is not None and obs.slos:
            slos = [s.to_config() for s in obs.slos]
        if obs is not None and obs.history is not None and obs.history.enabled:
            history = obs.history.to_config(
                str(store.outputs_dir(uuid) / "telemetry" / "history")
            )
        if obs is not None and obs.regression_rules:
            rules = obs.rules_config()
        return cls(
            bundle.module,
            params,
            model_name=program.model.name,
            step=step,
            config=config,
            expected_devices=expected_devices,
            slos=slos,
            debug_dir=(
                str(store.outputs_dir(uuid) / "debug")
                if (slos or rules)
                else None
            ),
            sharding_rules=bundle.sharding_rules,
            mesh=mesh,
            history=history,
            regression_rules=rules,
            event_sink=(
                (lambda kind, body: store.log_event(uuid, kind, body))
                if rules
                else None
            ),
        )

    # --------------------------------------------------------- validation
    def _validate(self, body: dict) -> dict:
        import numpy as np

        tokens = body.get("tokens")
        if not tokens or not isinstance(tokens, list):
            raise ServingError("body.tokens must be a non-empty [[int]] batch")
        max_new = int(body.get("maxNewTokens", 16))
        if max_new < 1:
            raise ServingError("maxNewTokens must be >= 1")
        try:
            arr = np.asarray(tokens, dtype=np.int32)
        except (ValueError, TypeError) as e:
            raise ServingError(f"tokens must be rectangular [[int]]: {e}")
        if arr.ndim != 2 or arr.shape[1] < 1:
            raise ServingError(
                "tokens must be rectangular [[int]] with >= 1 token per row"
            )
        cfg = self.module.cfg
        if arr.min() < 0 or arr.max() >= cfg.vocab_size:
            raise ServingError(
                f"token ids must be in [0, {cfg.vocab_size}); "
                f"got range [{arr.min()}, {arr.max()}]"
            )
        if arr.shape[1] + max_new > cfg.seq_len:
            raise ServingError(
                f"prompt ({arr.shape[1]}) + maxNewTokens ({max_new}) exceeds "
                f"the model's seq_len {cfg.seq_len}"
            )
        top_k = body.get("topK")
        eos = body.get("eosId")
        num_beams = int(body.get("numBeams", 1))
        # hard cap: numBeams is client-controlled and multiplies the KV
        # cache and candidate tensors — unbounded values are a remote OOM
        max_beams = min(32, cfg.vocab_size)
        if not 1 <= num_beams <= max_beams:
            raise ServingError(
                f"numBeams must be in [1, {max_beams}]"
            )
        # deadline: body deadlineMs wins, then the config default; absolute
        # monotonic time from here on (time.monotonic ONLY — the telemetry
        # lint rejects wall-clock deadline math in serving/)
        deadline_ms = body.get("deadlineMs", self.config.default_deadline_ms)
        deadline = None
        if deadline_ms is not None:
            deadline_ms = float(deadline_ms)
            if deadline_ms <= 0:
                raise ServingError(
                    f"deadlineMs must be > 0, got {deadline_ms}"
                )
            deadline = time.monotonic() + deadline_ms / 1e3
        # tenant resolution (ISSUE 19): the body's `tenant` field (the
        # router copies the X-Tenant header into it). Unknown names are a
        # client error, not a shed — quota isolation is meaningless if
        # anyone can mint a fresh tenant.
        raw_tenant = str(body.get("tenant") or "").strip()
        tenant, adapter = "default", ""
        if self._tenancy is not None:
            try:
                tspec = self._tenancy.resolve(raw_tenant)
            except KeyError:
                raise ServingError(f"unknown tenant {raw_tenant!r}")
            tenant, adapter = tspec.name, tspec.adapter
        elif raw_tenant and raw_tenant != "default":
            raise ServingError(
                f"unknown tenant {raw_tenant!r}: this server has no "
                "tenants configured"
            )
        if adapter and (num_beams > 1 or not self.config.batching):
            raise ServingError(
                "adapter-bound tenants require the coalesced decode path "
                "(no beam search, batching enabled)"
            )
        # disaggregated handoff (ISSUE 20): the router names a decode
        # replica in X-Handoff-Target (do_POST copies the header into
        # the body, same pattern as X-Tenant). Only a prefill-role
        # server acts on it; everyone else decodes monolithically.
        handoff_target, handoff_epoch = "", 0
        if self.config.role == "prefill":
            handoff_target = str(body.get("handoffTarget") or "").strip()
            try:
                handoff_epoch = int(body.get("handoffEpoch") or 0)
            except (TypeError, ValueError):
                handoff_epoch = 0
        return {
            "tenant": tenant,
            "adapter": adapter,
            "deadline": deadline,
            "deadline_ms": deadline_ms,
            "arr": arr,
            "max_new": max_new,
            "temperature": float(body.get("temperature", 0.0)),
            "top_k": int(top_k) if top_k is not None else None,
            "eos_id": int(eos) if eos is not None else None,
            "num_beams": num_beams,
            "length_penalty": float(body.get("lengthPenalty", 1.0)),
            "seed": int(body.get("seed", 0)),
            "handoff_target": handoff_target,
            "handoff_epoch": handoff_epoch,
        }

    def _make_requests(self, req: dict) -> list[PendingRequest]:
        """One PendingRequest PER ROW — rows of a multi-row body may land
        in different prompt buckets and coalesce with different peers.
        Row i samples from seed+i so identical rows still diverge (the
        scalar-seed legacy path had the same property via shared-batch
        sampling)."""
        cfg = self.module.cfg
        # decode mode (ISSUE 8): constant per server, but part of the
        # group signature so mixed-mode groups can never form (and the
        # compiled-program keys below inherit it via the key fields).
        # With the adaptive controller (ISSUE 15) the draft width — and
        # whether the group speculates at all — is the controller's
        # CURRENT decision: new groups land in plain lanes while
        # speculation is auto-disabled, and re-enter spec lanes at the
        # re-probed K. In-flight groups keep their admitted key.
        spec_on = bool(self.config.speculate)
        eff_k = int(self.config.draft_tokens) if spec_on else 0
        if spec_on and self._spec_controller is not None:
            eff_k = int(self._spec_controller.window_k())
            spec_on = eff_k > 0
            self._m_spec_effective_k.set(eff_k)
        mode = dict(
            speculate=spec_on,
            draft_tokens=eff_k,
            quantize=bool(self.config.quantize),
        )
        adapter = req.get("adapter") or ""
        tenant = req.get("tenant") or "default"
        out = []
        try:
            for i, row in enumerate(req["arr"]):
                # adapter residency first (ISSUE 19): pin the tenant's
                # adapter slot for this row — may cold-load or restore
                # from spill (timed into the load histogram), may shed
                # with reason "adapter_capacity" when every slot is
                # pinned by in-flight rows
                slot, acquired = 0, False
                if adapter:
                    t0a = _now()
                    try:
                        slot, loaded = self._adapter_registry.acquire(
                            adapter
                        )
                    except KeyError:
                        raise ServingError(f"unknown adapter {adapter!r}")
                    acquired = True
                    if loaded:
                        self._m_adapter_load.observe((_now() - t0a) * 1e3)
                try:
                    plan = None
                    if self._kv is not None:
                        # paged admission: prefix lookup + suffix
                        # bucketing + page reservation (may shed with
                        # reason "kv_pages")
                        plan = self._kv.plan_row(
                            row.tolist(),
                            req["max_new"],
                            self._prompt_ladder,
                            self._new_ladder,
                            int(cfg.seq_len),
                            trace=req.get("trace"),
                        )
                        pb, nb = plan.suffix_bucket, plan.new_bucket
                        key = GroupKey(
                            prompt_bucket=pb,
                            new_bucket=nb,
                            temperature=req["temperature"],
                            top_k=req["top_k"],
                            eos_id=req["eos_id"],
                            prefix_len=plan.prefix_len,
                            **mode,
                        )
                    else:
                        pb, nb = choose_buckets(
                            len(row),
                            req["max_new"],
                            self._prompt_ladder,
                            self._new_ladder,
                            int(cfg.seq_len),
                        )
                        key = GroupKey(
                            prompt_bucket=pb,
                            new_bucket=nb,
                            temperature=req["temperature"],
                            top_k=req["top_k"],
                            eos_id=req["eos_id"],
                            **mode,
                        )
                except BaseException:
                    if acquired:
                        self._adapter_registry.release(adapter)
                    raise
                r = PendingRequest(
                    tokens=row.tolist(),
                    prompt_len=len(row),
                    max_new=req["max_new"],
                    seed=req["seed"] + i,
                    key=key,
                    deadline=req["deadline"],
                    kv_plan=plan,
                    t0=_now(),
                    request_id=req.get("rid"),
                    trace=req.get("trace"),
                    row=i,
                    tenant=tenant,
                    adapter=adapter,
                    adapter_slot=slot,
                    handoff_target=req.get("handoff_target") or None,
                    handoff_epoch=int(req.get("handoff_epoch") or 0),
                )
                if plan is not None or adapter:
                    # on ANY terminal path (scatter, shed, deadline, crash,
                    # drain) the row's pages/reservation/prefix refs return
                    # to the pool and its adapter slot unpins — finish()
                    # is idempotent, so is release()
                    r.on_finish = self._release_row
                out.append(r)
        except (ShedError, ServingError):
            # row k failed admission: rows 0..k-1 already hold
            # reservations and adapter pins
            for r in out:
                self._release_row(r)
            raise
        return out

    def _release_row(self, r: PendingRequest) -> None:
        if r.kv_plan is not None and self._kv is not None:
            self._kv.release(r.kv_plan)
        if r.adapter and self._adapter_registry is not None:
            self._adapter_registry.release(r.adapter)

    # retained name: tests and older callsites reach for _release_plan
    _release_plan = _release_row

    # ------------------------------------------------------------ compute
    def _execute_group(self, batch: list[PendingRequest]):
        """Run ONE coalesced group (same GroupKey) and scatter row results
        back into each request. Called from the decode worker thread, or
        inline by generate() — both under _lock for the jax part."""
        import time as _time

        import jax.numpy as jnp
        import numpy as np

        key = batch[0].key
        n = len(batch)
        # chaos points: "sleep" on serving.slow injects decode latency
        # (deadline pressure), "raise" on serving.decode fails the batch
        # (breaker material) — both seed-scheduled via FaultPlan
        inject("serving.slow", rows=n)
        inject("serving.decode", rows=n)
        qnow = _time.monotonic()  # same clock as PendingRequest.enqueued_at
        for r in batch:
            self._observe_queue_wait(r, max(0.0, qnow - r.enqueued_at))
        self._m_occupancy.observe(n)
        self._m_batches.inc()
        gid, td = self._trace_group(batch)
        P, N = key.prompt_bucket, key.new_bucket
        bb = batch_bucket(n, max(n, self.config.max_batch))
        arr = np.zeros((bb, P), np.int32)
        lengths = np.ones((bb,), np.int32)  # pad rows: dummy length-1 prompt
        seeds = np.zeros((bb,), np.int32)
        for i, r in enumerate(batch):
            arr[i, P - r.prompt_len:] = r.tokens
            lengths[i] = r.prompt_len
            seeds[i] = r.seed
        ix = self._adapter_ix(batch, bb)
        with self._lock:
            fn = self._bucketed_fn(
                bb, P, N, key.temperature, key.top_k, key.eos_id
            )
            args = [
                self.params,
                jnp.asarray(arr),
                jnp.asarray(lengths),
                jnp.asarray(seeds),
            ]
            if ix is not None:
                args.append(jnp.asarray(ix))
            out = np.asarray(fn(*args))
        for i, r in enumerate(batch):
            pad = P - r.prompt_len
            if r.t0 is not None:
                # dense path has no incremental emission: TTFT degenerates
                # to whole-decode latency (the paged path beats this)
                self._m_ttft.observe((_now() - r.t0) * 1e3)
            # truncate the bucketed tail to what the client asked for — a
            # longer bucket's extra tokens are a strict continuation, so
            # the first max_new are identical to an exact-shape run
            r.finish(
                result=out[i, pad : pad + r.prompt_len + r.max_new].tolist()
            )
            if r.trace is not None:
                # dense path: one fused prefill+decode program, so the
                # whole dispatch is one decode span
                end = r.finished_t if r.finished_t is not None else _now()
                r.trace.add(
                    "decode",
                    start=td,
                    dur_s=end - td,
                    group=gid,
                    rows=n,
                    steps=N,
                    row=r.row,
                )
        self._spec_tick_plain(N)
        self._m_requests.inc(n)

    # ------------------------------------------------- speculative decode
    def _spec_prefill_fn(self, bb, pb, temperature, top_k):
        from ..models.spec_decode import jit_spec_prefill

        key = ("spec_prefill", bb, pb, temperature, top_k)
        return self._cached(
            key,
            lambda: jit_spec_prefill(
                self.module, temperature=temperature, top_k=top_k
            ),
        )

    def _spec_verify_fn(self, bb, draft_tokens, temperature, top_k, eos_id):
        from ..models.spec_decode import jit_spec_verify

        key = ("spec_verify", bb, draft_tokens, temperature, top_k, eos_id)
        return self._cached(
            key,
            lambda: jit_spec_verify(
                self.module,
                temperature=temperature,
                top_k=top_k,
                eos_id=eos_id,
            ),
        )

    def _spec_verify_paged_fn(
        self, bb, draft_tokens, prefix_len, n_pages, temperature, top_k,
        eos_id,
    ):
        from ..models.spec_decode import jit_spec_verify_paged

        key = (
            "spec_verify_paged", bb, draft_tokens, prefix_len, n_pages,
            temperature, top_k, eos_id,
        )
        return self._cached(
            key,
            lambda: jit_spec_verify_paged(
                self.module,
                kv_layout=self._kv.layout,
                prefix_len=prefix_len,
                temperature=temperature,
                top_k=top_k,
                eos_id=eos_id,
            ),
        )

    def _spec_observe(self, stats: dict) -> None:
        proposed = int(stats.get("proposed", 0))
        accepted = int(stats.get("accepted", 0))
        self._m_spec_proposed.inc(proposed)
        self._m_spec_accepted.inc(accepted)
        self._m_spec_rollback.inc(int(stats.get("rollback", 0)))
        self._m_spec_truncated.inc(int(stats.get("truncated", 0)))
        if self._spec_controller is not None and proposed:
            # the controller eats the truncation-CORRECTED accepts — the
            # raw committed count deflates near maxNewTokens and would
            # bias K downward on exactly the long-output requests where
            # speculation pays most (satellite of ISSUE 15)
            self._spec_controller.observe(
                proposed,
                int(stats.get("accepted_judged", accepted)),
                accepted_raw=accepted,
            )
            self._m_spec_effective_k.set(self._spec_controller.window_k())

    def _spec_tick_plain(self, steps: int) -> None:
        """Logical plain-decode progress: while the controller has
        speculation auto-disabled, these ticks drive the clock-free
        re-probe cadence."""
        if self._spec_controller is not None and steps > 0:
            self._spec_controller.tick_plain(int(steps))
            self._m_spec_effective_k.set(self._spec_controller.window_k())

    def _draft_prefill_fn(self):
        from ..models.draft import jit_draft_prefill

        return self._cached(
            ("draft_prefill",),
            lambda: jit_draft_prefill(self._draft_module),
        )

    def _make_drafter(self, prompts, lengths, seeds, *, temperature, top_k):
        """Batched ModelDrafter over the group's bucketed prompts (call
        under _lock — the ctor runs the draft prefill). Compiled draft
        programs are shared across all groups via the server-wide
        prefill fn and propose-fn dict."""
        from ..models.draft import ModelDrafter

        return ModelDrafter(
            self._draft_module,
            self._draft_params,
            prompts,
            lengths,
            seeds=seeds,
            temperature=temperature,
            top_k=top_k,
            prefill_fn=self._draft_prefill_fn(),
            propose_fns=self._draft_propose_fns,
        )

    def _execute_group_spec(self, batch: list[PendingRequest]):
        """Dense-cache speculative group: same bucketed shapes and
        byte-identical outputs as _execute_group, but the decode loop is
        models/spec_decode.spec_generate — n-gram drafts, one verify
        window per K+1 tokens, per-row accept lengths."""
        import time as _time

        import jax.numpy as jnp
        import numpy as np

        from ..models.spec_decode import spec_generate

        key = batch[0].key
        n = len(batch)
        inject("serving.slow", rows=n)
        inject("serving.decode", rows=n)
        qnow = _time.monotonic()
        for r in batch:
            self._observe_queue_wait(r, max(0.0, qnow - r.enqueued_at))
        self._m_occupancy.observe(n)
        self._m_batches.inc()
        gid, td = self._trace_group(batch)
        P, N = key.prompt_bucket, key.new_bucket
        bb = batch_bucket(n, max(n, self.config.max_batch))
        arr = np.zeros((bb, P), np.int32)
        lengths = np.ones((bb,), np.int32)
        seeds = np.zeros((bb,), np.int32)
        for i, r in enumerate(batch):
            arr[i, P - r.prompt_len:] = r.tokens
            lengths[i] = r.prompt_len
            seeds[i] = r.seed
        ix = self._adapter_ix(batch, bb)
        stats: dict = {}
        with self._lock:
            prefill_fn = self._spec_prefill_fn(
                bb, P, key.temperature, key.top_k
            )
            verify_fn = self._spec_verify_fn(
                bb, key.draft_tokens, key.temperature, key.top_k, key.eos_id
            )
            drafter = None
            if self._draft_module is not None:
                drafter = self._make_drafter(
                    arr, lengths, seeds,
                    temperature=key.temperature, top_k=key.top_k,
                )
            out = np.asarray(
                spec_generate(
                    self.module,
                    self.params,
                    jnp.asarray(arr),
                    max_new_tokens=N,
                    draft_tokens=key.draft_tokens,
                    temperature=key.temperature,
                    top_k=key.top_k,
                    eos_id=key.eos_id,
                    seeds=seeds,
                    prompt_lengths=lengths,
                    prefill_fn=prefill_fn,
                    verify_fn=verify_fn,
                    stats=stats,
                    drafter=drafter,
                    adapter_ix=None if ix is None else jnp.asarray(ix),
                )
            )
        self._spec_observe(stats)
        for i, r in enumerate(batch):
            pad = P - r.prompt_len
            if r.t0 is not None:
                self._m_ttft.observe((_now() - r.t0) * 1e3)
            r.finish(
                result=out[i, pad : pad + r.prompt_len + r.max_new].tolist()
            )
            if r.trace is not None:
                # spec_generate fuses prefill + all verify windows; the
                # span carries the group's accept accounting as attrs
                end = r.finished_t if r.finished_t is not None else _now()
                r.trace.add(
                    "decode",
                    start=td,
                    dur_s=end - td,
                    group=gid,
                    rows=n,
                    row=r.row,
                    proposed=int(stats.get("proposed", 0)),
                    accepted=int(stats.get("accepted", 0)),
                    rollback=int(stats.get("rollback", 0)),
                )
        self._m_requests.inc(n)

    def _execute_group_paged_spec(self, batch: list[PendingRequest]):
        """Paged speculative group: _execute_group_paged's admission,
        prefill, streaming, and harvest, with the chunk loop replaced by
        verify windows (jit_spec_verify_paged). Rows accept different
        lengths, so the write frontier and generation index are per-row
        vectors, and each window streams exactly the tokens it committed.
        Outputs stay byte-identical to the non-speculative paged path."""
        import time as _time

        import jax.numpy as jnp
        import numpy as np

        from ..models.spec_decode import NgramDrafter, commit_window

        kv = self._kv
        key = batch[0].key
        n = len(batch)
        K = int(key.draft_tokens)
        inject("serving.slow", rows=n)
        inject("serving.decode", rows=n)
        qnow = _time.monotonic()
        for r in batch:
            self._observe_queue_wait(r, max(0.0, qnow - r.enqueued_at))
        self._m_occupancy.observe(n)
        self._m_batches.inc()
        gid, td = self._trace_group(batch)
        L, pb, nb = key.prefix_len, key.prompt_bucket, key.new_bucket
        n_pages = kv.layout.pages_for(L + pb + nb - 1)
        bb = batch_bucket(n, max(n, self.config.max_batch))
        plans = [r.kv_plan for r in batch] + [None] * (bb - n)
        traces = [r.trace for r in batch]
        arr = np.zeros((bb, pb), np.int32)
        pads = np.full((bb,), pb - 1, np.int32)
        seeds = np.zeros((bb,), np.int32)
        for i, r in enumerate(batch):
            sfx = r.tokens[L:]
            arr[i, pb - len(sfx):] = sfx
            pads[i] = pb - len(sfx)
            seeds[i] = r.seed
        ix = self._adapter_ix(batch, bb)
        kv.ensure_pages(plans[:n], upto_slot=L + pb, traces=traces)
        tables = kv.tables(plans, bb, n_pages)
        with self._lock:
            # land any queued spill restores before the prefill reads
            # restored prefix pages (ISSUE 17)
            kv.flush_restores()
            fn = self._paged_prefill_fn(
                bb, pb, L, n_pages, key.temperature, key.top_k
            )
            pf_args = [
                self.params,
                kv.cache,
                jnp.asarray(arr),
                jnp.asarray(pads),
                jnp.asarray(tables),
                jnp.asarray(seeds),
            ]
            if ix is not None:
                pf_args.append(jnp.asarray(ix))
            kv.cache, first = fn(*pf_args)
        first_np = np.asarray(first)
        tnow = _now()
        gen = [[int(first_np[i])] for i in range(n)]
        for i, r in enumerate(batch):
            r.first_token_at = tnow
            if r.t0 is not None:
                self._m_ttft.observe((tnow - r.t0) * 1e3)
            if r.trace is not None:
                r.trace.add(
                    "prefill", start=td, dur_s=tnow - td, group=gid,
                    row=r.row, prefix_len=L, suffix_bucket=pb,
                )
            if r.on_tokens is not None:
                try:
                    r.on_tokens([int(first_np[i])])
                except Exception:  # noqa: BLE001 — a dead client stays local
                    pass

        def emit(i, fresh):
            gen[i].extend(int(t) for t in fresh)
            if len(fresh) and batch[i].on_tokens is not None:
                try:
                    batch[i].on_tokens([int(t) for t in fresh])
                except Exception:  # noqa: BLE001
                    pass

        # per-row loop state: drafters over the FULL prompt (prefix
        # included — that's where the repetitive material usually is),
        # write frontier `pos`, generation index `start_g`. A configured
        # draft MODEL replaces the n-gram index with one batched drafter
        # whose own dense cache spans prefix + suffix bucket, so its
        # frontier (base + start_g - 1) coincides with the paged pos.
        drafter = None
        drafters: list = []
        if self._draft_module is not None:
            dP = L + pb
            dprompts = np.zeros((bb, dP), np.int32)
            dlens = np.ones((bb,), np.int64)
            for i, r in enumerate(batch):
                dprompts[i, dP - len(r.tokens):] = r.tokens
                dlens[i] = len(r.tokens)
            with self._lock:
                drafter = self._make_drafter(
                    dprompts, dlens, seeds,
                    temperature=key.temperature, top_k=key.top_k,
                )
        else:
            drafters = [
                NgramDrafter(batch[i].tokens + [int(first_np[i])])
                for i in range(n)
            ]
        tok = np.zeros((bb,), np.int32)
        tok[:n] = first_np[:n]
        pos = np.full((bb,), L + pb, np.int64)
        start_g = np.ones((bb,), np.int64)
        done = np.zeros((bb,), bool)
        remaining = np.zeros((bb,), np.int64)
        for i, r in enumerate(batch):
            remaining[i] = r.max_new - 1
            if key.eos_id is not None and first_np[i] == key.eos_id:
                # everything after a generated eos is pinned: emit the
                # rest host-side and retire the row
                emit(i, [int(key.eos_id)] * int(remaining[i]))
                remaining[i] = 0
        totals = {
            "proposed": 0, "accepted": 0, "accepted_judged": 0,
            "truncated": 0, "rollback": 0,
        }
        t_prev, window = _now(), 0
        while (remaining > 0).any():
            fed = np.empty((bb, K + 1), np.int32)
            fed[:, 0] = tok
            if drafter is not None:
                with self._lock:
                    fed[:, 1:] = drafter.propose(tok, start_g, K)
                for b in range(bb):
                    if not (b < n and remaining[b] > 0):
                        fed[b, 1:] = tok[b]
            else:
                for b in range(bb):
                    fed[b, 1:] = (
                        drafters[b].propose(K)
                        if b < n and remaining[b] > 0
                        else tok[b]
                    )
            frontier = int(pos[:n].max()) + K + 1
            kv.ensure_pages(plans[:n], upto_slot=frontier)
            tables = kv.tables(plans, bb, n_pages)
            with self._lock:
                fn = self._spec_verify_paged_fn(
                    bb, K, L, n_pages, key.temperature, key.top_k,
                    key.eos_id,
                )
                vf_args = [
                    self.params,
                    kv.cache,
                    jnp.asarray(fed),
                    jnp.asarray(done),
                    jnp.asarray(pads),
                    jnp.asarray(tables),
                    jnp.asarray(seeds),
                    jnp.asarray(pos, jnp.int32),
                    jnp.asarray(start_g, jnp.int32),
                ]
                if ix is not None:
                    vf_args.append(jnp.asarray(ix))
                kv.cache, targets, accept = fn(*vf_args)
            committed, done, remaining, eos_hit, delta = commit_window(
                fed, targets, accept, remaining, done, key.eos_id
            )
            for k in totals:
                totals[k] += delta[k]
            t_new = _now()
            for r in batch:
                if r.trace is not None:
                    # one verify-window span per window, with the window's
                    # accept accounting — the per-window decode/verify
                    # children the trace invariant sums
                    r.trace.add(
                        "verify", start=t_prev, dur_s=t_new - t_prev,
                        group=gid, row=r.row, window=window,
                        proposed=delta["proposed"],
                        accepted=delta["accepted"],
                        rollback=delta["rollback"],
                    )
            t_prev, window = t_new, window + 1
            for i in range(n):
                toks = committed[i]
                if not len(toks):
                    continue
                emit(i, toks)
                if drafter is None:
                    drafters[i].extend(toks)
                tok[i] = toks[-1]
                pos[i] += len(toks)
                start_g[i] += len(toks)
                if eos_hit[i] and remaining[i] > 0:
                    emit(i, [int(key.eos_id)] * int(remaining[i]))
                    remaining[i] = 0
        self._spec_observe(totals)
        th0 = _now()
        try:
            with self._lock:  # harvest donates the pool buffer too
                kv.harvest(
                    [
                        (r.tokens, r.kv_plan, int(pads[i]), r.trace)
                        for i, r in enumerate(batch)
                    ]
                )
        except Exception:  # noqa: BLE001 — cache warmth must not fail rows
            pass
        th1 = _now()
        for i, r in enumerate(batch):
            if r.trace is not None:
                r.trace.add(
                    "kv_harvest", start=th0, dur_s=th1 - th0, group=gid,
                    row=r.row,
                )
            r.finish(result=list(r.tokens) + gen[i][: r.max_new])
        self._m_requests.inc(n)

    def _paged_prefill_fn(self, bb, pb, prefix_len, n_pages, temperature, top_k):
        from ..models.generate import jit_paged_prefill

        key = ("paged_prefill", bb, pb, prefix_len, n_pages, temperature, top_k)
        return self._cached(
            key,
            lambda: jit_paged_prefill(
                self.module,
                kv_layout=self._kv.layout,
                prefix_len=prefix_len,
                temperature=temperature,
                top_k=top_k,
            ),
        )

    def _paged_chunk_fn(
        self, bb, steps, prefix_len, n_pages, temperature, top_k, eos_id
    ):
        from ..models.generate import jit_paged_chunk

        key = (
            "paged_chunk", bb, steps, prefix_len, n_pages, temperature,
            top_k, eos_id,
        )
        return self._cached(
            key,
            lambda: jit_paged_chunk(
                self.module,
                steps=steps,
                kv_layout=self._kv.layout,
                prefix_len=prefix_len,
                temperature=temperature,
                top_k=top_k,
                eos_id=eos_id,
            ),
        )

    def _prefill_chunk_fn(self, final, temperature, top_k):
        """Chunked-prefill slice program (ISSUE 14). pos/prefix_lens/pad
        are traced and jit re-specializes per chunk width internally, so
        ONE cache entry per (final, sampling) signature serves every
        prefix length, bucket, and slice of every request."""
        from ..models.generate import jit_paged_prefill_chunk

        if not final:
            temperature, top_k = 0.0, None  # non-final slices never sample
        key = ("prefill_chunk", final, temperature, top_k)
        return self._cached(
            key,
            lambda: jit_paged_prefill_chunk(
                self.module,
                kv_layout=self._kv.layout,
                temperature=temperature,
                top_k=top_k,
                final=final,
            ),
        )

    def _paged_step_fn(self, temperature, top_k, eos_id):
        """Unified single-step decode program (ISSUE 14): per-row pos/g/
        prefix_lens are traced, so every plain paged row — whatever its
        buckets or cached prefix — shares one cache entry per sampling
        signature."""
        from ..models.generate import jit_paged_step

        key = ("paged_step", temperature, top_k, eos_id)
        return self._cached(
            key,
            lambda: jit_paged_step(
                self.module,
                kv_layout=self._kv.layout,
                temperature=temperature,
                top_k=top_k,
                eos_id=eos_id,
            ),
        )

    def _execute_group_paged(self, batch: list[PendingRequest]):
        """Paged decode for one coalesced group: prefill the suffixes
        through the page tables (the shared prefix is already in the
        pool), then stream sampled tokens out in `stream_chunk_tokens`
        chunks. Tokens are byte-identical to the dense bucketed path
        (pinned by tests/test_kv_pages.py); what changes is memory — one
        fixed pool instead of per-group worst-case caches — and latency
        shape: the first token leaves after prefill, not after the whole
        decode. The pool cache buffer is DONATED through every prefill/
        chunk call, so decode updates it in place."""
        import time as _time

        import jax.numpy as jnp
        import numpy as np

        kv = self._kv
        key = batch[0].key
        n = len(batch)
        inject("serving.slow", rows=n)
        inject("serving.decode", rows=n)
        qnow = _time.monotonic()
        for r in batch:
            self._observe_queue_wait(r, max(0.0, qnow - r.enqueued_at))
        self._m_occupancy.observe(n)
        self._m_batches.inc()
        gid, td = self._trace_group(batch)
        L, pb, nb = key.prefix_len, key.prompt_bucket, key.new_bucket
        pt = kv.layout.page_tokens
        n_pages = kv.layout.pages_for(L + pb + nb - 1)
        bb = batch_bucket(n, max(n, self.config.max_batch))
        plans = [r.kv_plan for r in batch] + [None] * (bb - n)
        traces = [r.trace for r in batch]
        arr = np.zeros((bb, pb), np.int32)
        pads = np.full((bb,), pb - 1, np.int32)  # dummy rows: length-1 suffix
        seeds = np.zeros((bb,), np.int32)
        for i, r in enumerate(batch):
            sfx = r.tokens[L:]
            arr[i, pb - len(sfx):] = sfx
            pads[i] = pb - len(sfx)
            seeds[i] = r.seed
        # prefill: writes suffix KV into slots [L, L+pb) of each row's pages
        ix = self._adapter_ix(batch, bb)
        kv.ensure_pages(plans[:n], upto_slot=L + pb, traces=traces)
        tables = kv.tables(plans, bb, n_pages)
        with self._lock:
            # land any queued spill restores before the prefill reads
            # restored prefix pages (ISSUE 17)
            kv.flush_restores()
            fn = self._paged_prefill_fn(
                bb, pb, L, n_pages, key.temperature, key.top_k
            )
            pf_args = [
                self.params,
                kv.cache,
                jnp.asarray(arr),
                jnp.asarray(pads),
                jnp.asarray(tables),
                jnp.asarray(seeds),
            ]
            if ix is not None:
                pf_args.append(jnp.asarray(ix))
            kv.cache, first = fn(*pf_args)
        first_np = np.asarray(first)
        tnow = _now()
        gen = [[int(first_np[i])] for i in range(n)]
        for i, r in enumerate(batch):
            r.first_token_at = tnow
            if r.t0 is not None:
                self._m_ttft.observe((tnow - r.t0) * 1e3)
            if r.trace is not None:
                r.trace.add(
                    "prefill", start=td, dur_s=tnow - td, group=gid,
                    row=r.row, prefix_len=L, suffix_bucket=pb,
                )
            if r.on_tokens is not None:
                try:
                    r.on_tokens([int(first_np[i])])
                except Exception:  # noqa: BLE001 — a dead client stays local
                    pass
        # chunked decode: fixed-steps compiles, traced pos/start_g
        tok = first
        done = jnp.zeros((bb,), bool)
        pos, g, remaining = L + pb, 1, nb - 1
        chunk_cap = max(1, int(self.config.stream_chunk_tokens))
        early_eos = False
        t_prev, window = tnow, 0
        while remaining > 0:
            steps = min(chunk_cap, remaining)
            kv.ensure_pages(plans[:n], upto_slot=pos + steps, traces=traces)
            tables = kv.tables(plans, bb, n_pages)
            with self._lock:
                fn = self._paged_chunk_fn(
                    bb, steps, L, n_pages, key.temperature, key.top_k,
                    key.eos_id,
                )
                ck_args = [
                    self.params,
                    kv.cache,
                    tok,
                    done,
                    jnp.asarray(pads),
                    jnp.asarray(tables),
                    jnp.asarray(seeds),
                    jnp.asarray(pos, jnp.int32),
                    jnp.asarray(g, jnp.int32),
                ]
                if ix is not None:
                    ck_args.append(jnp.asarray(ix))
                kv.cache, toks, done = fn(*ck_args)
            toks_np = np.asarray(toks)
            for i, r in enumerate(batch):
                already = len(gen[i])
                fresh = toks_np[i, : max(0, r.max_new - already)].tolist()
                gen[i].extend(int(t) for t in fresh)
                if fresh and r.on_tokens is not None:
                    try:
                        r.on_tokens([int(t) for t in fresh])
                    except Exception:  # noqa: BLE001
                        pass
            tok = toks[:, -1]
            t_new = _now()
            for r in batch:
                if r.trace is not None:
                    # contiguous per-window decode spans: each starts where
                    # the previous ended, so the children partition the
                    # decode region exactly (the /tracez sum invariant)
                    r.trace.add(
                        "decode", start=t_prev, dur_s=t_new - t_prev,
                        group=gid, row=r.row, window=window, steps=steps,
                    )
            t_prev, window = t_new, window + 1
            self._spec_tick_plain(steps)
            pos += steps
            g += steps
            remaining -= steps
            if key.eos_id is not None and bool(np.asarray(done)[:n].all()):
                # every real row has latched eos: the remaining samples
                # would all be pinned to eos_id — emit them host-side
                early_eos = True
                break
            if all(r.cancelled for r in batch):
                # every client vanished mid-stream (ISSUE 16): stop
                # decoding rows nobody will read — finish() below still
                # releases their pages through on_finish
                break
        if early_eos:
            for i, r in enumerate(batch):
                short = r.max_new - len(gen[i])
                if short > 0:
                    fresh = [int(key.eos_id)] * short
                    gen[i].extend(fresh)
                    if r.on_tokens is not None:
                        try:
                            r.on_tokens(fresh)
                        except Exception:  # noqa: BLE001
                            pass
        # index each row's page-aligned prompt prefix BEFORE finish()
        # releases the pages — the next request with this prompt prefix
        # skips its prefill
        th0 = _now()
        try:
            with self._lock:  # harvest donates the pool buffer too
                kv.harvest(
                    [
                        (r.tokens, r.kv_plan, int(pads[i]), r.trace)
                        for i, r in enumerate(batch)
                    ]
                )
        except Exception:  # noqa: BLE001 — cache warmth must not fail rows
            pass
        th1 = _now()
        for i, r in enumerate(batch):
            if r.trace is not None:
                r.trace.add(
                    "kv_harvest", start=th0, dur_s=th1 - th0, group=gid,
                    row=r.row,
                )
            r.finish(result=list(r.tokens) + gen[i][: r.max_new])
        self._m_requests.inc(n)

    def _execute_beam_group(self, batch: list[PendingRequest]):
        """Beam requests keep the legacy exact-shape program (beam search
        has no pad/per-row-seed path); same-shape requests still stack."""
        import jax.numpy as jnp
        import numpy as np

        key = batch[0].key
        arr = np.stack([np.asarray(r.tokens, np.int32) for r in batch])
        self._m_occupancy.observe(len(batch))
        self._m_batches.inc()
        gid, td = self._trace_group(batch)
        with self._lock:
            fn = self._decode_fn(
                arr.shape[0], arr.shape[1], key.new_bucket,
                key.temperature, key.top_k, key.eos_id,
                num_beams=key.num_beams, length_penalty=key.length_penalty,
            )
            out = np.asarray(
                fn(self.params, jnp.asarray(arr), jnp.asarray(0, jnp.int32))
            )
        for i, r in enumerate(batch):
            r.finish(result=out[i].tolist())
            if r.trace is not None:
                r.trace.add(
                    "decode",
                    start=td,
                    dur_s=(r.finished_t or _now()) - td,
                    group=gid,
                    rows=len(batch),
                    row=r.row,
                    num_beams=key.num_beams,
                )
        self._m_requests.inc(len(batch))

    def _bind_mesh(self) -> None:
        """Re-assert the decode mesh in THIS thread. set_current_mesh is
        thread-local (parallel.ring), so the mesh bound while restoring in
        the loading thread is invisible to the coalescer's worker thread
        and to HTTP handler threads — without this, constrain() silently
        degrades to no-ops at trace time and decode runs unsharded."""
        if self._mesh is not None:
            from ..parallel.ring import current_mesh, set_current_mesh

            if current_mesh() is not self._mesh:
                set_current_mesh(self._mesh)

    def _dispatch_group(self, batch: list[PendingRequest]):
        self._bind_mesh()
        key = batch[0].key
        if key.num_beams > 1:
            self._execute_beam_group(batch)
        elif self._kv is not None and batch[0].kv_plan is not None:
            if key.speculate:
                self._execute_group_paged_spec(batch)
            else:
                self._execute_group_paged(batch)
        elif key.speculate:
            self._execute_group_spec(batch)
        else:
            self._execute_group(batch)

    def generate(self, body: dict) -> dict:
        """Synchronous single-caller path (also the CLI/test surface):
        validates, then runs inline — bucketed when batching is enabled,
        the legacy exact-shape program otherwise."""
        import jax.numpy as jnp
        import numpy as np

        self._bind_mesh()
        req = self._validate(body)
        arr = req["arr"]
        if req["num_beams"] > 1 or not self.config.batching:
            with self._lock:
                fn = self._decode_fn(
                    arr.shape[0],
                    arr.shape[1],
                    req["max_new"],
                    req["temperature"],
                    req["top_k"],
                    req["eos_id"],
                    num_beams=req["num_beams"],
                    length_penalty=req["length_penalty"],
                )
                out = fn(
                    self.params,
                    jnp.asarray(arr),
                    jnp.asarray(req["seed"], jnp.int32),
                )
            self._m_requests.inc(arr.shape[0])
            return {"tokens": np.asarray(out).tolist()}
        rows = self._make_requests(req)
        by_key: dict = {}
        for r in rows:
            by_key.setdefault(r.key, []).append(r)
        for group in by_key.values():
            self._dispatch_group(group)
        return {"tokens": [r.result for r in rows]}

    def handle_request(
        self, body: dict, request_id: Optional[str] = None
    ) -> dict:
        """HTTP-path entry: producer side of the coalescer. Falls back to
        the synchronous path for beams and when batching is off. End-to-end
        latency (validate → all rows scattered back) lands in the
        request-seconds histogram either way, carrying the request id as
        its exemplar; the per-request trace lands in the tail sampler."""
        rid = request_id or new_trace_id()
        trace = self._new_trace(rid)
        t0 = _now()
        error: Optional[BaseException] = None
        try:
            return self._handle_request(body, rid=rid, trace=trace)
        except BaseException as e:
            error = e
            raise
        finally:
            dur = _now() - t0
            self._m_latency.observe(dur, exemplar=rid)
            self._observe_body_latency(body, dur)
            self._finish_trace(trace, error)

    def _handle_request(
        self,
        body: dict,
        rid: Optional[str] = None,
        trace: Optional[RequestTrace] = None,
    ) -> dict:
        if self._draining:
            self._observe("shed", reason="draining")
            raise ServerClosingError(
                "server draining: admission closed", reason="draining"
            )
        req = self._validate(body)
        req["rid"], req["trace"] = rid, trace
        if trace is not None and req.get("tenant"):
            trace.attrs["tenant"] = req["tenant"]
        if (
            self._coalescer is None
            or self._coalescer._thread is None
            or req["num_beams"] > 1
        ):
            # synchronous path: decode starts immediately, so the only
            # deadline that can already be lost is the admission one
            if req["deadline"] is not None and time.monotonic() >= req["deadline"]:
                self._observe("shed", reason="deadline")
                raise ShedError(
                    "deadline already expired at admission",
                    reason="deadline",
                )
            if trace is not None:
                t_sync = _now()
                trace.add("admission", start=trace.t0, dur_s=t_sync - trace.t0)
                out = self.generate(body)
                trace.add("decode", start=t_sync, dur_s=_now() - t_sync)
                return out
            return self.generate(body)
        rows = self._make_requests(req)
        submitted = []
        try:
            for r in rows:
                r.submitted_t = _now()
                self._coalescer.submit(r)
                submitted.append(r)
        except ShedError:
            # multi-row body partially admitted: the unsubmitted rows give
            # their page reservations and adapter pins back NOW (nobody
            # will finish them); then wait out the admitted rows (they
            # resolve normally, results discarded, on_finish releases
            # their resources) and report the shed — the client retries
            # the whole body
            for r in rows:
                if r not in submitted:
                    self._release_row(r)
            for r in submitted:
                r.done.wait(self.config.request_timeout_s)
            raise
        if trace is not None:
            # validate + kv plan + submit, measured from the root start to
            # the first row entering the queue — the piece of latency the
            # queue_wait/decode spans don't cover
            first = rows[0].submitted_t if rows else trace.t0
            trace.add("admission", start=trace.t0, dur_s=first - trace.t0)
        timeout = self.config.request_timeout_s
        for r in rows:
            if not r.done.wait(timeout):
                raise TimeoutError(
                    f"decode did not complete within {timeout:.0f}s"
                )
        # disaggregated handoff (ISSUE 20): prefill-role rows resolve
        # with a sentinel — page set exported, transfer not yet run.
        # Ship on this handler thread. Every ship landed → retryable 503
        # (reason kv_handoff_done): the router replays the body on the
        # decode replica, which adopts the pages and continues. Any ship
        # failed → monolithic fallback: re-run those rows locally (the
        # prefix is warm here; the decode side's partial adoptions are
        # just evictable cache warmth, never a leak).
        pending_handoff = [
            r for r in rows if isinstance(r.error, _HandoffPrefillDone)
        ]
        if pending_handoff:
            shipped = [self._handoff_ship(r) for r in pending_handoff]
            if all(shipped):
                self._observe("shed", reason="kv_handoff_done")
                raise ShedError(
                    "prefill complete: decode replica owns the KV",
                    reason="kv_handoff_done",
                )
            for r in pending_handoff:
                r2 = self._handoff_rerun(req, r.row)
                r.result, r.error = r2.result, None
        for r in rows:
            if r.error is not None:
                raise r.error
        out = {"tokens": [r.result for r in rows]}
        if trace is not None:
            # scatter-back: last row finishing → response body assembled
            done_t = max(
                (r.finished_t for r in rows if r.finished_t is not None),
                default=_now(),
            )
            trace.add("stream_flush", start=done_t, dur_s=_now() - done_t)
        return out

    # ----------------------------------------------------------- streaming
    def stream_request(self, body: dict, request_id: Optional[str] = None):
        """Streaming producer path (`POST /generate?stream=1`): yields one
        event dict per decoded chunk as the paged decode emits it —
        `{"row": i, "tokens": [...]}` with newly generated tokens (the
        client reconstructs the full row as prompt + concatenated chunks,
        which equals the non-streamed result token for token), then
        `{"row": i, "done": true}` (or `{"row": i, "error": msg}`) per
        row, then `{"done": true}`. Admission errors (400/503/504) raise
        before the first event so the HTTP layer can still set a status
        code; later failures become in-band error events."""
        rid = request_id or new_trace_id()
        trace = self._new_trace(rid, stream=True)
        t0 = _now()
        error: Optional[BaseException] = None
        try:
            yield from self._stream_request(body, rid=rid, trace=trace)
        except BaseException as e:
            error = e
            raise
        finally:
            dur = _now() - t0
            self._m_latency.observe(dur, exemplar=rid)
            self._observe_body_latency(body, dur)
            self._finish_trace(trace, error)

    def _stream_request(
        self,
        body: dict,
        rid: Optional[str] = None,
        trace: Optional[RequestTrace] = None,
    ):
        import queue as _queue

        if self._draining:
            self._observe("shed", reason="draining")
            raise ServerClosingError(
                "server draining: admission closed", reason="draining"
            )
        req = self._validate(body)
        req["rid"], req["trace"] = rid, trace
        if trace is not None and req.get("tenant"):
            trace.attrs["tenant"] = req["tenant"]
        if (
            self._kv is None
            or self._coalescer is None
            or self._coalescer._thread is None
            or req["num_beams"] > 1
        ):
            # no incremental decode on this path: degrade to one terminal
            # chunk per row (same event shape, no partial delivery)
            out = self._handle_request(body, rid=rid, trace=trace)
            for i, row in enumerate(out["tokens"]):
                yield {"row": i, "tokens": row[len(req["arr"][i]) :]}
                yield {"row": i, "done": True}
            yield {"done": True}
            return
        rows = self._make_requests(req)
        if rid is not None:
            self._stream_rows[rid] = rows
        events: _queue.Queue = _queue.Queue()
        for i, r in enumerate(rows):
            r.on_tokens = (
                lambda toks, i=i: events.put({"row": i, "tokens": toks})
            )
            release = r.on_finish  # _release_plan, set by _make_requests

            def _finished(req_row, i=i, release=release):
                if release is not None:
                    release(req_row)
                events.put(
                    {"row": i, "done": True}
                    if req_row.error is None
                    else {"row": i, "error": str(req_row.error)}
                )

            r.on_finish = _finished
        try:
            submitted = []
            try:
                for r in rows:
                    r.submitted_t = _now()
                    self._coalescer.submit(r)
                    submitted.append(r)
            except ShedError:
                for r in rows:
                    if r not in submitted:
                        self._release_row(r)
                for r in submitted:
                    r.done.wait(self.config.request_timeout_s)
                raise
            if trace is not None:
                first = rows[0].submitted_t if rows else trace.t0
                trace.add("admission", start=trace.t0, dur_s=first - trace.t0)
            pending = len(rows)
            while pending:
                try:
                    ev = events.get(timeout=self.config.request_timeout_s)
                except _queue.Empty:
                    raise TimeoutError(
                        f"decode did not complete within "
                        f"{self.config.request_timeout_s:.0f}s"
                    ) from None
                evs = [ev]
                if "error" in ev and isinstance(
                    rows[ev["row"]].error, _HandoffPrefillDone
                ):
                    # disaggregated handoff (ISSUE 20): ship the
                    # exported page set now; shipped → in-band error
                    # frame (router replays on the decode replica with
                    # trim), failed → local fallback events instead
                    evs = self._handoff_stream_resolve(
                        req, rows[ev["row"]]
                    )
                for ev in evs:
                    if "done" in ev or "error" in ev:
                        pending -= 1
                    yield ev
            if trace is not None:
                done_t = max(
                    (r.finished_t for r in rows if r.finished_t is not None),
                    default=_now(),
                )
                trace.add("stream_flush", start=done_t, dur_s=_now() - done_t)
            yield {"done": True}
        finally:
            if rid is not None:
                self._stream_rows.pop(rid, None)

    def cancel_stream(self, rid: str) -> int:
        """Cancel a live streamed request's unfinished rows — called by
        the HTTP layer on a broken pipe. The coalescer/step scheduler
        notice the flag at their next sweep, evict the rows, and
        `on_finish` releases their KV pages. Returns the number of rows
        cancelled; increments `serving_client_disconnects_total` once
        per request that still had live rows."""
        rows = self._stream_rows.get(rid)
        if not rows:
            return 0
        n = 0
        for r in rows:
            if not r.done.is_set():
                r.cancel()
                n += 1
        if n:
            self._m_client_disconnects.inc()
            self._observe("client_disconnect", request_id=rid, rows=n)
        return n

    # --------------------------------------------------------- readiness
    def readiness(self) -> tuple[bool, str]:
        """(ready, reason) for /readyz. Not ready while draining/stopped,
        or when `expected_devices` is set and the live device count has
        regressed (degraded slice). Result lands on the serving.ready
        gauge either way."""
        if self._httpd is None or self._draining:
            ready, reason = False, "draining" if self._draining else "stopped"
        elif self.expected_devices is not None:
            ready, reason = self._device_health()
        else:
            ready, reason = True, "ok"
        self._m_ready.set(1 if ready else 0)
        return ready, reason

    def _device_health(self) -> tuple[bool, str]:
        """check_slice(expected_devices=N), cached for 5s — the all-reduce
        probe is cheap but not per-scrape cheap."""
        now = time.monotonic()
        if self._health_cache is not None and now - self._health_cache[0] < 5.0:
            return self._health_cache[1], self._health_cache[2]
        from ..runtime.health import SliceHealthError, check_slice

        try:
            info = check_slice(expected_devices=self.expected_devices)
            out = (True, f"ok ({info['devices']} devices)")
        except SliceHealthError as e:
            out = (False, f"degraded slice: {e}")
        self._health_cache = (now, out[0], out[1])
        return out

    @staticmethod
    def _ms(v) -> Optional[float]:
        return round(v * 1e3, 3) if v is not None else None

    def kv_heads(self) -> dict:
        """GET /kvz payload: the prefix chain hashes this replica holds
        (in-pool or spilled), keyed by the pool's page size so the router
        hashes request prompts the same way."""
        if self._kv is None:
            return {
                "enabled": False,
                "pageTokens": 0,
                "heads": [],
                "role": self.config.role,
            }
        return {
            "enabled": self._kv.prefix is not None,
            "pageTokens": self._kv.layout.page_tokens,
            "heads": self._kv.advertised_heads(),
            "role": self.config.role,
        }

    def stats(self) -> dict:
        batches = rows = 0
        resilience = {}
        if self._coalescer is not None:
            c = self._coalescer
            batches = c.batches_run
            rows = c.rows_run
            resilience = {
                "queue_depth": c.depth,
                "max_queue": c.max_queue,
                "shed": int(self._m_shed.value),
                "deadline_exceeded": int(self._m_deadline.value),
                "worker_restarts": c.worker_restarts,
                "breaker": c.breaker.state if c.breaker else "disabled",
                "draining": self._draining,
            }
        lat = self._m_latency.summary()
        queue = self._m_queue_wait.summary()
        kv = {"enabled": False}
        if self._kv is not None:
            ttft = self._m_ttft.summary()
            kv = {
                "enabled": True,
                **self._kv.stats(),
                "ttft_ms": {
                    k: round(ttft[k], 3) if ttft[k] is not None else None
                    for k in ("p50", "p95", "p99", "mean")
                },
            }
        proposed = int(self._m_spec_proposed.value)
        accepted = int(self._m_spec_accepted.value)
        truncated = int(self._m_spec_truncated.value)
        speculation = {
            "enabled": bool(self.config.speculate),
            "draft_tokens": int(self.config.draft_tokens),
            "proposed": proposed,
            "accepted": accepted,
            "truncated": truncated,
            "rollbacks": int(self._m_spec_rollback.value),
            # raw rate counts only COMMITTED accepts; the corrected rate
            # re-credits accepted drafts truncated by maxNewTokens, which
            # is what the adaptive controller steers on (PR 8 deflation
            # fix — they diverge only near the end of a request's budget)
            "accept_rate": (
                round(accepted / proposed, 4) if proposed else None
            ),
            "accept_rate_raw": (
                round(accepted / proposed, 4) if proposed else None
            ),
            "accept_rate_corrected": (
                round((accepted + truncated) / proposed, 4)
                if proposed else None
            ),
            "adaptive": bool(self._spec_controller is not None),
            "effective_k": int(self._m_spec_effective_k.value),
            "auto_disabled": bool(
                self._spec_controller is not None
                and self._spec_controller.auto_disabled
            ),
            "draft_model": (
                None
                if self._draft_module is None
                else {
                    "n_layers": int(self._draft_module.cfg.n_layers),
                    "derived": bool(self._draft_derived),
                }
            ),
        }
        if self._spec_controller is not None:
            speculation["controller"] = self._spec_controller.stats()
        quant = {
            "enabled": bool(self.config.quantize),
            "bytes_saved": int(self._quant_bytes_saved),
        }
        chunked = {"enabled": False}
        c = self._coalescer
        if c is not None and hasattr(c, "steps_run"):
            st = self._m_step_tokens.summary()
            chunked = {
                "enabled": True,
                "prefill_chunk_tokens": int(self.config.prefill_chunk_tokens),
                "max_step_tokens": int(self.config.max_step_tokens),
                "steps": c.steps_run,
                # cumulative seconds of those steps by phase (telemetry
                # clock); all but `fetch` is the host's own time
                "phase_s": {
                    phase: round(float(m.value), 6)
                    for phase, m in self._m_phase.items()
                },
                "prefill_only_steps": c.prefill_only_steps,
                "classic_forced_steps": c.classic_forced_steps,
                "prefill_chunks": int(self._m_prefill_chunks.value),
                "prefill_queue_depth": c.prefill_queue_depth,
                "evicted_midflight": c.evicted_midflight,
                "step_tokens": {
                    k: round(st[k], 3) if st[k] is not None else None
                    for k in ("p50", "p95", "p99", "mean")
                },
            }
        tracing = {
            "enabled": bool(self.config.trace),
            **self.traces.stats(),
        }
        slo = (
            self.slo_engine.to_dict()
            if self.slo_engine is not None
            else {"enabled": False, "breached": False, "slos": []}
        )
        if self.flight_recorder is not None:
            slo["flight_recorder_dumps"] = self.flight_recorder.dumps
        mesh = {"enabled": False, "devices": 1}
        if self._mesh is not None:
            mesh = {
                "enabled": self._mesh.devices.size > 1,
                "devices": int(self._mesh.devices.size),
                "axes": {k: int(v) for k, v in self._mesh.shape.items()},
            }
        tenancy = {"enabled": self._tenancy is not None}
        if self._tenancy is not None:
            tenancy["tenants"] = self._tenancy.snapshot()
        if self._adapter_registry is not None:
            tenancy["adapters"] = self._adapter_registry.stats()
            if self._adapter_spill is not None:
                tenancy["adapter_spill"] = self._adapter_spill.stats()
        # disaggregated handoff (ISSUE 20): in-transit exports count as
        # held work (they gate drain), never as leaked pages — adopted
        # and harvested pages are prefix-cache entries, already covered
        # by the prefix_held discount in the kv block above
        handoff = {
            "role": self.config.role,
            "inflight": int(self._handoff_inflight),
            "exports": int(self._m_handoff_exports.value),
            "imports": int(self._m_handoff_imports.value),
            "rejected": int(self._m_handoff_rejected.value),
            "fallbacks": int(self._m_handoff_fallbacks.value),
            "leases": self._lease_table.stats(),
        }
        xla = compiles.mirror(self.telemetry)
        return {
            "device": self.device_info(),
            "tenancy": tenancy,
            "handoff": handoff,
            "mesh": mesh,
            "kv": kv,
            "chunked": chunked,
            "speculation": speculation,
            "quant": quant,
            **resilience,
            "batching": bool(self.config.batching),
            "compile_count": self.compile_count,
            # XLA programs as JAX reports them (this process, ever):
            # traced, lowered, and compiled or loaded from the cache
            "xla": {**xla, "recent": compiles.recent()},
            "compile_cache": {
                "hits": int(self._m_cache_hits.value),
                "misses": int(self._m_cache_misses.value),
            },
            "requests": self.requests_served,
            "batches": batches,
            "mean_batch_occupancy": round(rows / batches, 3) if batches else None,
            # percentiles estimated from the same histograms /metricsz
            # exposes — the two surfaces stay in sync by construction
            "latency_ms": {
                k: self._ms(lat[k]) for k in ("p50", "p95", "p99", "mean")
            },
            "queue_wait_ms": {
                k: self._ms(queue[k]) for k in ("p50", "p95", "p99", "mean")
            },
            "prompt_buckets": list(self._prompt_ladder),
            "max_new_buckets": list(self._new_ladder),
            "max_batch": self.config.max_batch,
            "max_wait_ms": self.config.max_wait_ms,
            "tracing": tracing,
            "slo": slo,
        }

    def device_info(self) -> dict:
        """Platform, device kind and ids, the attention backend the model
        resolves to here, and the devices' live memory counters."""
        from ..utils.jax_platform import device_memory

        return {**self._device_report, "memory": device_memory(self._devices)}

    # ------------------------------------------------------------ http
    def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Start serving in a background thread; returns the bound port."""
        server = self
        if self._coalescer is not None:
            self._coalescer.start()

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _send(
                self,
                code: int,
                payload: dict,
                headers: dict = None,
                rid: str = None,
            ):
                if rid is not None:
                    payload = {**payload, "requestId": rid}
                    headers = {**(headers or {}), "X-Request-Id": rid}
                self._send_raw(
                    code,
                    json.dumps(payload).encode(),
                    "application/json",
                    headers,
                )

            def _send_raw(
                self, code: int, data: bytes, ctype: str, headers: dict = None
            ):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                path, _, query = self.path.partition("?")
                if path == "/healthz":
                    self._send(
                        200,
                        {
                            "status": "ok",
                            "model": server.model_name,
                            "step": server.step,
                        },
                    )
                elif path == "/readyz":
                    ready, reason = server.readiness()
                    # role rides readiness (ISSUE 20): the router learns
                    # pool membership from the same probe it already
                    # makes — on BOTH the 200 and the 503 body, so a
                    # draining prefill replica still advertises its pool
                    self._send(
                        200 if ready else 503,
                        {
                            "ready": ready,
                            "reason": reason,
                            "role": server.config.role,
                        },
                    )
                elif path == "/statsz":
                    self._send(200, server.stats())
                elif path == "/metricsz":
                    # scrape-time refresh: the router's JSQ signal must
                    # reflect the queue NOW, not the last admission event
                    if server._coalescer is not None:
                        server._m_queue_depth.set(server._coalescer.depth)
                        pq = getattr(
                            server._coalescer, "prefill_queue_depth", None
                        )
                        if pq is not None:
                            server._m_prefill_queue.set(pq)
                    compiles.mirror(server.telemetry)
                    self._send_raw(
                        200,
                        server.telemetry.render_prometheus().encode(),
                        "text/plain; version=0.0.4",
                    )
                elif path == "/kvz":
                    # prefix-affinity advertisement (ISSUE 17): the chain
                    # hashes this replica can serve warm — resident
                    # PrefixCache entries plus restorable spilled ones.
                    # The router's directory scrapes this alongside
                    # /metricsz; staleness is harmless (a stale hit just
                    # re-prefills or restores, never serves wrong bytes)
                    self._send(200, server.kv_heads())
                elif path == "/tracez":
                    self._tracez(query)
                elif path == "/sloz":
                    self._send(
                        200,
                        server.slo_engine.to_dict()
                        if server.slo_engine is not None
                        else {"enabled": False, "breached": False, "slos": []},
                    )
                elif path == "/queryz":
                    # metrics history (ISSUE 18): rate/trend queries over
                    # the sampler's tiered store; 503 when history is off
                    code, payload = queryz_payload(server.history, query)
                    self._send(code, payload)
                else:
                    self._send(404, {"error": f"no route {self.path}"})

            def _kv_import(self):
                """POST /kv_import (ISSUE 20): adopt a prefill replica's
                exported page set. Status taxonomy the exporter's
                HandoffClient keys on: 400 malformed bytes or hash-chain
                mismatch (final — identical bytes never do better), 409
                stale epoch (a newer owner exists: stand down), 503 shed
                with reason kv_handoff (pool full, nothing evictable),
                200 with the adopted page count. Every abort path
                releases the lease so a higher-epoch retry proceeds."""
                from .handoff import (
                    HandoffError,
                    StaleLeaseError,
                    payload_from_wire,
                )
                from ..models.kv_pages import page_hashes

                rid = (
                    self.headers.get("X-Handoff-Id") or ""
                ).strip()[:128] or None
                server._m_http.inc()
                kv = server._kv
                if kv is None or kv.prefix is None:
                    server._m_handoff_rejected.inc()
                    self._send(
                        400,
                        {
                            "error": "no prefix cache on this replica",
                            "reason": "rejected",
                        },
                        rid=rid,
                    )
                    return
                try:
                    epoch = int(self.headers.get("X-Handoff-Epoch") or 0)
                except ValueError:
                    epoch = 0
                lease = None
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    data = self.rfile.read(n)
                    # chaos: a fault in the import window must adopt
                    # fully or not at all, and the exporter must see a
                    # clean failure it can retry or fall back from
                    inject(
                        "serving.kv_import",
                        rid=rid, epoch=epoch, size=len(data),
                    )
                    payload = payload_from_wire(data)
                    want = page_hashes(
                        list(payload.tokens),
                        kv.layout.page_tokens,
                        kv.prefix.hash_fn,
                    )
                    if list(want) != list(payload.hashes):
                        raise HandoffError(
                            "content-hash chain does not match the "
                            "prompt tokens"
                        )
                    lease = server._lease_table.acquire(
                        rid or "anon", epoch
                    )
                    adopted = kv.adopt_pages(payload)
                    if server._lease_table.complete(lease):
                        self._send(
                            200, {"adopted_pages": int(adopted)}, rid=rid
                        )
                    else:
                        # preempted mid-adopt by a higher epoch: the
                        # newer owner's adoption is authoritative; ours
                        # is just evictable cache warmth. Tell this
                        # exporter to stand down.
                        server._m_handoff_rejected.inc()
                        self._send(
                            409,
                            {
                                "error": "preempted mid-adopt",
                                "reason": "stale_epoch",
                            },
                            rid=rid,
                        )
                except StaleLeaseError as e:
                    server._m_handoff_rejected.inc()
                    self._send(
                        409,
                        {"error": str(e), "reason": "stale_epoch"},
                        rid=rid,
                    )
                except HandoffError as e:
                    server._m_handoff_rejected.inc()
                    self._send(
                        400,
                        {"error": str(e), "reason": "rejected"},
                        rid=rid,
                    )
                except ShedError as e:
                    if lease is not None:
                        server._lease_table.release(lease)
                    server._m_http_err.inc()
                    self._send(
                        503,
                        {"error": str(e), "reason": e.reason},
                        headers={
                            "Retry-After": str(
                                max(1, int(round(e.retry_after_s)))
                            )
                        },
                        rid=rid,
                    )
                except Exception as e:  # noqa: BLE001 — surface, don't kill
                    if lease is not None:
                        server._lease_table.release(lease)
                    server._m_http_err.inc()
                    self._send(
                        500,
                        {
                            "error": f"{type(e).__name__}: {e}",
                            "reason": "internal",
                        },
                        rid=rid,
                    )

            def _tracez(self, query: str):
                # ONE /tracez contract across every surface that owns a
                # ring (replica here, router): shared in telemetry
                from ..telemetry.tracing import tracez_payload

                code, payload = tracez_payload(server.traces, query)
                self._send(code, payload)

            def _stream(self, body, rid):
                """SSE response: one `data: <json>` frame per event from
                stream_request(). The first event is pulled BEFORE headers
                go out so admission failures still map to real status
                codes; mid-stream failures become an in-band error frame
                (the 200 is already on the wire). Every frame carries the
                request id — SSE clients can't reread response headers
                after a reconnect."""
                gen = server.stream_request(body, request_id=rid)
                first = next(gen)  # admission errors raise here
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-store")
                self.send_header("Connection", "close")
                self.send_header("X-Request-Id", rid)
                self.end_headers()
                import itertools

                try:
                    for ev in itertools.chain((first,), gen):
                        ev = {**ev, "requestId": rid}
                        self.wfile.write(
                            b"data: " + json.dumps(ev).encode() + b"\n\n"
                        )
                        self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError):
                    # client went away mid-stream (ISSUE 16): cancel the
                    # request's rows so the scheduler evicts them at its
                    # next sweep and their KV pages come back promptly,
                    # instead of decoding to completion for nobody
                    server.cancel_stream(rid)
                except Exception as e:  # noqa: BLE001 — in-band, then close
                    try:
                        self.wfile.write(
                            b"data: "
                            + json.dumps(
                                {"error": str(e), "requestId": rid}
                            ).encode()
                            + b"\n\n"
                        )
                    except OSError:
                        pass

            def do_POST(self):
                path, _, query = self.path.partition("?")
                if path == "/kv_import":
                    self._kv_import()
                    return
                if path != "/generate":
                    self._send(404, {"error": f"no route {self.path}"})
                    return
                # accept-or-assign: the caller's id (bounded, for log
                # correlation across services) or a fresh 16-hex one
                rid = (
                    (self.headers.get("X-Request-Id") or "").strip()[:128]
                    or new_trace_id()
                )
                want_stream = "stream=1" in query.split("&")
                server._m_http.inc()
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    # X-Tenant pass-through (ISSUE 19): the router (and
                    # any proxy) forwards the tenant as a header; the
                    # body field wins when both are present
                    hdr_tenant = (
                        self.headers.get("X-Tenant") or ""
                    ).strip()[:128]
                    if hdr_tenant and isinstance(body, dict):
                        body.setdefault("tenant", hdr_tenant)
                    # X-Handoff-Target/-Epoch (ISSUE 20): the router
                    # names the decode replica the same way — header →
                    # body field, body wins when both are present
                    hdr_target = (
                        self.headers.get("X-Handoff-Target") or ""
                    ).strip()[:256]
                    if hdr_target and isinstance(body, dict):
                        body.setdefault("handoffTarget", hdr_target)
                        body.setdefault(
                            "handoffEpoch",
                            self.headers.get("X-Handoff-Epoch") or 0,
                        )
                    if want_stream and server.config.stream:
                        self._stream(body, rid)
                    else:
                        self._send(
                            200,
                            server.handle_request(body, request_id=rid),
                            rid=rid,
                        )
                except ShedError as e:
                    # shed at admission: never queued, safe to retry later
                    server._m_http_err.inc()
                    self._send(
                        503,
                        {"error": str(e), "reason": e.reason},
                        headers={
                            "Retry-After": str(
                                max(1, int(round(e.retry_after_s)))
                            )
                        },
                        rid=rid,
                    )
                except DeadlineExceededError as e:
                    server._m_http_err.inc()
                    self._send(
                        504,
                        {"error": str(e), "reason": "deadline_exceeded"},
                        rid=rid,
                    )
                except ServingError as e:
                    # 400s are client errors: excluded from the
                    # availability SLO's bad-event counter
                    self._send(
                        400,
                        {"error": str(e), "reason": "invalid_request"},
                        rid=rid,
                    )
                except TimeoutError as e:
                    server._m_http_err.inc()
                    self._send(
                        504,
                        {"error": str(e), "reason": "timeout"},
                        rid=rid,
                    )
                except Exception as e:  # noqa: BLE001 — surface, don't kill
                    server._m_http_err.inc()
                    self._send(
                        500,
                        {
                            "error": f"{type(e).__name__}: {e}",
                            "reason": "internal",
                        },
                        rid=rid,
                    )

        self._httpd = _Httpd((host, port), Handler)
        self._draining = False
        self._m_ready.set(1)
        if self.slo_engine is not None:
            self.slo_engine.start()
        if self.history_sampler is not None:
            self.history_sampler.start()
        if self.sentinel is not None:
            self.sentinel.start()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self._httpd.server_address[1]

    def stop(self, drain_grace_s: Optional[float] = None):
        """Graceful drain, then shutdown (SIGTERM semantics):

        1. flip /readyz to 503 and close admission (new requests shed
           with a terminal 503 ServerClosingError);
        2. let the decode worker flush queued + in-flight groups for up
           to the drain budget (config.drainGraceS unless overridden) —
           the HTTP server keeps running so their responses go out;
        3. fail whatever remains fast, then stop the HTTP server."""
        grace = (
            self.config.drain_grace_s
            if drain_grace_s is None
            else drain_grace_s
        )
        self._draining = True
        self._m_ready.set(0)
        # drain honesty (ISSUE 20): an export in flight holds pages the
        # leak accounting cannot see yet — a drain must not report idle
        # while a page set is on the wire. Bounded by the same grace.
        self._handoff_idle.wait(timeout=max(0.0, grace))
        if self.slo_engine is not None:
            self.slo_engine.stop()
        if self.sentinel is not None:
            self.sentinel.stop()
        if self.history_sampler is not None:
            self.history_sampler.stop()
        if self._coalescer is not None:
            self._coalescer.stop(drain_s=grace)
            # a restarted server gets a fresh worker (and breaker)
            self._coalescer = self._make_coalescer()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        self._draining = False  # a restarted server admits again


def _pow2_at_least(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class _StepEngine:
    """serving.steps.StepEngine over ModelServer's jitted programs.

    Per-row device state (suffix array, write frontier, sampling cursor,
    stream buffer, drafter) lives on `req.step` — the RowStep the
    scheduler reads plus engine-private fields — so a watchdog restart
    carries nothing over. Everything here is byte-identity-preserving
    against the classic one-shot group path (pinned by
    tests/test_serving_chunked.py): chunk slices feed the SAME
    left-padded suffix layout, the final slice samples fold_in(key, 0),
    and decode steps sample fold_in(key, g) exactly like the scan body
    of `jit_paged_chunk`."""

    def __init__(self, server: ModelServer):
        self._s = server

    # --------------------------------------------------------------- protocol
    def phase(self, name: str):
        """The scheduler's own phases (it reads no clock): timed here."""
        return self._s._phase(name)

    def supports(self, r: PendingRequest) -> bool:
        return (
            self._s._kv is not None
            and r.kv_plan is not None
            and r.key.num_beams == 1
        )

    def begin(self, r: PendingRequest) -> None:
        import time as _time

        import numpy as np

        from .steps import RowStep

        s = self._s
        kv = s._kv
        key = r.key
        st = RowStep(
            phase="prefill",
            cost=(key.draft_tokens + 1) if key.speculate else 1,
        )
        L, pb, nb = key.prefix_len, key.prompt_bucket, key.new_bucket
        sfx = r.tokens[L:]
        arr = np.zeros((1, pb), np.int32)
        if sfx:
            arr[0, pb - len(sfx):] = sfx
        st.arr = arr
        st.pad = pb - len(sfx)
        st.L, st.pb, st.nb = L, pb, nb
        # tables are padded with the scratch page up to a power-of-2 width
        # so rows with different page counts share compiled step shapes;
        # reads beyond a row's own span are masked dead (exact 0.0 after
        # softmax), so the wider window is byte-identical
        st.n_pages = kv.layout.pages_for(L + pb + nb - 1)
        st.wt = _pow2_at_least(st.n_pages)
        st.chunk_w = min(max(1, int(s.config.prefill_chunk_tokens)), pb)
        st.off = 0
        st.next_chunk = min(st.chunk_w, pb)
        st.gid = next(s._group_seq)
        st.window = 0
        st.gen = None
        st.buf = []
        qnow = _time.monotonic()  # same clock as PendingRequest.enqueued_at
        s._observe_queue_wait(r, max(0.0, qnow - r.enqueued_at))
        st.t_prev = _now()
        if r.trace is not None:
            r.trace.set_group(st.gid)
            start = r.submitted_t if r.submitted_t is not None else r.trace.t0
            r.trace.add(
                "queue_wait",
                start=start,
                dur_s=st.t_prev - start,
                group=st.gid,
                row=r.row,
            )
        r.step = st

    def prefill_chunk(self, r: PendingRequest) -> int:
        import jax.numpy as jnp
        import numpy as np

        s = self._s
        kv = s._kv
        st = r.step
        key = r.key
        width = min(st.chunk_w, st.pb - st.off)
        final = st.off + width >= st.pb
        # chaos point: a fault here lands BETWEEN prefill chunks — the
        # row fails with its page table half-built, and on_finish must
        # return every page (tests/test_serving_chunked.py chaos case)
        inject("serving.prefill_chunk", row=r.row, off=st.off)
        with s._phase("prepare", lane="prefill_chunk"):
            kv.ensure_pages(
                [r.kv_plan], upto_slot=st.L + st.off + width, traces=[r.trace]
            )
            table = kv.tables([r.kv_plan], 1, st.wt)
            chunk = st.arr[:, st.off : st.off + width]
            pads = np.asarray([st.pad], np.int32)
            pls = np.asarray([st.L], np.int32)
            seeds = np.asarray([r.seed], np.int32)
        program = "prefill_slice_final" if final else "prefill_slice"
        with s._phase("dispatch", program=program, tokens=width), s._lock:
            # land any queued spill restores before the chunk reads
            # restored prefix pages (ISSUE 17)
            kv.flush_restores()
            fn = s._prefill_chunk_fn(final, key.temperature, key.top_k)
            pc_args = [
                s.params,
                kv.cache,
                jnp.asarray(chunk),
                jnp.asarray(pads),
                jnp.asarray(pls),
                jnp.asarray(table),
                jnp.asarray(seeds),
                jnp.asarray(st.L + st.off, jnp.int32),
            ]
            if s._adapter_slots_active:
                pc_args.append(
                    jnp.asarray([r.adapter_slot], jnp.int32)
                )
            out = fn(*pc_args)
            if final:
                kv.cache, first = out
            else:
                kv.cache = out
        st.off += width
        s._m_prefill_chunks.inc()
        tnow = _now()
        if r.trace is not None:
            r.trace.add(
                "prefill",
                start=st.t_prev,
                dur_s=tnow - st.t_prev,
                group=st.gid,
                row=r.row,
                chunk_off=st.off - width,
                chunk_tokens=width,
                prefix_len=st.L,
                suffix_bucket=st.pb,
            )
        st.t_prev = tnow
        if not final:
            st.next_chunk = min(st.chunk_w, st.pb - st.off)
            return width
        # prefill boundary: the first sampled token leaves NOW — TTFT no
        # longer waits for co-resident prompts (the whole point)
        with s._phase("fetch"):
            first_i = int(np.asarray(first)[0])
        with s._phase("emit"):
            r.first_token_at = tnow
            if r.t0 is not None:
                s._m_ttft.observe((tnow - r.t0) * 1e3)
            st.gen = [first_i]
            st.decode_t0 = tnow
            self._emit(r, [first_i])
            if key.eos_id is not None and first_i == key.eos_id:
                # everything after a generated eos is pinned: finish host-side
                fill = [int(key.eos_id)] * (r.max_new - 1)
                st.gen.extend(fill)
                self._emit(r, fill)
                self._finish_row(r)
            elif r.max_new <= 1:
                self._finish_row(r)
            elif not self._maybe_handoff(r, first_i):
                st.tok = first_i
                st.done = False
                st.pos = st.L + st.pb
                st.g = 1
                if key.speculate:
                    # step lanes recompose every step, so a batched draft
                    # cache cannot follow a row between lanes: each row gets
                    # its own B=1 drafter (prompt padded to the bucketed
                    # width, so draft compiles stay ladder-bounded)
                    if s._draft_module is not None:
                        import numpy as _np

                        dP = st.L + st.pb
                        dprompt = _np.zeros((1, dP), _np.int32)
                        dprompt[0, dP - len(r.tokens):] = r.tokens
                        with s._lock:
                            st.drafter = s._make_drafter(
                                dprompt, [len(r.tokens)], [r.seed],
                                temperature=key.temperature, top_k=key.top_k,
                            )
                        st.model_draft = True
                    else:
                        from ..models.spec_decode import NgramDrafter

                        st.drafter = NgramDrafter(r.tokens + [first_i])
                        st.model_draft = False
                    st.remaining = r.max_new - 1
                st.phase = "decode"
        return width

    def _maybe_handoff(self, r: PendingRequest, first_i: int) -> bool:
        """Prefill-role exit (ISSUE 20). With a decode target named by
        the router, harvest the finished page set into the prefix cache
        (the refs that keep it alive through the transfer window),
        capture the host bytes, and resolve the row with the
        `_HandoffPrefillDone` sentinel — the HTTP handler thread runs
        the transfer, never this worker. Returns False (fall through to
        local decode) when no target was named, the prompt spans less
        than one full page, or the capture fails for any reason:
        monolithic decode is always the graceful degradation."""
        s = self._s
        if not r.handoff_target or s.config.role != "prefill":
            return False
        kv = s._kv
        st = r.step
        t0 = _now()
        try:
            # chaos: a fault in the capture window degrades to local
            # decode — the row must still complete, byte-identical
            inject(
                "serving.kv_export",
                rid=r.request_id, row=r.row, phase="capture",
            )
            with s._lock:
                kv.harvest([(r.tokens, r.kv_plan, int(st.pad), r.trace)])
                payload = kv.export_prefix(r.tokens)
        except Exception:  # noqa: BLE001 — capture is best-effort
            payload = None
        if payload is None:
            # a handoff-targeted request completing by local monolithic
            # decode IS a fallback, whatever killed the capture
            s._m_handoff_fallbacks.inc()
            return False
        from .handoff import payload_to_wire

        r.handoff_payload = payload_to_wire(payload)
        st.phase = "done"
        if r.trace is not None:
            r.trace.add(
                "kv_export", start=t0, dur_s=_now() - t0, group=st.gid,
                row=r.row, pages=len(payload.pages),
            )
        r.finish(error=_HandoffPrefillDone(first_i))
        return True

    def lanes(self, rows: list) -> list[list]:
        """Plain rows share one compiled step program per sampling
        signature (pos/g/prefix_lens are traced); speculative rows need
        the verify window's static shape, so their lanes key on
        (draft_tokens, prefix_len) too. Lanes split at max_batch."""
        groups: dict = {}
        for r in rows:
            k = r.key
            if k.speculate:
                lane_key = (
                    "spec", k.draft_tokens, k.prefix_len, k.temperature,
                    k.top_k, k.eos_id,
                )
            else:
                lane_key = ("plain", k.temperature, k.top_k, k.eos_id)
            groups.setdefault(lane_key, []).append(r)
        mb = max(1, int(self._s.config.max_batch))
        out = []
        for g in groups.values():
            for i in range(0, len(g), mb):
                out.append(g[i : i + mb])
        return out

    def decode(self, lane: list) -> int:
        if lane[0].key.speculate:
            return self._decode_spec(lane)
        return self._decode_plain(lane)

    # -------------------------------------------------------------- internals
    def _emit(self, r: PendingRequest, toks: list) -> None:
        # len(), not truthiness: spec windows pass numpy slices
        if len(toks) and r.on_tokens is not None:
            try:
                r.on_tokens([int(t) for t in toks])
            except Exception:  # noqa: BLE001 — a dead client stays local
                pass

    def _finish_row(self, r: PendingRequest) -> None:
        s = self._s
        kv = s._kv
        st = r.step
        st.phase = "done"
        tnow = _now()
        if (
            r.trace is not None
            and not r.key.speculate
            and st.gen is not None
            and len(st.gen) > 1
        ):
            r.trace.add(
                "decode",
                start=st.decode_t0,
                dur_s=tnow - st.decode_t0,
                group=st.gid,
                row=r.row,
                steps=len(st.gen) - 1,
            )
        th0 = _now()
        try:
            with s._lock:  # harvest donates the pool buffer too
                kv.harvest([(r.tokens, r.kv_plan, int(st.pad), r.trace)])
        except Exception:  # noqa: BLE001 — cache warmth must not fail rows
            pass
        th1 = _now()
        if r.trace is not None:
            r.trace.add(
                "kv_harvest", start=th0, dur_s=th1 - th0, group=st.gid,
                row=r.row,
            )
        r.finish(result=list(r.tokens) + st.gen[: r.max_new])
        s._m_requests.inc(1)

    def _decode_plain(self, lane: list) -> int:
        import jax.numpy as jnp
        import numpy as np

        s = self._s
        kv = s._kv
        key0 = lane[0].key
        n = len(lane)
        inject("serving.slow", rows=n)
        inject("serving.decode", rows=n)
        with s._phase("prepare", lane="decode", rows=n):
            bb = batch_bucket(n, max(n, s.config.max_batch))
            wt = max(r.step.wt for r in lane)
            tok = np.zeros((bb,), np.int32)
            done = np.ones((bb,), bool)  # dummy rows: latched done
            pads = np.zeros((bb,), np.int32)
            pls = np.zeros((bb,), np.int32)
            seeds = np.zeros((bb,), np.int32)
            pos = np.zeros((bb,), np.int64)
            g = np.ones((bb,), np.int64)
            plans = [r.kv_plan for r in lane] + [None] * (bb - n)
            for i, r in enumerate(lane):
                st = r.step
                tok[i] = st.tok
                done[i] = st.done
                pads[i] = st.pad
                pls[i] = st.L
                seeds[i] = r.seed
                pos[i] = st.pos
                g[i] = st.g
            kv.ensure_pages(
                plans[:n],
                upto_slot=int(pos[:n].max()) + 1,
                traces=[r.trace for r in lane],
            )
            tables = kv.tables(plans, bb, wt)
            ix = s._adapter_ix(lane, bb)
        with s._phase("dispatch", program="decode_step", rows=n), s._lock:
            fn = s._paged_step_fn(key0.temperature, key0.top_k, key0.eos_id)
            step_args = [
                s.params,
                kv.cache,
                jnp.asarray(tok),
                jnp.asarray(done),
                jnp.asarray(pads),
                jnp.asarray(pls),
                jnp.asarray(tables),
                jnp.asarray(seeds),
                jnp.asarray(pos.astype(np.int32)),
                jnp.asarray(g.astype(np.int32)),
            ]
            if ix is not None:
                step_args.append(jnp.asarray(ix))
            kv.cache, nxt, done_out = fn(*step_args)
        with s._phase("fetch"):
            nxt = np.asarray(nxt)
            done_out = np.asarray(done_out)
        with s._phase("emit"):
            chunk_cap = max(1, int(s.config.stream_chunk_tokens))
            for i, r in enumerate(lane):
                st = r.step
                t = int(nxt[i])
                st.gen.append(t)
                st.buf.append(t)
                st.tok = t
                st.done = bool(done_out[i])
                st.pos += 1
                st.g += 1
                if key0.eos_id is not None and t == key0.eos_id:
                    fill = [int(key0.eos_id)] * (r.max_new - len(st.gen))
                    st.gen.extend(fill)
                    st.buf.extend(fill)
                    self._emit(r, st.buf)
                    st.buf = []
                    self._finish_row(r)
                elif len(st.gen) >= r.max_new:
                    self._emit(r, st.buf)
                    st.buf = []
                    self._finish_row(r)
                elif len(st.buf) >= chunk_cap:
                    # same emission cadence as the classic chunk loop: one
                    # event per stream_chunk_tokens decoded tokens
                    self._emit(r, st.buf)
                    st.buf = []
        s._spec_tick_plain(1)
        return n

    def _decode_spec(self, lane: list) -> int:
        import jax.numpy as jnp
        import numpy as np

        from ..models.spec_decode import commit_window

        s = self._s
        kv = s._kv
        key0 = lane[0].key
        n = len(lane)
        K = int(key0.draft_tokens)
        L = int(key0.prefix_len)
        inject("serving.slow", rows=n)
        inject("serving.decode", rows=n)
        with s._phase("prepare", lane="spec_decode", rows=n):
            bb = batch_bucket(n, max(n, s.config.max_batch))
            wt = max(r.step.wt for r in lane)
            fed = np.zeros((bb, K + 1), np.int32)
            pads = np.zeros((bb,), np.int32)
            seeds = np.zeros((bb,), np.int32)
            pos = np.zeros((bb,), np.int64)
            start_g = np.ones((bb,), np.int64)
            done = np.zeros((bb,), bool)
            remaining = np.zeros((bb,), np.int64)
            plans = [r.kv_plan for r in lane] + [None] * (bb - n)
            for i, r in enumerate(lane):
                st = r.step
                fed[i, 0] = st.tok
                if st.remaining <= 0:
                    fed[i, 1:] = st.tok
                elif getattr(st, "model_draft", False):
                    with s._lock:
                        fed[i, 1:] = st.drafter.propose([st.tok], [st.g], K)[0]
                else:
                    fed[i, 1:] = st.drafter.propose(K)
                pads[i] = st.pad
                seeds[i] = r.seed
                pos[i] = st.pos
                start_g[i] = st.g
                done[i] = st.done
                remaining[i] = st.remaining
            frontier = int(pos[:n].max()) + K + 1
            kv.ensure_pages(
                plans[:n], upto_slot=frontier, traces=[r.trace for r in lane]
            )
            tables = kv.tables(plans, bb, wt)
            ix = s._adapter_ix(lane, bb)
        with s._phase(
            "dispatch", program="spec_verify_paged", rows=n
        ), s._lock:
            fn = s._spec_verify_paged_fn(
                bb, K, L, wt, key0.temperature, key0.top_k, key0.eos_id
            )
            sv_args = [
                s.params,
                kv.cache,
                jnp.asarray(fed),
                jnp.asarray(done),
                jnp.asarray(pads),
                jnp.asarray(tables),
                jnp.asarray(seeds),
                jnp.asarray(pos.astype(np.int32)),
                jnp.asarray(start_g.astype(np.int32)),
            ]
            if ix is not None:
                sv_args.append(jnp.asarray(ix))
            kv.cache, targets, accept = fn(*sv_args)
        # commit_window reads targets and accept on the host: the wait
        # for the device is here
        with s._phase("fetch"):
            committed, done2, remaining2, eos_hit, delta = commit_window(
                fed, targets, accept, remaining, done, key0.eos_id
            )
        with s._phase("emit"):
            s._spec_observe(delta)
            tnow = _now()
            for i, r in enumerate(lane):
                st = r.step
                if r.trace is not None:
                    r.trace.add(
                        "verify",
                        start=st.t_prev,
                        dur_s=tnow - st.t_prev,
                        group=st.gid,
                        row=r.row,
                        window=st.window,
                        proposed=delta["proposed"],
                        accepted=delta["accepted"],
                        rollback=delta["rollback"],
                    )
                st.t_prev = tnow
                st.window += 1
                toks = committed[i]
                if len(toks):
                    # classic spec cadence: each window's committed tokens
                    # are one streamed event
                    st.gen.extend(int(t) for t in toks)
                    self._emit(r, toks)
                    if not getattr(st, "model_draft", False):
                        # the ModelDrafter's cache frontier is a function of
                        # st.g alone; only the n-gram index needs the text
                        st.drafter.extend(toks)
                    st.tok = int(toks[-1])
                    st.pos += len(toks)
                    st.g += len(toks)
                st.done = bool(done2[i])
                st.remaining = int(remaining2[i])
                if eos_hit[i] and st.remaining > 0:
                    fill = [int(key0.eos_id)] * st.remaining
                    st.gen.extend(fill)
                    self._emit(r, fill)
                    st.remaining = 0
                if st.remaining <= 0:
                    self._finish_row(r)
        return n * (K + 1)
