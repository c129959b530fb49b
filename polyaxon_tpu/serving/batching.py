"""Shape bucketing + cross-request coalescing for the serving fast path.

Two cooperating layers (ISSUE 2):

**Bucketing** — a realistic traffic mix has one distinct `(prompt_len,
max_new)` per request; jitting one decode program per exact shape means
20-40 s of XLA compile per novel request and an LRU that thrashes under
varied lengths. Instead prompts are LEFT-padded up to a small geometric
ladder of widths (models/generate.py masks the pad out of attention and
offsets rotary positions per row), so the compile count is O(#buckets),
not O(#distinct shapes).

**Coalescing** — a single-request decode leaves the accelerator idle
between dispatches. `DecodeCoalescer` runs ONE worker thread fed by a
queue: the HTTP handlers are producers only, and compatible requests
(same bucket + sampling signature; seed is a per-row runtime argument)
merge into one batched decode of up to `max_batch` rows, waiting at most
`max_wait_ms` for stragglers. Responses scatter back to the waiting
handler threads through per-request events. Single-threaded jax
tracing/execution holds by construction.

Plus the resilience layer (ISSUE 5) — goodput under overload and failure:

**Bounded queue + deadline-aware admission** — `submit` sheds with
`ShedError` (HTTP 503 + Retry-After at the server) when the queue holds
`max_queue` unfinished requests, when the request's deadline has already
expired, or when the circuit breaker is open; the worker loop drops
expired requests BEFORE spending a decode slot on them
(`DeadlineExceededError`, HTTP 504). All deadline math uses
`time.monotonic` (enforced by scripts/lint_telemetry.py).

**Watchdog + circuit breaker** — the single worker thread is supervised:
a crash fails its in-flight group fast (`WorkerCrashError`) and the loop
restarts over the surviving queue. `breaker_threshold` consecutive
decode failures trip a `CircuitBreaker` that sheds admissions until a
half-open probe succeeds.

**Graceful drain** — `stop(drain_s=...)` closes admission, lets the
worker flush queued + in-flight groups within the budget, then fails the
remainder with a terminal `ServerClosingError`.

This module is deliberately free of jax: the ladder math and the worker
loop are unit-testable with a fake executor (tests/test_serving_batch.py,
tests/test_serving_resilience.py). Chaos points `serving.worker` (here)
and `serving.decode`/`serving.slow` (server._execute_group) hook the
seeded FaultPlan machinery into this path.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Callable, Optional

from ..chaos.injector import inject
from ..telemetry import now as _metrics_now


# ------------------------------------------------------------------ errors
class ServingError(RuntimeError):
    """Client-visible serving failure. The HTTP layer maps the base class
    to 400 (validation); the resilience subclasses below carry their own
    status codes."""


class ShedError(ServingError):
    """Request shed at admission — queue full, breaker open, deadline
    already expired, or the server is draining. HTTP 503 + Retry-After:
    the request was NOT queued and is safe to retry elsewhere."""

    def __init__(
        self,
        message: str,
        *,
        reason: str = "overload",
        retry_after_s: float = 1.0,
    ):
        super().__init__(message)
        self.reason = reason
        self.retry_after_s = retry_after_s


class ServerClosingError(ShedError):
    """Terminal: the server is draining or shutting down. Queued requests
    failed with this will never be retried here — go elsewhere."""

    def __init__(
        self, message: str = "server shutting down", *, reason: str = "closing"
    ):
        super().__init__(message, reason=reason, retry_after_s=1.0)


class DeadlineExceededError(ServingError):
    """The request's deadline passed while it waited — dropped before a
    decode slot was spent on it (goodput, not throughput). HTTP 504."""


class ClientDisconnectedError(ServingError):
    """The streaming client went away mid-request (broken pipe). Nobody
    is listening for the result: the row is cancelled, its KV pages and
    decode slot released promptly. Never surfaces over HTTP — there is
    no client left to see it."""


class WorkerCrashError(RuntimeError):
    """The decode worker died with this group in flight; the watchdog
    failed the group fast and restarted the worker. NOT a ServingError:
    the client sees a 500, the request may or may not be safe to retry."""


def bucket_ladder(lo: int, hi: int, factor: int = 2) -> tuple[int, ...]:
    """Geometric ladder lo, lo*factor, ... capped at (and including) hi."""
    if hi < 1:
        raise ValueError(f"ladder upper bound must be >= 1, got {hi}")
    lo = max(1, min(lo, hi))
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= factor
    out.append(hi)
    return tuple(out)


def bucket_for(n: int, ladder: tuple[int, ...]) -> Optional[int]:
    """Smallest bucket >= n, or None when n exceeds the ladder."""
    for b in ladder:
        if b >= n:
            return b
    return None


def choose_buckets(
    prompt_len: int,
    max_new: int,
    prompt_ladder: tuple[int, ...],
    new_ladder: tuple[int, ...],
    seq_len: int,
) -> tuple[int, int]:
    """(prompt_bucket, new_bucket) for one request, guaranteeing
    prompt_bucket + new_bucket <= seq_len (the KV-cache size).

    Rounding both up can overflow the cache even when the raw request
    fits (seq 64, len 40 → bucket 64, new 16 → 80): prefer the largest
    ladder pair that fits, and degrade to the EXACT request shape as the
    escape hatch — correctness first, compile-sharing when possible."""
    nb = bucket_for(max_new, new_ladder) or max_new
    pb = None
    for b in prompt_ladder:
        if b >= prompt_len and b + nb <= seq_len:
            pb = b
            break
    if pb is None:
        pb = prompt_len
        if pb + nb > seq_len:
            nb = max_new
    return pb, nb


def batch_bucket(n: int, max_batch: int) -> int:
    """Round a partial batch up to the next power of two <= max_batch, so
    compiled batch shapes also form a small ladder (padded rows are dummy
    length-1 prompts whose outputs are dropped)."""
    b = 1
    while b < n and b < max_batch:
        b *= 2
    return min(b, max_batch)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Knobs for the serving fast path (schemas.run_kinds.V1ServingSpec
    carries the same fields in the stored spec; CLI flags override)."""

    max_batch: int = 8
    max_wait_ms: float = 5.0
    prompt_buckets: Optional[tuple[int, ...]] = None  # None = auto ladder
    max_new_buckets: Optional[tuple[int, ...]] = None
    batching: bool = True
    request_timeout_s: float = 600.0
    # resilience layer (ISSUE 5)
    max_queue: int = 64  # unfinished requests admitted before shedding
    default_deadline_ms: Optional[float] = None  # per-request deadlineMs wins
    drain_grace_s: float = 5.0  # stop(): budget to flush in-flight work
    breaker_threshold: int = 5  # consecutive decode failures → open
    breaker_cooldown_s: float = 1.0  # open → half-open probe interval
    # paged KV cache + streaming (ISSUE 6); kv_pool_pages=None → dense path
    kv_page_tokens: int = 128
    kv_pool_pages: Optional[int] = None
    prefix_cache: bool = True
    stream: bool = True  # expose POST /generate?stream=1
    stream_chunk_tokens: int = 8  # decode steps per emitted chunk
    # fast decode path (ISSUE 8): self-speculative verify windows of
    # draft_tokens n-gram drafts (byte-identical outputs; sampled
    # requests must carry per-row seeds, which serving always does) and
    # int8 weight-only quantized projections (quantize-on-load)
    speculate: bool = False
    draft_tokens: int = 4
    quantize: bool = False
    # adaptive speculation + KV quantization (ISSUE 15):
    # draft_model — `draft:` sub-config overrides for a real draft model
    #   (normalized sorted (key, value) tuple, hashable; None = n-gram
    #   drafter). Weights derive by layer truncation of the served
    #   checkpoint when the draft keeps the base widths.
    # adaptive_draft — accept-rate-driven per-group K: ramp up on high
    #   corrected accept rate, shrink toward 1, auto-disable (plain
    #   decode) when speculation measurably loses, re-probe on a logical
    #   cadence. Requires speculate.
    # kv_quant — "int8" stores the paged pool as int8 payload + per-slot
    #   f32 scales (~2-3.5x rows per HBM byte); requires kv_pool_pages.
    draft_model: Optional[tuple[tuple[str, object], ...]] = None
    adaptive_draft: bool = False
    kv_quant: str = "none"
    # per-request tracing (ISSUE 9): build RequestTrace span trees and
    # retain them in the server's tail-sampling TraceRing (/tracez)
    trace: bool = True
    trace_ring: int = 256  # recent-window capacity of the ring
    # tensor-parallel decode (ISSUE 10): named 2-D mesh sizes as sorted
    # (axis, size) pairs — hashable because the config is frozen and part
    # of compile-cache identity; None = single-chip (pre-mesh behaviour).
    # Only `batch`/`model` are legal (parallel.mesh.DECODE_AXES).
    mesh_axes: Optional[tuple[tuple[str, int], ...]] = None
    # chunked prefill + step scheduling (ISSUE 14): slice prefill into
    # prefill_chunk_tokens-wide device steps interleaved with decode so a
    # long prompt cannot monopolize the worker (head-of-line blocking).
    # max_step_tokens bounds the tokens any single device step may touch
    # (all decode rows + at most one prefill slice) — the admission
    # budget. Requires the paged KV path (kv_pool_pages); the dense path
    # ignores these and keeps the classic group coalescer.
    chunked_prefill: bool = False
    prefill_chunk_tokens: int = 64
    max_step_tokens: int = 256
    # tiered prefix spill (ISSUE 17): evicted PrefixCache entries demote
    # to a host-RAM tier (spill_ram_bytes budget) and overflow to
    # CRC-framed segment files under spill_dir (spill_dir_bytes budget;
    # None = unbounded); a prefix hit on a spilled entry restores pages
    # into the pool instead of re-prefilling. Requires kv_pool_pages +
    # prefix_cache; int8 kv_quant halves spilled bytes in both tiers.
    spill_ram_bytes: Optional[int] = None
    spill_dir: Optional[str] = None
    spill_dir_bytes: Optional[int] = None
    # multi-tenant serving (ISSUE 19): named LoRA adapters hot-swapped
    # into the stacked slot params (serving/adapters.py) and per-tenant
    # admission contracts (serving/tenancy.py).
    # adapters — sorted (name, source) pairs; source is an .npz path or
    #   "seed:<int>". Requires lora_rank > 0 on the served model.
    # tenants — sorted TenantSpec pair-tuples (tenancy.normalize_tenants);
    #   each may bind an adapter and carry outstanding/token caps + a
    #   fair-share weight.
    # adapter_slots — device-resident adapter slots BEYOND slot 0 (the
    #   checkpoint's own adapter); 0 = auto: one slot per configured
    #   adapter (no eviction until operators cap it lower).
    adapters: tuple = ()
    tenants: tuple = ()
    adapter_slots: int = 0
    # disaggregated pools (ISSUE 20): role splits serving across replica
    # pools. "both" (default) keeps the monolithic server; "prefill"
    # runs only chunked-prefill steps and ships the finished page set to
    # a decode replica over POST /kv_import (falling back to local
    # monolithic decode when no decode replica is routable or the import
    # sheds); "decode" advertises itself as an adoption target. The role
    # is pure dispatch advertisement — a decode replica still serves
    # whole requests, which is what makes prefill-pool outage degrade
    # gracefully instead of failing.
    role: str = "both"

    def ladders(self, seq_len: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
        pl = self.prompt_buckets or bucket_ladder(min(32, seq_len), seq_len)
        nl = self.max_new_buckets or bucket_ladder(min(16, seq_len), seq_len)
        return tuple(sorted(pl)), tuple(sorted(nl))


def normalize_mesh_axes(spec) -> Optional[tuple[tuple[str, int], ...]]:
    """dict or pair-tuple → the frozen `ServingConfig.mesh_axes` form.

    Sorted so `{'model': 2, 'batch': 1}` and `{'batch': 1, 'model': 2}`
    produce one compile-cache identity. jax-free on purpose: schemas and
    the CLI call this before any device exists."""
    if not spec:
        return None
    pairs = sorted(
        (str(ax), int(n))
        for ax, n in (spec.items() if hasattr(spec, "items") else spec)
    )
    for ax, n in pairs:
        if n < 1 and n != -1:
            raise ValueError(f"mesh axis {ax}={n}: sizes are >=1 (or -1)")
    if all(n == 1 for _, n in pairs):
        return None  # a 1x1 mesh IS the single-chip path; keep one identity
    return tuple(pairs)


def normalize_draft_model(spec) -> Optional[tuple[tuple[str, object], ...]]:
    """dict or pair-tuple of `draft:` overrides → the frozen hashable
    `ServingConfig.draft_model` form (sorted (key, value) pairs; list
    values become tuples). jax-free: schemas and the CLI call this before
    any device exists; field validation happens when the model builds.

    None means "no draft model"; an EMPTY dict/tuple means "auto" — build
    the draft from the model config's own `draft:` sub-config defaults —
    and normalizes to (), which is not-None so the server still builds."""
    if spec is None:
        return None
    pairs = spec.items() if hasattr(spec, "items") else spec
    return tuple(sorted(
        (str(k), tuple(v) if isinstance(v, list) else v) for k, v in pairs
    ))


@dataclasses.dataclass(frozen=True)
class GroupKey:
    """Requests coalesce iff their keys are equal: one compiled program and
    one batched dispatch per group. Seed is deliberately absent — it is a
    [B] runtime argument, not part of the signature."""

    prompt_bucket: int
    new_bucket: int
    temperature: float
    top_k: Optional[int]
    eos_id: Optional[int]
    num_beams: int = 1
    length_penalty: float = 1.0
    # paged path: rows in one group share the compiled (L, pb, nb) shape;
    # prompt_bucket then sizes the SUFFIX (tokens beyond the cached prefix)
    prefix_len: int = 0
    # decode mode (ISSUE 8): speculative verify windows compile a
    # different program shape, so groups must not mix modes — keying on
    # them keeps the buckets from fragmenting any further than that
    speculate: bool = False
    draft_tokens: int = 0  # verify window width - 1 (0 when not speculating)
    quantize: bool = False  # server-wide, but part of the mode signature


@dataclasses.dataclass
class PendingRequest:
    tokens: list  # [prompt_len] int token ids (single row)
    prompt_len: int
    max_new: int  # what the client asked for (<= key.new_bucket)
    seed: int
    key: GroupKey
    # absolute monotonic deadline; None = no deadline (wait forever)
    deadline: Optional[float] = None
    enqueued_at: float = dataclasses.field(default_factory=time.monotonic)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    result: Optional[list] = None  # row token ids on success
    error: Optional[BaseException] = None
    # paged KV + streaming (ISSUE 6)
    kv_plan: Optional[object] = None  # serving.kv.RowPlan when paged
    on_tokens: Optional[object] = None  # callable(list[int]) per decoded chunk
    on_finish: Optional[object] = None  # callable(req) on ANY terminal path
    t0: Optional[float] = None  # telemetry clock at admission (TTFT anchor)
    first_token_at: Optional[float] = None
    # per-request tracing (ISSUE 9): the HTTP request's identity and its
    # RequestTrace, shared by every row the body fanned into; `row`
    # disambiguates spans, `submitted_t`/`finished_t` (telemetry clock)
    # bound the queue_wait and stream_flush spans
    request_id: Optional[str] = None
    trace: Optional[object] = None  # telemetry.tracing.RequestTrace
    row: int = 0
    submitted_t: Optional[float] = None
    finished_t: Optional[float] = None
    # mid-stream client disconnect (ISSUE 16 satellite): the HTTP layer
    # flips this when the socket breaks; the coalescer/scheduler notice
    # at their next sweep and release the row's resources promptly
    cancelled: bool = False
    # multi-tenant serving (ISSUE 19): the tenant this row bills against
    # and the adapter slot its decode gathers (0 = the base adapter).
    # Runtime per-row state, deliberately NOT part of GroupKey: one
    # coalesced group mixes tenants.
    tenant: str = "default"
    adapter: str = ""  # adapter name, for registry release on finish
    adapter_slot: int = 0
    # disaggregated handoff (ISSUE 20): on a prefill-role server the
    # router names a decode replica in X-Handoff-Target; after the final
    # prefill slice the step engine exports the finished page set, parks
    # the wire bytes here, and resolves the row with a sentinel error so
    # the HTTP handler thread (not the decode worker) runs the transfer
    handoff_target: Optional[str] = None
    handoff_epoch: int = 0
    handoff_payload: Optional[bytes] = None

    def cancel(self) -> None:
        """Mark the row as abandoned by its client. Safe from any thread;
        a no-op once the row already resolved."""
        if not self.done.is_set():
            self.cancelled = True

    def finish(self, result=None, error=None):
        # idempotent: losing racers (deadline sweep vs decode completion)
        # must not clobber the outcome or re-fire resource release
        if self.done.is_set():
            return
        self.result = result
        self.error = error
        self.finished_t = _metrics_now()  # stream_flush span anchor
        if self.on_finish is not None:
            try:
                self.on_finish(self)
            except Exception:  # noqa: BLE001 — release must not mask result
                pass
        self.done.set()

    def expired(self, now: Optional[float] = None) -> bool:
        if self.deadline is None:
            return False
        return (time.monotonic() if now is None else now) >= self.deadline


class CircuitBreaker:
    """Consecutive-failure circuit breaker for the decode path.

    closed → (threshold consecutive failures) → open → (cooldown elapses,
    one probe admitted) → half_open → success closes / failure reopens.
    A probe that never reports an outcome (dropped on deadline, shed on
    shutdown) self-heals: another probe is admitted one cooldown later.

    `threshold <= 0` disables the breaker (always closed). Thread-safe:
    `allow()` runs on producer threads, `record_*` on the worker."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"
    _CODES = {CLOSED: 0, OPEN: 1, HALF_OPEN: 2}

    def __init__(
        self,
        threshold: int = 5,
        cooldown_s: float = 1.0,
        on_change: Optional[Callable[[int], None]] = None,
    ):
        self.threshold = int(threshold)
        self.cooldown_s = max(0.0, float(cooldown_s))
        self._on_change = on_change
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._failures = 0
        self._opened_at = 0.0
        self._probe_at = 0.0

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    @property
    def state_code(self) -> int:
        """0 closed, 1 open, 2 half-open — the serving.breaker_state gauge."""
        return self._CODES[self.state]

    def _set(self, state: str) -> None:
        # callers hold _lock
        if state == self._state:
            return
        self._state = state
        if self._on_change is not None:
            try:
                self._on_change(self._CODES[state])
            except Exception:  # noqa: BLE001 — telemetry must not break flow
                pass

    def allow(self) -> bool:
        """Admission gate. In OPEN, flips to HALF_OPEN and admits ONE
        probe once the cooldown has elapsed; in HALF_OPEN, re-admits a
        probe every cooldown until some probe reports an outcome."""
        if self.threshold <= 0:
            return True
        now = time.monotonic()
        with self._lock:
            if self._state == self.CLOSED:
                return True
            if self._state == self.OPEN:
                if now - self._opened_at >= self.cooldown_s:
                    self._set(self.HALF_OPEN)
                    self._probe_at = now
                    return True
                return False
            # HALF_OPEN: one probe per cooldown window
            if now - self._probe_at >= self.cooldown_s:
                self._probe_at = now
                return True
            return False

    def record_success(self) -> None:
        if self.threshold <= 0:
            return
        with self._lock:
            self._failures = 0
            self._set(self.CLOSED)

    def record_failure(self) -> None:
        if self.threshold <= 0:
            return
        now = time.monotonic()
        with self._lock:
            if self._state == self.HALF_OPEN:
                # the probe failed: straight back to open, restart cooldown
                self._failures = self.threshold
                self._opened_at = now
                self._set(self.OPEN)
                return
            self._failures += 1
            if self._failures >= self.threshold:
                self._opened_at = now
                self._set(self.OPEN)


class DecodeCoalescer:
    """Single consumer thread over a BOUNDED request queue.

    The worker drains the queue into a pending deque, drops anything whose
    deadline already passed, takes the OLDEST live request's key, and
    gathers every same-key request (arrival order kept) up to `max_batch`.
    A full batch flushes immediately; a partial one waits until the oldest
    member is `max_wait_ms` old, so an isolated request pays at most the
    wait and a burst pays (almost) nothing. Requests with other keys stay
    pending — never reordered relative to their own group, never starved
    (oldest-first head selection).

    Resilience: `submit` sheds (`ShedError`) at `max_queue` unfinished
    requests, on expired deadlines, and while the breaker is open; the
    worker thread is supervised (a crash fails its in-flight group fast
    and the loop restarts); `stop(drain_s=...)` drains gracefully before
    failing the remainder with `ServerClosingError`."""

    _SHUTDOWN = object()

    def __init__(
        self,
        execute: Callable[[list[PendingRequest]], None],
        *,
        max_batch: int = 8,
        max_wait_ms: float = 5.0,
        max_queue: int = 64,
        breaker: Optional[CircuitBreaker] = None,
        observer: Optional[Callable[..., None]] = None,
        tenancy=None,  # serving.tenancy.TenantAdmission (ISSUE 19)
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self._execute = execute
        self.max_batch = int(max_batch)
        self.max_wait = max(0.0, float(max_wait_ms)) / 1000.0
        self.max_queue = int(max_queue)
        self._breaker = breaker
        self._observer = observer
        self.tenancy = tenancy
        self._queue: queue.Queue = queue.Queue()
        self._pending: deque[PendingRequest] = deque()
        self._inflight: Optional[list[PendingRequest]] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._draining = threading.Event()
        # unfinished requests in the coalescer's custody (queued, pending,
        # or in flight) — the admission bound and the drain/idle signal
        self._count_lock = threading.Lock()
        self._outstanding = 0
        # occupancy + resilience telemetry (read by /statsz)
        self.batches_run = 0
        self.rows_run = 0
        self.shed_total = 0
        self.deadline_dropped = 0
        self.cancel_dropped = 0
        self.worker_restarts = 0

    # ----------------------------------------------------------- observers
    def _observe(self, event: str, **ctx) -> None:
        if self._observer is None:
            return
        try:
            self._observer(event, **ctx)
        except Exception:  # noqa: BLE001 — telemetry must not break serving
            pass

    @property
    def breaker(self) -> Optional[CircuitBreaker]:
        return self._breaker

    @property
    def depth(self) -> int:
        """Unfinished requests admitted and not yet resolved."""
        with self._count_lock:
            return self._outstanding

    @property
    def idle(self) -> bool:
        return self.depth == 0

    def _admit(self) -> None:
        with self._count_lock:
            self._outstanding += 1

    def _resolve(self, n: int = 1) -> None:
        with self._count_lock:
            self._outstanding = max(0, self._outstanding - n)

    # ------------------------------------------------------------ producer
    def submit(self, req: PendingRequest):
        """Admit one request, or shed it. Sheds are IMMEDIATE (the request
        is never queued): `ShedError` for overload/breaker/expired-at-
        admission, `ServerClosingError` while draining or stopped."""
        if self._stop.is_set():
            raise ServerClosingError("coalescer is stopped: shutting down")
        if self._draining.is_set():
            raise ServerClosingError(
                "server draining: admission closed", reason="draining"
            )
        if req.expired():
            self._shed(
                "deadline", "request deadline already expired at admission",
                tenant=req.tenant,
            )
        if self._breaker is not None and not self._breaker.allow():
            self._shed(
                "breaker_open",
                "circuit breaker open: decode is failing, try again later",
                retry_after_s=max(1.0, self._breaker.cooldown_s),
                tenant=req.tenant,
            )
        # per-tenant admission (ISSUE 19): charge the row's token budget
        # against its tenant BEFORE the global queue check, so a tenant's
        # flood sheds as `tenant_quota` on THAT tenant while everyone
        # else's requests never see a fuller queue
        release = None
        if self.tenancy is not None:
            try:
                release = self.tenancy.admit(
                    req.tenant, req.prompt_len + req.max_new
                )
            except ShedError as e:
                with self._count_lock:
                    self.shed_total += 1
                self._observe("shed", reason=e.reason, tenant=req.tenant)
                raise
            prev = req.on_finish

            def _finish_release(r, _prev=prev, _rel=release):
                try:
                    if _prev is not None:
                        _prev(r)
                finally:
                    _rel()  # idempotent: exactly-once per admitted row

            req.on_finish = _finish_release
        try:
            if self.depth >= self.max_queue:
                self._shed(
                    "queue_full",
                    f"decode queue full ({self.max_queue} requests in flight)",
                    tenant=req.tenant,
                )
        except BaseException:
            if release is not None:
                release()  # never charge a tenant for a row we refused
            raise
        self._admit()
        self._queue.put(req)

    def _shed(
        self,
        reason: str,
        message: str,
        retry_after_s: float = 1.0,
        tenant: Optional[str] = None,
    ):
        with self._count_lock:
            self.shed_total += 1
        self._observe("shed", reason=reason, tenant=tenant)
        raise ShedError(message, reason=reason, retry_after_s=retry_after_s)

    # ------------------------------------------------------------ lifecycle
    def start(self):
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name="decode-coalescer", daemon=True
        )
        self._thread.start()

    def drain(self, grace_s: float) -> bool:
        """Close admission and wait up to `grace_s` for every admitted
        request (queued + in flight) to resolve. Partial batches flush
        immediately while draining. Returns True when fully flushed."""
        self._draining.set()
        end = time.monotonic() + max(0.0, float(grace_s))
        while time.monotonic() < end:
            if self.idle:
                return True
            time.sleep(0.005)
        return self.idle

    def stop(self, timeout: float = 10.0, drain_s: float = 0.0):
        """Shut down. With `drain_s > 0`, first drain gracefully; whatever
        remains (queued or parked) is failed FAST with a terminal
        `ServerClosingError` — no client is left to ride out
        `request_timeout_s` against a dead server."""
        if self._thread is not None and drain_s > 0:
            self.drain(drain_s)
        self._draining.set()
        self._stop.set()
        self._queue.put(self._SHUTDOWN)
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        # fail fast for anything still parked — the server is going away
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not self._SHUTDOWN:
                self._pending.append(item)
        for req in list(self._pending):
            if not req.done.is_set():
                req.finish(error=ServerClosingError(
                    "server shutting down: request aborted"
                ))
            self._resolve()
        self._pending.clear()

    # ------------------------------------------------------------ consumer
    def _drain_into_pending(self, timeout: Optional[float]) -> bool:
        """Move queued requests into pending; block up to `timeout` for the
        first one. Returns False on shutdown."""
        try:
            item = self._queue.get(timeout=timeout) if timeout else self._queue.get_nowait()
        except queue.Empty:
            return True
        if item is self._SHUTDOWN:
            return False
        self._pending.append(item)
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return True
            if item is self._SHUTDOWN:
                return False
            self._pending.append(item)

    def _drop_expired(self, req: PendingRequest) -> None:
        self.deadline_dropped += 1
        self._observe("deadline_dropped")
        budget = ""
        if req.deadline is not None:
            budget = f" ({(req.deadline - req.enqueued_at) * 1e3:.0f}ms budget)"
        req.finish(error=DeadlineExceededError(
            f"deadline exceeded before decode dispatch{budget}"
        ))
        self._resolve()

    def _drop_cancelled(self, req: PendingRequest) -> None:
        self.cancel_dropped += 1
        self._observe("client_cancelled")
        req.finish(error=ClientDisconnectedError(
            "client disconnected before decode dispatch"
        ))
        self._resolve()

    def _purge_expired(self) -> None:
        """Drop every pending request whose deadline has passed — BEFORE a
        decode slot is spent on it (goodput over throughput). Cancelled
        rows (client gone) go the same way: nobody wants their tokens."""
        if not self._pending:
            return
        now = time.monotonic()
        for r in [r for r in self._pending if r.cancelled]:
            self._pending.remove(r)
            self._drop_cancelled(r)
        dead = [r for r in self._pending if r.expired(now)]
        for r in dead:
            self._pending.remove(r)
            self._drop_expired(r)

    def _run(self):
        """Worker thread body: `_loop` under a watchdog. A crash anywhere
        in the loop fails the in-flight group fast (the clients see a
        `WorkerCrashError`, not a `request_timeout_s` hang), counts a
        breaker failure, and restarts the loop over the surviving queue."""
        while True:
            try:
                self._loop()
                return  # clean shutdown
            except BaseException as e:  # noqa: BLE001 — supervise, restart
                batch, self._inflight = self._inflight, None
                for r in batch or ():
                    if not r.done.is_set():
                        r.finish(error=WorkerCrashError(
                            f"decode worker crashed mid-group: {e!r}"
                        ))
                if batch:
                    self._resolve(len(batch))
                if self._breaker is not None:
                    self._breaker.record_failure()
                self.worker_restarts += 1
                self._observe("worker_restart", error=repr(e))
                if self._stop.is_set():
                    return

    def _loop(self):
        alive = True
        while alive or self._pending:
            if self._stop.is_set():
                # stop() is failing the remainder fast — decoding on past
                # the drain budget would silently overrun it
                return
            self._purge_expired()
            if not self._pending:
                alive = self._drain_into_pending(timeout=0.1)
                continue
            # weighted fair head pick (ISSUE 19): among tenants with
            # pending work, serve the one with the smallest outstanding
            # tokens ÷ weight (FIFO inside a tenant via the enqueue-time
            # tiebreak). Without tenancy this is exactly the old
            # oldest-first rule. The group still mixes tenants: head only
            # chooses WHICH key flushes next.
            if self.tenancy is not None and len(self._pending) > 1:
                head = min(
                    self._pending,
                    key=lambda r: (
                        self.tenancy.share(r.tenant), r.enqueued_at
                    ),
                )
            else:
                head = self._pending[0]
            batch = [r for r in self._pending if r.key == head.key][
                : self.max_batch
            ]
            now = time.monotonic()
            # ISSUE 14 satellite: the flush deadline used to come from the
            # head request only, so an expired NON-head row sat in its slot
            # until the group flushed — and only then 504'd, after the
            # group's tokens were already spent around it. Cap the wait at
            # the earliest pending deadline so the purge above runs the
            # moment any row expires, extending the PR 5 "dropped BEFORE
            # spending a decode slot" contract to mid-group.
            dmin = min(
                (r.deadline for r in self._pending if r.deadline is not None),
                default=None,
            )
            if dmin is not None and dmin <= now:
                self._purge_expired()
                continue
            deadline = head.enqueued_at + self.max_wait
            if dmin is not None:
                deadline = min(deadline, dmin)
            if (
                len(batch) < self.max_batch
                and now < deadline
                and alive
                and not self._draining.is_set()
            ):
                # wait (bounded by the head's age AND the earliest pending
                # deadline) for coalescable arrivals
                alive = self._drain_into_pending(timeout=deadline - now)
                continue
            for r in batch:
                self._pending.remove(r)
            # last look before spending the slot: drop the already-dead
            now = time.monotonic()
            live = []
            for r in batch:
                if r.cancelled:
                    self._drop_cancelled(r)
                elif r.expired(now):
                    self._drop_expired(r)
                else:
                    live.append(r)
            if not live:
                continue
            batch = live
            self._inflight = batch
            # chaos point: a "kill" here takes the worker thread down with
            # this group in flight — the watchdog must recover
            inject("serving.worker", rows=len(batch))
            self.batches_run += 1
            self.rows_run += len(batch)
            try:
                self._execute(batch)
            except BaseException as e:  # noqa: BLE001 — scatter, don't die
                if self._breaker is not None:
                    self._breaker.record_failure()
                self._observe("decode_error", error=type(e).__name__)
                for r in batch:
                    if not r.done.is_set():
                        r.finish(error=e)
            else:
                if self._breaker is not None:
                    self._breaker.record_success()
            self._inflight = None
            self._resolve(len(batch))
            # opportunistically pick up anything that arrived mid-execute
            if alive:
                alive = self._drain_into_pending(timeout=None)
        self._stop.set()
