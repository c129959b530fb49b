"""Run kinds: what a component executes.

Reference parity (SURVEY.md §2 "Run kinds", unverified): upstream has V1Job,
V1Service, V1TFJob/V1PyTorchJob/V1MPIJob/V1XGBoostJob/V1PaddleJob (Kubeflow
replica specs), V1Dag, V1TunerJob. TPU-native addition per the north star:
**V1JAXJob** — the kind this framework executes itself (no Kubeflow
delegation): workers rendezvous via `jax.distributed`, shard over a
`jax.sharding.Mesh` whose axes come from the `mesh:` block, and may run either
a container command or a native `program:` (model/data/optimizer/train config
interpreted by polyaxon_tpu/runtime/).

Legacy distributed kinds (tfjob/pytorchjob/mpijob/xgboostjob/paddlejob/
daskjob/rayjob) parse for compatibility and are normalized to JAXJob by the
compiler (compiler/resolver.py).
"""

from __future__ import annotations

import math
from typing import Annotated, Any, Literal, Optional, Union

from pydantic import Field, model_validator

from .base import BaseSchema, to_camel
from .environment import V1Environment


class V1Container(BaseSchema):
    """Subset of a k8s container spec that both the k8s converter and the
    local subprocess runner understand."""

    name: Optional[str] = None
    image: Optional[str] = None
    command: Optional[list[str]] = None
    args: Optional[list[str]] = None
    env: Optional[dict[str, str] | list[dict[str, Any]]] = None
    working_dir: Optional[str] = None
    resources: Optional[dict] = None
    volume_mounts: Optional[list[dict]] = None


class V1Init(BaseSchema):
    """Init-time artifact/git/file provisioning (runs before the main work)."""

    artifacts: Optional[dict] = None
    git: Optional[dict] = None
    dockerfile: Optional[dict] = None
    file: Optional[dict] = None
    connection: Optional[str] = None
    container: Optional[V1Container] = None
    paths: Optional[list[str]] = None


# ------------------------------------------------------------------ native program
class V1ModelSpec(BaseSchema):
    """A model from the registry (polyaxon_tpu/models/registry.py)."""

    name: str
    config: Optional[dict[str, Any]] = None


# Scalar fields below accept `str` so `{{ params.x }}` templates survive parse
# time; the compiler (compiler/resolver.py) interpolates and re-validates, after
# which they are concrete numbers.
class V1DataSpec(BaseSchema):
    name: str = "synthetic"
    batch_size: int | str = 32
    config: Optional[dict[str, Any]] = None


class V1OptimizerSpec(BaseSchema):
    name: str = "adamw"
    learning_rate: float | str = 1e-3
    config: Optional[dict[str, Any]] = None
    schedule: Optional[dict[str, Any]] = None


class V1TrainSpec(BaseSchema):
    steps: int | str = 100
    eval_every: Optional[int | str] = None
    eval_steps: Optional[int | str] = None
    # jax.profiler capture window [start_step, end_step); the trace lands in
    # the run's outputs dir as a TensorBoard/Perfetto artifact (SURVEY.md §5)
    profile_start: Optional[int | str] = None
    profile_stop: Optional[int | str] = None
    log_every: int | str = 10
    checkpoint_every: Optional[int | str] = None
    # retention: how many recent checkpoints survive on disk (Orbax
    # max_to_keep); long runs with frequent saves must not fill the
    # artifact store. Default 3; must be >= 1 when set (0 would silently
    # coerce to the default, negatives would flow into Orbax unchecked).
    checkpoint_keep: Optional[int | str] = None
    # fast checkpoint tier (host SSD / ramdisk): boundary saves land here
    # first and replicate to the durable outputs dir in the background
    # (runtime/checkpoint.py CheckpointTiers). The executor scopes the
    # path per run (<dir>/<uuid>); restore searches durable-then-local.
    checkpoint_local_dir: Optional[str] = None
    resume: Optional[bool] = None
    seed: int | str = 0
    precision: Literal["bfloat16", "float32", "mixed"] = "mixed"
    # true: recompute in the backward as little as the device allows. The
    # Trainer compiles the step on a short ladder, most kept first, and runs
    # the first rung the device's compiler accepts: no checkpoint at all,
    # then one per transformer block (the whole apply for a model without
    # blocks). Unset or false: no checkpoint, and a step that does not fit
    # is refused
    remat: Optional[bool] = None
    # an explicit policy is obeyed as it is, with no ladder: the whole apply
    # in one jax.checkpoint that may keep nothing (recompute all), dots
    # (matmul outputs; recompute elementwise only) or dots_no_batch (only
    # non-batch matmuls, Megatron-style)
    remat_policy: Optional[Literal["nothing", "dots", "dots_no_batch"]] = None
    donate_state: bool = True
    loss: Optional[str] = None
    # microbatch gradient accumulation: the per-step batch is split into
    # this many sequential microbatches (lax.scan) before ONE optimizer
    # update — trades step latency for a bigger effective batch in the
    # same HBM footprint
    grad_accum: Optional[int | str] = None

    @model_validator(mode="after")
    def _check_checkpoint_keep(self):
        # str values are {{ param }} templates resolved at compile time
        if isinstance(self.checkpoint_keep, int) and self.checkpoint_keep < 1:
            raise ValueError(
                f"checkpointKeep must be >= 1, got {self.checkpoint_keep} "
                "(retention counts checkpoints, 0 would silently fall back "
                "to the default)"
            )
        return self


class V1TenantSpec(BaseSchema):
    """One serving tenant's admission contract (ISSUE 19) — V1QuotaSpec
    semantics at the request level: caps on outstanding requests and
    outstanding token budget, a weighted fair share, and optionally the
    named LoRA adapter the tenant's rows decode with."""

    name: str
    max_outstanding: Optional[int | str] = None
    max_tokens: Optional[int | str] = None
    weight: float | str = 1.0
    adapter: Optional[str] = None

    @model_validator(mode="after")
    def _check(self):
        if not self.name.strip():
            raise ValueError("tenant name must be non-empty")
        for field in ("max_outstanding", "max_tokens"):
            v = getattr(self, field)
            if isinstance(v, int) and v < 0:
                raise ValueError(
                    f"tenant {self.name!r}: {to_camel(field)} must be "
                    f">= 0, got {v}"
                )
        if isinstance(self.weight, (int, float)) and self.weight <= 0:
            raise ValueError(
                f"tenant {self.name!r}: weight must be > 0, "
                f"got {self.weight}"
            )
        return self


class V1PoolsSpec(BaseSchema):
    """Disaggregated prefill/decode replica pools (ISSUE 20). `prefill`
    replicas run only chunked-prefill steps and live-hand the finished
    KV page set to a `decode` replica over POST /kv_import; the router
    gangs both pools from one ReplicaSetManager and dispatches
    role-aware. Either pool at zero degrades to monolithic serving."""

    prefill: int | str = 1
    decode: int | str = 1

    @model_validator(mode="after")
    def _check(self):
        for field in ("prefill", "decode"):
            v = getattr(self, field)
            if isinstance(v, int) and v < 0:
                raise ValueError(
                    f"pools.{field} must be >= 0, got {v}"
                )
        if (
            isinstance(self.prefill, int)
            and isinstance(self.decode, int)
            and self.prefill + self.decode < 1
        ):
            raise ValueError(
                "pools needs at least one replica across prefill + decode"
            )
        return self


class V1ServingSpec(BaseSchema):
    """Serving fast-path knobs (serving/batching.py) a run can pin in its
    spec, so `polyaxon serve --uid <run>` comes up with the shape the model
    was validated at. CLI flags and an explicit ServingConfig override."""

    # continuous batching: coalesce up to maxBatch compatible requests,
    # waiting at most maxWaitMs for stragglers; batching=false restores the
    # legacy one-exact-shape-program-per-request path
    max_batch: int | str = 8
    max_wait_ms: float | str = 5.0
    batching: bool = True
    # shape-bucket ladders (ascending); None = geometric auto-ladder up to
    # the model's seq_len
    prompt_buckets: Optional[list[int]] = None
    max_new_buckets: Optional[list[int]] = None
    request_timeout_s: float | str = 600.0
    # resilience (ISSUE 5): admission bound, deadline budget applied to
    # requests that carry none, drain window on SIGTERM/stop, and the
    # consecutive-decode-failure count that trips the circuit breaker
    max_queue: int | str = 64
    default_deadline_ms: Optional[float | str] = None
    drain_grace_s: float | str = 5.0
    breaker_threshold: int | str = 5
    # paged KV cache + streaming (ISSUE 6): kvPoolPages sizes the fixed
    # block-paged KV pool (None keeps the dense per-group caches);
    # kvPageTokens is the block granularity, prefixCache enables
    # cross-request prefix KV reuse, stream exposes /generate?stream=1
    kv_page_tokens: int | str = 128
    kv_pool_pages: Optional[int | str] = None
    prefix_cache: bool = True
    stream: bool = True
    stream_chunk_tokens: int | str = 8
    # fast decode (ISSUE 8): speculate enables self-speculative decoding
    # (n-gram drafts of draftTokens verified in one batched window;
    # outputs stay byte-identical to plain decode), quantize loads the
    # checkpoint with int8 weight-only projection kernels
    speculate: bool = False
    draft_tokens: int | str = 4
    quantize: bool = False
    # adaptive speculation + KV quantization (ISSUE 15): draftModel swaps
    # the n-gram proposer for a real small draft model (same arch/vocab,
    # overrides like {"n_layers": 2} layer over the config's `draft:`
    # sub-config; params derive by layer truncation when widths match),
    # adaptiveDraft turns on the accept-rate AIMD controller that steers
    # the per-window K and auto-disables speculation when it loses, and
    # kvQuant stores the paged KV pool int8-per-slot (~2x the resident
    # rows per HBM byte; quantization is per-slot so chunked prefill,
    # prefix hits and one-shot prefill stay byte-identical to each other
    # on the quantized pool)
    draft_model: Optional[dict[str, int | str | float | bool]] = None
    adaptive_draft: bool = False
    kv_quant: Literal["none", "int8"] = "none"
    # chunked prefill + step scheduling (ISSUE 14): chunkedPrefill slices
    # prefill into prefillChunkTokens-wide device steps interleaved with
    # decode (kills head-of-line blocking behind long prompts; requires
    # kvPoolPages), and maxStepTokens bounds the tokens any single step
    # may touch — the admission token budget
    chunked_prefill: bool = False
    prefill_chunk_tokens: int | str = 64
    max_step_tokens: int | str = 256
    # horizontal serving (ISSUE 10): replicas is the fleet width (N
    # gang-placed ModelServer processes behind serving/router.py);
    # meshAxes is the per-replica decode mesh, e.g. {"batch": 2,
    # "model": 2} — `model` tensor-parallels the projection kernels,
    # `batch` splits concurrent sequences. Legacy specs may still spell
    # batch-parallelism as data/fsdp; parallel.mesh.decode_mesh folds
    # them into `batch`. -1 means "fill from the visible device count".
    replicas: int | str = 1
    mesh_axes: Optional[dict[str, int | str]] = None
    # cluster-wide tiered KV (ISSUE 17): prefixAffinity routes warm
    # prompts to the replica already holding their prefix KV (fleet
    # router knob, ignored at replicas=1); spillRamBytes bounds a
    # host-RAM tier for evicted prefix-cache entries, spillDir +
    # spillDirBytes add a CRC-framed on-disk tier below it — a prefix
    # hit on a spilled entry restores pages instead of re-prefilling.
    # Spill requires the paged pool with the prefix cache.
    prefix_affinity: bool = True
    spill_ram_bytes: Optional[int | str] = None
    spill_dir: Optional[str] = None
    spill_dir_bytes: Optional[int | str] = None
    # multi-tenant serving (ISSUE 19): `adapters` names the LoRA adapters
    # this server multiplexes (name → .npz path or "seed:<int>"; requires
    # a loraRank-trained checkpoint), `tenants` their admission contracts
    # (per-tenant caps + weighted fair share), and `adapterSlots` caps the
    # device-resident adapters beyond the checkpoint's own slot 0 (0 =
    # one slot per adapter; lower values evict idle adapters LRU through
    # the spill tiers and restore on request).
    adapters: Optional[dict[str, str]] = None
    tenants: Optional[list[V1TenantSpec]] = None
    adapter_slots: int | str = 0
    # disaggregated serving (ISSUE 20): `pools` splits the fleet into a
    # prefill pool (chunked-prefill only; ships the finished page set to
    # a decode replica as SpillPayload bytes over POST /kv_import) and a
    # decode pool that adopts the pages and continues the response
    # mid-flight. Supersedes `replicas` when set. Requires
    # chunkedPrefill + kvPoolPages + prefixCache (the handoff unit is
    # the page-aligned prefix-cache chain).
    pools: Optional[V1PoolsSpec] = None

    _MESH_AXES_ALLOWED = ("batch", "model", "data", "fsdp")

    @model_validator(mode="after")
    def _check(self):
        if isinstance(self.replicas, int) and self.replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {self.replicas}")
        if self.mesh_axes is not None:
            if not self.mesh_axes:
                raise ValueError("meshAxes must be a non-empty mapping")
            fills = 0
            for ax, n in self.mesh_axes.items():
                if ax not in self._MESH_AXES_ALLOWED:
                    raise ValueError(
                        f"meshAxes axis {ax!r}: serving meshes are "
                        f"`batch`×`model` (legacy data/fsdp fold into "
                        f"batch); got axes {sorted(self.mesh_axes)}"
                    )
                if isinstance(n, int):
                    if n == -1:
                        fills += 1
                    elif n < 1:
                        raise ValueError(
                            f"meshAxes[{ax!r}] must be >= 1 or -1 "
                            f"(fill), got {n}"
                        )
            if fills > 1:
                raise ValueError(
                    "meshAxes allows at most one -1 (fill) axis"
                )
        if isinstance(self.draft_tokens, int) and not (
            1 <= self.draft_tokens <= 16
        ):
            raise ValueError(
                f"draftTokens must be in [1, 16] (the verify window is "
                f"draftTokens + 1 wide), got {self.draft_tokens}"
            )
        if isinstance(self.max_batch, int) and self.max_batch < 1:
            raise ValueError(f"maxBatch must be >= 1, got {self.max_batch}")
        if isinstance(self.kv_page_tokens, int) and self.kv_page_tokens < 1:
            raise ValueError(
                f"kvPageTokens must be >= 1, got {self.kv_page_tokens}"
            )
        if isinstance(self.kv_pool_pages, int) and self.kv_pool_pages < 2:
            raise ValueError(
                f"kvPoolPages must be >= 2 (1 scratch + data), "
                f"got {self.kv_pool_pages}"
            )
        if (
            isinstance(self.stream_chunk_tokens, int)
            and self.stream_chunk_tokens < 1
        ):
            raise ValueError(
                f"streamChunkTokens must be >= 1, got {self.stream_chunk_tokens}"
            )
        if isinstance(self.max_queue, int) and self.max_queue < 1:
            raise ValueError(f"maxQueue must be >= 1, got {self.max_queue}")
        if (
            isinstance(self.prefill_chunk_tokens, int)
            and self.prefill_chunk_tokens < 1
        ):
            raise ValueError(
                f"prefillChunkTokens must be >= 1, "
                f"got {self.prefill_chunk_tokens}"
            )
        if isinstance(self.max_step_tokens, int) and self.max_step_tokens < 1:
            raise ValueError(
                f"maxStepTokens must be >= 1, got {self.max_step_tokens}"
            )
        if (
            self.chunked_prefill
            and self.kv_pool_pages is None
        ):
            raise ValueError(
                "chunkedPrefill requires the paged KV pool — set "
                "kvPoolPages (page tables are what let a half-prefilled "
                "row persist across device steps)"
            )
        if self.kv_quant != "none" and self.kv_pool_pages is None:
            raise ValueError(
                "kvQuant requires the paged KV pool — set kvPoolPages "
                "(dense per-group caches stay full-precision)"
            )
        for name in ("spill_ram_bytes", "spill_dir_bytes"):
            v = getattr(self, name)
            if isinstance(v, int) and v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
        if (self.spill_ram_bytes or self.spill_dir) and (
            self.kv_pool_pages is None or not self.prefix_cache
        ):
            raise ValueError(
                "spillRamBytes/spillDir require the paged KV pool with "
                "the prefix cache — set kvPoolPages and keep prefixCache "
                "on (spill tiers hold evicted prefix-cache entries)"
            )
        if self.spill_dir_bytes is not None and not self.spill_dir:
            raise ValueError(
                "spillDirBytes bounds the on-disk tier — set spillDir"
            )
        if self.draft_model is not None and not self.speculate:
            raise ValueError(
                "draftModel requires speculate: true (the draft model is "
                "a proposer for the speculative verify window)"
            )
        if self.adaptive_draft and not self.speculate:
            raise ValueError(
                "adaptiveDraft requires speculate: true (the controller "
                "steers the speculative draft width K)"
            )
        if isinstance(self.breaker_threshold, int) and self.breaker_threshold < 1:
            raise ValueError(
                f"breakerThreshold must be >= 1, got {self.breaker_threshold}"
            )
        if (
            isinstance(self.default_deadline_ms, (int, float))
            and self.default_deadline_ms <= 0
        ):
            raise ValueError(
                f"defaultDeadlineMs must be > 0, got {self.default_deadline_ms}"
            )
        if isinstance(self.drain_grace_s, (int, float)) and self.drain_grace_s < 0:
            raise ValueError(
                f"drainGraceS must be >= 0, got {self.drain_grace_s}"
            )
        for name in ("prompt_buckets", "max_new_buckets"):
            ladder = getattr(self, name)
            if ladder is not None and (
                not ladder or any(b < 1 for b in ladder)
            ):
                raise ValueError(
                    f"{name} must be a non-empty list of positive ints"
                )
        if self.adapters is not None:
            for name, src in self.adapters.items():
                if not str(name).strip() or not str(src).strip():
                    raise ValueError(
                        "adapters entries must map a non-empty name to a "
                        f"non-empty source, got {name!r}: {src!r}"
                    )
        if self.tenants:
            seen: set[str] = set()
            known = set(self.adapters or {})
            for t in self.tenants:
                if t.name in seen:
                    raise ValueError(f"duplicate tenant name {t.name!r}")
                seen.add(t.name)
                if t.adapter and t.adapter not in known:
                    raise ValueError(
                        f"tenant {t.name!r} binds adapter {t.adapter!r} "
                        f"which is not in adapters "
                        f"({sorted(known) or 'none declared'})"
                    )
        if isinstance(self.adapter_slots, int) and self.adapter_slots < 0:
            raise ValueError(
                f"adapterSlots must be >= 0 (0 = one slot per adapter), "
                f"got {self.adapter_slots}"
            )
        if self.pools is not None:
            has_prefill = not (
                isinstance(self.pools.prefill, int) and self.pools.prefill == 0
            )
            if has_prefill and (
                not self.chunked_prefill
                or self.kv_pool_pages is None
                or not self.prefix_cache
            ):
                raise ValueError(
                    "pools with a prefill pool requires chunkedPrefill + "
                    "kvPoolPages + prefixCache: the handoff ships the "
                    "page-aligned prefix-cache chain a chunked prefill "
                    "leaves behind"
                )
        return self

    def to_config(self):
        from ..serving.batching import (
            ServingConfig,
            normalize_draft_model,
            normalize_mesh_axes,
        )
        from ..serving.tenancy import normalize_adapters, normalize_tenants

        return ServingConfig(
            max_batch=int(self.max_batch),
            max_wait_ms=float(self.max_wait_ms),
            batching=self.batching,
            prompt_buckets=(
                tuple(self.prompt_buckets) if self.prompt_buckets else None
            ),
            max_new_buckets=(
                tuple(self.max_new_buckets) if self.max_new_buckets else None
            ),
            request_timeout_s=float(self.request_timeout_s),
            max_queue=int(self.max_queue),
            default_deadline_ms=(
                float(self.default_deadline_ms)
                if self.default_deadline_ms is not None
                else None
            ),
            drain_grace_s=float(self.drain_grace_s),
            breaker_threshold=int(self.breaker_threshold),
            kv_page_tokens=int(self.kv_page_tokens),
            kv_pool_pages=(
                int(self.kv_pool_pages)
                if self.kv_pool_pages is not None
                else None
            ),
            prefix_cache=self.prefix_cache,
            stream=self.stream,
            stream_chunk_tokens=int(self.stream_chunk_tokens),
            speculate=self.speculate,
            draft_tokens=int(self.draft_tokens),
            quantize=self.quantize,
            draft_model=normalize_draft_model(self.draft_model),
            adaptive_draft=self.adaptive_draft,
            kv_quant=str(self.kv_quant),
            chunked_prefill=self.chunked_prefill,
            prefill_chunk_tokens=int(self.prefill_chunk_tokens),
            max_step_tokens=int(self.max_step_tokens),
            spill_ram_bytes=(
                int(self.spill_ram_bytes)
                if self.spill_ram_bytes is not None
                else None
            ),
            spill_dir=self.spill_dir,
            spill_dir_bytes=(
                int(self.spill_dir_bytes)
                if self.spill_dir_bytes is not None
                else None
            ),
            mesh_axes=normalize_mesh_axes(
                {ax: int(n) for ax, n in self.mesh_axes.items()}
                if self.mesh_axes is not None
                else None
            ),
            adapters=normalize_adapters(self.adapters or {}),
            tenants=normalize_tenants(
                [
                    {
                        "name": t.name,
                        "max_outstanding": (
                            int(t.max_outstanding)
                            if t.max_outstanding is not None
                            else None
                        ),
                        "max_tokens": (
                            int(t.max_tokens)
                            if t.max_tokens is not None
                            else None
                        ),
                        "weight": float(t.weight),
                        "adapter": t.adapter or "",
                    }
                    for t in (self.tenants or [])
                ]
            ),
            adapter_slots=int(self.adapter_slots),
        )

    def chips_needed(self) -> Optional[int]:
        """Per-replica chip demand implied by meshAxes (None when the
        mesh has a -1 fill axis or no mesh is pinned)."""
        if not self.mesh_axes:
            return None
        sizes = list(self.mesh_axes.values())
        # unresolved {{param}} interpolations or a -1 fill: not knowable
        if any(not isinstance(n, int) for n in sizes) or -1 in sizes:
            return None
        return math.prod(sizes)


class V1SLOSpec(BaseSchema):
    """One service-level objective evaluated by the serving SLO engine
    (telemetry/slo.py) as multi-window burn rates. `availability` SLOs
    count 5xx responses against all requests; `latency` SLOs count
    requests slower than `thresholdMs` against all requests."""

    name: str
    kind: Literal["availability", "latency"] = "availability"
    # target success ratio in (0, 1), e.g. 0.999 = "three nines"
    objective: float | str = 0.999
    # latency kind only: the good/bad split point
    threshold_ms: Optional[float | str] = None
    # burn-rate evaluation windows, seconds, ascending; None = (60, 300)
    windows: Optional[list[float]] = None
    # breach when EVERY window burns >= this multiple of budget
    burn_threshold: float | str = 1.0

    @model_validator(mode="after")
    def _check(self):
        if isinstance(self.objective, (int, float)) and not (
            0.0 < self.objective < 1.0
        ):
            raise ValueError(
                f"slo {self.name!r}: objective must be in (0, 1), "
                f"got {self.objective}"
            )
        if self.kind == "latency":
            if self.threshold_ms is None:
                raise ValueError(
                    f"slo {self.name!r}: latency kind requires thresholdMs"
                )
            if (
                isinstance(self.threshold_ms, (int, float))
                and self.threshold_ms <= 0
            ):
                raise ValueError(
                    f"slo {self.name!r}: thresholdMs must be > 0, "
                    f"got {self.threshold_ms}"
                )
        elif self.threshold_ms is not None:
            raise ValueError(
                f"slo {self.name!r}: thresholdMs only applies to "
                "kind=latency"
            )
        w = self.windows
        if w is not None and (
            not w or any(x <= 0 for x in w) or sorted(set(w)) != list(w)
        ):
            raise ValueError(
                f"slo {self.name!r}: windows must be a strictly ascending "
                f"list of positive seconds, got {w}"
            )
        if (
            isinstance(self.burn_threshold, (int, float))
            and self.burn_threshold <= 0
        ):
            raise ValueError(
                f"slo {self.name!r}: burnThreshold must be > 0, "
                f"got {self.burn_threshold}"
            )
        return self

    def to_config(self) -> dict:
        """The normalized dict telemetry.slo.build_objectives consumes."""
        out = {
            "name": self.name,
            "kind": self.kind,
            "objective": float(self.objective),
            "burn_threshold": float(self.burn_threshold),
        }
        if self.windows is not None:
            out["windows"] = [float(w) for w in self.windows]
        if self.threshold_ms is not None:
            out["threshold_ms"] = float(self.threshold_ms)
        return out


class V1HistorySpec(BaseSchema):
    """Metrics-history store knobs (telemetry/history.py). When enabled,
    the serving layer samples its registry into CRC-framed tiered
    segments under `<outputs>/telemetry/history/` and serves `/queryz`
    rate/trend queries over them."""

    enabled: bool = True
    # sampler cadence, seconds
    interval_s: float | str = 1.0
    # total retention budget across all tiers, bytes
    max_bytes: Optional[int | str] = None
    # segment rotation size, bytes
    segment_bytes: Optional[int | str] = None

    @model_validator(mode="after")
    def _check(self):
        if (
            isinstance(self.interval_s, (int, float))
            and self.interval_s <= 0
        ):
            raise ValueError(
                f"history.intervalS must be > 0, got {self.interval_s}"
            )
        for field in ("max_bytes", "segment_bytes"):
            v = getattr(self, field)
            if isinstance(v, int) and v <= 0:
                raise ValueError(
                    f"history.{to_camel(field)} must be > 0, got {v}"
                )
        return self

    def to_config(self, history_dir: str) -> dict:
        """The dict ModelServer's `history=` ctor arg consumes; the
        store location is the caller's (it knows the run's outputs)."""
        out = {"dir": history_dir, "interval_s": float(self.interval_s)}
        if self.max_bytes is not None:
            out["max_bytes"] = int(self.max_bytes)
        if self.segment_bytes is not None:
            out["segment_bytes"] = int(self.segment_bytes)
        return out


class V1RegressionRuleSpec(BaseSchema):
    """One declarative perf-regression rule evaluated by the sentinel
    (telemetry/detect.py) over metrics-history windows."""

    name: str
    # a history series name, e.g. serving.ttft_ms
    series: str
    kind: Literal["ceiling", "window_ratio", "ewma_drift"] = "ceiling"
    agg: Literal["avg", "min", "max", "rate", "p50", "p95", "p99"] = "avg"
    window_s: float | str = 60.0
    threshold: float | str
    direction: Literal["above", "below"] = "above"
    # ewma_drift only: smoothing factor and baseline depth
    alpha: float | str = 0.3
    lookback_windows: int | str = 5
    min_samples: int | str = 3

    @model_validator(mode="after")
    def _check(self):
        if isinstance(self.window_s, (int, float)) and self.window_s <= 0:
            raise ValueError(
                f"rule {self.name!r}: windowS must be > 0, "
                f"got {self.window_s}"
            )
        if isinstance(self.alpha, (int, float)) and not (
            0.0 < self.alpha <= 1.0
        ):
            raise ValueError(
                f"rule {self.name!r}: alpha must be in (0, 1], "
                f"got {self.alpha}"
            )
        return self

    def to_config(self) -> dict:
        """The normalized dict telemetry.detect.build_rules consumes."""
        return {
            "name": self.name,
            "series": self.series,
            "kind": self.kind,
            "agg": self.agg,
            "window_s": float(self.window_s),
            "threshold": float(self.threshold),
            "direction": self.direction,
            "alpha": float(self.alpha),
            "lookback_windows": int(self.lookback_windows),
            "min_samples": int(self.min_samples),
        }


class V1ObservabilitySpec(BaseSchema):
    """Telemetry knobs (polyaxon_tpu/telemetry/) a run can pin in its
    spec. Presence of the section also opts the run into host/HBM
    sampling (tracking/monitors.SystemMonitor) at `sampleInterval`."""

    # SystemMonitor cadence, seconds
    sample_interval: float | str = 10.0
    # histogram bucket upper bounds (seconds, ascending) for the trainer's
    # registry; None = the registry's latency-shaped defaults
    histogram_buckets: Optional[list[float]] = None
    # span tracing on/off: the per-step data_wait/compute span tree
    # exported to <artifacts>/telemetry/spans.jsonl
    trace: bool = True
    # serving SLOs: enables the burn-rate engine + breach flight recorder
    # when this run's checkpoint is served (serving/server.py from_run)
    slos: Optional[list[V1SLOSpec]] = None
    # metrics history (ISSUE 18): sampler + /queryz when served
    history: Optional[V1HistorySpec] = None
    # perf-regression sentinel rules over history windows; the string
    # "default" arms the serving drift pack (telemetry.detect.
    # DEFAULT_SERVING_RULES). Requires `history`.
    regression_rules: Optional[list[V1RegressionRuleSpec] | str] = None

    @model_validator(mode="after")
    def _check(self):
        if (
            isinstance(self.sample_interval, (int, float))
            and self.sample_interval <= 0
        ):
            raise ValueError(
                f"sampleInterval must be > 0, got {self.sample_interval}"
            )
        b = self.histogram_buckets
        if b is not None and (
            not b or any(x <= 0 for x in b) or sorted(set(b)) != list(b)
        ):
            raise ValueError(
                "histogramBuckets must be a strictly ascending list of "
                f"positive numbers, got {b}"
            )
        if isinstance(self.regression_rules, str):
            if self.regression_rules != "default":
                raise ValueError(
                    "regressionRules must be a rule list or the string "
                    f"'default', got {self.regression_rules!r}"
                )
        if self.regression_rules is not None and (
            self.history is None or not self.history.enabled
        ):
            raise ValueError(
                "regressionRules require observability.history (the "
                "sentinel evaluates rules over the history store)"
            )
        if isinstance(self.regression_rules, list):
            names = [r.name for r in self.regression_rules]
            if len(names) != len(set(names)):
                raise ValueError(
                    f"duplicate regression rule names in {names}"
                )
        return self

    def rules_config(self) -> Optional[list[dict]]:
        """The normalized rule dicts telemetry.detect.build_rules
        consumes; resolves the "default" pack."""
        if self.regression_rules is None:
            return None
        if isinstance(self.regression_rules, str):
            from ..telemetry.detect import DEFAULT_SERVING_RULES

            return [dict(r) for r in DEFAULT_SERVING_RULES]
        return [r.to_config() for r in self.regression_rules]


class V1Program(BaseSchema):
    """Native training program executed in-process by the JAXJob runtime
    (runtime/trainer.py) — this replaces the reference's user-container +
    Kubeflow delegation with an owned training loop."""

    model: V1ModelSpec
    data: Optional[V1DataSpec] = None
    optimizer: Optional[V1OptimizerSpec] = None
    train: Optional[V1TrainSpec] = None
    serving: Optional[V1ServingSpec] = None
    observability: Optional[V1ObservabilitySpec] = None


class V1MeshSpec(BaseSchema):
    """Logical mesh axes → sizes. Recognized axes: data, fsdp, model (tensor),
    pipeline, context (sequence), expert. Sizes must multiply to the chip
    count of the tpu spec (validated at compile time, where both are known).
    A size of -1 means 'fill with remaining devices' (at most one axis)."""

    data: Optional[int] = None
    fsdp: Optional[int] = None
    model: Optional[int] = None
    pipeline: Optional[int] = None
    context: Optional[int] = None
    expert: Optional[int] = None

    def axis_sizes(self) -> dict[str, int]:
        out = {}
        for ax in ("data", "fsdp", "model", "pipeline", "context", "expert"):
            v = getattr(self, ax)
            if v is not None:
                out[ax] = v
        return out

    @model_validator(mode="after")
    def _check(self):
        sizes = self.axis_sizes()
        n_fill = sum(1 for v in sizes.values() if v == -1)
        if n_fill > 1:
            raise ValueError("at most one mesh axis may be -1 (auto-fill)")
        for ax, v in sizes.items():
            if v == 0 or v < -1:
                raise ValueError(f"mesh axis {ax!r} has invalid size {v}")
        return self


# ------------------------------------------------------------------ run kinds
class V1Job(BaseSchema):
    kind: Literal["job"] = "job"
    container: Optional[V1Container] = None
    init: Optional[list[V1Init]] = None
    sidecars: Optional[list[V1Container]] = None
    environment: Optional[V1Environment] = None
    connections: Optional[list[str]] = None
    volumes: Optional[list[dict]] = None


class V1Service(BaseSchema):
    kind: Literal["service"] = "service"
    container: Optional[V1Container] = None
    init: Optional[list[V1Init]] = None
    sidecars: Optional[list[V1Container]] = None
    environment: Optional[V1Environment] = None
    connections: Optional[list[str]] = None
    volumes: Optional[list[dict]] = None
    ports: Optional[list[int]] = None
    rewrite_path: Optional[bool] = None
    is_external: Optional[bool] = None
    replicas: Optional[int] = Field(default=None, ge=1)


class V1JAXJob(BaseSchema):
    """TPU-native distributed training job (the framework's own runtime)."""

    kind: Literal["jaxjob"] = "jaxjob"
    replicas: int = Field(default=1, ge=1)  # host processes; each drives its local chips
    mesh: Optional[V1MeshSpec] = None
    program: Optional[V1Program] = None
    container: Optional[V1Container] = None
    init: Optional[list[V1Init]] = None
    sidecars: Optional[list[V1Container]] = None
    environment: Optional[V1Environment] = None
    connections: Optional[list[str]] = None
    volumes: Optional[list[dict]] = None
    coordinator_port: int = 8476

    @model_validator(mode="after")
    def _check(self):
        if self.program is None and self.container is None:
            raise ValueError("jaxjob needs `program` (native) or `container`")
        # serving meshAxes vs resources.chips: a pinned decode mesh that
        # multiplies past the run's own chip request can never come up —
        # reject at parse time, not at restore time on the serving host
        serving = self.program.serving if self.program is not None else None
        res = (
            self.environment.resources
            if self.environment is not None
            else None
        )
        if serving is not None and res is not None:
            need = serving.chips_needed()
            have = (
                res.tpu.total_chips
                if res.tpu is not None
                else res.chips
            )
            if need is not None and have is not None and need > have:
                raise ValueError(
                    f"serving.meshAxes {serving.mesh_axes} needs {need} "
                    f"chips per replica, but resources request only "
                    f"{have}"
                )
        return self


class V1KFReplica(BaseSchema):
    """Replica spec of legacy Kubeflow-style kinds (chief/worker/ps/master)."""

    replicas: int = Field(default=1, ge=1)
    container: Optional[V1Container] = None
    init: Optional[list[V1Init]] = None
    sidecars: Optional[list[V1Container]] = None
    environment: Optional[V1Environment] = None
    connections: Optional[list[str]] = None


class V1TFJob(BaseSchema):
    kind: Literal["tfjob"] = "tfjob"
    chief: Optional[V1KFReplica] = None
    worker: Optional[V1KFReplica] = None
    ps: Optional[V1KFReplica] = None
    evaluator: Optional[V1KFReplica] = None
    clean_pod_policy: Optional[str] = None
    # native-extension passthroughs so legacy kinds can still pick a mesh/program
    mesh: Optional[V1MeshSpec] = None
    program: Optional[V1Program] = None


class V1PyTorchJob(BaseSchema):
    kind: Literal["pytorchjob"] = "pytorchjob"
    master: Optional[V1KFReplica] = None
    worker: Optional[V1KFReplica] = None
    clean_pod_policy: Optional[str] = None
    mesh: Optional[V1MeshSpec] = None
    program: Optional[V1Program] = None


class V1MPIJob(BaseSchema):
    kind: Literal["mpijob"] = "mpijob"
    launcher: Optional[V1KFReplica] = None
    worker: Optional[V1KFReplica] = None
    slots_per_worker: Optional[int] = None
    clean_pod_policy: Optional[str] = None
    mesh: Optional[V1MeshSpec] = None
    program: Optional[V1Program] = None


class V1XGBoostJob(BaseSchema):
    kind: Literal["xgboostjob"] = "xgboostjob"
    master: Optional[V1KFReplica] = None
    worker: Optional[V1KFReplica] = None
    clean_pod_policy: Optional[str] = None
    mesh: Optional[V1MeshSpec] = None
    program: Optional[V1Program] = None


class V1PaddleJob(BaseSchema):
    kind: Literal["paddlejob"] = "paddlejob"
    master: Optional[V1KFReplica] = None
    worker: Optional[V1KFReplica] = None
    clean_pod_policy: Optional[str] = None
    mesh: Optional[V1MeshSpec] = None
    program: Optional[V1Program] = None


class V1DaskJob(BaseSchema):
    kind: Literal["daskjob"] = "daskjob"
    job: Optional[V1KFReplica] = None
    scheduler: Optional[V1KFReplica] = None
    worker: Optional[V1KFReplica] = None
    mesh: Optional[V1MeshSpec] = None
    program: Optional[V1Program] = None


class V1RayJob(BaseSchema):
    kind: Literal["rayjob"] = "rayjob"
    head: Optional[V1KFReplica] = None
    worker: Optional[V1KFReplica] = None
    entrypoint: Optional[str] = None
    ray_version: Optional[str] = None
    mesh: Optional[V1MeshSpec] = None
    program: Optional[V1Program] = None


class V1TunerJob(BaseSchema):
    """Auxiliary tuner job driving a matrix sweep (Polytune)."""

    kind: Literal["tuner"] = "tuner"
    container: Optional[V1Container] = None
    environment: Optional[V1Environment] = None


class V1Dag(BaseSchema):
    kind: Literal["dag"] = "dag"
    operations: list["V1OperationRef"] = Field(default_factory=list)
    concurrency: Optional[int] = None
    early_stopping: Optional[list[dict]] = None
    environment: Optional[V1Environment] = None


class V1OperationRef(BaseSchema):
    """An operation inside a DAG: inline component or path ref + deps."""

    name: str
    dag_ref: Optional[str] = None
    path_ref: Optional[str] = None
    hub_ref: Optional[str] = None
    component: Optional[dict] = None  # inline component (validated lazily)
    params: Optional[dict[str, Any]] = None
    # a sweep NODE: the dag walker drives it through the tuner and exposes
    # the winner as {{ ops.<name>.outputs.best.<param> }}
    matrix: Optional[dict[str, Any]] = None
    depends_on: Optional[list[str]] = None
    trigger: Optional[str] = None  # all_succeeded | all_done | one_succeeded ...
    conditions: Optional[str] = None


V1Dag.model_rebuild()

V1RunKind = Union[
    V1Job,
    V1Service,
    V1JAXJob,
    V1TFJob,
    V1PyTorchJob,
    V1MPIJob,
    V1XGBoostJob,
    V1PaddleJob,
    V1DaskJob,
    V1RayJob,
    V1TunerJob,
    V1Dag,
]

# Discriminated-union form for embedding in parent schemas: pydantic dispatches
# on `kind` and produces clean per-kind errors.
V1RunKindField = Annotated[V1RunKind, Field(discriminator="kind")]

RUN_KINDS: dict[str, type] = {
    "job": V1Job,
    "service": V1Service,
    "jaxjob": V1JAXJob,
    "tfjob": V1TFJob,
    "pytorchjob": V1PyTorchJob,
    "mpijob": V1MPIJob,
    "xgboostjob": V1XGBoostJob,
    "paddlejob": V1PaddleJob,
    "daskjob": V1DaskJob,
    "rayjob": V1RayJob,
    "tuner": V1TunerJob,
    "dag": V1Dag,
}


def run_num_slices(run) -> int:
    """Slice count of a run's `tpu:` block (1 when absent) — the single
    accessor for multi-slice plumbing (executor → worker payloads)."""
    env = getattr(run, "environment", None)
    tpu = env.resources.tpu if env and env.resources else None
    return tpu.num_slices if tpu is not None else 1


def parse_run(data: dict) -> V1RunKind:
    kind = data.get("kind")
    if kind not in RUN_KINDS:
        raise ValueError(f"unknown run kind {kind!r}; one of {sorted(RUN_KINDS)}")
    return RUN_KINDS[kind].model_validate(data)
