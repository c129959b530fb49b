"""The JAXJob training loop: a concrete `V1Program` → trained state.

This is the compute the reference never owned (SURVEY.md §1: training lived
in user containers behind Kubeflow CRDs). TPU-first design decisions:

- ONE jit-compiled `train_step` (params donated, static shapes) — the Python
  loop only feeds batches and reads metrics on log steps, so steps between
  logs run back-to-back on device with no host sync.
- Mixed precision the TPU way: trained params in f32, compute in bf16
  (MXU-native); a LoRA step's frozen half in the compute type, since no
  step writes it; no loss scaling — bf16 keeps f32's exponent range.
- Sharding via NamedShardings from model-declared logical rules
  (parallel/sharding.py); init runs under jit with `out_shardings`, so params
  materialize directly on their devices — no host-side full copy.
- `train.remat: true` recomputes as little as the device allows: the step
  is compiled on a short ladder of rungs (`_RematLadder`), most kept first,
  and the first that the device's compiler accepts runs.
- Time to the first step is told by the program, from reads of the clock on
  calls set-up makes anyway: nothing stands around the calls that trace,
  lower and compile the step, and when its executable exists the Trainer
  writes the record of the finished set-up (`Trainer._report_startup`).
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from ..chaos.injector import inject
from ..data import build_data
from ..models import build_model
from ..ops.losses import accuracy as accuracy_metric
from ..ops.losses import build_loss
from ..ops.optimizers import build_optimizer
from ..parallel.mesh import build_mesh, local_batch_slice
from ..parallel.sharding import (
    batch_sharding,
    make_global_batch,
    param_shardings,
    replicated,
)
from ..retry import Preempted
from ..schemas.run_kinds import V1Program
from ..telemetry import MetricsRegistry, SpanTracer, compiles, get_registry, now as _now
from ..telemetry import process_age
from ..telemetry import mfu as _mfu_of
from ..telemetry import required_train_step_flops
from . import preemption


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    # non-trained variable collections (e.g. BatchNorm batch_stats), keyed by
    # collection name; empty dict when the model declares none
    extra: Any = struct.field(default_factory=dict)


@dataclasses.dataclass
class TrainResult:
    state: TrainState
    history: list[dict]
    steps_per_sec: float
    final_metrics: dict


def _compute_dtype(precision: str):
    return {"float32": jnp.float32, "mixed": jnp.bfloat16, "bfloat16": jnp.bfloat16}[
        precision
    ]


def _cast_floats(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


def param_dtype_for(precision: str):
    """Master-weight dtype for a train.precision setting."""
    return jnp.bfloat16 if precision == "bfloat16" else jnp.float32


def param_labels(bundle, params):
    """`train` for a leaf the optimizer updates, `freeze` for one it never
    does: every leaf trains unless the bundle names `trainable_patterns`
    (LoRA), and then only the leaves whose path one of them matches."""
    if not bundle.trainable_patterns:
        return jax.tree.map(lambda _: "train", params)
    import re as _re

    from ..parallel.sharding import _path_str

    pats = tuple(_re.compile(p) for p in bundle.trainable_patterns)
    return jax.tree_util.tree_map_with_path(
        lambda path, _: "train"
        if any(p.search(_path_str(path)) for p in pats)
        else "freeze",
        params,
    )


def make_param_init(bundle, param_dtype, example, frozen_dtype=None):
    """The init-and-cast recipe for a bundle's params + mutable collections.

    Shared between training setup (_build_step) and the serving restore
    (serving/server.from_run): serving rebuilds the ABSTRACT param tree
    from the stored spec to partial-restore a checkpoint, and the two code
    paths must produce identical trees or the restore breaks — one
    function, no drift. Params do not depend on the example's batch dim,
    so any batch size works for shape inference.

    `frozen_dtype` is the type the step reads its params in: a leaf the
    optimizer never updates (`freeze` in `param_labels`) is stored in it,
    cast in this same program, so the step casts it never. None stores
    every float leaf in `param_dtype`, as serving does: its tree has the
    trainer's paths and shapes, and the restore casts a checkpoint's
    frozen leaf up to it, so a served model computes in its own type."""

    def stored(params):
        if param_dtype != jnp.float32:
            params = _cast_floats(params, param_dtype)
        if frozen_dtype is not None and frozen_dtype != param_dtype:
            params = jax.tree.map(
                lambda label, x: x if label == "train" else _cast_floats(x, frozen_dtype),
                param_labels(bundle, params), params,
            )
        return params

    # `init_fn`'s frame is live while the model's init traces: kept at the
    # size it had before `stored` (a live frame that grows moves every
    # frame under it, and tracing under it slows; PERF.md section 6)
    def init_fn(rng):
        variables = bundle.module.init(
            {"params": rng, **{k: rng for k in bundle.rngs}},
            example,
            train=False,
        )
        params = stored(variables["params"])
        extra = {k: variables[k] for k in tuple(bundle.mutable)}
        return params, extra

    return init_fn


class _RematLadder:
    """`train_step` under `train.remat: true`: the step of every rung, ordered
    by recomputation, and the executable of the first whose compile the
    device's compiler accepts.

    The choice is made at the first call (or the first `lower`), from the
    shapes that call brings, by compiling ahead of time; the executable that
    compile gave is what every call then runs, so where the first rung fits
    the step is traced, lowered and compiled once, as a plain `jax.jit` would.
    Like any compiled step it takes one shape of state and batch. A rung is
    refused only by the compiler's own RESOURCE_EXHAUSTED, which comes before
    anything ran and so before any state was donated; every other error is
    raised as it is, and when every rung is refused, the last refusal.

    Every process of a multi-host job compiles the same program for the same
    kind of device, so all land on the same rung without a collective.

    The choice is timed by reads of the clock alone (`began`, `clock`): no
    span, frame or wait stands around `.lower()` and `.compile()`, which do
    here what a plain `jax.jit`'s first call does."""

    def __init__(self, steps: dict, report: Callable[[dict], None]):
        self.steps = steps  # rung -> the `jax.jit` of its step
        self.rung: Optional[str] = None
        self.tried: list[dict] = []
        self.began: Optional[float] = None  # `_now()` when the choice began
        self.clock: dict = {}  # rung -> [`_now()` before `lower`, between, after `compile`]
        self._report = report
        self._compiled = None

    def attempt(self, rung: str, state, batch):
        """(what the compiler answered, the executable or its refusal):
        `fits` with the compiler's bytes, or `refused` with its first line.
        Every answer has `lower_seconds` (the step traced to a jaxpr and
        lowered to StableHLO: paid once a rung, cache or no cache),
        `compile_seconds` (the device's compiler, or the load from the
        persistent cache: `cache` says `hit`, `miss` or `off`) and `seconds`,
        their sum: a refused rung's `seconds` is its lowering AND its
        refused compile, not the compile alone.

        This frame is live while the step is traced, and its size (locals
        + stack, 15 words: `tests/test_runtime.py` holds it there) is part
        of how long that takes: CPython keeps frames on a data stack of 16
        KiB chunks, a frame that grows moves every frame under it, and a
        hot call that then crosses a chunk's end maps and unmaps a chunk
        each time. Five words more here read 0.8 s of 10.4 in InternLM2's
        lowering (PERF.md section 6, PR 38), so two of the instants live in
        `clock` and not in locals."""
        t0 = _now()
        lowered = self.steps[rung].lower(state, batch)
        self.clock[rung] = [t0, _now()]
        mark = compiles.own()
        try:
            compiled = lowered.compile()
        except jax.errors.JaxRuntimeError as e:
            self.clock[rung].append(_now())
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return self._answer(rung, mark, e)
        self.clock[rung].append(_now())
        return self._answer(rung, mark, compiled)

    def _answer(self, rung: str, mark: dict, outcome):
        """(the answer with its share of the clock, `outcome`), once `compile`
        has returned the executable or raised the refusal."""
        t0, t1, t2 = self.clock[rung]
        lower, compile_ = round(t1 - t0, 3), round(t2 - t1, 3)
        if isinstance(outcome, Exception):
            said = {"result": "refused", "compiler": str(outcome).strip().splitlines()[0][:400]}
        else:
            said = {"result": "fits", "bytes": _step_bytes(outcome)}
        return {
            "rung": rung, **said,
            "seconds": round(lower + compile_, 3),
            "lower_seconds": lower,
            "compile_seconds": compile_,
            "cache": compiles.cache_since(mark),
        }, outcome

    def _choose(self, state, batch):
        self.began = _now()
        for rung in self.steps:
            answer, outcome = self.attempt(rung, state, batch)
            self.tried.append(answer)
            if answer["result"] == "fits":
                self.rung, self._compiled = rung, outcome
                break
        self._report(
            {"rung": self.rung, "ladder": list(self.steps), "tried": self.tried}
        )
        if self.rung is None:
            raise outcome  # every rung refused: the last refusal

    def __call__(self, state, batch):
        if self._compiled is None:
            self._choose(state, batch)
        return self._compiled(state, batch)

    def lower(self, state, batch):
        """The lowering of the rung that runs, chosen first (from these
        shapes) where no step has run yet."""
        if self._compiled is None:
            self._choose(state, batch)
        return self.steps[self.rung].lower(state, batch)


def _step_bytes(compiled) -> Optional[int]:
    """The compiler's total for an executable on one device, where the
    backend gives one."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    return int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )


# the `startup` record's numbers that are `train.startup.*` gauges too, each
# read by the benchmark's per-layer metric of its name (PERF.md section 3)
_STARTUP_GAUGES = (
    "before_trainer_seconds", "build_seconds", "step_lower_seconds",
    "step_compile_seconds", "refused_compile_seconds",
)


class Trainer:
    """Drives one program on one mesh. Multi-host setup (jax.distributed)
    happens in the executor before this class is built."""

    def __init__(
        self,
        program: V1Program,
        *,
        mesh_axes: Optional[dict[str, int]] = None,
        devices: Optional[list] = None,
        slices: int = 1,
        log_fn: Optional[Callable[[int, dict], None]] = None,
        event_fn: Optional[Callable[[str, dict], None]] = None,
        checkpoint_dir: Optional[str] = None,
        local_checkpoint_dir: Optional[str] = None,
        artifacts_dir: Optional[str] = None,
        registry: Optional[MetricsRegistry] = None,
    ):
        # the process's age here (the interpreter, the imports, JAX's look
        # for the chip, under `polyaxon run` the CLI and the executor), and
        # the clock at the entry: `_report_startup` reads both
        self._startup: dict = {"age": process_age(), "entered": _now()}
        self._startup_log: dict = {}
        self.artifacts_dir = artifacts_dir
        self.event_fn = event_fn
        self.program = program
        tspec = program.train
        if tspec is None:
            from ..schemas.run_kinds import V1TrainSpec

            tspec = V1TrainSpec()
        self.tspec = tspec
        self.log_fn = log_fn or (lambda step, m: None)
        self.checkpoint_dir = checkpoint_dir
        self.local_checkpoint_dir = local_checkpoint_dir or (
            tspec.checkpoint_local_dir if tspec else None
        )
        self._tiers = None
        # ONE metrics pipeline: every number the trainer reports flows
        # through this registry (and from there to the store via _emit).
        obs = program.observability
        self.obs = obs
        self.telemetry = registry or MetricsRegistry(
            default_buckets=obs.histogram_buckets if obs else None
        )
        trace = obs.trace if obs is not None else True
        self.tracer = SpanTracer(
            path=(
                str(Path(artifacts_dir) / "telemetry" / "spans.jsonl")
                if (artifacts_dir and trace)
                else None
            ),
            prefix="polyaxon.train.",
        )
        compiles.install()

        from ..utils.jax_platform import apply_compilation_cache, device_report

        cache_dir = apply_compilation_cache()
        self.bundle = build_model(program.model.name, program.model.config)
        dspec = program.data
        data_name = dspec.name if dspec else "synthetic"
        batch_size = int(dspec.batch_size) if dspec else 32
        self.data = build_data(
            data_name,
            batch_size,
            dspec.config if dspec else None,
            seed=int(tspec.seed),
            process_index=jax.process_index(),
            process_count=jax.process_count(),
        )
        ospec = program.optimizer
        self.steps = int(tspec.steps)
        self.tx, self.sched = build_optimizer(
            name=ospec.name if ospec else "adamw",
            learning_rate=float(ospec.learning_rate) if ospec else 1e-3,
            config=ospec.config if ospec else None,
            schedule=ospec.schedule if ospec else None,
            total_steps=self.steps,
        )
        self.loss_fn = build_loss(tspec.loss or self.bundle.loss)
        self.mesh = build_mesh(mesh_axes, devices=devices, slices=slices)
        # model-internal collectives (ring attention, MoE all-to-all) read
        # the mesh from this context var at trace time
        from ..parallel.ring import set_current_mesh

        set_current_mesh(self.mesh)
        # where this trainer runs, on the run store before any step does:
        # a run that landed on the wrong device must be readable as such
        # from outside the process
        self._event(
            "device",
            {
                **device_report(
                    list(self.mesh.devices.flat),
                    getattr(self.bundle.module, "cfg", None),
                    self.data.meta.get("seq_len"),
                ),
                "mesh": {k: int(v) for k, v in self.mesh.shape.items()},
                "process_count": jax.process_count(),
                "compile_cache_dir": cache_dir,
            },
        )
        self.compute_dtype = _compute_dtype(tspec.precision)
        self.param_dtype = param_dtype_for(tspec.precision)
        self._build_step()
        self._startup["built"] = _now()
        if not isinstance(self.train_step, _RematLadder):
            # a plain `jax.jit`: its lowering and compile are `xla.*_seconds`'
            self._report_startup()

    def _validate_mesh_fit(self):
        """Friendly config errors instead of opaque XLA sharding failures:
        every mesh axis must divide the model/data dimension it splits."""
        mesh, cfg = self.mesh, getattr(self.bundle.module, "cfg", None)

        def check(axis: int, dim: int, what: str):
            if axis > 1 and dim % axis != 0:
                raise ValueError(
                    f"mesh axis mismatch: {what} ({dim}) is not divisible by "
                    f"the mesh's {axis}-way split — adjust the mesh or the model"
                )

        if cfg is not None:
            model_deg = mesh.shape.get("model", 1)
            check(model_deg, getattr(cfg, "n_heads", model_deg), "n_heads")
            # GQA: k/v activations carry n_kv_heads — they shard too
            check(model_deg, getattr(cfg, "n_kv_heads", model_deg), "n_kv_heads")
            ctx = mesh.shape.get("context", 1)
            # runtime shapes come from the DATA stream's seq_len, not the
            # model's maximum — validate what will actually be sharded
            seq = self.data.meta.get("seq_len") or getattr(cfg, "seq_len", ctx)
            check(ctx, int(seq), "data seq_len")
            pipe = mesh.shape.get("pipeline", 1)
            check(pipe, getattr(cfg, "n_layers", pipe), "n_layers")
            exp = mesh.shape.get("expert", 1)
            n_experts = getattr(cfg, "n_experts", 0) or 0
            if exp > 1:
                if n_experts == 0:
                    raise ValueError(
                        "mesh declares an expert axis but the model has no "
                        "experts (set model.config.n_experts)"
                    )
                # the axis splits the stacked kernels, which hold the
                # experts held here: all the router knows, or this
                # process's share of them
                held = getattr(cfg, "held", n_experts)
                check(
                    exp, held,
                    "n_experts" if held == n_experts
                    else f"experts_held (of {n_experts} published)",
                )
        self._validate_data_shape()

    def _validate_data_shape(self):
        """Feature-dim mismatches between the data stream and the model
        surface as an opaque flax ScopeParamShapeError at apply time —
        catch them up front with a config-level message. Only enforced for
        datasets that declare their feature shape (classification streams);
        token streams size themselves by seq_len."""
        declared = self.data.meta.get("shape")
        if not declared:
            return
        example = self.bundle.example_inputs(1)
        if not hasattr(example, "shape") or example.ndim < 2:
            return
        import math as _math

        model_shape = tuple(example.shape[1:])
        declared = tuple(declared)
        # element-count comparison, not tuple equality: models may flatten
        # (mlp reshapes (28,28,1) -> 784), so (28,28,1) vs (784,) is valid
        if _math.prod(declared) != _math.prod(model_shape):
            raise ValueError(
                f"data/model shape mismatch: dataset "
                f"'{self.data.name}' emits features of shape "
                f"{declared} but model '{self.program.model.name}' "
                f"expects {model_shape} — align data.config.shape with the "
                f"model config"
            )

    # -------------------------------------------------------------- setup
    def _build_step(self):
        bundle, mesh, tspec = self.bundle, self.mesh, self.tspec
        self._validate_mesh_fit()  # after self.data exists (seq_len check)
        global_batch = self.data.batch_size * jax.process_count()
        if global_batch % local_batch_slice(mesh) != 0:
            raise ValueError(
                f"global batch {global_batch} not divisible by batch-sharded "
                f"mesh axes ({local_batch_slice(mesh)})"
            )
        example = bundle.example_inputs(global_batch)
        init_rng = jax.random.PRNGKey(int(tspec.seed))

        mutable = tuple(bundle.mutable)
        init_fn = make_param_init(
            bundle, self.param_dtype, example, self.compute_dtype
        )
        abstract_params, abstract_extra = jax.eval_shape(init_fn, init_rng)
        self._train_labels = None  # all parameters train
        labels = param_labels(bundle, abstract_params)
        if bundle.trainable_patterns:
            # LoRA-style fine-tune: non-matching params get zero updates.
            # multi_transform (not optax.masked — masked passes raw grads
            # through as updates for the frozen side).
            self.tx = optax.multi_transform(
                {"train": self.tx, "freeze": optax.set_to_zero()}, labels
            )
            self._train_labels = labels
        self._report_differentiated(abstract_params, labels)
        self._report_layers()
        self.p_shard = param_shardings(abstract_params, bundle.sharding_rules, mesh)
        e_shard = param_shardings(abstract_extra, bundle.sharding_rules, mesh)
        o_shard = _opt_state_shardings(self.tx, abstract_params, self.p_shard, mesh)
        t_init = _now()
        params, extra = jax.jit(init_fn, out_shardings=(self.p_shard, e_shard))(
            init_rng
        )
        opt_state = jax.jit(self.tx.init, out_shardings=o_shard)(params)
        # traced, compiled or loaded, and dispatched: not waited for
        self._startup["init"] = (t_init, _now())
        rep = replicated(mesh)
        self.state = TrainState(
            # placed like every later step's: an array that is not on the
            # mesh has another type, and the second call would trace and
            # compile the whole step again
            step=jax.device_put(jnp.zeros((), jnp.int32), rep),
            params=params,
            opt_state=opt_state,
            extra=extra,
        )
        # token batches [B, S] shard the sequence dim over `context` so ring
        # attention's shard_map receives already-placed chunks
        extra_axes = None
        if bundle.task in ("lm", "mlm") and mesh.shape.get("context", 1) > 1:
            extra_axes = {"1": "context"}
        self.b_shard = batch_sharding(mesh, extra_axes)
        state_shardings = TrainState(
            step=rep, params=self.p_shard, opt_state=o_shard, extra=e_shard
        )

        compute_dtype = self.compute_dtype
        loss_fn, tx, sched = self.loss_fn, self.tx, self.sched
        is_classification = bundle.task == "classification"
        seed = int(tspec.seed)

        step_metrics = bundle.step_metrics
        collections = (
            list(mutable)
            + (["losses"] if bundle.aux_losses else [])
            + (list(bundle.step_collections) if step_metrics is not None else [])
        )

        fused_loss = bundle.fused_loss
        if fused_loss is not None and (tspec.loss or bundle.loss) not in (
            None,
            "masked_lm",
        ):
            raise ValueError(
                f"fused_lm_loss computes chunked masked-LM cross-entropy "
                f"and cannot honor train.loss={tspec.loss or bundle.loss!r} "
                "— drop the loss override or disable fused_lm_loss"
            )
        # static per-model: with a fused head+loss the module returns pre-
        # head FEATURES and the loss computes the head in vocab chunks
        # (the [B,S,V] logits never materialize — ops/losses.py)
        apply_kw = {"return_features": True} if fused_loss is not None else {}

        def apply(keep_kw, params, extra, inputs, rng):
            rngs = {k: jax.random.fold_in(rng, i) for i, k in enumerate(bundle.rngs)}
            variables = {"params": params, **extra}
            if not collections:
                logits = bundle.module.apply(
                    variables, inputs, train=True, rngs=rngs, **apply_kw, **keep_kw
                )
                return logits, {}, jnp.zeros((), jnp.float32), {}
            logits, updates = bundle.module.apply(
                variables, inputs, train=True, rngs=rngs, mutable=collections,
                **apply_kw, **keep_kw
            )
            updates = dict(updates)
            sown = updates.pop("losses", {})
            aux = sum(
                (jnp.sum(jnp.asarray(v)) for v in jax.tree.leaves(sown)),
                jnp.zeros((), jnp.float32),
            )
            stats = (
                step_metrics({c: updates.pop(c, {}) for c in bundle.step_collections})
                if step_metrics is not None else {}
            )
            return logits, updates, aux, stats

        # What the backward finds kept, by rung, most kept first. "all": no
        # checkpoint, the compiler keeps what the backward reads. A rung the
        # bundle offers (`keep_rungs`: "block") is a static argument of the
        # module. "apply": the whole apply checkpointed, which holds what
        # "all" holds while the forward runs again and so is never chosen
        # for a module that names its blocks; it can still lower the peak of
        # one that does not (a loss over unfused logits).
        policies = {
            None: None,  # jax.checkpoint's default: save nothing
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.checkpoint_dots,
            "dots_no_batch": (
                jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            ),
        }

        def apply_of(rung):
            fn = functools.partial(
                apply, {"keep": rung} if rung in bundle.keep_rungs else {}
            )
            if rung == "apply":
                fn = jax.checkpoint(fn, policy=policies[tspec.remat_policy])
            return fn

        if tspec.remat_policy:
            rungs = ("apply",)  # an explicit policy is obeyed: no ladder
        elif tspec.remat:
            rungs = ("all", *(bundle.keep_rungs or ("apply",)))
        else:
            rungs = ("all",)  # no checkpoint, and a refusal is the user's

        param_dtype = self.param_dtype

        grad_accum = int(tspec.grad_accum) if tspec.grad_accum else 1
        if grad_accum < 1:
            raise ValueError(f"train.gradAccum must be >= 1, got {grad_accum}")
        # the divisibility contract is an automatic adjustment, not an
        # error: an elastic resize changes the batch-sharded mesh width, so
        # pick the smallest feasible accumulation >= the requested one that
        # keeps the global batch constant (microbatch = global/(g*shards))
        microbatches = global_batch // local_batch_slice(mesh)
        if microbatches % grad_accum != 0:
            requested = grad_accum
            grad_accum = next(
                (
                    g
                    for g in range(requested, microbatches + 1)
                    if microbatches % g == 0
                ),
                microbatches,
            )
            self._event(
                "grad_accum_adjusted",
                {
                    "requested": requested,
                    "effective": grad_accum,
                    "global_batch": global_batch,
                    "batch_shards": local_batch_slice(mesh),
                },
            )
        self.grad_accum = grad_accum

        # The step differentiates the leaves labelled `train` only (every
        # leaf, when the bundle names no `trainable_patterns`). Both halves
        # keep the tree's shape, with `None` (an empty subtree) where the
        # other half's leaves sit: a frozen kernel enters `loss_of` as a
        # value, and no weight gradient of it is ever asked of XLA.
        def split(params):
            return (
                jax.tree.map(
                    lambda label, x: x if label == "train" else None,
                    labels, params,
                ),
                jax.tree.map(
                    lambda label, x: None if label == "train" else x,
                    labels, params,
                ),
            )

        def merge(trained, frozen):
            return jax.tree.map(
                lambda label, t, f: t if label == "train" else f,
                labels, trained, frozen,
            )

        def grads_of(apply, trained, frozen, extra, batch, rng):
            """One microbatch: (loss, grads, new_extra, logits, stats);
            `grads` mirrors `trained`, the differentiated half of the
            parameters; `stats` is what the module sowed for the step's
            metrics (routed layers), empty for most models."""

            def loss_of(t):
                p = merge(t, frozen)
                compute_params = (
                    _cast_floats(p, compute_dtype)
                    if compute_dtype != param_dtype
                    else p
                )
                inputs = batch["inputs"]
                if jnp.issubdtype(inputs.dtype, jnp.floating):
                    inputs = inputs.astype(compute_dtype)
                logits, new_extra, aux, stats = apply(
                    compute_params, extra, inputs, rng
                )
                if fused_loss is not None:  # `logits` carries features
                    return (
                        fused_loss(compute_params, logits, batch) + aux,
                        (logits, new_extra, stats),
                    )
                return loss_fn(logits, batch) + aux, (logits, new_extra, stats)

            (loss, (logits, new_extra, stats)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(trained)
            return loss, grads, new_extra, logits, stats

        def step(apply, state: TrainState, batch):
            rng = jax.random.fold_in(jax.random.PRNGKey(seed), state.step)
            trained, frozen = split(state.params)

            if grad_accum == 1:
                loss, grads, new_extra, logits, stats = grads_of(
                    apply, trained, frozen, state.extra, batch, rng
                )
                acc_metric = (
                    accuracy_metric(logits, batch) if is_classification else None
                )
            else:
                # microbatch scan: grads accumulate in param dtype; ONE
                # optimizer update per step. The leading batch dim splits
                # [B] → [A, B/A]; XLA keeps the data-axis sharding on the
                # inner dim, so each microbatch is still mesh-parallel.
                micro = jax.tree.map(
                    lambda x: x.reshape(
                        grad_accum, x.shape[0] // grad_accum, *x.shape[1:]
                    ),
                    batch,
                )

                def one(carry, mb):
                    extra_c, grads_c, loss_c, acc_c, i = carry
                    # the sown stats of a microbatch are not carried: a step
                    # of several reports none
                    loss, grads, new_extra, logits, _ = grads_of(
                        apply, trained, frozen, extra_c, mb,
                        jax.random.fold_in(rng, i),
                    )
                    grads = _cast_floats(grads, param_dtype)
                    grads_c = jax.tree.map(jnp.add, grads_c, grads)
                    acc = (
                        accuracy_metric(logits, mb)
                        if is_classification
                        else jnp.zeros((), jnp.float32)
                    )
                    return (new_extra, grads_c, loss_c + loss, acc_c + acc, i + 1), None

                zero_grads = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, param_dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating)
                    else jnp.zeros_like(x),
                    trained,
                )
                carry, _ = jax.lax.scan(
                    one,
                    (
                        state.extra,
                        zero_grads,
                        jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.int32),
                    ),
                    micro,
                )
                new_extra, grads, loss, acc_sum, _ = carry
                grads = jax.tree.map(lambda g: g / grad_accum, grads)
                loss = loss / grad_accum
                acc_metric = acc_sum / grad_accum if is_classification else None
                stats = {}
            # grads come out in compute dtype; update math runs in param dtype
            grads = _cast_floats(grads, param_dtype)
            # the norm of the gradient the optimizer is given: under
            # `trainable_patterns` the adapters' (what `grad_clip_norm`
            # clips on), never a frozen kernel's
            grad_norm = optax.global_norm(grads).astype(jnp.float32)
            # `tx` takes the whole tree; a frozen leaf's gradient is a zero
            # that `set_to_zero` drops and XLA folds away
            grads = merge(grads, jax.tree.map(jnp.zeros_like, frozen))
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            metrics = {
                "loss": loss.astype(jnp.float32),
                "learning_rate": jnp.asarray(sched(state.step), jnp.float32),
                "grad_norm": grad_norm,
            }
            if acc_metric is not None:
                metrics["accuracy"] = acc_metric
            metrics.update(stats)
            return (
                TrainState(
                    step=state.step + 1,
                    params=params,
                    opt_state=opt_state,
                    extra=new_extra,
                ),
                metrics,
            )

        donate = (0,) if tspec.donate_state else ()

        def jit_step(rung):
            apply = apply_of(rung)

            def step_fn(state: TrainState, batch):  # the program `jit_step_fn`
                return step(apply, state, batch)

            return jax.jit(
                step_fn,
                in_shardings=(state_shardings, self.b_shard),
                out_shardings=(state_shardings, rep),
                donate_argnums=donate,
            )

        if len(rungs) == 1:
            self.train_step = jit_step(rungs[0])
        else:
            self.train_step = _RematLadder(
                {rung: jit_step(rung) for rung in rungs}, self._report_remat
            )

        def eval_fn(state: TrainState, batch):
            params = (
                _cast_floats(state.params, compute_dtype)
                if compute_dtype != param_dtype
                else state.params
            )
            inputs = batch["inputs"]
            if jnp.issubdtype(inputs.dtype, jnp.floating):
                inputs = inputs.astype(compute_dtype)
            variables = {"params": params, **state.extra}
            logits = bundle.module.apply(
                variables, inputs, train=False, **apply_kw
            )
            if fused_loss is not None:
                loss = fused_loss(params, logits, batch).astype(jnp.float32)
            else:
                loss = loss_fn(logits, batch).astype(jnp.float32)
            metrics = {"eval.loss": loss}
            if is_classification:
                metrics["eval.accuracy"] = accuracy_metric(logits, batch)
            # cross-entropy family (LM/MLM/seq2seq): loss is mean nats per
            # token, so perplexity is well-defined
            loss_name = self.tspec.loss or self.bundle.loss
            if "cross_entropy" in loss_name or loss_name == "masked_lm":
                metrics["eval.perplexity"] = jnp.exp(loss)
            return metrics

        self.eval_step = jax.jit(
            eval_fn,
            in_shardings=(state_shardings, self.b_shard),
            out_shardings=rep,
        )

    # -------------------------------------------------------------- loop
    def run(self) -> TrainResult:
        try:
            return self._run()
        finally:
            self.tracer.flush()  # a run that raises leaves its spans too

    def _run(self) -> TrainResult:
        from ..parallel.ring import set_current_mesh

        set_current_mesh(self.mesh)  # re-bind: another Trainer may have traced
        tspec = self.tspec
        log_every = max(1, int(tspec.log_every))
        ckpt_every = int(tspec.checkpoint_every) if tspec.checkpoint_every else 0
        start_step = 0
        if self.checkpoint_dir and tspec.resume:
            start_step = self.restore()
        history: list[dict] = []
        it = self.data.iterator
        metrics = {}
        pending: Optional[tuple[int, dict]] = None

        # prefetch: host batch prep + device_put run on a producer thread,
        # overlapping the device step — keeps the input pipeline off the
        # critical path (host-side generation was 14x the step time on v5e)
        import queue as _queue
        import threading as _threading

        n_steps = self.steps - start_step
        feed: _queue.Queue = _queue.Queue(maxsize=2)

        def _produce():
            try:
                for _ in range(n_steps):
                    feed.put(make_global_batch(next(it), self.mesh, self.b_shard))
            except BaseException as e:  # noqa: BLE001 — surface in consumer
                feed.put(e)

        producer = _threading.Thread(target=_produce, daemon=True)
        producer.start()

        eval_every = int(tspec.eval_every) if tspec.eval_every else 0
        eval_steps = int(tspec.eval_steps) if tspec.eval_steps else 4
        prof_start = (
            int(tspec.profile_start) if tspec.profile_start is not None else None
        )
        prof_stop = int(tspec.profile_stop) if tspec.profile_stop is not None else None
        self._profiling = False

        # dispatch back-pressure: the async dispatch queue must stay bounded
        # or queued steps exhaust XLA's collective thread pool on multi-device
        # CPU meshes (observed: abort at an all-reduce rendezvous with 7/8
        # threads after ~100 unflushed steps). Blocking on step N-K keeps K
        # steps in flight — deep enough that dispatch never stalls the device,
        # shallow enough that the host can't run away.
        import collections as _collections

        inflight: _collections.deque = _collections.deque()
        max_inflight = 4

        self._init_throughput_facts()
        step_hist = self.telemetry.histogram(
            "trainer.step_seconds", help="Per-step walltime"
        )
        wait_hist = self.telemetry.histogram(
            "trainer.data_wait_seconds",
            help="Per-step time blocked on the input pipeline",
        )
        busy_hist = self.telemetry.histogram(
            "trainer.compute_seconds",
            help="Per-step walltime minus data wait",
        )
        steps_ctr = self.telemetry.counter(
            "trainer.steps", help="Training steps completed"
        )
        # process-global on purpose: /metricsz serves it, and it shows
        # whether async checkpointing keeps the step-loop stall near zero
        from ..telemetry import get_registry

        stall_hist = get_registry().histogram(
            "trainer.checkpoint_stall_ms",
            buckets=(0.1, 0.5, 1.0, 5.0, 10.0, 50.0, 100.0, 500.0,
                     1000.0, 5000.0),
            help="Step-loop stall per boundary save (async write), ms",
        )
        t0 = _now()
        self._win = {"t0": t0, "steps": 0, "wait": 0.0, "busy": 0.0}
        self._xla_logged = None
        for step in range(start_step, self.steps):
            # two-level span tree per iteration: data_wait + compute cover
            # the whole step body, so their durations sum to the step span
            # (the invariant tests/test_telemetry.py pins within 10%)
            with self.tracer.span("step", step=step) as step_span:
                inject("trainer.step", step=step)
                if preemption.requested():
                    self._preempt_exit(step, start_step)
                if prof_start is not None and step == prof_start and self.artifacts_dir:
                    self._start_profiler()
                with self.tracer.span("data_wait") as wait_span:
                    batch = feed.get()
                if isinstance(batch, BaseException):
                    raise batch
                # compute's children name where its time goes, as the
                # benchmark's own spans do: the call that enqueues the
                # step (tracing and compiling it the first time), the
                # wait that keeps max_inflight steps queued, the blocking
                # read of a log point's metrics, and evaluation
                with self.tracer.span("compute") as busy_span:
                    with self.tracer.span("dispatch"):
                        self.state, metrics = self.train_step(self.state, batch)
                    inflight.append(metrics["loss"])
                    if len(inflight) > max_inflight:
                        with self.tracer.span("backpressure"):
                            inflight.popleft().block_until_ready()
                    if (
                        self._profiling
                        and prof_stop is not None
                        and step + 1 >= prof_stop
                    ):
                        jax.block_until_ready(metrics["loss"])
                        self._stop_profiler()
                    if (step + 1) % log_every == 0 or step + 1 == self.steps:
                        # flush the previous log point first: keeps one step
                        # of pipelining so logging never stalls the device
                        if pending is not None:
                            with self.tracer.span("emit"):
                                self._emit(history, *pending)
                        pending = (step + 1, metrics)
                    if eval_every and (
                        (step + 1) % eval_every == 0 or step + 1 == self.steps
                    ):
                        with self.tracer.span("eval"):
                            eval_metrics = self._evaluate(eval_steps)
                        with self.tracer.span("emit"):
                            if pending is not None:
                                self._emit(history, *pending)
                                pending = None
                            self._emit(history, step + 1, eval_metrics)
                if ckpt_every and (step + 1) % ckpt_every == 0:
                    # the save is async (Orbax snapshots on device, writes in
                    # the background) — this span measures the REAL stall the
                    # step loop pays, which should stay near-zero
                    with self.tracer.span(
                        "checkpoint", step=step + 1
                    ) as ckpt_span:
                        try:
                            self.save(step + 1)
                        except Exception:
                            # a fault that surfaces in the save (a failed
                            # upload of an earlier boundary) must not cost
                            # the run the log point still held back
                            if pending is not None:
                                self._emit(history, *pending)
                            raise
                    stall_hist.observe(ckpt_span.dur_s * 1000.0)
            step_hist.observe(step_span.dur_s)
            wait_hist.observe(wait_span.dur_s)
            busy_hist.observe(busy_span.dur_s)
            steps_ctr.inc()
            self._win["steps"] += 1
            self._win["wait"] += wait_span.dur_s
            self._win["busy"] += busy_span.dur_s
        # loop-exit guard: when the profiler window end coincides with the
        # last step, the inner stop already ran — _stop_profiler is
        # idempotent, so the capture is never double-closed (previously a
        # raw second stop_trace() here raised out of an otherwise-healthy
        # run)
        self._stop_profiler()
        if pending is not None:
            self._emit(history, *pending)
        elapsed = _now() - t0
        steps_done = self.steps - start_step
        sps = steps_done / elapsed if elapsed > 0 else 0.0
        if self.checkpoint_dir and ckpt_every:
            self.save(self.steps, wait=True)
        final = dict(history[-1]) if history else {}
        final["steps_per_sec"] = sps
        final["examples_per_sec"] = sps * self.data.batch_size * jax.process_count()
        return TrainResult(
            state=self.state, history=history, steps_per_sec=sps, final_metrics=final
        )

    def _evaluate(self, eval_steps: int) -> dict:
        """Average eval metrics over `eval_steps` batches from a dedicated
        eval stream (own iterator: the training iterator is owned by the
        prefetch thread, and a distinct seed gives held-out data)."""
        if not hasattr(self, "_eval_data"):
            dspec = self.program.data
            # same seed (the synthetic task — prototypes/chain — must match
            # training); the shifted process_index decorrelates the sample
            # stream so eval batches differ from training batches
            self._eval_data = build_data(
                dspec.name if dspec else "synthetic",
                self.data.batch_size * jax.process_count(),
                dspec.config if dspec else None,
                seed=int(self.tspec.seed),
                process_index=jax.process_index() + 7919 * jax.process_count(),
                process_count=jax.process_count(),
            )
        totals: dict[str, float] = {}
        it = self._eval_data.iterator
        for _ in range(eval_steps):
            batch = make_global_batch(next(it), self.mesh, self.b_shard)
            m = self.eval_step(self.state, batch)
            for k, v in m.items():
                totals[k] = totals.get(k, 0.0) + float(v)
        return {k: v / eval_steps for k, v in totals.items()}

    # -------------------------------------------------------- telemetry
    def _start_profiler(self):
        if self._profiling:
            return
        trace_dir = Path(self.artifacts_dir) / "profile"
        jax.profiler.start_trace(str(trace_dir))
        self._profiling = True
        self.tracer.event("profiler.start", path=str(trace_dir))

    def _stop_profiler(self):
        """Idempotent capture-window close; registers the emitted trace
        directory as a run artifact so the profile is discoverable from
        the run's events, not just by knowing the outputs layout."""
        if not self._profiling:
            return
        jax.profiler.stop_trace()
        self._profiling = False
        trace_dir = Path(self.artifacts_dir) / "profile"
        self.tracer.event("profiler.stop", path=str(trace_dir))
        self._event(
            "artifact",
            {"kind": "profile", "path": "profile", "abs_path": str(trace_dir)},
        )

    def _report_differentiated(self, abstract_params, labels):
        """How many parameters the step takes a gradient of, and how many
        enter it as values only, the type the frozen half is stored in, and
        how many of its bytes are stored in the compute type where that is
        not the masters' (a LoRA step under `precision: mixed`; 0 else): an
        event and three gauges, at build."""
        leaves = jax.tree.leaves(abstract_params)
        frozen_leaves = [
            x for x, label in zip(leaves, jax.tree.leaves(labels)) if label != "train"
        ]
        frozen = sum(int(x.size) for x in frozen_leaves)
        trainable = sum(int(x.size) for x in leaves) - frozen
        frozen_type = next(
            (
                jnp.dtype(x.dtype).name
                for x in frozen_leaves
                if jnp.issubdtype(x.dtype, jnp.floating)
            ),
            None,
        )
        in_compute_type = (
            sum(
                int(x.size) * jnp.dtype(x.dtype).itemsize
                for x in frozen_leaves
                if x.dtype == self.compute_dtype
            )
            if self.compute_dtype != self.param_dtype
            else 0
        )
        self.telemetry.gauge("train.params_differentiated").set(trainable)
        self.telemetry.gauge("train.params_frozen").set(frozen)
        self.telemetry.gauge("train.params_frozen_compute_type_bytes").set(
            in_compute_type
        )
        self._event(
            "differentiated",
            {
                "trainable_params": trainable,
                "frozen_params": frozen,
                "frozen_dtype": frozen_type,
                "frozen_compute_type_bytes": in_compute_type,
            },
        )

    def _report_remat(self, choice: dict):
        """Which rung of the remat ladder runs, and what each rung tried
        cost: event `polyaxon.train.remat` (run store `remat`), gauges
        `train.remat.rung` (0 = `all`; -1 = every rung refused) and
        `train.remat.refused_compiles`. A refused answer's `seconds`, and
        `refused_seconds` over all of them, hold the refused rungs' lowering
        AND refused compile (`_RematLadder.attempt`); the `startup` record
        made next (`_report_startup`) is the one place that splits them."""
        import json

        refused = [t for t in choice["tried"] if t["result"] == "refused"]
        ladder = choice["ladder"]
        self.telemetry.gauge("train.remat.rung").set(
            ladder.index(choice["rung"]) if choice["rung"] else -1
        )
        self.telemetry.gauge("train.remat.refused_compiles").set(len(refused))
        self.tracer.event(
            "remat", rung=choice["rung"] or "", ladder=json.dumps(ladder),
            tried=json.dumps(choice["tried"]),
            refused_seconds=round(sum(t["seconds"] for t in refused), 3),
        )
        self._event("remat", choice)
        self._report_startup(choice)

    def _report_startup(self, choice: Optional[dict] = None):
        """The record of a finished set-up, made once: when the ladder has
        chosen (the step's executable exists), or for a plain `jax.jit`
        when `__init__` returns, with `before_trainer`, `build` and `init`
        alone (its lowering and compile are `xla.*_seconds`'). Every number
        is a difference of clock reads taken on calls set-up makes anyway.

        - gauges `train.startup.*_seconds` of the PROCESS-GLOBAL registry
          (as `xla.*`: `/metricsz` serves them and they outlive the
          Trainer): `before_trainer` the process's age at the entry of
          `__init__` (not set off Linux), `build` the whole of `__init__`,
          `step_lower` every rung tried traced and lowered, `step_compile`
          the compile, or the load from the persistent cache, of the rung
          that runs, `refused_compile` the compiles of the rungs refused
          (0.0 where none was);
        - run-store event `startup`, tracer event `polyaxon.train.startup`:
          the same numbers under the gauges' names, `init_seconds` (the
          host's time to trace, compile or load, and dispatch the two
          programs that make the state: not waited for), `cache` and `rung`
          of the step that runs, and `total_s`, the process's age now;
        - the spans, written after the fact (`SpanTracer.record_span`; no
          annotations): `build` > `init`, and `first_step` > one `rung` a
          rung tried (`rung`, `result`, `bytes` or the compiler's line) >
          `lower`, `compile` (`cache`);
        - `startup_*` at the Trainer's first log point, once."""
        import time

        started = self._startup
        wall = time.time() - _now()  # the metrics clock -> wall clock
        entered, built, (init0, init1) = started["entered"], started["built"], started["init"]
        record = {
            "before_trainer_seconds": started["age"],
            "build_seconds": round(built - entered, 3),
            "init_seconds": round(init1 - init0, 3),
        }
        span = self.tracer.record_span
        build = span("build", wall + entered, built - entered)
        span("init", wall + init0, init1 - init0, build)
        if choice is not None:
            ladder, tried = self.train_step, choice["tried"]
            runs = next((t for t in tried if t["result"] == "fits"), {})
            refused = [t["compile_seconds"] for t in tried if t["result"] == "refused"]
            record.update(
                step_lower_seconds=round(sum(t["lower_seconds"] for t in tried), 3),
                step_compile_seconds=runs.get("compile_seconds"),
                refused_compile_seconds=round(sum(refused, 0.0), 3),
                cache=runs.get("cache"),
                rung=choice["rung"],
            )
            first = span(
                "first_step", wall + ladder.began, _now() - ladder.began,
                rung=choice["rung"] or "", rungs_tried=len(tried),
            )
            for t in tried:
                t0, t1, t2 = ladder.clock[t["rung"]]
                rung_id = span(
                    "rung", wall + t0, t2 - t0, first,
                    **{k: t[k] for k in ("rung", "result", "bytes", "compiler")
                       if t.get(k) is not None},
                )
                span("lower", wall + t0, t1 - t0, rung_id)
                span("compile", wall + t1, t2 - t1, rung_id, cache=t["cache"])
        record["total_s"] = process_age()
        record = {k: v for k, v in record.items() if v is not None}
        gauges = get_registry()
        for k in _STARTUP_GAUGES:
            if k in record:
                gauges.gauge(f"train.startup.{k}").set(record[k])
        self.tracer.event("startup", **record)
        self._event("startup", record)
        self._startup_log = {
            f"startup_{k}": float(v) for k, v in record.items() if isinstance(v, (int, float))
        }

    def _report_layers(self):
        """What each layer of a decoder is (mixer: attention or mamba; kind,
        heads, rope or a Mamba layer's heads, head width, state, conv, chunk
        and groups; dense or routed, experts held of published): one event at
        build, on the run store and as `polyaxon.model.layers` in the tracer's
        ring and any profiler capture. A model with Mamba layers adds
        `polyaxon.model.ssm` (run store `model_ssm`): the scan's chunk count
        and the bytes of its largest intermediate for the step's shape, and
        per Mamba layer which path each fused chain of the mixer takes
        (`ops/mamba_fused.py`: `pallas` with its tiles, or `xla` and why). A
        model with KDA layers adds `polyaxon.model.kda` (run store
        `model_kda`): the delta-rule scan's chunk, sub-block, chunk count,
        heads walked at a time and largest intermediate, and per KDA layer
        the path of its three short convolutions. A model with
        power-retention layers adds `polyaxon.model.retention` (run store
        `model_retention`): the scan's chunk, chunks and run, query heads a
        walk step, one layer's carried state and the largest intermediate in
        bytes (of the `xla` path), the feature map's form and width, and per
        retention layer the scan's path (`ops/power_retention_fused.py`:
        `pallas` with its run, heads a grid step, saved states' bytes and
        VMEM limit, or `xla` and why)."""
        cfg = getattr(self.bundle.module, "cfg", None)
        if cfg is None or not hasattr(cfg, "layer"):
            return
        import json

        from ..telemetry.spans import get_tracer

        layers = [cfg.layer(i).describe(cfg) for i in range(cfg.n_layers)]
        get_tracer().event(
            "model.layers", n_layers=len(layers), layers=json.dumps(layers)
        )
        self._event("model_layers", {"layers": layers})
        rows = max(  # a device's rows of the global batch
            1, self.data.batch_size * jax.process_count() // local_batch_slice(self.mesh)
        )
        seq = int(self.data.meta.get("seq_len") or cfg.seq_len)
        if any(layer["mixer"] == "mamba" for layer in layers):
            from ..ops.mamba_fused import conv_plan, gate_plan
            from ..ops.ssd import heads_per_step, largest_intermediate_bytes

            shape = (rows, seq, cfg.mamba_chunk_size)
            inner = cfg.mamba_n_heads * cfg.mamba_d_head
            conv_width = inner + 2 * cfg.mamba_n_groups * cfg.mamba_d_state
            fused = {  # the same for every Mamba layer: they share their widths
                "conv_silu": conv_plan(
                    seq, conv_width, self.compute_dtype, inner, cfg.mamba_d_conv
                ),
                "gate_norm": gate_plan(seq, inner, self.compute_dtype),
            }
            ssm = {
                "rows": rows, "seq_len": seq, "chunk": cfg.mamba_chunk_size,
                "chunks": seq // cfg.mamba_chunk_size,
                "heads_per_step": heads_per_step(
                    *shape, cfg.mamba_n_heads // cfg.mamba_n_groups
                ),
                "largest_intermediate_bytes": largest_intermediate_bytes(
                    *shape, cfg.mamba_n_heads, cfg.mamba_n_groups
                ),
                "fused": [
                    {"layer": i, **fused}
                    for i, layer in enumerate(layers) if layer["mixer"] == "mamba"
                ],
            }
            get_tracer().event("model.ssm", **{**ssm, "fused": json.dumps(ssm["fused"])})
            self._event("model_ssm", ssm)
        kda_layers = [(i, layer) for i, layer in enumerate(layers) if layer["mixer"] == "kda"]
        if kda_layers:
            from ..ops import kda as kda_ops
            from ..ops.kda_fused import scan_plan
            from ..ops.mamba_fused import conv_plan

            heads, width = kda_layers[0][1]["heads"], kda_layers[0][1]["key_width"]  # of all alike
            shape = (rows, seq, cfg.kda_chunk_size, heads, width,
                     jnp.dtype(self.compute_dtype).itemsize)
            conv = conv_plan(seq, heads * width, self.compute_dtype, 0, cfg.kda_conv)
            scan = scan_plan(rows, seq, cfg.kda_chunk_size, heads, width, width,
                             self.compute_dtype)
            kda = {
                "rows": rows, "seq_len": seq, "chunk": cfg.kda_chunk_size,
                "sub_block": kda_ops.SUB, "chunks": seq // cfg.kda_chunk_size,
                # what the `xla` path walks; the kernels keep a state a run
                "heads_per_step": kda_ops.heads_per_step(*shape),
                "largest_intermediate_bytes": kda_ops.largest_intermediate_bytes(*shape),
                "layers": [{"layer": i, "conv_silu": conv, "scan": scan} for i, _ in kda_layers],
            }
            get_tracer().event("model.kda", **{**kda, "layers": json.dumps(kda["layers"])})
            self._event("model_kda", kda)
        held = [i for i, layer in enumerate(layers) if layer["mixer"] == "power_retention"]
        if held:
            from ..ops import power_retention as pr
            from ..ops.power_retention_fused import scan_plan as retention_plan

            heads, p, chunk = cfg.layer(held[0]).n_heads, cfg.head_size, cfg.retention_chunk_size
            chunks = -(-seq // chunk)
            scan = retention_plan(rows, seq, chunk, heads, cfg.n_kv_heads, p, self.compute_dtype)
            retention = {
                "rows": rows, "seq_len": seq, "chunk": chunk, "chunks": chunks,
                # the `xla` path's walk; the kernels' own are each layer's `scan`
                "run": pr.run_length(chunks),
                "heads_per_step": heads // cfg.n_kv_heads,  # a key-value group's
                "state_bytes_per_layer": rows * pr.state_bytes(heads, p),
                "largest_intermediate_bytes": pr.largest_intermediate_bytes(
                    chunk, heads // cfg.n_kv_heads, p, jnp.dtype(self.compute_dtype).itemsize
                ),
                # the symmetric square in blocks of `phi_block` channels
                "phi": "symmetric", "phi_block": pr.FEATURE_BLOCK,
                "feature_width": pr.feature_width(p),
                "layers": [{"layer": i, "scan": scan} for i in held],
            }
            get_tracer().event(
                "model.retention", **{**retention, "layers": json.dumps(retention["layers"])}
            )
            self._event("model_retention", retention)
        self._report_flash_tiles(cfg)

    def _report_flash_tiles(self, cfg):
        """What the flash kernels of this model run, per distinct call shape
        (sequence, head width, GQA group, window) and kernel: the blocks the
        kernel chose (or was given) and its walk's counts of one head (grid
        steps, live steps, steps that mask, executed over required pairs).
        One event at build, on the run store (`flash_tiles`) and as
        `polyaxon.kernels.flash_tiles` beside `polyaxon.model.layers`; none
        where attention does not run the flash kernels."""
        import json

        from ..ops.attention import resolve_auto_backend
        from ..ops.flash_attention import tile_report
        from ..telemetry.spans import get_tracer

        seq = int(self.data.meta.get("seq_len") or cfg.seq_len)
        backend = cfg.attention
        if backend == "auto":
            backend = resolve_auto_backend(seq, cfg.attention_block, cfg.head_size)
        if backend != "flash":
            return
        specs = [cfg.layer(i) for i in range(cfg.n_layers)]
        # (score width, value width, GQA group, window) of each distinct call:
        # a latent-attention layer's heads each have keys of their own
        shapes = sorted(
            {(cfg.head_size, cfg.head_size, s.n_heads // cfg.n_kv_heads, s.window)
             for s in specs if s.mixer == "attention"}
            | {(cfg.mla_nope_dim + cfg.mla_rope_dim, cfg.mla_value_dim, 1, 0)
               for s in specs if s.mixer == "mla"}
        )
        calls = [
            call
            for width, value, group, window in shapes
            for call in tile_report(
                seq, width, group, window or None, self.compute_dtype,
                block_kv=cfg.attention_block, value_dim=value,
            )
        ]
        get_tracer().event("kernels.flash_tiles", n_calls=len(calls), calls=json.dumps(calls))
        self._event("flash_tiles", {"calls": calls})

    def _init_throughput_facts(self):
        """Static facts behind the tokens/s and MFU gauges: tokens per
        step (token tasks only) and the analytic step FLOPs (transformer
        cfg only) — None disables the corresponding gauge rather than
        reporting a wrong number."""
        self._tokens_per_step = None
        self._flops_per_step = None
        cfg = getattr(self.bundle.module, "cfg", None)
        if self.bundle.task not in ("lm", "mlm") or cfg is None:
            return
        seq = self.data.meta.get("seq_len") or getattr(cfg, "seq_len", None)
        if not seq:
            return
        global_batch = self.data.batch_size * jax.process_count()
        self._tokens_per_step = global_batch * int(seq)
        if getattr(cfg, "layers", ()) or getattr(cfg, "n_experts", 0):
            # the count below takes every held weight as touched by every
            # token and one head count for all layers: wrong for routed
            # experts and for layers that differ, so no `mfu` gauge there
            return
        try:
            from ..parallel.sharding import _path_str

            flat = jax.tree_util.tree_flatten_with_path(self.state.params)[0]
            labels = (
                jax.tree.leaves(self._train_labels)
                if self._train_labels is not None
                else ["train"] * len(flat)
            )
            frozen = trainable = 0
            for (path, x), label in zip(flat, labels):
                # weights that enter a product: not norm scales or biases
                # (by a layer's own rank: `scan_layers` stacks one axis
                # before it, pipeline stages two), and not a table that is
                # only looked up
                where = _path_str(path)
                stacked = (
                    2 if where.startswith("pipeline/")
                    else 1 if where.startswith("layers/")
                    else 0
                )
                looked_up = where.endswith("embedding") and not getattr(
                    cfg, "tie_embeddings", False
                )
                if x.ndim - stacked < 2 or looked_up:
                    continue
                if label == "train":
                    trainable += x.size
                else:
                    frozen += x.size
            head_dim = getattr(cfg, "head_dim", None) or cfg.dim // cfg.n_heads
            self._flops_per_step = required_train_step_flops(
                frozen, trainable, cfg.n_layers, cfg.n_heads * head_dim,
                int(seq), self._tokens_per_step,
                causal=self.bundle.task == "lm",
            )
        except (AttributeError, TypeError):
            pass

    def _drain_window(self) -> dict:
        """Derived rates since the last log point: steps/s, tokens/s, MFU
        against the device generation's peak FLOPs, and the fraction of
        walltime blocked on the input pipeline. Resets the window, so an
        eval emit immediately after a train emit adds nothing."""
        w = self._win
        dt = _now() - w["t0"]
        if not w["steps"] or dt <= 0:
            return {}
        out = {}
        sps = w["steps"] / dt
        busy = w["wait"] + w["busy"]
        if busy > 0:
            out["data_wait_frac"] = w["wait"] / busy
        if self._tokens_per_step:
            out["tokens_per_sec"] = sps * self._tokens_per_step
        if self._flops_per_step:
            mfu = _mfu_of(
                sps * self._flops_per_step,
                self.mesh.devices.flat[0].device_kind,
                self.mesh.devices.size,
            )
            if mfu is not None:
                out["mfu"] = mfu
        self._win = {"t0": _now(), "steps": 0, "wait": 0.0, "busy": 0.0}
        return out

    def _hbm_gauges(self):
        """Device HBM occupancy via memory_stats() — registry gauges only
        (the per-run store copies stay SystemMonitor's job)."""
        from ..tracking.monitors import device_metrics

        for name, val in device_metrics().items():
            self.telemetry.gauge(name).set(val)

    def _emit(self, history, step, metrics):
        vals = {k: float(v) for k, v in metrics.items()}
        if vals.get("moe.overflow", 0.0) > 0:
            # a routed layer's buffer was too short and assignments were left
            # out: the step computed another model's loss
            raise RuntimeError(
                f"step {step}: {vals['moe.overflow']:.0f} assignments to held "
                "experts did not fit the routed layers' buffer "
                "(model.config.expert_buffer_factor is too small for this "
                "load): nothing may be dropped, so the run stops"
            )
        vals.update(self._drain_window())
        for k, v in vals.items():
            self.telemetry.gauge(f"train.{k}").set(v)
        # what the process has traced, lowered and compiled (or loaded from
        # the compile cache) so far: the same series /statsz `xla` shows,
        # and in the log only where it moved (the first log point, a first
        # evaluation)
        xla = compiles.mirror(self.telemetry)
        if xla != self._xla_logged:
            self._xla_logged = xla
            vals.update((f"xla_{k}", float(v)) for k, v in xla.items())
        vals.update(self._startup_log)  # the finished set-up, once
        self._startup_log = {}
        self._hbm_gauges()
        history.append({"step": step, **vals})
        self.log_fn(step, vals)
        self.tracer.flush()

    def _event(self, kind: str, body: dict):
        """Lifecycle events (preempted/resumed/checkpoint_fallback) to the
        run store; advisory — an event sink fault never fails training."""
        if self.event_fn is None:
            return
        try:
            self.event_fn(kind, body)
        except Exception:  # noqa: BLE001
            pass

    def _preempt_exit(self, step: int, start_step: int):
        """SIGTERM landed: flush a checkpoint at the current boundary and
        raise `Preempted` so the supervisor restarts us warm instead of
        counting a failure. `step` steps are complete when the loop head
        observes the flag, so the saved step IS the resume point."""
        saved = None
        if self.checkpoint_dir:
            saved = self._checkpoint_tiers().latest_step()
            if step > start_step and (saved or 0) < step:
                self.save(step, wait=True)
                saved = step
        self._event(
            "preempted", {"step": step, "resume_step": int(saved or 0)}
        )
        raise Preempted(
            f"SIGTERM preemption notice at step {step}", step=saved
        )

    def close(self):
        """Release data-pipeline resources (native prefetch threads, corpus
        mmaps) deterministically. Long-lived agent processes run many
        trainers; GC-time __del__ on the native loader is best-effort and
        can outlive the run — the executor/worker call this on teardown."""
        self.data.shutdown()
        if hasattr(self, "_eval_data"):
            self._eval_data.shutdown()
        self.tracer.close()

    # -------------------------------------------------------------- ckpt
    def _ckpt_keep(self) -> Optional[int]:
        return (
            int(self.tspec.checkpoint_keep)
            if self.tspec.checkpoint_keep
            else None
        )

    def _checkpoint_tiers(self):
        if self._tiers is None and self.checkpoint_dir:
            from .checkpoint import CheckpointTiers

            self._tiers = CheckpointTiers(
                self.checkpoint_dir,
                local=self.local_checkpoint_dir,
                keep=self._ckpt_keep(),
            )
        return self._tiers

    def save(self, step: int, wait: bool = False):
        self._checkpoint_tiers().save(step, self.state, wait=wait)

    def restore(self) -> int:
        # the newest intact step across BOTH tiers: durable copy preferred,
        # local copy as fallback (a kill mid-upload leaves the newest step
        # local-only), corrupt copies quarantined per tier
        state, step, corrupt, tier = self._checkpoint_tiers(
        ).restore_latest_intact(self.state)
        if corrupt:
            self._event(
                "checkpoint_fallback",
                {
                    "corrupt_steps": sorted({s for _t, s in corrupt}),
                    "corrupt_copies": [[t, s] for t, s in corrupt],
                    "restored_step": step,
                },
            )
        if step > 0:
            self.state = state
            self._event("resumed", {"step": step, "tier": tier})
        return step


def _opt_state_shardings(tx, params, p_shard, mesh):
    """Optimizer state shards like the params it mirrors. Moment trees embed
    the param path in their own leaf paths (e.g. `0/mu/dense_0/kernel`), so
    the model's regex rules apply transitively; scalar leaves (step counts)
    fall through to replication."""
    from ..parallel.sharding import param_shardings as _ps

    shape = jax.eval_shape(tx.init, params)
    rules = _rules_from(p_shard)
    return _ps(shape, rules, mesh)


def _rules_from(p_shard):
    """Recover (path-regex, axes) rules from a resolved param-sharding tree —
    exact escaped paths anchored at the end, so moment-tree prefixes match."""
    import re as _re

    rules = []
    def add(path, sh):
        from ..parallel.sharding import _path_str

        axes = tuple(
            ax if not isinstance(ax, tuple) else ax for ax in (sh.spec or ())
        )
        if any(a is not None for a in axes):
            rules.append((_re.escape(_path_str(path)) + "$", axes))
        return sh

    jax.tree_util.tree_map_with_path(add, p_shard)
    return tuple(rules)
