"""Driver for training cells: the step `polyaxon run` builds
(`Trainer._build_step`), fed and called as the Trainer's own loop feeds and
calls it, on weights and batches the benchmark makes from the seed.

Set-up builds ONE object, the Trainer with its compiled step and state,
drives it through the first `reference.steps` steps, and hands that same
object to the window. The comparison that decides `correct` follows those
first steps with the plain reference, after the window has closed, the
memory peak has been read and the program's state is freed.
"""

from __future__ import annotations

import collections
import queue
import re
import statistics
import threading
import time

import numpy as np

from cellbench import compare, traffic, weights
from cellbench.common import ref_to_program_paths

FAULTS = ("state_unchanged", "half_batch")


def program_spec(ctx) -> dict:
    """The `program` of a Polyaxonfile: the configuration's model block
    verbatim, the cell's additions to it, and the cell's data, optimizer and
    train sections with the seed."""
    spec = ctx.cell["program"]
    train = dict(spec["train"], seed=weights.fold_seed(ctx.seed))
    return {
        "model": {
            "name": ctx.config["model_name"],
            "config": {**ctx.config["model"], **spec.get("model_extra", {})},
        },
        "data": spec["data"],
        "optimizer": spec["optimizer"],
        "train": train,
    }


class Feed:
    """Host batches made and put on the device by a producer thread, two
    ahead, as `Trainer.run` does it."""

    def __init__(self, gen, sharding):
        import jax

        self.q: queue.Queue = queue.Queue(maxsize=2)
        self.stop = threading.Event()

        def produce():
            for batch in gen:
                item = jax.device_put(batch, sharding)
                while not self.stop.is_set():
                    try:
                        self.q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self.stop.is_set():
                    return

        self.thread = threading.Thread(target=produce, daemon=True)
        self.thread.start()

    def get(self):
        return self.q.get()

    def close(self):
        self.stop.set()
        self.thread.join(timeout=10)


def capture(trainer) -> dict:
    """Shapes, types and placement of the Trainer's state, taken once; the
    program's own initial values are then dropped."""
    import jax

    old = trainer.state
    cap = {
        "abstract": jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), old.params),
        "o_shard": jax.tree.map(lambda x: x.sharding, old.opt_state),
        "step_sharding": old.step.sharding,
        "template": old,
    }
    cap["shapes"] = {
        weights.path_str(p): (tuple(a.shape), a.dtype)
        for p, a in jax.tree_util.tree_flatten_with_path(cap["abstract"])[0]
    }
    drop_state(trainer)
    return cap


def drop_state(trainer) -> None:
    import jax

    for leaf in jax.tree.leaves((trainer.state.params, trainer.state.opt_state)):
        if not leaf.is_deleted():
            leaf.delete()


def seed_state(trainer, cap: dict, seed: int, rules) -> None:
    """The Trainer's state with the benchmark's weights in it: same tree,
    same types, same placement; a fresh optimizer state over them."""
    import jax

    params = weights.tree(seed, cap["abstract"], rules, shardings=trainer.p_shard)
    opt_state = jax.jit(trainer.tx.init, out_shardings=cap["o_shard"])(params)
    step = jax.device_put(np.zeros((), np.int32), cap["step_sharding"])
    trainer.state = cap["template"].replace(step=step, params=params, opt_state=opt_state)


def trainable_leaves(tree, pattern: str) -> dict:
    """path -> host array, for the leaves whose path matches `pattern`."""
    import jax

    rx = re.compile(pattern)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    picked = {weights.path_str(p): x for p, x in flat if rx.search(weights.path_str(p))}
    return {k: np.asarray(v) for k, v in jax.device_get(picked).items()}


def first_moments(opt_state, pattern: str) -> dict:
    """Adam's first moment of the trainable leaves, by the parameter's path."""
    import jax

    rx = re.compile(pattern)
    flat = jax.tree_util.tree_flatten_with_path(opt_state)[0]
    out = {}
    for p, x in flat:
        s = weights.path_str(p)
        if "/mu/" in f"/{s}" and rx.search(s):
            out[f"/{s}".split("/mu/", 1)[1]] = x
    return {k: np.asarray(v) for k, v in jax.device_get(out).items()}


def run_reference(ctx, shapes: dict, seed: int, products: str = "float32",
                  half_batch: bool = False):
    """The plain reference over the first steps, from the seed alone."""
    import jax
    import jax.numpy as jnp

    ref = ctx.reference()
    spec = ctx.cell["reference"]
    d = ref.Dims.from_published(ctx.config)
    rules = ctx.config["init"]
    paths = ref_to_program_paths(ctx.config, d.layers)

    def get(name):
        shape, _ = shapes[paths[name]]
        return weights.leaf(seed, paths[name], shape, jnp.float32, rules)

    lora = [
        {t: {ab: get(f"layers.{i}.{t}.{ab}") for ab in ("lora_a", "lora_b")}
         for t in spec["lora"]["targets"]}
        for i in range(d.layers)
    ]
    gen = traffic.generator(ctx.cell["traffic"]["generator"])(
        ctx.cell["traffic"], d.vocab, seed
    )
    batches = []
    for _ in range(int(spec["steps"])):
        b = next(gen)
        tok, lab = b["inputs"], b["labels"]
        if half_batch:
            tok, lab = tok[: len(tok) // 2], lab[: len(lab) // 2]
        batches.append((jnp.asarray(tok), jnp.asarray(lab)))
    scale = spec["lora"]["alpha"] / spec["lora"]["rank"]
    with jax.default_matmul_precision("highest"):
        losses, grads, final = ref.train_steps(
            get, d, lora, batches, scale=scale, adamw=spec["adamw"], products=products
        )

    def named(tree_):
        out = {}
        for i, layer in enumerate(tree_):
            for t, ab in layer.items():
                for k, v in ab.items():
                    out[paths[f"layers.{i}.{t}.{k}"]] = np.asarray(v)
        return out

    return {"losses": losses, "grads": named(grads), "final": named(final),
            "initial": named(lora)}


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared: each step's loss, the first gradient's norm and
    the parameters' change, both by the worst leaf."""
    out = {
        f"loss_step{i + 1}": compare.rel_gap(p, r)
        for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))
    }
    out["grad1_worst_leaf"], where_g = compare.worst_norm_gap(prog["grads"], ref["grads"])
    out["grad1_direction"] = compare.one_minus_cos(prog["grads"], ref["grads"])
    out["grad1_diff_worst_leaf"], _ = compare.worst_rel_diff(prog["grads"], ref["grads"])
    skip = compare.tiny_gradient_leaves(ref["grads"])
    change = lambda side: {  # noqa: E731
        k: np.asarray(side["final"][k], np.float64) - np.asarray(ref["initial"][k], np.float64)
        for k in ref["final"]
    }
    out["change_worst_leaf"], where_c = compare.worst_norm_gap(
        change(prog), change(ref), skip=skip
    )
    return out, {"grad1_worst_leaf": where_g, "change_worst_leaf": where_c,
                 "leaves_skipped_tiny_gradient": len(skip)}


def first_steps(ctx, trainer, feed, call) -> dict:
    """The first `reference.steps` steps through the window's own call and
    feed, and what the comparison needs of them: each loss, the first
    gradient as the optimizer got it (Adam's first moment after one step,
    over 1 - b1), and the trainable leaves after the last."""
    import jax

    spec = ctx.cell["reference"]
    n_first, pattern = int(spec["steps"]), spec["trainable"]
    b1 = float(spec["adamw"]["b1"])
    prog = {"losses": []}
    t = time.perf_counter()
    for i in range(n_first):
        metrics = call(feed.get())
        prog["losses"].append(float(metrics["loss"]))
        if i == 0:
            mu = first_moments(trainer.state.opt_state, pattern)
            prog["grads"] = {k: v / (1.0 - b1) for k, v in mu.items()}
            ctx.log(f"first step (compiles in a cold checkout) {time.perf_counter() - t:.1f} s")
    prog["final"] = trainable_leaves(trainer.state.params, pattern)
    jax.block_until_ready(trainer.state)
    ctx.log(
        f"first {n_first} steps {time.perf_counter() - t:.1f} s; losses "
        + " ".join(f"{x:.6f}" for x in prog["losses"])
    )
    return prog


def make_feed(ctx, trainer, seed: int, fault: str | None = None):
    cell = ctx.cell
    gen = traffic.generator(cell["traffic"]["generator"])(
        cell["traffic"], int(ctx.config["vocab_size"]), seed
    )
    if fault == "half_batch":
        half = int(cell["traffic"]["rows"]) // 2

        def halved(g):
            for b in g:
                # the second half of the rows left out: masked from the
                # loss, so that the mean is over the rest
                lab = b["labels"].copy()
                lab[half:] = -100
                yield {"inputs": b["inputs"], "labels": lab}

        gen = halved(gen)
    return Feed(gen, trainer.b_shard)


def build(ctx, devices):
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import V1Program

    from cellbench.common import cache_everything

    t = time.perf_counter()
    trainer = Trainer(V1Program.model_validate(program_spec(ctx)))
    if devices[0].platform != "cpu":
        cache_everything()
    ctx.log(f"trainer built in {time.perf_counter() - t:.1f} s (the program's own init included)")
    return trainer, capture(trainer)


def run(ctx, devices, fault: str | None = None) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from cellbench.common import Tracer, free_device, memory_peak

    cell, cfg = ctx.cell, ctx.config
    n_first = int(cell["reference"]["steps"])
    rows, seq = int(cell["traffic"]["rows"]), int(cell["traffic"]["seq_len"])
    tokens_per_step = rows * seq

    # ------------------------------------------------------------- set-up
    trainer, cap = build(ctx, devices)
    shapes = cap["shapes"]
    t = time.perf_counter()
    seed_state(trainer, cap, ctx.seed, cfg["init"])
    jax.block_until_ready(trainer.state.params)
    ctx.log(f"weights from the seed in {time.perf_counter() - t:.1f} s")
    feed = make_feed(ctx, trainer, ctx.seed, fault)
    step_fn = trainer.train_step
    if fault == "state_unchanged":
        real = step_fn

        def step_fn(state, batch):  # noqa: F811
            # the step is run and its result thrown away; the state handed
            # back is a copy of the one that came in (the real step donates)
            keep = jax.tree.map(lambda x: x.copy(), state)
            _, metrics = real(state, batch)
            return keep, metrics

    def call(batch):
        trainer.state, metrics = step_fn(trainer.state, batch)
        return metrics

    prog = first_steps(ctx, trainer, feed, call)
    before, tc0 = ctx.compiles.snapshot(), time.perf_counter()

    # ------------------------------------------------------------- window
    inflight: collections.deque = collections.deque()
    marks, steps = [], 0
    seconds = ctx.seconds
    if ctx.trace:
        seconds = min(seconds, float(cell.get("trace_seconds", seconds)))
    with Tracer(ctx) as tracer:
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_process
        with TraceAnnotation("cellbench.window"):
            while time.perf_counter() - t0 < seconds:
                with TraceAnnotation("cellbench.data_wait"):
                    batch = feed.get()
                with TraceAnnotation("cellbench.step_dispatch"):
                    metrics = call(batch)
                inflight.append(metrics["loss"])
                steps += 1
                if len(inflight) > 4:  # Trainer.run's max_inflight
                    with TraceAnnotation("cellbench.backpressure"):
                        inflight.popleft().block_until_ready()
                    marks.append(time.perf_counter())
            with TraceAnnotation("cellbench.drain"):
                jax.block_until_ready((trainer.state, metrics))
        t1 = time.perf_counter()
    window_s = t1 - t0
    feed.close()
    in_window, compiled = ctx.compiles.window_report(
        before, ctx.compiles.snapshot(), tc0, time.perf_counter()
    )
    last_loss = float(metrics["loss"])
    peak = memory_peak(devices)
    step_ms = [1e3 * (b - a) for a, b in zip(marks, marks[1:])]
    ctx.log(
        f"window {window_s:.3f} s: steps={steps} tokens={steps * tokens_per_step} "
        f"median_step_ms={statistics.median(step_ms) if step_ms else float('nan'):.2f} "
        f"last_loss={last_loss:.4f}"
    )
    ctx.log(compiled)
    ctx.log(f"peak_bytes_in_use={peak} (the allocator's counter: a floor, not the fit)")
    if ctx.trace:
        try:
            ma = trainer.train_step.lower(trainer.state, batch).compile().memory_analysis()
            total = (
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            )
            ctx.log(
                f"compiler memory_analysis of the step: total={total} "
                f"arguments={ma.argument_size_in_bytes} temporaries={ma.temp_size_in_bytes} "
                f"aliased={ma.alias_size_in_bytes}"
            )
        except Exception as e:  # noqa: BLE001 - a reading for a log line only
            ctx.log(f"compiler memory_analysis not available: {e!r}")
    trace = tracer.read()

    # ---------------------------------------- free the program, then compare
    trainer.close()
    del trainer, feed, inflight, metrics, batch
    left = free_device(devices)
    ctx.log(f"trainer closed and its arrays deleted; bytes_in_use={left}")
    t = time.perf_counter()
    ref = run_reference(ctx, shapes, ctx.seed)
    nums, where = numbers(prog, ref)
    ok, table = compare.verdict(nums, cell["limits"])
    ctx.log(
        f"reference over {n_first} steps in {time.perf_counter() - t:.1f} s; losses "
        + " ".join(f"{x:.6f}" for x in ref["losses"]) + f"; worst leaves {where}"
    )
    ctx.log("numbers read (those with a limit are compared): " + repr(nums))
    ok = ok and in_window["programs"] == 0 and steps > 0

    rate = steps * tokens_per_step / window_s
    obs = {
        "cell": cell, "config": cfg, "window_s": window_s, "steps": steps,
        "tokens": steps * tokens_per_step, "rows": rows, "seq_len": seq,
        "peaks": getattr(ctx, "peaks", None), "chips": int(ctx.entry["chips"]),
    }
    breakdown = None
    if trace is not None:
        from cellbench import trace_reduce

        obs["trace"], breakdown = trace_reduce.summarise(trace)
        obs["trace_raw"] = trace
        ctx.log(
            f"trace: busy_s={obs['trace']['busy_s']:.4f} of window_s={window_s:.4f}; "
            f"programs {trace_reduce.top(obs['trace']['modules'], 4)}"
        )
    return {
        "end_to_end": {"train_tokens_per_s": rate, "setup_s": setup_s},
        "observations": obs,
        "breakdown": breakdown,
        "correct": ok,
        "compared": table,
        "attempted": steps,
        "failed": 0,
        "memory_peak_bytes": peak,
    }


def readings(ctx, devices, seeds, control_seeds) -> list[dict]:
    """For setting limits (cellbench/calibrate.py): the numbers compared, on
    each seed, of the program against the reference; and on the control
    seeds, of the control (the reference with 8-bit products) and of the
    half-batch fault planted in the reference, each against the reference.
    One Trainer serves every seed. No window is measured."""
    import jax

    cfg = ctx.config
    trainer, cap = build(ctx, devices)

    def call(batch):
        trainer.state, metrics = trainer.train_step(trainer.state, batch)
        return metrics

    out = []
    for seed in seeds:
        seed_state(trainer, cap, seed, cfg["init"])
        feed = make_feed(ctx, trainer, seed)
        prog = first_steps(ctx, trainer, feed, call)
        feed.close()
        drop_state(trainer)
        del feed
        t = time.perf_counter()
        ref = run_reference(ctx, cap["shapes"], seed)
        row = {"seed": seed, "program": numbers(prog, ref)[0],
               "reference_s": time.perf_counter() - t,
               "losses": {"program": prog["losses"], "reference": ref["losses"]}}
        if seed in control_seeds:
            for name, kw in (("control_int8", {"products": "int8"}),
                             ("fault_half_batch", {"half_batch": True})):
                other = run_reference(ctx, cap["shapes"], seed, **kw)
                row[name] = numbers(other, ref)[0]
        ctx.log("readings " + repr(row))
        out.append(row)
    trainer.close()
    return out
