"""The whole training step of a benchmark cell, compiled by the chip's own
compiler for a TPU v5e that is described, not attached: what the step needs
of the device's memory (`memory_analysis`) and how long it takes to trace
and lower, with no chip time. Nothing executes.

    JAX_PLATFORMS=cpu python scripts/step_memory.py <cell> [--rungs] [--rows N] [--lower-only] [--repo <checkout>]

Under `train.remat: true` the Trainer's step is a ladder of rungs (PR 32:
`all`, then `block`): the line names the rung the Trainer chose for the
described device and what each rung it tried answered; `--rungs` compiles
every rung and gives the compiler's bytes or its refusal for each. `--rows N`
sizes the step at N rows in place of the cell's. `--repo` reads another
checkout (a `git archive` of the parent, say), so two trees can be compared.
The Trainer is built on the described device with
`jax.jit` and `jax.device_put` replaced, while it builds, by stand-ins that
return shapes placed as the real calls would place arrays (a described
device cannot hold one); the step itself is the Trainer's own `train_step`,
lowered from those shapes. PR 29 found the result equal to the byte to what
the chip's traced runs print; `PERF.md` section 4 has the table read with it.
"""

import argparse
import importlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--rungs", action="store_true", help="compile every rung of the remat ladder")
    ap.add_argument("--rows", type=int, help="rows a step, in place of the cell's")
    args = ap.parse_args()
    sys.path.insert(0, args.repo)
    os.chdir(args.repo)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    device = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]

    from polyaxon_tpu.ops import flash_attention

    flash_attention._interpret = lambda: False  # lower the kernels for Mosaic
    for kernels in ("mamba_fused", "kda_fused"):
        try:  # wraps its kernels in `jax.jit` as it is imported: before the stand-in below
            importlib.import_module(f"polyaxon_tpu.ops.{kernels}")
        except ImportError:  # a `--repo` from before PR 34 or PR 36
            pass
    from cellbench.drivers import train as driver
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import V1Program

    real_jit, real_put = jax.jit, jax.device_put

    class ShapesOnly:
        """`jax.jit(fn, ...)` that answers a call with the shapes `fn` would
        give, placed by `out_shardings`, and lowers like the real thing."""

        def __init__(self, fn, **kw):
            self.fn, self.kw, self.lower = fn, kw, real_jit(fn, **kw).lower

        def __call__(self, *a):
            out, places = jax.eval_shape(self.fn, *a), self.kw.get("out_shardings")
            if places is None:
                return out
            return jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), out, places
            )

    def put_shapes(x, sharding=None, **_):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a), sharding=sharding), x
        )

    cell = json.load(open(f"cellbench/workloads/{args.cell}.json"))
    if args.rows:
        cell["traffic"]["rows"] = cell["program"]["data"]["batchSize"] = args.rows
    ctx = argparse.Namespace(
        cell=cell, config=json.load(open(f"cellbench/configs/{cell['config']}.json")), seed=7
    )
    jax.jit = lambda fn=None, **kw: ShapesOnly(fn, **kw) if fn else (lambda f: ShapesOnly(f, **kw))
    jax.device_put = put_shapes
    try:
        t0 = time.time()
        trainer = Trainer(V1Program.model_validate(driver.program_spec(ctx)), devices=[device])
        build_s = time.time() - t0
    finally:
        jax.jit, jax.device_put = real_jit, real_put
    rows, seq = int(cell["traffic"]["rows"]), int(cell["traffic"]["seq_len"])
    batch = {
        k: jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=trainer.b_shard)
        for k in ("inputs", "labels")
    }
    step = trainer.train_step
    ladder = getattr(step, "steps", None)  # None: a plain `jax.jit`, no ladder
    out = {"cell": args.cell, "repo": args.repo, "rows": rows, "build_s": round(build_s, 1)}
    t0 = time.time()
    if ladder is None or args.lower_only:
        lowered = (step if ladder is None else ladder["all"]).lower(trainer.state, batch)
        out["trace_and_lower_s"] = round(time.time() - t0, 1)
        if not args.lower_only:
            ma = lowered.compile().memory_analysis()
            out.update(
                total=ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
                arguments=ma.argument_size_in_bytes, temporaries=ma.temp_size_in_bytes,
                aliased=ma.alias_size_in_bytes,
            )
    else:
        try:
            step.lower(trainer.state, batch)  # the Trainer's own choice, by compiling
        except jax.errors.JaxRuntimeError:
            pass  # every rung refused: `tried` has each refusal
        out.update(ladder=list(ladder), rung=step.rung, tried=list(step.tried),
                   choose_s=round(time.time() - t0, 1))
        if args.rungs:  # and what the rungs under the chosen one would answer
            out["tried"] += [
                step.attempt(rung, trainer.state, batch)[0]
                for rung in list(ladder)[len(step.tried):]
            ]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
