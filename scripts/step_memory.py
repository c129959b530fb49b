"""The whole training step of a benchmark cell, compiled by the chip's own
compiler for a TPU v5e that is described, not attached: what the step needs
of the device's memory (`memory_analysis`) and how long it takes to trace
and lower, with no chip time. Nothing executes.

    JAX_PLATFORMS=cpu python scripts/step_memory.py <cell> [--lower-only] [--repo <checkout>]

`--repo` reads another checkout (a `git archive` of the parent, say), so two
trees can be compared. The Trainer is built on the described device with
`jax.jit` and `jax.device_put` replaced, while it builds, by stand-ins that
return shapes placed as the real calls would place arrays (a described
device cannot hold one); the step itself is the Trainer's own `train_step`,
lowered from those shapes. PR 29 found the result equal to the byte to what
the chip's traced runs print; PR 30 read 15,889,743,872 -> 15,870,779,904
(`internlm2-1.8b.lora-train-2k`) and 15,897,312,256 -> 15,895,892,992
(`laguna-s-2.1-ep8.lora-train`) with it.
"""

import argparse
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
    t0 = time.time()
    lowered = trainer.train_step.lower(trainer.state, batch)
    out = {"cell": args.cell, "repo": args.repo, "build_s": round(build_s, 1),
           "trace_and_lower_s": round(time.time() - t0, 1)}
    if not args.lower_only:
        ma = lowered.compile().memory_analysis()
        out.update(
            total=ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
            arguments=ma.argument_size_in_bytes, temporaries=ma.temp_size_in_bytes,
            aliased=ma.alias_size_in_bytes,
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
