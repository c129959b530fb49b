"""Where a benchmark cell's training step spends its device time, by place in
the model rather than by the compiler's name for a fusion (`breakdown` of a
traced run lists kinds like `fusion` and `copy`; PERF.md section 7, PR 32 (7)).

    chiprun -- python scripts/scope_profile.py <cell> [--steps 4] [--seed N] [--scopes name=regex,...]

Builds the cell's Trainer as the benchmark's driver does (seeded weights, the
driver's feed), runs the first three steps, then `--steps` steps under the
profiler, and joins the trace's operations to the compiled step's own HLO:
every instruction there carries `op_name` metadata (`jit(step_fn)/.../
layer_3/mamba/ssd/...`: flax's module path and any `jax.named_scope`), so an
executed operation falls into the first scope whose regex matches its
`op_name` (a loop, such as the scan's walk over its head blocks, is one
operation of the trace and counts whole), and into a pass: `bwd` where the
name holds `transpose(` (the backward, and with it what a checkpoint runs
again inside it: a block's second forward on rung `block`), else `fwd`. A
fusion carries its root's name: a fusion that mixes places counts under one
of them; what carries no module's name (the grouped products' custom call,
the fused head+loss, casts of the masters) is listed under `other`.

Also prints what the Trainer reported while it built and chose its rung
(`remat`, `startup`, `model_ssm`, `model_kda`, `differentiated`), and the step's sown metrics.
Writes the table to `chiprun_out/scope_profile.<cell>.json`. Not a benchmark
metric: a builder's reading for PERF.md section 5.
"""

import argparse
import json
import os
import re
import shutil
import sys
import time

DEFAULT_SCOPES = (
    "ssd=/ssd/,mamba_in_proj=/mamba/in_proj,mamba_out_proj=/mamba/out_proj,"
    # the mixer's two fused chains (`ops/mamba_fused.py` names their scopes), then what is left
    "mamba_conv_silu=/mamba/.*conv_silu,mamba_gate_norm=/mamba/.*gate_norm,mamba_rest=/mamba/,"
    "moe=/moe/,shared_mlp=/shared_expert/,"
    "attention=/attention/,norms=_norm/,embed_head_loss=embed|lm_head|logsumexp|fused"
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=2147483700)
    ap.add_argument("--scopes", default=DEFAULT_SCOPES)
    ap.add_argument("--rehearse", action="store_true", help="the cell's tiny size (a CPU try-out)")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    os.chdir(root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from cellbench import trace_reduce
    from cellbench.common import load_cell
    from cellbench.drivers import train as drv
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import V1Program

    _, _, cell, config = load_cell(args.cell, args.rehearse)
    ctx = argparse.Namespace(cell=cell, config=config, seed=args.seed,
                             log=lambda m: print(m, flush=True))
    events = []
    t0 = time.time()
    trainer = Trainer(
        V1Program.model_validate(drv.program_spec(ctx)),
        event_fn=lambda kind, body: events.append((kind, body)),
    )
    cap = drv.capture(trainer)
    drv.seed_state(trainer, cap, args.seed, config["init"])
    feed = drv.make_feed(ctx, trainer, args.seed)
    metrics = None
    for _ in range(3):
        trainer.state, metrics = trainer.train_step(trainer.state, feed.get())
    jax.block_until_ready(trainer.state)
    print(f"built, seeded and three steps in {time.time() - t0:.1f} s", flush=True)
    for kind, body in events:
        if kind in ("remat", "startup", "model_ssm", "model_kda", "differentiated"):
            print(kind, json.dumps(body), flush=True)
    print("step metrics", {k: float(v) for k, v in jax.device_get(metrics).items()}, flush=True)

    trace_dir = os.path.join(root, ".cellbench", "scope_profile")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    t0 = time.time()
    for _ in range(args.steps):
        batch = feed.get()
        trainer.state, metrics = trainer.train_step(trainer.state, batch)
    jax.block_until_ready(trainer.state)
    wall = time.time() - t0
    jax.profiler.stop_trace()
    feed.close()

    step = trainer.train_step
    compiled = getattr(step, "_compiled", None) or step.lower(trainer.state, batch).compile()
    text = compiled.as_text()
    op_name = {
        m.group(1): m.group(2)
        for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", text, re.M)
    }
    scopes = [(n, re.compile(rx)) for n, rx in (s.split("=", 1) for s in args.scopes.split(","))]
    trace = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"{len(op_name)} instructions of the compiled step carry an op_name", flush=True)
    if not trace["devices"]:
        print("no TPU plane in the trace (a CPU try-out): no table")
        return
    by_scope, by_pass, unnamed, total = {}, {}, {}, 0.0
    covered = 0  # a loop's own event spans the operations inside it: the outermost counts
    for name, start, dur in sorted(trace["devices"][0]["ops"], key=lambda e: (e[1], -e[2])):
        if start < covered:
            continue
        covered = start + dur
        inst = name.split(" = ", 1)[0].lstrip("%")
        where = op_name.get(inst, "")
        scope = next((n for n, rx in scopes if rx.search(where)), "other")
        which = "bwd" if "transpose(" in where else "fwd"
        ms = dur * 1e-6 / args.steps
        total += ms
        by_scope[scope] = by_scope.get(scope, 0.0) + ms
        by_pass[(scope, which)] = by_pass.get((scope, which), 0.0) + ms
        if scope == "other":
            key = re.sub(r"[.\d]+$", "", inst) + " | " + where[-60:]
            unnamed[key] = unnamed.get(key, 0.0) + ms
    out = {
        "cell": args.cell, "steps": args.steps, "wall_ms_a_step": 1e3 * wall / args.steps,
        "device_ms_a_step": total, "rung": getattr(step, "rung", None),
        "ms_a_step_by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "ms_a_step_by_scope_and_pass": {
            f"{s}.{p}": v for (s, p), v in sorted(by_pass.items(), key=lambda kv: -kv[1])
        },
        "other_top": dict(sorted(unnamed.items(), key=lambda kv: -kv[1])[:12]),
    }
    print(json.dumps(out, indent=1), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/scope_profile.{args.cell}.json", "w") as f:
        json.dump(out, f, indent=1)
    trainer.close()


if __name__ == "__main__":
    main()
