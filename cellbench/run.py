"""cellbench: one cell, one run.

    python3 cellbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration or per-layer metric is a
file of its own, found by the name in `BENCHMARK.json`:

    cellbench/workloads/<cell>.json        the cell: driver, traffic, program spec, limits
    cellbench/configs/<config>.json        the configuration: published keys, model block, init rules
    cellbench/references/<name>.py         its plain reference
    cellbench/drivers/<driver>.py          `run(ctx) -> dict` for a kind of cell (train, serve)
    cellbench/layer_metrics/<metric>.py    `read(obs) -> float | None` for one per-layer metric

Nothing in this file or in the drivers selects behaviour by a cell's,
configuration's or metric's name. See cellbench/README.md.

The last line of standard output is the result. Earlier lines each name the
platform, the device kind and the device count, and carry what is not a
metric: medians, counts, compilations in the window, memory readings.
`--rehearse` runs the cell's `rehearse` overrides (a tiny size) on whatever
platform JAX finds and never prints a device metric as measured.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


from cellbench.common import HERE, Ctx, load_cell, load_module, setup_jax  # noqa: E402


def per_layer(ctx: Ctx, obs: dict) -> dict:
    """Each per-layer metric of BENCHMARK.json that lists this cell (or
    lists none), from its own reader. A reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in ctx.bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and ctx.entry["name"] not in cells:
            continue
        path = HERE / "layer_metrics" / f"{m['name']}.py"
        reader = load_module(path, "cellbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(ctx: Ctx, measured: dict) -> dict:
    out = {}
    for m in ctx.bench["end_to_end"]:
        cells = m.get("workloads")
        if cells is not None and ctx.entry["name"] not in cells:
            continue
        if m["name"] not in measured:
            raise KeyError(
                f"the driver reported no {m['name']!r}, which BENCHMARK.json "
                f"lists for {ctx.entry['name']!r}"
            )
        out[m["name"]] = {"value": float(measured[m["name"]]), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.t_process = T_PROCESS

    try:
        bench, entry, cell, config = load_cell(args.workload, args.rehearse, listed_only=True)
    except KeyError as e:
        print(f"cellbench: {e.args[0]}", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    ctx = Ctx(args, bench, entry, cell, config)
    devices = setup_jax(ctx, int(entry["chips"]))

    driver = importlib.import_module(f"cellbench.drivers.{cell['driver']}")
    res = driver.run(ctx, devices)

    metrics = (
        per_layer(ctx, res["observations"]) if ctx.trace else end_to_end(ctx, res["end_to_end"])
    )
    device = dict(ctx.device, memory_peak_bytes=int(res["memory_peak_bytes"]))
    line = {
        "correct": bool(res["correct"]),
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if ctx.trace:
        device["busy_s"] = res["observations"]["trace"]["busy_s"]
        device["window_s"] = res["observations"]["window_s"]
        line["breakdown"] = res["breakdown"]
    if ctx.rehearse:
        # a rehearsal's timings are the CPU's: never under a metric's name
        ctx.log(f"rehearsal: metrics withheld from the result ({sorted(metrics)})")
        line["metrics"] = {}
        line["rehearsal"] = True
    line["compared"] = res["compared"]
    sys.stdout.flush()
    for name, row in res["compared"].items():
        print(
            f"compared {name}: value={row['value']!r} limit={row['limit']!r} "
            f"{'ok' if row['ok'] else 'FAILED'}",
            file=sys.stderr,
        )
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
