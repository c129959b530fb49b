"""Readings for the limits of `correct`: the program against the reference
over many seeds (the lower readings), and the control and the planted faults
against the reference on a few (the upper readings). Run on the chip at the
cell's own size; PERF.md records what the limits were set from.

    python3 cellbench/calibrate.py --workload <cell> --seeds 11,12,... --control-seeds 11,12,13
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cellbench.common import Ctx, load_cell, setup_jax  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    args.seed, args.trace, args.t_process = 0, 0, T_PROCESS
    bench, entry, cell, config = load_cell(args.workload, args.rehearse)
    ctx = Ctx(args, bench, entry, cell, config)
    devices = setup_jax(ctx, int(entry["chips"]))
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    driver = importlib.import_module(f"cellbench.drivers.{cell['driver']}")
    rows = driver.readings(ctx, devices, seeds, control)
    summary: dict = {}
    for row in rows:
        for side in ("program", "control_int8", "fault_half_batch", "fault_token_altered"):
            for k, v in (row.get(side) or {}).items():
                summary.setdefault(side, {}).setdefault(k, []).append(v)
    table = {
        side: {k: {"min": min(v), "max": max(v), "n": len(v)} for k, v in nums.items()}
        for side, nums in summary.items()
    }
    print(json.dumps({"rows": rows, "summary": table}, default=float))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows, "summary": table}, default=float, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
