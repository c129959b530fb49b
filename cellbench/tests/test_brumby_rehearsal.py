"""The Brumby cell end to end at its `rehearse` size on the CPU, through the
benchmark's own entry point: `correct` true, every metric withheld; a state
left unchanged fails the rehearsal's limits, and so do the same program under
`precision: mixed` and the reference with 8-bit products (the rehearsal runs
float32: the cell's file says why)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "brumby-14b-base-pp10.lora-train-32k"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_withholds_its_metrics(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path), TMPDIR=str(tmp_path),
               BENCH_RUN="ignored")
    done = subprocess.run(
        [sys.executable, "cellbench/run.py", "--workload", CELL, "--rehearse",
         "--seed", str(2**31 + 44), "--seconds", "1", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"], line["compared"]
    assert line["rehearsal"] and line["metrics"] == {} and line["failed"] == 0
    assert set(line["compared"]) == set(
        json.loads((ROOT / "cellbench" / "workloads" / f"{CELL}.json").read_text())
        ["rehearse"]["cell"]["limits"])
    assert ("breakdown" in line) == bool(trace)


def test_a_state_left_unchanged_fails(make_ctx):
    import jax

    from cellbench.drivers import train as drv

    ctx = make_ctx(CELL, 2**31 + 45)
    res = drv.run(ctx, jax.devices()[:1], fault="state_unchanged")
    assert not res["correct"]
    assert res["compared"]["change_worst_leaf"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_bf16_products_fail_the_rehearsals_limits(make_ctx):
    import jax

    from cellbench.drivers import train as drv

    ctx = make_ctx(CELL, 2**31 + 46)
    ctx.cell["program"]["train"]["precision"] = "mixed"
    res = drv.run(ctx, jax.devices()[:1])
    assert not res["correct"], res["compared"]


def test_the_int8_control_fails_the_rehearsals_limits(make_ctx):
    """The reference with 8-bit products against itself in float32: over at
    least one limit, as the harness's comparison asks of every cell."""
    import jax

    from cellbench import compare
    from cellbench.drivers import train as drv

    ctx = make_ctx(CELL, 2**31 + 47)
    trainer, cap = drv.build(ctx, jax.devices()[:1])
    trainer.close()
    sound = drv.run_reference(ctx, cap["shapes"], ctx.seed)
    control = drv.run_reference(ctx, cap["shapes"], ctx.seed, products="int8")
    ok, table = compare.verdict(drv.numbers(control, sound)[0], ctx.cell["limits"])
    assert not ok, table
