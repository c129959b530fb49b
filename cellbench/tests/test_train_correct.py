"""`correct` for a training cell, at the cell's `rehearse` size on the CPU.

1. The control (the reference with 8-bit products, put in the program's
   place) comes out as not correct under the limits of the rehearsal.
2. The rest of a run, with the timed path broken underneath, comes out as
   not correct: once for a step that returns its state unchanged, once for
   half of the batch left out with the mean taken over the rest. (A training
   cell on one chip has no exchange between chips and produces no token.)

The limits here are the rehearsal's own (`rehearse.cell.limits`), set from
CPU readings at the tiny size as the chip's are set from the chip's.
"""

import json
from pathlib import Path

import jax
import pytest

from cellbench import compare
from cellbench.common import load_json
from cellbench.drivers import train

WORKLOADS = Path(__file__).resolve().parents[1] / "workloads"


def train_cells():
    return [
        cell["name"] for cell in map(load_json, sorted(WORKLOADS.glob("*.json")))
        if cell["driver"] == "train"
    ]


@pytest.mark.parametrize("name", train_cells())
def test_sound_run_is_correct(name, make_ctx):
    res = train.run(make_ctx(name, 2**31 + 17), jax.devices())
    assert res["correct"], json.dumps(res["compared"])


@pytest.mark.parametrize("name", train_cells())
@pytest.mark.parametrize("fault", train.FAULTS)
def test_broken_timed_path_is_not_correct(name, fault, make_ctx):
    res = train.run(make_ctx(name, 2**31 + 18), jax.devices(), fault=fault)
    assert not res["correct"], json.dumps(res["compared"])


@pytest.mark.parametrize("name", train_cells())
@pytest.mark.parametrize("seed", [31, 32, 33])
def test_control_in_lower_precision_is_not_correct(name, seed, make_ctx):
    ctx = make_ctx(name, seed)
    trainer, cap = train.build(ctx, jax.devices())
    trainer.close()
    ref = train.run_reference(ctx, cap["shapes"], seed)
    control = train.run_reference(ctx, cap["shapes"], seed, products="int8")
    nums, _ = train.numbers(control, ref)
    ok, table = compare.verdict(nums, ctx.cell["limits"])
    assert not ok, json.dumps(table)
