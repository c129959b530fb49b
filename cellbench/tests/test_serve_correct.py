"""`correct` for a serving cell, at the cell's `rehearse` size on the CPU.

1. The control comes out as not correct: at each position of the sampled
   prompts and served tokens, the token that the reference with 8-bit
   products puts first lies further below the float32 reference's best than
   the rehearsal's limit allows.
2. The rest of a run with the timed path broken underneath (a token altered
   where it is produced, in the frame that carries it to the client) comes
   out as not correct. A serving cell on one chip has no exchange between
   chips, no state to leave unchanged and no batch mean.

The limit here is the rehearsal's own (`rehearse.cell.limits`), set from
CPU readings at the tiny size as the chip's is set from the chip's.
"""

import json
from pathlib import Path

import jax
import pytest

from cellbench import compare
from cellbench.common import load_json
from cellbench.drivers import serve

WORKLOADS = Path(__file__).resolve().parents[1] / "workloads"


def serve_cells():
    return [
        cell["name"] for cell in map(load_json, sorted(WORKLOADS.glob("*.json")))
        if cell["driver"] == "serve"
    ]


@pytest.mark.parametrize("name", serve_cells())
def test_sound_run_is_correct_and_control_is_not(name, make_ctx):
    ctx = make_ctx(name, 2**31 + 27, seconds=2.0)
    ctx.compiles.install()
    res = serve.run(ctx, jax.devices())
    assert res["correct"], json.dumps(res["compared"])
    obs = res["observations"]
    red = serve.reduce_records(obs["records"], obs["t_open"], obs["t_close"])
    sample = serve.sample_for_reference(red["completed"], ctx.seed, ctx.cell["reference"])
    serve.attach_prompts(ctx, sample)
    ref_logits = serve.run_reference(ctx, res["shapes"], sample)
    low = serve.run_reference(ctx, res["shapes"], sample, products="int8")
    firsts = [{"tokens": lg.argmax(-1).tolist()} for lg in low]
    nums, _ = serve.serve_numbers(firsts, ref_logits)
    ok, table = compare.verdict(nums, ctx.cell["limits"])
    assert not ok, json.dumps(table)


@pytest.mark.parametrize("name", serve_cells())
@pytest.mark.parametrize("fault", serve.FAULTS)
def test_broken_timed_path_is_not_correct(name, fault, make_ctx):
    ctx = make_ctx(name, 2**31 + 28, seconds=2.0)
    ctx.compiles.install()
    try:
        res = serve.run(ctx, jax.devices(), fault=fault)
    finally:
        serve.unplant_faults()
    assert not res["correct"], json.dumps(res["compared"])
