"""The whole program of a hybrid decoder of KDA and MLA layers with
sigmoid-routed experts (Ling-3.0-flash-VL's language model as one chip's share
of an expert-parallel job) at the benchmark cell's `rehearse` size on the CPU,
against the plain reference `cellbench/references/ling_decoder.py` on seeded
weights: three steps' losses, the first LoRA gradient on every named target,
the adapters after three AdamW steps; bf16 and planted mistakes failing the
float32 tolerances; rung `block` against rung `all`; what the Trainer reports.
The parts (scan, kernels, mixers, router, shares, counts) are
`tests/test_kda_mla.py`'s.
"""

import functools

import jax
import numpy as np
import pytest

from cellbench import weights
from cellbench.drivers import train as drv
from tests import test_laguna as laguna  # the cell-rehearsal helpers, by cell name
from tests.test_kda_mla import CELL, SEED, small

ctx_for = functools.partial(laguna.ctx_for, seed=SEED, name=CELL)
program_side = functools.partial(laguna.program_side, seed=SEED, name=CELL)


@functools.lru_cache(maxsize=None)
def sound():
    """(program, reference, shapes, numbers) of the sound program in float32."""
    cell, config = small()
    prog, shapes, ctx = program_side(cell, config)
    reference = drv.run_reference(ctx, shapes, SEED)
    return prog, reference, shapes, drv.numbers(prog, reference)[0]


# float32 both sides, the order of the sums only; the same program under
# `precision: mixed` reads ten times these or more (the test after these)
FLOAT32 = {"loss": 2e-6, "grad1_direction": 1e-6, "grad1_worst_leaf": 2e-3,
           "grad1_diff_worst_leaf": 3e-3}


def test_loss_of_three_steps_matches_the_reference():
    _, _, _, nums = sound()
    assert max(nums[f"loss_step{i}"] for i in (1, 2, 3)) < FLOAT32["loss"], nums


def test_first_lora_gradient_matches_the_reference_on_every_target():
    """q, k, v, o of the six KDA layers and q, kv_a, kv_b, o of the MLA
    layer: every named target has its adapters and each gets a gradient."""
    prog, reference, _, nums = sound()
    assert len(reference["grads"]) == 2 * 4 * 7
    assert sum("kda/" in k for k in prog["grads"]) == 2 * 4 * 6
    assert sorted(k.split("/")[2] for k in prog["grads"] if "/mla/" in k and "lora_a" in k) == [
        "kv_a_proj", "kv_b_proj", "o_proj", "q_proj"]
    assert all(np.abs(g).max() > 0 for g in reference["grads"].values())
    for name in ("grad1_direction", "grad1_worst_leaf", "grad1_diff_worst_leaf"):
        assert nums[name] < FLOAT32[name], nums


def test_three_adamw_steps_match_the_reference():
    _, _, _, nums = sound()
    # Adam's first update is a sign, so a gradient element near nought may
    # step the other way: a few of them in a leaf, not the leaf
    assert nums["change_worst_leaf"] < 1e-2, nums


def failed_float32(nums) -> list:
    failed = [k for k in ("grad1_direction", "grad1_worst_leaf", "grad1_diff_worst_leaf")
              if nums[k] >= FLOAT32[k]]
    worst_loss = max(nums[f"loss_step{i}"] for i in (1, 2, 3))
    return failed + ["loss"] * (worst_loss >= FLOAT32["loss"])


def test_bf16_products_fail_the_float32_tolerances():
    _, reference, _, _ = sound()
    prog, _, _ = program_side(*small(precision="mixed"))
    nums, _ = drv.numbers(prog, reference)
    assert failed_float32(nums), nums


MISTAKES = {
    "softmax_router": {"router_score": "softmax"},
    "no_groups": {"router_groups": 1, "router_groups_kept": 1},
    "gate_bound_1": {"kda_gate_bound": -1.0},
}


@pytest.mark.parametrize("mistake", sorted(MISTAKES))
def test_planted_mistake_fails_the_float32_tolerances(mistake):
    """The program with one published constant or mechanism wrong, in
    float32, against the reference of the right one. (A model without the
    bias or the norms has fewer leaves: its weights come from the same seed
    by path, so the rest are the same.)"""
    _, reference, _, _ = sound()
    prog, _, _ = program_side(*small(model_over=MISTAKES[mistake]))
    nums, _ = drv.numbers(prog, reference)
    assert failed_float32(nums), nums


# ----------------------------------------------------- the Trainer's readings
def test_trainer_reports_the_layers_the_scan_and_its_gates():
    from polyaxon_tpu.telemetry.spans import get_tracer

    events: list = []
    cell, config = small()
    trainer = laguna.one_chip_trainer(
        ctx_for(cell, config), train={"steps": 2, "logEvery": 1},
        event_fn=lambda kind, body: events.append((kind, body)),
    )
    trainer.run()
    trainer.close()
    gauge = lambda name: trainer.telemetry.gauge(name).value  # noqa: E731
    # 16 positions of a chunk, each log-decay in (-5, 0)
    assert -80.0 < gauge("train.kda.log_decay_min") < 0.0
    assert 0.5 < gauge("train.kda.beta_max") < 1.0
    assert gauge("train.moe.overflow") == 0
    by_kind = dict(events)
    layers = by_kind["model_layers"]["layers"]
    assert [(l["mixer"], l["mlp"]) for l in layers] == [
        ("kda", "dense"), ("kda", "routed"), ("kda", "routed"), ("kda", "routed"),
        ("mla", "routed"), ("kda", "routed"), ("kda", "routed")]
    assert layers[0] == {
        "mixer": "kda", "heads": 4, "key_width": 16, "value_width": 16, "conv": 4, "chunk": 16,
        "gate_bound": -5, "mlp": "dense", "experts_held": 0, "experts_published": 0}
    assert layers[4] == {
        "mixer": "mla", "heads": 4, "latent": 32, "nope_width": 16, "rope_width": 8,
        "value_width": 16, "rope_theta": 6000000.0, "gate": True, "mlp": "routed",
        "experts_held": 4, "experts_published": 16, "score": "sigmoid", "router_groups": 4,
        "router_groups_kept": 2}
    # the rehearsal's width of 64 is no multiple of 128: the jax.numpy conv
    conv = {"path": "xla", "why": "width 64 is no multiple of 128"}
    # and a head's width of 16 is none either: the jax.numpy scan, which says why
    scan = {"path": "xla", "why": "width 16 is no multiple of 128"}
    assert by_kind["model_kda"] == {
        "rows": 1, "seq_len": 64, "chunk": 16, "sub_block": 16, "chunks": 4, "heads_per_step": 4,
        "largest_intermediate_bytes": 4 * 64 * 1 * 16 * 4,
        "layers": [{"layer": i, "conv_silu": conv, "scan": scan} for i in (0, 1, 2, 3, 5, 6)],
    }
    tiles = by_kind["flash_tiles"]["calls"]
    assert [(c["head_dim"], c["value_dim"], c["group"]) for c in tiles] == [(24, 16, 1)] * 3
    marks = [r["name"] for r in get_tracer().recent(400)]
    assert "model.layers" in marks and "model.kda" in marks
    sizes = jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]
    lora = sum(x.size for p, x in sizes if "lora_" in weights.path_str(p))
    assert gauge("train.params_differentiated") == lora


def test_a_checkpoint_per_block_takes_the_same_three_steps():
    """KDA and MLA mixers, the scan's own checkpoints inside the block's,
    `kda_stats` and `moe_stats` sown under it."""
    laguna.assert_block_takes_the_steps_of_all(
        {"moe.overflow", "kda.log_decay_min", "kda.beta_max"},
        case=small(), seed=SEED, name=CELL, rtol=1e-4, atol=5e-4,  # an element in a thousand has a first
        # gradient of nought to rounding and steps the other way under Adam (2 x lr = 4e-4)
    )
