"""A hybrid decoder of Mamba-2 and attention layers with routed experts
(Granite-4.0-H-Small as one chip's share of an expert-parallel job), at the
benchmark cell's `rehearse` size on the CPU: the chunked state-space scan of
`ops/ssd.py` against the step-by-step recurrence, the causal convolution
against its explicit sum, the whole program against the plain reference
`cellbench/references/granite_hybrid_decoder.py` on seeded weights, the two
orders of routing, rung `block` against rung `all`, the cut's parameter
counts, and what such a model refuses.

The comparisons with the reference run the program in float32
(`precision: float32`) so that what is compared is the mathematics: the
tolerances are float32 accumulation order, nothing else, and the program
under `precision: mixed` fails them. The rehearsal through the benchmark's
own entry point and its limits are `cellbench/tests/`'s.
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import weights
from cellbench.common import HERE, load_cell, load_module, ref_to_program_paths
from cellbench.drivers import train as drv
from polyaxon_tpu.models import build_model
from polyaxon_tpu.ops.ssd import causal_conv1d, heads_per_step, ssd_scan
from tests import test_laguna as laguna  # the cell-rehearsal helpers, by cell name

CELL = "granite-4.0-h-small-ep8.lora-train-8k"
SEED = 2**31 + 33
ref = load_module(HERE / "references" / "granite_hybrid_decoder.py", "test_granite_reference")


# ------------------------------------------------------- the scan, the conv
def scan_case(regime: str, groups: int = 1):
    """Seeded inputs of a small scan. `slow`: dt about 1e-3 (decays near 1,
    a state that remembers the whole sequence); `plain`: dt about 1; `fast`:
    dt about 30 with A down to -20, so the in-chunk running sums pass -100
    and most decays underflow to exact zeros."""
    k = jax.random.split(jax.random.PRNGKey(5), 7)
    b, s, h, p, n = 2, 32, 4, 8, 16
    scale = {"slow": 1e-3, "plain": 1.0, "fast": 30.0}[regime]
    return {
        "x": jax.random.normal(k[0], (b, s, h, p)),
        "B": jax.random.normal(k[1], (b, s, groups, n)),
        "C": jax.random.normal(k[2], (b, s, groups, n)),
        "dt": jax.nn.softplus(jax.random.normal(k[3], (b, s, h))) * scale,
        "A_log": jax.random.normal(k[4], (h,)),
        "D": jax.random.normal(k[5], (h,)),
    }, jax.random.normal(k[6], (b, s, h, p))


def by_recurrence(x, B, C, dt, A_log, D):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t + D x_t,
    one position at a time (the reference's literal form, row by row)."""
    a = -jnp.exp(A_log)
    return jax.vmap(
        lambda xr, dtr, br, cr: ref.recurrence_step_by_step(xr, dtr, a, br, cr, D)
    )(x, dt, B, C)


def by_chunks(chunk, x, B, C, dt, A_log, D):
    return ssd_scan(x, dt, -jnp.exp(A_log), B, C, D, chunk=chunk, block_heads=2)


@pytest.mark.parametrize("regime", ["slow", "plain", "fast"])
@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_scan_is_the_recurrence(chunk, regime):
    """Values and the gradients with respect to x, B, C, dt, A_log and D."""
    args, ct = scan_case(regime)
    names = tuple(args)

    def graded(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * ct), argnums=tuple(range(len(names)))
        )(*args.values())

    with jax.default_matmul_precision("highest"):
        if regime == "fast":
            sums = jnp.cumsum((args["dt"] * -jnp.exp(args["A_log"])).reshape(2, -1, chunk, 4), 2)
            assert float(sums.min()) < -100
        want_y, got_y = by_recurrence(*args.values()), by_chunks(chunk, *args.values())
        (want, want_g), (got, got_g) = graded(by_recurrence), graded(
            functools.partial(by_chunks, chunk)
        )
    assert np.isfinite(np.asarray(got_y)).all()
    # float32 on both sides: the chunked form sums in another order
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, w, g in zip(names, want_g, got_g):
        assert np.isfinite(np.asarray(g)).all(), name
        # against the gradient's largest element: a head whose decays nearly
        # all underflow has a gradient of A_log that is the rounding of sums
        # of terms as large as the other heads' (4e-5 of the largest, alike
        # on every head, in the fast regime)
        scale = float(jnp.max(jnp.abs(w))) + 1e-3
        np.testing.assert_allclose(g / scale, w / scale, atol=1e-4, err_msg=name)


def test_heads_of_two_groups_read_their_own_b_and_c():
    args, _ = scan_case("plain", groups=2)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            by_chunks(8, *args.values()), by_recurrence(*args.values()), rtol=2e-5, atol=2e-5
        )


def test_reference_blocks_are_the_recurrence():
    """The reference's own block-by-block evaluation (a block of 4 here)
    against its literal form, slow and fast decays."""
    for regime in ("slow", "fast"):
        args, _ = scan_case(regime)
        row = {k: v[0] if v.ndim > 1 else v for k, v in args.items()}
        a = -jnp.exp(row["A_log"])
        with jax.default_matmul_precision("highest"):
            got = ref.recurrence(row["x"], row["dt"], a, row["B"], row["C"], row["D"], block=4)
            want = ref.recurrence_step_by_step(
                row["x"], row["dt"], a, row["B"], row["C"], row["D"]
            )
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_walk_over_heads_keeps_one_blocks_decays_small():
    # the cell: 1 row x 8,192 positions, chunk 256: 8 MB of float32 decays a head
    assert heads_per_step(1, 8192, 256, 128) == 16
    assert heads_per_step(1, 64, 8, 8) == 8 and heads_per_step(64, 8192, 256, 6) == 1


def test_causal_conv_is_the_explicit_sum():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 6))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 6))
    bias = jnp.arange(6.0)
    want = np.zeros((2, 16, 6))
    for t in range(16):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += np.asarray(w)[k] * np.asarray(x)[:, t - 3 + k]
    np.testing.assert_allclose(causal_conv1d(x, w, bias), want + np.asarray(bias), atol=1e-5)
    np.testing.assert_allclose(
        jax.vmap(lambda r: ref.conv1d_causal(r, w, bias))(x), want + np.asarray(bias), atol=1e-5
    )


def test_top_k_then_softmax_is_softmax_then_top_k_renormalised():
    """The published order (the reference's) and the program's give the same
    experts and weights: softmax is monotone, and renormalising over the
    chosen cancels the other experts' share of the denominator."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(9), (512, 72))
    experts, w = ref.routing_weights(logits, 10)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 10)
    np.testing.assert_array_equal(experts, top_e)
    np.testing.assert_allclose(w, top_p / top_p.sum(-1, keepdims=True), rtol=1e-5)


# ------------------------------------------------- program against reference
def small(model_over=None, precision="float32"):
    """(cell, config) at the rehearsal size: mamba, attention, mamba; routed
    + shared MLP; the four multipliers; a tied head over the rows held; LoRA
    on all six targets."""
    _, _, cell, config = load_cell(CELL, rehearse=True)
    cell, config = copy.deepcopy(cell), copy.deepcopy(config)
    cell["program"]["train"]["precision"] = precision
    config["model"].update(model_over or {})
    return cell, config


ctx_for = functools.partial(laguna.ctx_for, seed=SEED, name=CELL)
program_side = functools.partial(laguna.program_side, seed=SEED, name=CELL)


@functools.lru_cache(maxsize=None)
def sound():
    """(program, reference, shapes, numbers) of the sound program in float32."""
    cell, config = small()
    prog, shapes, ctx = program_side(cell, config)
    reference = drv.run_reference(ctx, shapes, SEED)
    return prog, reference, shapes, drv.numbers(prog, reference)[0]


# float32 both sides, the order of the sums only. Each is under a tenth of
# what the same program reads under `precision: mixed` (bf16 products:
# loss 2e-6 to 1e-5, direction 8e-4, worst leaf 1e-2), which the last test
# of this group holds to
FLOAT32 = {"loss": 1e-6, "grad1_direction": 1e-6, "grad1_worst_leaf": 1e-3,
           "grad1_diff_worst_leaf": 2e-3}


@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_logits_match_the_reference(backend):
    """One forward of the whole model, the flash kernel (interpreted) taking
    the published scale and no rotation as the einsum does."""
    cell, config = small()
    model = {**config["model"], **cell["program"]["model_extra"],
             "fused_lm_loss": False, "attention": backend}
    bundle = build_model("transformer_lm", model)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 256, (2, 64)), jnp.int32)
    abstract = jax.eval_shape(
        lambda: bundle.module.init({"params": jax.random.PRNGKey(0)}, tokens)
    )["params"]
    params = weights.tree(SEED, abstract, config["init"])
    shapes = {weights.path_str(p): tuple(a.shape)
              for p, a in jax.tree_util.tree_flatten_with_path(abstract)[0]}
    d = ref.Dims.from_published(config)
    paths = ref_to_program_paths(config, d.layers)

    def get(name):
        return weights.leaf(SEED, paths[name], shapes[paths[name]], jnp.float32,
                            config["init"])

    targets = cell["reference"]["lora"]["targets"]
    lora = [ref.own_adapters(d, i, {
        t: {ab: get(f"layers.{i}.{t}.{ab}") for ab in ("lora_a", "lora_b")} for t in targets
    }) for i in range(d.layers)]
    assert [sorted(layer) for layer in lora] == [
        ["in_proj", "out_proj"], ["k", "o", "q", "v"], ["in_proj", "out_proj"]]
    with jax.default_matmul_precision("highest"):
        got = bundle.module.apply({"params": params}, tokens)
        embed = get("embed")
        x = [embed[tokens[r]] * d.embedding_multiplier for r in range(2)]
        for i in range(d.layers):
            layer, _ = ref._layer_fns(d, i, 2.0, "float32")
            w = ref.layer_weights(get, d, i)
            x = [layer(w, lora[i], xr) for xr in x]
        _, logits_at = ref._head_fns(d, "float32")
        want = jnp.stack([logits_at(xr, get("final_norm"), embed, jnp.arange(64)) for xr in x])
    assert float(jnp.std(want)) > 0.01
    # logits of magnitude 0.1 (over logits_scaling 16); float32 sums in
    # another order over three layers and a top-3 of 8 both sides take alike
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_loss_of_three_steps_matches_the_reference():
    _, _, _, nums = sound()
    assert max(nums[f"loss_step{i}"] for i in (1, 2, 3)) < FLOAT32["loss"], nums


def test_first_lora_gradient_matches_the_reference():
    """All six targets: q, k, v, o of the attention layer and in_proj,
    out_proj of both Mamba layers, the latter through the scan's backward."""
    prog, reference, _, nums = sound()
    assert len(reference["grads"]) == 2 * (4 + 2 + 2)
    assert sum("mamba/in_proj" in k for k in prog["grads"]) == 4
    for name in ("grad1_direction", "grad1_worst_leaf", "grad1_diff_worst_leaf"):
        assert nums[name] < FLOAT32[name], nums


def test_three_adamw_steps_match_the_reference():
    _, _, _, nums = sound()
    # Adam's first update is a sign, so a gradient element near nought may
    # step the other way: a few of them in a leaf, not the leaf
    assert nums["change_worst_leaf"] < 1e-2, nums


def failed_float32(nums) -> list:
    """The tolerances of FLOAT32 that `nums` does not hold."""
    failed = [k for k in ("grad1_direction", "grad1_worst_leaf", "grad1_diff_worst_leaf")
              if nums[k] >= FLOAT32[k]]
    worst_loss = max(nums[f"loss_step{i}"] for i in (1, 2, 3))
    return failed + ["loss"] * (worst_loss >= FLOAT32["loss"])


def test_bf16_products_fail_the_float32_tolerances():
    """The same program under `precision: mixed` against the same reference:
    a lower precision than float32 is seen by at least one tolerance above."""
    _, reference, _, _ = sound()
    prog, _, _ = program_side(*small(precision="mixed"))
    nums, _ = drv.numbers(prog, reference)
    assert failed_float32(nums), nums


MISTAKES = {
    "softmax_scale_1_over_sqrt_head": {"attention_multiplier": None},
    "residual_multiplier_left_out": {"residual_multiplier": 1.0},
    "logits_not_scaled": {"logits_scaling": 1.0},
    "embedding_multiplier_left_out": {"embedding_multiplier": 1.0},
    "attention_rotates": {"position_embedding_type": "rope"},
    "top_2_for_top_3": {"experts_per_token": 2},
}


@pytest.mark.parametrize("mistake", sorted(MISTAKES))
def test_planted_mistake_fails_the_float32_tolerances(mistake):
    """The program with one published constant or mechanism wrong, in
    float32, against the reference of the right one: at least one tolerance
    above sees each (a logit scale left out turns no gradient, it lengthens
    every one 15 times; a rotation in the one attention layer of three turns
    the gradient by 1.6e-4 only)."""
    _, reference, _, _ = sound()
    prog, _, _ = program_side(*small(model_over=MISTAKES[mistake]))
    nums, _ = drv.numbers(prog, reference)
    assert failed_float32(nums), nums


# ----------------------------------------------------- the Trainer's readings
def test_trainer_reports_mixers_the_scan_and_its_decays():
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
    assert 0 < gauge("train.ssm.dt_max") < 20
    assert gauge("train.ssm.chunk_decay_min") < 0
    assert gauge("train.moe.overflow") == 0
    assert trainer.telemetry.gauge("train.mfu").value in (None, 0)
    by_kind = dict(events)
    layers = by_kind["model_layers"]["layers"]
    assert [l["mixer"] for l in layers] == ["mamba", "attention", "mamba"]
    assert layers[0] == {
        "mixer": "mamba", "heads": 8, "head_width": 16, "state": 16, "conv": 4,
        "chunk": 8, "groups": 1, "mlp": "routed", "experts_held": 2, "experts_published": 8,
        "score": "softmax", "router_groups": 1, "router_groups_kept": 1,
    }
    assert layers[1]["rope"] == "none" and layers[1]["heads"] == 4
    # the rehearsal's widths: `xBC` of 128 + 2 x 16 columns is no multiple of
    # 128 and takes the `jax.numpy` path; the gate's 128 run the kernels
    fused = {
        "conv_silu": {"path": "xla", "why": "width 160 is no multiple of 128"},
        "gate_norm": {"path": "pallas", "block_rows": 64, "chunk_rows": 16, "in_place": True},
    }
    assert by_kind["model_ssm"] == {
        "rows": 1, "seq_len": 64, "chunk": 8, "chunks": 8, "heads_per_step": 8,
        "largest_intermediate_bytes": 8 * 64 * 8 * 4,
        "fused": [{"layer": 0, **fused}, {"layer": 2, **fused}],
    }
    marks = [r["name"] for r in get_tracer().recent(400)]
    assert "model.layers" in marks and "model.ssm" in marks
    # 6 adapters' pairs in the attention layer would be 8 leaves, the two
    # Mamba layers' 4 each: all differentiated, nothing else
    sizes = jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]
    lora = sum(x.size for p, x in sizes if "lora_" in weights.path_str(p))
    assert gauge("train.params_differentiated") == lora


def test_a_checkpoint_per_block_takes_the_same_three_steps():
    """Mamba and attention mixers, the scan's own checkpoints inside the
    block's, `ssm_stats` and `moe_stats` sown under it."""
    laguna.assert_block_takes_the_steps_of_all(
        {"moe.overflow", "ssm.dt_max", "ssm.chunk_decay_min"},
        case=small(), seed=SEED, name=CELL,
    )


def test_the_cells_size_is_what_the_issue_reckoned():
    """2,055,031,424 frozen and 5,199,872 differentiated at the published
    widths, from shapes alone (nothing is allocated); `cellbench/flops_ssm.py`
    counts the same from the published keys."""
    from cellbench import flops_ssm

    _, _, cell, config = load_cell(CELL)
    bundle = build_model("transformer_lm",
                         {**config["model"], **cell["program"]["model_extra"]})
    shapes = jax.eval_shape(
        lambda: bundle.module.init({"params": jax.random.PRNGKey(0)},
                                   jnp.zeros((1, 8192), jnp.int32))
    )["params"]
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    lora = sum(x.size for p, x in flat if "lora_" in weights.path_str(p))
    assert lora == 5_199_872
    assert sum(x.size for _, x in flat) - lora == 2_055_031_424
    assert flops_ssm.held_params(config) == 2_055_031_424
    assert flops_ssm.lora_params(config, 16, cell["reference"]["lora"]["targets"]) == lora
    cfg = bundle.module.cfg
    assert [s.mixer for s in cfg.layers] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert all(s.rope.rotary_factor == 0 and s.routed for s in cfg.layers)
    assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (4096, 16768)
    assert shapes["embed"]["embedding"].shape == (12544, 4096) and "lm_head" not in shapes


# ----------------------------------------------------------- what is refused
@pytest.mark.parametrize("stacked", [{"scan_layers": True}, {"pipeline_stages": 3}],
                         ids=["scan_layers", "pipeline_stages"])
def test_a_hybrid_refuses_a_stacked_form(stacked):
    _, config = small()
    with pytest.raises(ValueError, match="layers that differ"):
        build_model("transformer_lm", {**config["model"], **stacked})


def hybrid_module():
    cell, config = small()
    bundle = build_model("transformer_lm", {**config["model"], **cell["program"]["model_extra"],
                                            "attention": "xla"})
    return bundle.module


def test_a_mamba_layer_refuses_decode():
    module = hybrid_module()
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(lambda: module.init({"params": jax.random.PRNGKey(0)}, tokens))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        jax.eval_shape(
            lambda p: module.apply(p, tokens, decode=True, mutable=["cache"]), params
        )


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    module = hybrid_module()
    with pytest.raises(ValueError, match="no multiple of the chunk 8"):
        jax.eval_shape(
            lambda: module.init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 60), jnp.int32))
        )


def test_unknown_layer_type_and_position_embedding_are_refused():
    _, config = small()
    with pytest.raises(ValueError, match="unknown layer type 'rwkv'"):
        build_model("transformer_lm", {**config["model"], "layer_types": ["rwkv"] * 3})
    with pytest.raises(ValueError, match="position_embedding_type 'alibi'"):
        build_model("transformer_lm", {**config["model"], "position_embedding_type": "alibi"})
