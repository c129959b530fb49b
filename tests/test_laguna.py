"""A decoder of mixed window/full attention with routed experts
(Laguna-S-2.1 as one chip's share of an expert-parallel job), at the
benchmark cell's `rehearse` size on the CPU: the program against the plain
reference `cellbench/references/laguna_decoder.py` on seeded weights, the
windowed flash kernels against masked XLA attention, the share of the
experts against the uncut layer, planted mistakes that the comparison must
see, and that nothing of it moved the dense decoder.

The comparisons with the reference run the program in float32
(`precision: float32`) so that what is compared is the mathematics: the
tolerances are float32 accumulation order, nothing else. The rehearsal under
`precision: mixed` and its limits are `cellbench/tests/`'s.
"""

import argparse
import copy
import functools
import hashlib
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import compare, weights
from cellbench.common import HERE, Ctx, load_cell, load_module, ref_to_program_paths
from cellbench.drivers import train as drv
from polyaxon_tpu.models import build_model
from polyaxon_tpu.models.moe import MoEFeedForward, buffer_rows
from polyaxon_tpu.ops.attention import dot_product_attention
from polyaxon_tpu.ops.flash_attention import flash_attention, flash_shapes_ok

CELL = "laguna-s-2.1-ep8.lora-train"
SEED = 2**31 + 29
ref = load_module(HERE / "references" / "laguna_decoder.py", "test_laguna_reference")


def small(model_over=None, config_over=None):
    """(cell, config) at the rehearsal size, the program in float32."""
    _, _, cell, config = load_cell(CELL, rehearse=True)
    cell, config = copy.deepcopy(cell), copy.deepcopy(config)
    cell["program"]["train"]["precision"] = "float32"
    config["model"].update(model_over or {})
    config.update(config_over or {})
    return cell, config


def ctx_for(cell, config, seed=SEED, name=CELL):
    bench, entry, _, _ = load_cell(name, rehearse=True)
    args = argparse.Namespace(seed=seed, seconds=0.2, trace=0, rehearse=True,
                              t_process=time.perf_counter())
    ctx = Ctx(args, bench, entry, cell, config)
    ctx.tag = "[test platform=cpu]"
    return ctx


def one_chip_trainer(ctx, **kw):
    """`drv.build`'s Trainer on one of the suite's eight virtual devices, as
    the cell has one chip (2 rows do not split over eight)."""
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import V1Program

    spec = drv.program_spec(ctx)
    spec["train"].update(kw.pop("train", {}))
    return Trainer(V1Program.model_validate(spec), devices=jax.devices()[:1], **kw)


def program_side(cell, config, seed=SEED, name=CELL):
    """What the comparison reads of the program: the Trainer's own step on
    seeded weights, three steps (losses, first gradient, adapters after).
    `name`: the cell whose BENCHMARK.json entry the context carries (the
    hybrid decoder's tests pass their own)."""
    ctx = ctx_for(cell, config, seed, name)
    trainer = one_chip_trainer(ctx)
    cap = drv.capture(trainer)
    drv.seed_state(trainer, cap, seed, config["init"])
    feed = drv.make_feed(ctx, trainer, seed)

    def call(batch):
        trainer.state, metrics = trainer.train_step(trainer.state, batch)
        return metrics

    with jax.default_matmul_precision("highest"):
        prog = drv.first_steps(ctx, trainer, feed, call)
    feed.close()
    trainer.close()
    return prog, cap["shapes"], ctx


@functools.lru_cache(maxsize=None)
def sound():
    """(program, reference, numbers) of the sound program, computed once."""
    cell, config = small()
    prog, shapes, ctx = program_side(cell, config)
    reference = drv.run_reference(ctx, shapes, SEED)
    return prog, reference, shapes, drv.numbers(prog, reference)[0]


# ------------------------------------------------- program against reference
@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_logits_match_the_reference(backend):
    cell, config = small()
    model = {**config["model"], **cell["program"]["model_extra"],
             "fused_lm_loss": False, "attention": backend}
    bundle = build_model("transformer_lm", model)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 512, (2, 128)), jnp.int32
    )
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

    lora = [{t: {ab: get(f"layers.{i}.{t}.{ab}") for ab in ("lora_a", "lora_b")}
             for t in "qkvo"} for i in range(d.layers)]
    with jax.default_matmul_precision("highest"):
        got = bundle.module.apply({"params": params}, tokens)
        x = [get("embed")[tokens[r]] for r in range(2)]
        for i in range(d.layers):
            layer, _ = ref._layer_fns(d, i, 2.0, "float32")
            w = ref.layer_weights(get, d, i)
            x = [layer(w, lora[i], xr) for xr in x]
        _, logits_at = ref._head_fns(d, "float32")
        want = jnp.stack([
            logits_at(xr, get("final_norm"), get("lm_head"), jnp.arange(128)) for xr in x
        ])
    # logits of magnitude 4; float32 sums in another order (five layers, a
    # top-2 of 16 that both sides take alike)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_loss_of_three_steps_matches_the_reference():
    _, _, _, nums = sound()
    # float32 both sides: the order of the sums only
    assert max(nums[f"loss_step{i}"] for i in (1, 2, 3)) < 2e-5, nums


def test_first_lora_gradient_matches_the_reference():
    _, _, _, nums = sound()
    # by direction over all leaves, by the worst leaf's norm, and by the
    # worst leaf's difference: float32 against float32
    assert nums["grad1_direction"] < 1e-6, nums
    assert nums["grad1_worst_leaf"] < 1e-3, nums
    assert nums["grad1_diff_worst_leaf"] < 2e-3, nums


def test_three_adamw_steps_match_the_reference():
    _, _, _, nums = sound()
    # Adam's first update is a sign, so a gradient element near nought may
    # step the other way: a few of them in a leaf, not the leaf
    assert nums["change_worst_leaf"] < 1e-2, nums


def swapped_ropes(model):
    ropes = model["rope_parameters"]
    return {"rope_parameters": {"full_attention": ropes["sliding_attention"],
                                "sliding_attention": ropes["full_attention"]}}


MISTAKES = {
    "sliding_layers_run_as_full": lambda m: {"sliding_window": 128},
    "gate_left_out": lambda m: {"attn_gate": False},
    "top_1_for_top_2": lambda m: {"experts_per_token": 1},
    "routed_scale_1": lambda m: {"routed_scale": 1.0},
    "rope_tables_swapped": swapped_ropes,
}


@pytest.mark.parametrize("mistake", sorted(MISTAKES))
def test_planted_mistake_fails_the_rehearsal_limits(mistake):
    """The program with one mechanism wrong, against the reference of the
    right one: the comparison that decides `correct` must see it at the
    rehearsal's own limits (the cell's top-10 of 256 is a top-2 of 16 there,
    so top-9 for top-10 is top-1 for top-2)."""
    _, reference, _, _ = sound()
    cell, config = small(model_over=MISTAKES[mistake](small()[1]["model"]))
    prog, _, _ = program_side(cell, config)
    nums, _ = drv.numbers(prog, reference)
    ok, table = compare.verdict(nums, cell["limits"])
    assert not ok, json.dumps(table)


def test_int8_products_fail_the_rehearsal_limits():
    _, reference, shapes, _ = sound()
    cell, config = small()
    control = drv.run_reference(ctx_for(cell, config), shapes, SEED, products="int8")
    nums, _ = drv.numbers(control, reference)
    ok, table = compare.verdict(nums, cell["limits"])
    assert not ok, json.dumps(table)


# ------------------------------------------------------ the windowed kernels
@functools.lru_cache(maxsize=None)
def attention_pair(window):
    """(flash, xla) forward and the three gradients on one seeded case;
    sequence 128 in q blocks of 32 and kv blocks of 64."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (2, 128, 6, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    ct = jax.random.normal(ks[3], (2, 128, 6, 32))

    def both(fn):
        out = fn(q, k, v)
        grads = jax.grad(lambda *a: (fn(*a) * ct).sum(), (0, 1, 2))(q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"), (out, *grads)))

    return (
        both(lambda *a: flash_attention(*a, causal=True, block_q=32, block_kv=64,
                                        window=window)),
        both(lambda *a: dot_product_attention(*a, causal=True, backend="xla",
                                              window=window)),
    )


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize(
    "window", [1, 16, 48, 100, 128, 200],
    ids=["one", "in_a_block", "across_blocks", "unaligned", "the_sequence", "larger"],
)
def test_flash_window_matches_masked_xla(window, what):
    flash, xla = attention_pair(window)
    # float32 online softmax against the whole softmax
    np.testing.assert_allclose(flash[what], xla[what], atol=5e-6)


def test_window_changes_the_result():
    """The XLA mask itself is right: a key `window` back is not seen."""
    _, near = attention_pair(16)
    _, far = attention_pair(None)
    assert np.abs(near["out"][:, :16] - far["out"][:, :16]).max() == 0.0
    assert np.abs(near["out"][:, 16:] - far["out"][:, 16:]).max() > 1e-3


@pytest.fixture
def no_mesh():
    """An earlier test's trainer may have left its mesh bound (tier-1 runs
    several files in one process); model code would then add sharding
    constraints to the trace that the pinned text has not."""
    from polyaxon_tpu.parallel import ring

    was = ring.current_mesh()
    ring.set_current_mesh(None)
    yield
    ring.set_current_mesh(was)


def _normalised(jaxpr) -> str:
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr)).encode()
    ).hexdigest()[:16]


def test_no_window_traces_to_the_kernels_of_the_parent(no_mesh):
    """The jaxpr of forward and backward at explicit blocks, kernel bodies,
    grids and index maps included, is pinned by its hash, so a change to a
    kernel cannot pass unseen. PR 30 rewrote how the three kernels tile and
    walk (a GQA group a step, masks on edge tiles only, clamped index maps,
    transposed dk/dv tiles), so PR 28's hash went stale by design: this one
    was taken anew from PR 30's finished tree (jax 0.9.0; another jax prints
    another text, and the pin is then taken anew from a tree known to be
    unchanged)."""
    q = jax.ShapeDtypeStruct((2, 256, 4, 32), jnp.float32)
    k = jax.ShapeDtypeStruct((2, 256, 2, 32), jnp.float32)

    def grads(q, k, v):
        return jax.grad(
            lambda *a: flash_attention(*a, causal=True, block_q=64, block_kv=128).sum(),
            (0, 1, 2),
        )(q, k, v)

    text = str(jax.make_jaxpr(grads)(q, k, k))
    assert "flash_attention_fwd" in text and "flash_window" not in text
    if jax.__version__ == "0.9.0":
        assert _normalised(text) == "2c8a6e666f64a69f"


def test_window_names_its_three_kernels():
    q = jax.ShapeDtypeStruct((2, 256, 4, 32), jnp.float32)
    k = jax.ShapeDtypeStruct((2, 256, 2, 32), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda q, k, v: jax.grad(
            lambda *a: flash_attention(*a, causal=True, block_q=64, block_kv=128,
                                       window=40).sum(), (0, 1, 2))(q, k, v)
    )(q, k, k))
    for name in ("flash_window_fwd", "flash_window_dq", "flash_window_dkv"):
        assert name in text
    assert "flash_attention_" not in text


def test_a_windowed_q_block_walks_only_the_kv_blocks_it_sees():
    """The walk of the cell's sliding layers (4,096 tokens, window 512, nine
    query heads a kv head): a q block steps over the kv blocks its window
    touches and no further, in the forward and dq kernels; a kv block over
    the q blocks that see it, in dk/dv. With the blocks the kernels choose
    the backward walks execute 1.5 of the window's own pairs (PR 29's q
    blocks of 128 on kv blocks of 512 executed 2); the forward keeps 2,
    because its steps cost more than the pairs they would save."""
    from polyaxon_tpu.ops.flash_attention import _Walk, tile_report

    assert _Walk(4096, 128, 512, True, 512).steps == 2  # PR 29's blocks
    assert _Walk(4096, 128, 128, True, 512).steps == 5
    assert _Walk(4096, 256, 256, True, 512).steps == 3
    assert _Walk(4096, 256, 256, True, 512, kv_major=True).steps == 3
    assert _Walk(1024, 64, 128, True, 40).steps == 2  # one, two across an edge
    assert _Walk(4096, 128, 512, True, None).steps == 8
    assert _Walk(4096, 128, 512, False, None).steps == 8
    for call in tile_report(4096, 128, group=9, window=512):
        assert call["kernel"].startswith("flash_window_")
        assert call["executed_over_required"] < (2.01 if call["kernel"].endswith("fwd") else 1.6)
        assert call["mask_steps"] <= call["live_steps"] < call["grid_steps"]


def test_flash_shapes_ok_knows_the_window():
    assert flash_shapes_ok(4096, 128, 512, window=512)
    assert flash_shapes_ok(4096, 128, 512, window=77)
    assert not flash_shapes_ok(4096, 128, 512, window=0)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(*(jnp.zeros((1, 64, 2, 32)),) * 3, causal=False, window=8)
    with pytest.raises(ValueError, match="no sliding window"):
        dot_product_attention(*(jnp.zeros((1, 64, 2, 32)),) * 3, causal=True,
                              backend="ring", window=8)


def test_dense_config_traces_as_on_the_parent(no_mesh):
    """An existing dense config is untouched by fields it does not set:
    forward and backward of the `tiny` LoRA decoder are pinned by the hash of
    their jaxpr. It holds the flash kernels' bodies, which PR 30 rewrote (and
    `attention_block` is None now: the kernels choose), so PR 28's hash went
    stale by design and this one was taken anew from PR 30's finished tree."""
    bundle = build_model("transformer_lm", {"preset": "tiny", "seq_len": 64,
                                            "attention": "flash", "lora": {"rank": 4}})
    cfg = bundle.module.cfg
    assert cfg.head_dim is None and cfg.head_size == 32 and cfg.layers == ()
    assert cfg.attention_block is None
    tokens = jnp.zeros((2, 64), jnp.int32)
    variables = jax.eval_shape(
        lambda: bundle.module.init({"params": jax.random.PRNGKey(0)}, tokens)
    )
    text = str(jax.make_jaxpr(
        lambda p: jax.grad(
            lambda p: bundle.module.apply(p, tokens, train=True).astype(jnp.float32).sum()
        )(p)
    )(variables))
    if jax.__version__ == "0.9.0":
        assert _normalised(text) == "6f67103a5000a097"


# ------------------------------------------------------------------ the share
GRANITE = "granite-4.0-h-small-ep8.lora-train-8k"
granite_ref = load_module(
    HERE / "references" / "granite_hybrid_decoder.py", "test_laguna_granite_reference"
)


def uncut_layer_case(family="laguna"):
    """One sparse layer of a rehearsal's widths with ALL its experts: weights,
    tokens, and what the uncut reference gives for routed + shared. `laguna`:
    16 experts, top-2, weights 2.5 x softmax renormalised (laguna_decoder.py);
    `granite`: the published 72 experts and top-10, softmax over the chosen
    (granite_hybrid_decoder.py)."""
    if family == "laguna":
        module, n = ref, 16
        _, config = small()
    else:
        module, n = granite_ref, 72
        _, _, _, config = load_cell(GRANITE, rehearse=True)
        config = {**config, "router_width": n, "num_experts_per_tok": 10}
    d = module.Dims.from_published(config, lo=0, held=n)
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    D, F, Fs = d.hidden, d.expert, d.shared
    w = {
        "router": jax.random.normal(ks[0], (D, n)) / np.sqrt(D),
        "experts.gate": jax.random.normal(ks[1], (n, D, F)) / np.sqrt(D),
        "experts.up": jax.random.normal(ks[2], (n, D, F)) / np.sqrt(D),
        "experts.down": jax.random.normal(ks[3], (n, F, D)) / np.sqrt(F),
        "shared.gate": jax.random.normal(ks[4], (D, Fs)) / np.sqrt(D),
        "shared.up": jax.random.normal(ks[5], (D, Fs)) / np.sqrt(D),
        "shared.down": jax.random.normal(ks[6], (Fs, D)) / np.sqrt(Fs),
    }
    m = jax.random.normal(ks[7], (256, D))
    with jax.default_matmul_precision("highest"):
        whole = module._routed(m, w, d, module._mm_f32) + module._swiglu(
            m, w["shared.gate"], w["shared.up"], w["shared.down"], module._mm_f32
        )
    return module, config, d, w, m, whole


def share_of(w, lo, held):
    return {k: (v[lo:lo + held] if k.startswith("experts.") else v) for k, v in w.items()}


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize(
    "family,shares,held", [("laguna", 4, 4), ("laguna", 2, 8), ("granite", 8, 9)],
    ids=["4x4", "2x8", "granite-8x9"],
)
def test_shares_add_up_to_the_uncut_layer(side, family, shares, held):
    """The guide's share test: over all shares of a layer, the routed parts
    summed and the shared expert counted once equal the uncut reference.
    Granite's case is the cell's own cut: eight shares of 9 of 72 experts
    (offsets 0, 9, ..., 63), top-10."""
    module, config, d, w, m, whole = uncut_layer_case(family)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for s in range(shares):
            lo = s * held
            part = share_of(w, lo, held)
            if side == "reference":
                ds = module.Dims.from_published(config, lo=lo, held=held)
                total = total + module._routed(m, part, ds, module._mm_f32)
            else:
                layer = MoEFeedForward(
                    d.hidden, d.expert, d.router, held=held, offset=lo, top_k=d.top_k,
                    routed_scale=getattr(d, "routed_scale", 1.0),
                    norm_topk=getattr(d, "norm_topk", True), aux_weight=0.0,
                )
                params = {"router": {"kernel": part["router"]},
                          "gate_kernel": part["experts.gate"],
                          "up_kernel": part["experts.up"],
                          "down_kernel": part["experts.down"]}
                total = total + layer.apply({"params": params}, m[None])[0]
        total = total + module._swiglu(
            m, w["shared.gate"], w["shared.up"], w["shared.down"], module._mm_f32
        )
    np.testing.assert_allclose(total, whole, atol=2e-5)  # f32 sums, other order


def one_expert_case(buffer_factor):
    """Every token routed to experts 0 and 1 (a router of noughts: all
    probabilities equal, the lowest indices win), both held here."""
    D, F, held, E = 32, 16, 4, 16
    layer = MoEFeedForward(D, F, E, held=held, offset=0, top_k=2, norm_topk=True,
                           routed_scale=2.5, aux_weight=0.0, buffer_factor=buffer_factor)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    params = {"router": {"kernel": jnp.zeros((D, E))},
              "gate_kernel": jax.random.normal(ks[0], (held, D, F)),
              "up_kernel": jax.random.normal(ks[1], (held, D, F)),
              "down_kernel": jax.random.normal(ks[2], (held, F, D))}
    x = jax.random.normal(ks[3], (2, 512, D))
    with jax.default_matmul_precision("highest"):
        out, sown = layer.apply({"params": params}, x, mutable=["moe_stats"])
        want = sum(
            1.25 * (jax.nn.silu(x @ params["gate_kernel"][e]) * (x @ params["up_kernel"][e]))
            @ params["down_kernel"][e]
            for e in (0, 1)
        )
    stats = {k: float(v[0]) for k, v in sown["moe_stats"].items()}
    return out, want, stats


def test_a_batch_routed_to_one_place_loses_nothing_where_the_buffer_holds_it():
    assert buffer_rows(1024, 2, 4, 16, 16.0) == 2048  # the worst case, no more
    out, want, stats = one_expert_case(buffer_factor=16.0)
    assert stats == {"assignments_local": 2048.0, "load_max_over_mean": 2.0, "overflow": 0.0}
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)


def test_a_buffer_too_short_is_counted_not_hidden():
    # twice the 512 expected of 1,024 tokens x top-2 x 4 of 16 held
    assert buffer_rows(1024, 2, 4, 16, 2.0) == 1024
    _, _, stats = one_expert_case(buffer_factor=2.0)
    assert stats["assignments_local"] == 2048.0 and stats["overflow"] == 1024.0


# ----------------------------------------------------- the Trainer's readings
def tiny_trainer(events, steps=2, seq_len=128):
    cell, config = small()
    cell["traffic"]["seq_len"] = seq_len
    cell["program"]["model_extra"]["seq_len"] = seq_len
    cell["program"]["data"]["config"]["seq_len"] = seq_len
    return one_chip_trainer(
        ctx_for(cell, config), train={"steps": steps, "logEvery": 1},
        event_fn=lambda kind, body: events.append((kind, body)),
    )


def test_trainer_reports_layers_routing_and_what_it_differentiates():
    from polyaxon_tpu.telemetry.spans import get_tracer

    events: list = []
    trainer = tiny_trainer(events)
    trainer.run()
    trainer.close()
    gauge = lambda name: trainer.telemetry.gauge(name).value  # noqa: E731
    # 2 rows x 128 tokens x top-2 x 4 of 16 held = 128 expected a layer
    assert 64 < gauge("train.moe.assignments_local") < 192
    assert 1.0 <= gauge("train.moe.load_max_over_mean") <= 4.0
    assert gauge("train.moe.overflow") == 0
    frozen, trained = gauge("train.params_frozen"), gauge("train.params_differentiated")
    sizes = jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]
    lora = sum(x.size for p, x in sizes if "lora_" in weights.path_str(p))
    assert trained == lora and frozen == sum(x.size for _, x in sizes) - lora
    layers = dict(events)["model_layers"]["layers"]
    assert [(l["kind"], l["heads"], l["rope"], l["mlp"]) for l in layers] == [
        ("full", 4, "yarn", "dense"),
        ("sliding", 6, "default", "routed"),
        ("sliding", 6, "default", "routed"),
        ("sliding", 6, "default", "routed"),
        ("full", 4, "yarn", "routed"),
    ]
    assert layers[1]["experts_held"] == 4 and layers[1]["experts_published"] == 16
    assert layers[1]["window"] == 16 and layers[0]["experts_held"] == 0
    marks = [r for r in get_tracer().recent(200) if r["name"] == "model.layers"]
    assert marks and json.loads(marks[-1]["attrs"]["layers"]) == layers


def test_trainer_reports_the_tiles_its_flash_kernels_run():
    """`polyaxon.kernels.flash_tiles`: once a build, per distinct call shape
    and kernel the blocks and the walk's counts, on the run store and in the
    tracer's ring; a windowed and a dense toy model; nothing without flash."""
    from polyaxon_tpu.telemetry.spans import get_tracer

    fields = {"kernel", "seq", "head_dim", "group", "window", "causal", "block_q",
              "block_kv", "grid_steps", "live_steps", "mask_steps",
              "executed_over_required"}

    def marks():
        return [r for r in get_tracer().recent(400) if r["name"] == "kernels.flash_tiles"]

    before = len(marks())
    events: list = []
    tiny_trainer(events).close()
    tiles = [body for kind, body in events if kind == "flash_tiles"]
    assert len(tiles) == 1 and len(marks()) == before + 1
    calls = tiles[0]["calls"]
    assert json.loads(marks()[-1]["attrs"]["calls"]) == calls
    assert all(set(c) == fields for c in calls)
    # the toy Laguna: full layers of 4 heads, sliding ones of 6 with window
    # 16, on 2 kv heads of 32, 128 tokens: two call shapes x three kernels
    assert [(c["kernel"], c["group"], c["window"]) for c in calls] == [
        ("flash_attention_fwd", 2, None), ("flash_attention_dq", 2, None),
        ("flash_attention_dkv", 2, None), ("flash_window_fwd", 3, 16),
        ("flash_window_dq", 3, 16), ("flash_window_dkv", 3, 16),
    ]
    for c in calls:
        assert c["seq"] == 128 and c["head_dim"] == 32
        assert c["seq"] % c["block_q"] == 0 and c["seq"] % c["block_kv"] == 0
        assert c["mask_steps"] <= c["live_steps"] <= c["grid_steps"]
        assert c["executed_over_required"] >= 1.0

    # a dense model with a kv block its file wrote: obeyed, and reported
    from polyaxon_tpu.models import build_model
    from polyaxon_tpu.ops.flash_attention import tile_report

    dense = build_model("transformer_lm", {"preset": "tiny", "seq_len": 256,
                                           "attention": "flash", "attention_block": 64})
    cfg = dense.module.cfg
    calls = tile_report(256, cfg.head_size, cfg.n_heads // cfg.n_kv_heads,
                        block_kv=cfg.attention_block)
    assert [c["block_kv"] for c in calls] == [64, 64, 64]
    assert [c["kernel"] for c in calls] == [
        "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"]


def three_steps_on(rung, case=None, seed=SEED, name=CELL):
    """Three steps of one rung of the remat ladder (`train.remat: true`, as
    the cell says) on the cell's seeded weights and feed: every metric of
    every step (loss, gradient norm, what the layers sowed) and the adapters
    after. `case`: (cell, config); this file's `small()` by default."""
    cell, config = case or small()
    ctx = ctx_for(cell, config, seed, name)
    trainer = one_chip_trainer(ctx)
    cap = drv.capture(trainer)
    drv.seed_state(trainer, cap, seed, config["init"])
    feed = drv.make_feed(ctx, trainer, seed)
    assert list(trainer.train_step.steps) == ["all", "block"]
    step, metrics = trainer.train_step.steps[rung], []
    for _ in range(3):
        trainer.state, m = step(trainer.state, feed.get())
        metrics.append(jax.device_get(m))
    adapters = drv.trainable_leaves(trainer.state.params, cell["reference"]["trainable"])
    feed.close()
    trainer.close()
    return metrics, adapters


def assert_block_takes_the_steps_of_all(sown: set, rtol: float = 1e-5, atol: float = 1e-6,
                                        **case):
    """Rung `block` against rung `all`, in float32: every step's metrics
    (`sown` among them, to `rtol`) and the adapters after the third (to
    `atol`: Adam's first update of an element near nought is a sign)."""
    want_metrics, want = three_steps_on("all", **case)
    got_metrics, got = three_steps_on("block", **case)
    assert {"loss", "grad_norm"} | sown <= set(want_metrics[0])
    for w, g in zip(want_metrics, got_metrics):
        assert set(g) == set(w)
        for name in w:
            np.testing.assert_allclose(g[name], w[name], rtol=rtol, err_msg=name)
    assert want and set(got) == set(want)
    for path in want:
        np.testing.assert_allclose(
            got[path], want[path], rtol=1e-4, atol=atol, err_msg=path
        )


def test_a_checkpoint_per_block_takes_the_same_three_steps():
    """Window and full layers, a routed MLP and `moe_stats` sown under the
    checkpoint."""
    assert_block_takes_the_steps_of_all({"moe.assignments_local", "moe.overflow"})


def test_trainer_stops_on_overflow():
    trainer = tiny_trainer([], steps=2, seq_len=256)
    # a router of noughts sends every token to experts 0 and 1, both held:
    # 2 x 512 assignments a layer where the buffer holds 512 (at 128 tokens
    # a row the buffer's one tile is already the worst case)
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x) if "router" in weights.path_str(p) else x,
        trainer.state.params,
    )
    trainer.state = trainer.state.replace(params=params)
    with pytest.raises(RuntimeError, match="did not fit"):
        trainer.run()
    trainer.close()


def test_the_cells_size_is_what_the_issue_reckoned():
    """1,716.99 M frozen and 2.42 M differentiated at the published widths,
    from shapes alone (nothing is allocated)."""
    _, _, cell, config = load_cell(CELL)
    bundle = build_model("transformer_lm",
                         {**config["model"], **cell["program"]["model_extra"]})
    shapes = jax.eval_shape(
        lambda: bundle.module.init({"params": jax.random.PRNGKey(0)},
                                   jnp.zeros((1, 4096), jnp.int32))
    )["params"]
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    lora = sum(x.size for p, x in flat if "lora_" in weights.path_str(p))
    assert lora == 2_424_832
    assert sum(x.size for _, x in flat) - lora == 1_716_986_880
    cfg = bundle.module.cfg
    assert [s.n_heads for s in cfg.layers] == [48, 72, 72, 72, 48]
    assert [s.window for s in cfg.layers] == [0, 512, 512, 512, 0]
    assert cfg.layers[0].rope.yarn_factor == 128 and cfg.layers[1].rope.theta == 10000


# ----------------------------------------------------------- what is refused
@pytest.mark.parametrize("stacked", [{"scan_layers": True}, {"pipeline_stages": 5}],
                         ids=["scan_layers", "pipeline_stages"])
def test_layers_that_differ_refuse_a_stacked_form(stacked):
    _, config = small()
    with pytest.raises(ValueError, match="layers that differ"):
        build_model("transformer_lm", {**config["model"], **stacked})


def test_expert_axis_is_checked_against_the_experts_held():
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import V1Program

    cell, config = small()
    spec = drv.program_spec(ctx_for(cell, config))
    spec["data"]["batchSize"] = 8
    with pytest.raises(ValueError, match=r"experts_held \(of 16 published\)"):
        Trainer(V1Program.model_validate(spec), mesh_axes={"expert": 8})


# ------------------------------------------------------------------- decoding
def test_decode_over_the_dense_cache_is_the_full_forward():
    """Prefill through the dense cache computes window, gate, per-layer
    heads and ropes and the routed layer as the full forward does."""
    cell, config = small()
    model = {**config["model"], "attention": "xla", "seq_len": 64}
    bundle = build_model("transformer_lm", model)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 512, (2, 48)), jnp.int32)
    params = bundle.module.init({"params": jax.random.PRNGKey(1)}, tokens)["params"]
    with jax.default_matmul_precision("highest"):
        want = bundle.module.apply({"params": params}, tokens)
        _, made = bundle.module.apply(
            {"params": params}, jnp.zeros((2, 1), jnp.int32), decode=True, mutable=["cache"]
        )
        first, filled = bundle.module.apply(
            {"params": params, "cache": made["cache"]}, tokens[:, :40], decode=True,
            mutable=["cache"],
        )
        rest, _ = bundle.module.apply(
            {"params": params, "cache": filled["cache"]}, tokens[:, 40:], decode=True,
            mutable=["cache"],
        )
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), want, atol=2e-4)


def test_a_window_refuses_the_paged_cache():
    from polyaxon_tpu.models.kv_pages import PagedKVLayout

    _, config = small()
    bundle = build_model("transformer_lm", {**config["model"], "attention": "xla"})
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = bundle.module.init({"params": jax.random.PRNGKey(1)}, tokens)["params"]
    layout = PagedKVLayout(pool_pages=4, page_tokens=8)
    with pytest.raises(NotImplementedError, match="dense cache only"):
        bundle.module.apply(
            {"params": params}, tokens, decode=True, pages=jnp.zeros((1, 2), jnp.int32),
            pos=jnp.zeros((), jnp.int32), kv_layout=layout, mutable=["cache"],
        )
