"""A hybrid decoder of delta-rule linear-attention (KDA) and latent-attention
(MLA) layers with sigmoid-routed experts (Ling-3.0-flash-VL's language model
as one chip's share of an expert-parallel job), at the benchmark cell's
`rehearse` size on the CPU: the chunked delta rule of `ops/kda.py` against
the token-by-token recurrence, the flash kernels at unequal score and value
widths against the einsum, each mixer and the router against the plain
reference `cellbench/references/ling_decoder.py`, the sixteen shares of a routed layer, the cut's
parameter counts, and what such a model refuses. The whole program against
the reference over three steps, and what the Trainer reports, are
`tests/test_ling_hybrid.py`'s; the rehearsal through the benchmark's own
entry point and its limits are `cellbench/tests/`'s.
"""

import copy
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import weights
from cellbench.common import HERE, load_cell, load_module
from polyaxon_tpu.models import build_model
from polyaxon_tpu.models.kda import KimiDeltaAttention
from polyaxon_tpu.models.mla import LatentAttention
from polyaxon_tpu.models.moe import MoEFeedForward, limited_choice
from polyaxon_tpu.models.transformer import _make_config
from polyaxon_tpu.ops import kda as kda_ops
from polyaxon_tpu.ops.attention import dot_product_attention
from polyaxon_tpu.ops.flash_attention import choose_blocks, flash_attention, tile_report

CELL = "ling-3.0-flash-vl-ep16.lora-train-16k"
SEED = 2**31 + 35
ref = load_module(HERE / "references" / "ling_decoder.py", "test_ling_reference")


# ------------------------------------------------------------- the delta rule
def scan_case(gate: str, seq: int = 64, key: int = 16, val: int = 8):
    """Seeded inputs of a small scan. `spread`: log-decays over all of
    (-5, 0); `pinned`: every one at the bound -5 (the sub-block's own
    reference row then stands exp(75) over its last row); `open`: -1e-4 (a
    state that remembers the whole sequence)."""
    k = jax.random.split(jax.random.PRNGKey(35), 6)
    b, h = 2, 4
    keys = jax.random.normal(k[1], (b, seq, h, key))
    g = {
        "spread": -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(k[3], (b, seq, h, key))),
        "pinned": jnp.full((b, seq, h, key), -5.0),
        "open": jnp.full((b, seq, h, key), -1e-4),
    }[gate]
    return {
        "q": jax.random.normal(k[0], (b, seq, h, key)) * key**-0.5,
        "k": keys / jnp.linalg.norm(keys, axis=-1, keepdims=True),
        "v": jax.random.normal(k[2], (b, seq, h, val)),
        "g": g,
        "beta": jax.nn.sigmoid(jax.random.normal(k[4], (b, seq, h))),
    }, jax.random.normal(k[5], (b, seq, h, val))


def by_reference_recurrence(q, k, v, g, beta):
    """The reference's literal form, row by row (segments of 8)."""
    return jax.vmap(lambda *row: ref.delta_rule(*row, segment=8))(q, k, v, g, beta)


@pytest.mark.parametrize(
    "seq,chunk,gate",
    [(64, 64, "spread"), (64, 64, "pinned"), (64, 16, "spread"), (128, 32, "pinned"),
     (128, 32, "open"), (192, 64, "spread"), (192, 64, "pinned"), (96, 48, "spread")],
    ids=["1x64-spread", "1x64-pinned", "4x16-spread", "4x32-pinned", "4x32-open", "3x64-spread",
         "3x64-pinned", "2x48-spread"],
)
def test_chunked_delta_rule_is_the_recurrence(seq, chunk, gate):
    """Values and the gradients with respect to q, k, v, g and beta, against
    the literal form (which the next test holds equal to the reference's)."""
    args, ct = scan_case(gate, seq)

    def graded(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * ct), argnums=tuple(range(5))
        )(*args.values())

    chunked = functools.partial(kda_ops.kda_scan, chunk=chunk, block_heads=2)
    with jax.default_matmul_precision("highest"):
        want_y, got_y = kda_ops.kda_recurrence(*args.values()), chunked(*args.values())
        (want, want_g), (got, got_g) = graded(kda_ops.kda_recurrence), graded(chunked)
    assert np.isfinite(np.asarray(got_y)).all()
    # float32 on both sides: the chunked form sums in another order
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for name, w, g in zip(args, want_g, got_g):
        assert np.isfinite(np.asarray(g)).all(), name
        # against the gradient's largest element: with every gate at the
        # bound the gradient of g is the rounding of sums of terms exp(5)
        # times larger (5e-4 of the largest there, 5e-6 elsewhere)
        scale = float(jnp.max(jnp.abs(w))) + 1e-3
        np.testing.assert_allclose(g / scale, w / scale, atol=2e-3 if gate == "pinned" else 1e-4,
                                   err_msg=name)


@pytest.mark.parametrize("gate", ["spread", "pinned"])
def test_the_two_literal_forms_agree(gate):
    """`ops/kda.kda_recurrence` and the reference's `delta_rule` (rows one at
    a time, a checkpoint every 8 positions), values and gradients."""
    args, ct = scan_case(gate)

    def graded(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * ct), argnums=tuple(range(5))
        )(*args.values())

    with jax.default_matmul_precision("highest"):
        (want, want_g), (got, got_g) = graded(by_reference_recurrence), graded(
            kda_ops.kda_recurrence)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, w, g in zip(args, want_g, got_g):
        np.testing.assert_allclose(g, w, atol=1e-5 * (float(jnp.abs(w).max()) + 1), err_msg=name)


def test_the_walk_over_heads_keeps_one_blocks_operand_small():
    # the cell: 1 x 16,384, chunk 64, 32 heads of 128 in bf16 -> 8 heads a block
    assert kda_ops.heads_per_step(1, 16384, 64, 32, 128, 2) == 8
    assert kda_ops.largest_intermediate_bytes(1, 16384, 64, 32, 128, 2) == 8 * 16384 * 4 * 128 * 2
    assert kda_ops.heads_per_step(1, 16384, 64, 32, 128, 4) == 4
    assert kda_ops.heads_per_step(64, 16384, 64, 32, 128, 2) == 1
    # what a [chunk, chunk, key] decay of every head and chunk would take
    assert 32 * 256 * 64 * 64 * 128 * 4 > 17e9


def test_a_block_of_heads_is_every_head():
    args, _ = scan_case("spread")
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            kda_ops.kda_scan(*args.values(), chunk=32, block_heads=1),
            kda_ops.kda_scan(*args.values(), chunk=32, block_heads=4), rtol=1e-6, atol=1e-6,
        )


def test_a_chunk_off_the_sub_block_or_the_sequence_is_refused():
    args, _ = scan_case("spread")
    with pytest.raises(ValueError, match="no multiple of the chunk 48"):
        kda_ops.kda_scan(*args.values(), chunk=48)
    with pytest.raises(ValueError, match="no multiple of the sub-block 16"):
        kda_ops.kda_scan(*args.values(), chunk=8)


# ------------------------------------------- the flash kernels at two widths
@pytest.mark.parametrize("heads,kv,score,value", [(4, 4, 48, 32), (4, 2, 24, 16), (2, 2, 16, 32)],
                         ids=["mla-48-32", "gqa-24-16", "value-wider"])
def test_flash_kernels_take_a_score_and_a_value_width(heads, kv, score, value):
    """Forward and the three gradients against the einsum (Pallas interpret
    mode): scores over `score` columns (the default scale 1/sqrt(score)),
    values and output of `value`."""
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(k[0], (2, 128, heads, score))
    key = jax.random.normal(k[1], (2, 128, kv, score))
    v = jax.random.normal(k[2], (2, 128, kv, value))
    ct = jax.random.normal(k[3], (2, 128, heads, value))

    def graded(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * ct), argnums=(0, 1, 2))(q, key, v)

    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, key, v, block_q=32, block_kv=64)
        (got, got_g), (want, want_g) = (
            graded(lambda *a: flash_attention(*a, block_q=32, block_kv=64)),
            graded(lambda *a: dot_product_attention(*a, causal=True)),
        )
        np.testing.assert_allclose(out, dot_product_attention(q, key, v, causal=True), atol=2e-5)
    assert out.shape == (2, 128, heads, value)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for g, w in zip(got_g, want_g):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_equal_widths_choose_the_tiles_they_chose():
    """`value_dim` left out, or equal to the score width, is the call the
    three older cells make: the same blocks, the same report."""
    for kernel in ("fwd", "dq", "dkv"):
        for seq, width, group in ((2048, 128, 2), (4096, 128, 9), (8192, 128, 4), (2048, 64, 4)):
            assert choose_blocks(kernel, seq, width, group) == choose_blocks(
                kernel, seq, width, group, value_dim=width)
    assert tile_report(2048, 128, 2) == tile_report(2048, 128, 2, value_dim=128)
    wide = tile_report(16384, 192, 1, value_dim=128)
    assert [c["value_dim"] for c in wide] == [128] * 3 and wide[0]["head_dim"] == 192
    with pytest.raises(ValueError, match="scores need one width"):
        flash_attention(jnp.zeros((1, 64, 2, 32)), jnp.zeros((1, 64, 2, 16)),
                        jnp.zeros((1, 64, 2, 16)))


# ------------------------------------------------- program against reference
def small(model_over=None, precision="float32"):
    """(cell, config) at the rehearsal size: a dense KDA layer, KDA x3, MLA,
    KDA x2; routed + shared MLPs; an untied head; LoRA on all six targets."""
    _, _, cell, config = load_cell(CELL, rehearse=True)
    cell, config = copy.deepcopy(cell), copy.deepcopy(config)
    cell["program"]["train"]["precision"] = precision
    config["model"].update(model_over or {})
    return cell, config



def model_cfg():
    cell, config = small()
    return _make_config({**config["model"], **cell["program"]["model_extra"], "attention": "xla"})


def mixer_case(kind: str):
    """A mixer of the rehearsal's widths alone: the program's module with
    seeded parameters, the same leaves under the reference's names, and a
    normed input [2, 64, 64]."""
    cfg = model_cfg()
    d = ref.Dims.from_published(small()[1])
    index = d.kinds.index(kind)
    module = (
        KimiDeltaAttention(cfg, cfg.layers[index].n_heads) if kind == "kda"
        else LatentAttention(cfg, cfg.layers[index])
    )
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.dim))
    params = module.init({"params": jax.random.PRNGKey(4)}, u)["params"]
    flat = {weights.path_str(p): x for p, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    # every leaf away from its init's ones and zeros, adapters included
    flat = {
        name: x + 0.3 * jax.random.normal(jax.random.PRNGKey(i), x.shape)
        for i, (name, x) in enumerate(sorted(flat.items()))
    }
    names = (
        {"q": "q_proj", "k": "k_proj", "v": "v_proj", "o": "o_proj", "f": "f_proj/kernel",
         "g": "g_proj/kernel", "b": "b_proj/kernel", "q_conv": "q_conv_kernel",
         "k_conv": "k_conv_kernel", "v_conv": "v_conv_kernel", "dt_bias": "dt_bias",
         "A_log": "A_log", "o_norm": "o_norm_scale"}
        if kind == "kda" else
        {"q": "q_proj", "kv_a": "kv_a_proj", "kv_b": "kv_b_proj", "o": "o_proj",
         "kv_a_norm": "kv_a_norm/scale", "q_norm": "q_norm/scale", "k_norm": "k_norm/scale",
         "attn_gate": "gate_proj/kernel"}
    )
    w, lora = {}, {}
    for name, path in names.items():
        if path.endswith("_proj"):
            w[name] = flat[f"{path}/kernel"]
            lora[name] = {ab: flat[f"{path}/{ab}"] for ab in ("lora_a", "lora_b")}
        else:
            w[name] = flat[path]
    unflat = {}
    for path, x in flat.items():
        node = unflat
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return module, unflat, u, w, lora, d, cfg


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_mixer_matches_the_reference(kind):
    """Output and the gradients of the input and of every adapter: all four
    LoRA targets of each mixer are reached, KDA's through the scan's backward
    and the convolutions, MLA's `kv_a_proj` through the latent's norm, the
    shared rotary key and `kv_b_proj`."""
    module, params, u, w, lora, d, cfg = mixer_case(kind)
    scale = cfg.lora_alpha / cfg.lora_rank
    ct = jax.random.normal(jax.random.PRNGKey(9), u.shape)
    side = ref._kda if kind == "kda" else ref._mla

    def program(params, u):
        return jnp.sum(module.apply({"params": params}, u, mutable=["kda_stats"])[0] * ct)

    def reference(lora, u):
        rows = [side(u[r], w, lora, d, scale, ref._mm_f32) for r in range(u.shape[0])]
        return jnp.sum(jnp.stack(rows) * ct)

    with jax.default_matmul_precision("highest"):
        got, (got_p, got_u) = jax.value_and_grad(program, argnums=(0, 1))(params, u)
        want, (want_l, want_u) = jax.value_and_grad(reference, argnums=(0, 1))(lora, u)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    np.testing.assert_allclose(got_u, want_u, atol=2e-4 * float(jnp.abs(want_u).max()))
    assert sorted(want_l) == (["k", "o", "q", "v"] if kind == "kda" else ["kv_a", "kv_b", "o", "q"])
    for target, pair in want_l.items():
        for ab, want_g in pair.items():
            got_g = got_p[f"{target}_proj"][ab]
            assert float(jnp.abs(want_g).max()) > 1e-4, (target, ab)
            np.testing.assert_allclose(
                got_g, want_g, atol=2e-4 * float(jnp.abs(want_g).max()), err_msg=f"{target}.{ab}"
            )


def router_case(bias_std=0.01):
    d = ref.Dims.from_published(small()[1], lo=0, held=16)
    k = jax.random.split(jax.random.PRNGKey(21), 3)
    scores = jax.nn.sigmoid(jax.random.normal(k[0], (256, d.router)))
    return d, scores, bias_std * jax.random.normal(k[1], (d.router,))


def test_router_chooses_within_the_best_groups_with_the_bias():
    """16 experts in 4 groups, 2 kept, top-4: the program's choice is the
    reference's; every chosen expert lies in a kept group; the weights are
    2.5 x the chosen scores renormalised, never the biased ones; and a bias
    of the scores' own size changes some choices while none changes none."""
    d, scores, bias = router_case()
    assert (d.groups, d.groups_kept, d.top_k, d.routed_scale) == (4, 2, 4, 2.5)
    want_e, want_w = ref.routing_weights(scores, bias, d)
    got_e = limited_choice(scores, d.top_k, bias, d.groups, d.groups_kept)
    np.testing.assert_array_equal(np.sort(got_e, -1), np.sort(want_e, -1))
    per = d.router // d.groups
    grouped = (scores + bias).reshape(-1, d.groups, per)
    group_score = np.sort(np.asarray(grouped), -1)[..., -2:].sum(-1)
    kept = np.argsort(-group_score, -1)[:, : d.groups_kept]
    assert all(set(np.asarray(e) // per) <= set(k) for e, k in zip(want_e, kept))
    np.testing.assert_allclose(want_w.sum(-1), 2.5, rtol=1e-6)
    picked = jnp.take_along_axis(scores, want_e, -1)
    np.testing.assert_allclose(want_w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # without groups the choice differs on some tokens: the groups bind
    free = jax.lax.top_k(scores + bias, d.top_k)[1]
    assert (np.sort(free, -1) != np.sort(want_e, -1)).any()
    # the bias: none changes nothing, a large one changes some choices
    none = limited_choice(scores, d.top_k, None, d.groups, d.groups_kept)
    np.testing.assert_array_equal(
        np.sort(none, -1), np.sort(ref.routing_weights(scores, 0.0 * bias, d)[0], -1))
    large = 20 * bias
    moved = limited_choice(scores, d.top_k, large, d.groups, d.groups_kept)
    assert (np.sort(moved, -1) != np.sort(none, -1)).any()
    np.testing.assert_array_equal(
        np.sort(moved, -1), np.sort(ref.routing_weights(scores, large, d)[0], -1))


def test_rows_no_expert_owns_may_hold_anything(monkeypatch):
    """The buffer's rows past the held experts' groups come out of the
    grouped-product kernel as whatever it leaves there (on the chip: stale
    memory, NaN bit patterns among it; PR 35's first chip run lost its first
    gradient to them). With NaN planted in those rows the layer's output, the
    gradient of its input and of its router (through the routing weights) are
    what they are with zeros there."""
    real = jax.lax.ragged_dot

    def leaves_nan(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes)
        owned = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.where(owned, out, jnp.nan)

    d = ref.Dims.from_published(small()[1], lo=0, held=16)
    layer = MoEFeedForward(
        d.hidden, d.expert, d.router, held=4, offset=4, top_k=d.top_k, routed_scale=2.5,
        norm_topk=True, aux_weight=0.0, score="sigmoid", bias=True, groups=4, groups_kept=2,
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, d.hidden))
    params = layer.init({"params": jax.random.PRNGKey(2)}, x)["params"]

    def graded():
        def loss(p, x):
            y, _ = layer.apply({"params": p}, x, mutable=["moe_stats"])
            return jnp.sum(y * jnp.cos(jnp.arange(d.hidden)))
        return jax.value_and_grad(loss, argnums=(0, 1))(params, x)

    want, (want_p, want_x) = graded()
    monkeypatch.setattr(jax.lax, "ragged_dot", leaves_nan)
    got, (got_p, got_x) = graded()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_x, want_x, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_p["router"]["kernel"], want_p["router"]["kernel"],
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(want_p["router"]["kernel"]).max()) > 0


def routed_layer_case():
    """One routed layer of the rehearsal's widths with ALL 16 experts:
    weights, tokens, and what the uncut reference gives for routed + shared."""
    d = ref.Dims.from_published(small()[1], lo=0, held=16)
    ks = jax.random.split(jax.random.PRNGKey(11), 9)
    D, F, Fs, n = d.hidden, d.expert, d.shared, d.router
    w = {
        "router": jax.random.normal(ks[0], (D, n)) / np.sqrt(D),
        "router_bias": 0.2 * jax.random.normal(ks[8], (n,)),
        "experts.gate": jax.random.normal(ks[1], (n, D, F)) / np.sqrt(D),
        "experts.up": jax.random.normal(ks[2], (n, D, F)) / np.sqrt(D),
        "experts.down": jax.random.normal(ks[3], (n, F, D)) / np.sqrt(F),
        "shared.gate": jax.random.normal(ks[4], (D, Fs)) / np.sqrt(D),
        "shared.up": jax.random.normal(ks[5], (D, Fs)) / np.sqrt(D),
        "shared.down": jax.random.normal(ks[6], (Fs, D)) / np.sqrt(Fs),
    }
    m = jax.random.normal(ks[7], (256, D))
    with jax.default_matmul_precision("highest"):
        whole = ref._routed(m, w, d, ref._mm_f32) + ref._swiglu(
            m, w["shared.gate"], w["shared.up"], w["shared.down"], ref._mm_f32
        )
    return d, w, m, whole


@pytest.mark.parametrize("side", ["program", "reference"])
def test_sixteen_shares_add_up_to_the_uncut_layer(side):
    """The guide's share test at the cell's own cut, sixteen shares: the
    routed parts of every share summed (one expert each here; 32 of 512 in
    the cell) and the shared expert counted once equal the uncut reference.
    Every share routes over all 16 experts and all 4 groups."""
    d, w, m, whole = routed_layer_case()
    config = small()[1]
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for lo in range(16):
            part = {k: (v[lo : lo + 1] if k.startswith("experts.") else v) for k, v in w.items()}
            if side == "reference":
                ds = ref.Dims.from_published(config, lo=lo, held=1)
                total = total + ref._routed(m, part, ds, ref._mm_f32)
            else:
                layer = MoEFeedForward(
                    d.hidden, d.expert, d.router, held=1, offset=lo, top_k=d.top_k,
                    routed_scale=d.routed_scale, norm_topk=d.norm_topk, aux_weight=0.0,
                    score="sigmoid", bias=True, groups=d.groups, groups_kept=d.groups_kept,
                )
                params = {"router": {"kernel": part["router"]},
                          "router_bias": part["router_bias"],
                          "gate_kernel": part["experts.gate"], "up_kernel": part["experts.up"],
                          "down_kernel": part["experts.down"]}
                total = total + layer.apply({"params": params}, m[None], mutable=["moe_stats"])[0][0]
        total = total + ref._swiglu(
            m, w["shared.gate"], w["shared.up"], w["shared.down"], ref._mm_f32
        )
    np.testing.assert_allclose(total, whole, atol=2e-5)  # f32 sums, other order


def test_the_cells_size_is_what_the_issue_reckoned():
    """1,733,806,400 frozen and 2,991,104 differentiated at the published
    widths, from shapes alone (nothing is allocated); `cellbench/flops_kda.py`
    counts the same from the published keys."""
    from cellbench import flops_kda

    _, _, cell, config = load_cell(CELL)
    bundle = build_model("transformer_lm",
                         {**config["model"], **cell["program"]["model_extra"]})
    shapes = jax.eval_shape(
        lambda: bundle.module.init({"params": jax.random.PRNGKey(0)},
                                   jnp.zeros((1, 16384), jnp.int32))
    )["params"]
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    lora = sum(x.size for p, x in flat if "lora_" in weights.path_str(p))
    frozen = sum(x.size for _, x in flat) - lora
    assert (frozen, lora) == (1_733_806_400, 2_991_104)
    assert flops_kda.held_params(config) == frozen
    assert flops_kda.lora_params(config, 16, cell["reference"]["lora"]["targets"]) == lora
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    frozen_of = lambda tree: sum(  # noqa: E731
        x.size for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]
        if "lora_" not in weights.path_str(p))
    assert frozen_of(shapes["layer_1"]["kda"]) == 63_049_888
    assert frozen_of(shapes["layer_4"]["mla"]) == 31_966_080
    assert size(shapes["layer_1"]["moe"]) == 2560 * 512 + 512 + 32 * 3 * 2560 * 768
    cfg = bundle.module.cfg
    assert [s.mixer for s in cfg.layers] == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    assert [s.routed for s in cfg.layers] == [False] + [True] * 6
    assert (cfg.router_score, cfg.router_groups, cfg.router_groups_kept) == ("sigmoid", 8, 4)
    assert shapes["layer_4"]["mla"]["q_proj"]["kernel"].shape == (2560, 32 * 192)
    assert shapes["layer_4"]["mla"]["kv_a_proj"]["kernel"].shape == (2560, 576)
    assert shapes["layer_4"]["mla"]["kv_b_proj"]["kernel"].shape == (512, 32 * 256)
    assert shapes["layer_0"]["mlp"]["gate_proj"]["kernel"].shape == (2560, 6144)
    assert shapes["embed"]["embedding"].shape == (19648, 2560)
    assert shapes["lm_head"]["kernel"].shape == (2560, 19648)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the guide's catalog is not on this machine")
def test_the_configuration_holds_the_catalogs_numbers_but_the_four_cut():
    _, _, _, config = load_cell(CELL)
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if '"Ling-3.0-flash-VL"' in line)
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]


# ----------------------------------------------------------- what is refused
@pytest.mark.parametrize("stacked", [{"scan_layers": True}, {"pipeline_stages": 7}],
                         ids=["scan_layers", "pipeline_stages"])
def test_a_hybrid_refuses_a_stacked_form(stacked):
    _, config = small()
    with pytest.raises(ValueError, match="layers that differ"):
        build_model("transformer_lm", {**config["model"], **stacked})


@pytest.mark.parametrize("kind,why", [("kda", "matrix state"), ("mla", "cache of the latent")])
def test_a_kda_or_mla_layer_refuses_decode(kind, why):
    module, params, u, *_ = mixer_case(kind)
    with pytest.raises(NotImplementedError, match=why):
        jax.eval_shape(lambda p: module.apply({"params": p}, u, decode=True), params)


def test_unknown_kinds_scores_and_groups_are_refused():
    _, config = small()
    with pytest.raises(ValueError, match="unknown layer type 'rwkv'"):
        build_model("transformer_lm", {**config["model"], "layer_types": ["rwkv"] * 7})
    with pytest.raises(ValueError, match="router_groups 3"):
        build_model("transformer_lm", {**config["model"], "router_groups": 3})
    with pytest.raises(ValueError, match="hold fewer than experts_per_token"):
        build_model("transformer_lm", {**config["model"], "router_groups_kept": 1,
                                       "experts_per_token": 5})
    bundle = build_model("transformer_lm", {**config["model"], "router_score": "tanh"})
    with pytest.raises(ValueError, match="unknown router score 'tanh'"):
        jax.eval_shape(lambda: bundle.module.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 64), jnp.int32)))
