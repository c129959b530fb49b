"""A decoder of degree-2 power-retention layers (Brumby-14B-Base as one stage
of a ten-stage pipeline) on the CPU: the chunked scan of
`ops/power_retention.py` against the quadratic form of the plain reference
`cellbench/references/brumby_decoder.py`, the feature map's identity, the
whole program at the benchmark cell's `rehearse` size against the reference
over three steps, what the Trainer reports, the configuration file against
the model it builds at the published widths (shapes only), and what such a
model refuses. The rehearsal through the benchmark's own entry point and its
limits are `cellbench/tests/`'s.
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import weights
from cellbench.common import HERE, load_cell, load_module
from cellbench.drivers import train as drv
from polyaxon_tpu.models import build_model
from polyaxon_tpu.ops import power_retention as pr
from tests import test_laguna as laguna  # the cell-rehearsal helpers, by cell name

CELL = "brumby-14b-base-pp10.lora-train-32k"
SEED = 2**31 + 44
ref = load_module(HERE / "references" / "brumby_decoder.py", "test_brumby_reference")


# ------------------------------------------------------------------ the scan
def scan_case(gate: str, seq: int):
    """Seeded inputs of a small scan: 2 rows, 4 query heads over 2 key-value
    groups of 32 (two blocks of phi's 16). `spread`: gates of unit-variance
    logits (as the cell's seeded weights give); `shut`: every log-gate -20
    (each decay after the diagonal underflows); `open`: -1e-4 (the state
    remembers the whole sequence)."""
    k = jax.random.split(jax.random.PRNGKey(44), 6)
    b, h, g, p = 2, 4, 2, 32
    log_g = {
        "spread": jax.nn.log_sigmoid(jax.random.normal(k[3], (b, seq, h))),
        "shut": jnp.full((b, seq, h), -20.0),
        "open": jnp.full((b, seq, h), -1e-4),
    }[gate]
    args = (jax.random.normal(k[0], (b, seq, h, p)), jax.random.normal(k[1], (b, seq, g, p)),
            jax.random.normal(k[2], (b, seq, g, p)), log_g)
    return args, jax.random.normal(k[4], (b, seq, h, p))


def quadratic(q, k, v, log_g):
    """The reference's quadratic form, row by row."""
    return jax.vmap(lambda *row: ref.retention(*row, pr.EPS))(q, k, v, log_g)


@pytest.mark.parametrize(
    "seq,chunk,gate",
    [(64, 16, "spread"), (100, 32, "spread"), (96, 32, "shut"), (64, 64, "open")],
    ids=["4x16-spread", "100-off-the-chunk-spread", "3x32-shut", "1x64-open"],
)
def test_chunked_scan_is_the_quadratic_form(seq, chunk, gate):
    """Values and the gradients with respect to q, k, v and the log-gate,
    over several chunks, a length that is no multiple of the chunk (padded
    at its end), gates at -20 and gates that keep everything."""
    args, ct = scan_case(gate, seq)
    chunked = functools.partial(pr.retention_scan, chunk=chunk)

    def graded(fn):
        # one compiled program a side, the values its aux: the scan's nested
        # maps and checkpoints run op by op many times slower
        def loss(*a):
            y, low = fn(*a)
            return jnp.sum(y * ct), (y, low)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(*args)

    with jax.default_matmul_precision("highest"):
        (want, (want_y, _)), want_g = graded(lambda *a: (quadratic(*a), 0.0))
        (got, (got_y, low)), got_g = graded(chunked)
    # float32 on both sides: the chunked form sums in another order, and the
    # readout is a ratio whose terms reach (q . k)^2 / P ~ 30
    np.testing.assert_allclose(got_y, want_y, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert 0.0 < float(low) < float("inf")
    for name, w, g in zip("q k v log_g".split(), want_g, got_g):
        assert np.isfinite(np.asarray(g)).all(), name
        # against the gradient's largest element (float32 rounding: 1e-6 of
        # it). With every gate at -20 the log-gate's gradient (8e-6) is what
        # is left of terms of the q gradient's size (37) that cancel on the
        # diagonal, where a decay is exp(G_i - G_i): their float32 rounding
        # (2e-7) is held against the q gradient's scale, at 1e-8 of it
        shut = gate == "shut" and name == "log_g"
        scale = float(jnp.max(jnp.abs(want_g[0] if shut else w))) + 1e-6
        atol = 1e-8 if shut else 1e-4
        np.testing.assert_allclose(g / scale, w / scale, atol=atol, err_msg=name)


def test_phi_is_the_square_of_the_dot_product():
    """phi(a) . phi(b) = (a . b)^2 at the published head width, with 9,216
    features (36 pairs of 16-channel blocks) where the exact symmetric square
    has 8,256 and the full square 16,384."""
    a, b = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 128))
    with jax.default_matmul_precision("highest"):
        got = jnp.sum(pr.features(a) * pr.features(b), axis=-1)
    want = jnp.sum(a * b, axis=-1) ** 2
    # float32 sums of 9,216 products of size ~1 against one of size ~128
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)
    assert pr.feature_width(128) == 9216 and pr.features(a).shape == (64, 9216)
    with pytest.raises(ValueError, match="no multiple of phi's block"):
        pr.features(jnp.zeros((3, 24)))


# ------------------------------------------------- program against reference
def small():
    """(cell, config) at the rehearsal size: four retention layers of 4 query
    heads over 2 groups of 32, an untied head, LoRA on q/k/v/o; the program
    in float32, as the rehearsal runs it."""
    _, _, cell, config = load_cell(CELL, rehearse=True)
    cell, config = copy.deepcopy(cell), copy.deepcopy(config)
    assert cell["program"]["train"]["precision"] == "float32"
    return cell, config


@functools.lru_cache(maxsize=None)
def trained():
    """One Trainer at the rehearsal size on seeded weights, shared by the
    two tests below: its first three steps as the comparison reads them
    (`cellbench/drivers/train.py`), then one more through `Trainer.run`,
    whose log point sets the gauges; what it reported as it was built."""
    from polyaxon_tpu.telemetry.spans import get_tracer

    events: list = []
    cell, config = small()
    ctx = laguna.ctx_for(cell, config, seed=SEED, name=CELL)
    trainer = laguna.one_chip_trainer(
        ctx, train={"steps": 1, "logEvery": 1},
        event_fn=lambda kind, body: events.append((kind, body)),
    )
    cap = drv.capture(trainer)
    drv.seed_state(trainer, cap, SEED, config["init"])
    feed = drv.make_feed(ctx, trainer, SEED)

    def call(batch):
        trainer.state, metrics = trainer.train_step(trainer.state, batch)
        return metrics

    with jax.default_matmul_precision("highest"):
        prog = drv.first_steps(ctx, trainer, feed, call)
        feed.close()
        trainer.run()
    trainer.close()
    gauges = {n: trainer.telemetry.gauge(f"train.retention.{n}").value
              for n in ("log_gate_min", "denominator_min")}
    marks = [r["name"] for r in get_tracer().recent(400)]
    return prog, cap["shapes"], ctx, dict(events), gauges, marks


def test_program_matches_the_reference():
    """Three steps of the Trainer's own step in float32 against the plain
    reference on the same seeded weights and batches: each step's loss, the
    first gradient on every adapter of the four layers (reached through the
    scan's backward), and the adapters after three AdamW steps."""
    prog, shapes, ctx, *_ = trained()
    reference = drv.run_reference(ctx, shapes, SEED)
    nums, _ = drv.numbers(prog, reference)
    assert len(reference["grads"]) == 2 * 4 * 4
    assert all(np.abs(g).max() > 0 for g in reference["grads"].values())
    # float32 both sides, the order of the sums only (the same program under
    # `precision: mixed` reads grad1_direction 1e-2 and more)
    assert max(nums[f"loss_step{i}"] for i in (1, 2, 3)) < 2e-6, nums
    assert nums["grad1_direction"] < 1e-6, nums
    assert nums["grad1_worst_leaf"] < 2e-3 and nums["grad1_diff_worst_leaf"] < 3e-3, nums
    # Adam's first update is a sign, so a gradient element near nought may
    # step the other way: a few of them in a leaf, not the leaf
    assert nums["change_worst_leaf"] < 1e-2, nums


def test_trainer_reports_the_layers_the_scan_and_its_gauges():
    *_, by_kind, gauges, marks = trained()
    # 16 positions of a chunk, each log-gate a logsigmoid of a logit of
    # about unit spread about GATE_BIAS = 7 (-0.13 at a logit of 2)
    assert -16 * 0.13 < gauges["log_gate_min"] < 0.0
    assert 0.0 < gauges["denominator_min"] < 100.0
    assert by_kind["model_layers"]["layers"] == [{
        "mixer": "power_retention", "heads": 4, "kv_heads": 2, "head_width": 32, "degree": 2,
        "feature_width": 528, "qk_norm": True, "gate_width": 4, "gate_bias": 7.0, "eps": 1e-2,
        "rope_theta": 1000000.0,
        "mlp": "dense", "experts_held": 0, "experts_published": 0}] * 4
    assert by_kind["model_retention"] == {
        "rows": 1, "seq_len": 64, "chunk": 16, "chunks": 4, "run": 2, "heads_per_step": 2,
        "state_bytes_per_layer": 4 * (768 * 32 + 32 * 32) * 4,
        "largest_intermediate_bytes": 16 * 2 * 768 * 4,  # float32 activations
        "phi": "symmetric", "phi_block": 16, "feature_width": 768, "path": "xla",
        "layers": [0, 1, 2, 3],
    }
    assert "model.layers" in marks and "model.retention" in marks


# ------------------------------------------------------- the published widths
def test_the_configuration_file_agrees_with_the_built_model():
    """The repository's own configuration file: the published widths as the
    built model has them (shapes only, nothing allocated), the one key cut
    beside its published value, every assumed reading named and the numbers
    of those the layer fixes equal to the program's (the reference reads
    them from the file), and the parameter counts worked by hand, by
    `jax.eval_shape` and by `cellbench/flops_retention.py`."""
    from cellbench import flops_retention
    from polyaxon_tpu.models import retention

    _, _, cell, config = load_cell(CELL)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40} and config["num_hidden_layers"] == 4
    assert {"layer_types", "power_degree", "gate", "retention_eps", "rope", "qk_norm", "chunk",
            "output_norm", "init"} <= set(config["assumed"])
    assert (config["power_degree"], config["retention_eps"], config["retention_gate_bias"]) == (
        pr.DEGREE, pr.EPS, retention.GATE_BIAS)
    model = {**config["model"], **cell["program"]["model_extra"]}
    published = {"dim": "hidden_size", "n_heads": "num_attention_heads", "head_dim": "head_dim",
                 "n_kv_heads": "num_key_value_heads", "hidden_dim": "intermediate_size",
                 "vocab_size": "vocab_size", "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
                 "n_layers": "num_hidden_layers", "seq_len": "max_position_embeddings"}
    assert {k: model[k] for k in published} == {k: config[v] for k, v in published.items()}
    bundle = build_model("transformer_lm", model)
    shapes = jax.eval_shape(
        lambda: bundle.module.init({"params": jax.random.PRNGKey(0)},
                                   jnp.zeros((1, 256), jnp.int32))
    )["params"]
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    lora = sum(x.size for p, x in flat if "lora_" in weights.path_str(p))
    frozen = sum(x.size for _, x in flat) - lora
    assert (frozen, lora) == (2_877_896_704, 2_097_152)
    assert flops_retention.held_params(config) == frozen
    assert flops_retention.lora_params(config, 16, cell["reference"]["lora"]["targets"]) == lora
    mixer = shapes["layer_0"]["retention"]
    assert {n: mixer[f"{n}_proj"]["kernel"].shape for n in ("q", "k", "v", "o", "gate")} == {
        "q": (5120, 5120), "k": (5120, 1024), "v": (5120, 1024), "o": (5120, 5120),
        "gate": (5120, 40)}
    assert mixer["q_norm"]["scale"].shape == mixer["k_norm"]["scale"].shape == (128,)
    assert shapes["layer_0"]["mlp"]["gate_proj"]["kernel"].shape == (5120, 17408)
    assert shapes["lm_head"]["kernel"].shape == (5120, 151936)
    assert [s.mixer for s in bundle.module.cfg.layers] == ["power_retention"] * 4
    # the per-token reckoning at chunk 256: 4.33 MFLOP a head forward
    assert flops_retention.scan_flops(config, 256) / 256 / 40 == 4_326_145


# ----------------------------------------------------------- what is refused
def test_a_retention_layer_refuses_decode_and_a_stacked_form():
    _, config = small()
    with pytest.raises(ValueError, match="layers that differ"):
        build_model("transformer_lm", {**config["model"], "scan_layers": True})
    bundle = build_model("transformer_lm", config["model"])
    from polyaxon_tpu.models.retention import PowerRetention

    cfg = bundle.module.cfg
    module = PowerRetention(cfg, cfg.layers[0])
    u = jnp.zeros((1, 16, cfg.dim))
    params = jax.eval_shape(lambda: module.init({"params": jax.random.PRNGKey(0)}, u))
    with pytest.raises(NotImplementedError, match="no decode path"):
        jax.eval_shape(lambda p: module.apply(p, u, decode=True), params)
