"""The Mamba mixer's two fused ops (`ops/mamba_fused.py`: conv+SiLU and
gate+RMSNorm, each a forward and a backward Pallas kernel under a
`custom_vjp`), interpreted on the CPU at small 128-aligned shapes, against
the `jax.numpy` forms of `ops/ssd.py` computed in float32: values and every
gradient, two batch rows, several row tiles and chunks (the halo at every
edge), the column range read in place or copied, the shapes the kernels
refuse, a Granite rehearsal whose widths engage both kernels against the
plain reference, rung `block` against rung `all` there, and what a LoRA
step's compiled program leaves out.
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.drivers import train as drv
from polyaxon_tpu.ops import mamba_fused as mf
from polyaxon_tpu.ops.ssd import causal_conv1d, gated_rmsnorm
from tests import test_laguna as laguna
from tests import test_ssm_hybrid as hybrid

F32 = jnp.float32
B, S = 2, 64


@pytest.fixture(autouse=True)
def no_mesh_left_bound():
    """An earlier file's trainer may have left a mesh of several devices
    bound in this process, under which the plans refuse the kernels; a test
    that builds a Trainer binds its own (one device)."""
    from polyaxon_tpu.parallel import ring

    was = ring.current_mesh()
    ring.set_current_mesh(None)
    yield
    ring.set_current_mesh(was)


def tiles(monkeypatch, rows=None, chunk=None, gate_rows=None):
    """Hold the kernels to small tiles, so that a sequence of 64 is several
    of them (the plans read these when an op is called)."""
    if rows:
        monkeypatch.setattr(mf, "_CONV_ROWS", rows)
    if chunk:
        monkeypatch.setattr(mf, "_CHUNK", chunk)
    if gate_rows:
        monkeypatch.setattr(mf, "_GATE_ROWS", gate_rows)


def conv_case(dtype, width=256, wide=640, taps=4):
    k = jax.random.split(jax.random.PRNGKey(11), 4)
    return {
        "x": jax.random.normal(k[0], (B, S, wide), dtype),
        "kernel": 0.5 * jax.random.normal(k[1], (taps, width)),
        "bias": jax.random.normal(k[2], (width,)),
    }, cotangent(k[3], width, dtype)


def cotangent(key, width, dtype):
    """Of the op's result, so already of its type: both sides see the same."""
    return jax.random.normal(key, (B, S, width)).astype(dtype).astype(F32)


def conv_want(first, width, x, kernel, bias):
    """The `jax.numpy` form in float32 on the same column range."""
    return jax.nn.silu(causal_conv1d(x[..., first : first + width].astype(F32), kernel, bias))


def gate_case(dtype, width=256, wide=640):
    k = jax.random.split(jax.random.PRNGKey(12), 4)
    return {
        "y": jax.random.normal(k[0], (B, S, width), dtype),
        "z": jax.random.normal(k[1], (B, S, wide), dtype),
        "scale": 1.0 + 0.1 * jax.random.normal(k[2], (width,)),
    }, cotangent(k[3], width, dtype)


def gate_want(first, width, y, z, scale):
    return gated_rmsnorm(y.astype(F32), z[..., first : first + width].astype(F32), scale, 1e-5)


def value_and_grads(fn, args, ct):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(F32) * ct), argnums=tuple(range(len(args)))
    )(*args.values())


def assert_close(got, want, dtype, name):
    """float32: the order of the sums only. bfloat16: the kernel rounds once,
    as it writes, what the float32 form keeps whole: under a unit in the last
    of 8 bits of the array's largest element."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), name
    scale = np.abs(want).max() + 1e-6
    tol = 2e-6 if jnp.dtype(dtype) == F32 or got.ndim < 3 else 2.0**-7
    np.testing.assert_allclose(got / scale, want / scale, atol=tol, err_msg=name)


@pytest.mark.parametrize("what", ["values", "dx", "dkernel", "dbias"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_conv_silu_is_the_jnp_form(monkeypatch, dtype, what):
    """Two tiles of 32 rows in chunks of 16, read in place at column 256 of
    a wider operand: values, and the gradient of the operand (zeros outside
    the column range), the taps and the bias."""
    tiles(monkeypatch, rows=32, chunk=16)
    args, ct = conv_case(dtype)
    assert mf.conv_plan(S, 256, dtype, 256) == {
        "path": "pallas", "block_rows": 32, "block_cols": 256, "chunk_rows": 16, "in_place": True}
    got, got_g = value_and_grads(
        lambda *a: mf.conv_silu(*a, columns=(256, 256)), args, ct)
    want, want_g = value_and_grads(functools.partial(conv_want, 256, 256), args, ct)
    if what == "values":
        out = mf.conv_silu(*args.values(), columns=(256, 256))
        assert out.dtype == jnp.dtype(dtype) and out.shape == (B, S, 256)
        assert_close(out, conv_want(256, 256, *args.values()), dtype, what)
        np.testing.assert_allclose(got, want, rtol=2e-3 if dtype == "bfloat16" else 1e-5)
        return
    i = ["dx", "dkernel", "dbias"].index(what)
    assert got_g[i].dtype == list(args.values())[i].dtype
    assert_close(got_g[i], want_g[i], dtype, what)
    if what == "dx":
        outside = np.asarray(got_g[0], np.float32)
        assert not outside[..., :256].any() and not outside[..., 512:].any()


@pytest.mark.parametrize("what", ["values", "dy", "dz", "dscale"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_rmsnorm_is_the_jnp_form(monkeypatch, dtype, what):
    """Two tiles of 32 rows; the gate read in place at column 256."""
    tiles(monkeypatch, gate_rows=32)
    args, ct = gate_case(dtype)
    assert mf.gate_plan(S, 256, dtype, 256) == {
        "path": "pallas", "block_rows": 32, "chunk_rows": 16, "in_place": True}
    fused = lambda *a: mf.gated_rmsnorm(*a, 1e-5, z_columns=(256, 256))  # noqa: E731
    if what == "values":
        out = fused(*args.values())
        assert out.dtype == jnp.dtype(dtype) and out.shape == (B, S, 256)
        assert_close(out, gate_want(256, 256, *args.values()), dtype, what)
        return
    _, got_g = value_and_grads(fused, args, ct)
    _, want_g = value_and_grads(functools.partial(gate_want, 256, 256), args, ct)
    i = ["dy", "dz", "dscale"].index(what)
    assert got_g[i].dtype == list(args.values())[i].dtype
    assert_close(got_g[i], want_g[i], dtype, what)
    if what == "dz":
        outside = np.asarray(got_g[1], np.float32)
        assert not outside[..., :256].any() and not outside[..., 512:].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "rows,chunk", [(16, 16), (32, 16), (32, 32), (64, 16), (64, 64)],
    ids=["4-tiles", "2-tiles-of-2-chunks", "2-tiles", "1-tile-of-4-chunks", "1-tile"],
)
def test_conv_halo_at_every_tile_and_chunk_edge(monkeypatch, rows, chunk, dtype):
    """Forward and backward: a tap reads the 3 rows before a tile's and a
    chunk's first, the backward the 3 after its last."""
    tiles(monkeypatch, rows=rows, chunk=chunk)
    args, ct = conv_case(dtype, wide=256)
    plan = mf.conv_plan(S, 256, dtype)
    assert (plan["block_rows"], plan["chunk_rows"]) == (rows, chunk)
    _, got_g = value_and_grads(mf.conv_silu, args, ct)
    _, want_g = value_and_grads(functools.partial(conv_want, 0, 256), args, ct)
    assert_close(mf.conv_silu(*args.values()), conv_want(0, 256, *args.values()), dtype, "values")
    for name, g, w in zip(args, got_g, want_g):
        assert_close(g, w, dtype, "d" + name)


@pytest.mark.parametrize("taps", [2, 4])
def test_conv_takes_another_count_of_taps(monkeypatch, taps):
    tiles(monkeypatch, rows=16, chunk=16)
    args, ct = conv_case("float32", wide=256, taps=taps)
    _, got_g = value_and_grads(mf.conv_silu, args, ct)
    _, want_g = value_and_grads(functools.partial(conv_want, 0, 256), args, ct)
    for name, g, w in zip(args, got_g, want_g):
        assert_close(g, w, "float32", "d" + name)


@pytest.mark.parametrize("op", ["conv_silu", "gated_rmsnorm"])
@pytest.mark.parametrize("first,in_place", [(128, False), (384, False), (0, True)])
def test_a_column_range_off_the_block_is_copied_first(monkeypatch, op, first, in_place):
    """Column 128 or 384 of 640 is no multiple of the block of 256: the
    range is sliced out (a copy) and the kernels run on it; the result and
    the padded cotangent are those of the range read in place."""
    tiles(monkeypatch, rows=32, chunk=16, gate_rows=32)
    if op == "conv_silu":
        args, ct = conv_case("float32")
        assert mf.conv_plan(S, 256, "float32", first)["in_place"] is in_place
        fused = lambda *a: mf.conv_silu(*a, columns=(first, 256))  # noqa: E731
        want = functools.partial(conv_want, first, 256)
    else:
        args, ct = gate_case("float32")
        assert mf.gate_plan(S, 256, "float32", first)["in_place"] is in_place
        fused = lambda *a: mf.gated_rmsnorm(*a, 1e-5, z_columns=(first, 256))  # noqa: E731
        want = functools.partial(gate_want, first, 256)
    (got, got_g), (wanted, want_g) = value_and_grads(fused, args, ct), value_and_grads(want, args, ct)
    np.testing.assert_allclose(got, wanted, rtol=1e-5)
    for name, g, w in zip(args, got_g, want_g):
        assert_close(g, w, "float32", "d" + name)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_rows_of_a_batch_do_not_mix(monkeypatch, direction):
    """Row 1 starts from zeros, not from row 0's last positions, and row
    0's last positions get no gradient from row 1's first."""
    tiles(monkeypatch, rows=16, chunk=16)
    args, ct = conv_case("float32", wide=256)
    x = args["x"]
    if direction == "forward":
        alone = mf.conv_silu(x[1:], args["kernel"], args["bias"])
        other = mf.conv_silu(x.at[0].set(7.0), args["kernel"], args["bias"])
        np.testing.assert_array_equal(other[1], alone[0])
        # position 0 of either row sees the last tap and the bias alone
        pre = args["bias"] + args["kernel"][-1] * x[:, 0]
        np.testing.assert_allclose(
            mf.conv_silu(*args.values())[:, 0], jax.nn.silu(pre), rtol=1e-5, atol=1e-6)
        return
    grad = jax.grad(lambda x, ct: jnp.sum(mf.conv_silu(x, args["kernel"], args["bias"]) * ct))
    both = grad(x, ct)
    row0_only = grad(x, ct.at[1].set(0.0))
    np.testing.assert_array_equal(both[0], row0_only[0])
    assert np.abs(np.asarray(both[0, -3:])).max() > 0


# ------------------------------------------------------------- what is refused
REFUSED = {
    "width-160": ((64, 160, "bfloat16"), "width 160 is no multiple of 128"),
    "sequence-60": ((60, 256, "float32"), "sequence 60 is no multiple of 8"),
    "sequence-72-bf16": ((72, 256, "bfloat16"), "sequence 72 is no multiple of 16"),
    "float16": ((64, 256, "float16"), "activations of float16"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_shape_the_kernels_refuse_takes_the_jnp_path_and_says_why(case):
    """The plan names the reason, and the op IS the `jax.numpy` form there,
    to the bit, in the activations' own type (as before the kernels)."""
    (seq, width, dtype), why = REFUSED[case]
    plan = mf.conv_plan(seq, width, dtype)
    assert plan == {"path": "xla", "why": why}
    assert mf.gate_plan(seq, width, dtype)["path"] == "xla"
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(k[0], (1, seq, width), dtype)
    w, b = jax.random.normal(k[1], (4, width)), jax.random.normal(k[2], (width,))
    np.testing.assert_array_equal(
        mf.conv_silu(x, w, b), jax.nn.silu(causal_conv1d(x, w, b)))
    y, scale = jax.random.normal(k[3], (1, seq, width), dtype), jnp.ones((width,))
    np.testing.assert_array_equal(mf.gated_rmsnorm(y, x, scale), gated_rmsnorm(y, x, scale))


def test_a_mesh_of_several_devices_takes_the_jnp_path():
    """The kernels have no partitioning rule: under a live mesh of two
    devices both plans say so; with the mesh gone they engage again."""
    from jax.sharding import Mesh

    from polyaxon_tpu.parallel import ring

    ring.set_current_mesh(Mesh(np.array(jax.devices()[:2]), ("data",)))
    try:
        why = "a mesh of 2 devices: the kernels have no partitioning rule"
        assert mf.conv_plan(64, 256, "bfloat16") == {"path": "xla", "why": why}
        assert mf.gate_plan(64, 256, "bfloat16") == {"path": "xla", "why": why}
    finally:
        ring.set_current_mesh(None)
    assert mf.conv_plan(64, 256, "bfloat16")["path"] == "pallas"


def test_the_cells_shape_engages_both_kernels_in_place():
    """1 row x 8,192 in bfloat16, `xBC` of 8,448 at column 8,192 and `z` of
    8,192 at column 0 of `in_proj`'s 16,768."""
    assert mf.conv_plan(8192, 8448, "bfloat16", 8192) == {
        "path": "pallas", "block_rows": 2048, "block_cols": 256, "chunk_rows": 64,
        "in_place": True}
    assert mf.gate_plan(8192, 8192, "bfloat16", 0) == {
        "path": "pallas", "block_rows": 64, "chunk_rows": 16, "in_place": True}


# ------------------------------------------- the whole model, kernels engaged
ENGAGED = {"mamba_n_heads": 16, "mamba_d_state": 64}


def engaged(precision="float32"):
    """The Granite rehearsal with an inner width of 256 and a state of 64:
    `xBC` is 384 wide at column 256 of `in_proj`'s 656 (read in place in
    column blocks of 128), `z` 256 wide at column 0."""
    cell, config = hybrid.small(model_over=ENGAGED, precision=precision)
    config = {**copy.deepcopy(config), **ENGAGED}  # the published keys the reference reads
    return cell, config


@functools.lru_cache(maxsize=None)
def engaged_sound():
    cell, config = engaged()
    prog, shapes, ctx = hybrid.program_side(cell, config)
    reference = drv.run_reference(ctx, shapes, hybrid.SEED)
    return prog, reference, drv.numbers(prog, reference)[0]


def test_the_rehearsals_widths_engage_both_kernels():
    events: list = []
    trainer = laguna.one_chip_trainer(
        hybrid.ctx_for(*engaged()), train={"steps": 1, "logEvery": 1},
        event_fn=lambda kind, body: events.append((kind, body)),
    )
    trainer.close()
    fused = dict(events)["model_ssm"]["fused"]
    assert [f["layer"] for f in fused] == [0, 2]
    for f in fused:
        assert f["conv_silu"] == {"path": "pallas", "block_rows": 64, "block_cols": 128,
                                  "chunk_rows": 64, "in_place": True}
        assert f["gate_norm"] == {"path": "pallas", "block_rows": 64, "chunk_rows": 16,
                                  "in_place": True}


def test_engaged_loss_of_three_steps_matches_the_reference():
    _, _, nums = engaged_sound()
    assert max(nums[f"loss_step{i}"] for i in (1, 2, 3)) < hybrid.FLOAT32["loss"], nums


def test_engaged_first_lora_gradient_matches_the_reference():
    """Through both kernels' hand-written backward, to the adapters of
    `in_proj` (under them) and `out_proj`, inside the tolerances the
    `jax.numpy` path is held to."""
    prog, _, nums = engaged_sound()
    assert sum("mamba/in_proj" in k for k in prog["grads"]) == 4
    for name in ("grad1_direction", "grad1_worst_leaf", "grad1_diff_worst_leaf"):
        assert nums[name] < hybrid.FLOAT32[name], nums


def test_engaged_a_checkpoint_per_block_takes_the_same_three_steps():
    laguna.assert_block_takes_the_steps_of_all(
        {"moe.overflow", "ssm.dt_max", "ssm.chunk_decay_min"},
        case=engaged(), seed=hybrid.SEED, name=hybrid.CELL,
    )


@pytest.mark.parametrize("differentiated", ["adapters", "everything"])
def test_a_lora_step_computes_no_frozen_cotangent(differentiated):
    """The cotangents of the conv's taps and bias and of the norm's scale
    are XLA operations of the backward rules under one scope: the compiled
    LoRA step (the Trainer's own, rung `all`) holds none of them; a gradient
    of every parameter holds them (so the scope's name is what to look for)."""
    trainer = laguna.one_chip_trainer(hybrid.ctx_for(*engaged()))
    batch = {k: jnp.zeros((1, 64), jnp.int32) for k in ("inputs", "labels")}
    if differentiated == "adapters":
        text = trainer.train_step.steps["all"].lower(trainer.state, batch).compile().as_text()
        assert mf.FROZEN_SCOPE not in text
    else:
        module = trainer.bundle.module

        def loss(params):
            return jnp.sum(module.apply({"params": params}, batch["inputs"]) ** 2)

        text = jax.jit(jax.grad(loss)).lower(trainer.state.params).compile().as_text()
        assert mf.FROZEN_SCOPE in text
    trainer.close()
