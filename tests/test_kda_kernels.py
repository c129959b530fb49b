"""The delta-rule scan's Pallas kernels (`ops/kda_fused.py`), interpreted on
the CPU at 2 heads of 128 and a few hundred positions: the forward against
the token-by-token recurrence, the five gradients against autodiff of the
`jax.numpy` chunked form, several runs (the saved run states and the reverse
walk), what `scan_plan` refuses and that a refused shape runs the
`jax.numpy` form to the bit, and decays that underflow. That the chip's
compiler takes the kernels at the cell's shape is `tests/test_tpu_compile.py`'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.ops import kda as kda_ops
from polyaxon_tpu.ops import kda_fused

HEADS, WIDTH = 2, 128


def scan_case(gate: str, seq: int, dtype=jnp.float32, width: int = WIDTH, heads: int = HEADS):
    """As `tests/test_kda_mla.py::scan_case`, at a width the kernels take."""
    k = jax.random.split(jax.random.PRNGKey(36), 6)
    shape = (1, seq, heads, width)
    keys = jax.random.normal(k[1], shape)
    g = {
        "spread": -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(k[3], shape)),
        "pinned": jnp.full(shape, -5.0),
        "open": jnp.full(shape, -1e-4),
    }[gate]
    args = {
        "q": (jax.random.normal(k[0], shape) * width**-0.5).astype(dtype),
        "k": (keys / jnp.linalg.norm(keys, axis=-1, keepdims=True)).astype(dtype),
        "v": jax.random.normal(k[2], shape).astype(dtype),
        "g": g,
        "beta": jax.nn.sigmoid(jax.random.normal(k[4], shape[:3])),
    }
    return args, jax.random.normal(k[5], shape).astype(dtype)


def graded(fn, args, ct):
    f32 = jnp.float32
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(f32) * ct.astype(f32)), argnums=tuple(range(5))
    ))(*args.values())


def relative(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


LONG = 2048  # two runs of sixteen chunks of 64 (eight tiles of two chunks each)


@pytest.mark.parametrize("gate", ["spread", "pinned", "open"])
def test_forward_is_the_recurrence(gate):
    args, _ = scan_case(gate, LONG)
    plan = kda_fused.scan_plan(1, LONG, 64, HEADS, WIDTH, WIDTH, jnp.float32)
    assert (plan["path"], plan["run"], plan["chunks_per_tile"]) == ("pallas", 16, 2)
    got = kda_ops.kda_scan(*args.values(), chunk=64)
    want = kda_ops.kda_recurrence(*args.values())
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("gate", ["spread", "pinned"])
def test_gradients_are_autodiffs_of_the_chunked_form(gate, dtype):
    """Values and the five gradients over two runs of sixteen chunks (the
    saved run states, the rebuilt states and the reverse walk) against
    autodiff of the `jax.numpy` form; in bf16 both sides round, and each is
    held to the float32 form."""
    args, ct = scan_case(gate, LONG, dtype)
    kernels = functools.partial(kda_ops.kda_scan, chunk=64)
    plain = functools.partial(kda_ops._scan_xla, chunk=64)
    got, got_g = graded(kernels, args, ct)
    with jax.default_matmul_precision("highest"):
        want, want_g = graded(plain, args, ct)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        for name, w, g in zip(args, want_g, got_g):
            assert np.isfinite(np.asarray(g)).all(), name
            assert relative(g, w) < (5e-4 if gate == "pinned" else 5e-5), name
        return
    exact = {**scan_case(gate, LONG)[0], **{n: args[n].astype(jnp.float32) for n in "qkv"}}
    with jax.default_matmul_precision("highest"):
        _, exact_g = graded(plain, exact, ct.astype(jnp.float32))
    for name, e, w, g in zip(args, exact_g, want_g, got_g):
        assert np.isfinite(np.asarray(g, np.float32)).all(), name
        # the kernels stand no further from float32 than autodiff's bf16 does
        assert relative(g, e) < 1.5 * relative(w, e) + 2e-3, name


@pytest.mark.parametrize("chunk,seq", [(16, 256), (32, 256), (64, 384), (128, 256)])
def test_every_chunk_that_divides_a_tile(chunk, seq):
    """Eight, four, two and one chunk a tile; two or three tiles are one run
    (a block that is the whole axis)."""
    args, ct = scan_case("spread", seq)
    plan = kda_fused.scan_plan(1, seq, chunk, HEADS, WIDTH, WIDTH, jnp.float32)
    assert (plan["path"], plan["run"], plan["chunks_per_tile"]) == (
        "pallas", seq // chunk, 128 // chunk)
    assert plan["saved_state_bytes"] == HEADS * WIDTH * WIDTH * 4
    got, got_g = graded(functools.partial(kda_ops.kda_scan, chunk=chunk), args, ct)
    with jax.default_matmul_precision("highest"):
        want, want_g = graded(functools.partial(kda_ops._scan_xla, chunk=chunk), args, ct)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for name, w, g in zip(args, want_g, got_g):
        assert relative(g, w) < 5e-5, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_queries_and_keys_normalised_by_the_kernels(dtype):
    """`unit_scales`: q and k come as a conv leaves them, merged `[B, S, H x
    K]` beside v and g, and the kernels normalise the rows they hold; values
    and the five gradients against `l2_unit` and the `jax.numpy` scan."""
    args, ct = scan_case("spread", 256, dtype)
    args["q"], args["k"] = args["q"] * 3.0, args["k"] * 0.5 + 0.1
    scales = (WIDTH**-0.5, 1.0)
    merge = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731

    def kernels(q, k, v, g, beta):
        return kda_ops.kda_scan(merge(q), merge(k), merge(v), merge(g), beta, chunk=64,
                                unit_scales=scales).reshape(v.shape)

    def plain(q, k, v, g, beta):
        return kda_ops._scan_xla(kda_ops.l2_unit(q, scales[0]), kda_ops.l2_unit(k, scales[1]),
                                 v, g, beta, chunk=64)

    got, got_g = graded(kernels, args, ct)
    with jax.default_matmul_precision("highest"):
        want, want_g = graded(plain, args, ct)
    tight = dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-4 if tight else 2e-2)
    for name, w, g in zip(args, want_g, got_g):
        assert np.isfinite(np.asarray(g, np.float32)).all(), name
        assert relative(g, w) < (5e-5 if tight else 3e-2), name


REFUSED = {
    "a-width-off-128": ((1, 128, 32, 2, 64, 64, jnp.float32), "no multiple of 128"),
    "unequal-widths": ((1, 128, 32, 2, 128, 256, jnp.float32), "differ"),
    "a-chunk-off-the-sub-block": ((1, 192, 24, 2, 128, 128, jnp.float32), "sub-block 16"),
    "a-chunk-that-divides-no-tile": ((1, 192, 48, 2, 128, 128, jnp.float32), "does not divide 128"),
    "a-sequence-off-the-tile": ((1, 192, 64, 2, 128, 128, jnp.float32), "tile of 128"),
    "no-whole-run": ((1, 20 * 128, 64, 2, 128, 128, jnp.float32), "20 tiles"),
    "float16": ((1, 128, 32, 2, 128, 128, jnp.float16), "float16"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_plan_refuses_with_a_reason(case):
    shape, why = REFUSED[case]
    plan = kda_fused.scan_plan(*shape)
    assert plan["path"] == "xla" and why in plan["why"]


def test_the_cells_plan():
    plan = kda_fused.scan_plan(1, 16384, 64, 32, 128, 128, jnp.bfloat16)
    assert plan == {"path": "pallas", "chunks_per_step": 16, "chunks_per_tile": 2,
                    "tiles_together": 2, "heads_per_step": 1, "run": 16,
                    "saved_state_bytes": 16 * 32 * 128 * 128 * 4}  # 33.5 MB a layer


@pytest.mark.parametrize("shape", ["a-width-off-128", "a-sequence-off-the-tile"])
def test_a_refused_shape_runs_todays_code_to_the_bit(shape, monkeypatch):
    seq, width = (128, 64) if shape == "a-width-off-128" else (192, 128)
    args, ct = scan_case("spread", seq, width=width)
    called = []
    monkeypatch.setattr(kda_fused, "scan", lambda *a, **k: called.append(1))
    got, got_g = graded(functools.partial(kda_ops.kda_scan, chunk=64), args, ct)
    want, want_g = graded(functools.partial(kda_ops._scan_xla, chunk=64), args, ct)
    assert not called
    np.testing.assert_array_equal(got, want)
    for w, g in zip(want_g, got_g):
        np.testing.assert_array_equal(g, w)


def test_an_underflowing_decay_is_an_exact_zero(monkeypatch):
    """Every gate at the bound over whole chunks: the chunk's decay
    exp(-5 x 64) underflows. The factors that vanish are exact zeros, none
    is infinite or NaN, the values are the recurrence's and the gradients
    finite."""
    args, ct = scan_case("pinned", 128)
    # outside a kernel the rows' rotation is `jnp.roll`
    monkeypatch.setattr(kda_fused.pltpu, "roll", lambda x, shift, axis: jnp.roll(x, shift, axis))
    one = lambda x: x[0, :64, 0]  # noqa: E731 - the first chunk of head 0
    brow = one(args["beta"][..., None]).reshape(1, 64)
    ops = kda_fused._operands(
        one(args["q"]), one(args["k"]), one(args["g"]), brow, jnp.float32, 64)
    assert float(jnp.max(ops["whole"][0])) == 0.0  # exp(-320)
    assert float(jnp.max(ops["decayed"][32:])) == 0.0  # exp(-165) and beyond
    assert float(jnp.max(jnp.abs(ops["subs"][0]["k_facing"][16:]))) == 0.0  # after the sub-block
    assert float(jnp.max(ops["subs"][3]["facing"])) == float(jnp.exp(75.0))
    flat = [x for x in jax.tree.leaves(ops) if isinstance(x, jax.Array)]
    assert len(flat) >= 30 and all(np.isfinite(np.asarray(x, np.float32)).all() for x in flat)
    got, got_g = graded(functools.partial(kda_ops.kda_scan, chunk=64), args, ct)
    want = kda_ops.kda_recurrence(*args.values())
    np.testing.assert_allclose(got, jnp.sum(want * ct), rtol=1e-4)
    for name, g in zip(args, got_g):
        assert np.isfinite(np.asarray(g)).all(), name


def test_the_inverse_is_a_substitution():
    """(I + A)^-1 of a strictly lower triangle whose Neumann series has
    large terms (correlated keys: every entry near 1)."""
    n = 64
    a = jnp.tril(0.9 + 0.1 * jax.random.uniform(jax.random.PRNGKey(0), (n, n)), -1)
    got = np.asarray(kda_fused._inverse(a, n), np.float64)
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got @ (np.eye(n) + np.asarray(a, np.float64)), np.eye(n), atol=1e-5)
