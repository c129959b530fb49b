"""The power-retention scan's Pallas kernels (`ops/power_retention_fused.py`),
interpreted on the CPU at the head width they take (128) and a few hundred
positions: values, the smallest denominator and the gradients of q, k, v and
the log-gate against the `jax.numpy` scan of `ops/power_retention.py`, over
several runs of several chunks (the state crosses a chunk's border and a
run's: the saved states and the reverse walk), with a state that carries
and with a short memory, five query heads to a group; in bf16 no further
from float32 than the `jax.numpy` scan; what `scan_plan` refuses; and a
layer that runs either path. That the chip's compiler takes the kernels at
the cell's shape is `tests/test_tpu_compile.py`'s.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import retention
from polyaxon_tpu.ops import power_retention as pr
from polyaxon_tpu.ops import power_retention_fused as prf

P = prf.WIDTH
CHUNK, RUN = 128, 2  # 4 chunks: 2 runs of 2


def scan_case(gate: str, seq: int = 4 * CHUNK, heads: int = 5, groups: int = 1,
              rows: int = 1, dtype=jnp.float32):
    """Seeded merged inputs: q and k of unit spread (as the q/k norms leave
    them), v, log-gates of unit-spread logits about the layer's GATE_BIAS
    (`carry`: about 660 positions of memory, so the state carries across
    chunks) or about 0 (`short`: a few positions)."""
    k = jax.random.split(jax.random.PRNGKey(45), 5)
    bias = {"carry": retention.GATE_BIAS, "short": 0.0}[gate]
    args = (jax.random.normal(k[0], (rows, seq, heads * P)).astype(dtype),
            jax.random.normal(k[1], (rows, seq, groups * P)).astype(dtype),
            jax.random.normal(k[2], (rows, seq, groups * P)).astype(dtype),
            jax.nn.log_sigmoid(jax.random.normal(k[3], (rows, seq, heads)) + bias))
    return args, jax.random.normal(k[4], (rows, seq, heads * P))


def plain(q, k, v, log_g, chunk=CHUNK):
    """The `jax.numpy` scan on the same merged operands."""
    bsz, seq, heads = log_g.shape
    apart = lambda x: x.reshape(bsz, seq, -1, P)  # noqa: E731
    y, low = pr.retention_scan(apart(q), apart(k), apart(v), log_g, chunk=chunk)
    return y.reshape(bsz, seq, heads * P), low


kernels = functools.partial(prf.scan, chunk=CHUNK, run=RUN)


def graded(fn, args, ct):
    """(y, the smallest denominator, the gradients of q, k, v, log_g) of
    sum(y * ct), one compiled program."""
    def loss(*a):
        y, low = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * ct), (y, low)

    (_, (y, low)), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    return y.astype(jnp.float32), low, [g.astype(jnp.float32) for g in grads]


def relative(got, want):
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.mark.parametrize(
    "gate,heads,groups,rows",
    [("carry", 5, 1, 1), ("short", 5, 1, 1), ("carry", 2, 2, 2)],
    ids=["carry-5-to-a-group", "short-5-to-a-group", "carry-2-groups-2-rows"],
)
def test_kernels_are_the_jnp_scan(gate, heads, groups, rows):
    """float32 on both sides: the sums run in another order only."""
    args, ct = scan_case(gate, heads=heads, groups=groups, rows=rows)
    got_y, got_low, got_g = graded(kernels, args, ct)
    with jax.default_matmul_precision("highest"):
        want_y, want_low, want_g = graded(plain, args, ct)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-4, atol=2e-5)
    assert float(got_low) == pytest.approx(float(want_low), rel=1e-5)
    assert 0.0 < float(got_low) < float("inf")
    for name, w, g in zip("q k v log_g".split(), want_g, got_g):
        assert np.isfinite(np.asarray(g)).all(), name
        assert relative(g, w) < 1e-5, name


def test_the_state_crosses_chunks_and_runs():
    """At the carrying gate the first chunk's keys still weigh in the last
    chunk's readout: zeroing them moves the last run's values."""
    args, _ = scan_case("carry")
    q, k, v, log_g = args
    y = kernels(*args)[0]
    cut = kernels(q, k.at[:, :CHUNK].set(0.0), v.at[:, :CHUNK].set(0.0), log_g)[0]
    assert relative(cut[:, -CHUNK:], y[:, -CHUNK:]) > 1e-3


def test_under_a_checkpoint_the_gradients_are_the_same():
    """Under `jax.checkpoint` (rung `block`) the first forward runs without
    saving states (`optimize_remat`) and the backward's own forward saves
    them: the same values and gradients."""
    args, ct = scan_case("carry", seq=2 * CHUNK, heads=2)
    plain_y, _, plain_g = graded(kernels, args, ct)
    ckpt_y, _, ckpt_g = graded(jax.checkpoint(kernels), args, ct)
    np.testing.assert_array_equal(ckpt_y, plain_y)
    for w, g in zip(plain_g, ckpt_g):
        np.testing.assert_array_equal(g, w)

    def outputs(jaxpr, found):  # each `pallas_call`'s number of outputs
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.add(len(eqn.outvars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                outputs(sub, found)
        return found

    loss = lambda *a: jnp.sum(jax.checkpoint(kernels)(*a)[0] * ct)  # noqa: E731
    # y and the smallest denominator; those and the saved states; the backward's four
    assert outputs(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(*args).jaxpr,
                   set()) == {2, 3, 4}


def test_bf16_is_no_further_from_float32_than_the_jnp_scan():
    """Under `precision: mixed`: the kernels take bf16 operands and sum in
    float32 as the `jax.numpy` scan does; against float32 arithmetic on the
    same bf16 inputs, their values and gradients are no further off."""
    args, ct = scan_case("carry", heads=2, dtype=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        exact = graded(plain, tuple(x.astype(jnp.float32) for x in args), ct)
        jnp_bf16 = graded(plain, args, ct)
    ours = graded(kernels, args, ct)
    for n, name in enumerate(["y", "q", "k", "v", "log_g"]):
        pick = lambda r: r[0] if n == 0 else r[2][n - 1]  # noqa: E731
        theirs, mine = relative(pick(jnp_bf16), pick(exact)), relative(pick(ours), pick(exact))
        assert mine <= 1.25 * theirs + 1e-6, (name, mine, theirs)


REFUSED = {
    "a-width-off-128": ((1, 512, 128, 4, 2, 64, jnp.float32), "width 64"),
    "a-width-of-256": ((1, 512, 128, 4, 2, 256, jnp.float32), "head width 256"),
    "a-chunk-the-tiles-do-not-take": ((1, 512, 64, 4, 2, 128, jnp.float32), "chunk 64"),
    "heads-off-the-groups": ((1, 512, 128, 5, 2, 128, jnp.float32), "do not divide"),
    "a-sequence-off-the-chunk": ((1, 500, 128, 4, 2, 128, jnp.float32), "no whole number of chunks"),
    "no-whole-run": ((1, 9 * 128, 128, 4, 2, 128, jnp.float32), "9 chunks"),
    "float16": ((1, 512, 128, 4, 2, 128, jnp.float16), "float16"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_plan_refuses_with_a_reason(case):
    shape, why = REFUSED[case]
    plan = prf.scan_plan(*shape)
    assert plan["path"] == "xla" and why in plan["why"]


def test_the_plan_refuses_a_live_mesh():
    from jax.sharding import Mesh

    from polyaxon_tpu.parallel import ring

    was = ring.current_mesh()
    ring.set_current_mesh(Mesh(np.array(jax.devices()[:2]), ("data",)))
    try:
        plan = prf.scan_plan(1, 512, 128, 4, 2, 128, jnp.float32)
    finally:
        ring.set_current_mesh(was)
    assert plan["path"] == "xla" and "mesh of 2 devices" in plan["why"]


def test_the_cells_plan():
    """1 row x 32,768 at chunk 256, 40 heads over 8 groups: 16 runs of 8
    chunks, a saved state a run and head, 3.06 GB a layer."""
    plan = prf.scan_plan(1, 32768, 256, 40, 8, 128, jnp.bfloat16)
    assert plan == {"path": "pallas", "run": 8, "heads_per_step": 1,
                    "saved_state_bytes": 16 * 40 * (9216 + 128) * 128 * 4,
                    "vmem_limit_bytes": 100 * 2**20}
    assert prf.scan_plan(1, 512, 128, 4, 2, 128, jnp.float32)["run"] == 4  # a short sequence: one run


def test_a_layer_runs_either_path_alike(monkeypatch):
    """A power-retention layer at the kernels' head width takes them, with
    q, k, v merged; refused, it runs the `jax.numpy` scan, to the same
    values and gradients."""
    from polyaxon_tpu.models.transformer import LayerSpec, TransformerConfig

    cfg = TransformerConfig(vocab_size=16, dim=64, n_layers=1, n_heads=2, n_kv_heads=1,
                            head_dim=P, hidden_dim=64, seq_len=CHUNK,
                            retention_chunk_size=CHUNK)
    layer = retention.PowerRetention(cfg, LayerSpec(mixer="power_retention", n_heads=2))
    u = jax.random.normal(jax.random.PRNGKey(1), (1, CHUNK, 64))
    params = jax.jit(layer.init)(jax.random.PRNGKey(2), u)["params"]

    def run():
        def loss(p, u):
            y, stats = layer.apply({"params": p}, u, mutable=["retention_stats"])
            return jnp.sum(y * y), stats
        (_, stats), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
            params, u)
        return stats, grads

    called = []
    fused = retention.fused_scan
    monkeypatch.setattr(retention, "fused_scan", lambda *a, **k: called.append(1) or fused(*a, **k))
    got_stats, got = run()
    assert called
    monkeypatch.setattr(retention, "scan_plan", lambda *a: {"path": "xla", "why": "test"})
    with jax.default_matmul_precision("highest"):
        want_stats, want = run()
    assert len(called) == 1
    for w, g in zip(jax.tree.leaves((want_stats, want)), jax.tree.leaves((got_stats, got))):
        assert relative(g, w) < 1e-5
