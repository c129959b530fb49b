"""Flash-attention kernel and ring-attention correctness vs the XLA
reference implementation, forward and backward (pallas kernels run
interpreted on the CPU test mesh; the same code compiles on TPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.ops.attention import dot_product_attention
from polyaxon_tpu.ops.flash_attention import flash_attention
from polyaxon_tpu.parallel.mesh import build_mesh
from polyaxon_tpu.parallel.ring import ring_attention, set_current_mesh


def _qkv(B=2, S=128, H=4, D=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla_forward(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal, backend="xla")
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla_backward(causal):
    q, k, v = _qkv(S=64)

    def loss_flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=32, block_kv=32
        ).sum()

    def loss_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=causal, backend="xla").sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)


def test_flash_rejects_indivisible_seq():
    q, k, v = _qkv(S=100)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_kv=64)


# ------------------------------------------------- the tiling and the walk
# (group, block_q, block_kv, causal, window) at 256 tokens: several q and kv
# blocks, block_q under and over block_kv, so interior, edge and dead tiles
# are all there; windows aligned and unaligned to both blocks
TILINGS = {
    "g2-32x64": (2, 32, 64, True, None),
    "g6-64x32": (6, 64, 32, True, None),
    "g9-128x32": (9, 128, 32, True, None),
    "g2-32x128": (2, 32, 128, True, None),
    "g1-64x64-whole": (1, 64, 64, False, None),
    "g6-32x64-whole": (6, 32, 64, False, None),
    "g2-64x32-whole": (2, 64, 32, False, None),
    "g9-64x32-w1": (9, 64, 32, True, 1),
    "g9-64x32-w100": (9, 64, 32, True, 100),
    "g9-64x32-w128": (9, 64, 32, True, 128),
    "g9-64x32-w200": (9, 64, 32, True, 200),
    "g2-32x64-w1": (2, 32, 64, True, 1),
    "g2-32x64-w100": (2, 32, 64, True, 100),
    "g2-32x64-w128": (2, 32, 64, True, 128),
    "g2-32x64-w200": (2, 32, 64, True, 200),
    "g6-the-rule-around-kv64": (6, None, 64, True, 100),
    "g2-the-rule": (2, None, None, True, None),
}


@functools.lru_cache(maxsize=None)
def _tiled(case):
    """(flash, xla): forward and the three gradients on one seeded case."""
    group, block_q, block_kv, causal, window = TILINGS[case]
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (1, 256, 2 * group, 16))
    k = jax.random.normal(ks[1], (1, 256, 2, 16))
    v = jax.random.normal(ks[2], (1, 256, 2, 16))
    ct = jax.random.normal(ks[3], q.shape)

    def both(fn):
        out = fn(q, k, v)
        grads = jax.grad(lambda *a: (fn(*a) * ct).sum(), (0, 1, 2))(q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"), (out, *grads)))

    return (
        both(lambda *a: flash_attention(*a, causal=causal, block_q=block_q,
                                        block_kv=block_kv, window=window)),
        both(lambda *a: dot_product_attention(*a, causal=causal, backend="xla",
                                              window=window)),
    )


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(TILINGS))
def test_flash_tiling_matches_masked_xla(case, what):
    flash, xla = _tiled(case)
    # float32 online softmax against the whole softmax
    np.testing.assert_allclose(flash[what], xla[what], atol=2e-5, rtol=2e-5)


def _walked(walk, outer):
    """[(block, live)] of every step of outer block `outer`, as the index
    maps (clamped) and the kernel bodies (live) compute them."""
    return [
        (int(walk.at(outer, step, clamp=True)), bool(walk.at(outer, step)[1]))
        for step in range(walk.steps)
    ]


@pytest.mark.parametrize("kv_major", [False, True], ids=["q-major", "kv-major"])
@pytest.mark.parametrize(
    "block_q,block_kv,causal,window",
    [(128, 512, True, None), (512, 128, True, None), (256, 256, True, 512),
     (128, 256, True, 300), (256, 256, False, None)],
    ids=["128x512", "512x128", "w512", "w300-unaligned", "whole"],
)
def test_a_dead_step_repeats_the_previous_block(block_q, block_kv, causal, window, kv_major):
    """Pallas issues a DMA when a block index changes: a skipped step must
    keep the index of the step before it, with or without a window, in the
    q-major kernels and in dk/dv's q-side maps; a live step fetches the
    block it computes on."""
    from polyaxon_tpu.ops.flash_attention import _Walk

    walk = _Walk(2048, block_q, block_kv, causal, window, kv_major=kv_major)
    dead = 0
    for outer in range(walk.n_outer):
        steps = _walked(walk, outer)
        first, last = walk.span(outer)
        assert [b for b, live in steps if live] == list(range(first, last + 1))
        assert steps[0][1]  # every row sees its own key: the first step is live
        for (block, live), (before, _) in zip(steps[1:], steps):
            if not live:
                dead += 1
                assert block == before == last
    counts = walk.counts()
    assert counts["grid_steps"] - counts["live_steps"] == dead
    assert (dead > 0) == causal  # whole attention has no edge to fall off


def test_only_a_tile_an_edge_crosses_is_masked():
    from polyaxon_tpu.ops.flash_attention import _Walk

    def needs(walk, iq, ik):  # by the elements themselves
        r = np.arange(iq * walk.block_q, (iq + 1) * walk.block_q)[:, None]
        c = np.arange(ik * walk.block_kv, (ik + 1) * walk.block_kv)[None, :]
        seen = r >= c
        if walk.window is not None:
            seen &= r - c < walk.window
        return not seen.all()

    for bq, bkv, window in [(64, 128, None), (128, 64, None), (64, 128, 100), (128, 64, 256)]:
        walk = _Walk(1024, bq, bkv, True, window)
        masked = 0
        for iq in range(walk.nq):
            first, last = walk.span(iq)
            for ik in range(first, last + 1):
                assert bool(walk.edge(iq, ik)) == needs(walk, iq, ik), (bq, bkv, window, iq, ik)
                masked += needs(walk, iq, ik)
        assert walk.counts()["mask_steps"] == masked
        assert masked < walk.counts()["live_steps"] or window == 100


# --------------------------------------------------------------- the rule
@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize(
    "seq,head_dim,group,window",
    [(2048, 128, 2, None), (4096, 128, 6, None), (4096, 128, 9, 512), (2048, 64, 4, None),
     (2496, 128, 2, None), (8192, 128, 4, None), (1000, 64, 1, None), (100, 32, 1, None),
     (4096, 128, 1, None), (512, 128, 8, 64)],
)
def test_the_rule_gives_blocks_that_divide_and_are_not_narrow(kernel, seq, head_dim, group, window):
    from polyaxon_tpu.ops.flash_attention import choose_blocks

    bq, bkv = choose_blocks(kernel, seq, head_dim, group, window)
    for b in (bq, bkv):
        assert seq % b == 0
        assert b % 8 == 0 or b == seq
        assert b >= min(128, seq) and b <= 1024
    # an explicit block is obeyed to the letter, the other chosen around it
    assert choose_blocks(kernel, seq, head_dim, group, window, block_q=seq // 2)[0] == seq // 2
    if seq % 8 == 0:
        assert choose_blocks(kernel, seq, head_dim, group, window, block_kv=seq // 4)[1] == seq // 4
    assert choose_blocks(kernel, seq, head_dim, group, window, block_q=4, block_kv=seq) == (4, seq)
    # a pure function of the shape
    assert choose_blocks(kernel, seq, head_dim, group, window) == (bq, bkv)


def test_the_rule_is_the_least_estimate_and_knows_the_cells():
    """The choice is the least `_estimate_seconds` among the pairs the
    sequence allows and VMEM holds, and at the benchmark's call shapes it is the pair the
    chip measured fastest (PR 30's sweep): wide kv blocks for the forward,
    512 x 512 for the backward, and for a window of 512 at 4,096 fewer
    executed pairs than the 2x of PR 29's 128 x 512 in both backward kernels."""
    from polyaxon_tpu.ops.flash_attention import (
        _VMEM_BUDGET, _Walk, _candidates, _estimate_seconds, _vmem_bytes, choose_blocks,
        tile_report,
    )

    for kernel in ("fwd", "dq", "dkv"):
        for seq, head_dim, group, window in [(2048, 128, 2, None), (4096, 128, 9, 512)]:
            costs = {
                (bq, bkv): _estimate_seconds(
                    kernel, _Walk.of(kernel, seq, (bq, bkv), True, window), head_dim, group, 2)
                for bq in _candidates(seq, None) for bkv in _candidates(seq, None)
                if _vmem_bytes(kernel, bq, bkv, head_dim, group, 2) <= _VMEM_BUDGET
            }
            chosen = choose_blocks(kernel, seq, head_dim, group, window)
            assert costs[chosen] == min(costs.values())
    blocks = lambda *shape: [(c["block_q"], c["block_kv"]) for c in tile_report(*shape)]  # noqa: E731
    assert blocks(2048, 128, 2) == [(512, 1024), (512, 512), (512, 512)]
    assert blocks(4096, 128, 6) == [(256, 1024), (256, 512), (512, 512)]
    assert blocks(4096, 128, 9, 512) == [(256, 512), (256, 256), (256, 256)]
    assert blocks(2048, 64, 4) == [(512, 1024), (512, 512), (512, 512)]
    waste = [c["executed_over_required"] for c in tile_report(4096, 128, 9, 512)]
    assert waste[1] < 1.51 and waste[2] < 1.51 and waste[0] < 2.01


def test_the_rule_refuses_what_no_block_divides():
    from polyaxon_tpu.ops.flash_attention import choose_blocks, flash_shapes_ok

    # 2,056 = 8 x 257: no sublane-aligned divisor between 128 and 1,024
    with pytest.raises(ValueError, match="not divisible"):
        choose_blocks("fwd", 2056, 128)
    assert not flash_shapes_ok(2056)
    assert flash_shapes_ok(2496) and flash_shapes_ok(2048, block_kv=512)
    assert not flash_shapes_ok(2048, block_kv=192)  # an explicit block must divide
    assert not flash_shapes_ok(2048, block_q=4, block_kv=4 * 3)
    assert flash_shapes_ok(100)  # one block, the whole sequence


@pytest.mark.parametrize(
    "causal", [True, pytest.param(False, marks=pytest.mark.slow)]
)
def test_ring_matches_xla(causal):
    """Ring attention over a real context axis == single-device attention."""
    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(S=64)
        ref = dot_product_attention(q, k, v, causal=causal, backend="xla")
        out = ring_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


@pytest.mark.slow
def test_ring_backward_matches_xla():
    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(S=64)
        g1 = jax.grad(lambda q: ring_attention(q, k, v).sum())(q)
        g2 = jax.grad(
            lambda q: dot_product_attention(
                q, k, v, causal=True, backend="xla"
            ).sum()
        )(q)
        np.testing.assert_allclose(g1, g2, atol=5e-5, rtol=5e-5)
    finally:
        set_current_mesh(None)


@pytest.mark.slow
def test_ring_degrades_indivisible_batch():
    """B=1 (eval/decode) on a data×context mesh: the batch axis degrades to
    replication instead of a shard_map divisibility error."""
    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(B=1, S=64)
        ref = dot_product_attention(q, k, v, causal=True, backend="xla")
        out = ring_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


def test_ring_falls_back_to_xla_on_indivisible_seq():
    """S not divisible by the context degree: einsum fallback, same math."""
    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(S=66)
        ref = dot_product_attention(q, k, v, causal=True, backend="xla")
        out = ring_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


def test_ring_falls_back_without_context_axis():
    set_current_mesh(None)
    q, k, v = _qkv(S=64)
    out = ring_attention(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True, backend="xla")
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_trainer_ring_attention_end_to_end():
    """Full train step with context parallelism: mesh {data:2, context:4},
    transformer with attention=ring — loss finite and sequence sharded."""
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import (
        V1DataSpec,
        V1ModelSpec,
        V1OptimizerSpec,
        V1Program,
        V1TrainSpec,
    )

    prog = V1Program(
        model=V1ModelSpec(
            name="transformer_lm",
            config={"preset": "tiny", "seq_len": 128, "attention": "ring"},
        ),
        data=V1DataSpec(
            name="synthetic_text",
            batch_size=4,
            config={"seq_len": 128, "vocab_size": 4096},
        ),
        optimizer=V1OptimizerSpec(name="adamw", learning_rate=1e-3),
        train=V1TrainSpec(steps=2, log_every=1, precision="float32"),
    )
    trainer = Trainer(prog, mesh_axes={"data": 2, "context": 4})
    result = trainer.run()
    assert np.isfinite(result.history[-1]["loss"])


# --------------------------------------------------------------- ulysses
@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_xla(causal):
    """All-to-all sequence parallelism == single-device attention."""
    from polyaxon_tpu.parallel.ulysses import ulysses_attention

    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(S=64)  # H=8 divisible by context=4
        ref = dot_product_attention(q, k, v, causal=causal, backend="xla")
        out = ulysses_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


def test_ulysses_rejects_indivisible_heads():
    from polyaxon_tpu.parallel.ulysses import ulysses_attention

    mesh = build_mesh({"context": 8})  # H=8 heads... use S small
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(S=64)
        # H=8, context=8: divisible — force the error with a model axis? use
        # a 3-head tensor instead
        import jax.numpy as jnp

        q3, k3, v3 = (x[:, :, :6] for x in (q, k, v))  # 6 heads vs ctx 8
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q3, k3, v3)
    finally:
        set_current_mesh(None)


def test_ulysses_falls_back_without_context_axis():
    from polyaxon_tpu.parallel.ulysses import ulysses_attention

    set_current_mesh(None)
    q, k, v = _qkv(S=32)
    ref = dot_product_attention(q, k, v, causal=True, backend="flash")
    np.testing.assert_allclose(ulysses_attention(q, k, v), ref, atol=1e-6)


@pytest.mark.slow
def test_trainer_ulysses_attention_end_to_end(tmp_home):
    """Full train step with attention=ulysses on a context mesh."""
    from polyaxon_tpu.runtime.trainer import Trainer
    from polyaxon_tpu.schemas.run_kinds import (
        V1DataSpec,
        V1ModelSpec,
        V1OptimizerSpec,
        V1Program,
        V1TrainSpec,
    )

    program = V1Program(
        model=V1ModelSpec(
            name="transformer_lm",
            config={"preset": "tiny", "seq_len": 64, "attention": "ulysses",
                    "n_heads": 8, "n_kv_heads": 8},
        ),
        data=V1DataSpec(
            name="synthetic_text",
            batch_size=8,
            config={"seq_len": 64, "vocab_size": 4096},
        ),
        optimizer=V1OptimizerSpec(name="adamw", learning_rate=1e-3),
        train=V1TrainSpec(steps=3, log_every=3, precision="float32"),
    )
    result = Trainer(program, mesh_axes={"context": 2, "data": 4}).run()
    assert result.history[-1]["loss"] == result.history[-1]["loss"]


def test_auto_backend_resolution(monkeypatch):
    """`auto` picks the flash kernel on TPU whenever the sequence dim stays
    whole per device (single chip, or DP/FSDP/TP meshes via the shard_map
    dispatch); ring when the mesh shards the sequence; einsum for short or
    block-misaligned shapes and off-mesh multi-device tracing."""
    import jax

    from polyaxon_tpu.ops.attention import resolve_auto_backend

    # off-TPU (this suite's CPU slice): always the einsum
    assert resolve_auto_backend(4096, 512) == "xla"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    set_current_mesh(None)
    assert resolve_auto_backend(1024, 512) == "xla"  # short seq
    assert resolve_auto_backend(2056, 512) == "xla"  # no block divides 8 x 257
    assert resolve_auto_backend(2048, 192) == "xla"  # an explicit kv block must
    # 2,496 = 64 x 39 runs since the kernels choose: q and kv blocks of 416
    assert resolve_auto_backend(2496) == ("flash" if len(jax.devices()) == 1 else "xla")
    assert resolve_auto_backend(4096, 512, head_dim=80) == "xla"  # odd D
    assert resolve_auto_backend(4096, 512, head_dim=512) == "xla"  # huge D
    # no mesh bound: only a lone device can run the unpartitioned kernel
    expect = "flash" if len(jax.devices()) == 1 else "xla"
    assert resolve_auto_backend(4096, 512) == expect

    try:
        # seq whole per device -> flash via the shard_map dispatch
        set_current_mesh(build_mesh({"data": 2, "fsdp": 2, "model": 2}))
        assert resolve_auto_backend(4096, 512) == "flash"
        # seq sharded over context -> ring
        set_current_mesh(build_mesh({"data": 2, "context": 4}))
        assert resolve_auto_backend(4096, 512) == "ring"
    finally:
        set_current_mesh(None)

    # inside a shard_map body the per-device view is single-device
    from polyaxon_tpu.parallel.sharding import suspend_constraints

    with suspend_constraints():
        assert resolve_auto_backend(4096, 512) == "flash"


@pytest.mark.parametrize("axes", [{"data": 2, "fsdp": 2, "model": 2},
                                  {"fsdp": 4, "model": 2}])
def test_flash_sharded_matches_xla(axes):
    """backend=flash on a live multi-device mesh == the einsum reference:
    the shard_map dispatch partitions batch over data/fsdp and heads over
    model while keeping the sequence whole per device."""
    mesh = build_mesh(axes)
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(B=4, S=64, H=4, D=32)
        ref = dot_product_attention(q, k, v, causal=True, backend="xla")
        out = dot_product_attention(q, k, v, causal=True, backend="flash")
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


@pytest.mark.slow
def test_flash_sharded_backward_matches_xla():
    mesh = build_mesh({"data": 2, "fsdp": 2, "model": 2})
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(B=4, S=64, H=4, D=32)
        g1 = jax.grad(
            lambda q: dot_product_attention(
                q, k, v, causal=True, backend="flash"
            ).sum()
        )(q)
        g2 = jax.grad(
            lambda q: dot_product_attention(
                q, k, v, causal=True, backend="xla"
            ).sum()
        )(q)
        np.testing.assert_allclose(g1, g2, atol=5e-5, rtol=5e-5)
    finally:
        set_current_mesh(None)


def test_flash_sharded_degrades_indivisible_dims():
    """Odd batch/head counts degrade those axes to replication instead of
    erroring — correctness over parallelism."""
    mesh = build_mesh({"data": 2, "model": 4})
    set_current_mesh(mesh)
    try:
        q, k, v = _qkv(B=2, S=64, H=3, D=32)  # H=3 % model=4 fails
        ref = dot_product_attention(q, k, v, causal=True, backend="xla")
        out = dot_product_attention(q, k, v, causal=True, backend="flash")
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


# ----------------------------------------------------------- GQA native
@pytest.mark.parametrize(
    "causal",
    [pytest.param(True, marks=pytest.mark.slow),
     pytest.param(False, marks=pytest.mark.slow)],
)
def test_flash_gqa_native_matches_expanded(causal):
    """Grouped-query flash: kv stays [B,S,KV,D] (no repeated K/V in HBM);
    output and ALL grads match the expand-then-attend reference."""
    B, S, H, KV, D = 2, 64, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)

    def ref(q, k, v):
        ke = jnp.repeat(k, H // KV, axis=2)
        ve = jnp.repeat(v, H // KV, axis=2)
        return dot_product_attention(q, ke, ve, causal=causal, backend="xla")

    out = flash_attention(q, k, v, causal=causal, block_q=32, block_kv=32)
    np.testing.assert_allclose(out, ref(q, k, v), atol=2e-5, rtol=2e-5)
    g1 = jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=32, block_kv=32
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(lambda q, k, v: ref(q, k, v).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)


def test_flash_sharded_gqa_on_mesh():
    """backend=flash with grouped kv on a live TP mesh: kv heads shard
    over `model` when they divide, and the result matches the expanded
    einsum reference."""
    mesh = build_mesh({"data": 4, "model": 2})
    set_current_mesh(mesh)
    try:
        B, S, H, KV, D = 4, 64, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        ref = dot_product_attention(
            q,
            jnp.repeat(k, H // KV, axis=2),
            jnp.repeat(v, H // KV, axis=2),
            causal=True,
            backend="xla",
        )
        out = dot_product_attention(q, k, v, causal=True, backend="flash")
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


def test_flash_sharded_mqa_expands_to_keep_tp():
    """KV smaller than the model axis (MQA-ish): kv expands so head TP is
    kept rather than replicating every query head per device."""
    mesh = build_mesh({"data": 2, "model": 4})
    set_current_mesh(mesh)
    try:
        B, S, H, KV, D = 2, 64, 8, 1, 16
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        ref = dot_product_attention(
            q,
            jnp.repeat(k, H, axis=2),
            jnp.repeat(v, H, axis=2),
            causal=True,
            backend="xla",
        )
        out = dot_product_attention(q, k, v, causal=True, backend="flash")
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


def test_attention_rejects_indivisible_gqa_heads():
    q, k, v = _qkv(H=4)
    k5 = jnp.concatenate([k, k[:, :, :1] * 0 + 1.0], axis=2)[:, :, :3]
    with pytest.raises(ValueError, match="divisible"):
        dot_product_attention(q[:, :, :4], k5, k5, causal=True, backend="xla")


def test_ulysses_gqa_grouped_matches_expanded():
    """GQA ulysses: kv scatter at true kv-head width == the expanded
    reference (4x less all-to-all traffic at llama ratios)."""
    from polyaxon_tpu.parallel.ulysses import ulysses_attention

    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        B, S, H, KV, D = 2, 64, 8, 4, 16
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        ref = dot_product_attention(
            q,
            jnp.repeat(k, H // KV, axis=2),
            jnp.repeat(v, H // KV, axis=2),
            causal=True,
            backend="xla",
        )
        out = ulysses_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


def test_ulysses_gqa_expands_when_kv_indivisible():
    """KV heads that don't divide the context degree expand internally —
    correct result, not an error."""
    from polyaxon_tpu.parallel.ulysses import ulysses_attention

    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        B, S, H, KV, D = 2, 64, 8, 2, 16  # KV=2 % context=4 != 0
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        ref = dot_product_attention(
            q,
            jnp.repeat(k, H // KV, axis=2),
            jnp.repeat(v, H // KV, axis=2),
            causal=True,
            backend="xla",
        )
        out = ulysses_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


@pytest.mark.parametrize("kv", [4, 2])
def test_ulysses_gqa_with_model_axis(kv):
    """Grouped kv under TP+context: model-sharded heads keep their group
    alignment through the all-to-all (kv=4 rides grouped; kv=2 expands
    because local kv 2/model 2 = 1 % context 2 != 0)."""
    from polyaxon_tpu.parallel.ulysses import ulysses_attention

    mesh = build_mesh({"data": 2, "context": 2, "model": 2})
    set_current_mesh(mesh)
    try:
        B, S, H, D = 2, 64, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, kv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, kv, D), jnp.float32)
        ref = dot_product_attention(
            q,
            jnp.repeat(k, H // kv, axis=2),
            jnp.repeat(v, H // kv, axis=2),
            causal=True,
            backend="xla",
        )
        out = ulysses_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


@pytest.mark.parametrize(
    "kv",
    [pytest.param(2, marks=pytest.mark.slow),
     pytest.param(4, marks=pytest.mark.slow)],
)
def test_ring_gqa_grouped_matches_expanded(kv):
    """GQA ring: K/V rotate the ring at true kv-head width; result matches
    the expanded reference."""
    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        B, S, H, D = 2, 64, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, kv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, kv, D), jnp.float32)
        ref = dot_product_attention(
            q,
            jnp.repeat(k, H // kv, axis=2),
            jnp.repeat(v, H // kv, axis=2),
            causal=True,
            backend="xla",
        )
        out = ring_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


@pytest.mark.slow
def test_ring_gqa_backward_matches_expanded():
    mesh = build_mesh({"data": 2, "context": 4})
    set_current_mesh(mesh)
    try:
        B, S, H, KV, D = 2, 64, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(10), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        g1 = jax.grad(
            lambda q, k, v: ring_attention(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g2 = jax.grad(
            lambda q, k, v: dot_product_attention(
                q,
                jnp.repeat(k, H // KV, axis=2),
                jnp.repeat(v, H // KV, axis=2),
                causal=True,
                backend="xla",
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)
    finally:
        set_current_mesh(None)


def test_ring_gqa_grouped_with_model_axis():
    """The riskiest path: grouped KV stays unexpanded while a live model
    axis shards heads (KV % model == 0) — per-shard group alignment must
    survive the head split AND the ring rotation."""
    mesh = build_mesh({"data": 2, "context": 2, "model": 2})
    set_current_mesh(mesh)
    try:
        B, S, H, KV, D = 2, 64, 8, 2, 16  # KV=2 % model=2 == 0: grouped
        ks = jax.random.split(jax.random.PRNGKey(12), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        ref = dot_product_attention(
            q,
            jnp.repeat(k, H // KV, axis=2),
            jnp.repeat(v, H // KV, axis=2),
            causal=True,
            backend="xla",
        )
        out = ring_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)


def test_ring_gqa_with_model_axis_expands_when_needed():
    """TP+context with KV % model != 0 forces the internal expansion —
    correct result either way."""
    mesh = build_mesh({"data": 2, "context": 2, "model": 2})
    set_current_mesh(mesh)
    try:
        B, S, H, KV, D = 2, 64, 8, 1, 16  # KV=1 % model=2 != 0
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        ref = dot_product_attention(
            q,
            jnp.repeat(k, H, axis=2),
            jnp.repeat(v, H, axis=2),
            causal=True,
            backend="xla",
        )
        out = ring_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    finally:
        set_current_mesh(None)
