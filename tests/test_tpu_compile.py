"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's own compiler runs here and refuses what the chip would
refuse (tiling, VMEM, HBM), which interpret mode on the CPU never shows.
Nothing executes, so these say nothing about results or speed.

The shapes are those of `chip_smoke.py` (Llama-3.2-1B widths: 32 query / 8
kv heads of 64, batch 4, seq 2048) plus head width 128, and the benchmark's
three call shapes with the blocks the kernels choose for them, the two
fused ops of the Granite cell's Mamba mixer at its widths, the Ling
cell's delta-rule scan, and the Brumby cell's power-retention scan.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from polyaxon_tpu.ops import flash_attention as fa
from polyaxon_tpu.parallel import ring


@pytest.fixture(scope="module")
def chip():
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def for_the_chip(monkeypatch):
    """Lower the Pallas kernels for Mosaic although the backend is the CPU,
    and keep these compiles out of the persistent cache: an entry written
    for a described chip cannot be read back without one."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    # an earlier test's trainer or server may have left its CPU mesh bound;
    # model code would then constrain shardings onto CPU devices
    mesh_was = ring.current_mesh()
    ring.set_current_mesh(None)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    ring.set_current_mesh(mesh_was)


def _qkv(chip, heads, kv_heads, head_dim, batch=4, seq=2048):
    def sds(h):
        return jax.ShapeDtypeStruct(
            (batch, seq, h, head_dim), jnp.bfloat16, sharding=chip
        )

    return sds(heads), sds(kv_heads), sds(kv_heads)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize(
    "heads,kv_heads,head_dim,block_kv",
    [(32, 8, 64, 512), (32, 8, 64, 128), (16, 4, 128, 512)],
    ids=["D64-kv512", "D64-kv128", "D128-kv512"],
)
def test_flash_kernel_compiles_for_v5e(chip, heads, kv_heads, head_dim, block_kv, backward):
    def attend(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, block_q=128, block_kv=block_kv
        )

    fn = attend
    if backward:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
            )(q, k, v)

    compiled = jax.jit(fn).lower(*_qkv(chip, heads, kv_heads, head_dim)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "heads,head_dim,block_kv,window,seq",
    [(72, 128, 512, 512, 4096), (72, 128, 512, 500, 4096), (32, 64, 128, 200, 2048)],
    ids=["the-cell-72x128-w512", "unaligned-w500", "D64-kv128-w200"],
)
def test_windowed_flash_kernels_compile_for_v5e(chip, heads, head_dim, block_kv, window, seq):
    """Forward and backward with a window: the shorter grids, the index maps
    that start at the window's first block, and the three kernels' own
    names, as a sliding layer of the Laguna cell calls them (2 rows x 4,096,
    72 query heads on 8 kv heads of 128, window 512)."""
    def grads(q, k, v):
        return jax.grad(
            lambda *a: fa.flash_attention(
                *a, causal=True, block_q=128, block_kv=block_kv, window=window
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    text = jax.jit(grads).lower(
        *_qkv(chip, heads, 8, head_dim, batch=2, seq=seq)
    ).compile().as_text()
    for name in ("flash_window_fwd", "flash_window_dq", "flash_window_dkv"):
        assert name in text
    assert "flash_attention_" not in text


# (rows, seq, query heads, kv heads, head width, window): the call shapes of
# the benchmark's two cells and of chip_smoke.py
CALLS = {
    "internlm2-2x2048-16q8kv": (2, 2048, 16, 8, 128, None),
    "laguna-full-2x4096-48q8kv": (2, 4096, 48, 8, 128, None),
    "laguna-sliding-2x4096-72q8kv-w512": (2, 4096, 72, 8, 128, 512),
    "chip-smoke-4x2048-32q8kv-D64": (4, 2048, 32, 8, 64, None),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("call", sorted(CALLS))
def test_the_chosen_tiles_compile_for_v5e(chip, call, backward):
    """No block is given: each kernel runs the blocks `choose_blocks` gives
    it for the call's shape, and the chip's compiler takes them (VMEM, the
    tiling of every block, the statistics' two layouts). The kernels keep
    the names the benchmark's roofline metrics find them by."""
    rows, seq, heads, kv_heads, head_dim, window = CALLS[call]
    chosen = fa.tile_report(seq, head_dim, heads // kv_heads, window)
    assert {(c["block_q"], c["block_kv"]) for c in chosen} != {(128, 512)}

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window)

    fn = attend
    if backward:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
            )(q, k, v)

    text = jax.jit(fn).lower(
        *_qkv(chip, heads, kv_heads, head_dim, batch=rows, seq=seq)
    ).compile().as_text()
    stem, other = ("flash_window_", "flash_attention_") if window else (
        "flash_attention_", "flash_window_")
    for kernel in ("fwd", "dq", "dkv") if backward else ("fwd",):
        assert stem + kernel in text
    assert other not in text


# the Granite cell's mixer: 1 row x 8,192, `in_proj`'s output of 16,768
# columns holding `z` (8,192) | `xBC` (8,448) | `dt` (128); and the same
# chains in float32 at a width that gets column blocks of 512 and 128
MIXERS = {
    "granite-bf16-1x8192": (1, 8192, 8192, 8448, jnp.bfloat16),
    "float32-2x2048": (2, 2048, 1024, 1152, jnp.float32),
}


@pytest.mark.parametrize("op", ["conv_silu", "gated_rmsnorm"])
@pytest.mark.parametrize("mixer", sorted(MIXERS))
def test_fused_mamba_kernels_compile_for_v5e(chip, mixer, op):
    """Forward and backward of each fused chain with the tiles its plan
    chooses, read in place from `in_proj`'s output: the chip's compiler takes
    the blocks, the halo blocks of one sublane tile, the rotation along the
    rows and the chunked walk (interpret mode shows none of that), and the
    four kernels keep the names `mamba_fused_roofline.train` finds them by."""
    from polyaxon_tpu.ops import mamba_fused as mf

    rows, seq, inner, conv, dtype = MIXERS[mixer]
    wide = inner + conv + 128

    def sds(*shape, of=dtype):
        return jax.ShapeDtypeStruct(shape, of, sharding=chip)

    if op == "conv_silu":
        assert mf.conv_plan(seq, conv, dtype, inner)["in_place"]

        def grads(x, kernel, bias):
            return jax.grad(lambda *a: mf.conv_silu(*a, columns=(inner, conv))
                            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(x, kernel, bias)

        args = (sds(rows, seq, wide), sds(4, conv, of=jnp.float32), sds(conv, of=jnp.float32))
        names = ("mamba_conv_silu_fwd", "mamba_conv_silu_bwd")
    else:
        assert mf.gate_plan(seq, inner, dtype, 0)["in_place"]

        def grads(y, z, scale):
            return jax.grad(lambda *a: mf.gated_rmsnorm(*a, 1e-5, z_columns=(0, inner))
                            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(y, z, scale)

        args = (sds(rows, seq, inner), sds(rows, seq, wide), sds(inner, of=jnp.float32))
        names = ("mamba_gate_norm_fwd", "mamba_gate_norm_bwd")
    text = jax.jit(grads).lower(*args).compile().as_text()
    assert names[1] in text and mf.FROZEN_SCOPE in text
    forward = mf.conv_silu if op == "conv_silu" else mf.gated_rmsnorm
    columns = {"columns": (inner, conv)} if op == "conv_silu" else {"z_columns": (0, inner)}
    text = jax.jit(lambda *a: forward(*a, **columns)).lower(*args).compile().as_text()
    assert names[0] in text


def test_kda_scan_kernels_compile_for_v5e(chip):
    """Forward and gradient of the delta-rule scan at the Ling cell's shape
    (1 x 16,384, 32 heads of 128, bf16 with float32 `g` and `beta`): the
    chip's compiler takes the in-place blocks of one head's columns, the
    state scratch, the inverse's substitution and block products, and the
    backward's transposed products; the kernels keep the names
    `kda_scan_roofline.train` finds them by, and nothing of the `jax.numpy`
    form is left under the `kda` scope (its walks, the compiler's triangular
    solver)."""
    from polyaxon_tpu.ops import kda, kda_fused

    rows, seq, heads, width = 1, 16384, 32, 128
    assert kda_fused.scan_plan(rows, seq, 64, heads, width, width, jnp.bfloat16)["path"] == "pallas"

    def sds(*shape, of=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, of, sharding=chip)

    # as `models/kda.py` calls it: q, k, v, g with heads and widths merged
    # (what the convs and `f_proj` hand over), q and k normalised by the kernels
    wide = sds(rows, seq, heads * width)
    args = (wide, wide, wide, sds(rows, seq, heads * width, of=jnp.float32),
            sds(rows, seq, heads, of=jnp.float32))
    scan = functools.partial(kda.kda_scan, chunk=64, unit_scales=(width**-0.5, 1.0))

    def grads(*a):
        return jax.grad(lambda *x: scan(*x).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(*a)

    text = jax.jit(grads).lower(*args).compile().as_text()
    assert "kda_scan_fwd" in text and "kda_scan_bwd" in text
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert not [line for line in text.splitlines() if " while(" in line and "kda" in line]
    # merged operands are read where they lie: no copy or re-layout of a wide array
    assert not [line for line in text.splitlines()
                if (" copy(" in line or " reshape(" in line) and "16384,4096" in line]
    forward = jax.jit(scan).lower(*args).compile().as_text()
    assert "kda_scan_fwd" in forward and "kda_scan_bwd" not in forward


def test_retention_scan_kernels_compile_for_v5e(chip):
    """Forward and gradient of the power-retention scan at the Brumby cell's
    shape (1 x 32,768, 40 query heads over 8 groups of 128, chunk 256, bf16
    with a float32 log-gate): the chip's compiler takes the merged blocks,
    the state scratch and the backward's scratch of a run's states in VMEM;
    the kernels keep the names `retention_scan_roofline.train` finds them
    by, and no wide operand is copied at their border."""
    from polyaxon_tpu.ops import power_retention_fused as prf

    rows, seq, heads, groups, width = 1, 32768, 40, 8, 128
    plan = prf.scan_plan(rows, seq, 256, heads, groups, width, jnp.bfloat16)
    assert plan["path"] == "pallas" and plan["run"] == 8

    def sds(*shape, of=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, of, sharding=chip)

    # as `models/retention.py` calls it: q, k, v with heads and widths merged
    args = (sds(rows, seq, heads * width), sds(rows, seq, groups * width),
            sds(rows, seq, groups * width), sds(rows, seq, heads, of=jnp.float32))
    scan = functools.partial(prf.scan, chunk=256, run=plan["run"])

    def grads(*a):
        return jax.grad(lambda *x: scan(*x)[0].astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))(*a)

    text = jax.jit(grads).lower(*args).compile().as_text()
    assert "retention_scan_fwd" in text and "retention_scan_bwd" in text
    assert not [line for line in text.splitlines()
                if " copy(" in line and ("32768,5120" in line or "32768,1024" in line)]
    forward = jax.jit(scan).lower(*args).compile().as_text()
    assert "retention_scan_fwd" in forward and "retention_scan_bwd" not in forward


def _dense_decode(chip, batch, cache_len=8192, n_layers=1):
    """The dense `generate` program at Llama-3.2-1B widths, the prompt
    filling half of an 8,192-token cache. Depth is cut to one layer: what
    decides the compile is one layer's attention, not how many follow."""
    from polyaxon_tpu.models import build_model
    from polyaxon_tpu.models.generate import generate
    from polyaxon_tpu.runtime.trainer import make_param_init

    bundle = build_model(
        "transformer_lm",
        {"preset": "llama3-1b", "seq_len": cache_len, "n_layers": n_layers},
    )
    abstract, _ = jax.eval_shape(
        make_param_init(bundle, jnp.bfloat16, bundle.example_inputs(1)),
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), abstract
    )
    prompt = jax.ShapeDtypeStruct((batch, cache_len // 2), jnp.int32, sharding=chip)
    rows = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=chip)
    fn = jax.jit(
        lambda p, prompt, lengths, seeds: generate(
            bundle.module, p, prompt, max_new_tokens=256, seed=seeds,
            prompt_lengths=lengths,
        )
    )
    return fn.lower(params, prompt, rows, rows).compile()


@pytest.mark.slow  # 6 s; the refusal below is what tier-1 pins
def test_dense_decode_at_cache_8192_compiles_for_one_row(chip):
    _dense_decode(chip, batch=1)


@pytest.mark.xfail(
    strict=True,
    raises=jax.errors.JaxRuntimeError,
    reason="the one-shot dense prefill scores the whole prompt against the "
    "whole cache: f32[8, 32, 4096, 8192] is 32 GiB on a 16 GiB chip "
    "(RESOURCE_EXHAUSTED; ROADMAP Speed item 3)",
)
def test_dense_decode_at_cache_8192_batch_8(chip):
    _dense_decode(chip, batch=8)
