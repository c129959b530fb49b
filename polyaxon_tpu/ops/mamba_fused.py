"""The elementwise interior of a Mamba-2 mixer as two fused ops, each a pair
of Pallas TPU kernels (forward, backward) under a `jax.custom_vjp`:

    conv_silu(x, kernel, bias)        silu(causal_conv1d(x, kernel, bias))
    gated_rmsnorm(y, z, scale, eps)   RMSNorm(y * silu(z)) * scale

Every kernel reads its operands from HBM once, computes in float32 in VMEM
and rounds once, to the activations' type, as it writes. The backward keeps
what the step holds anyway (`x`; `y`, `z` and the rows' `rsqrt`, float32) and
builds the rest again: no float32 `[rows, width]` temporary crosses HBM.
The `jax.numpy` forms of `ops/ssd.py` (`causal_conv1d`, `gated_rmsnorm`) are
what the kernels are checked against, and what runs for a shape the kernels
refuse (`conv_plan`, `gate_plan`: a width that is no multiple of 128, a
sequence that is no multiple of the type's sublane tile, another type than
float32 or bfloat16, a live mesh of several devices: the kernels have no
partitioning rule and no `shard_map` around them yet). Off the TPU the
kernels run interpreted.

**Columns in place of a split.** `columns=(first, width)` reads the op's
operand from that column range of a wider array (`in_proj`'s output holds
`z | xBC | dt` side by side): where the range is aligned to the column block
the kernels' index maps offset the column and no slice is copied; the
cotangent comes back padded to the wide array's width, as a split's would.

**The walks.** The conv kernels tile `[rows, columns]` and take a tile in
chunks of `_CHUNK` rows inside a loop, so that a chunk's float32 values stay
in registers. A tap `k` reads `x[t - (K-1) + k]`: a chunk is laid under the
last 8 rows of what precedes it (the previous chunk; for a tile's first
chunk a halo block of the rows before the tile; zeros at position 0) and
rotated along the rows. The backward walks a tile's chunks from the last to
the first, because `dx[t]` reads `dpre[t .. t + K-1]`: it carries the first
rows of the chunk after, and starts from the halo blocks AFTER the tile
(zeros past the sequence's end). Blocks never span two batch rows. The
gate+norm kernels take whole rows of the inner width, `_GATE_SUB` at a time.

**Frozen parameters.** The cotangents of `kernel`, `bias` and `scale` are
plain XLA operations of the backward rule, under the scope
`mamba_frozen_cotangents`, not outputs of the kernel that writes `dx`: a
step that differentiates adapters only never asks for them and the compiler
drops them whole.

Each `pallas_call` has a `name=` (`mamba_conv_silu_fwd`, `_bwd`,
`mamba_gate_norm_fwd`, `_bwd`): the kernels' kinds in a device trace, which
the benchmark's `mamba_fused_roofline.train` finds them by.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _flash  # `_interpret`: one switch for every kernel
from .ssd import causal_conv1d
from .ssd import gated_rmsnorm as _gated_rmsnorm_xla

_VMEM_LIMIT = 32 * 2**20
_VMEM_BUDGET = _VMEM_LIMIT // 2  # what the tiles are sized to: the rest is the body's
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)
_CONV_ROWS = 2048  # a conv tile's rows, at most
_CONV_COLS = (512, 256, 128)  # column blocks, widest first
_CHUNK = 64  # rows of a tile taken at a time
_GATE_ROWS = 256  # a gate+norm tile's rows, at most
_GATE_SUB = 16  # rows of it taken at a time
_CARRY = 8  # float32 rows carried between chunks: one sublane tile; K - 1 <= 8
FROZEN_SCOPE = "mamba_frozen_cotangents"


# ------------------------------------------------------------------ the plans
def _sublanes(dtype) -> int | None:
    """Rows of one tile of `dtype` in VMEM; None for a type the kernels do
    not take."""
    return {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}.get(jnp.dtype(dtype))


def _refused(dtype, width: int) -> str | None:
    """Why no kernel runs whatever the sequence is, or None."""
    from ..parallel.ring import current_mesh
    from ..parallel.sharding import constraints_suspended

    mesh = current_mesh()
    if mesh is not None and mesh.size > 1 and not constraints_suspended():
        return f"a mesh of {mesh.size} devices: the kernels have no partitioning rule"
    if _sublanes(dtype) is None:
        return f"activations of {jnp.dtype(dtype).name}"
    if width % 128:
        return f"width {width} is no multiple of 128"
    return None


def _largest_divisor(n: int, unit: int, top: int) -> int | None:
    """The largest multiple of `unit` that divides `n` and is at most `top`."""
    return next((d for d in range(min(top, n) // unit * unit, 0, -unit) if n % d == 0), None)


def conv_plan(seq: int, width: int, dtype, first: int = 0, taps: int = 4) -> dict:
    """How `conv_silu` runs `[.., seq, width]` of `dtype` read from column
    `first` of its operand: `{"path": "pallas", "block_rows", "block_cols",
    "chunk_rows", "in_place"}` (`in_place`: the column range is read where it
    lies, no slice is copied) or `{"path": "xla", "why"}`."""
    why, sub = _refused(dtype, width), _sublanes(dtype)
    if why:
        return {"path": "xla", "why": why}
    if taps - 1 > _CARRY:
        return {"path": "xla", "why": f"{taps} taps reach over {_CARRY} rows"}
    rows = _largest_divisor(seq, sub, _CONV_ROWS)
    if rows is None:
        return {"path": "xla", "why": f"sequence {seq} is no multiple of {sub}"}
    cols = next(c for c in _CONV_COLS if width % c == 0)
    return {
        "path": "pallas", "block_rows": rows, "block_cols": cols,
        "chunk_rows": _largest_divisor(rows, sub, _CHUNK), "in_place": first % cols == 0,
    }


def gate_plan(seq: int, width: int, dtype, first: int = 0) -> dict:
    """As `conv_plan`, for `gated_rmsnorm` over rows of `width` whose gate is
    read from column `first` of its operand. A tile is whole rows; its rows
    are as many as keep the backward's five blocks, double-buffered, inside
    the VMEM budget."""
    why = _refused(dtype, width)
    if why:
        return {"path": "xla", "why": why}
    fit = _VMEM_BUDGET // (2 * 5 * width * jnp.dtype(dtype).itemsize)
    rows = _largest_divisor(seq, _GATE_SUB, min(_GATE_ROWS, fit))
    if rows is None:
        return {"path": "xla", "why": f"sequence {seq} is no multiple of {_GATE_SUB}, "
                                      f"or a row of {width} is too wide for VMEM"}
    return {"path": "pallas", "block_rows": rows, "chunk_rows": _GATE_SUB,
            "in_place": first % width == 0}


def _one_lowering(*static):
    """`jax.jit` with `static` and `interpret` static: the layers of a model
    (and a checkpoint's second forward) that call a kernel at one shape share
    one trace of its body and one lowering to Mosaic."""
    def wrap(fn):
        jitted = jax.jit(fn, static_argnames=(*static, "interpret"))

        @functools.wraps(fn)
        def call(*args, **kwargs):
            return jitted(*args, **kwargs, interpret=_flash._interpret())

        return call

    return wrap


def _silu_grad(x):
    """(sigmoid(x), d silu / dx)."""
    s = jax.nn.sigmoid(x)
    return s, s * (1.0 + x * (1.0 - s))


# ------------------------------------------------------------------ conv + silu
def _pre_activation(before, x, w_ref, b_ref):
    """bias + sum_k w[k] x[t - (K-1) + k] for the rows of `x` [R, C], float32,
    `before` [8, C] being the rows that precede them."""
    taps = w_ref.shape[0]
    under = jnp.concatenate([before, x], axis=0)
    pre = b_ref[...] + w_ref[taps - 1 : taps, :] * x
    for k in range(taps - 1):
        pre += w_ref[k : k + 1, :] * pltpu.roll(under, taps - 1 - k, 0)[_CARRY:]
    return pre


def _conv_fwd_kernel(x_ref, before_ref, w_ref, b_ref, o_ref, *, chunk):
    f32 = jnp.float32
    halo = before_ref.shape[1]
    before = before_ref[0].astype(f32)[halo - _CARRY :]
    before = jnp.where(pl.program_id(2) == 0, 0.0, before)  # zeros before position 0

    def rows_of(j, before):
        rows = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        x = x_ref[0, rows, :].astype(f32)
        pre = _pre_activation(before, x, w_ref, b_ref)
        o_ref[0, rows, :] = (pre * jax.nn.sigmoid(pre)).astype(o_ref.dtype)
        return x[chunk - _CARRY :]

    jax.lax.fori_loop(0, x_ref.shape[1] // chunk, rows_of, before)


def _conv_bwd_kernel(
    x_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref, b_ref, dx_ref, *, chunk
):
    f32 = jnp.float32
    taps, halo, rows_tile = w_ref.shape[0], before_ref.shape[1], x_ref.shape[1]
    n = rows_tile // chunk

    def dpre_of(before, x, g):
        pre = _pre_activation(before, x, w_ref, b_ref)
        return g * _silu_grad(pre)[1]

    # dpre of the first rows after the tile: zeros past the sequence's end
    last = x_ref[0, rows_tile - halo :, :].astype(f32)[halo - _CARRY :]
    after = dpre_of(
        last, after_ref[0].astype(f32)[:_CARRY], g_after_ref[0].astype(f32)[:_CARRY]
    )
    after = jnp.where(pl.program_id(2) == pl.num_programs(2) - 1, 0.0, after)
    before_tile = before_ref[0].astype(f32)[halo - _CARRY :]
    before_tile = jnp.where(pl.program_id(2) == 0, 0.0, before_tile)

    def rows_of(i, after):
        j = n - 1 - i  # the last chunk first: dx[t] reads dpre[t .. t + K-1]
        rows = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        x = x_ref[0, rows, :].astype(f32)
        prev = pl.ds(pl.multiple_of(jnp.maximum(j * chunk - halo, 0), halo), halo)
        before = x_ref[0, prev, :].astype(f32)[halo - _CARRY :]
        before = jnp.where(j == 0, before_tile, before)
        dpre = dpre_of(before, x, g_ref[0, rows, :].astype(f32))
        over = jnp.concatenate([dpre, after], axis=0)
        dx = w_ref[taps - 1 : taps, :] * dpre
        for k in range(taps - 1):
            ahead = pltpu.roll(over, chunk + _CARRY - (taps - 1 - k), 0)[:chunk]
            dx += w_ref[k : k + 1, :] * ahead
        dx_ref[0, rows, :] = dx.astype(dx_ref.dtype)
        return dpre[:_CARRY]

    jax.lax.fori_loop(0, n, rows_of, after)


def _conv_specs(seq, width, first, dtype, rows, cols):
    """Grid and block specs of the conv kernels over `[B, seq, .]`: a tile of
    the operand read at column `first`, its halo blocks before and after, a
    tile and a halo after of a `[B, seq, width]` array, the taps and bias."""
    halo = _sublanes(dtype)
    per_tile, c0, n_halo = rows // halo, first // cols, seq // halo
    tile = lambda shift: pl.BlockSpec((1, rows, cols), lambda b, c, i: (b, i, c + shift))  # noqa: E731
    before = lambda shift: pl.BlockSpec(  # noqa: E731
        (1, halo, cols), lambda b, c, i: (b, jnp.maximum(i * per_tile - 1, 0), c + shift))
    after = lambda shift: pl.BlockSpec(  # noqa: E731
        (1, halo, cols), lambda b, c, i: (b, jnp.minimum((i + 1) * per_tile, n_halo - 1), c + shift))
    taps = lambda k: pl.BlockSpec((k, cols), lambda b, c, i: (0, c))  # noqa: E731
    grid = lambda batch: (batch, width // cols, seq // rows)  # noqa: E731
    return grid, tile, before, after, taps, c0


@_one_lowering("first", "width", "rows", "cols", "chunk")
def _conv_fwd(x, kernel, bias, first, width, rows, cols, chunk, *, interpret):
    batch, seq, _ = x.shape
    grid, tile, before, _, taps, c0 = _conv_specs(seq, width, first, x.dtype, rows, cols)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, chunk=chunk),
        grid=grid(batch),
        in_specs=[tile(c0), before(c0), taps(kernel.shape[0]), taps(1)],
        out_specs=tile(0),
        out_shape=jax.ShapeDtypeStruct((batch, seq, width), x.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="mamba_conv_silu_fwd",
    )(x, x, kernel, bias)


@_one_lowering("first", "width", "rows", "cols", "chunk")
def _conv_bwd(x, g, kernel, bias, first, width, rows, cols, chunk, *, interpret):
    batch, seq, _ = x.shape
    grid, tile, before, after, taps, c0 = _conv_specs(seq, width, first, x.dtype, rows, cols)
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, chunk=chunk),
        grid=grid(batch),
        in_specs=[tile(c0), before(c0), after(c0), tile(0), after(0),
                  taps(kernel.shape[0]), taps(1)],
        out_specs=tile(0),
        out_shape=jax.ShapeDtypeStruct((batch, seq, width), x.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="mamba_conv_silu_bwd",
    )(x, x, x, g, g, kernel, bias)


def _f32_rows(kernel, bias):
    f32 = jnp.float32
    return kernel.astype(f32), bias.astype(f32).reshape(1, -1)


def _conv_silu_fwd(x, kernel, bias, first, width, tiles):
    out = _conv_fwd(x, *_f32_rows(kernel, bias), first, width, *tiles)
    return out, (x, kernel, bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_silu(x, kernel, bias, first, width, tiles):
    return _conv_silu_fwd(x, kernel, bias, first, width, tiles)[0]


def _conv_silu_bwd(first, width, tiles, res, g):
    x, kernel, bias = res
    dx = _conv_bwd(x, g, *_f32_rows(kernel, bias), first, width, *tiles)
    dx = jnp.pad(dx, ((0, 0), (0, 0), (first, x.shape[-1] - first - width)))
    with jax.named_scope(FROZEN_SCOPE):
        f32 = jnp.float32
        xs = x[..., first : first + width].astype(f32)
        k, seq = kernel.shape[0], x.shape[1]
        pre = causal_conv1d(xs, kernel.astype(f32), bias.astype(f32))
        dpre = g.astype(f32) * _silu_grad(pre)[1]
        padded = jnp.pad(xs, ((0, 0), (k - 1, 0), (0, 0)))
        dkernel = jnp.stack(
            [jnp.sum(dpre * padded[:, i : i + seq], axis=(0, 1)) for i in range(k)]
        )
        dbias = jnp.sum(dpre, axis=(0, 1))
    return dx, dkernel.astype(kernel.dtype), dbias.astype(bias.dtype)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def _columns(x, columns):
    first, width = columns or (0, x.shape[-1])
    if first < 0 or width < 1 or first + width > x.shape[-1]:
        raise ValueError(f"columns {columns} lie outside a width of {x.shape[-1]}")
    return first, width


def _narrow(x, first, width):
    return x if (first, width) == (0, x.shape[-1]) else x[..., first : first + width]


def conv_silu(x, kernel, bias, *, columns=None):
    """silu(causal_conv1d(x, kernel, bias)): x [B, S, C] (with `columns =
    (first, C)` the operand is that column range of a wider x), kernel
    [K, C], bias [C] -> [B, S, C] in x's type. Sums in float32."""
    first, width = _columns(x, columns)
    plan = conv_plan(x.shape[1], width, x.dtype, first, kernel.shape[0])
    with jax.named_scope("conv_silu"):
        if plan["path"] == "xla":
            return jax.nn.silu(causal_conv1d(_narrow(x, first, width), kernel, bias))
        if not plan["in_place"]:
            x, first = _narrow(x, first, width), 0
        tiles = (plan["block_rows"], plan["block_cols"], plan["chunk_rows"])
        return _conv_silu(x, kernel, bias, first, width, tiles)


# ------------------------------------------------------------------ gate + norm
def _gate_fwd_kernel(y_ref, z_ref, w_ref, o_ref, r_ref, *, eps, sub):
    f32 = jnp.float32

    def rows_of(j, _):
        rows = pl.ds(pl.multiple_of(j * sub, sub), sub)
        y, z = y_ref[0, rows, :].astype(f32), z_ref[0, rows, :].astype(f32)
        g = y * (z * jax.nn.sigmoid(z))
        r = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        o_ref[0, rows, :] = (g * r * w_ref[...]).astype(o_ref.dtype)
        r_ref[0, rows, :] = r
        return _

    jax.lax.fori_loop(0, y_ref.shape[1] // sub, rows_of, None)


def _gate_bwd_kernel(y_ref, z_ref, g_ref, r_ref, w_ref, dy_ref, dz_ref, *, sub):
    f32 = jnp.float32

    def rows_of(j, _):
        rows = pl.ds(pl.multiple_of(j * sub, sub), sub)
        y, z = y_ref[0, rows, :].astype(f32), z_ref[0, rows, :].astype(f32)
        s, dsilu = _silu_grad(z)
        silu = z * s
        r = r_ref[0, rows, :]
        normed = y * silu * r
        t = g_ref[0, rows, :].astype(f32) * w_ref[...]
        dg = r * (t - normed * jnp.mean(t * normed, axis=-1, keepdims=True))
        dy_ref[0, rows, :] = (dg * silu).astype(dy_ref.dtype)
        dz_ref[0, rows, :] = (dg * y * dsilu).astype(dz_ref.dtype)
        return _

    jax.lax.fori_loop(0, y_ref.shape[1] // sub, rows_of, None)


def _gate_specs(width, first, rows):
    at = lambda shift: pl.BlockSpec((1, rows, width), lambda b, i: (b, i, shift))  # noqa: E731
    stat = pl.BlockSpec((1, rows, 1), lambda b, i: (b, i, 0))
    scale = pl.BlockSpec((1, width), lambda b, i: (0, 0))
    return at(0), at(first // width), stat, scale


@_one_lowering("first", "eps", "rows", "sub")
def _gate_fwd(y, z, scale, first, eps, rows, sub, *, interpret):
    batch, seq, width = y.shape
    row, gate, stat, w = _gate_specs(width, first, rows)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, eps=eps, sub=sub),
        grid=(batch, seq // rows),
        in_specs=[row, gate, w],
        out_specs=[row, stat],
        out_shape=[
            jax.ShapeDtypeStruct(y.shape, y.dtype),
            jax.ShapeDtypeStruct((batch, seq, 1), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="mamba_gate_norm_fwd",
    )(y, z, scale)


@_one_lowering("first", "rows", "sub")
def _gate_bwd(y, z, g, r, scale, first, rows, sub, *, interpret):
    batch, seq, width = y.shape
    row, gate, stat, w = _gate_specs(width, first, rows)
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, sub=sub),
        grid=(batch, seq // rows),
        in_specs=[row, gate, row, stat, w],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype)] * 2,
        compiler_params=_PARAMS,
        interpret=interpret,
        name="mamba_gate_norm_bwd",
    )(y, z, g, r, scale)


def _f32_row(scale):
    return scale.astype(jnp.float32).reshape(1, -1)


def _gate_norm_fwd(y, z, scale, first, eps, tiles):
    out, r = _gate_fwd(y, z, _f32_row(scale), first, eps, *tiles)
    return out, (y, z, scale, r)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gate_norm(y, z, scale, first, eps, tiles):
    return _gate_norm_fwd(y, z, scale, first, eps, tiles)[0]


def _gate_norm_bwd(first, eps, tiles, res, g):
    y, z, scale, r = res
    width = y.shape[-1]
    dy, dz = _gate_bwd(y, z, g, r, _f32_row(scale), first, *tiles)
    dz = jnp.pad(dz, ((0, 0), (0, 0), (first, z.shape[-1] - first - width)))
    with jax.named_scope(FROZEN_SCOPE):
        f32 = jnp.float32
        z32 = z[..., first : first + width].astype(f32)
        normed = y.astype(f32) * (z32 * jax.nn.sigmoid(z32)) * r
        dscale = jnp.sum(g.astype(f32) * normed, axis=(0, 1))
    return dy, dz, dscale.astype(scale.dtype)


_gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def gated_rmsnorm(y, z, scale, eps: float = 1e-5, *, z_columns=None):
    """RMSNorm(y * silu(z)) * scale over the last axis, the gate before the
    norm: y [B, S, N]; z [B, S, N] (with `z_columns = (first, N)` that column
    range of a wider z); scale [N] -> [B, S, N] in y's type. The product, the
    mean of squares and the scaling in float32."""
    first, width = _columns(z, z_columns)
    if width != y.shape[-1]:
        raise ValueError(f"a gate of width {width} for rows of {y.shape[-1]}")
    plan = gate_plan(y.shape[1], width, y.dtype, first)
    with jax.named_scope("gate_norm"):
        if plan["path"] == "xla" or z.dtype != y.dtype:
            return _gated_rmsnorm_xla(y, _narrow(z, first, width), scale, eps)
        if not plan["in_place"]:
            z, first = _narrow(z, first, width), 0
        tiles = (plan["block_rows"], plan["chunk_rows"])
        return _gate_norm(y, z, scale, first, float(eps), tiles)
