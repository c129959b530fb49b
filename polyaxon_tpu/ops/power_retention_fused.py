"""The chunked degree-2 power-retention scan of `ops/power_retention.py` as
Pallas TPU kernels under a `jax.custom_vjp`: `retention_scan_fwd` and
`retention_scan_bwd`. The algorithm, the numbers and the types are
`ops/power_retention.py`'s (its docstring has the algebra): the symmetric
square in blocks of `FEATURE_BLOCK` channels, the scale before the square,
`EPS`; gates, running sums, decays, the state `S [D, P]`, the normaliser
`Z [P, P]`, the scores and the readout's sums in float32; the products'
operands in the activations' type, accumulated in float32. What changes is
where the values live: a chunk's features, its `[C, C]` squared, decayed,
causal scores and its decays exist in VMEM only, and a head's state is a
VMEM scratch carried across a sequential grid axis.

**Layouts.** q, k, v and y are read and written merged, `[B, S, H x P]` /
`[B, S, G x P]`: a block `(1, rows, P)` at column block `h` is query head
`h`'s rows, at `h // (H / G)` its key-value group's (P = 128). The running
sums `G` of the log-gate inside a chunk (taken outside the kernels, as the
`jax.numpy` form takes them) are handed over a chunk a row, `[B, H, runs,
run, C]` float32. The state lives as one `[D + P, P]` float32 array: `S`'s
pairs of blocks one after another (`ops/power_retention.features`' order),
then `Z`.

**Features in VMEM.** A chunk's q and k rows are held transposed, `[P, C]`
float32, positions along the lanes. Pair `(I, J)` of phi is then `[256, C]`
of sublane broadcasts and products: row `16 a + b` is `x_{16 I + a} x_{16 J +
b}` (times sqrt(2) off the diagonal), rounded once to the activations' type,
and is taken at once by `[256, C] x [256, P]` products: the read `phi(Q) S`
and the update `phi(K)^T (exp(G_C - G) V)`. The 36 pairs are a loop; no
feature reaches HBM. In the backward a pair's cotangent goes back onto q and
k as float32 sums over the 16 rows of a block (sublanes) and over the 16
blocks.

**The grid** is `(B, H, S / (run x chunk))`, rows and heads parallel, the
runs sequential ("arbitrary"); a grid step walks `run` chunks of one head.
The forward that a gradient will follow also writes the state each run
starts from (`[B, runs, H, D + P, P]` float32); under a checkpoint only the
backward's own forward does (`optimize_remat`), not the first. The smallest
denominator is kept in a `[B, H, 8, 128]` block the run axis does not move.

**The backward** walks the runs from the last to the first. For a run it
copies the saved state in, walks the run's chunks forward once to rebuild the
state every chunk starts from (a VMEM scratch `[run, D + P, P]`), then walks
them in reverse carrying `dS` and `dZ` (a VMEM scratch). The read's cotangent
needs no second read: `rowsum(dy * phi(Q) S)` is summed from the products
`S dy^T` that give phi(Q)'s cotangent. Keys and values are read a group's
columns at a time and their cotangents written a query head's columns at a
time in float32; the group's heads are summed outside.

`scan_plan` lets the shape decide between these kernels and the `jax.numpy`
form; off the TPU the kernels run interpreted (`flash_attention._interpret`,
the one switch, through `mamba_fused._one_lowering`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda_fused import _NN, _NT, _TN, _column, _dot, _iota, _row
from .mamba_fused import _one_lowering, _refused  # `_one_lowering`: interpreted off the TPU
from .power_retention import EPS, FEATURE_BLOCK, feature_width, state_bytes

WIDTH = 128  # the head width the kernels take: 8 blocks of phi's 16, 36 pairs
RUN = 8  # chunks a grid step takes, and a saved state covers, at most
CHUNKS = (128, 256, 512)  # chunks the kernels take: whole lanes of positions
_VMEM_LIMIT = 100 * 2**20
_TOGETHER = 3  # pairs a loop step takes: a layer's scan 15 % faster than at 1 (v5e)
_F32 = jnp.float32
_BLOCKS = WIDTH // FEATURE_BLOCK
_PAIRS = _BLOCKS * (_BLOCKS + 1) // 2
_PAIR = FEATURE_BLOCK * FEATURE_BLOCK  # features of one pair of blocks
_STATE = feature_width(WIDTH) + WIDTH  # rows of S, then of Z


def scan_plan(batch: int, seq: int, chunk: int, heads: int, groups: int, width: int,
              dtype) -> dict:
    """How the power-retention scan runs this shape: `{"path": "pallas",
    "run", "heads_per_step", "saved_state_bytes", "vmem_limit_bytes"}` (`run`
    chunks a grid step walks and a saved state covers; the saved states are
    one layer's, kept from the forward for the backward) or `{"path": "xla",
    "why"}`."""
    why = _refused(dtype, width)
    if why is None and width != WIDTH:
        why = f"head width {width}: the kernels take {WIDTH}"
    if why is None and chunk not in CHUNKS:
        why = f"chunk {chunk} is none of the kernels' {CHUNKS}"
    if why is None and heads % groups:
        why = f"{heads} query heads do not divide over {groups} key-value groups"
    if why is None and seq % chunk:
        why = f"sequence {seq} is no whole number of chunks of {chunk}"
    if why is None and (seq // chunk) % RUN and seq // chunk > RUN:
        why = f"{seq // chunk} chunks are no whole number of runs of {RUN}"
    if why:
        return {"path": "xla", "why": why}
    run = min(RUN, seq // chunk)
    return {
        "path": "pallas", "run": run, "heads_per_step": 1,
        "saved_state_bytes": batch * (seq // (run * chunk)) * state_bytes(heads, width),
        "vmem_limit_bytes": _VMEM_LIMIT,
    }


# ------------------------------------------------------------ a chunk's values
def _pair_of(p):
    """Pair `p` of phi's blocks -> (I, J), I <= J, in `features`' order."""
    n = _BLOCKS
    i = sum((p >= a * n - a * (a - 1) // 2).astype(jnp.int32) for a in range(1, n))
    return i, p - (i * (2 * n + 1 - i)) // 2 + i


def _pairs(body, carry):
    """`body(p, carry)` for phi's pairs p in order, `_TOGETHER` a step of
    one loop."""
    def step(n, carry):
        for p in range(_TOGETHER):
            carry = body(n * _TOGETHER + p, carry)
        return carry

    return jax.lax.fori_loop(0, _PAIRS // _TOGETHER, step, carry)


def _rows(x_ref, block):
    return x_ref[pl.ds(pl.multiple_of(block * FEATURE_BLOCK, FEATURE_BLOCK), FEATURE_BLOCK), :]


def _blocks(x_ref, i, j):
    """Pair (i, j)'s two blocks of the rows held transposed in x_ref [P, C]
    float32 ([16, C] each) and its weight."""
    left, right = _rows(x_ref, i), _rows(x_ref, j)
    return left, right, jnp.where(i == j, 1.0, math.sqrt(2.0)).astype(_F32)


def _features(x_ref, i, j):
    """Pair (i, j) of phi of the rows held transposed in x_ref [P, C]:
    [256, C] float32, `power_retention.features`' numbers."""
    b = FEATURE_BLOCK
    left, right, w = _blocks(x_ref, i, j)
    c = left.shape[1]
    outer = jnp.broadcast_to(left[:, None, :], (b, b, c)) * right[None, :, :] * w
    return outer.reshape(b * b, c)


def _features_grad(d, x_ref, dx_ref, i, j):
    """Adds the cotangent of x [P, C] (held transposed) that pair (i, j)'s
    cotangent d [256, C] float32 gives into dx_ref."""
    b = FEATURE_BLOCK
    left, right, w = _blocks(x_ref, i, j)
    d = (d * w).reshape(b, b, d.shape[1])  # [a, b, C]
    at = lambda k: pl.ds(pl.multiple_of(k * b, b), b)  # noqa: E731
    dx_ref[at(i), :] += jnp.sum(d * right[None, :, :], axis=1)
    dx_ref[at(j), :] += jnp.sum(d * left[:, None, :], axis=0)


def _gates(g_ref, c):
    """Chunk c's running sums G: (row [1, C], column [C, 1], G_C [1, 1])."""
    row = g_ref[0, 0, 0, pl.ds(c, 1), :]
    last = _iota(row.shape, 1) == row.shape[1] - 1
    return row, _column(row), jnp.sum(jnp.where(last, row, 0.0), axis=1, keepdims=True)


def _scores(q, k, g_row, g_col):
    """The chunk's own part: (q k^T [C, C], the causal decays exp(G_i -
    G_j)) in float32."""
    c = q.shape[0]
    raw = _dot(q, k, _NT)
    causal = _iota((c, c), 0) >= _iota((c, c), 1)
    decay = jnp.exp(jnp.where(causal, g_col - g_row, -jnp.inf))
    return raw, decay


def _parts(x, dtype):
    """x float32 as the sum of two arrays of `dtype` (one where that is
    float32): the products of both carry 16 bits of x's mantissa."""
    high = x.astype(dtype)
    if high.dtype == x.dtype:
        return (high,)
    return high, (x - high.astype(x.dtype)).astype(dtype)


def _pair_rows(p):
    return pl.ds(pl.multiple_of(p * _PAIR, _PAIR), _PAIR)


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, y_ref, low_ref, *rest, chunk, run, save):
    saved_ref, state_ref, qt_ref, kt_ref = rest if save else (None, *rest)
    dtype = q_ref.dtype
    d = _STATE - WIDTH
    sq = WIDTH**-0.5 * WIDTH**-0.5

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)
        low_ref[...] = jnp.full_like(low_ref, jnp.inf)

    if save:
        saved_ref[0, 0, 0] = state_ref[...]

    def one(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        q, k, v = q_ref[0, rows, :], k_ref[0, rows, :], v_ref[0, rows, :]
        q32, k32 = q.astype(_F32), k.astype(_F32)
        qt_ref[...], kt_ref[...] = q32.T, k32.T
        g_row, g_col, g_last = _gates(g_ref, c)
        into, whole = jnp.exp(g_col), jnp.exp(g_last)
        vw = (v.astype(_F32) * jnp.exp(g_last - g_col)).astype(dtype)  # [C, P]
        kwt = (kt_ref[...] * jnp.exp(g_last - g_row)).astype(dtype)  # [P, C]

        def pair(p, read):
            i, j = _pair_of(p)
            fq = _features(qt_ref, i, j).astype(dtype)
            fk = _features(kt_ref, i, j).astype(dtype)
            at = _pair_rows(p)
            s = state_ref[at, :]
            state_ref[at, :] = whole * s + _dot(fk, vw, _NN)
            return read + _dot(s.astype(dtype), fq, _TN)  # (phi(Q) S)^T [P, C]

        read = _pairs(pair, jnp.zeros((WIDTH, chunk), _F32))
        z = state_ref[d:, :]
        qz = _dot(q, z.astype(dtype), _NN)
        num = read.T * (sq * into)
        den = jnp.sum(qz * q32, axis=1, keepdims=True) * sq * into
        raw, decay = _scores(q, k, g_row, g_col)
        a = sq * raw * raw * decay
        num = num + _dot(a.astype(dtype), v, _NN)
        den = den + jnp.sum(a, axis=1, keepdims=True)
        y_ref[0, rows, :] = (num / (den + EPS)).astype(y_ref.dtype)
        low_ref[...] = jnp.minimum(low_ref[...], jnp.min(den))
        state_ref[d:, :] = whole * z + _dot(kwt, k, _NN)
        return carry

    jax.lax.fori_loop(0, run, one, None)


def _specs(heads, groups, chunk, run):
    per = heads // groups
    rows = run * chunk
    head = pl.BlockSpec((1, rows, WIDTH), lambda b, h, i: (b, i, h))
    group = pl.BlockSpec((1, rows, WIDTH), lambda b, h, i: (b, i, h // per))
    gates = pl.BlockSpec((1, 1, 1, run, chunk), lambda b, h, i: (b, h, i, 0, 0))
    return head, group, gates


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
)


@_one_lowering("heads", "chunk", "run", "save")
def _scan_fwd(q, k, v, g, heads, chunk, run, save, *, interpret):
    batch, seq, _ = q.shape
    groups = k.shape[2] // WIDTH
    runs = seq // (run * chunk)
    head, group, gates = _specs(heads, groups, chunk, run)
    low = pl.BlockSpec((1, 1, 8, 128), lambda b, h, i: (b, h, 0, 0))
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct((batch, heads, 8, 128), _F32)]
    out_specs = [head, low]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((batch, runs, heads, _STATE, WIDTH), _F32))
        out_specs.append(pl.BlockSpec((1, 1, 1, _STATE, WIDTH), lambda b, h, i: (b, i, h, 0, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, run=run, save=save),
        grid=(batch, heads, runs),
        in_specs=[head, group, group, gates],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_STATE, WIDTH), _F32),
                        pltpu.VMEM((WIDTH, chunk), _F32), pltpu.VMEM((WIDTH, chunk), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="retention_scan_fwd",
    )(q, k, v, g)


# ------------------------------------------------------------------ backward
def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, dy_ref, saved_ref,
                dq_ref, dk_ref, dv_ref, dg_ref,
                states, dstate_ref, qt_ref, kt_ref, dqt_ref, dkt_ref,
                *, chunk, run, runs):
    dtype = q_ref.dtype
    d = _STATE - WIDTH
    sq = WIDTH**-0.5 * WIDTH**-0.5

    @pl.when(pl.program_id(2) == 0)  # the last run: nothing follows it
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    b, h = pl.program_id(0), pl.program_id(1)
    pltpu.sync_copy(saved_ref.at[b, runs - 1 - pl.program_id(2), h], states.at[0])

    def chunk_of(c):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        g_row, g_col, g_last = _gates(g_ref, c)
        return rows, g_row, g_col, g_last

    # forward from the saved state: the state every chunk of the run starts from
    def rebuild(c, carry):
        rows, g_row, g_col, g_last = chunk_of(c)
        k = k_ref[0, rows, :]
        kt_ref[...] = k.astype(_F32).T
        whole = jnp.exp(g_last)
        vw = (v_ref[0, rows, :].astype(_F32) * jnp.exp(g_last - g_col)).astype(dtype)
        kwt = (kt_ref[...] * jnp.exp(g_last - g_row)).astype(dtype)

        def pair(p, carry):
            i, j = _pair_of(p)
            fk = _features(kt_ref, i, j).astype(dtype)
            at = _pair_rows(p)
            states[c + 1, at, :] = whole * states[c, at, :] + _dot(fk, vw, _NN)
            return carry

        _pairs(pair, None)
        states[c + 1, d:, :] = whole * states[c, d:, :] + _dot(kwt, k, _NN)
        return carry

    jax.lax.fori_loop(0, run - 1, rebuild, None)

    def back(n, carry):
        c = run - 1 - n
        rows, g_row, g_col, g_last = chunk_of(c)
        q, k, v, dy = q_ref[0, rows, :], k_ref[0, rows, :], v_ref[0, rows, :], dy_ref[0, rows, :]
        q32, k32, v32, dy32 = (x.astype(_F32) for x in (q, k, v, dy))
        qt_ref[...], kt_ref[...] = q32.T, k32.T
        dqt_ref[...] = jnp.zeros_like(dqt_ref)
        dkt_ref[...] = jnp.zeros_like(dkt_ref)
        qbt = qt_ref[...].astype(dtype)
        dybt = dy32.T.astype(dtype)
        into, whole = jnp.exp(g_col), jnp.exp(g_last)
        out = jnp.exp(g_last - g_col)  # [C, 1]
        vwt = (v32.T * jnp.exp(g_last - g_row)).astype(dtype)  # vw^T
        kw = (k32 * out).astype(dtype)
        # what the forward made of the chunk, but the read
        z = states[c, d:, :]
        zb = z.astype(dtype)
        qz = _dot(q, zb, _NN)
        qzq = jnp.sum(qz * q32, axis=1, keepdims=True)
        raw, decay = _scores(q, k, g_row, g_col)
        a = sq * raw * raw * decay
        ab = a.astype(dtype)
        den = qzq * sq * into + jnp.sum(a, axis=1, keepdims=True)
        dnum = dy32 / (den + EPS)
        dread = (dnum * (sq * into)).astype(dtype)  # of phi(Q) S
        coef = _row(sq * into / (den + EPS))  # phi(Q)'s cotangent is (S dy^T) times this

        def pair(p, carry):
            dy_read, dvwt, dwhole = carry
            i, j = _pair_of(p)
            fq = _features(qt_ref, i, j).astype(dtype)
            fk = _features(kt_ref, i, j).astype(dtype)
            at = _pair_rows(p)
            s, ds = states[c, at, :], dstate_ref[at, :]  # ds: of the state after the chunk
            dsb = ds.astype(dtype)
            m = _dot(s.astype(dtype), dybt, _NN)  # S dy^T [256, C]
            dy_read = dy_read + jnp.sum(fq.astype(_F32) * m, axis=0, keepdims=True)
            _features_grad(m * coef, qt_ref, dqt_ref, i, j)
            _features_grad(_dot(dsb, vwt, _NN), kt_ref, dkt_ref, i, j)
            dvwt = dvwt + _dot(dsb, fk, _TN)
            dwhole = dwhole + jnp.sum(ds * s, keepdims=True)
            dstate_ref[at, :] = whole * ds + _dot(fq, dread, _NN)
            return dy_read, dvwt, dwhole

        dy_read, dvwt, dwhole = _pairs(
            pair, (jnp.zeros((1, chunk), _F32), jnp.zeros((WIDTH, chunk), _F32),
                   jnp.zeros((1, 1), _F32)))
        dy_read = _column(dy_read)  # rowsum(dy * phi(Q) S)
        nv = _dot(ab, v, _NN)
        dy_num = dy_read * sq * into + jnp.sum(dy32 * nv, axis=1, keepdims=True)
        dden = -dy_num / ((den + EPS) * (den + EPS))
        d_into = dy_read * sq / (den + EPS) + dden * qzq * sq
        # the normaliser: read, then updated
        dqzq = dden * sq * into
        dqz = (dqzq * q32).astype(dtype)
        dq = dqzq * qz + _dot(dqz, zb, _NT)
        dz = dstate_ref[d:, :]
        dzb = dz.astype(dtype)
        dwhole = dwhole + jnp.sum(dz * z, keepdims=True)
        dkw = _dot(k, dzb, _NT)
        dk = _dot(kw, dzb, _NN)
        dstate_ref[d:, :] = whole * dz + _dot(qbt, dqz, _NN)
        # the chunk's own part. The readout's cotangent is taken at 16 bits
        # of mantissa (two parts of the activations' type): the scores'
        # cotangent `dnum v^T + dden` cancels where a row's values are near
        # its readout, and one rounding of dnum leaves that unbalanced
        dn = _parts(dnum, dtype)
        da = sum(_dot(x, v, _NT) for x in dn) + dden
        dv = sum(_dot(ab, x, _TN) for x in dn)
        t = da * decay
        draw = (t * raw * (2.0 * sq)).astype(dtype)
        dexp = da * a  # of the decays' exponents
        dq = dq + _dot(draw, k, _NN)
        dkt_ref[...] += _dot(qbt, draw, _NN)
        # the values and keys as the state's update weighs them
        dvw = dvwt.T
        dv = dv + dvw * out
        dk = dk + dkw * out
        dout = (jnp.sum(dvw * v32, axis=1, keepdims=True)
                + jnp.sum(dkw * k32, axis=1, keepdims=True)) * out
        dlast = jnp.sum(dout, keepdims=True) + dwhole * whole
        dg = (jnp.sum(dexp, axis=1, keepdims=True) - _column(jnp.sum(dexp, axis=0, keepdims=True))
              - dout + d_into * into)
        last = _iota((chunk, 1), 0) == chunk - 1
        dg_ref[0, 0, 0, pl.ds(c, 1), :] = _row(dg + jnp.where(last, dlast, 0.0))
        dq_ref[0, rows, :] = (dq + dqt_ref[...].T).astype(dq_ref.dtype)
        dk_ref[0, 0, rows, :] = dk + dkt_ref[...].T
        dv_ref[0, 0, rows, :] = dv
        return carry

    jax.lax.fori_loop(0, run, back, None)


@_one_lowering("heads", "chunk", "run")
def _scan_bwd(q, k, v, g, dy, saved, heads, chunk, run, *, interpret):
    batch, seq, _ = q.shape
    groups = k.shape[2] // WIDTH
    runs = seq // (run * chunk)
    back = lambda spec: pl.BlockSpec(  # noqa: E731 - the runs from the last to the first
        spec.block_shape, lambda b, h, i: spec.index_map(b, h, runs - 1 - i))
    head, group, gates = (back(spec) for spec in _specs(heads, groups, chunk, run))
    # a group's cotangents, a query head's part: the group's heads one after
    # another along a leading axis, summed outside
    per = heads // groups
    per_head = jax.ShapeDtypeStruct((per, *k.shape), _F32)
    part = pl.BlockSpec((1, 1, run * chunk, WIDTH),
                        lambda b, h, i: (h % per, b, runs - 1 - i, h // per))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, run=run, runs=runs),
        grid=(batch, heads, runs),
        in_specs=[head, group, group, gates, head, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[head, part, part, gates],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), per_head, per_head,
                   jax.ShapeDtypeStruct(g.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((run, _STATE, WIDTH), _F32),
                        pltpu.VMEM((_STATE, WIDTH), _F32)]
                       + [pltpu.VMEM((WIDTH, chunk), _F32)] * 4,
        compiler_params=_PARAMS,
        interpret=interpret,
        name="retention_scan_bwd",
    )(q, k, v, g, dy, saved)


# ------------------------------------------------------------------ the op
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _scan(q, k, v, g, heads, chunk, run):
    return tuple(_scan_fwd(q, k, v, g, heads, chunk, run, False))


def _scan_vjp_fwd(q, k, v, g, heads, chunk, run):
    y, low, saved = _scan_fwd(q, k, v, g, heads, chunk, run, True)
    return (y, low), (q, k, v, g, saved)


def _scan_vjp_bwd(heads, chunk, run, res, cts):
    q, k, v, g, saved = res
    dy = cts[0]  # the smallest denominator is a reading, not differentiated
    dq, dk, dv, dg = _scan_bwd(q, k, v, g, dy.astype(q.dtype), saved, heads, chunk, run)
    return dq, dk.sum(0).astype(k.dtype), dv.sum(0).astype(v.dtype), dg


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd, optimize_remat=True)


def scan(q, k, v, log_g, *, chunk: int, run: int):
    """`power_retention.retention_scan` for a shape `scan_plan` gives the
    kernels: q [B, S, H x P], k, v [B, S, G x P], log_g [B, S, H] (<= 0) ->
    (y [B, S, H x P] in q's type, the smallest denominator, float32)."""
    bsz, seq, heads = log_g.shape
    runs, nc = seq // (run * chunk), seq // chunk
    g = jnp.cumsum(log_g.astype(_F32).reshape(bsz, nc, chunk, heads), axis=2)
    g = g.transpose(0, 3, 1, 2).reshape(bsz, heads, runs, run, chunk)
    y, low = _scan(q, k, v, g, heads, chunk, run)
    return y, jnp.min(low)
