"""The chunked delta-rule scan of `ops/kda.py` as Pallas TPU kernels under a
`jax.custom_vjp`: `kda_scan_fwd` and `kda_scan_bwd`. The algorithm, the
numbers and the types are `ops/kda.py`'s (its docstring has the algebra):
decays, sums, `beta`, the inverse, the carried state and its update in
float32; the products' operands in the activations' type, accumulated in
float32. What changes is where the values live: a chunk's running sum `G`,
the sub-block factors, `A`, the scores, the inverse, `W`, `U` and the
pseudo-values exist in VMEM only, and the state `[K, V]` is a VMEM scratch
carried across a sequential grid axis.

**Layouts.** q, k, v, g and o are read and written merged, `[B, S, H x K]`:
a block `(1, rows, K)` at column block `h` is one head's rows (K = V a
multiple of 128). On the chip `[B, S, H, K]` is ANOTHER tiling (a tile there
is 8 or 16 heads of one position, here as many positions of one head), so an
operand that arrives with a heads' axis is copied once by the compiler and
one that arrives merged (what a projection or a conv hands over) is read
where it lies. `beta` `[B, S, H]` is handed over as rows a tile, `[B, H, S /
128, 128]` float32 (a 2 MB transpose outside the kernel at the cell's
shape): a block `(1, 1, tiles, 128)` holds a head's step sizes of one grid
step, one tile a sublane row; the kernel makes the column form it also
needs with an identity mask. The cotangent of `beta` leaves the backward
kernel in the same layout. With `unit_scales` the kernels also l2-normalise
the rows of q and k they hold (`ops/kda.l2_unit`'s numbers: float32, rounded
once to the activations' type), and the backward carries the cotangents
through it.

**Tiles.** The unit of work is a tile of 128 positions of one head: `128 /
chunk` whole chunks (two of 64), so that a `[tile, tile]` array fills the
128 lanes and a product's 128 columns; such an array holds each chunk's
`[C, C]` on the diagonal and exact zeros elsewhere. The running sum is
float32 additions in `log2(chunk)` doubling steps (a rotation along the
rows, masked at each chunk's start). A sub-block's keys are scaled only
over the rows it meets (its chunk's start to its own end); one product a
sub-block (`kp` over `qp` against those keys) gives its rows of `A` and of
the scores. The state-dependent part walks the tile's chunks in order.

**The grid** is `(B, H, S / (run x chunk))`, batch and heads parallel, the
runs sequential ("arbitrary"). A grid step takes one run of `run` chunks of
one head in an inner loop over its tiles, `_TOGETHER` tiles a loop step:
their operands first (they do not wait for the state), then their chunks.
The forward that a gradient will follow also writes the state each run
starts from (`[B, S / (run x chunk), H, K, V]` float32: 33.5 MB a layer at
16,384 positions and 32 heads of 128, where a state a chunk would be 537 MB).

**The inverse** `(I + A)^-1` is a float32 substitution: the `SUB` x `SUB`
diagonal blocks by forward substitution on the vector unit (column `j` of
every block of the tile in one step: `SUB - 1` steps), merged upward to the
chunk by block products at `Precision.HIGHEST` (`Y <- Y - Y L Y`, `L` the
blocks a merge joins, only the rows that change multiplied: the block form
of the same substitution, exact in exact arithmetic and no Neumann series).

**The backward** walks the runs from the last to the first. For a run it
walks the tiles forward once from the saved state to rebuild the state
every chunk starts from and every tile's inverse (VMEM scratches `[run + 1,
K, V]` and `[tiles, 128, 128]`), then backward carrying `dS`: a tile's
operands again (the inverse read back), the chunks' walk in reverse for
what meets the state, then the tile's gradient term by term: through both
factors of every decay into `dG` and by a reverse running sum into `dg`;
`dA = -X^T dX X^T` as `ops/kda._inverse_bwd`.

`scan_plan` lets the shape decide between these kernels and the `jax.numpy`
form; off the TPU the kernels run interpreted (`flash_attention._interpret`,
the one switch, through `mamba_fused._one_lowering`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mamba_fused import _one_lowering, _refused, _sublanes  # `_one_lowering`: interpreted off the TPU

SUB = 16  # positions of a sub-block: SUB * |gate bound| must stay under float32's 88
RUN = 16  # chunks a grid step takes, and a saved state covers, at most
L2_EPS = 1e-6  # of `ops/kda.l2_unit`
_TILE = 128  # positions of a tile: chunks are taken `_TILE // chunk` at a time
_VMEM_LIMIT = 64 * 2**20
_TOGETHER = 2  # tiles whose operands one loop step builds before it walks their chunks
_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # A @ B
_NT = (((1,), (1,)), ((), ()))  # A @ B^T
_TN = (((0,), (0,)), ((), ()))  # A^T @ B
_F32 = jnp.float32


def _together(tiles_per_step: int) -> int:
    return _TOGETHER if tiles_per_step % _TOGETHER == 0 else 1


def scan_plan(batch: int, seq: int, chunk: int, heads: int, key: int, value: int, dtype) -> dict:
    """How `kda_scan` runs this shape: `{"path": "pallas", "chunks_per_step",
    "chunks_per_tile", "tiles_together", "heads_per_step", "run",
    "saved_state_bytes"}` (`run` chunks a saved state covers = chunks a grid
    step; `tiles_together`: tiles a step of the inner loop takes, so that one
    tile's operands are built while another's chunks wait for the state) or
    `{"path": "xla", "why"}`."""
    why = _refused(dtype, key)
    if why is None and key != value:
        why = f"key width {key} and value width {value} differ"
    if why is None and chunk % SUB:
        why = f"chunk {chunk} is no multiple of the sub-block {SUB}"
    if why is None and (chunk % _sublanes(dtype) or _TILE % chunk):
        why = f"chunk {chunk} is no multiple of the sublane tile, or does not divide {_TILE}"
    if why is None and seq % _TILE:
        why = f"sequence {seq} is no multiple of a tile of {_TILE} positions"
    if why:
        return {"path": "xla", "why": why}
    group, tiles = _TILE // chunk, seq // _TILE
    # beta's block holds a tile a sublane row: 8 rows, or the whole axis
    per_step = next((t for t in range(min(RUN // group, tiles), 0, -1)
                     if tiles % t == 0 and (t % 8 == 0 or t == tiles)), None)
    if per_step is None:
        return {"path": "xla", "why": f"{tiles} tiles of {_TILE} positions are no whole "
                                      f"number of runs of {RUN} chunks at most"}
    run = per_step * group
    return {
        "path": "pallas", "chunks_per_step": run, "chunks_per_tile": group,
        "tiles_together": _together(per_step), "heads_per_step": 1, "run": run,
        "saved_state_bytes": batch * (seq // (run * chunk)) * heads * key * value * 4,
    }


# ------------------------------------------------------------ a tile's values
def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, dimension_numbers=dims, precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(row):
    """[1, n] -> [n, 1]: the row under an identity mask, summed along lanes."""
    n = row.shape[1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col):
    """[n, 1] -> [1, n]."""
    n = col.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _running_sum(g, chunk, reverse=False):
    """G: the running sum of g [P, K] float32 down the rows of each chunk,
    float32 additions in log2(chunk) doubling steps (a rotation along the
    rows, masked at the chunk's start); `reverse`: its transpose, up the
    rows."""
    p = g.shape[0]
    position = _iota((p, 1), 0) % chunk
    shift = 1
    while shift < chunk:
        if reverse:
            g = g + jnp.where(position < chunk - shift, pltpu.roll(g, p - shift, 0), 0.0)
        else:
            g = g + jnp.where(position >= shift, pltpu.roll(g, shift, 0), 0.0)
        shift *= 2
    return g


def _inverse(a, chunk):
    """(I + a)^-1 for a [P, P] float32 whose chunks' diagonal blocks are
    strictly lower triangular and the rest 0 (chunk = SUB x a power of two):
    forward substitution inside the SUB x SUB diagonal blocks (step j
    settles column j of every block: Y -= a[:, j] Y[j, :]), then merges of
    neighbouring blocks up to the chunk by products at HIGHEST."""
    p = a.shape[0]
    rows, cols = _iota((p, p), 0), _iota((p, p), 1)
    y = (rows == cols).astype(_F32)
    diag = jnp.where((rows // SUB) == (cols // SUB), a, 0.0)
    for j in range(SUB - 1):
        # column j of each diagonal block, along the lanes of its rows
        col = jnp.sum(jnp.where(cols == (rows // SUB) * SUB + j, diag, 0.0), axis=1,
                      keepdims=True)
        # row j of each block of y, under the rows of its block
        row = jnp.concatenate(
            [jnp.broadcast_to(y[b * SUB + j : b * SUB + j + 1, :], (SUB, p))
             for b in range(p // SUB)], axis=0)
        y = y - col * row
    size = SUB
    while size < chunk:
        # Y <- Y - Y L Y, L the blocks of `a` that join two neighbours: only
        # the second neighbour's rows change, so only they are multiplied
        second = [slice(b + size, b + 2 * size) for b in range(0, p, 2 * size)]
        joins = (cols // size) == (rows // size) - 1
        low = jnp.where(joins, a, 0.0)
        change = _dot(_dot(jnp.concatenate([y[r] for r in second], axis=0), low, _NN, _HI),
                      y, _NN, _HI)
        zeros = jnp.zeros((size, p), _F32)
        y = y - jnp.concatenate(
            [part for n in range(len(second))
             for part in (zeros, change[n * size : (n + 1) * size])], axis=0)
        size *= 2
    return y


def _rows_of_tile(part, at: slice, tile: int):
    """`part` as the rows `at` of a `[tile, n]` array of zeros."""
    zeros = lambda n: [jnp.zeros((n, part.shape[1]), part.dtype)] if n else []  # noqa: E731
    return jnp.concatenate(zeros(at.start) + [part] + zeros(tile - at.stop), axis=0)


def _unit_rows(x, scale):
    """Each row of x [P, K] over its l2 norm, times `scale`, rounded to x's
    type as `ops/kda.l2_unit` rounds it -> (that in float32, the unrounded
    unit rows, the rows' rsqrt [P, 1])."""
    x32 = x.astype(_F32)
    r = jax.lax.rsqrt(jnp.sum(x32 * x32, axis=1, keepdims=True) + L2_EPS)
    unit = x32 * r
    return (unit * scale).astype(x.dtype).astype(_F32), unit, r


def _unit_grad(d, unit, r, scale):
    """The cotangent of the rows `_unit_rows` normalised, from that of its
    result."""
    return (scale * r) * (d - unit * jnp.sum(d * unit, axis=1, keepdims=True))


def _operands(q, k, g, brow, dtype, chunk, x=None, scales=None):
    """What the products of a tile of whole chunks are made of: q (None:
    nothing of q is built), k [P, K]; g [P, K] float32; brow [1, P] float32;
    x: the inverse where it is known already -> a dict of float32 and
    `dtype` arrays (the names are `ops/kda.py`'s). `[P, P]` arrays hold a
    chunk's `[C, C]` on the diagonal and exact zeros elsewhere. `scales`
    (of q, of k): the rows arrive un-normalised (`_unit_rows`)."""
    p = k.shape[0]
    run = _running_sum(g, chunk)  # G
    units = {}
    if scales is None:
        k32 = k.astype(_F32)
        q32 = None if q is None else q.astype(_F32)
    else:
        k32, *units["k"] = _unit_rows(k, scales[1])
        if q is not None:
            q32, *units["q"] = _unit_rows(q, scales[0])
    products, subs = [], []
    for i in range(p // SUB):
        block = slice(i * SUB, (i + 1) * SUB)
        first = run[i * SUB : i * SUB + 1, :]  # r_I
        rows = jnp.exp(run[block] - first)  # exp(G_i - r_I) <= 1
        # the chunk's keys as this sub-block's rows meet them: exp(r_I - G_j)
        # from the chunk's start to the sub-block's end, an exact 0 elsewhere
        met = slice(i * SUB // chunk * chunk, (i + 1) * SUB)
        facing = jnp.exp(first - run[met])
        # kp over qp: one product a sub-block gives its rows of `a` and of the scores
        scaled = k32[block] * rows
        if q is not None:
            scaled = jnp.concatenate([scaled, q32[block] * rows], axis=0)
        sub = {"rows": rows, "facing": facing, "met": met, "kq": scaled.astype(dtype),
               "k_facing": _rows_of_tile((k32[met] * facing).astype(dtype), met, p)}
        products.append(_dot(sub["kq"], sub["k_facing"], _NT))
        subs.append(sub)
    rows_i, cols_i = _iota((p, p), 0), _iota((p, p), 1)
    same = (rows_i // chunk) == (cols_i // chunk)
    bcol = _column(brow)
    a_raw = jnp.where((rows_i > cols_i) & same,
                      jnp.concatenate([x_[:SUB] for x_ in products], axis=0), 0.0)
    if x is None:
        x = _inverse(a_raw * bcol, chunk)
    decayed = jnp.exp(run)
    ends = [run[c * chunk + chunk - 1 : (c + 1) * chunk, :] for c in range(p // chunk)]  # G_C
    to_end = jnp.exp(
        jnp.concatenate([jnp.broadcast_to(e, (chunk, e.shape[1])) for e in ends], axis=0) - run)
    ops = {
        "k32": k32, "subs": subs, "a_raw": a_raw, "x": x, "bcol": bcol, "same": same,
        "units": units,
        "t": (x * brow).astype(dtype),  # (I + A)^-1 Diag(beta)
        "decayed": decayed, "to_end": to_end, "whole": [jnp.exp(e) for e in ends],
        "k_in": (k32 * decayed).astype(dtype), "k_out": (k32 * to_end).astype(dtype),
    }
    if q is not None:
        ops["q32"] = q32
        ops["q_in"] = (q32 * decayed).astype(dtype)
        ops["scores"] = jnp.where(
            (rows_i >= cols_i) & same,
            jnp.concatenate([x_[SUB:] for x_ in products], axis=0), 0.0).astype(dtype)
    return ops


def _w_u(ops, v, dtype):
    """(W, U) [P, .] in `dtype`."""
    return (_dot(ops["t"], ops["k_in"], _NN).astype(dtype),
            _dot(ops["t"], v, _NN).astype(dtype))


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, chunk, tile, save, scales):
    states_ref, state_ref = rest if save else (None, rest[0])
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    if save:
        states_ref[0, 0, 0] = state_ref[...]

    def some(pair, carry):
        # the operands of `together` tiles first: they do not wait for the state
        tiles = []
        for i in (pair * together + n for n in range(together)):
            rows = pl.ds(pl.multiple_of(i * tile, tile), tile)
            ops = _operands(q_ref[0, rows, :], k_ref[0, rows, :], g_ref[0, rows, :],
                            b_ref[0, 0, pl.ds(i, 1), :], dtype, chunk, scales=scales)
            tiles.append((i, ops, _w_u(ops, v_ref[0, rows, :], dtype)))
        state = state_ref[...]
        for i, ops, (w, u) in tiles:
            for c in range(tile // chunk):
                at = slice(c * chunk, (c + 1) * chunk)
                before = state.astype(dtype)
                new = (u[at].astype(_F32) - _dot(w[at], before, _NN)).astype(dtype)  # U - W S
                out = _dot(ops["q_in"][at], before, _NN) + _dot(
                    ops["scores"][at], _rows_of_tile(new, at, tile), _NN)
                o_ref[0, pl.ds(pl.multiple_of(i * tile + c * chunk, chunk), chunk), :] = (
                    out.astype(o_ref.dtype))
                state = _column(ops["whole"][c]) * state + _dot(ops["k_out"][at], new, _TN)
        state_ref[...] = state
        return carry

    n = q_ref.shape[1] // tile
    together = _together(n)
    jax.lax.fori_loop(0, n // together, some, None)


def _specs(width, tile, per_step):
    rows = per_step * tile
    wide = pl.BlockSpec((1, rows, width), lambda b, h, i: (b, i, h))
    beta = pl.BlockSpec((1, 1, per_step, tile), lambda b, h, i: (b, h, i, 0))
    saved = pl.BlockSpec((1, 1, 1, width, width), lambda b, h, i: (b, i, h, 0, 0))
    return wide, beta, saved


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
)


@_one_lowering("heads", "chunk", "run", "save", "scales")
def _scan_fwd(q, k, v, g, beta_rows, heads, chunk, run, save, scales, *, interpret):
    batch, seq, inner = q.shape
    width = inner // heads
    wide, beta, saved = _specs(width, _TILE, run * chunk // _TILE)
    runs = seq // (run * chunk)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [wide]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((batch, runs, heads, width, width), _F32))
        out_specs.append(saved)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, tile=_TILE, save=save, scales=scales),
        grid=(batch, heads, runs),
        in_specs=[wide, wide, wide, wide, beta],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((width, width), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="kda_scan_fwd",
    )(q, k, v, g, beta_rows)


# ------------------------------------------------------------------ backward
def _tile_grad(ops, v, brow, walked, dtype, chunk):
    """The cotangents of a tile's q, k (float32), v, g and beta (a row), once
    the walk over its chunks has left the cotangents of what meets the
    state (`walked`: of q_in, k_out, W, U' = U, the scores, and of each
    chunk's exp(G_C) as a row). Products take `dtype` operands as the
    forward's do."""
    p = v.shape[0]
    k32, q32, x, t = ops["k32"], ops["q32"], ops["x"], ops["t"]
    decayed, to_end = ops["decayed"], ops["to_end"]
    d_qin, d_kout, dw, dn, d_scores, d_whole = walked
    # W = T k_in, U = T v
    dwd = dw.astype(dtype)
    d_t = jnp.where(ops["same"], _dot(dwd, ops["k_in"], _NT) + _dot(dn, v, _NT), 0.0)
    d_kin = _dot(t, dwd, _TN)
    d_v = _dot(t, dn, _TN)
    # T = X Diag(beta); X = (I + A)^-1: dA = -X^T dX X^T; A = Diag(beta) a
    d_brow = jnp.sum(d_t * x, axis=0, keepdims=True)
    rows_i, cols_i = _iota((p, p), 0), _iota((p, p), 1)
    d_a = -_dot(_dot(x, d_t * brow, _TN, _HI), x, _NT, _HI)
    d_a = jnp.where((rows_i > cols_i) & ops["same"], d_a, 0.0)
    d_brow = d_brow + _row(jnp.sum(d_a * ops["a_raw"], axis=1, keepdims=True))
    d_a = (d_a * ops["bcol"]).astype(dtype)
    d_s = jnp.where((rows_i >= cols_i) & ops["same"], d_scores, 0.0).astype(dtype)
    # the decays: every exponent's cotangent goes to G
    dk = d_kin * decayed + d_kout * to_end
    dq = d_qin * decayed
    through_end = d_kout * k32 * to_end
    d_run = d_kin * k32 * decayed + dq * q32 - through_end
    position = _iota((p, 1), 0)
    for c, whole in enumerate(ops["whole"]):
        at = slice(c * chunk, (c + 1) * chunk)
        d_last = jnp.sum(through_end[at], axis=0, keepdims=True) + d_whole[c] * whole
        d_run = d_run + jnp.where(position == (c + 1) * chunk - 1, d_last, 0.0)
    d_subs = []
    for i, sub in enumerate(ops["subs"]):
        block = slice(i * SUB, (i + 1) * SUB)
        d_as = jnp.concatenate([d_a[block], d_s[block]], axis=0)  # [2 SUB, P]
        d_kq = _dot(d_as, sub["k_facing"], _NN) * jnp.concatenate([sub["rows"]] * 2, axis=0)
        d_kf = _dot(d_as, sub["kq"], _TN)  # [P, K]
        met = sub["met"]  # elsewhere the factor is 0
        d_facing = d_kf[met] * sub["facing"]
        through_facing = d_facing * k32[met]
        dk = dk + _rows_of_tile(d_facing, met, p)
        d_run = d_run - _rows_of_tile(through_facing, met, p)
        through_rows = d_kq[:SUB] * k32[block] + d_kq[SUB:] * q32[block]
        d_first = (jnp.sum(through_facing, axis=0, keepdims=True)
                   - jnp.sum(through_rows, axis=0, keepdims=True))
        d_subs.append((d_kq, through_rows + jnp.where(_iota((SUB, 1), 0) == 0, d_first, 0.0)))
    dk = dk + jnp.concatenate([d_kq[:SUB] for d_kq, _ in d_subs], axis=0)
    dq = dq + jnp.concatenate([d_kq[SUB:] for d_kq, _ in d_subs], axis=0)
    d_run = d_run + jnp.concatenate([through for _, through in d_subs], axis=0)
    return dq, dk, d_v, _running_sum(d_run, chunk, reverse=True), d_brow


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, states_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, starts, inverses, dstate_ref,
                *, chunk, tile, scales):
    dtype = q_ref.dtype
    n, group = q_ref.shape[1] // tile, tile // chunk

    @pl.when(pl.program_id(2) == 0)  # the last run: nothing follows it
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    def tile_of(i):
        rows = pl.ds(pl.multiple_of(i * tile, tile), tile)
        return rows, k_ref[0, rows, :], g_ref[0, rows, :], b_ref[0, 0, pl.ds(i, 1), :]

    # forward from the saved state: the state every chunk of the run starts
    # from and every tile's inverse
    starts[0] = states_ref[0, 0, 0]

    def rebuild(some, carry):
        # the operands of `together` tiles first: they do not wait for the state
        tiles = []
        for i in (some * together + m for m in range(together)):
            rows, k, g, brow = tile_of(i)
            ops = _operands(None, k, g, brow, dtype, chunk, scales=scales)
            inverses[i] = ops["x"]
            tiles.append((i, ops, _w_u(ops, v_ref[0, rows, :], dtype)))
        state = starts[some * together * group]
        for i, ops, (w, u) in tiles:
            for c in range(group):
                at = slice(c * chunk, (c + 1) * chunk)
                new = (u[at].astype(_F32) - _dot(w[at], state.astype(dtype), _NN)).astype(dtype)
                state = _column(ops["whole"][c]) * state + _dot(ops["k_out"][at], new, _TN)
                starts[i * group + c + 1] = state
        return carry

    together = _together(n)
    jax.lax.fori_loop(0, n // together, rebuild, None)

    def back(some, carry):
        tiles = []
        for i in (n - 1 - some * together - m for m in range(together)):
            rows, k, g, brow = tile_of(i)
            ops = _operands(q_ref[0, rows, :], k, g, brow, dtype, chunk, x=inverses[i],
                            scales=scales)
            tiles.append((i, rows, brow, ops, _w_u(ops, v_ref[0, rows, :], dtype)))
        dstate = dstate_ref[...]
        for i, rows, brow, ops, (w, u) in tiles:
            v, do = v_ref[0, rows, :], do_ref[0, rows, :]
            walked = []
            for c in reversed(range(group)):
                at = slice(c * chunk, (c + 1) * chunk)
                state = starts[i * group + c]
                before, ds = state.astype(dtype), dstate.astype(dtype)
                new = (u[at].astype(_F32) - _dot(w[at], before, _NN)).astype(dtype)
                # o = q_in S + scores U'; S' = Diag(exp G_C) S + k_out^T U'
                d_new = _dot(ops["scores"][at], do[at], _TN)[at] + _dot(ops["k_out"][at], ds, _NN)
                dn = d_new.astype(dtype)
                walked.append((
                    _dot(do[at], before, _NT),  # of q_in
                    _dot(new, ds, _NT),  # of k_out
                    -_dot(dn, before, _NT),  # of W: U' = U - W S
                    dn,  # of U
                    _dot(do[at], _rows_of_tile(new, at, tile), _NT),  # of the scores' rows
                    _row(jnp.sum(dstate * state, axis=1, keepdims=True)),  # of exp(G_C), a row
                ))
                dstate = (_column(ops["whole"][c]) * dstate + _dot(ops["q_in"][at], do[at], _TN)
                          - _dot(w[at], dn, _TN))
            walked = walked[::-1]
            rows_of = lambda n_: jnp.concatenate([x_[n_] for x_ in walked], axis=0)  # noqa: E731
            dq, dk, dv, dg, d_brow = _tile_grad(
                ops, v, brow,
                (rows_of(0), rows_of(1), rows_of(2), rows_of(3), rows_of(4),
                 [x_[5] for x_ in walked]), dtype, chunk)
            if scales is not None:
                dq = _unit_grad(dq, *ops["units"]["q"], scales[0])
                dk = _unit_grad(dk, *ops["units"]["k"], scales[1])
            dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
            dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
            dg_ref[0, rows, :] = dg
            db_ref[0, 0, pl.ds(i, 1), :] = d_brow
        dstate_ref[...] = dstate
        return carry

    jax.lax.fori_loop(0, n // together, back, None)


@_one_lowering("heads", "chunk", "run", "scales")
def _scan_bwd(q, k, v, g, beta_rows, do, states, heads, chunk, run, scales, *, interpret):
    batch, seq, inner = q.shape
    width = inner // heads
    runs, per_step = seq // (run * chunk), run * chunk // _TILE
    back = lambda spec: pl.BlockSpec(  # noqa: E731 - the runs from the last to the first
        spec.block_shape, lambda b, h, i: spec.index_map(b, h, runs - 1 - i))
    wide, beta, saved = (back(spec) for spec in _specs(width, _TILE, per_step))
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, tile=_TILE, scales=scales),
        grid=(batch, heads, runs),
        in_specs=[wide, wide, wide, wide, beta, wide, saved],
        out_specs=[wide, wide, wide, wide, beta],
        out_shape=[like(q), like(k), like(v), like(g), like(beta_rows)],
        scratch_shapes=[pltpu.VMEM((run + 1, width, width), _F32),
                        pltpu.VMEM((per_step, _TILE, _TILE), _F32),
                        pltpu.VMEM((width, width), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="kda_scan_bwd",
    )(q, k, v, g, beta_rows, do, states)


# ------------------------------------------------------------------ the op
def _flat(x):
    return x.reshape(*x.shape[:2], -1)


def _beta_rows(beta):
    """[B, S, H] -> [B, H, S / tile, tile] float32: a tile a sublane row."""
    bsz, seq, heads = beta.shape
    return beta.astype(_F32).transpose(0, 2, 1).reshape(bsz, heads, seq // _TILE, _TILE)


def _forward(q, k, v, g, beta, chunk, run, scales, save):
    out = _scan_fwd(_flat(q), _flat(k), _flat(v), _flat(g.astype(_F32)),
                    _beta_rows(beta), beta.shape[2], chunk, run, save, scales)
    return (out[0].reshape(v.shape), *out[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(q, k, v, g, beta, chunk, run, scales):
    return _forward(q, k, v, g, beta, chunk, run, scales, False)[0]


def _scan_vjp_fwd(q, k, v, g, beta, chunk, run, scales):
    out, states = _forward(q, k, v, g, beta, chunk, run, scales, True)
    return out, (q, k, v, g, beta, states)


def _scan_vjp_bwd(chunk, run, scales, res, do):
    q, k, v, g, beta, states = res
    bsz, seq, heads = beta.shape
    dq, dk, dv, dg, db = _scan_bwd(
        _flat(q), _flat(k), _flat(v), _flat(g.astype(_F32)), _beta_rows(beta),
        _flat(do), states, heads, chunk, run, scales)
    db = db.reshape(bsz, heads, seq).transpose(0, 2, 1)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype), db.astype(beta.dtype))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def scan(q, k, v, g, beta, *, chunk: int, run: int, unit_scales=None):
    """`ops/kda.kda_scan` for a shape `scan_plan` gives the kernels: q, k, v,
    g each `[B, S, H, K]` or merged `[B, S, H x K]`; o in v's form."""
    scales = None if unit_scales is None else tuple(float(x) for x in unit_scales)
    return _scan(q, k, v, g, beta, chunk, run, scales)
