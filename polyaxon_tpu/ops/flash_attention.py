"""Blockwise (flash) attention as Pallas TPU kernels, fwd + bwd.

The hot op of every transformer in the zoo. Design (pallas_guide.md):
- Online softmax over KV blocks: running max/denominator in VMEM scratch,
  O(S) memory instead of the O(S^2) score matrix.
- Three kernels, the FlashAttention-2 recipe: forward over (q-block,
  kv-steps), dq the same walk, dk/dv over (kv-block, q-steps); the two
  backward kernels recompute p from the saved logsumexp. The innermost
  grid dim runs sequentially on a TPU core, so scratch accumulators carry
  across it; outputs are written on its last step.
- Scores, softmax statistics and accumulators in f32 (bf16 softmax loses
  probability mass); the products hit the MXU via preferred_element_type,
  `p` and `ds` cast to the operands' type for the second product only.
- Off-TPU (CPU tests) the same kernels run with interpret=True.
- Each `pallas_call` has a `name=` (`flash_attention_fwd`, `_dq`, `_dkv`;
  with a window `flash_window_fwd`, `_dq`, `_dkv`): it becomes the stem of
  the custom call's HLO instruction, which is what a device trace calls the
  kernel's events (`%flash_attention_dq.7 = ...`); the benchmark's
  `flash_attn_roofline.train` and `flash_window_roofline.train` find them
  by it.

How the kernels tile and walk the score matrix:
- **The blocks are the kernel's own choice** (`choose_blocks`): one pure
  function of what a call can see (sequence, head width, GQA group, window,
  dtype, which kernel) returns `(block_q, block_kv)` per kernel. `block_q=`
  / `block_kv=` given to `flash_attention` are obeyed to the letter;
  `TransformerConfig.attention_block` (None by default) is such a
  `block_kv`.
- **One grid step serves a whole GQA group.** q, o, do are `[B*KV, group,
  S, D]`; a step holds one K and one V block and the `block_q` rows of
  EVERY query head that shares them, so a K/V byte fetched is used by
  `group x block_q` rows: the forward's and dq's intensity is `group x
  block_q` FLOP/B (bf16) with the diagonal waste of `block_q` alone.
- **A step visits only the blocks its rows can see**: the inner grid dim is
  as long as the widest span (causal edge, and with `window` the window's
  far edge: query i sees keys j with 0 <= i - j < window), the index maps
  start at the span's first block, and a step past the span's end is
  skipped AND keeps the previous step's block index, so Pallas issues no
  DMA for it.
- **A tile is masked only where an edge crosses it**: tiles wholly under
  the diagonal and wholly inside the window run a body without iota,
  compare and select. The softmax scale is folded into the exponent's
  argument (forward) or applied once to the accumulator (dq, dk), never as
  a pass over a score tile.
- **Two widths.** q and k share the score width (their last dim), v, o and
  do the value width (v's last dim); the two may differ (latent attention:
  scores over 192, values of 128). Nothing is padded: each operand's block
  is as wide as the operand, dq and dk accumulate at the score width, o and
  dv at the value width. Where the two are equal every kernel is what it
  was.
- **dk/dv works on transposed tiles** (`s^T = k q^T`, `[block_kv,
  block_q]`): both accumulating products are then plain `A @ B`, no tile is
  transposed, and logsumexp and delta come as lane-major rows (`[..., 1,
  block_q]`, 8x padding in HBM) instead of the columns the q-major kernels
  read (`[..., block_q, 1]`, 128x).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_LANES = 128  # f32 scratch lane width
# what a kernel may take of the core's VMEM (128 MiB on a v5e; the compiler's
# default scope is 16 MiB): twice what `choose_blocks` budgets, because its
# count of a step's temporaries is an estimate
_VMEM_LIMIT = 32 * 2**20
KERNELS = ("fwd", "dq", "dkv")
_HEADS_UNROLLED = 3


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ the walk
class _Walk:
    """How one kernel walks the score matrix of an `S x S` call in tiles of
    `block_q x block_kv`: q-major (forward, dq: a q block steps over kv
    blocks) or kv-major (dk/dv: a kv block steps over q blocks). Every
    method takes Python ints (the static counts) or traced ints (kernel
    bodies and index maps) alike."""

    def __init__(self, seq, block_q, block_kv, causal, window, kv_major=False):
        self.seq, self.block_q, self.block_kv = seq, block_q, block_kv
        self.causal, self.window, self.kv_major = causal, window, kv_major
        self.nq, self.nk = seq // block_q, seq // block_kv
        self.n_outer = self.nk if kv_major else self.nq
        self.steps = max(
            last - first + 1 for first, last in map(self.span, range(self.n_outer))
        )

    @classmethod
    def of(cls, kernel, seq, blocks, causal, window):
        """The walk of kernel `fwd`, `dq` or `dkv` at `blocks`."""
        return cls(seq, *blocks, causal, window, kv_major=kernel == "dkv")

    def span(self, outer, mx=max, mn=min):
        """(first, last) inner block the outer block `outer` can see."""
        bq, bkv = self.block_q, self.block_kv
        if self.kv_major:  # q blocks that see kv block `outer`
            first = (outer * bkv) // bq if self.causal else 0
            last = self.nq - 1
            if self.window is not None:
                last = mn((outer * bkv + bkv - 1 + self.window - 1) // bq, last)
            return first, last
        first = 0
        if self.window is not None:
            first = mx(outer * bq - (self.window - 1), 0) // bkv
        last = (outer * bq + bq - 1) // bkv if self.causal else self.nk - 1
        return first, last

    def at(self, outer, step, clamp=False):
        """Traced: (inner block of `step`, whether the step is live); with
        `clamp` the block a dead step keeps (the span's last)."""
        first, last = self.span(outer, jnp.maximum, jnp.minimum)
        inner = first + step
        if clamp:
            return jnp.minimum(inner, last)
        return inner, inner <= last

    def edge(self, iq, ik):
        """Whether the diagonal or the window's far edge crosses tile
        (iq, ik): only such a tile needs the mask."""
        bq, bkv = self.block_q, self.block_kv
        crossed = ik * bkv + bkv - 1 > iq * bq
        if self.window is not None:
            crossed = crossed | (iq * bq + bq - 1 - ik * bkv >= self.window)
        return crossed

    def counts(self) -> dict:
        """Static counts of one head's walk: grid steps, live steps, steps
        that mask, and executed over required pairs (required = the pairs
        a row may see)."""
        live = masked = 0
        for outer in range(self.n_outer):
            first, last = self.span(outer)
            live += last - first + 1
            if self.causal:
                masked += sum(
                    bool(self.edge(inner, outer) if self.kv_major else self.edge(outer, inner))
                    for inner in range(first, last + 1)
                )
        S, w = self.seq, self.window
        if not self.causal:
            required = S * S
        elif w is None:
            required = S * (S + 1) // 2
        else:
            required = w * (w + 1) // 2 + (S - w) * w
        return {
            "grid_steps": self.n_outer * self.steps,
            "live_steps": live,
            "mask_steps": masked,
            "executed_over_required": round(
                live * self.block_q * self.block_kv / required, 4
            ),
        }


def _mask(s, row0, col0, window, transposed=False):
    """Keep score (row, col) where 0 <= row - col (< window); `s` is the
    tile whose first element is (row0, col0), rows along dim 0 unless
    `transposed`."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 if transposed else 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0 if transposed else 1)
    back = (row0 - col0) + (rows - cols)  # how far behind its query a key lies
    keep = back >= 0
    if window is not None:
        keep = keep & (back < window)
    return jnp.where(keep, s, NEG_INF)


def _on_tiles(walk, live, edge, tiles):
    """Run `tiles(masked)` for a live step: the masking body where an edge
    crosses the tile, the bare one elsewhere."""
    if not walk.causal:  # every step is live and nothing is masked
        tiles(False)
        return
    pl.when(live & edge)(functools.partial(tiles, True))
    pl.when(live & jnp.logical_not(edge))(functools.partial(tiles, False))


def _each_head(group, head):
    """`head(g)` for each query head of the group a step holds. Up to
    `_HEADS_UNROLLED` heads stand unrolled side by side, so that one head's
    softmax overlaps the next one's products; a larger group loops over
    such bodies. Every unrolled head is traced and lowered again at every
    set-up, 0.06 s a body on the chip's host: with groups of 6 and 9 (PR 30's
    chip runs) all heads unrolled train 1 % faster than three and cost 5.5 s
    of set-up where three cost 1.3; one head a body costs none and trains
    3.7 % slower (dk/dv 4.7 ms a call against 3.2 and 2.7)."""
    unroll = max(u for u in range(1, _HEADS_UNROLLED + 1) if group % u == 0)

    def some(i, carry):
        for j in range(unroll):
            head(i * unroll + j)
        return carry

    if unroll == group:
        some(0, None)
    else:
        jax.lax.fori_loop(0, group // unroll, some, None)


_NT = (((1,), (1,)), ((), ()))  # A @ B^T
_NN = (((1,), (0,)), ((), ()))  # A @ B


def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, dimension_numbers=dims, preferred_element_type=jnp.float32
    )


def _one_lowering(fn):
    """jit with everything but the arrays static: the layers of a model (and
    remat's second forward) that call a kernel at one shape then share one
    trace of its body and ONE lowering to Mosaic, not one each; a step of
    24 layers spent 9 s of every set-up lowering 96 copies."""
    jitted = jax.jit(
        fn, static_argnames=("causal", "scale", "blocks", "window", "interpret")
    )

    @functools.wraps(fn)
    def call(*args, **kwargs):
        # off-TPU the same kernels run interpreted; part of the trace's key
        return jitted(*args, **kwargs, interpret=_interpret())

    return call


# ------------------------------------------------------------------ forward
def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, walk,
):
    iq, step = pl.program_id(1), pl.program_id(2)
    ik, live = walk.at(iq, step)
    group = q_ref.shape[1]

    @pl.when(step == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tiles(masked):
        k, v = k_ref[0], v_ref[0]

        def head(g):
            s = _dot(q_ref[0, g], k, _NT)  # [bq, bkv], unscaled
            if masked:
                s = _mask(s, iq * walk.block_q, ik * walk.block_kv, walk.window)
            m_prev = m_scr[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp((m_prev - m_new) * scale)
            p = jnp.exp((s - m_new) * scale)
            l_scr[g, :, :1] = alpha * l_scr[g, :, :1] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + _dot(p.astype(v.dtype), v, _NN)
            m_scr[g, :, :1] = m_new

        _each_head(group, head)

    _on_tiles(walk, live, walk.edge(iq, ik), tiles)

    @pl.when(step == walk.steps - 1)
    def _():
        l = jnp.maximum(l_scr[:, :, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :, :1] * scale + jnp.log(l)


def _names(window):
    stem = "flash_attention_" if window is None else "flash_window_"
    return {k: stem + k for k in KERNELS}


def _q_major_specs(walk, group, D, Dv):
    """Block specs of the q-major kernels: (rows of every head of the
    group at the score width and at the value width, the K and the V block
    of a step, the rows' statistics)."""
    bq, bkv = walk.block_q, walk.block_kv

    def rows(width):
        return pl.BlockSpec((1, group, bq, width), lambda b, i, j: (b, 0, i, 0))

    def cols(width):
        # a dead step keeps the block of the last live one: no DMA is issued
        return pl.BlockSpec(
            (1, bkv, width), lambda b, i, j: (b, walk.at(i, j, clamp=True), 0)
        )

    # statistics ride a trailing singleton dim: Mosaic requires the last
    # two block dims divisible by (8, 128) OR equal to the array's
    stat = pl.BlockSpec((1, group, bq, 1), lambda b, i, j: (b, 0, i, 0))
    return rows(D), rows(Dv), cols(D), cols(Dv), stat


@_one_lowering
def _fwd(q, k, v, causal, scale, blocks, window=None, *, interpret):
    """q: [B*KV, group, S, D]; k: [B*KV, S, D]; v: [B*KV, S, Dv] -> (o
    [B*KV, group, S, Dv], lse [B*KV, group, S, 1] f32)."""
    from jax.experimental.pallas import tpu as pltpu

    BKV, group, S, D = q.shape
    Dv = v.shape[-1]
    walk = _Walk.of("fwd", S, blocks, causal, window)
    q_rows, o_rows, k_cols, v_cols, stat = _q_major_specs(walk, group, D, Dv)
    bq = walk.block_q
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, walk=walk),
        grid=(BKV, walk.nq, walk.steps),
        in_specs=[q_rows, k_cols, v_cols],
        out_specs=[o_rows, stat],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, group, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((BKV, group, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, bq, _LANES), jnp.float32),
            pltpu.VMEM((group, bq, _LANES), jnp.float32),
            pltpu.VMEM((group, bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=_names(window)["fwd"],
    )(q, k, v)


# ------------------------------------------------------------------ backward
def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, scale, walk,
):
    iq, step = pl.program_id(1), pl.program_id(2)
    ik, live = walk.at(iq, step)
    group = q_ref.shape[1]

    @pl.when(step == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tiles(masked):
        k, v = k_ref[0], v_ref[0]

        def head(g):
            s = _dot(q_ref[0, g], k, _NT)
            if masked:
                s = _mask(s, iq * walk.block_q, ik * walk.block_kv, walk.window)
            p = jnp.exp(s * scale - lse_ref[0, g])  # lse [bq, 1] broadcasts over kv
            dp = _dot(do_ref[0, g], v, _NT)
            ds = p * (dp - delta_ref[0, g])  # the scale waits for the accumulator
            dq_scr[g] += _dot(ds.astype(k.dtype), k, _NN)

        _each_head(group, head)

    _on_tiles(walk, live, walk.edge(iq, ik), tiles)

    @pl.when(step == walk.steps - 1)
    def _():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


@_one_lowering
def _dq(q, k, v, do, lse, delta, causal, scale, blocks, window=None, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    BKV, group, S, D = q.shape
    walk = _Walk.of("dq", S, blocks, causal, window)
    q_rows, o_rows, k_cols, v_cols, stat = _q_major_specs(walk, group, D, v.shape[-1])
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, walk=walk),
        grid=(BKV, walk.nq, walk.steps),
        in_specs=[q_rows, k_cols, v_cols, o_rows, stat, stat],
        out_specs=q_rows,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((group, walk.block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=_names(window)["dq"],
    )(q, k, v, do, lse, delta)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, walk,
):
    # the K/V block stays; a step brings the q rows of EVERY query head
    # sharing this kv head (GQA) and the scratch accumulates dk/dv over
    # heads and steps. Tiles are transposed: [block_kv, block_q].
    ik, step = pl.program_id(1), pl.program_id(2)
    iq, live = walk.at(ik, step)
    group = q_ref.shape[1]

    @pl.when(step == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tiles(masked):
        k, v = k_ref[0], v_ref[0]

        def head(g):
            q, do = q_ref[0, g], do_ref[0, g]
            st = _dot(k, q, _NT)  # s^T [bkv, bq]
            if masked:
                st = _mask(st, iq * walk.block_q, ik * walk.block_kv, walk.window,
                           transposed=True)
            pt = jnp.exp(st * scale - lse_ref[0, g, 0])  # lse row [1, bq]
            dv_scr[:] += _dot(pt.astype(do.dtype), do, _NN)
            dpt = _dot(v, do, _NT)
            dst = pt * (dpt - delta_ref[0, g, 0])
            dk_scr[:] += _dot(dst.astype(q.dtype), q, _NN)

        _each_head(group, head)

    _on_tiles(walk, live, walk.edge(iq, ik), tiles)

    @pl.when(step == walk.steps - 1)
    def _():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@_one_lowering
def _dkv(q, k, v, do, lse, delta, causal, scale, blocks, window=None, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    BKV, group, S, D = q.shape
    Dv = v.shape[-1]
    walk = _Walk.of("dkv", S, blocks, causal, window)
    bq, bkv = walk.block_q, walk.block_kv

    def q_at(b, j, t):
        return (b, 0, walk.at(j, t, clamp=True), 0)

    def rows(width):
        return pl.BlockSpec((1, group, bq, width), q_at)

    def cols(width):
        return pl.BlockSpec((1, bkv, width), lambda b, j, t: (b, j, 0))

    # a q block's statistics as one lane-major row: the last two block dims
    # (1, bq) ARE the array's, whatever bq is
    stat = pl.BlockSpec((1, group, 1, 1, bq), lambda b, j, t: (*q_at(b, j, t), 0))
    as_rows = lambda x: x.reshape(BKV, group, walk.nq, 1, bq)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, walk=walk),
        grid=(BKV, walk.nk, walk.steps),
        in_specs=[rows(D), cols(D), cols(Dv), rows(Dv), stat, stat],
        out_specs=[cols(D), cols(Dv)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, D), jnp.float32),
            pltpu.VMEM((bkv, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=_names(window)["dkv"],
    )(q, k, v, do, as_rows(lse), as_rows(delta))


def _bwd_impl(q, k, v, lse, do, delta, causal, scale, blocks, window=None):
    """The dq and dk/dv kernels (FA-2 recipe). `delta` is the per-row
    correction term: rowsum(do*o) for the plain vjp; callers that also
    have an lse cotangent fold it in as rowsum(do*o) - dlse, which is all
    d lse/d s = p costs (see _flash_lse_bwd). `blocks` maps each kernel to
    its (block_q, block_kv)."""
    dq = _dq(q, k, v, do, lse, delta, causal, scale, blocks.dq, window)
    dk, dv = _dkv(q, k, v, do, lse, delta, causal, scale, blocks.dkv, window)
    return dq, dk, dv


def _delta(do, o):
    # [B*KV, group, S, 1]: same trailing-singleton layout as lse
    return jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )


# ------------------------------------------------------------------ custom vjp
@dataclasses.dataclass(frozen=True)
class _Blocks:
    """(block_q, block_kv) of each kernel: a static, hashable argument."""

    fwd: tuple[int, int]
    dq: tuple[int, int]
    dkv: tuple[int, int]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, blocks, window=None):
    return _fwd(q, k, v, causal, scale, blocks.fwd, window)[0]


def _flash_fwd(q, k, v, causal, scale, blocks, window):
    o, lse = _fwd(q, k, v, causal, scale, blocks.fwd, window)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, blocks, window, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, lse, do, _delta(do, o), causal, scale, blocks, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


# lse-returning variant: ring attention merges per-hop outputs with the
# online-softmax rule, which needs each hop's logsumexp — and its backward
# needs the lse cotangent folded into delta (d lse/d s = p, so the dlse
# term rides the same p·(dp − delta) expression the kernels already
# compute; only `delta` changes, not the kernels).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_lse(q, k, v, causal, scale, blocks):
    return _fwd(q, k, v, causal, scale, blocks.fwd)


def _flash_lse_fwd(q, k, v, causal, scale, blocks):
    # symbolic_zeros=True wraps each primal in CustomVJPPrimal
    q, k, v = q.value, k.value, v.value
    o, lse = _fwd(q, k, v, causal, scale, blocks.fwd)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, scale, blocks, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    if isinstance(do, jax.custom_derivatives.SymbolicZero):
        do = jnp.zeros(do.shape, do.dtype)
    delta = _delta(do, o)
    # ring callers differentiate only through `o`, so dlse arrives as a
    # SymbolicZero and the subtraction (and its zeros buffer) is skipped
    if not isinstance(dlse, jax.custom_derivatives.SymbolicZero):
        delta = delta - dlse.astype(jnp.float32)
    return _bwd_impl(q, k, v, lse, do, delta, causal, scale, blocks)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd, symbolic_zeros=True)


# ------------------------------------------------------------------ the rule
# The chip the rule is derived for (TPU v5e, one TensorCore): HBM bandwidth,
# the VMEM a kernel may use, and what a step costs. Another chip changes
# these numbers, not the rule.
_HBM_BYTES_PER_SECOND = 819e9  # under 197 TFLOP/s in bf16: a ridge of 240 FLOP/B
_VMEM_BUDGET = _VMEM_LIMIT // 2
_PREFERRED = 1024  # no block wider: a [1024, 1024] f32 tile is 4 MiB
# What a step of each kernel costs, fitted to a sweep of the three kernels
# alone over 12 to 19 block pairs at four call shapes on the chip (PR 30;
# rms error 6-11 %): seconds a score executed (at the MXU's rate or close:
# 2.6, 3.9 and 5.2 ps are the peak's for two, three and four products at a
# head of 128), a resident row a live step (the forward's running max,
# denominator and accumulator rescale), a streamed row a live step, a
# score of a tile that masks, and a grid step.
_COSTS = {
    "fwd": (3.0e-12, 1.7e-9, 0.5e-9, 0.7e-12, 0.2e-6),
    "dq": (3.4e-12, 0.6e-9, 0.4e-9, 1.6e-12, 0.3e-6),
    "dkv": (5.0e-12, 0.4e-9, 0.15e-9, 0.4e-12, 0.3e-6),
}


def _candidates(seq: int, fixed: int | None) -> list[int]:
    """Block sizes a sequence allows: its sublane-aligned divisors from 128
    (the floor) to `_PREFERRED`; where it has none there, the sequence whole
    if that is no wider, else nothing (the caller falls back). An explicit block is
    the only candidate (clamped to the sequence, as ever)."""
    if fixed is not None:
        return [min(int(fixed), seq)]
    aligned = [b for b in range(8, min(seq, _PREFERRED) + 1, 8) if seq % b == 0]
    wide = [b for b in aligned if b >= 128]
    if wide:
        # one candidate per octave: the largest divisor at or under 128,
        # 256, 512, 1024 (2,496 has 416 under 512 and 832 under 1,024)
        picks = {max(b for b in wide if b <= top)
                 for top in (128, 256, 512, 1024) if any(b <= top for b in wide)}
        return sorted(picks)
    return [seq] if seq <= _PREFERRED else []


def _vmem_bytes(kernel, bq, bkv, head_dim, group, itemsize, value_dim=None):
    """What a step keeps in VMEM: double-buffered operand blocks, the f32
    scratch, and the score-sized temporaries (s, p, and for the backward dp,
    ds, with p's and ds's casts; the unrolled heads of a group overlap, so
    some of the next head's live beside them). `head_dim` is the score
    width (q, k, dq, dk), `value_dim` the value width (v, o, do, dv; None =
    the same)."""
    dv = head_dim if value_dim is None else value_dim
    tile = bq * bkv * 4 * (1 + group / 6)
    rows, cols = group * bq, bkv  # times a width: an operand's block
    both = head_dim + dv
    if kernel == "fwd":  # q, o | k, v
        blocks = 2 * itemsize * (rows * both + cols * both) + 2 * group * bq * _LANES * 4
        scratch = group * bq * (2 * _LANES + dv) * 4
        return blocks + scratch + 3 * tile
    stats = 2 * 2 * group * bq * _LANES * 4
    if kernel == "dq":  # q, dq, do | k, v
        blocks = 2 * itemsize * (rows * (both + head_dim) + cols * both) + stats
        return blocks + rows * head_dim * 4 + 5 * tile
    blocks = 2 * itemsize * (rows * both + 2 * cols * both) + stats // 16  # q, do | k, v, dk, dv
    return blocks + cols * both * 4 + 5 * tile


def _estimate_seconds(kernel, walk, head_dim, group, itemsize, value_dim=None):
    """One kv head's walk by `_COSTS`: a live step costs its scores, its
    resident and its streamed rows (but not less than the streamed blocks
    take across HBM), a step that masks the mask, every grid step its
    fixed cost. A head narrower than the MXU's 128 columns costs a full
    one; a wider one in proportion (a kernel's products are half over the
    score width and half over the value width: their mean)."""
    bq, bkv = walk.block_q, walk.block_kv
    score, resident, streamed, mask, step = _COSTS[kernel]
    both = head_dim + (head_dim if value_dim is None else value_dim)
    score *= max(both / 2, 128) / 128
    rows, cols = (bkv, group * bq) if walk.kv_major else (group * bq, bkv)
    if walk.kv_major:  # q, do and the two statistics rows stream, K/V stay
        hbm = cols * (both * itemsize + 2 * 8 * 4)
    else:  # K and V stream, the rows stay
        hbm = cols * both * itemsize
    live = max(
        rows * cols * score + rows * resident + cols * streamed,
        hbm / _HBM_BYTES_PER_SECOND,
    )
    c = walk.counts()
    return (
        c["live_steps"] * live
        + c["mask_steps"] * rows * cols * mask
        + c["grid_steps"] * step
    )


def choose_blocks(
    kernel: str, seq: int, head_dim: int, group: int = 1,
    window: int | None = None, dtype=jnp.bfloat16, causal: bool = True,
    block_q: int | None = None, block_kv: int | None = None,
    value_dim: int | None = None,
) -> tuple[int, int]:
    """`(block_q, block_kv)` for one of the three kernels (`fwd`, `dq`,
    `dkv`), from the call's shape alone: no table of models, no switch.
    `head_dim` is the score width; `value_dim` the value width where it
    differs.

    The pair that minimises an estimate of the walk's time
    (`_estimate_seconds`, its constants measured on the chip) over the
    blocks the sequence allows (`_candidates`: sublane-aligned divisors, 128
    the floor, 1,024 the widest) that fit the VMEM budget (`_vmem_bytes`;
    tests/test_tpu_compile.py holds the choice against the chip's
    compiler). What the estimate weighs:
    - a K/V block fetched by the forward or dq kernel is used by `group x
      block_q` rows, `2 x group x block_q / itemsize` FLOP a byte (1.5x
      that for dq), so under the chip's ridge (240 FLOP/B) a step waits for
      HBM: the rows a step holds must clear it. dk/dv streams the q side
      and its intensity is `block_kv`'s;
    - every live step pays for its resident rows (the forward's running
      max, denominator and accumulator rescale: 1.7 ns a row) and every
      grid step a fixed 0.2-0.3 us, so few, wide steps: the forward wants
      kv blocks of 1,024, the backward kernels 512;
    - but the causal edge makes a walk execute about `1 + max(block_q,
      block_kv) / S` of the pairs it needs, and a window `(block_q +
      window) / window` rounded up to kv blocks, each pair at the MXU's
      rate: not wider than the waste repays (a window of 512 at 4,096
      gets 256 x 256 in the backward, 1.5x its own pairs, and 256 x 512 in
      the forward, 2x: there the steps saved are worth the pairs);
    - ties go to the smaller tile (less VMEM, a shorter compile).
    Heads of 64 pay a full MXU pass a score, so they get the blocks of 128.
    An explicit `block_q` / `block_kv` is obeyed to the letter and the
    other is chosen around it."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown flash kernel {kernel!r}")
    return _choose(kernel, seq, head_dim, group, _clip(window, seq),
                   jnp.dtype(dtype).itemsize, causal, block_q, block_kv,
                   None if value_dim == head_dim else value_dim)


def _clip(window, seq):
    """A window that covers the sequence is plain causal attention."""
    return None if window is None or window >= seq else int(window)


@functools.lru_cache(maxsize=None)
def _choose(kernel, seq, head_dim, group, window, itemsize, causal, block_q, block_kv,
            value_dim=None):
    best = None
    for bq in _candidates(seq, block_q):
        for bkv in _candidates(seq, block_kv):
            if seq % bq or seq % bkv:  # an explicit block that does not divide
                continue
            fits = _vmem_bytes(
                kernel, bq, bkv, head_dim, group, itemsize, value_dim
            ) <= _VMEM_BUDGET
            walk = _Walk.of(kernel, seq, (bq, bkv), causal, window)
            cost = _estimate_seconds(kernel, walk, head_dim, group, itemsize, value_dim)
            # a pair that does not fit is kept only while nothing fits (an
            # explicit block is obeyed whatever it needs)
            key = (not fits, cost, bq * bkv)
            if best is None or key < best[0]:
                best = (key, (bq, bkv))
    if best is None:
        raise ValueError(
            f"seq len {seq} not divisible by blocks {block_q}/{block_kv}"
        )
    return best[1]


def _all_blocks(seq, head_dim, group, window, dtype, causal, block_q, block_kv,
                value_dim=None):
    return _Blocks(*(
        choose_blocks(kernel, seq, head_dim, group, window, dtype, causal,
                      block_q, block_kv, value_dim)
        for kernel in KERNELS
    ))


def tile_report(
    seq: int, head_dim: int, group: int = 1, window: int | None = None,
    dtype=jnp.bfloat16, causal: bool = True, block_q: int | None = None,
    block_kv: int | None = None, value_dim: int | None = None,
) -> list[dict]:
    """What the three kernels of one call shape would run: per kernel its
    name, the shape, the blocks chosen and the walk's counts (grid steps,
    live steps, steps that mask, executed over required pairs), a head. A
    call whose value width differs from its score width (`head_dim`) says
    so under `value_dim`."""
    window = _clip(window, seq)
    blocks = _all_blocks(
        seq, head_dim, group, window, dtype, causal, block_q, block_kv, value_dim
    )
    out = []
    for kernel in KERNELS:
        walk = _Walk.of(kernel, seq, getattr(blocks, kernel), causal, window)
        out.append({
            "kernel": _names(window)[kernel], "seq": seq, "head_dim": head_dim,
            "group": group, "window": window, "causal": causal,
            "block_q": walk.block_q, "block_kv": walk.block_kv, **walk.counts(),
            **({} if value_dim in (None, head_dim) else {"value_dim": value_dim}),
        })
    return out


# ------------------------------------------------------------------ public api
def flash_shapes_ok(
    seq: int, block_q: int | None = None, block_kv: int | None = None,
    window: int | None = None, head_dim: int = 128, group: int = 1,
) -> bool:
    """True when `seq` satisfies the kernels' block layout with the blocks
    `choose_blocks` would run (or the explicit ones): each block divides
    the sequence (`choose_blocks` raises where none does) and is either the
    whole sequence or sublane-aligned (Mosaic: multiple of 8). A predicate for dispatch code. A window is any
    positive count of keys (it need not align to a block)."""
    if window is not None and window < 1:
        return False
    try:
        blocks = _all_blocks(
            seq, head_dim, group, window, jnp.bfloat16, True, block_q, block_kv
        )
    except ValueError:
        return False
    return all(
        b == seq or b % 8 == 0 for pair in dataclasses.astuple(blocks) for b in pair
    )


def _prepare(q, k, v, block_q, block_kv, window, causal):
    """Shared front of the two entry points: the group, the blocks of the
    three kernels, and the layouts the kernels take. q and k share the score
    width, v has the value width (the output's)."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    if H % KV:
        raise ValueError(f"query heads {H} not divisible by kv heads {KV}")
    if k.shape[3] != D:
        raise ValueError(f"q is {D} wide and k {k.shape[3]}: scores need one width")
    group = H // KV
    blocks = _all_blocks(
        S, D, group, window, q.dtype, causal, block_q, block_kv, v.shape[3]
    )

    def rows(x):  # [B,S,H,D] -> [B*KV, group, S, D]
        return x.transpose(0, 2, 1, 3).reshape(B * KV, group, S, x.shape[3])

    def cols(x):  # [B,S,KV,D] -> [B*KV, S, D]
        return x.transpose(0, 2, 1, 3).reshape(B * KV, S, x.shape[3])

    def back(o):  # [B*KV, group, S, Dv] -> [B,S,H,Dv]
        return o.reshape(B, H, S, o.shape[3]).transpose(0, 2, 1, 3)

    return blocks, rows, cols, back


def flash_attention_lse(
    q, k, v, *, causal=True, block_q=None, block_kv=None, sm_scale=None
):
    """flash_attention that also returns the logsumexp: (o [B,S,H,D],
    lse [B,H,S] f32). The lse is differentiable (its cotangent folds into
    the delta term of the shared backward kernels) — ring attention's
    cross-hop online-softmax merge depends on that."""
    B, S, H, D = q.shape
    blocks, rows, cols, back = _prepare(q, k, v, block_q, block_kv, None, causal)
    scale = sm_scale if sm_scale is not None else D ** -0.5
    o, lse = _flash_lse(rows(q), cols(k), cols(v), causal, scale, blocks)
    return back(o), lse.reshape(B, H, S)


def flash_attention(
    q, k, v, *, causal=True, block_q=None, block_kv=None, sm_scale=None,
    window=None,
):
    """q: [B, S, H, D]; k: [B, S, KV, D]; v: [B, S, KV, Dv] with KV dividing
    H. D is the score width (the default scale is D ** -0.5), Dv the value
    width, which is the output's; the two may differ.

    `window` (causal only): query i attends keys j with 0 <= i - j <
    window. A window that covers the whole sequence is plain causal
    attention and runs as such.

    `block_q` / `block_kv`: None lets each kernel choose (`choose_blocks`);
    a number is obeyed to the letter by all three.

    GQA is native: when KV < H the kernels hold the rows of a whole group
    of H/KV query heads beside one K/V block — the repeated K/V copies
    (`jnp.repeat` before the call) never exist in HBM, and a K/V byte
    fetched serves the whole group. The backward accumulates dk/dv across
    the group inside the kv-block scratch.
    Returns [B, S, H, Dv]."""
    S, D = q.shape[1], q.shape[3]
    if window is not None:
        if not causal:
            raise ValueError("a window needs causal attention")
        if window < 1:
            raise ValueError(f"window must be at least 1, got {window}")
        window = _clip(window, S)
    blocks, rows, cols, back = _prepare(q, k, v, block_q, block_kv, window, causal)
    scale = sm_scale if sm_scale is not None else D ** -0.5
    return back(_flash(rows(q), cols(k), cols(v), causal, scale, blocks, window))
