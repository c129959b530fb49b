"""Blockwise (flash) attention as Pallas TPU kernels, fwd + bwd.

The hot op of every transformer in the zoo. Design (pallas_guide.md):
- Online softmax over KV blocks: running max/denominator in VMEM scratch,
  O(S) memory instead of the O(S^2) score matrix.
- Grid (batch*heads, q-blocks, kv-blocks) — the innermost grid dim runs
  sequentially on a TPU core, so scratch accumulators carry across KV
  blocks; output is written on the last KV step.
- Causal runs skip fully-masked blocks via pl.when (half the FLOPs).
- Scores/accumulators in f32 (bf16 softmax loses probability mass); the
  two matmuls per block hit the MXU via preferred_element_type.
- Backward = two kernels: dq over (q-block, kv-steps), dk/dv over
  (kv-block, q-steps), each recomputing p from the saved logsumexp —
  the standard FlashAttention-2 recipe.
- Off-TPU (CPU tests) the same kernels run with interpret=True.
- Each `pallas_call` has a `name=` (`flash_attention_fwd`, `_dq`, `_dkv`):
  it becomes the stem of the custom call's HLO instruction, which is what a
  device trace calls the kernel's events (`%flash_attention_dq.7 = ...`);
  the benchmark's `flash_attn_roofline.train` finds them by it.
- `window` (sliding-window attention: query i sees keys j with
  0 <= i - j < window) runs the same three bodies over a SHORTER grid: a q
  block visits only the kv blocks its window touches (the index maps start
  at the window's first block), so blocks wholly behind the window are
  neither computed nor fetched; the block on the window's edge is masked.
  Those calls are named `flash_window_fwd`, `_dq`, `_dkv`. `window=None`
  builds the grids, index maps and names it always built.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_LANES = 128  # f32 scratch lane width


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _span_blocks(n_outer, block_outer, block_inner, n_inner, first, last):
    """How many inner blocks the widest outer block's span touches:
    `first(i)` / `last(i)` give the first and last inner ELEMENT outer block
    `i` may see (static Python ints)."""
    return max(
        min(n_inner - 1, last(i) // block_inner) - max(0, first(i)) // block_inner + 1
        for i in range(n_outer)
    )


def _kv_start(iq, block_q, block_kv, window):
    """First kv block the window of q block `iq` touches."""
    return jnp.maximum(iq * block_q - (window - 1), 0) // block_kv


def _q_start(ik, block_q, block_kv):
    """First q block that sees kv block `ik` (causal: rows >= cols)."""
    return (ik * block_kv) // block_q


def _mask(s, iq, ik, block_q, block_kv, window):
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0
    )
    cols = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1
    )
    keep = rows >= cols
    if window is not None:
        keep = keep & (rows - cols < window)
    return jnp.where(keep, s, NEG_INF)


# ------------------------------------------------------------------ forward
def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_kv, window=None,
):
    iq, step = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    ik = step if window is None else _kv_start(iq, block_q, block_kv, window) + step

    @pl.when(step == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = (
        ik * block_kv <= iq * block_q + block_q - 1 if causal else ik >= 0
    )

    @pl.when(live)
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bkv]
        if causal:
            s = _mask(s, iq, ik, block_q, block_kv, window)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:, :1] = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, :1] = m_new

    @pl.when(step == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _kv_walk(nq, nk, block_q, block_kv, window):
    """(kv steps a q block takes, kv block of step j of q block i)."""
    if window is None:
        return nk, lambda i, j: j
    steps = _span_blocks(
        nq, block_q, block_kv, nk,
        first=lambda i: i * block_q - (window - 1),
        last=lambda i: i * block_q + block_q - 1,
    )
    # past the causal edge the step is skipped; its fetch stays in range
    return steps, lambda i, j: jnp.minimum(
        _kv_start(i, block_q, block_kv, window) + j, nk - 1
    )


def _fwd(q, k, v, causal, scale, block_q, block_kv, group=1, window=None):
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    nq, nk = S // block_q, S // block_kv
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, window=window,
    )
    steps, kv_at = _kv_walk(nq, nk, block_q, block_kv, window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            # GQA: `group` query heads share one kv head — the kv operands
            # stay [B*KV, S, D] and the grid's head index maps down, so
            # repeated K/V never materialize in HBM
            pl.BlockSpec((1, block_kv, D), lambda b, i, j, g=group: (b // g, kv_at(i, j), 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j, g=group: (b // g, kv_at(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            # lse rides a trailing singleton dim: Mosaic requires the last
            # two block dims divisible by (8, 128) OR equal to the array's
            # — (block_q, 1) on a [BH, S, 1] array satisfies that without
            # the official kernel's 128x lane-broadcast duplication
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_fwd" if window is None else "flash_window_fwd",
    )(q, k, v)
    return o, lse


# ------------------------------------------------------------------ backward
def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, causal, block_q, block_kv, window=None,
):
    iq, step = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    ik = step if window is None else _kv_start(iq, block_q, block_kv, window) + step

    @pl.when(step == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = (
        ik * block_kv <= iq * block_q + block_q - 1 if causal else ik >= 0
    )

    @pl.when(live)
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _mask(s, iq, ik, block_q, block_kv, window)
        p = jnp.exp(s - lse_ref[0])  # lse block [bq, 1] broadcasts over kv
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, block_q, block_kv, nq_seq, window=None,
    nq_all=None,
):
    # grid dim 2 walks the q blocks of EVERY query head sharing this kv
    # head (GQA): step t = member * nq_seq + q-block; the scratch
    # accumulates dk/dv across all of them sequentially. With a window,
    # nq_seq counts only the q blocks that can see this kv block, from the
    # first that does.
    ik, it = pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(2)
    iq = it % nq_seq  # q-block index within the sequence
    if window is not None:
        iq = _q_start(ik, block_q, block_kv) + iq

    @pl.when(it == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = (
        iq * block_q + block_q - 1 >= ik * block_kv if causal else iq >= 0
    )
    if window is not None:
        # behind the window's far edge (and past the sequence's last block,
        # where the fetch was clamped) nothing of this kv block is seen
        live = (
            live
            & (iq < nq_all)
            & (iq * block_q - (window - 1) <= ik * block_kv + block_kv - 1)
        )

    @pl.when(live)
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _mask(s, iq, ik, block_q, block_kv, window)
        p = jnp.exp(s - lse_ref[0])  # [bq, bkv] via [bq, 1] lane broadcast
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(it == nt - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ------------------------------------------------------------------ custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_kv, group, window=None):
    o, _ = _fwd(q, k, v, causal, scale, block_q, block_kv, group, window)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_kv, group, window):
    o, lse = _fwd(q, k, v, causal, scale, block_q, block_kv, group, window)
    return o, (q, k, v, o, lse)


def _bwd_impl(q, k, v, o, lse, do, delta, causal, scale, block_q, block_kv, group,
              window=None):
    """Shared dq/dk/dv kernels (FA-2 recipe). `delta` is the per-row
    correction term — rowsum(do*o) for the plain vjp; callers that also
    have an lse cotangent fold it in as rowsum(do*o) - dlse, which is all
    d lse/d s = p costs (see _flash_lse_bwd)."""
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    nq, nk = S // block_q, S // block_kv

    common = dict(scale=scale, causal=causal, block_q=block_q, block_kv=block_kv)
    if window is not None:
        common["window"] = window
    steps, kv_at = _kv_walk(nq, nk, block_q, block_kv, window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(BH, nq, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j, g=group: (b // g, kv_at(i, j), 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j, g=group: (b // g, kv_at(i, j), 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_attention_dq" if window is None else "flash_window_dq",
    )(q, k, v, do, lse, delta)

    if window is None:
        nqw = nq

        def q_at(j, t):  # (member of the group, q block) of step t
            return t // nq, t % nq
    else:
        # a kv block is seen by the q blocks from its own rows to window - 1
        # rows past its last: walk those only
        nqw = _span_blocks(
            nk, block_kv, block_q, nq,
            first=lambda j: j * block_kv,
            last=lambda j: j * block_kv + block_kv - 1 + window - 1,
        )
        common["nq_all"] = nq

        def q_at(j, t):
            return t // nqw, jnp.minimum(
                _q_start(j, block_q, block_kv) + t % nqw, nq - 1
            )

    def q_spec(width):
        def index(b, j, t, g=group):
            member, i = q_at(j, t)
            return (b * g + member, i, 0)

        return pl.BlockSpec((1, block_q, width), index)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq_seq=nqw, **common),
        grid=(BH // group, nk, nqw * group),
        in_specs=[
            q_spec(D),
            pl.BlockSpec((1, block_kv, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, j, t: (b, j, 0)),
            q_spec(D),
            q_spec(1),
            q_spec(1),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_dkv" if window is None else "flash_window_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _flash_bwd(causal, scale, block_q, block_kv, group, window, res, do):
    q, k, v, o, lse = res
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [BH, S, 1] — same trailing-singleton layout as lse
    return _bwd_impl(
        q, k, v, o, lse, do, delta, causal, scale, block_q, block_kv, group,
        window,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


# lse-returning variant: ring attention merges per-hop outputs with the
# online-softmax rule, which needs each hop's logsumexp — and its backward
# needs the lse cotangent folded into delta (d lse/d s = p, so the dlse
# term rides the same p·(dp − delta) expression the kernels already
# compute; only `delta` changes, not the kernels).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, block_kv, group):
    return _fwd(q, k, v, causal, scale, block_q, block_kv, group)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_kv, group):
    # symbolic_zeros=True wraps each primal in CustomVJPPrimal
    q, k, v = q.value, k.value, v.value
    o, lse = _fwd(q, k, v, causal, scale, block_q, block_kv, group)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, scale, block_q, block_kv, group, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    if isinstance(do, jax.custom_derivatives.SymbolicZero):
        do = jnp.zeros(do.shape, do.dtype)
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    # ring callers differentiate only through `o`, so dlse arrives as a
    # SymbolicZero and the subtraction (and its zeros buffer) is skipped
    if not isinstance(dlse, jax.custom_derivatives.SymbolicZero):
        delta = delta - dlse.astype(jnp.float32)
    return _bwd_impl(
        q, k, v, o, lse, do, delta, causal, scale, block_q, block_kv, group
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd, symbolic_zeros=True)


# ------------------------------------------------------------------ public api
def flash_shapes_ok(
    seq: int, block_q: int = 128, block_kv: int = 128, window: int | None = None
) -> bool:
    """True when `seq` satisfies the kernel's block layout (the same
    checks flash_attention enforces, as a predicate for dispatch code):
    seq divides into both (clamped) blocks, and each block is either the
    whole sequence or sublane-aligned (Mosaic: multiple of 8). A window is
    any positive count of keys (it need not align to a block)."""
    if window is not None and window < 1:
        return False
    bq, bkv = min(block_q, seq), min(block_kv, seq)
    if seq % bq or seq % bkv:
        return False
    return all(b == seq or b % 8 == 0 for b in (bq, bkv))


def flash_attention_lse(
    q, k, v, *, causal=True, block_q=128, block_kv=128, sm_scale=None
):
    """flash_attention that also returns the logsumexp: (o [B,S,H,D],
    lse [B,H,S] f32). The lse is differentiable (its cotangent folds into
    the delta term of the shared backward kernels) — ring attention's
    cross-hop online-softmax merge depends on that."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    if H % KV:
        raise ValueError(f"query heads {H} not divisible by kv heads {KV}")
    group = H // KV
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    if S % block_q or S % block_kv:
        raise ValueError(f"seq len {S} not divisible by blocks {block_q}/{block_kv}")
    scale = sm_scale if sm_scale is not None else D ** -0.5

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, S, D)

    o, lse = _flash_lse(
        to_bh(q), to_bh(k), to_bh(v), causal, scale, block_q, block_kv, group
    )
    return (
        o.reshape(B, H, S, D).transpose(0, 2, 1, 3),
        lse.reshape(B, H, S),
    )


def flash_attention(
    q, k, v, *, causal=True, block_q=128, block_kv=128, sm_scale=None,
    window=None,
):
    """q: [B, S, H, D]; k/v: [B, S, KV, D] with KV dividing H.

    `window` (causal only): query i attends keys j with 0 <= i - j <
    window. A window that covers the whole sequence is plain causal
    attention and runs as such.

    GQA is native: when KV < H the kernel maps each group of H/KV query
    heads onto one kv head through the grid index maps — the repeated K/V
    copies (`jnp.repeat` before the call) never exist in HBM, which at
    llama ratios (H/KV = 4) cuts the kernel's K/V read traffic 4x. The
    backward accumulates dk/dv across the group inside the kv-block
    scratch (one extra grid dim, still race-free sequential steps).
    Returns [B, S, H, D]."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    if H % KV:
        raise ValueError(f"query heads {H} not divisible by kv heads {KV}")
    group = H // KV
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    if S % block_q or S % block_kv:
        raise ValueError(f"seq len {S} not divisible by blocks {block_q}/{block_kv}")
    scale = sm_scale if sm_scale is not None else D ** -0.5
    if window is not None:
        if not causal:
            raise ValueError("a window needs causal attention")
        if window < 1:
            raise ValueError(f"window must be at least 1, got {window}")
        window = None if window >= S else int(window)

    def to_bh(x):  # [B,S,h,D] -> [B*h, S, D]
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, S, D)

    o = _flash(
        to_bh(q), to_bh(k), to_bh(v), causal, scale, block_q, block_kv, group,
        window,
    )
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)
