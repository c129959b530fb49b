"""The gated delta rule of a Kimi-Delta-Attention layer (arXiv:2510.26692) in
its chunked form. `kda_scan` runs it as the Pallas kernels of `kda_fused.py`
where the shape allows (`kda_fused.scan_plan`: key = value width a multiple
of 128, a chunk that is a multiple of `SUB` and divides a tile of 128
positions, a sequence of whole tiles and runs, float32 or bf16) and in
`jax.numpy`, differentiable by
autodiff, everywhere else (`_scan_xla`, which the kernels are checked
against). The algebra and the numbers below are both paths'.

Per head, with a state `S` in R^(K x V), `S_0 = 0`, a log-decay `g_t <= 0` a
KEY CHANNEL and a step size `beta_t` in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T        o_t = S_t^T q_t

The sequence is cut into chunks of `chunk` positions. With `G` the running
sum of `g` inside a chunk (`G_C` the sum over the whole chunk), `S` the state
the chunk starts from, and the pseudo-values `u_t = beta_t (v_t - S_{t-1}^T
(k_t * exp g_t))`, the recurrence unrolls to `S_t = Diag(exp G_t) S + sum_{j<=t}
Diag(exp(G_t - G_j)) k_j u_j^T`, so that

    A_ij = beta_i <k_i * exp(G_i - G_j), k_j>   (j < i)        T = (I + A)^-1 Diag(beta)
    W = T (K * exp G)        U = T V        U' = U - W S       (the chunk's pseudo-values)
    O  = (Q * exp G) S + tril(<q_i * exp(G_i - G_j), k_j>) U'
    S' = Diag(exp G_C) S + (K * exp(G_C - G))^T U'

**Numbers.** Decays, sums, `beta`, the triangular inverse and the carried
state are float32 whatever `q` is; the products take operands of `q`'s type
(bf16 under `precision: mixed`) and accumulate in float32. A per-channel
in-chunk decay `exp(G_i - G_j)` is never built as a `[chunk, chunk, K]`
array: a chunk is cut into sub-blocks of `SUB` = 16 positions, and for a
row `i` of sub-block `I`, whose first row of `G` is `r_I`,

    exp(G_i - G_j) = exp(G_i - r_I) * exp(r_I - G_j)        j <= i

The first factor's exponent is <= 0. The second's is <= 0 for every `j`
before the sub-block, and inside the sub-block, against that sub-block's own
reference row, at most `15 |g|` (75 at the gate's bound of -5: under
float32's 88; a model whose gate is not bounded must not run this scan);
for the positions after the sub-block the factor is an exact 0. So both `A`
and the q-k scores are plain products over the key channels: a sub-block's
rows against the keys scaled as that sub-block meets them. A decay that
underflows is an exact zero, forward and backward: no exponent is ever
positive where its partner could be infinite, and what a mask removes is
finite.

**The inverse.** `(I + A)` is unit lower triangular: a forward substitution
in float32 (`solve_triangular` here; in the kernels the sub-blocks by
substitution and their merges by block products at `HIGHEST`), with a
hand-written backward of two float32 products at `Precision.HIGHEST`
(`-X^T dX X^T`) in place of the substitution's own transpose.

**Memory, on the `jax.numpy` path** (the kernels keep a chunk's values in
VMEM and one state a run in HBM: `kda_fused.py`). The largest intermediate
is a head block's keys scaled for each of the sub-blocks that meet them
(`[B, S, heads, chunk / 16, K]`: four times a head block's keys at chunk 64,
in `q`'s type); the heads are walked in
blocks (`lax.map`, each block's body a `jax.checkpoint`, as `ops/ssd.py`
walks its own) so that it stays under `_BLOCK_BYTES`. The walk over the chunks that
carries the state takes every head at once (its steps are latency, not
bytes) in runs of `_RUN` chunks, a checkpoint a run, so that the backward
keeps a state a run and not a state a chunk.

A sequence that is no multiple of the chunk is refused, not padded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kda_fused

SUB = kda_fused.SUB  # positions of a sub-block
_BLOCK_BYTES = 128 * 1024 * 1024  # one head block's largest intermediate
_RUN = 16  # chunks the carried walk takes under one checkpoint
_HI = jax.lax.Precision.HIGHEST


def heads_per_step(batch: int, seq: int, chunk: int, heads: int, key_dim: int,
                   itemsize: int = 2) -> int:
    """The largest divisor of the heads whose keys, scaled for each sub-block
    that meets them (`batch x seq x (chunk / SUB) x key_dim` of the
    activations' type a head), stay under `_BLOCK_BYTES`."""
    per_head = batch * seq * (chunk // SUB) * key_dim * itemsize
    fit = max(1, _BLOCK_BYTES // per_head)
    return max(d for d in range(1, heads + 1) if heads % d == 0 and d <= fit)


def largest_intermediate_bytes(batch: int, seq: int, chunk: int, heads: int,
                               key_dim: int, itemsize: int = 2) -> int:
    """Bytes of the scan's largest intermediate for this shape: one head
    block's keys, scaled for each sub-block that meets them."""
    hb = heads_per_step(batch, seq, chunk, heads, key_dim, itemsize)
    return hb * batch * seq * (chunk // SUB) * key_dim * itemsize


@jax.custom_vjp
def _inverse_unit_lower(a):
    """(I + a)^-1 for strictly lower triangular `a` [..., n, n] (float32): a
    forward substitution (`solve_triangular`: stable whatever the keys'
    correlations make of `a`, where a Neumann product of its powers cancels
    badly), and a backward that is two products with the inverse itself."""
    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    return jax.scipy.linalg.solve_triangular(eye + a, eye, lower=True, unit_diagonal=True)


def _inverse_fwd(a):
    x = _inverse_unit_lower(a)
    return x, x


def _inverse_bwd(x, dx):
    # d (I + a)^-1 = -X da X: the cotangent of `a` is -X^T dX X^T (the caller's
    # mask keeps its strict lower triangle)
    xt = jnp.swapaxes(x, -1, -2)
    return (-jnp.matmul(jnp.matmul(xt, dx, precision=_HI), xt, precision=_HI),)


_inverse_unit_lower.defvjp(_inverse_fwd, _inverse_bwd)


def _chunk_operands(q, k, v, g, beta):
    """One block of heads. q, k [B, nc, C, h, K]; v [B, nc, C, h, V]; g
    [B, nc, C, h, K] float32 (<= 0); beta [B, nc, C, h] float32 ->
    (w [B, nc, h, C, K], u [B, nc, h, C, V], scores [B, nc, h, C, C],
    q_in [B, nc, h, C, K], k_out [B, nc, h, C, K]) in q's type and
    whole [B, nc, h, K] float32: what the walk over the chunks needs."""
    dtype, f32 = q.dtype, jnp.float32
    c = q.shape[2]
    ns = c // SUB
    heads_first = lambda x: jnp.moveaxis(x, 3, 2)  # noqa: E731 - [B, nc, h, C, ...]
    q, k, v, g = (heads_first(x) for x in (q, k, v, g))
    beta = jnp.moveaxis(beta, 3, 2)  # [B, nc, h, C]
    run = jnp.cumsum(g, axis=3)  # G: the running sum inside the chunk, <= 0
    sub = lambda x: x.reshape(*x.shape[:3], ns, SUB, x.shape[-1])  # noqa: E731
    gs, qs, ks = sub(run), sub(q.astype(f32)), sub(k.astype(f32))
    first = gs[..., :1, :]  # r_I: the sub-block's first row of G  [.., ns, 1, K]
    rows = jnp.exp(gs - first)  # exp(G_i - r_I) <= 1
    kp, qp = (ks * rows).astype(dtype), (qs * rows).astype(dtype)
    # the keys as sub-block I's rows meet them: k_j * exp(r_I - G_j) for j up
    # to the sub-block's end (<= 0 before it, at most 15 |g| inside it), an
    # exact 0 for the positions after it  [.., I, C, K]
    upto = (jnp.arange(c)[None, :] < SUB * (jnp.arange(ns)[:, None] + 1))[..., None]
    facing = jnp.exp(jnp.where(upto, first - run[..., None, :, :], -jnp.inf))
    k_facing = (k.astype(f32)[..., None, :, :] * facing).astype(dtype)

    def products(rows_op):
        """<rows_i * exp(G_i - G_j), k_j> for every i of the chunk and every
        j up to the end of i's sub-block (inside it both triangles, for the
        caller to mask), 0 after -> [.., C, C] float32."""
        out = jnp.einsum("...Iik,...Ijk->...Iij", rows_op, k_facing, preferred_element_type=f32)
        return out.reshape(*out.shape[:3], c, c)

    lower = jnp.tril(jnp.ones((c, c), bool))
    a = jnp.where(lower & ~jnp.eye(c, dtype=bool), products(kp), 0.0) * beta[..., None]
    scores = jnp.where(lower, products(qp), 0.0).astype(dtype)
    t = (_inverse_unit_lower(a) * beta[..., None, :]).astype(dtype)  # (I + A)^-1 Diag(beta)
    decayed = jnp.exp(run)
    k_in = (k.astype(f32) * decayed).astype(dtype)  # K * exp G
    w = jnp.einsum("...ij,...jk->...ik", t, k_in, preferred_element_type=f32).astype(dtype)
    u = jnp.einsum("...ij,...jv->...iv", t, v, preferred_element_type=f32).astype(dtype)
    q_in = (q.astype(f32) * decayed).astype(dtype)  # Q * exp G
    k_out = (k.astype(f32) * jnp.exp(run[..., -1:, :] - run)).astype(dtype)  # K * exp(G_C - G)
    return w, u, scores, q_in, k_out, jnp.exp(run[..., -1, :])


@functools.partial(jax.checkpoint, static_argnums=(1,))
def l2_unit(x, scale: float):
    """x / sqrt(sum x^2 + eps) * scale over the last axis, in x's type: how a
    KDA layer normalises a head's queries and keys. Under a checkpoint: the
    backward keeps x and builds the float32 values again."""
    x32 = x.astype(jnp.float32)
    unit = x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + kda_fused.L2_EPS)
    return (unit * scale).astype(x.dtype)


def kda_scan(q, k, v, g, beta, *, chunk: int = 64, block_heads: int | None = None,
             unit_scales: tuple[float, float] | None = None):
    """q, k [B, S, H, K]; v [B, S, H, V]; g [B, S, H, K] (log-decay, in
    [-88 / SUB, 0]); beta [B, S, H]. Returns o [B, S, H, V] in q's type.
    Each of q, k, v, g may also come with heads and width merged, `[B, S, H x
    K]`, as a projection or a conv hands it over (on the chip that is another
    tiling than `[B, S, H, K]`, and the kernels read the merged one: what
    comes merged is not copied); o comes back as v came. With `unit_scales`
    = (of q, of k) a head's queries and keys arrive as the convs leave them
    and are normalised here (`l2_unit`; the kernels do it to the rows they
    hold, so q and k too can come merged). The shape decides
    what runs (`kda_fused.scan_plan`): the Pallas kernels, or the `jax.numpy`
    form below, of which `block_heads` overrides how many heads are taken at
    a time."""
    bsz, seq, heads = beta.shape
    merged = v.ndim == 3
    key = q.shape[-1] // (heads if q.ndim == 3 else 1)
    val = v.shape[-1] // (heads if merged else 1)
    if seq % chunk:
        raise ValueError(
            f"the delta-rule scan works on whole chunks: a sequence of {seq} "
            f"positions is no multiple of the chunk {chunk} (kda_chunk_size); "
            "pad the batch to a multiple of it or choose a chunk that divides it"
        )
    if chunk % SUB:
        raise ValueError(f"the chunk {chunk} is no multiple of the sub-block {SUB}")
    plan = kda_fused.scan_plan(bsz, seq, chunk, heads, key, val, q.dtype)
    with jax.named_scope("kda"):
        if plan["path"] == "pallas":
            o = kda_fused.scan(q, k, v, g, beta, chunk=chunk, run=plan["run"],
                               unit_scales=unit_scales)
        else:
            q, k, v, g = (x.reshape(bsz, seq, heads, -1) for x in (q, k, v, g))
            if unit_scales is not None:
                q, k = l2_unit(q, float(unit_scales[0])), l2_unit(k, float(unit_scales[1]))
            o = _scan_xla(q, k, v, g, beta, chunk=chunk, block_heads=block_heads)
        return o.reshape(bsz, seq, heads * val) if merged else o.reshape(bsz, seq, heads, val)


def _scan_xla(q, k, v, g, beta, *, chunk: int = 64, block_heads: int | None = None):
    """The scan in `jax.numpy`, differentiable by autodiff: every shape the
    kernels refuse, and what they are checked against."""
    bsz, seq, heads, key = q.shape
    val = v.shape[-1]
    hb = block_heads or heads_per_step(bsz, seq, chunk, heads, key, q.dtype.itemsize)
    if heads % hb:
        raise ValueError(f"block_heads {hb} does not divide {heads} heads")
    nc, nb = seq // chunk, heads // hb
    dtype, f32 = q.dtype, jnp.float32

    def blocks(x):  # [B, S, H, ...] -> [nb, B, nc, C, hb, ...]
        x = x.reshape(bsz, nc, chunk, nb, hb, *x.shape[3:])
        return jnp.moveaxis(x, 3, 0)

    parts = jax.lax.map(
        lambda t: jax.checkpoint(_chunk_operands)(*t),
        (blocks(q), blocks(k), blocks(v), blocks(g.astype(f32)), blocks(beta.astype(f32))),
    )
    # [nb, B, nc, hb, ...] -> [nc, B, H, ...]: the walk's leading axis
    w, u, scores, q_in, k_out, whole = (
        jnp.moveaxis(x, 0, 2).reshape(bsz, nc, heads, *x.shape[4:]).swapaxes(0, 1)
        for x in parts
    )

    def carry(state, inp):
        w_c, u_c, k_c, whole_c, q_c, s_c = inp
        before = state.astype(dtype)
        new = (u_c.astype(f32) - jnp.einsum(
            "bhik,bhkv->bhiv", w_c, before, preferred_element_type=f32
        )).astype(dtype)  # U - W S
        out = jnp.einsum("bhik,bhkv->bhiv", q_c, before, preferred_element_type=f32)
        out = out + jnp.einsum("bhij,bhjv->bhiv", s_c, new, preferred_element_type=f32)
        state = whole_c[..., None] * state + jnp.einsum(
            "bhik,bhiv->bhkv", k_c, new, preferred_element_type=f32
        )
        return state, out.astype(dtype)

    # the chunks in runs of `_RUN`, a checkpoint a run: the backward keeps
    # the state each run starts from and walks the run again, not the
    # state of every chunk (`[S / chunk, H, K, V]` float32: 0.5 GB at
    # 16,384 positions and 32 heads of 128)
    run_len = max(d for d in range(1, min(_RUN, nc) + 1) if nc % d == 0)
    runs = jax.tree.map(
        lambda x: x.reshape(nc // run_len, run_len, *x.shape[1:]),
        (w, u, k_out, whole, q_in, scores),
    )
    state0 = jnp.zeros((bsz, heads, key, val), f32)
    _, out = jax.lax.scan(
        jax.checkpoint(lambda state, run: jax.lax.scan(carry, state, run)), state0, runs
    )
    # [nc / run, run, B, H, C, V] -> [B, S, H, V]
    out = out.reshape(nc, bsz, heads, chunk, val)
    return out.transpose(1, 0, 3, 2, 4).reshape(bsz, seq, heads, val)


def kda_recurrence(q, k, v, g, beta):
    """The literal form, one position at a time, in float32 (for the tests
    that hold the chunked form equal to it)."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))

    def step(state, inp):
        qt, kt, vt, gt, bt = inp  # [B, H, K], [B, H, K], [B, H, V], [B, H, K], [B, H]
        state = jnp.exp(gt)[..., None] * state
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt, precision=_HI))
        state = state + kt[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=_HI)

    state0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), f32)
    seq_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    _, out = jax.lax.scan(step, state0, tuple(seq_first(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)
