"""The state-space scan of a Mamba-2 layer in its chunked (state-space
duality) form, in `jax.numpy`, differentiable by autodiff.

Per head, with a state `h` in R^(P x N), `h_0 = 0`, a step size `dt_t > 0`
and one decay rate `A < 0`:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

The sequence is cut into chunks of `chunk` positions. With `a_t = dt_t A` and
`s` its running sum inside a chunk (`s_Q` the sum over the whole chunk):

    Y_intra[i] = sum_{j<=i} exp(s_i - s_j) (C_i . B_j) dt_j x_j     in-chunk, quadratic
    S_c        = sum_j exp(s_Q - s_j) dt_j x_j B_j^T                the chunk's own state
    H_c        = exp(s_Q) H_{c-1} + S_c                             carried over chunks
    Y_inter[i] = exp(s_i) H_{c-1} C_i                               what came before

**Numbers.** A decay is only ever `exp` of a difference that is <= 0 (never
`exp(s_i) * exp(-s_j)`: with a fast head the in-chunk sums pass -100 and the
second factor overflows), in float32; a decay that underflows is an exact
zero, forward and backward. `dt`, `A`, the sums and the carried state are
float32 whatever `x` is; the four products take operands of `x`'s type (bf16
under `precision: mixed`) and accumulate in float32. `C . B^T` is computed
once a chunk and shared by the heads of a group.

**Memory.** The in-chunk decays of all heads are `[B, S/Q, H, Q, Q]` float32
(1.07 GB for 8,192 positions, 128 heads, chunk 256), and autodiff would keep
them and their product with `C . B^T`. So the heads are walked in blocks
(`lax.map`), each block's body a `jax.checkpoint`: the backward keeps a
block's inputs and builds its decays again. `heads_per_step` is chosen so
that one block's decays stay under `_DECAY_BYTES`.

A sequence that is no multiple of the chunk is refused, not padded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_DECAY_BYTES = 128 * 1024 * 1024  # one head block's float32 in-chunk decays


def heads_per_step(batch: int, seq: int, chunk: int, heads_per_group: int) -> int:
    """The largest divisor of a group's heads whose in-chunk decays
    (`batch x seq x chunk` float32 a head) stay under `_DECAY_BYTES`."""
    per_head = batch * seq * chunk * 4
    fit = max(1, _DECAY_BYTES // per_head)
    return max(d for d in range(1, heads_per_group + 1)
               if heads_per_group % d == 0 and d <= fit)


def largest_intermediate_bytes(batch: int, seq: int, chunk: int, heads: int,
                               groups: int = 1) -> int:
    """Bytes of the scan's largest intermediate for this shape: one head
    block's float32 in-chunk decays."""
    return heads_per_step(batch, seq, chunk, heads // groups) * batch * seq * chunk * 4


def _head_block(xh, dth, a_rate, d_skip, bm, cm, cb):
    """One block of heads of one group. xh [B, nc, Q, h, P]; dth [B, nc, Q, h]
    float32; a_rate, d_skip [h] float32; bm, cm [B, nc, Q, N]; cb [B, nc, Q, Q]
    float32 -> y [B, nc, Q, h, P] float32."""
    dtype = xh.dtype
    q = xh.shape[2]
    f32 = jnp.float32
    dt_t = dth.transpose(0, 1, 3, 2)  # [B, nc, h, Q]
    s = jnp.cumsum(dt_t * a_rate[:, None], axis=-1)  # running sum in the chunk, <= 0
    # in-chunk: exp(s_i - s_j) for j <= i, an exact 0 elsewhere
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, s[..., :, None] - s[..., None, :], -jnp.inf))
    mix = (cb[:, :, None] * decay * dt_t[..., None, :]).astype(dtype)  # [B, nc, h, i, j]
    y = jnp.einsum("bchij,bcjhp->bcihp", mix, xh, preferred_element_type=f32)
    # the chunk's own state: sum_j exp(s_Q - s_j) dt_j x_j B_j^T
    w = (jnp.exp(s[..., -1:] - s) * dt_t).transpose(0, 1, 3, 2)  # [B, nc, Q, h]
    xw = (xh.astype(f32) * w[..., None]).astype(dtype)
    own = jnp.einsum("bcjhp,bcjn->bchpn", xw, bm, preferred_element_type=f32)
    # carried over the chunks, float32
    whole = jnp.exp(s[..., -1])  # [B, nc, h]: the decay over a whole chunk

    def carry(h_prev, inp):
        own_c, whole_c = inp
        return whole_c[..., None, None] * h_prev + own_c, h_prev

    h0 = jnp.zeros(own.shape[:1] + own.shape[2:], f32)
    _, before = jax.lax.scan(
        carry, h0, (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0))
    )
    before = jnp.moveaxis(before, 0, 1)  # [B, nc, h, P, N]: the state each chunk starts from
    inter = jnp.einsum(
        "bcin,bchpn->bcihp", cm, before.astype(dtype), preferred_element_type=f32
    )
    y = y + inter * jnp.exp(s).transpose(0, 1, 3, 2)[..., None]
    return y + xh.astype(f32) * d_skip[:, None]


def ssd_scan(x, dt, a_rate, b, c, d_skip, *, chunk: int = 256, block_heads: int | None = None):
    """x [B, S, H, P]; dt [B, S, H] (> 0); a_rate [H] (< 0); b, c [B, S, G, N]
    with G dividing H (head h reads group h // (H / G)); d_skip [H].
    Returns y [B, S, H, P] in x's type. `block_heads` overrides how many heads
    of a group are taken at a time."""
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if seq % chunk:
        raise ValueError(
            f"the state-space scan works on whole chunks: a sequence of {seq} "
            f"positions is no multiple of the chunk {chunk} (mamba_chunk_size); "
            "pad the batch to a multiple of it or choose a chunk that divides it"
        )
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide over {groups} groups")
    per_group = heads // groups
    hb = block_heads or heads_per_step(bsz, seq, chunk, per_group)
    if per_group % hb:
        raise ValueError(f"block_heads {hb} does not divide a group's {per_group} heads")
    nc, nb = seq // chunk, per_group // hb
    f32 = jnp.float32
    dt, a_rate, d_skip = dt.astype(f32), a_rate.astype(f32), d_skip.astype(f32)

    with jax.named_scope("ssd"):
        # [G, nb, B, nc, Q, hb, ...]: one leading index a block of heads
        xs = x.reshape(bsz, nc, chunk, groups, nb, hb, p).transpose(3, 4, 0, 1, 2, 5, 6)
        dts = dt.reshape(bsz, nc, chunk, groups, nb, hb).transpose(3, 4, 0, 1, 2, 5)
        bs = b.reshape(bsz, nc, chunk, groups, n).transpose(3, 0, 1, 2, 4)  # [G, B, nc, Q, N]
        cs = c.reshape(bsz, nc, chunk, groups, n).transpose(3, 0, 1, 2, 4)
        cbs = jnp.einsum("gbcin,gbcjn->gbcij", cs, bs, preferred_element_type=f32)
        rates = a_rate.reshape(groups, nb, hb)
        skips = d_skip.reshape(groups, nb, hb)

        body = jax.checkpoint(_head_block)

        def group(args):
            xg, dtg, rg, sg, bg, cg, cbg = args
            return jax.lax.map(lambda t: body(*t, bg, cg, cbg), (xg, dtg, rg, sg))

        ys = jax.lax.map(group, (xs, dts, rates, skips, bs, cs, cbs))
        # [G, nb, B, nc, Q, hb, P] -> [B, S, H, P]
        y = ys.transpose(2, 3, 4, 0, 1, 5, 6).reshape(bsz, seq, heads, p)
        return y.astype(x.dtype)


def causal_conv1d(x, kernel, bias):
    """Depthwise convolution over time, causal: x [B, S, C], kernel [K, C],
    bias [C]: y_t = bias + sum_k kernel[k] x_{t - (K-1) + k}, positions before
    the sequence read as zeros (a left pad of K-1)."""
    k, seq = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, i : i + seq] * kernel[i].astype(x.dtype) for i in range(k))
    return y + bias.astype(x.dtype)


def gated_rmsnorm(y, z, scale, eps: float = 1e-5):
    """RMSNorm(y * silu(z)) * scale over the last axis, the gate BEFORE the
    norm, in float32; the result in y's type."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    normed = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return (normed * scale).astype(y.dtype)
