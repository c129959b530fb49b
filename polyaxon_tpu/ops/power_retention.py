"""Degree-2 power retention (Manifest AI's *Symmetric Power Transformers*,
2024) in its chunked form, in `jax.numpy`, differentiable by autodiff. Where
`power_retention_fused.scan_plan` takes the shape (heads of 128, a chunk of
whole lanes, whole runs, float32 or bf16, no live mesh) `models/retention.py`
runs the Pallas kernels of `power_retention_fused.py` in its place: the same
algebra and numbers, checked against `retention_scan` below.

Per query head, with `s_tj = (scale * q_t . k_j)^2`, a log-gate `log g_t <= 0`
(one scalar a head and position) and the decay from `j` to `t` the product
of the gates after `j`:

    y_t = sum_{j<=t} exp(sum_{j<l<=t} log g_l) s_tj v_j / (sum_{j<=t} exp(...) s_tj + EPS)

Equivalently, with `phi` the degree-2 symmetric power of a vector (so that
`phi(a) . phi(b) = (a . b)^2`), a state `S` of `phi(k) v^T` and a normaliser
`z` of `phi(k)`, both decayed by the gate:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)      y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + EPS)

The sequence is cut into chunks of `chunk` positions. With `G` the running
sum of `log g` inside a chunk (`G_C` its last row) and `S`, `Z` the state the
chunk starts from, a chunk's rows read

    num = exp(G) * scale^2 (phi(Q) S) + (tril(exp(G_i - G_j)) * (scale Q K^T)^2) V
    den = exp(G) * scale^2 rowsum((Q Z) * Q) + rowsum(tril(exp(G_i - G_j)) * (scale Q K^T)^2)
    y = num / (den + EPS)
    S' = exp(G_C) S + phi(K)^T (exp(G_C - G) * V)        Z' = exp(G_C) Z + K^T (exp(G_C - G) * K)

`Z` is the normaliser kept as a `[P, P]` matrix: `phi(q) . z = q^T Z q` for
`Z = sum_j w_j k_j k_j^T`, so the denominator costs a `P`-wide product and
not a `D`-wide one. The value state `S` is `[D, P]` a head.

**phi.** The key's `P` = 128 channels are cut into blocks of
`FEATURE_BLOCK` = 16; `phi(x)` holds the full outer product `x_I x_J^T` of
every pair of blocks `I <= J`, the pairs off the diagonal times sqrt(2):
`(a . b)^2 = sum_I (a_I . b_I)^2 + 2 sum_{I<J} (a_I . b_I)(a_J . b_J)`. That is
36 pairs of 256 = 9,216 features (the exact symmetric power has C(129, 2) =
8,256; the full tensor square 16,384): a lane-aligned width, built from
static slices and broadcasts, with no gather.

**Numbers.** Gates, running sums, decays, the state, the normaliser, the
scores and the readout's sums are float32 whatever `q` is. A decay is `exp`
of a difference that is never positive (the upper triangle is masked before
the `exp`), so a decay that underflows is an exact zero, forward and
backward. The products take operands of `q`'s type (bf16 under `precision:
mixed`: the features, the state cast for the read, the scores for the
values) and accumulate in float32; the scale is applied in float32 after
the product.

**Walk and memory.** Query heads are walked one row's key-value group at a
time (`lax.map`, each group's body a `jax.checkpoint`): a group's `r` query heads
share their keys, so `phi(K)` is built once for them, and the state of one
group (`r x D x P` float32: 23.6 MB for five heads) is what the walk carries.
The walk over the chunks runs in runs of `run_length(chunks)` chunks, a
checkpoint a run, and each chunk's body under a checkpoint of its own: the
backward keeps one state a run and rebuilds the run's states, one a chunk,
and a chunk's features, from it (a state a chunk for every head would be
`[S / chunk, H, D, P]` float32: 21.8 GB at 32,768 positions, chunk 256 and
40 heads). A sequence that is no multiple of the chunk is padded at its end
with zero keys, values and queries and gates of 1; causality keeps the
padding from reaching back.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

DEGREE = 2  # the power of q . k in a score: phi is the symmetric square
FEATURE_BLOCK = 16  # channels of one block of phi's pairs
# added to the readout's denominator, a sum of scores (q_t . k_j)^2 / P that
# near a sequence's start holds one or a few and may be as near nought as the
# query is to those keys at right angles; where q and k are bf16 a score of
# 1e-3 carries a rounding error of about a tenth of itself, and EPS keeps
# the denominator clear of scores that small
EPS = 1e-2


def feature_width(p: int, block: int = FEATURE_BLOCK) -> int:
    """phi's width for a key of `p` channels: every pair of blocks I <= J."""
    n = p // block
    return n * (n + 1) // 2 * block * block


def features(x, block: int = FEATURE_BLOCK):
    """phi(x) [..., feature_width(P)] in float32 for x [..., P]: the outer
    products of every pair of channel blocks I <= J, those with I < J times
    sqrt(2), so that phi(a) . phi(b) = (a . b)^2."""
    p = x.shape[-1]
    if p % block:
        raise ValueError(f"a key of {p} channels is no multiple of phi's block {block}")
    n = p // block
    xb = x.astype(jnp.float32).reshape(*x.shape[:-1], n, block)
    lead = xb.shape[:-2]
    left = jnp.concatenate(
        [jnp.broadcast_to(xb[..., i : i + 1, :], (*lead, n - i, block)) for i in range(n)],
        axis=-2,
    )
    right = jnp.concatenate([xb[..., i:, :] for i in range(n)], axis=-2)
    weight = np.concatenate([[1.0] + [math.sqrt(2.0)] * (n - 1 - i) for i in range(n)])
    outer = left[..., :, :, None] * right[..., :, None, :] * weight[:, None, None].astype(np.float32)
    return outer.reshape(*lead, feature_width(p, block))


def run_length(chunks: int) -> int:
    """Chunks a checkpointed run takes: the divisor of `chunks` nearest its
    square root, which keeps fewest states in the backward (one a run, and
    one a chunk of the run being walked back)."""
    return min(
        (d for d in range(1, chunks + 1) if chunks % d == 0),
        key=lambda d: (abs(d - math.sqrt(chunks)), d),
    )


def state_bytes(heads: int, p: int) -> int:
    """Bytes of one row's carried state over `heads` query heads: S and Z in
    float32."""
    return heads * (feature_width(p) * p + p * p) * 4


def largest_intermediate_bytes(chunk: int, heads_per_step: int, p: int, itemsize: int = 2) -> int:
    """Bytes of a walk step's query features, the largest array a chunk's
    body builds: chunk x a group's query heads x feature_width, in the
    activations' type."""
    return chunk * heads_per_step * feature_width(p) * itemsize


def _chunk(state, inp, *, scale: float, dtype):
    """One chunk of one row's key-value group. state: (S [r, D, P], Z [r, P,
    P]) float32; inp: q [r, C, P], k, v [C, P] of `dtype`, G [r, C] float32
    (the running sum of the log-gate inside the chunk). Returns the next
    state and (y [r, C, P] float32, each position's smallest denominator
    over the group's heads [C])."""
    s_state, z_state = state
    q, k, v, g = inp
    f32 = jnp.float32
    c = q.shape[1]
    sq = scale * scale
    fq = features(q).astype(dtype)  # [r, C, D]
    fk = features(k).astype(dtype)  # [C, D]
    into = jnp.exp(g)  # exp(G_i) <= 1   [r, C]
    num = jnp.einsum("rcd,rdp->rcp", fq, s_state.astype(dtype),
                     preferred_element_type=f32) * (sq * into)[..., None]
    qz = jnp.einsum("rcp,rpq->rcq", q, z_state.astype(dtype), preferred_element_type=f32)
    den = jnp.sum(qz * q.astype(f32), axis=-1) * sq * into
    # the chunk's own part: squared scores, decayed, causal
    raw = jnp.einsum("rcp,jp->rcj", q, k, preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(causal, g[:, :, None] - g[:, None, :], -jnp.inf))
    a = sq * raw * raw * decay  # [r, C, C]
    num = num + jnp.einsum("rcj,jp->rcp", a.astype(dtype), v, preferred_element_type=f32)
    den = den + jnp.sum(a, axis=-1)
    y = num / (den + EPS)[..., None]
    # the state the next chunk starts from
    whole = jnp.exp(g[:, -1])  # exp(G_C)   [r]
    out = jnp.exp(g[:, -1:] - g)  # exp(G_C - G_j) <= 1   [r, C]
    vw = (v.astype(f32)[None] * out[..., None]).astype(dtype)  # [r, C, P]
    kw = (k.astype(f32)[None] * out[..., None]).astype(dtype)
    s_state = whole[:, None, None] * s_state + jnp.einsum(
        "cd,rcp->rdp", fk, vw, preferred_element_type=f32)
    z_state = whole[:, None, None] * z_state + jnp.einsum(
        "rcp,cq->rpq", kw, k, preferred_element_type=f32)
    return (s_state, z_state), (y, jnp.min(den, axis=0))


def retention_scan(q, k, v, log_g, *, chunk: int = 256):
    """q [B, S, H, P]; k, v [B, S, G, P] (query head h reads group h // (H /
    G)); log_g [B, S, H] (<= 0). Returns (y [B, S, H, P] in q's type, the
    smallest denominator `phi(q)^T z` over every position and head, float32).
    q . k is scaled by 1 / sqrt(P) before the square."""
    bsz, seq, heads, p = q.shape
    groups = k.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} query heads do not divide over {groups} key-value groups")
    r = heads // groups
    scale = p**-0.5
    dtype, f32 = q.dtype, jnp.float32
    pad = -seq % chunk
    if pad:
        widen = lambda x: jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))  # noqa: E731
        q, k, v, log_g = widen(q), widen(k), widen(v), widen(log_g)
    total = seq + pad
    nc = total // chunk
    run = run_length(nc)
    g = jnp.cumsum(log_g.astype(f32).reshape(bsz, nc, chunk, heads), axis=2)

    # [B x G, nc / run, run, (r,) C, ...]: a row's group's walk, chunks in
    # runs, a group's query heads before the chunk's positions
    def walk_order(x, per_group, heads_first):
        x = jnp.moveaxis(x.reshape(bsz, nc // run, run, chunk, groups, *per_group), 4, 1)
        if heads_first:
            x = jnp.moveaxis(x, 5, 4)  # [.., C, r, ...] -> [.., r, C, ...]
        return x.reshape(bsz * groups, nc // run, run, *x.shape[4:])

    body = jax.checkpoint(functools.partial(_chunk, scale=scale, dtype=dtype))

    @jax.checkpoint
    def group(qg, kg, vg, gg):
        state0 = (jnp.zeros((r, feature_width(p), p), f32), jnp.zeros((r, p, p), f32))
        walk = jax.checkpoint(lambda state, xs: jax.lax.scan(body, state, xs))
        _, (y, low) = jax.lax.scan(walk, state0, (qg, kg, vg, gg))
        return y.astype(dtype), low

    with jax.named_scope("power_retention"):
        y, low = jax.lax.map(
            lambda t: group(*t),
            (walk_order(q, (r, p), True), walk_order(k, (p,), False),
             walk_order(v, (p,), False), walk_order(g, (r,), True)),
        )
    # [B x G, nc / run, run, r, C, P] -> [B, S, H, P]
    y = y.reshape(bsz, groups, nc, r, chunk, p).transpose(0, 2, 4, 1, 3, 5)
    y = y.reshape(bsz, total, heads, p)
    low = low.reshape(bsz, groups, total)[:, :, :seq]  # the padding's rows read 0
    return y[:, :seq], jnp.min(low)
