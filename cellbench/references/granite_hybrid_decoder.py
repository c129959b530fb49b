"""Plain reference for a hybrid decoder of Mamba-2 and attention layers with
sparse experts (Granite-4.0-H), as one chip's share of an expert-parallel job.

    x = E[ids] * embedding_multiplier
    for each layer l:
        h = N_mix(x);  h = Mamba2(h) if layer_types[l] == "mamba" else Attn(h)
        x = x + residual_multiplier * h
        m = N_mlp(x);  x = x + residual_multiplier * (Routed(m) + Shared(m))
    logits = N_f(x) E^T / logits_scaling                  the head is the table, tied

`Attn`: q, k, v, o without bias, H query heads over KV key/value heads of
width hd, NO rotation, `s_ij = q_i . k_j * attention_multiplier`, causal
softmax, key head = query head // (H / KV).

`Mamba2` on u [S, D], with H heads of width P, a state of N, G groups of B
and C (head h reads group h // (H / G)), a depthwise convolution of K taps:

    [z | xBC | dt] = u W_in          widths H*P | H*P + 2*G*N | H
    xBC_t = silu(bias + sum_k w_k xBC_{t-(K-1)+k})        zeros before the sequence
    [x | B | C] = xBC;   dt = softplus(dt + dt_bias);   A = -exp(A_log)
    per head, h_0 = 0:   h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
                         y_t = h_t C_t + D x_t
    y = N_gated(y * silu(z));   out = y W_out             the gate BEFORE the norm

**How the recurrence is evaluated.** Exactly, block by block, with a block of
`BLOCK` = 64 positions (a length of this file's own: not the program's chunk).
Inside a block the recurrence unrolls to

    y_i = sum_{j<=i} exp(r_i - r_j) (C_i . B_j) dt_j x_j + exp(r_i) h_in C_i + D x_i
    h_out = exp(r_L) h_in + sum_j exp(r_L - r_j) dt_j x_j B_j^T

with `r` the running sum of `dt A` inside the block: every decay the `exp` of
a difference that is <= 0, so nothing overflows and what underflows is an exact
zero, as in the step-by-step form. The blocks run one after another
(`lax.scan`) with the state `[H, P, N]` carried in float32; each block's body
is a `jax.checkpoint`. `recurrence_step_by_step` is the literal form, kept
for the tests that hold the two equal.

`Routed`: logits `m W_r` over the router's published width; the `top_k`
largest; their softmax (the published order: top-k of the logits, then
softmax). y = sum over the chosen experts e with lo <= e < lo + held of
w_e E_e(m), E_e(m) = (silu(m G_e) * (m U_e)) D_e; `Shared` the same form,
added ungated. **The share**: routing is over all the router's experts; the
sum runs over the `held` experts from `lo` only; with `lo = 0, held = router
width` this file gives the uncut layer.

Straightforward float32 `jax.numpy`, every product at `Precision.HIGHEST`,
importing nothing of the program. It works a layer at a time (a layer's
weights fetched leaf by leaf and dropped after it, its input kept for the
backward: a checkpoint a layer), rows one at a time, attention over one key
head's group of query heads and one block of queries at a time, the held
experts one at a time over every token.

Leaf names (`get(name)`; the harness backs it with `cellbench/weights.py`):

    embed [V, D]   final_norm [D]
    layers.<i>.mixer_norm  .mlp_norm [D]
    attention:  layers.<i>.q [D, H*hd]  .k .v [D, KV*hd]  .o [H*hd, D]
    mamba:      layers.<i>.in_proj [D, 2*H*P + 2*G*N + H]  .out_proj [H*P, D]
                .conv_kernel [K, H*P + 2*G*N]  .conv_bias [H*P + 2*G*N]
                .dt_bias .A_log .D [H]  .gated_norm [H*P]
    layers.<i>.router [D, E_published]
    layers.<i>.experts.gate .up [held, D, Fe]  .experts.down [held, Fe, D]
    layers.<i>.shared.gate .up [D, Fs]  .shared.down [Fs, D]
    layers.<i>.<q|k|v|o|in_proj|out_proj>.lora_a [in, r]  .lora_b [r, out]   training only

`lora[i]` may hold more names than layer `i` has projections (the harness
asks every layer for every target); a layer reads its own mixer's only, and
gradients and final values come back for those alone.

`products="int8"` is the control: the same mathematics with both operands of
every linear layer (router included) rounded to 8 bits, per token for
activations and per output channel for weights. Attention's own products and
the recurrence stay float32 there, as in a W8A8 deployment.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
BLOCK = 64  # positions the recurrence advances at a time

ATTENTION_TARGETS = ("q", "k", "v", "o")
MAMBA_TARGETS = ("in_proj", "out_proj")


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    mamba: tuple  # True where the layer's mixer is Mamba-2
    heads: int
    kv_heads: int
    head_dim: int
    attention_multiplier: float
    m_heads: int
    m_head: int
    m_state: int
    m_groups: int
    m_conv: int
    expert: int
    shared: int
    router: int  # the router's published width
    held: int
    lo: int
    top_k: int
    vocab: int
    eps: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @classmethod
    def from_published(cls, c: dict, lo: int | None = None, held: int | None = None) -> "Dims":
        """`num_local_experts` and `vocab_size` are the counts HELD; the
        router's published width stands beside them as `router_width`.
        `lo`/`held` override the share (the share test walks all of them)."""
        n = int(c["num_hidden_layers"])
        kinds = list(c["layer_types"])[:n]
        if set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"no reference for layer types {sorted(set(kinds))}")
        if c.get("position_embedding_type") != "nope":
            raise ValueError("this reference rotates nothing: position_embedding_type nope")
        heads = int(c["num_attention_heads"])
        return cls(
            hidden=int(c["hidden_size"]),
            layers=n,
            mamba=tuple(k == "mamba" for k in kinds),
            heads=heads,
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim") or int(c["hidden_size"]) // heads),
            attention_multiplier=float(c["attention_multiplier"]),
            m_heads=int(c["mamba_n_heads"]),
            m_head=int(c["mamba_d_head"]),
            m_state=int(c["mamba_d_state"]),
            m_groups=int(c["mamba_n_groups"]),
            m_conv=int(c["mamba_d_conv"]),
            expert=int(c["intermediate_size"]),
            shared=int(c["shared_intermediate_size"]),
            router=int(c.get("router_width") or c["num_local_experts"]),
            held=int(c["num_local_experts"] if held is None else held),
            lo=int(c.get("expert_offset", 0) if lo is None else lo),
            top_k=int(c["num_experts_per_tok"]),
            vocab=int(c["vocab_size"]),
            eps=float(c["rms_norm_eps"]),
            embedding_multiplier=float(c["embedding_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]),
            logits_scaling=float(c["logits_scaling"]),
        )


# ------------------------------------------------------------------ products
def _mm_f32(a, b):
    return jnp.matmul(a, b, precision=HI)


def _q8(x, axis):
    """x rounded to 8 bits against the largest magnitude along `axis`; the
    gradient passes straight through the rounding, as int8 training has it."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm_int8(a, b):
    return jnp.matmul(_q8(a, -1), _q8(b, 0), precision=HI)


PRODUCTS = {"float32": _mm_f32, "int8": _mm_int8}


# --------------------------------------------------------------------- parts
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _attend(q, k, v, scale: float):
    """q [S, H, hd], k and v [S, KV, hd] -> [S, H, hd]; causal. One key
    head's group of query heads against all keys, one block of queries at a
    time."""
    s, h, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    bq = min(s, Q_BLOCK)
    nb = s // bq
    qb = q.reshape(nb, bq, kv, g, hd).transpose(2, 0, 3, 1, 4).reshape(kv * nb, g, bq, hd)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [KV, S, hd]
    which = jnp.arange(kv * nb)
    cols = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(args):
        qg, ix = args
        j, b = ix // nb, ix % nb
        rows = b * bq + jnp.arange(bq)[:, None]
        sc = jnp.einsum("gqd,kd->gqk", qg, kt[j], precision=HI) * scale
        p = jax.nn.softmax(jnp.where((cols <= rows)[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vt[j], precision=HI)

    out = jax.lax.map(block, (qb, which))  # [KV * nb, G, bq, hd]
    return out.reshape(kv, nb, g, bq, hd).transpose(1, 3, 0, 2, 4).reshape(s, h, hd)


def conv1d_causal(x, kernel, bias):
    """x [S, C], kernel [K, C], bias [C]: y_t = bias + sum_k kernel[k] x_{t-(K-1)+k}."""
    k, s = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], axis=0)
    return bias + sum(kernel[i] * padded[i : i + s] for i in range(k))


def recurrence(x, dt, a, b, c, d_skip, block: int = BLOCK):
    """x [S, H, P]; dt [S, H]; a [H] (< 0); b, c [S, G, N]; d_skip [H] ->
    y [S, H, P]. The recurrence of the module's docstring, `block` positions
    at a time, the state carried in float32."""
    s, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    if s % block:
        raise ValueError(f"{s} positions are no multiple of the block {block}")
    nb, rep = s // block, h // g
    xs = x.reshape(nb, block, g, rep, p)
    dts = dt.reshape(nb, block, g, rep)
    bs, cs = b.reshape(nb, block, g, n), c.reshape(nb, block, g, n)
    a, d_skip = a.reshape(g, rep), d_skip.reshape(g, rep)
    lower = jnp.tril(jnp.ones((block, block), bool))[:, :, None, None]

    @jax.checkpoint
    def one(h_in, args):
        xb, dtb, bb, cb = args  # [L, G, R, P], [L, G, R], [L, G, N], [L, G, N]
        r = jnp.cumsum(dtb * a, axis=0)  # [L, G, R]
        decay = jnp.exp(jnp.where(lower, r[:, None] - r[None, :], -jnp.inf))  # [i, j, G, R]
        cb_ij = jnp.einsum("ign,jgn->ijg", cb, bb, precision=HI)
        mix = cb_ij[..., None] * decay * dtb[None]
        y = jnp.einsum("ijgr,jgrp->igrp", mix, xb, precision=HI)
        y = y + jnp.exp(r)[..., None] * jnp.einsum("grpn,ign->igrp", h_in, cb, precision=HI)
        w = jnp.exp(r[-1][None] - r) * dtb  # [L, G, R]
        h_out = jnp.exp(r[-1])[..., None, None] * h_in + jnp.einsum(
            "jgrp,jgn->grpn", xb * w[..., None], bb, precision=HI
        )
        return h_out, y + d_skip[None, :, :, None] * xb

    _, ys = jax.lax.scan(one, jnp.zeros((g, rep, p, n), jnp.float32), (xs, dts, bs, cs))
    return ys.reshape(s, h, p)


def recurrence_step_by_step(x, dt, a, b, c, d_skip):
    """The literal form, one position at a time (for small sizes)."""
    h, g = x.shape[1], b.shape[1]
    bh, ch = jnp.repeat(b, h // g, axis=1), jnp.repeat(c, h // g, axis=1)

    def step(state, args):
        xt, dtt, bt, ct = args  # [H, P], [H], [H, N], [H, N]
        state = jnp.exp(dtt * a)[:, None, None] * state + (
            (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        )
        return state, jnp.einsum("hpn,hn->hp", state, ct, precision=HI) + d_skip[:, None] * xt

    state0 = jnp.zeros((h, x.shape[2], b.shape[2]), jnp.float32)
    return jax.lax.scan(step, state0, (x, dt, bh, ch))[1]


def _swiglu(m, gate, up, down, mm):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def routing_weights(logits, top_k: int):
    """The published order: the `top_k` largest logits, then their softmax.
    Returns the chosen experts [T, k] and their weights [T, k]."""
    top_l, top_e = jax.lax.top_k(logits, top_k)
    return top_e, jax.nn.softmax(top_l, axis=-1)


def _routed(m, w, d: Dims, mm):
    """sum over the held experts of w_e E_e(m): the experts one at a time
    over every token, the weight nought where the token is not routed to it."""
    top_e, top_w = routing_weights(mm(m, w["router"]), d.top_k)
    held = d.lo + jnp.arange(d.held)
    weight = jnp.sum(
        jnp.where(top_e[:, :, None] == held[None, None, :], top_w[:, :, None], 0.0), axis=1
    )  # [T, held]

    @jax.checkpoint
    def expert(args):
        gate, up, down, we = args
        return we[:, None] * _swiglu(m, gate, up, down, mm)

    parts = jax.lax.map(
        expert, (w["experts.gate"], w["experts.up"], w["experts.down"], weight.T)
    )
    return jnp.sum(parts, axis=0)


def _proj(h, w, lora, name, scale, mm):
    y = mm(h, w[name])
    if name in lora:
        y = y + scale * mm(mm(h, lora[name]["lora_a"]), lora[name]["lora_b"])
    return y


def _attention(a, w, lora, d: Dims, scale, mm):
    s = a.shape[0]
    q = _proj(a, w, lora, "q", scale, mm).reshape(s, d.heads, d.head_dim)
    k = _proj(a, w, lora, "k", scale, mm).reshape(s, d.kv_heads, d.head_dim)
    v = _proj(a, w, lora, "v", scale, mm).reshape(s, d.kv_heads, d.head_dim)
    o = _attend(q, k, v, d.attention_multiplier)
    return _proj(o.reshape(s, d.heads * d.head_dim), w, lora, "o", scale, mm)


def _mamba(u, w, lora, d: Dims, scale, mm):
    s = u.shape[0]
    inner, bc = d.m_heads * d.m_head, d.m_groups * d.m_state
    zxbcdt = _proj(u, w, lora, "in_proj", scale, mm)
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner : 2 * inner + 2 * bc], zxbcdt[:, 2 * inner + 2 * bc :]
    xbc = jax.nn.silu(conv1d_causal(xbc, w["conv_kernel"], w["conv_bias"]))
    x, b, c = xbc[:, :inner], xbc[:, inner : inner + bc], xbc[:, inner + bc :]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(
        x.reshape(s, d.m_heads, d.m_head), dt, -jnp.exp(w["A_log"]),
        b.reshape(s, d.m_groups, d.m_state), c.reshape(s, d.m_groups, d.m_state), w["D"],
        block=min(BLOCK, s),
    ).reshape(s, inner)
    y = _rms(y * jax.nn.silu(z), w["gated_norm"], d.eps)
    return _proj(y, w, lora, "out_proj", scale, mm)


def _layer(w, lora, x, *, d: Dims, i: int, scale: float, mm):
    """Block `i` on one sequence: x [S, D] -> [S, D]. `lora` holds the
    layer's own mixer's adapters."""
    a = _rms(x, w["mixer_norm"], d.eps)
    mixed = _mamba(a, w, lora, d, scale, mm) if d.mamba[i] else _attention(a, w, lora, d, scale, mm)
    h = x + d.residual_multiplier * mixed
    m = _rms(h, w["mlp_norm"], d.eps)
    shared = _swiglu(m, w["shared.gate"], w["shared.up"], w["shared.down"], mm)
    return h + d.residual_multiplier * (_routed(m, w, d, mm) + shared)


def _head_loss(x, norm_w, embed, labels, *, d: Dims, mm):
    """Sum over the row's tokens of the next-token cross entropy; the head
    is the table, its logits over `logits_scaling`."""
    logits = mm(_rms(x, norm_w, d.eps), embed.T) / d.logits_scaling
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


@functools.lru_cache(maxsize=None)
def _layer_fns(d: Dims, i: int, scale: float, products: str):
    mm = PRODUCTS[products]
    layer = functools.partial(_layer, d=d, i=i, scale=scale, mm=mm)

    def layer_bwd(w, lora, x, dy):
        _, vjp = jax.vjp(lambda lo, xx: layer(w, lo, xx), lora, x)
        return vjp(dy)  # (dlora, dx)

    return jax.jit(layer), jax.jit(layer_bwd)


@functools.lru_cache(maxsize=None)
def _head_fns(d: Dims, products: str):
    mm = PRODUCTS[products]
    head = functools.partial(_head_loss, d=d, mm=mm)

    def logits_at(x, norm_w, embed, rows):
        return mm(_rms(x[rows], norm_w, d.eps), embed.T) / d.logits_scaling

    return jax.jit(jax.value_and_grad(head)), jax.jit(logits_at)


def layer_leaves(d: Dims, i: int) -> tuple:
    mixer = (
        ("in_proj", "out_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "gated_norm")
        if d.mamba[i] else ATTENTION_TARGETS
    )
    return ("mixer_norm", "mlp_norm") + mixer + (
        "router", "experts.gate", "experts.up", "experts.down",
        "shared.gate", "shared.up", "shared.down",
    )


def layer_weights(get, d: Dims, i: int) -> dict:
    return {n: get(f"layers.{i}.{n}") for n in layer_leaves(d, i)}


def own_adapters(d: Dims, i: int, lora_i: dict) -> dict:
    """Of what the harness handed layer `i`, the adapters its mixer has."""
    names = MAMBA_TARGETS if d.mamba[i] else ATTENTION_TARGETS
    return {t: lora_i[t] for t in names if t in lora_i}


# ------------------------------------------------------------------- forward
def logits_for(get, d: Dims, seqs, rows, products="float32", pad_to=512, rows_to=256):
    """Full forward over each sequence, layer by layer; for sequence j the
    float32 logits at positions `rows[j]`. Sequences are right-padded to a
    multiple of `pad_to` (causal: padding cannot reach back)."""
    _, logits_at = _head_fns(d, products)
    embed = get("embed")
    xs = []
    for s in seqs:
        n = -(-len(s) // pad_to) * pad_to
        ids = jnp.zeros((n,), jnp.int32).at[: len(s)].set(jnp.asarray(s, jnp.int32))
        xs.append(embed[ids] * d.embedding_multiplier)
    for i in range(d.layers):
        layer, _ = _layer_fns(d, i, 0.0, products)
        w = layer_weights(get, d, i)
        xs = [layer(w, {}, x) for x in xs]
    norm_w = get("final_norm")
    out = []
    for x, r in zip(xs, rows):
        n = -(-len(r) // rows_to) * rows_to
        idx = jnp.zeros((n,), jnp.int32).at[: len(r)].set(jnp.asarray(r, jnp.int32))
        out.append(logits_at(x, norm_w, embed, idx)[: len(r)])
    return out


# ------------------------------------------------------------------ training
def loss_and_grads(get, d: Dims, lora, tokens, labels, scale, products="float32"):
    """Mean next-token loss over every row and token of the batch, and its
    gradient for the LoRA leaves. `lora[i][proj] = {lora_a, lora_b}`, layer
    `i`'s own mixer's projections. Forward keeps each layer's input; backward
    runs layer by layer from the top, one row at a time, with the layer's
    weights fetched again. A batch without rows (the harness's half-batch
    fault of a one-row cell) has no loss: NaN, and gradients of nought."""
    head, _ = _head_fns(d, products)
    b, s = tokens.shape
    if b == 0:
        return jnp.float32(jnp.nan), jax.tree.map(jnp.zeros_like, lora)
    embed = get("embed")
    x = [embed[tokens[r]] * d.embedding_multiplier for r in range(b)]
    inputs = []
    for i in range(d.layers):
        layer, _ = _layer_fns(d, i, float(scale), products)
        w = layer_weights(get, d, i)
        inputs.append(x)
        x = [layer(w, lora[i], xr) for xr in x]
        del w
    norm_w = get("final_norm")
    total, dx = 0.0, []
    for r in range(b):
        val, g = head(x[r], norm_w, embed, labels[r])
        total = total + val
        dx.append(g / (b * s))
    del norm_w, embed
    loss = total / (b * s)
    grads = [None] * d.layers
    for i in reversed(range(d.layers)):
        _, layer_bwd = _layer_fns(d, i, float(scale), products)
        w = layer_weights(get, d, i)
        acc = None
        for r in range(b):
            dl, dx[r] = layer_bwd(w, lora[i], inputs[i][r], dx[r])
            acc = dl if acc is None else jax.tree.map(jnp.add, acc, dl)
        grads[i] = acc
        inputs[i] = None
        del w
    return loss, grads


def adamw_step(p, g, m, v, t, *, lr, b1, b2, eps, weight_decay):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1**t)
    vhat = v / (1 - b2**t)
    return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p), m, v


def train_steps(get, d: Dims, lora, batches, *, scale, adamw, products="float32"):
    """Follow `len(batches)` steps of LoRA fine-tuning under AdamW. Returns
    each step's loss, the first step's gradients, and the LoRA leaves after
    the last step: for each layer, of its own mixer's projections."""
    lora = [own_adapters(d, i, layer) for i, layer in enumerate(lora)]
    zeros = jax.tree.map(jnp.zeros_like, lora)
    m, v = zeros, zeros
    losses, first = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(get, d, lora, tokens, labels, scale, products)
        losses.append(float(loss))
        if first is None:
            first = grads
        out = jax.tree.map(
            lambda p, g, mm_, vv: adamw_step(p, g, mm_, vv, t, **adamw),
            lora, grads, m, v,
        )
        pick = lambda k: jax.tree.map(  # noqa: E731
            lambda o: o[k], out, is_leaf=lambda o: isinstance(o, tuple)
        )
        lora, m, v = pick(0), pick(1), pick(2)
    return losses, first, lora
