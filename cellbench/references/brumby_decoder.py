"""Plain reference for a decoder of degree-2 power-retention layers
(Brumby-14B-Base: Qwen3-14B's projections and MLP, its attention replaced by
power retention), as one stage of a pipeline: the layers held, and both ends.

    x = E[ids]
    for each layer l:
        h = N_mix(x);  x = x + Retention(h)
        m = N_mlp(x);  x = x + (silu(m W_gate) * (m W_up)) W_down
    logits = N_f(x) W_head                                  the head is untied

`Retention` on u [S, D], H query heads and G key-value groups of width P
(query head h reads group h // (H / G), as Qwen3's grouped attention does):

    q_h = RoPE(RMSNorm_P(u W_q)_h * w_qn)        k_g = RoPE(RMSNorm_P(u W_k)_g * w_kn)        v_g = u W_v
    log g_t,h = logsigmoid(u_t W_gate_ret + b)_h  one scalar a head and position, b the gate's bias
    s_tj = (q_t . k_j / sqrt(P))^2
    y_t = sum_{j<=t} exp(sum_{j<l<=t} log g_l) s_tj v_j / (sum_{j<=t} exp(sum_{j<l<=t} log g_l) s_tj + eps)
    out = concat_h(y) W_o

RoPE rotates (first half, second half) pairs of the whole head at
`rope_theta`, after the norm. The configuration's file gives the readings
its published keys do not: `power_degree` (2, the only one built here),
`retention_gate_bias` (b) and `retention_eps` (eps).

**How the mixer is evaluated.** In the quadratic form above, literally: per
head and block of query rows, the `[rows, S]` squared scores times the masked
decays, then their products with the values and their row sums, in float32
(`retention`). The exponent of a decay is a sum of log-gates taken backwards
from the block's last row (`back_j = sum_{j<l<=T} log g_l`, so that the sum
over `(j, t]` is `back_j - back_t`): near the rows it is a small number, where
a running sum from the sequence's start would be tens of thousands at 32,768
positions and keep three decimals. No feature map, no state, no chunks:
nothing of the program's algorithm.

Straightforward float32 `jax.numpy`, every product at `Precision.HIGHEST`,
importing nothing of the program. A layer at a time (its weights fetched leaf
by leaf and dropped after it, its input kept for the backward), rows one at a
time, the mixer one head and one block of queries at a time, the MLP and the
head one block of `TOKEN_BLOCK` positions at a time, each under a checkpoint.

Leaf names (`get(name)`; the harness backs it with `cellbench/weights.py`):

    embed [V, D]   final_norm [D]   lm_head [D, V]
    layers.<i>.mixer_norm  .mlp_norm [D]
    layers.<i>.q [D, H*P]  .k .v [D, G*P]  .o [H*P, D]  .q_norm .k_norm [P]  .retention_gate [D, H]
    layers.<i>.gate .up [D, F]  .down [F, D]
    layers.<i>.<q|k|v|o>.lora_a [in, r]  .lora_b [r, out]         training only

`products="int8"` is the control: the same mathematics with both operands of
every linear layer (the gate's projection and the head included) rounded to
8 bits, per token for activations and per output channel for weights. The
mixer's own products stay float32 there, as in a W8A8 deployment.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
TOKEN_BLOCK = 2048  # positions of one block of the MLP and of the head


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    mlp: int
    vocab: int
    eps: float
    gate_bias: float
    retention_eps: float

    @classmethod
    def from_published(cls, c: dict) -> "Dims":
        n = int(c["num_hidden_layers"])
        kinds = set(tuple(c["layer_types"])[:n])
        if kinds != {"power_retention"} or int(c["power_degree"]) != 2:
            raise ValueError(f"no reference for layer types {sorted(kinds)} or degree "
                             f"{c.get('power_degree')}")
        return cls(
            hidden=int(c["hidden_size"]),
            layers=n,
            heads=int(c["num_attention_heads"]),
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            theta=float(c["rope_theta"]),
            mlp=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]),
            eps=float(c["rms_norm_eps"]),
            gate_bias=float(c["retention_gate_bias"]),
            retention_eps=float(c["retention_eps"]),
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


def _rotate(x, theta: float):
    """x [S, heads, P]: (first half, second half) pairs rotated by position."""
    s, p = x.shape[0], x.shape[-1]
    half = p // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def retention(q, k, v, log_g, eps: float, block: int = Q_BLOCK):
    """q [S, H, P]; k, v [S, G, P]; log_g [S, H] -> y [S, H, P]: the
    quadratic form of the module's docstring, one head and one block of
    query rows at a time."""
    s, h, p = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)  # head h reads h // rep
    bq = min(s, block)
    if s % bq:
        raise ValueError(f"{s} positions are no multiple of the block {bq}")
    nb = s // bq
    qb = q.reshape(nb, bq, h, p).transpose(2, 0, 1, 3).reshape(h * nb, bq, p)
    kt, vt, gt = k.transpose(1, 0, 2), v.transpose(1, 0, 2), log_g.T  # [H, S, .]
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qg, ix = args
        j, b = ix // nb, ix % nb
        last = b * bq + bq - 1
        rows = b * bq + jnp.arange(bq)
        # back_j = sum of log g over (j, last]: small near the block's rows
        upto = jnp.cumsum(jnp.where(cols <= last, gt[j], 0.0)[::-1])[::-1]  # over [j, last]
        back = jnp.concatenate([upto[1:], jnp.zeros((1,), jnp.float32)])
        exponent = back[None, :] - back[rows][:, None]  # sum over (j, t]
        decay = jnp.exp(jnp.where(cols[None, :] <= rows[:, None], exponent, -jnp.inf))
        sc = jnp.einsum("qd,kd->qk", qg, kt[j], precision=HI) * p**-0.5
        a = sc * sc * decay
        num = jnp.einsum("qk,kd->qd", a, vt[j], precision=HI)
        return num / (jnp.sum(a, axis=-1) + eps)[:, None]

    out = jax.lax.map(one, (qb, jnp.arange(h * nb)))  # [H * nb, bq, P]
    return out.reshape(h, nb, bq, p).transpose(1, 2, 0, 3).reshape(s, h, p)


def _proj(h, w, lora, name, scale, mm):
    y = mm(h, w[name])
    if name in lora:
        y = y + scale * mm(mm(h, lora[name]["lora_a"]), lora[name]["lora_b"])
    return y


def _retention(u, w, lora, d: Dims, scale, mm):
    s, h, g, p = u.shape[0], d.heads, d.kv_heads, d.head_dim
    q = _rms(_proj(u, w, lora, "q", scale, mm).reshape(s, h, p), w["q_norm"], d.eps)
    k = _rms(_proj(u, w, lora, "k", scale, mm).reshape(s, g, p), w["k_norm"], d.eps)
    v = _proj(u, w, lora, "v", scale, mm).reshape(s, g, p)
    log_g = jax.nn.log_sigmoid(mm(u, w["retention_gate"]) + d.gate_bias)
    y = retention(_rotate(q, d.theta), _rotate(k, d.theta), v, log_g, d.retention_eps)
    return _proj(y.reshape(s, h * p), w, lora, "o", scale, mm)


def _in_blocks(fn, *xs):
    """fn over blocks of TOKEN_BLOCK positions of each x [S, ...], one block
    at a time under a checkpoint; the blocks' results stacked."""
    s = xs[0].shape[0]
    n = min(s, TOKEN_BLOCK)
    if s % n:
        raise ValueError(f"{s} positions are no multiple of the block {n}")
    return jax.lax.map(jax.checkpoint(lambda t: fn(*t)),
                       tuple(x.reshape(s // n, n, *x.shape[1:]) for x in xs))


def _layer(w, lora, x, *, d: Dims, scale: float, mm):
    """One block on one sequence: x [S, D] -> [S, D]."""
    h = x + _retention(_rms(x, w["mixer_norm"], d.eps), w, lora, d, scale, mm)

    def mlp(hb):
        m = _rms(hb, w["mlp_norm"], d.eps)
        return mm(jax.nn.silu(mm(m, w["gate"])) * mm(m, w["up"]), w["down"])

    return h + _in_blocks(mlp, h).reshape(h.shape)


def _head_loss(x, norm_w, head_w, labels, *, d: Dims, mm):
    """Sum over the row's tokens of the next-token cross entropy."""

    def block(xb, lb):
        logits = mm(_rms(xb, norm_w, d.eps), head_w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)

    return jnp.sum(_in_blocks(block, x, labels))


@functools.lru_cache(maxsize=None)
def _layer_fns(d: Dims, scale: float, products: str):
    mm = PRODUCTS[products]
    layer = functools.partial(_layer, d=d, scale=scale, mm=mm)

    def layer_bwd(w, lora, x, dy):
        _, vjp = jax.vjp(lambda lo, xx: layer(w, lo, xx), lora, x)
        return vjp(dy)  # (dlora, dx)

    return jax.jit(layer), jax.jit(layer_bwd)


@functools.lru_cache(maxsize=None)
def _head_fns(d: Dims, products: str):
    mm = PRODUCTS[products]
    head = functools.partial(_head_loss, d=d, mm=mm)

    def logits_at(x, norm_w, head_w, rows):
        return mm(_rms(x[rows], norm_w, d.eps), head_w)

    return jax.jit(jax.value_and_grad(head)), jax.jit(logits_at)


LEAVES = ("mixer_norm", "mlp_norm", "q", "k", "v", "o", "q_norm", "k_norm", "retention_gate",
          "gate", "up", "down")


def layer_weights(get, i: int) -> dict:
    return {n: get(f"layers.{i}.{n}") for n in LEAVES}


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
        xs.append(embed[ids])
    layer, _ = _layer_fns(d, 0.0, products)
    for i in range(d.layers):
        w = layer_weights(get, i)
        xs = [layer(w, {}, x) for x in xs]
    norm_w, head_w = get("final_norm"), get("lm_head")
    out = []
    for x, r in zip(xs, rows):
        n = -(-len(r) // rows_to) * rows_to
        idx = jnp.zeros((n,), jnp.int32).at[: len(r)].set(jnp.asarray(r, jnp.int32))
        out.append(logits_at(x, norm_w, head_w, idx)[: len(r)])
    return out


# ------------------------------------------------------------------ training
def loss_and_grads(get, d: Dims, lora, tokens, labels, scale, products="float32"):
    """Mean next-token loss over every row and token of the batch, and its
    gradient for the LoRA leaves (`lora[i][proj] = {lora_a, lora_b}`).
    Forward keeps each layer's input; backward runs layer by layer from the
    top, one row at a time, with the layer's weights fetched again. A batch
    without rows (the harness's half-batch fault of a one-row cell) has no
    loss: NaN, and gradients of nought."""
    head, _ = _head_fns(d, products)
    b, s = tokens.shape
    if b == 0:
        return jnp.float32(jnp.nan), jax.tree.map(jnp.zeros_like, lora)
    layer, layer_bwd = _layer_fns(d, float(scale), products)
    embed = get("embed")
    x = [embed[tokens[r]] for r in range(b)]
    del embed
    inputs = []
    for i in range(d.layers):
        w = layer_weights(get, i)
        inputs.append(x)
        x = [layer(w, lora[i], xr) for xr in x]
        del w
    norm_w, head_w = get("final_norm"), get("lm_head")
    total, dx = 0.0, []
    for r in range(b):
        val, g = head(x[r], norm_w, head_w, labels[r])
        total = total + val
        dx.append(g / (b * s))
    del norm_w, head_w
    loss = total / (b * s)
    grads = [None] * d.layers
    for i in reversed(range(d.layers)):
        w = layer_weights(get, i)
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
    the last step."""
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
