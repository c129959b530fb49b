"""Plain reference for a dense pre-norm decoder (InternLM2, Mistral-7B, ...).

RMSNorm, rotary positions on (first half, second half) pairs as the public
checkpoints of these families use them, grouped-query causal attention,
SwiGLU MLP, untied head. Straightforward float32 `jax.numpy`, every product
at `Precision.HIGHEST`; no kernel, no cache, no batching (rows one at a
time), layer by layer so that it fits beside nothing else on a 16 GB chip.
It imports nothing of the program. Weights come through `get(name)`, which
the harness backs with `cellbench/weights.py`; names are this file's own:

    embed [V, D]   final_norm [D]   lm_head [D, V]
    layers.<i>.attn_norm  layers.<i>.mlp_norm                      [D]
    layers.<i>.q [D, H*hd]  .k .v [D, KV*hd]  .o [H*hd, D]
    layers.<i>.gate .up [D, F]  .down [F, D]
    layers.<i>.<proj>.lora_a [in, r]  .lora_b [r, out]   (training only)

Departure from the published checkpoints: InternLM2 fuses q/k/v into one
`wqkv`; separate projections of the same shapes are the same mathematics on
seeded weights.

`products="int8"` is the control: the same mathematics with both operands of
every linear layer rounded to 8 bits (per token for activations, per output
channel for weights), the precision just below the bfloat16 the
configurations state. Attention's own products stay float32 there, as in a
W8A8 deployment.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
PROJS = ("q", "k", "v", "o", "gate", "up", "down")
NORMS = ("attn_norm", "mlp_norm")


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    mlp: int
    vocab: int
    rope_theta: float
    eps: float

    @classmethod
    def from_published(cls, c: dict) -> "Dims":
        heads = int(c["num_attention_heads"])
        return cls(
            hidden=int(c["hidden_size"]),
            layers=int(c["num_hidden_layers"]),
            heads=heads,
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim") or int(c["hidden_size"]) // heads),
            mlp=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]),
            rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
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


# --------------------------------------------------------------------- block
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, H, hd]; position = row index."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _proj(h, w, lora, name, scale, mm):
    y = mm(h, w[name])
    if name in lora:
        y = y + scale * mm(mm(h, lora[name]["lora_a"]), lora[name]["lora_b"])
    return y


def _layer(w, lora, x, *, d: Dims, scale: float, mm):
    """One block on one sequence: x [S, D] -> [S, D]."""
    s = x.shape[0]
    h = _rms(x, w["attn_norm"], d.eps)
    q = _proj(h, w, lora, "q", scale, mm).reshape(s, d.heads, d.head_dim)
    k = _proj(h, w, lora, "k", scale, mm).reshape(s, d.kv_heads, d.head_dim)
    v = _proj(h, w, lora, "v", scale, mm).reshape(s, d.kv_heads, d.head_dim)
    q, k = _rope(q, d.rope_theta), _rope(k, d.rope_theta)
    g = d.heads // d.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(d.head_dim)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(s, -1)
    x = x + _proj(a, w, lora, "o", scale, mm)
    h = _rms(x, w["mlp_norm"], d.eps)
    gate = _proj(h, w, lora, "gate", scale, mm)
    up = _proj(h, w, lora, "up", scale, mm)
    return x + _proj(jax.nn.silu(gate) * up, w, lora, "down", scale, mm)


def _head_loss(x, norm_w, head_w, labels, *, d: Dims, mm):
    """Sum over the row's tokens of the next-token cross entropy."""
    logits = mm(_rms(x, norm_w, d.eps), head_w)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


@functools.lru_cache(maxsize=None)
def _fns(d: Dims, scale: float, products: str):
    mm = PRODUCTS[products]
    layer = functools.partial(_layer, d=d, scale=scale, mm=mm)
    head = functools.partial(_head_loss, d=d, mm=mm)

    def layer_bwd(w, lora, x, dy):
        _, vjp = jax.vjp(lambda lo, xx: layer(w, lo, xx), lora, x)
        return vjp(dy)  # (dlora, dx)

    def logits_at(x, norm_w, head_w, rows):
        return mm(_rms(x[rows], norm_w, d.eps), head_w)

    return (
        jax.jit(layer),
        jax.jit(layer_bwd),
        jax.jit(jax.value_and_grad(head)),
        jax.jit(logits_at),
    )


def layer_weights(get, i: int) -> dict:
    return {n: get(f"layers.{i}.{n}") for n in PROJS + NORMS}


# ------------------------------------------------------------------- serving
def logits_for(get, d: Dims, seqs, rows, products="float32", pad_to=512, rows_to=256):
    """Full forward over each sequence (prompt + served tokens), layer by
    layer with each layer's weights fetched once for all sequences. Returns
    for sequence j the float32 logits at positions `rows[j]`. Sequences are
    right-padded to a multiple of `pad_to` (causal: padding cannot reach
    back) and the rows asked for to a multiple of `rows_to`, so that few
    shapes compile."""
    layer, _, _, logits_at = _fns(d, 0.0, products)
    embed = get("embed")
    xs = []
    for s in seqs:
        n = -(-len(s) // pad_to) * pad_to
        ids = jnp.zeros((n,), jnp.int32).at[: len(s)].set(jnp.asarray(s, jnp.int32))
        xs.append(embed[ids])
    del embed
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
    gradient for the LoRA leaves. `lora[i][proj] = {lora_a, lora_b}`.
    Forward keeps each layer's input; backward runs layer by layer from the
    top, one row at a time, with the layer's weights fetched again."""
    layer, layer_bwd, head, _ = _fns(d, float(scale), products)
    b, s = tokens.shape
    embed = get("embed")
    x = [embed[tokens[r]] for r in range(b)]
    del embed
    inputs = []
    for i in range(d.layers):
        w = layer_weights(get, i)
        inputs.append(x)
        x = [layer(w, lora[i], xr) for xr in x]
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
