"""Plain reference for a decoder of mixed window/full attention with sparse
experts (Laguna-S-2.1), as one chip's share of an expert-parallel job.

Per layer `l` with `H_l` query heads, `KV` key/value heads of width `hd`,
RMSNorm `N`:

    a = N_att(x);  q = a Wq -> [T, H_l, hd];  k = a Wk, v = a Wv -> [T, KV, hd]
    full layer:    the FIRST rot = partial_rotary_factor * hd of each head
                   rotated with YaRN frequencies (inv_freq blended between
                   theta^(-2i/rot) and that / factor by the linear ramp between
                   the correction dims of beta_fast and beta_slow over
                   original_max_position_embeddings), cos and sin multiplied
                   by attention_factor
    sliding layer: all of the head rotated with its own theta, no scaling
    s_ij = q_i . k_j / sqrt(hd), key head = query head // (H_l / KV)
    allowed: j <= i, and in a sliding layer also i - j < window
    o_h = softmax_j(s) v
    g = sigmoid(a Wg) -> [T, H_l]                    one scalar a head and token
    h = x + concat_h(g_h * o_h) Wo
    m = N_mlp(h)
    dense layer:   y = h + (silu(m W1) * (m W3)) W2
    sparse layer:  p = softmax(m Wr) over the router's published width
                   S = the top_k largest of p;  w_e = scale * p_e / sum_{S} p
                   y = h + sum_{e in S, lo <= e < lo + held} w_e E_e(m) + E_shared(m)
    logits = N_f(x_L) W_head

Rotary pairs are (first half, second half) of the rotated width, as the
program's `apply_rope` has them. **The share**: routing is over all the
router's experts; the sum runs over the `held` experts from `lo` only, and
what the absent ones would add is left out (the program leaves out the same).
With `lo = 0, held = router width` this file gives the uncut layer.

Straightforward float32 `jax.numpy`, every product at `Precision.HIGHEST`,
importing nothing of the program. It works **in blocks** so that the
published widths fit beside nothing else on a 16 GB chip: rows one at a time,
a layer's weights fetched leaf by leaf and dropped after it, attention over
one key head's group of query heads and one block of queries at a time (the
scores of a sliding layer at 4,096 tokens are 4.8 GB a row whole), the held
experts one at a time over every token with the weight of the tokens not
routed to them nought. Each block's body is a `jax.checkpoint`, so the
backward pass keeps a block's inputs and not its scores.

Leaf names (`get(name)`; the harness backs it with `cellbench/weights.py`):

    embed [V, D]   final_norm [D]   lm_head [D, V]
    layers.<i>.attn_norm  .mlp_norm [D]
    layers.<i>.q [D, H_i*hd]  .k .v [D, KV*hd]  .o [H_i*hd, D]  .attn_gate [D, H_i]
    layers.<i>.gate .up [D, F]  .down [F, D]                      dense layers only
    layers.<i>.router [D, E_published]                            sparse layers only
    layers.<i>.experts.gate .up [held, D, Fe]  .experts.down [held, Fe, D]
    layers.<i>.shared.gate .up [D, Fs]  .shared.down [Fs, D]
    layers.<i>.<q|k|v|o>.lora_a [in, r]  .lora_b [r, out]         training only

`products="int8"` is the control: the same mathematics with both operands of
every linear layer (router and gate included) rounded to 8 bits, per token
for activations and per output channel for weights. Attention's own products
stay float32 there, as in a W8A8 deployment.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Rope:
    theta: float
    rotary: float = 1.0  # share of the head's width that rotates
    yarn_factor: float = 0.0  # 0: plain
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def from_published(cls, r: dict) -> "Rope":
        if r.get("rope_type", "default") == "default":
            return cls(float(r["rope_theta"]), float(r.get("partial_rotary_factor", 1.0)))
        if r["rope_type"] != "yarn":
            raise ValueError(f"no reference for rope_type {r['rope_type']!r}")
        return cls(
            float(r["rope_theta"]), float(r.get("partial_rotary_factor", 1.0)),
            float(r["factor"]), int(r["original_max_position_embeddings"]),
            float(r["beta_fast"]), float(r["beta_slow"]), float(r["attention_factor"]),
        )


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    heads: tuple  # query heads of each layer
    windows: tuple  # 0 = full causal attention
    ropes: tuple  # a Rope a layer
    sparse: tuple  # True where the layer's MLP is routed
    kv_heads: int
    head_dim: int
    mlp: int  # the dense layers' width
    expert: int
    shared: int
    router: int  # the router's published width
    held: int
    lo: int
    top_k: int
    norm_topk: bool
    routed_scale: float
    vocab: int
    eps: float

    @classmethod
    def from_published(cls, c: dict, lo: int | None = None, held: int | None = None) -> "Dims":
        """`num_experts` and `vocab_size` are the counts HELD; the router's
        published width stands beside them as `router_width`. `lo`/`held`
        override the share (the share test walks all of them)."""
        n = int(c["num_hidden_layers"])
        kinds = list(c["layer_types"])[:n]
        dense = set(c.get("mlp_only_layers") or ())
        if "mlp_layer_types" in c:
            dense |= {i for i, t in enumerate(c["mlp_layer_types"][:n]) if t == "dense"}
        ropes = {k: Rope.from_published(v) for k, v in c["rope_parameters"].items()}
        return cls(
            hidden=int(c["hidden_size"]),
            layers=n,
            heads=tuple(int(h) for h in c["num_attention_heads_per_layer"][:n]),
            windows=tuple(
                int(c["sliding_window"]) if k == "sliding_attention" else 0 for k in kinds
            ),
            ropes=tuple(ropes[k] for k in kinds),
            sparse=tuple(i not in dense for i in range(n)),
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            mlp=int(c["intermediate_size"]),
            expert=int(c["moe_intermediate_size"]),
            shared=int(c["shared_expert_intermediate_size"]),
            router=int(c.get("router_width") or c["num_experts"]),
            held=int(c["num_experts"] if held is None else held),
            lo=int(c.get("expert_offset", 0) if lo is None else lo),
            top_k=int(c["num_experts_per_tok"]),
            norm_topk=bool(c["norm_topk_prob"]),
            routed_scale=float(c["moe_routed_scaling_factor"]),
            vocab=int(c["vocab_size"]),
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


# --------------------------------------------------------------------- parts
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _inv_freq(rope: Rope, rot: int):
    half = rot // 2
    pos = rope.theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    if not rope.yarn_factor:
        return 1.0 / pos

    def correction_dim(rotations):
        return rot * math.log(rope.original_len / (rotations * 2 * math.pi)) / (
            2 * math.log(rope.theta)
        )

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return (1.0 / (rope.yarn_factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def _rope(x, rope: Rope):
    """x [S, H, hd]; position = row index."""
    s, _, hd = x.shape
    rot = int(hd * rope.rotary)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * _inv_freq(rope, rot)[None, :]
    c = (jnp.cos(ang) * rope.attention_factor)[:, None, :]
    sn = (jnp.sin(ang) * rope.attention_factor)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2 : rot], x[..., rot:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn, rest], -1)


def _attend(q, k, v, window: int):
    """q [S, H, hd], k and v [S, KV, hd] -> [S, H, hd]; causal, and with a
    window > 0 only the `window` newest keys. One key head's group of query
    heads against all keys, one block of queries at a time."""
    s, h, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    bq = min(s, Q_BLOCK)
    nb = s // bq
    # [KV * nb, G, bq, hd]: block (j, b) holds queries b*bq.. of key head j's group
    qb = q.reshape(nb, bq, kv, g, hd).transpose(2, 0, 3, 1, 4).reshape(kv * nb, g, bq, hd)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [KV, S, hd]
    which = jnp.arange(kv * nb)
    cols = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(args):
        qg, ix = args
        j, b = ix // nb, ix % nb
        rows = b * bq + jnp.arange(bq)[:, None]
        ok = cols <= rows
        if window:
            ok = ok & (rows - cols < window)
        sc = jnp.einsum("gqd,kd->gqk", qg, kt[j], precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vt[j], precision=HI)

    out = jax.lax.map(block, (qb, which))  # [KV * nb, G, bq, hd]
    return out.reshape(kv, nb, g, bq, hd).transpose(1, 3, 0, 2, 4).reshape(s, h, hd)


def _swiglu(m, gate, up, down, mm):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def _routed(m, w, d: Dims, mm):
    """sum over the held experts of w_e E_e(m): the experts one at a time
    over every token, the weight nought where the token is not routed to it."""
    probs = jax.nn.softmax(mm(m, w["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, d.top_k)
    top_w = top_p / jnp.sum(top_p, -1, keepdims=True) if d.norm_topk else top_p
    top_w = top_w * d.routed_scale
    # [T, held]: the weight of held expert lo + e for each token, 0 if unchosen
    held = d.lo + jnp.arange(d.held)
    weight = jnp.sum(
        jnp.where(top_e[:, :, None] == held[None, None, :], top_w[:, :, None], 0.0), axis=1
    )

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


def _layer(w, lora, x, *, d: Dims, i: int, scale: float, mm):
    """Block `i` on one sequence: x [S, D] -> [S, D]."""
    s = x.shape[0]
    nh, hd = d.heads[i], d.head_dim
    a = _rms(x, w["attn_norm"], d.eps)
    q = _proj(a, w, lora, "q", scale, mm).reshape(s, nh, hd)
    k = _proj(a, w, lora, "k", scale, mm).reshape(s, d.kv_heads, hd)
    v = _proj(a, w, lora, "v", scale, mm).reshape(s, d.kv_heads, hd)
    q, k = _rope(q, d.ropes[i]), _rope(k, d.ropes[i])
    o = _attend(q, k, v, d.windows[i])
    g = jax.nn.sigmoid(mm(a, w["attn_gate"]))  # [S, nh]
    h = x + _proj((o * g[:, :, None]).reshape(s, nh * hd), w, lora, "o", scale, mm)
    m = _rms(h, w["mlp_norm"], d.eps)
    if not d.sparse[i]:
        return h + _swiglu(m, w["gate"], w["up"], w["down"], mm)
    shared = _swiglu(m, w["shared.gate"], w["shared.up"], w["shared.down"], mm)
    return h + _routed(m, w, d, mm) + shared


def _head_loss(x, norm_w, head_w, labels, *, d: Dims, mm):
    """Sum over the row's tokens of the next-token cross entropy."""
    logits = mm(_rms(x, norm_w, d.eps), head_w)
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

    def logits_at(x, norm_w, head_w, rows):
        return mm(_rms(x[rows], norm_w, d.eps), head_w)

    return jax.jit(jax.value_and_grad(head)), jax.jit(logits_at)


def layer_leaves(d: Dims, i: int) -> tuple:
    names = ("attn_norm", "mlp_norm", "q", "k", "v", "o", "attn_gate")
    if not d.sparse[i]:
        return names + ("gate", "up", "down")
    return names + (
        "router", "experts.gate", "experts.up", "experts.down",
        "shared.gate", "shared.up", "shared.down",
    )


def layer_weights(get, d: Dims, i: int) -> dict:
    return {n: get(f"layers.{i}.{n}") for n in layer_leaves(d, i)}


# ------------------------------------------------------------------- forward
def logits_for(get, d: Dims, seqs, rows, products="float32", pad_to=512, rows_to=256):
    """Full forward over each sequence, layer by layer with each layer's
    weights fetched once for all sequences; for sequence j the float32
    logits at positions `rows[j]`. Sequences are right-padded to a multiple
    of `pad_to` (causal: padding cannot reach back)."""
    _, logits_at = _head_fns(d, products)
    embed = get("embed")
    xs = []
    for s in seqs:
        n = -(-len(s) // pad_to) * pad_to
        ids = jnp.zeros((n,), jnp.int32).at[: len(s)].set(jnp.asarray(s, jnp.int32))
        xs.append(embed[ids])
    del embed
    for i in range(d.layers):
        layer, _ = _layer_fns(d, i, 0.0, products)
        w = layer_weights(get, d, i)
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
    head, _ = _head_fns(d, products)
    b, s = tokens.shape
    embed = get("embed")
    x = [embed[tokens[r]] for r in range(b)]
    del embed
    inputs = []
    for i in range(d.layers):
        layer, _ = _layer_fns(d, i, float(scale), products)
        w = layer_weights(get, d, i)
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
