"""Plain reference for a hybrid decoder of delta-rule linear-attention layers
(KDA) and latent-attention layers (MLA) with sigmoid-routed experts
(Ling-3.0-flash-VL's language model), as one chip's share of an
expert-parallel job.

    x = E[ids]
    for each layer l:
        h = N_mix(x);  x = x + (KDA(h) if layer_types[l] == "kda" else MLA(h))
        m = N_mlp(x);  x = x + (Dense(m) if l < first_k_dense_replace else Routed(m) + Shared(m))
    logits = N_f(x) W_head                                  the head is untied

`KDA` on u [S, D], H heads, key and value width P, a depthwise causal
convolution of K taps WITHOUT bias (zeros before the sequence):

    q = l2norm(silu(conv(u W_q))) / sqrt(P)    k = l2norm(silu(conv(u W_k)))    v = silu(conv(u W_v))
    g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (u_t W_f + dt_bias))     [H, P], in (bound, 0)
    beta_t = sigmoid(u_t W_b)                                               [H]
    per head, S_0 = 0 in R^(P x P):
        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T        o_t = S_t^T q_t
    y = [RMSNorm_P(o_t) * w * sigmoid(u_t W_g)] W_o           the norm a head, the gate after it
    l2norm(x) = x / sqrt(sum x^2 + 1e-6)

**How the recurrence is evaluated.** Literally, one position at a time, in
float32 (`lax.scan`), in segments of `SEGMENT` positions whose body is a
`jax.checkpoint`: the backward keeps the state at each segment's start
(`[H, P, P]`) and walks the segment again. No chunked form, no triangular
solve: nothing of the program's algorithm.

`MLA` on u [S, D], H heads, a latent of L, per head N score columns without
position and R with, values of V:

    q = u W_q -> [H, N + R]          [c | k_r] = u W_kva -> L + R          c = RMSNorm(c) * w_c
    [k_n | v]_h = c W_kvb -> [H, N + V]          k_h = [k_n,h | k_r]      (k_r shared by every head)
    q_h, k_h <- RMSNorm_(N+R)(.) * w_q, w_k;  then the LAST R of each rotated, theta = rope_theta,
                                               pairs (first half, second half) of the R
    o_h = softmax_causal(q_h k_h^T / sqrt(N + R)) v_h;    o_h <- o_h * sigmoid(u W_gate)_h;    y = o W_o

`Routed`: s = sigmoid(m W_r) over the router's published width; c = s + b
(`b` the frozen selection bias); the experts in `n_group` consecutive groups,
a group's score the sum of its two largest c; the `topk_group` best groups
kept; the `top_k` largest c among their experts; w_e = routed_scaling_factor
* s_e / sum of the chosen s. y = sum over the chosen e with lo <= e < lo +
held of w_e E_e(m), E_e(m) = (silu(m G_e) * (m U_e)) D_e; `Shared` and
`Dense` the same form, added as they are. **The share**: routing is over all
the router's experts and groups; the sum runs over the `held` experts from
`lo` only; with `lo = 0, held = router width` this file gives the uncut layer.

**Departures from the published description** (the configuration's `assumed`
has each with the key it reads): the vision tower and multi-token prediction
are left out; layer kinds come from `layer_types` (derived from
`layer_group_size`); `expert_swiglu_limit_list` reads 0 for the layers kept,
so nothing is clamped.

Straightforward float32 `jax.numpy`, every product at `Precision.HIGHEST`,
importing nothing of the program. A layer at a time (its weights fetched leaf
by leaf and dropped after it, its input kept for the backward), rows one at a
time, attention one head and one block of queries at a time, the held
experts one at a time over every token.

Leaf names (`get(name)`; the harness backs it with `cellbench/weights.py`):

    embed [V, D]   final_norm [D]   lm_head [D, V]
    layers.<i>.mixer_norm  .mlp_norm [D]
    kda:  layers.<i>.q .k .v .f .g [D, H*P]  .b [D, H]  .o [H*P, D]
          .q_conv .k_conv .v_conv [K, H*P]  .dt_bias [H*P]  .A_log [H]  .o_norm [P]
    mla:  layers.<i>.q [D, H*(N+R)]  .kv_a [D, L+R]  .kv_b [L, H*(N+V)]  .o [H*V, D]
          .kv_a_norm [L]  .q_norm .k_norm [N+R]  .attn_gate [D, H]
    dense MLP:  layers.<i>.gate .up [D, F]  .down [F, D]
    routed:     layers.<i>.router [D, E_published]  .router_bias [E_published]
                .experts.gate .up [held, D, Fe]  .experts.down [held, Fe, D]
                .shared.gate .up [D, Fs]  .shared.down [Fs, D]
    layers.<i>.<q|k|v|o|kv_a|kv_b>.lora_a [in, r]  .lora_b [r, out]         training only

`lora[i]` may hold more names than layer `i` has projections (the harness
asks every layer for every target); a layer reads its own mixer's only.

`products="int8"` is the control: the same mathematics with both operands of
every linear layer (router, gates and head included) rounded to 8 bits, per
token for activations and per output channel for weights. Attention's own
products and the recurrence stay float32 there, as in a W8A8 deployment.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
SEGMENT = 64  # positions of the recurrence under one checkpoint
L2_EPS = 1e-6

KDA_TARGETS = ("q", "k", "v", "o")
MLA_TARGETS = ("q", "kv_a", "kv_b", "o")


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    kinds: tuple  # "kda" or "mla" a layer
    dense: tuple  # True where the layer's MLP is dense
    heads: int
    head_dim: int  # KDA's key and value width
    conv: int
    gate_bound: float
    latent: int
    nope: int
    rope: int
    value: int
    theta: float
    qk_norm: bool
    mlp: int
    expert: int
    shared: int
    router: int  # the router's published width
    held: int
    lo: int
    top_k: int
    groups: int
    groups_kept: int
    routed_scale: float
    norm_topk: bool
    vocab: int
    eps: float

    @classmethod
    def from_published(cls, c: dict, lo: int | None = None, held: int | None = None) -> "Dims":
        """`num_experts` and `vocab_size` are the counts HELD; the router's
        published width stands beside them as `router_width`. `lo`/`held`
        override the share (the share test walks all of them)."""
        n = int(c["num_hidden_layers"])
        kinds = tuple(c["layer_types"])[:n]
        if set(kinds) - {"kda", "mla"}:
            raise ValueError(f"no reference for layer types {sorted(set(kinds))}")
        if c.get("score_function") != "sigmoid" or c.get("q_lora_rank") is not None:
            raise ValueError("this reference scores by a sigmoid and has a full q_proj")
        return cls(
            hidden=int(c["hidden_size"]),
            layers=n,
            kinds=kinds,
            dense=tuple(i < int(c["first_k_dense_replace"]) for i in range(n)),
            heads=int(c["num_attention_heads"]),
            head_dim=int(c["head_dim"]),
            conv=int(c["short_conv_kernel_size"]),
            gate_bound=float(c["kda_lower_bound"]),
            latent=int(c["kv_lora_rank"]),
            nope=int(c["qk_nope_head_dim"]),
            rope=int(c["qk_rope_head_dim"]),
            value=int(c["v_head_dim"]),
            theta=float(c["rope_theta"]),
            qk_norm=bool(c["use_qk_norm"]),
            mlp=int(c["intermediate_size"]),
            expert=int(c["moe_intermediate_size"]),
            shared=int(c["moe_shared_expert_intermediate_size"]),
            router=int(c.get("router_width") or c["num_experts"]),
            held=int(c["num_experts"] if held is None else held),
            lo=int(c.get("expert_offset", 0) if lo is None else lo),
            top_k=int(c["num_experts_per_tok"]),
            groups=int(c["n_group"]),
            groups_kept=int(c["topk_group"]),
            routed_scale=float(c["routed_scaling_factor"]),
            norm_topk=bool(c["norm_topk_prob"]),
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


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def conv1d_causal(x, kernel):
    """x [S, C], kernel [K, C]: y_t = sum_k kernel[k] x_{t-(K-1)+k}."""
    k, s = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(kernel[i] * padded[i : i + s] for i in range(k))


def delta_rule(q, k, v, g, beta, segment: int = SEGMENT):
    """q, k, g [S, H, P]; v [S, H, V]; beta [S, H] -> o [S, H, V]: the
    recurrence of the module's docstring, one position at a time, the state
    `[H, P, V]` carried in float32; a checkpoint a segment."""
    s, h, p = q.shape
    seg = min(segment, s)
    if s % seg:
        raise ValueError(f"{s} positions are no multiple of the segment {seg}")

    def step(state, args):
        qt, kt, vt, gt, bt = args  # [H, P], [H, P], [H, V], [H, P], [H]
        state = jnp.exp(gt)[:, :, None] * state
        seen = jnp.einsum("hpv,hp->hv", state, kt, precision=HI)
        state = state + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None, :]
        return state, jnp.einsum("hpv,hp->hv", state, qt, precision=HI)

    @jax.checkpoint
    def walk(state, args):
        return jax.lax.scan(step, state, args)

    cut = lambda x: x.reshape(s // seg, seg, *x.shape[1:])  # noqa: E731
    state0 = jnp.zeros((h, p, v.shape[2]), jnp.float32)
    _, out = jax.lax.scan(walk, state0, tuple(cut(x) for x in (q, k, v, g, beta)))
    return out.reshape(s, h, v.shape[2])


def _rotate_tail(x, rot: int, theta: float):
    """The last `rot` of each head rotated: pairs (first half, second half)."""
    s = x.shape[0]
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    keep, x1, x2 = x[..., :-rot], x[..., -rot : -half], x[..., -half:]
    return jnp.concatenate([keep, x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _attend(q, k, v):
    """q, k [S, H, dq], v [S, H, dv] -> [S, H, dv]; causal, scores over
    sqrt(dq). One head and one block of queries at a time."""
    s, h, dq = q.shape
    bq = min(s, Q_BLOCK)
    nb = s // bq
    qb = q.reshape(nb, bq, h, dq).transpose(2, 0, 1, 3).reshape(h * nb, bq, dq)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [H, S, d]
    which = jnp.arange(h * nb)
    cols = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(args):
        qg, ix = args
        j, b = ix // nb, ix % nb
        rows = b * bq + jnp.arange(bq)[:, None]
        sc = jnp.einsum("qd,kd->qk", qg, kt[j], precision=HI) * dq**-0.5
        p = jax.nn.softmax(jnp.where(cols <= rows, sc, -jnp.inf), axis=-1)
        return jnp.einsum("qk,kd->qd", p, vt[j], precision=HI)

    out = jax.lax.map(block, (qb, which))  # [H * nb, bq, dv]
    return out.reshape(h, nb, bq, -1).transpose(1, 2, 0, 3).reshape(s, h, -1)


def _swiglu(m, gate, up, down, mm):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def routing_weights(scores, bias, d: Dims):
    """scores [T, E] = sigmoid of the router's logits. Returns the chosen
    experts [T, k] and their weights [T, k]."""
    choice = scores + bias
    if d.groups > 1:
        t = choice.shape[0]
        grouped = choice.reshape(t, d.groups, -1)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        kept = jax.lax.top_k(group_score, d.groups_kept)[1]  # [T, kept]
        alive = jnp.any(kept[:, :, None] == jnp.arange(d.groups)[None, None, :], axis=1)
        choice = jnp.where(alive[:, :, None], grouped, -jnp.inf).reshape(t, -1)
    top_e = jax.lax.top_k(choice, d.top_k)[1]
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    if d.norm_topk:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    return top_e, top_s * d.routed_scale


def _routed(m, w, d: Dims, mm):
    """sum over the held experts of w_e E_e(m): the experts one at a time
    over every token, the weight nought where the token is not routed to it."""
    top_e, top_w = routing_weights(jax.nn.sigmoid(mm(m, w["router"])), w["router_bias"], d)
    held = d.lo + jnp.arange(d.held)
    weight = jnp.sum(
        jnp.where(top_e[:, :, None] == held[None, None, :], top_w[:, :, None], 0.0), axis=1
    )  # [T, held]

    @jax.checkpoint
    def expert(gate, up, down, we):
        return we[:, None] * _swiglu(m, gate, up, down, mm)

    def add(acc, args):
        return acc + expert(*args), None

    out, _ = jax.lax.scan(
        add, jnp.zeros_like(m), (w["experts.gate"], w["experts.up"], w["experts.down"], weight.T)
    )
    return out


def _proj(h, w, lora, name, scale, mm):
    y = mm(h, w[name])
    if name in lora:
        y = y + scale * mm(mm(h, lora[name]["lora_a"]), lora[name]["lora_b"])
    return y


def _kda(u, w, lora, d: Dims, scale, mm):
    s = u.shape[0]
    h, p = d.heads, d.head_dim
    heads = lambda x: x.reshape(s, h, -1)  # noqa: E731

    def short(name):
        return jax.nn.silu(conv1d_causal(_proj(u, w, lora, name, scale, mm), w[f"{name}_conv"]))

    q = _l2norm(heads(short("q"))) * p**-0.5
    k = _l2norm(heads(short("k")))
    v = heads(short("v"))
    rate = jnp.repeat(jnp.exp(w["A_log"]), p)
    g = heads(d.gate_bound * jax.nn.sigmoid(rate * (mm(u, w["f"]) + w["dt_bias"])))
    beta = jax.nn.sigmoid(mm(u, w["b"]))
    o = delta_rule(q, k, v, g, beta)
    o = _rms(o, w["o_norm"], d.eps).reshape(s, h * p) * jax.nn.sigmoid(mm(u, w["g"]))
    return _proj(o, w, lora, "o", scale, mm)


def _mla(u, w, lora, d: Dims, scale, mm):
    s, h = u.shape[0], d.heads
    q = _proj(u, w, lora, "q", scale, mm).reshape(s, h, d.nope + d.rope)
    kv_a = _proj(u, w, lora, "kv_a", scale, mm)
    c = _rms(kv_a[:, : d.latent], w["kv_a_norm"], d.eps)
    kv_b = _proj(c, w, lora, "kv_b", scale, mm).reshape(s, h, d.nope + d.value)
    k_r = jnp.broadcast_to(kv_a[:, None, d.latent :], (s, h, d.rope))
    k = jnp.concatenate([kv_b[..., : d.nope], k_r], axis=-1)
    v = kv_b[..., d.nope :]
    if d.qk_norm:
        q, k = _rms(q, w["q_norm"], d.eps), _rms(k, w["k_norm"], d.eps)
    o = _attend(_rotate_tail(q, d.rope, d.theta), _rotate_tail(k, d.rope, d.theta), v)
    o = o * jax.nn.sigmoid(mm(u, w["attn_gate"]))[:, :, None]
    return _proj(o.reshape(s, h * d.value), w, lora, "o", scale, mm)


def _layer(w, lora, x, *, d: Dims, i: int, scale: float, mm):
    """Block `i` on one sequence: x [S, D] -> [S, D]. `lora` holds the
    layer's own mixer's adapters."""
    a = _rms(x, w["mixer_norm"], d.eps)
    mixer = _kda if d.kinds[i] == "kda" else _mla
    h = x + mixer(a, w, lora, d, scale, mm)
    m = _rms(h, w["mlp_norm"], d.eps)
    if d.dense[i]:
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
    mixer = (
        ("q", "k", "v", "f", "g", "b", "o", "q_conv", "k_conv", "v_conv", "dt_bias", "A_log",
         "o_norm")
        if d.kinds[i] == "kda"
        else ("q", "kv_a", "kv_b", "o", "kv_a_norm", "attn_gate")
        + (("q_norm", "k_norm") if d.qk_norm else ())
    )
    mlp = ("gate", "up", "down") if d.dense[i] else (
        "router", "router_bias", "experts.gate", "experts.up", "experts.down",
        "shared.gate", "shared.up", "shared.down",
    )
    return ("mixer_norm", "mlp_norm") + mixer + mlp


def layer_weights(get, d: Dims, i: int) -> dict:
    return {n: get(f"layers.{i}.{n}") for n in layer_leaves(d, i)}


def own_adapters(d: Dims, i: int, lora_i: dict) -> dict:
    """Of what the harness handed layer `i`, the adapters its mixer has."""
    names = KDA_TARGETS if d.kinds[i] == "kda" else MLA_TARGETS
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
        xs.append(embed[ids])
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
