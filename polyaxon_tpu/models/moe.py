"""Mixture-of-Experts FFN: top-k routing over all the experts, grouped
products over the experts held here, nothing dropped.

One routed layer for both uses:

- the whole layer on one process or mesh (`held == n_experts`): the stacked
  expert kernels carry a leading expert dim that `MOE_RULES` shard over the
  mesh `expert` axis;
- one chip's share of an expert-parallel deployment (`held < n_experts`): the
  router keeps its published width, the top-k and their weights are the
  published ones, and this process computes the part of the result that
  experts `[offset, offset + held)` give. What the absent experts would add is
  left out; no code stands in for the other chips or their exchange.

How: router logits and softmax in f32; the k largest of all `n_experts`;
`w = p / sum of the k` where `norm_topk`, times `routed_scale`. A router of
DeepSeek-V3's kind (`score="sigmoid"`, `bias`, `groups`) scores by a sigmoid
of logits kept in float32,
chooses by `c = s + b` with `b` a frozen selection bias (`router_bias`), and
where `groups > 1` only among the experts of the `groups_kept` best of
`groups` consecutive groups (a group's score: the sum of its two largest
`c`); the weights are the chosen experts' `s`, never `c`. The
assignments to held experts are ordered by expert (one stable argsort) into a
buffer of static length, their tokens' rows gathered, the three SwiGLU
products taken as grouped products over the held experts
(`jax.lax.ragged_dot`: on the TPU one grouped-matmul kernel, `ragged-dot` in a
device trace; no `[B,S,E,C]` mask, no per-expert capacity), and the results
added back to their tokens with `w`, in f32.

The buffer holds `buffer_factor` times the expected count of local
assignments and never more than the worst case (`tokens * min(top_k, held)`);
with every expert held and top-1 it is the worst case. What does not fit is
counted, not hidden: the layer sows `overflow` into `moe_stats`, with
`assignments_local` and `load_max_over_mean`, and the Trainer stops on a
non-zero reading.

The Switch load-balancing loss (`aux_weight * E * sum_e f_e p_e`) is sown
into `losses` where `aux_weight > 0`; a frozen router asks for none.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

_ROW_TILE = 512  # buffer rows are a multiple of it: whole tiles for the kernel


def buffer_rows(tokens: int, top_k: int, held: int, n_experts: int,
                factor: float) -> int:
    """Rows of the routed layer's buffer for `tokens` tokens."""
    worst = tokens * min(top_k, held)
    expected = tokens * top_k * held / n_experts
    rows = math.ceil(factor * expected / _ROW_TILE) * _ROW_TILE
    return max(1, min(worst, rows))


class MoEFeedForward(nn.Module):
    dim: int
    ffn_dim: int  # one expert's width
    n_experts: int  # the router's width: the published count
    held: int = 0  # experts computed here; 0 = all
    offset: int = 0  # the first of them
    top_k: int = 1
    routed_scale: float = 1.0
    norm_topk: bool = False
    aux_weight: float = 0.01
    buffer_factor: float = 2.0
    router_noise: float = 0.0
    score: str = "softmax"  # softmax | sigmoid
    bias: bool = False  # a selection bias a expert, added for the choice only
    groups: int = 1  # > 1: the choice is limited to the best `groups_kept` groups
    groups_kept: int = 1

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        B, S, D = x.shape
        E, K = self.n_experts, self.top_k
        held = self.held or E
        T = B * S
        m = x.reshape(T, D)

        # a sigmoid router's choice hangs on gaps of a thousandth between group
        # scores: its logits come out of the product in float32 (as the
        # published gate computes them), not rounded to the activations' type
        # first (bf16 near 2.3 steps by 0.016: a token in five then keeps
        # another group than float32 does)
        router_dtype = jnp.float32 if self.score == "sigmoid" else None
        logits = nn.Dense(E, use_bias=False, name="router", dtype=router_dtype)(m).astype(
            jnp.float32
        )
        if train and self.router_noise > 0:
            rng = self.make_rng("dropout")
            logits = logits + self.router_noise * jax.random.normal(
                rng, logits.shape, jnp.float32
            )
        if self.score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
        elif self.score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"unknown router score {self.score!r} (known: softmax, sigmoid)")
        if self.bias or self.groups > 1:
            b = (
                self.param("router_bias", nn.initializers.zeros, (E,)) if self.bias else None
            )
            top_e = limited_choice(probs, K, b, self.groups, self.groups_kept)
            top_p = jnp.take_along_axis(probs, top_e, axis=-1)
        else:
            top_p, top_e = jax.lax.top_k(probs, K)  # [T, K]
        weight = top_p / jnp.sum(top_p, -1, keepdims=True) if self.norm_topk else top_p
        weight = weight * self.routed_scale

        if self.aux_weight > 0:
            # Switch eq. 4 over the k choices: E * sum_e f_e * p_e
            chosen = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
            aux = E * jnp.sum(chosen / (T * K) * probs.mean(axis=0))
            self.sow("losses", "moe_aux", self.aux_weight * aux)

        # ---- the assignments to experts held here, ordered by expert
        local = top_e - self.offset
        local = jnp.where((local >= 0) & (local < held), local, held)  # held = elsewhere
        flat = local.reshape(T * K)
        rows = buffer_rows(T, K, held, E, self.buffer_factor)
        order = jnp.argsort(flat, stable=True)
        by_expert = flat[order]  # ascending; `held` marks the ones elsewhere
        # where each held expert's run starts in the order: its count, with
        # no scatter over the assignments
        starts = jnp.searchsorted(by_expert, jnp.arange(held + 1), side="left")
        counts = jnp.diff(starts).astype(jnp.int32)
        order = order[:rows]
        token = order // K
        here = by_expert[:rows] < held
        # what fits: the buffer cuts the last experts' tails, never a middle
        ends = jnp.minimum(jnp.cumsum(counts), rows)
        sizes = jnp.diff(ends, prepend=0)
        total = jnp.sum(counts)
        self.sow("moe_stats", "assignments_local", total)
        self.sow(
            "moe_stats", "load_max_over_mean",
            jnp.max(counts) * held / jnp.maximum(total, 1),
        )
        self.sow("moe_stats", "overflow", total - ends[-1])

        # ---- grouped SwiGLU over the held experts
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        wg = self.param("gate_kernel", init, (held, D, self.ffn_dim))
        wu = self.param("up_kernel", init, (held, D, self.ffn_dim))
        wd = self.param("down_kernel", init, (held, self.ffn_dim, D))
        # rows past the groups are no expert's: they go in as zeros and come
        # out as zeros whatever the kernel leaves there, forward and backward
        picked = jnp.where(here[:, None], m[token], 0)  # [rows, D]
        dt = picked.dtype
        h = nn.silu(jax.lax.ragged_dot(picked, wg.astype(dt), sizes))
        h = h * jax.lax.ragged_dot(picked, wu.astype(dt), sizes)
        y = jax.lax.ragged_dot(h, wd.astype(dt), sizes).astype(jnp.float32)
        w_row = weight.reshape(T * K)[order]
        # the rows no expert owns are cleared BEFORE the weights multiply
        # them: what the kernel leaves there may be NaN, and the weights'
        # cotangent is a product with these rows (0 x NaN on the way back)
        y = jnp.where(here[:, None], y, 0.0) * w_row[:, None]
        out = jnp.zeros((T, D), jnp.float32).at[token].add(y)
        return out.astype(x.dtype).reshape(B, S, D)


def limited_choice(scores, top_k: int, bias=None, groups: int = 1, groups_kept: int = 1):
    """The `top_k` experts [T, k] by `scores + bias` (scores [T, E] float32),
    among the experts of the `groups_kept` of `groups` consecutive groups
    whose two best sum highest."""
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if groups > 1:
        tokens = choice.shape[0]
        grouped = choice.reshape(tokens, groups, -1)
        best_two = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [T, groups]
        _, kept = jax.lax.top_k(best_two, groups_kept)
        keep = jnp.any(kept[:, :, None] == jnp.arange(groups), axis=1)  # [T, groups]
        choice = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(tokens, -1)
    return jax.lax.top_k(choice, top_k)[1]


# sharding rules for stacked expert weights: expert dim over `expert` axis,
# hidden dim over `model` (TP within each expert)
MOE_RULES = (
    (r"(gate_kernel|up_kernel)$", ("expert", "fsdp", "model")),
    (r"down_kernel$", ("expert", "model", "fsdp")),
    (r"router/kernel", (None, None)),
)
