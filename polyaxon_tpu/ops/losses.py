"""Loss registry. All losses take (logits, batch) and return a scalar f32 —
computed in float32 regardless of compute dtype: reductions on bf16
accumulate error, and the scalar is HBM-free anyway.

Batch schema: dict with "inputs" plus task-specific targets:
  classification: "labels" int32 [B]
  mlm:            "labels" int32 [B,S] with -100 = unmasked (ignored)
  lm:             "labels" int32 [B,S] shifted next-token targets, -100 pad
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import optax

_LOSSES: dict[str, Callable] = {}


def register_loss(name: str):
    def deco(fn):
        _LOSSES[name] = fn
        return fn

    return deco


def build_loss(name: str) -> Callable:
    if name not in _LOSSES:
        raise ValueError(f"unknown loss {name!r}; registered: {sorted(_LOSSES)}")
    return _LOSSES[name]


@register_loss("softmax_cross_entropy")
def softmax_cross_entropy(logits, batch):
    labels = batch["labels"]
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    )
    return losses.mean()


@register_loss("masked_lm")
def masked_lm(logits, batch):
    """Cross entropy over positions with label != -100 (BERT MLM / causal LM).

    The mask trick keeps shapes static (no boolean gather) so XLA fuses the
    whole thing into the final matmul's epilogue.
    """
    labels = batch["labels"]
    mask = (labels != -100).astype(jnp.float32)
    safe = jnp.where(labels == -100, 0, labels)
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), safe
    )
    return (losses * mask).sum() / jnp.maximum(mask.sum(), 1.0)


@register_loss("mse")
def mse(logits, batch):
    target = batch["labels"].astype(jnp.float32)
    return jnp.mean((logits.astype(jnp.float32) - target) ** 2)


def accuracy(logits, batch) -> jnp.ndarray:
    """Classification accuracy metric (not a loss)."""
    labels = batch["labels"]
    pred = jnp.argmax(logits, axis=-1)
    if labels.ndim == pred.ndim:  # token-level with ignore index
        mask = (labels != -100).astype(jnp.float32)
        return ((pred == labels).astype(jnp.float32) * mask).sum() / jnp.maximum(
            mask.sum(), 1.0
        )
    return (pred == labels).astype(jnp.float32).mean()


# ---------------------------------------------------------- fused lm head
# Bytes of float32 logits, positions x vocabulary, above which the fused loss
# walks its positions in blocks: the compiler keeps every vocab chunk's logits
# of one call alive at once (see `fused_linear_masked_lm`)
LOGITS_BUDGET_BYTES = 2 << 30


def fused_linear_masked_lm(features, kernel, labels, *, chunk_size=8192):
    """Masked LM cross-entropy computed straight from pre-head FEATURES —
    the lm-head matmul and the softmax are fused over vocab chunks so the
    [B, S, V] logit tensor never materializes as one array.

    Why: at llama vocab sizes the logits dominate activation memory
    (b8 x s1024 x v128k f32 = 4 GB forward + the same again for the
    backward's dlogits) and their HBM round-trip is pure overhead — the
    loss only needs one scalar per token. Chunking runs the head as
    n_chunks MXU matmuls of [N, D] @ [D, C] with an online logsumexp
    (same recurrence as flash attention's softmax), and the custom VJP
    recomputes each chunk's logits instead of saving them.

    Memory: the chunks are a Python loop, so the compiler sees them all at
    once and may keep every chunk's [N, C] logits alive together, [N, V]
    float32 in all, whatever `chunk_size` is (32,768 positions over 151,936
    rows: 19.9 GB). Where N x V x 4 bytes passes `LOGITS_BUDGET_BYTES`, the
    positions are walked in blocks, one block at a time under a checkpoint
    (`lax.map`), the block halved until its logits fit; below it the call
    is one block, as it always was.

    Sharding note: intended for meshes where the vocab dim is NOT sharded
    (single chip, DP/FSDP). Under tensor parallelism the regular path's
    per-device logit shard is already V/tp small, and chunked slicing of
    a V-sharded kernel would reshard every chunk.

    features: [B, S, D] (any float dtype; math accumulates f32)
    kernel:   [D, V] lm-head weight
    labels:   [B, S] int32, -100 = ignore
    → scalar f32 mean over unmasked positions (identical semantics to
    `masked_lm`).
    """
    if chunk_size < 1:
        raise ValueError(
            f"fused_loss_chunk must be >= 1, got {chunk_size}"
        )
    B, S, D = features.shape
    V = kernel.shape[1]
    x = features.reshape(B * S, D)
    flat = labels.reshape(B * S)
    block = B * S
    while block * V * 4 > LOGITS_BUDGET_BYTES and block % 2 == 0:
        block //= 2
    if block == B * S:
        return _fused_lm(x, kernel, flat, int(chunk_size), V)

    @jax.checkpoint
    def one(args):
        xb, lb = args
        count = jnp.sum(lb != -100).astype(jnp.float32)
        return _fused_lm(xb, kernel, lb, int(chunk_size), V) * jnp.maximum(count, 1.0), count

    sums, counts = jax.lax.map(
        one, (x.reshape(-1, block, D), flat.reshape(-1, block))
    )
    return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1.0)


def _chunks(V, chunk_size):
    return [(lo, min(lo + chunk_size, V)) for lo in range(0, V, chunk_size)]


def _chunk_logits(x, kernel, lo, hi):
    return jax.lax.dot_general(
        x,
        jax.lax.slice_in_dim(kernel, lo, hi, axis=1),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _fused_lm_fwd_core(x, kernel, flat, chunk_size, V):
    N = x.shape[0]
    mask = (flat != -100).astype(jnp.float32)
    safe = jnp.where(flat == -100, 0, flat)
    m = jnp.full((N,), -jnp.inf, jnp.float32)
    l = jnp.zeros((N,), jnp.float32)
    label_logit = jnp.zeros((N,), jnp.float32)
    for lo, hi in _chunks(V, chunk_size):
        logits = _chunk_logits(x, kernel, lo, hi)  # [N, C] f32
        m_new = jnp.maximum(m, logits.max(axis=1))
        l = l * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[:, None]
        ).sum(axis=1)
        m = m_new
        in_chunk = (safe >= lo) & (safe < hi)
        idx = jnp.clip(safe - lo, 0, hi - lo - 1)
        picked = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        label_logit = jnp.where(in_chunk, picked, label_logit)
    lse = m + jnp.log(l)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = ((lse - label_logit) * mask).sum() / denom
    return loss, (lse, mask, safe, denom)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_lm(x, kernel, flat, chunk_size, V):
    return _fused_lm_fwd_core(x, kernel, flat, chunk_size, V)[0]


def _fused_lm_fwd(x, kernel, flat, chunk_size, V):
    loss, (lse, mask, safe, denom) = _fused_lm_fwd_core(
        x, kernel, flat, chunk_size, V
    )
    return loss, (x, kernel, flat, lse, mask, safe, denom)


def _fused_lm_bwd(chunk_size, V, res, dloss):
    x, kernel, flat, lse, mask, safe, denom = res
    # d(loss)/d(logits[n, v]) = (softmax - onehot) * mask_n / denom * dloss
    scale = (mask / denom * dloss)[:, None]  # [N, 1] f32
    dx = jnp.zeros(x.shape, jnp.float32)
    dws = []
    for lo, hi in _chunks(V, chunk_size):
        logits = _chunk_logits(x, kernel, lo, hi)  # recompute, [N, C]
        p = jnp.exp(logits - lse[:, None])
        in_chunk = (safe >= lo) & (safe < hi)
        idx = jnp.clip(safe - lo, 0, hi - lo - 1)
        onehot = (
            jax.nn.one_hot(idx, hi - lo, dtype=jnp.float32)
            * in_chunk[:, None]
        )
        g = (p - onehot) * scale  # [N, C] f32
        w = jax.lax.slice_in_dim(kernel, lo, hi, axis=1)
        dx = dx + jax.lax.dot_general(
            g,
            w,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dws.append(
            jax.lax.dot_general(
                x,
                g,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
    dkernel = jnp.concatenate(dws, axis=1).astype(kernel.dtype)
    return dx.astype(x.dtype), dkernel, None


_fused_lm.defvjp(_fused_lm_fwd, _fused_lm_bwd)
