"""Shared scaled-dot-product attention dispatch for every model in the zoo.

One implementation, three backends:
  xla   — einsum + softmax; scores accumulated in f32 via
          preferred_element_type (a bf16 MXU dot would round the scores
          before any later cast could help).
  flash — Pallas blockwise kernel (ops/flash_attention.py), O(S) memory.
  ring  — context-parallel blockwise over the mesh `context` axis
          (parallel/ring.py); falls back to flash off-mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def resolve_auto_backend(
    seq_len: int, block_kv: int | None = None, head_dim: int | None = None
) -> str:
    """`auto` policy: the Pallas flash kernel on TPU when the O(S^2) score
    matrix starts to matter and the shapes satisfy the kernel's block
    layout; the XLA einsum otherwise.

    Rationale: at short seq the einsum path is a single fused MXU pass and
    XLA's softmax fusion is hard to beat; past ~2k tokens the [B,H,S,S]
    f32 score matrix dominates HBM traffic and the blockwise kernel's
    O(S) VMEM streaming wins (pallas_guide.md). Shape guards are
    flash_attention's own (`flash_shapes_ok`): the blocks the kernels would
    choose, around `block_kv` where the caller fixed it, divide the sequence.

    Mesh dispatch: on multi-device meshes where the SEQUENCE dim stays
    whole per device (DP/FSDP/TP — batch and heads shard, not seq) the
    kernel runs inside a shard_map over the batch/head axes
    (`dot_product_attention` below), so multi-chip no longer falls back to
    the O(S^2) einsum. When the mesh DOES shard the sequence (`context`
    axis live), blockwise ring attention is the seq-partitioned strategy
    and `auto` picks it when shapes divide. Off-mesh on a multi-device
    backend the einsum remains the only partitionable path."""
    if jax.default_backend() != "tpu" or seq_len < 2048:
        return "xla"
    from .flash_attention import flash_shapes_ok

    blocks_ok = flash_shapes_ok(seq_len, block_kv=block_kv, head_dim=head_dim or 128)
    # unusual head dims must fall back, not surface as Mosaic layout
    # errors: the kernel's VMEM tiles want lane-friendly D (64/128/192/256).
    # Explicit `attention: flash` bypasses this — an opt-in to the kernel.
    head_ok = head_dim is None or (head_dim % 64 == 0 and head_dim <= 256)
    flash_ok = blocks_ok and head_ok
    from ..parallel.ring import current_mesh
    from ..parallel.sharding import constraints_suspended

    if constraints_suspended():
        # inside a shard_map body (pipeline stage): seq_len is already the
        # per-device view; the plain kernel applies directly
        return "flash" if flash_ok else "xla"
    mesh = current_mesh()
    if mesh is None:
        # no mesh bound: only a lone chip can run the unpartitioned kernel
        return (
            "flash" if flash_ok and len(jax.devices()) == 1 else "xla"
        )
    ctx = mesh.shape.get("context", 1)
    if ctx > 1:
        # the seq-partitioned strategy has no block/head-dim constraints
        # (einsum-based ring body) — only the ring chunking must divide
        return "ring" if seq_len % ctx == 0 else "xla"
    return "flash" if flash_ok else "xla"


def _flash_sharded(q, k, v, *, causal: bool, block_kv: int | None, mesh, window=None,
                   scale=None):
    """The Pallas flash kernel on a live multi-device mesh.

    The kernel has no GSPMD partitioning rule, so partition it manually:
    shard_map over the axes that DON'T touch the sequence dim — batch over
    data/fsdp, heads over model, seq and head_dim whole per device. Each
    device then runs the ordinary single-device kernel on its [b/dp, S,
    h/tp, D] block; no cross-device attention math is needed because every
    (batch, head) pair lives wholly on one device. Axes whose size doesn't
    divide the corresponding dim degrade to replication (mirroring
    `parallel.sharding.constrain`), so odd shapes stay correct — just less
    parallel. With seq sharded over `context` callers want ring/ulysses
    instead; entering here anyway is correct (GSPMD gathers seq to match
    the in_specs) but wasteful."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from .flash_attention import flash_attention
    from ..parallel.mesh import BATCH_AXES
    from ..parallel.sharding import live_axes, shard_map_nocheck

    B, _, H, _ = q.shape
    KV = k.shape[2]
    batch = live_axes(mesh, BATCH_AXES, B)
    # heads shard when BOTH head counts divide the model axis (KV | H, so
    # the group structure survives the split). When only H divides (MQA /
    # few kv heads vs a wide model axis), EXPAND kv first — losing the
    # grouped-kv bandwidth saving but keeping head TP, which dominates.
    model = mesh.shape.get("model", 1)
    head_ax = live_axes(mesh, ("model",), KV)
    head = head_ax[0] if head_ax and H % model == 0 else None
    if head is None and model > 1 and H % model == 0 and KV < H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        head = "model"
    q_spec = P(batch or None, None, head, None)
    kv_spec = P(batch or None, None, head, None)
    body = partial(
        flash_attention, causal=causal, block_kv=block_kv, window=window, sm_scale=scale
    )
    fn = shard_map_nocheck(
        body,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
    )
    return fn(q, k, v)


def dot_product_attention(
    q, k, v, *, causal: bool, backend: str = "xla", block_kv: int | None = None,
    window: int | None = None, scale: float | None = None,
):
    """q: [B, S, H, D]; k/v: [B, S, KV, D] with KV dividing H → [B, S, H, D].

    `scale`: what `q k^T` is multiplied by before the softmax; None is
    1 / sqrt(D). The einsum and the flash kernels take another (a published
    `attention_multiplier`); the context-parallel backends have none.

    `block_kv`: None lets the flash kernels choose their blocks (and the
    ring and ulysses chunk stay 512); a number is the kv block of all.

    `window` (causal only): query i attends keys j with 0 <= i - j <
    window. The einsum masks; the flash kernel skips the kv blocks behind
    the window; the context-parallel backends have no window and say so.

    GQA expansion happens HERE, per backend: the flash kernel consumes
    grouped kv natively (no repeated K/V in HBM); the einsum/ring/ulysses
    paths get kv expanded to the query head count."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"query heads {q.shape[2]} not divisible by kv heads {k.shape[2]}"
        )
    if backend == "auto":
        backend = resolve_auto_backend(q.shape[1], block_kv, q.shape[-1])
    if window is not None:
        if not causal:
            raise ValueError("a window needs causal attention")
        if backend in ("ring", "ulysses"):
            raise ValueError(
                f"attention backend {backend!r} has no sliding window: the "
                "context-parallel kernels attend the whole sequence (use "
                "xla or flash for windowed layers)"
            )
    if scale is not None and backend in ("ring", "ulysses"):
        raise ValueError(
            f"attention backend {backend!r} scales by 1 / sqrt(head width) only "
            "(use xla or flash with an attention_multiplier)"
        )
    # flash consumes grouped kv natively; ring rotates it and ulysses
    # scatters it at kv-head width (4x less fabric traffic at llama
    # ratios), both expanding internally only when shards don't divide.
    # Only the plain einsum needs pre-expanded kv.
    if backend == "xla" and k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if backend == "flash":
        from .flash_attention import flash_attention
        from ..parallel.ring import current_mesh
        from ..parallel.sharding import constraints_suspended

        mesh = current_mesh()
        if mesh is not None and mesh.size > 1 and not constraints_suspended():
            return _flash_sharded(
                q, k, v, causal=causal, block_kv=block_kv, mesh=mesh,
                window=window, scale=scale,
            )
        return flash_attention(
            q, k, v, causal=causal, block_kv=block_kv, window=window, sm_scale=scale
        )
    if backend == "ring":
        from ..parallel.ring import ring_attention

        return ring_attention(q, k, v, block_kv=block_kv or 512, causal=causal)
    if backend == "ulysses":
        from ..parallel.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, block_kv=block_kv or 512, causal=causal)
    if backend != "xla":
        raise ValueError(f"unknown attention backend {backend!r}")
    hd = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    scores = scores / np.sqrt(hd) if scale is None else scores * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((S, S), bool), -int(window))
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
