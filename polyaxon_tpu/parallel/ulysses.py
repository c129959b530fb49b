"""Ulysses-style sequence parallelism: all-to-all head/sequence reshard.

The second context-parallel strategy SURVEY.md §5 commits to, next to ring
attention: instead of rotating KV chunks around an ICI ring (N-1 hops,
compute overlapped), TWO all-to-alls flip the sharding from
sequence-sharded [B, S/c, H, D] to head-sharded [B, S, H/c, D], run plain
(flash) attention on the full sequence locally, and flip back.

Trade-off vs ring (why both exist): Ulysses moves each token exactly twice
over the fabric regardless of ring size — lower traffic and no
per-hop softmax merges, the better choice when S_local² compute is small
relative to bandwidth (short-ish sequences, many chips). Ring keeps heads
whole — the only option when heads don't divide the context degree, and
the better overlap profile at very long S. Select per model with
`attention: ulysses` / `attention: ring`.

Constraint: local head count must divide by the context degree (heads are
what gets scattered)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import BATCH_AXES
from .ring import current_mesh


def _ulysses_body(q, k, v, axis_name: str, causal: bool, block_kv: int):
    from ..ops.flash_attention import flash_attention

    def seq_to_heads(x):  # [B, S/c, H, D] → [B, S, H/c, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):  # [B, S, H/c, D] → [B, S/c, H, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    q, k, v = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    o = flash_attention(q, k, v, causal=causal, block_kv=block_kv)
    return heads_to_seq(o)


def ulysses_attention(
    q, k, v, *, axis_name: str = "context", block_kv: int = 512, causal: bool = True
):
    """Attention with Q/K/V sequence-sharded over `axis_name`.

    q: [B, S, H, D]; k/v: [B, S, KV, D] with KV dividing H — pass GQA kv
    UNEXPANDED: when the kv shards divide the model axis and context
    degree they ride the all-to-all at true kv-head width (4x less K/V
    traffic at llama ratios) and the flash kernel consumes the groups
    natively; indivisible shapes expand internally. Falls back to the
    sharded flash dispatch when the mesh has no (non-trivial) context
    axis, mirroring ring_attention's contract."""
    mesh = current_mesh()
    n = int(mesh.shape.get(axis_name, 1)) if mesh is not None else 1
    if n <= 1:
        # no context axis: route through the flash dispatch so a live
        # DP/FSDP/TP mesh still gets the shard_map-partitioned kernel
        from ..ops.attention import dot_product_attention

        return dot_product_attention(
            q, k, v, causal=causal, backend="flash", block_kv=block_kv
        )

    if q.shape[1] % n:
        # sequence doesn't divide the context degree: the partitionable
        # einsum is the only correct fallback on a live multi-device mesh
        from ..ops.attention import dot_product_attention

        return dot_product_attention(q, k, v, causal=causal, backend="xla")
    from .sharding import live_axes, shard_map_nocheck

    H, KV = q.shape[2], k.shape[2]
    model = mesh.shape.get("model", 1)
    head_live = live_axes(mesh, ("model",), H)
    local_heads = H // model if head_live else H
    if local_heads % n != 0:
        raise ValueError(
            f"ulysses needs local head count {local_heads} divisible by the "
            f"context degree {n} (heads are scattered); use attention: ring "
            "for this shape"
        )
    # GQA: kv ride the all-to-all at their TRUE head width when the kv
    # shards divide both the model axis and the context degree (4x less
    # K/V traffic at llama ratios; the flash kernel consumes grouped kv
    # natively). Otherwise expand — correct, just more traffic.
    local_kv = KV // model if head_live else KV
    kv_grouped = (
        KV == H
        or ((KV % model == 0 if head_live else True) and local_kv % n == 0)
    )
    if not kv_grouped:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        KV = H
    # batch degrades to replication when it doesn't divide (B=1 eval)
    batch = live_axes(mesh, BATCH_AXES, q.shape[0]) or None
    head = head_live[0] if head_live else None
    # by here head is only non-None when KV % model == 0 (kv_grouped's
    # conditions or the expand branch guarantee it) — one spec serves both
    q_spec = P(batch, axis_name, head, None)
    kv_spec = q_spec
    body = partial(
        _ulysses_body, axis_name=axis_name, causal=causal, block_kv=block_kv
    )
    inner = shard_map_nocheck(
        body,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
    )
    return inner(q, k, v)
