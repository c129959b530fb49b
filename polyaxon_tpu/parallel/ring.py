"""Ring attention: context-parallel causal attention over the `context` axis.

The long-context strategy the reference never had in-repo (SURVEY.md §5:
sequence parallelism was user-code's problem). Design:

- The trainer shards the sequence dim of token batches over the mesh's
  `context` axis; inside the model, `ring_attention` drops into `shard_map`
  so each device holds one sequence chunk of Q/K/V.
- N-1 `ppermute` hops rotate KV chunks around the ring (nearest-neighbor
  ICI traffic only); each hop's block attention is merged with the online-
  softmax rule, so memory stays O(S_local^2) per step and the full S^2
  score matrix never materializes anywhere.
- Causality by chunk provenance: a KV chunk from an earlier rank attends
  fully, the own chunk attends lower-triangular, later ranks are skipped
  (masked to zero weight — static shapes, XLA-friendly).
- Pure jnp + ppermute, so autodiff produces the reverse-ring backward for
  free; the unrolled Python loop lets XLA overlap each hop's collective
  with the previous hop's compute.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from .mesh import BATCH_AXES

NEG_INF = -1e30

# Mesh currently in scope for model-internal collectives (ring attention,
# pipelined layers). The trainer sets this before tracing; a context var
# rather than a module argument keeps model code mesh-agnostic. Thread-local
# because the sweep driver traces concurrent trials, each on its own
# device sub-slice — a shared global would cross-wire their meshes.
import threading as _threading

_MESH_STATE = _threading.local()


def set_current_mesh(mesh: Optional[Mesh]) -> None:
    _MESH_STATE.mesh = mesh


def current_mesh() -> Optional[Mesh]:
    return getattr(_MESH_STATE, "mesh", None)


def _chunk_attention(q, k, v, scale, full, same):
    """One KV chunk's contribution: returns (o_unnorm, m, l).

    full/same are scalar bools (chunk provenance); masked-out entries get
    probability 0 via the `allowed` mask, never a -inf softmax (avoids the
    all-masked NaN)."""
    B, S_q, H, D = q.shape
    S_k, KV = k.shape[1], k.shape[2]
    if H == KV:
        s = (
            jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
            )
            * scale
        )
    else:
        # GQA: score against the TRUE kv heads — the rotating K/V chunks
        # stay at kv width, never expanded. Head order h = kv*G + g
        # matches jnp.repeat's, so downstream [b,h,q,k] logic is unchanged.
        G = H // KV
        s = (
            jnp.einsum(
                "bqkgd,bskd->bkgqs",
                q.reshape(B, S_q, KV, G, D),
                k,
                preferred_element_type=jnp.float32,
            ).reshape(B, H, S_q, S_k)
            * scale
        )
    tril = jnp.tril(jnp.ones((S_q, S_k), bool))
    allowed = full | (same & tril[None, None])
    s = jnp.where(allowed, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [b,h,q,1]
    p = jnp.where(allowed, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if H == KV:
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    else:
        o = jnp.einsum(
            "bkgqs,bskd->bqkgd",
            p.reshape(B, KV, H // KV, S_q, S_k).astype(v.dtype),
            v,
        ).reshape(B, S_q, H, D)
    return o, m, l


def _ring_body(q, k, v, axis_name: str, n: int, scale: float, causal: bool):
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    o = jnp.zeros((B, S, H, D), jnp.float32)
    m = jnp.full((B, H, S, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, S, 1), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]
    for t in range(n):
        src = (idx - t) % n
        if causal:
            full, same = src < idx, src == idx
        else:
            full, same = jnp.bool_(True), jnp.bool_(False)
        o_i, m_i, l_i = _chunk_attention(q, k, v, scale, full=full, same=same)
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)  # rescale of the running accumulator
        beta = jnp.exp(m_i - m_new)  # rescale of this chunk
        l = alpha * l + beta * l_i
        o = o * alpha.transpose(0, 2, 1, 3) + o_i * beta.transpose(0, 2, 1, 3)
        m = m_new
        if t != n - 1:  # rotate KV to the next rank; last hop needs no send
            k, v = jax.lax.ppermute((k, v), axis_name, perm)
    return (o / jnp.maximum(l.transpose(0, 2, 1, 3), 1e-30)).astype(q.dtype)


def _ring_body_flash(q, k, v, axis_name: str, n: int, scale: float, causal: bool):
    """Ring body with the Pallas flash kernel inside each hop.

    The einsum body above materializes a [B,H,S_local,S_local] f32 score
    matrix per hop — at the examples/longcontext.yaml shape (32k over a
    4-way ring) that's a multi-hundred-MB HBM intermediate. Here each
    hop runs the blockwise kernel (O(S_local) memory) and hops merge by
    logsumexp:  lse = logaddexp(lse_a, lse_b);
                o   = o_a·exp(lse_a−lse) + o_b·exp(lse_b−lse).

    Hop provenance is static in t only at t=0 (own chunk → causal
    kernel). Later hops come from another rank: earlier ranks attend in
    full, later ranks contribute nothing — that predicate depends on
    axis_index, so the kernel always runs non-causal and a skipped hop's
    lse is masked to −inf, zeroing its merge weight. Same compute as the
    masked einsum (SPMD uniformity), none of its memory."""
    from ..ops.flash_attention import flash_attention_lse

    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]
    o, lse = flash_attention_lse(q, k, v, causal=causal, sm_scale=scale)
    o = o.astype(jnp.float32)  # merge in f32; cast once at the end
    lse = lse[..., None]  # [B,H,S,1]
    for t in range(1, n):
        k, v = jax.lax.ppermute((k, v), axis_name, perm)
        src = (idx - t) % n
        o_i, lse_i = flash_attention_lse(q, k, v, causal=False, sm_scale=scale)
        if causal:
            # chunks from later ranks are fully masked: −inf lse ⇒ zero
            # merge weight (exp(−inf − lse_new) = 0)
            keep = (src < idx)[None, None, None, None]
            lse_i = jnp.where(keep, lse_i[..., None], NEG_INF)
        else:
            lse_i = lse_i[..., None]
        lse_new = jnp.logaddexp(lse, lse_i)
        w = jnp.exp(lse - lse_new).transpose(0, 2, 1, 3)  # [B,S,H,1]
        w_i = jnp.exp(lse_i - lse_new).transpose(0, 2, 1, 3)
        o = o * w + o_i.astype(jnp.float32) * w_i
        lse = lse_new
    return o.astype(q.dtype)


def ring_attention(
    q, k, v, *, axis_name: str = "context", block_kv: int = 512, causal: bool = True
):
    """Attention with Q/K/V sequence-sharded over `axis_name`.

    q: [B, S, H, D]; k/v: [B, S, KV, D] with KV dividing H — pass GQA kv
    UNEXPANDED: the rotating K/V chunks then travel the ring at true
    kv-head width (4x less ICI traffic per hop at llama ratios) and the
    blockwise math scores groups directly. kv expands internally only
    when head TP needs it (KV doesn't divide the model axis). Falls back
    to the sharded flash dispatch when the mesh has no (non-trivial)
    context axis, so models can use `attention: ring` unconditionally."""
    mesh = current_mesh()
    n = int(mesh.shape.get(axis_name, 1)) if mesh is not None else 1
    scale = q.shape[-1] ** -0.5
    if n <= 1:
        # no context axis: route through the flash dispatch so a live
        # DP/FSDP/TP mesh still gets the shard_map-partitioned kernel
        from ..ops.attention import dot_product_attention

        return dot_product_attention(
            q, k, v, causal=causal, backend="flash", block_kv=block_kv
        )

    if q.shape[1] % n:
        # sequence doesn't divide the ring: the partitionable einsum is the
        # only correct fallback on a live multi-device mesh
        from ..ops.attention import dot_product_attention

        return dot_product_attention(q, k, v, causal=causal, backend="xla")
    from .sharding import live_axes

    # batch/head axes degrade to replication when they don't divide
    # (e.g. B=1 eval batches on a data×context mesh)
    H, KV = q.shape[2], k.shape[2]
    batch = live_axes(mesh, BATCH_AXES, q.shape[0]) or None
    head_live = live_axes(mesh, ("model",), H)
    head = head_live[0] if head_live else None
    model = mesh.shape.get("model", 1)
    if KV != H and head is not None and KV % model != 0:
        # head TP needs the kv heads to split with the q heads: expand —
        # correct, just without the grouped-kv ring-traffic saving
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    # the Pallas kernel runs inside each hop whenever the per-device
    # sequence chunk fits its block layout — the einsum body (O(S_local^2)
    # HBM per hop) is only the fallback for odd shapes
    from ..ops.flash_attention import flash_shapes_ok
    from .sharding import shard_map_nocheck

    s_local = q.shape[1] // n
    # head_dim gate mirrors resolve_auto_backend (ops/attention.py): the
    # kernel's lane layout needs D a multiple of 64 and within VMEM tiling
    D = q.shape[-1]
    use_flash = flash_shapes_ok(s_local) and D % 64 == 0 and D <= 256
    body = _ring_body_flash if use_flash else _ring_body
    q_spec = P(batch, axis_name, head, None)
    make = shard_map_nocheck if use_flash else partial(shard_map)
    inner = make(
        partial(body, axis_name=axis_name, n=n, scale=scale, causal=causal),
        mesh=mesh,
        in_specs=(q_spec, q_spec, q_spec),
        out_specs=q_spec,
    )
    return inner(q, k, v)
