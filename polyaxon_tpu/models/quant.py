"""int8 weight-only quantization for the serving decode path (ISSUE 8).

Decode is weight-bandwidth-bound: every sampled token re-reads every
projection kernel out of HBM, so shrinking the resident kernels shrinks
the step time ceiling directly. This module quantizes the seven
transformer projection kernels (q/k/v/o and gate/up/down) to int8 with a
PER-OUTPUT-CHANNEL symmetric scale:

    scale[o] = max_i |W[i, o]| / 127        (float32, one per column)
    Wq[i, o] = round(W[i, o] / scale[o])    (int8, clipped to [-127, 127])

`Int8Dense` then feeds the int8 kernel STRAIGHT into
`jax.lax.dot_general(x, Wq, preferred_element_type=f32)` — a mixed
int8×bf16/f32 matmul, no dequantized copy of the kernel ever
materializes in HBM — and folds the scale into the f32 accumulator
output. Embedding, lm_head and the norms stay full precision (the
quality-critical ends of the network). LoRA checkpoints quantize the
FROZEN base kernel only: the adapter deltas (`lora_a`/`lora_b`) are a
rank-r sliver of HBM and carry all the tenant-specific signal, so they
stay at checkpoint precision while the shared base rides the int8 path
(transformer.LoRADense with quant="int8" — ISSUE 15 lifted the old
reject-LoRA restriction, unblocking multi-tenant int8 serving).

The same per-channel scale machinery also backs the int8 KV-cache path
(ISSUE 15): `quantize_kv` maps each cache slot's per-head K/V vector to
an int8 payload plus one f32 scale per (slot, head). Quantization is a
PURE function of the slot's own fp vector — no page- or chunk-level
statistics — so the quantized bytes are identical no matter what order
slots are written in (one-shot prefill, chunked prefill, COW reuse),
which is what keeps the paged byte-identity contracts testable on a
quantized pool.

Quantize-on-load: serving restores the checkpoint's fp params with the
ordinary module, calls `quantize_module()` once, and drops the dense
tree — the fp kernels are never resident past startup. The transform is
pure tree surgery: each targeted `{kernel}` dict gains a sibling
`scale`, matching what `Int8Dense` (selected by
`TransformerConfig.quant == "int8"`) reads back.

No clocks in here — quantization is a load-time transform and the
speculation/quant decode path orders everything by logical generation
index (scripts/lint_telemetry.py pins this module clock-free).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

# the seven decode projections; everything else (embed, lm_head, norms,
# lora_a/b, MoE router) stays at checkpoint precision
QUANT_TARGETS = (
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
)


class Int8Dense(nn.Module):
    """Weight-only int8 projection: int8 kernel + per-output-channel f32
    scale, applied as one dequant-free mixed matmul. Drop-in for the
    nn.Dense(use_bias=False) projections — same param path (`.../kernel`),
    one extra `scale` leaf, so the sharding rules keep matching."""

    features: int

    @nn.compact
    def __call__(self, x):
        in_dim = x.shape[-1]
        kernel = self.param(
            "kernel", lambda _, s: jnp.zeros(s, jnp.int8),
            (in_dim, self.features),
        )
        scale = self.param(
            "scale", nn.initializers.ones, (self.features,)
        )
        # mixed int8 x activation-dtype contraction: XLA widens kernel
        # tiles on the fly inside the matmul — the f32 accumulator comes
        # from preferred_element_type, the dequant is the one scale
        # multiply on the [.., features] output
        y = jax.lax.dot_general(
            x,
            kernel,
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (y * scale).astype(x.dtype)


def quantize_kernel(w) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., in, out] fp kernel → (int8 kernel, f32 scale[..., out]).
    Leading layer axes (nn.scan stacking) quantize per (layer, column)."""
    w32 = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(w32 / scale[..., None, :]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def quantize_kv(x) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., head_dim] fp K/V activations → (int8 payload, f32
    scale[...]) with one symmetric scale per leading index (per cache
    slot, per kv head). Same scheme as quantize_kernel, amax'd over the
    head dim — a pure per-vector transform, so the quantized bytes never
    depend on which prefill chunk or COW path wrote the slot."""
    x32 = jnp.asarray(x).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x32 / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of quantize_kv: int8 payload [..., head_dim] + f32
    scale [...] → fp values in `dtype`."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def kv_pool_bytes(layout, n_layers: int, n_kv_heads: int, head_dim: int,
                  kv_dtype_bytes: int = 2) -> int:
    """HBM bytes the paged K+V pool occupies under `layout`. int8 pools
    pay 1 byte per element plus one f32 scale per (slot, head); fp pools
    pay `kv_dtype_bytes` per element. `kv_pool_bytes` on /statsz reads
    this: the formula the server budgets with is the one it reports."""
    slots = layout.pool_pages * layout.page_tokens
    if getattr(layout, "kv_quant", "none") == "int8":
        per_slot = n_kv_heads * (head_dim * 1 + 4)  # payload + f32 scale
    else:
        per_slot = n_kv_heads * head_dim * kv_dtype_bytes
    return 2 * n_layers * slots * per_slot  # 2 = K and V


def _is_mapping(x: Any) -> bool:
    return hasattr(x, "items") and not hasattr(x, "shape")


def quantize_params(params, *, allow_lora: bool = False) -> tuple[dict, int]:
    """Quantize every QUANT_TARGETS projection kernel in a params tree.
    Returns (new tree, HBM bytes saved). Non-target leaves pass through
    untouched. With `allow_lora`, a target that carries LoRA adapters
    quantizes its frozen base `kernel` and passes `lora_a`/`lora_b`
    through at checkpoint precision; without it such a target is
    rejected (callers that cannot rebuild the module with the combined
    int8+LoRA projection must not silently drop the adapters)."""
    saved = 0

    def walk(tree):
        nonlocal saved
        out = {}
        for k, v in tree.items():
            if (
                k in QUANT_TARGETS
                and _is_mapping(v)
                and "kernel" in v
            ):
                has_lora = any(name.startswith("lora_") for name in v)
                if has_lora and not allow_lora:
                    raise ValueError(
                        f"cannot int8-quantize {k!r}: it carries LoRA "
                        "adapter params (pass allow_lora=True to "
                        "quantize the frozen base and keep the adapter "
                        "deltas fp)"
                    )
                w = jnp.asarray(v["kernel"])
                q, s = quantize_kernel(w)
                saved += (
                    w.size * w.dtype.itemsize
                    - q.size * q.dtype.itemsize
                    - s.size * s.dtype.itemsize
                )
                out[k] = {"kernel": q, "scale": s}
                for name, leaf in v.items():
                    if name.startswith("lora_"):
                        out[k][name] = leaf
            elif _is_mapping(v):
                out[k] = walk(v)
            else:
                out[k] = v
        return out

    return walk(params), int(saved)


def decode_weight_bytes(params) -> tuple[int, int]:
    """(target projection bytes, total param bytes): what quantizing
    the projections can save is measured over these."""
    target = total = 0

    def walk(tree, in_target):
        nonlocal target, total
        for k, v in tree.items():
            if _is_mapping(v):
                walk(v, in_target or k in QUANT_TARGETS)
            else:
                b = v.size * v.dtype.itemsize
                total += b
                if in_target:
                    target += b

    walk(params, False)
    return target, total


def quantize_module(module, params) -> tuple[Any, dict, int]:
    """Quantize-on-load for serving: rebuild `module` with the int8
    projection path (`cfg.quant = "int8"`) and transform `params` to
    match. Returns (module, params, bytes_saved)."""
    cfg = getattr(module, "cfg", None)
    if cfg is None or not hasattr(cfg, "quant"):
        raise ValueError(
            f"{type(module).__name__} has no quantizable decode path"
        )
    if cfg.quant != "none":
        raise ValueError(
            f"module is already quantized (cfg.quant = {cfg.quant!r}) — "
            "quantize-on-load runs once, on the fp checkpoint"
        )
    # LoRA checkpoints: quantize the frozen base kernels, keep the
    # adapter deltas fp — the rebuilt module's LoRADense picks the int8
    # base path from cfg.quant and still applies the fp delta on top
    lora = getattr(cfg, "lora_rank", 0) > 0
    qparams, saved = quantize_params(params, allow_lora=lora)
    qmodule = type(module)(dataclasses.replace(cfg, quant="int8"))
    return qmodule, qparams, saved
