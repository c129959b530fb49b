"""Multi-head latent attention (DeepSeek-V2's, with a full `q_proj`:
`q_lora_rank` null) as a mixer of the decoder. H heads; a latent of `L`;
per head `N` score columns without position, `R` with, and values of `V`:

    q = q_proj u -> [H, N + R]          [c | k_r] = kv_a_proj u -> L + R          c = RMSNorm(c) * w
    [k_n | v]_h = kv_b_proj c -> [H, N + V]         k_h = [k_n,h | k_r]        (k_r shared by every head)
    q_h, k_h <- RMSNorm_(N+R)(.) * w   (`mla_qk_norm`), then the LAST R of each rotated
    o_h = softmax_causal(q_h k_h^T / sqrt(N + R)) v_h;   o_h <- o_h * sigmoid(gate_proj u)_h   (`attn_gate`)
    out = o_proj o

Scores are `N + R` wide and values `V`: the flash kernels take the two
widths as they are (`ops/flash_attention.py`), nothing is padded. The four
projections go through the decoder's `_proj`, so `lora_targets` may name
`q_proj`, `kv_a_proj`, `kv_b_proj`, `o_proj`. Keys and values are expanded
from the latent for every position (training and prefill); decoding from a
cache of the latent and `k_r` alone (the absorbed form) is not built.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp


class _NormedRotated(nn.Module):
    """A head's RMSNorm (where `norm`) and the rotation of its last columns,
    as one module under `nn.remat`: the backward keeps the projection's
    output and builds the float32 `[tokens, heads, width]` values again.
    `scale` lies where an `RMSNorm` of this name would keep it."""

    eps: float
    nope: int
    norm: bool

    @nn.compact
    def __call__(self, x, cos, sin):
        from .transformer import _rotate

        if self.norm:
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
            x32 = x.astype(jnp.float32)
            x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
            x = (x32 * scale).astype(x.dtype)
        return jnp.concatenate(
            [x[..., : self.nope], _rotate(x[..., self.nope :], cos, sin)], axis=-1
        )


class LatentAttention(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py imports this module
    spec: "LayerSpec"  # noqa: F821

    @nn.compact
    def __call__(self, u, *, decode: bool = False, adapter_ix=None):
        from ..ops.attention import dot_product_attention
        from .transformer import RMSNorm, _run_proj, rope_table

        cfg, heads = self.cfg, self.spec.n_heads
        if decode:
            raise NotImplementedError(
                "a latent-attention layer has no decode path: serving it wants a "
                "cache of the latent and the shared rotary key (mla_latent + "
                "mla_rope_dim a token, not heads x 2 x head width), which "
                "models/kv_pages.py and the step engine do not know; train it, "
                "or serve a model of plain attention layers"
            )
        latent, nope, rot, val = (
            cfg.mla_latent, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_value_dim
        )
        bsz, seq, _ = u.shape
        gate = None
        if cfg.attn_gate:  # one scalar a head and token; a plain Dense (never a LoRA target)
            gate = nn.sigmoid(
                nn.Dense(heads, use_bias=False, name="gate_proj")(u).astype(jnp.float32)
            )[..., None].astype(u.dtype)

        q = _run_proj(cfg, heads * (nope + rot), "q_proj", u, adapter_ix)
        q = q.reshape(bsz, seq, heads, nope + rot)
        kv_a = _run_proj(cfg, latent + rot, "kv_a_proj", u, adapter_ix)
        c = RMSNorm(cfg.norm_eps, name="kv_a_norm")(kv_a[..., :latent])
        kv_b = _run_proj(cfg, heads * (nope + val), "kv_b_proj", c, adapter_ix)
        kv_b = kv_b.reshape(bsz, seq, heads, nope + val)
        k_r = jnp.broadcast_to(kv_a[..., None, latent:], (bsz, seq, heads, rot))
        k = jnp.concatenate([kv_b[..., :nope], k_r], axis=-1)
        v = kv_b[..., nope:]
        cos_np, sin_np = rope_table(cfg.seq_len, rot, self.spec.rope.theta)
        cos = jnp.asarray(cos_np)[None, :seq, None, :]
        sin = jnp.asarray(sin_np)[None, :seq, None, :]
        shaped = functools.partial(
            nn.remat(_NormedRotated), cfg.norm_eps, nope, cfg.mla_qk_norm
        )
        q, k = shaped(name="q_norm")(q, cos, sin), shaped(name="k_norm")(k, cos, sin)
        out = dot_product_attention(
            q, k, v, causal=True, backend=cfg.attention, block_kv=cfg.attention_block,
        )
        if gate is not None:
            out = out * gate
        return _run_proj(cfg, cfg.dim, "o_proj", out.reshape(bsz, seq, heads * val), adapter_ix)
