"""The Kimi-Delta-Attention mixer of a hybrid decoder (arXiv:2510.26692, as
`fla`'s `KimiDeltaAttention` computes it in training, with the switches a
published config gives: `no_kda_lora`, `kda_safe_gate`, `linear_silu`,
`num_kv_heads_for_linear_attn` 0). Per head, key and value width `P`:

    q = l2norm(silu(conv(q_proj u))) / sqrt(P)    k = l2norm(silu(conv(k_proj u)))    v = silu(conv(v_proj u))
    g = kda_gate_bound * sigmoid(exp(A_log_h) * (f_proj u + dt_bias))      a log-decay a KEY CHANNEL, in (bound, 0)
    beta = sigmoid(b_proj u)                                              one scalar a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,   o_t = S_t^T q_t     (ops/kda.py)
    out = o_proj(RMSNorm_P(o) * w * sigmoid(g_proj u))                     the norm a head, the gate after it

`conv` is a depthwise causal convolution over time without bias, and the
SiLU after it is fused into it (`ops/mamba_fused.conv_silu`, the kernels of
the Mamba mixer: a Pallas pair where the shape allows). q, k, v and g reach
the scan with heads and widths merged, `[B, S, H x P]`, as the convs and
`f_proj` leave them (on the chip `[B, S, H, P]` is another tiling, and the
scan's kernels read the merged one), and the scan normalises q and k
(`kda_scan(..., unit_scales=)`). No rotation: a
delta-rule layer takes positions from its recurrence. `f_proj` and `g_proj`
are one full matrix each (`no_kda_lora`; Kimi Linear has a low-rank pair).

The five wide projections go through the decoder's own `_proj`, so
`lora_targets` may name `q_proj`, `k_proj`, `v_proj`, `o_proj` and the
gradient reaches their adapters through the scan's backward. The gate, the
step size, the sums and the carried state are float32 whatever the
activations are. Per step the layer sows into `kda_stats` the most negative
in-chunk running sum of `g` (how far the in-chunk decays underflow) and the
largest step size.

Serving is not built: a KDA layer carries a matrix state `[H, P, P]` and
three convolution tails of `K - 1` positions a sequence, which the KV
manager, the paged pool and the step engine do not know.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.kda import kda_scan
from ..ops.mamba_fused import conv_silu
from .ssm import _a_log_init, _dt_bias_init  # fla draws both as Mamba-2 does


# The mixer's elementwise chains, each under a `jax.checkpoint` (the l2 norm
# of q and k is `ops/kda.l2_unit`, applied by the scan): the backward keeps a
# chain's inputs in the activations' type and builds its float32 `[tokens,
# heads x width]` values again (a KDA block kept a dozen of them, 256 MB each
# at 16,384 tokens: more than the chip has beside the weights).
@functools.partial(jax.checkpoint, static_argnums=(3,))
def _log_decay(f, a_log, dt_bias, bound: float):
    """bound * sigmoid(exp(A_log_h) * (f + dt_bias)): f [B, S, H x P], a_log
    [H], dt_bias [H x P] -> float32 in (bound, 0), heads and widths merged as
    `f_proj` gives them (elementwise: nothing here needs a head's axis, and
    the scan's kernels read the merged form)."""
    f32 = jnp.float32
    rate = jnp.repeat(jnp.exp(a_log.astype(f32)), dt_bias.shape[0] // a_log.shape[0])
    return bound * jax.nn.sigmoid(rate * (f.astype(f32) + dt_bias.astype(f32)))


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _normed_gated(o, z, scale, eps: float):
    """RMSNorm(o) * scale * sigmoid(z) over the last axis (a head's width),
    in float32; the result in o's type."""
    o32 = o.astype(jnp.float32)
    normed = o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, -1, keepdims=True) + eps)
    return (normed * scale.astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32))).astype(
        o.dtype
    )


class KimiDeltaAttention(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py imports this module
    n_heads: int

    @nn.compact
    def __call__(self, u, *, decode: bool = False, adapter_ix=None):
        from .transformer import _run_proj

        cfg = self.cfg
        if decode:
            raise NotImplementedError(
                "a KDA layer has no decode path: serving it needs a matrix state "
                "[heads, head width, head width] and three convolution tails of "
                "kda_conv - 1 positions a sequence, kept beside the KV pages "
                "(serving/kv.py, models/kv_pages.py and the step engine know "
                "neither); train it, or serve a model of attention layers"
            )
        heads, p, taps = self.n_heads, cfg.head_size, cfg.kda_conv
        bsz, seq, _ = u.shape
        inner = heads * p
        f32 = jnp.float32

        def short_conv(name):
            x = _run_proj(cfg, inner, f"{name}_proj", u, adapter_ix)
            kernel = self.param(
                f"{name}_conv_kernel", nn.initializers.normal(1.0 / np.sqrt(taps)), (taps, inner)
            )
            return conv_silu(x, kernel, jnp.zeros((inner,), kernel.dtype))

        # heads and widths merged, as the scan's kernels read them; the scan
        # normalises a head's queries and keys (`ops/kda.l2_unit`)
        q, k, v = short_conv("q"), short_conv("k"), short_conv("v")

        a_log = self.param("A_log", _a_log_init, (heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
        f = nn.Dense(inner, use_bias=False, name="f_proj")(u)
        g = _log_decay(f, a_log, dt_bias, float(cfg.kda_gate_bound))  # merged as v
        beta = jax.nn.sigmoid(nn.Dense(heads, use_bias=False, name="b_proj")(u).astype(f32))

        chunk = cfg.kda_chunk_size
        # refuses a sequence off the chunk; o merged as v came
        o = kda_scan(q, k, v, g, beta, chunk=chunk, unit_scales=(p**-0.5, 1.0)).reshape(
            bsz, seq, heads, p)
        sums = g.reshape(bsz, seq // chunk, chunk, inner).sum(axis=2)
        self.sow("kda_stats", "log_decay_min", jnp.min(sums))
        self.sow("kda_stats", "beta_max", jnp.max(beta))

        # the norm a head, in float32; the gate after it, an element each
        scale = self.param("o_norm_scale", nn.initializers.ones, (p,))
        z = nn.Dense(inner, use_bias=False, name="g_proj")(u).reshape(bsz, seq, heads, p)
        y = _normed_gated(o, z, scale, float(cfg.norm_eps)).reshape(bsz, seq, inner)
        return _run_proj(cfg, cfg.dim, "o_proj", y, adapter_ix)
