"""The Mamba-2 mixer of a hybrid decoder (`granitemoehybrid` and its kin), as
`transformers`' `GraniteMoeHybridMambaLayer` computes it in training:

    [z | xBC | dt] = in_proj(u)              widths  H*P | H*P + 2*G*N | H
    xBC = silu(conv1d(xBC))                  depthwise over time, causal, with bias  (ops/mamba_fused.py)
    [x | B | C] = xBC                        x as H heads of P; B, C as G groups of N
    dt = softplus(dt + dt_bias)              A = -exp(A_log)        (one each a head)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t     (ops/ssd.py)
    y = RMSNorm(y * silu(z)) * w             the gate BEFORE the norm, over all H*P  (ops/mamba_fused.py)
    out = out_proj(y)

The two projections go through the decoder's own `_proj`, so `lora_targets`
may name `in_proj` and `out_proj` and the gradient reaches their adapters
through the scan's backward. `dt`, `A` and the scan's sums are float32
whatever the activations are. The two elementwise chains are one fused op
each (`conv_silu`, `gated_rmsnorm`: Pallas kernels where the shape allows,
the `jax.numpy` forms of `ops/ssd.py` elsewhere), and read `z` and `xBC`
where they lie in `in_proj`'s output: only `dt` is sliced out. Per step the layer sows into `ssm_stats` the
largest step size and the most negative in-chunk running sum of `dt A` (how
far the in-chunk decays underflow).

Serving is not built: a Mamba layer carries a recurrent state `[H, P, N]` and
a convolution tail of `K - 1` positions a sequence, which the KV manager, the
paged pool and the step engine do not know.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.mamba_fused import conv_silu, gated_rmsnorm
from ..ops.ssd import ssd_scan


def _a_log_init(key, shape, dtype=jnp.float32):
    # the published draw: A uniform in [1, 16]
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    # the published draw: dt log-uniform in [1e-3, 1e-1], through softplus's inverse
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class _GatedNorm(nn.Module):
    """The mixer's `norm`: `scale` where `RMSNorm` would keep it
    (`mamba/norm/scale`), the gate and the norm one fused op."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, y, zxbcdt):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
        return gated_rmsnorm(y, zxbcdt, scale, self.eps, z_columns=(0, y.shape[-1]))


class Mamba2(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py imports this module

    @nn.compact
    def __call__(self, u, *, decode: bool = False, adapter_ix=None):
        from .transformer import _run_proj

        cfg = self.cfg
        if decode:
            raise NotImplementedError(
                "a Mamba layer has no decode path: serving it needs a recurrent "
                "state [heads, head width, state] and a convolution tail of "
                "mamba_d_conv - 1 positions a sequence, kept beside the KV pages "
                "(serving/kv.py, models/kv_pages.py and the step engine know "
                "neither); train it, or serve a model of attention layers"
            )
        heads, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        groups, k = cfg.mamba_n_groups, cfg.mamba_d_conv
        bsz, seq, _ = u.shape
        inner, bc = heads * p, groups * n
        f32 = jnp.float32

        zxbcdt = _run_proj(cfg, 2 * inner + 2 * bc + heads, "in_proj", u, adapter_ix)
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.normal(1.0 / np.sqrt(k)), (k, inner + 2 * bc)
        )
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (inner + 2 * bc,))
        xbc = conv_silu(zxbcdt, conv_kernel, conv_bias, columns=(inner, inner + 2 * bc))
        x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)

        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        d_skip = self.param("D", nn.initializers.ones, (heads,))
        dt = zxbcdt[..., 2 * inner + 2 * bc :]
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))  # [B, S, H]
        a_rate = -jnp.exp(a_log.astype(f32))

        chunk = cfg.mamba_chunk_size
        y = ssd_scan(  # refuses a sequence that is no multiple of the chunk
            x.reshape(bsz, seq, heads, p), dt, a_rate,
            b.reshape(bsz, seq, groups, n), c.reshape(bsz, seq, groups, n),
            d_skip, chunk=chunk,
        ).reshape(bsz, seq, inner)
        sums = (dt * a_rate).reshape(bsz, seq // chunk, chunk, heads).sum(axis=2)
        self.sow("ssm_stats", "dt_max", jnp.max(dt))
        self.sow("ssm_stats", "chunk_decay_min", jnp.min(sums))

        # the gate before the norm; the norm over the whole inner width, in float32
        y = _GatedNorm(cfg.norm_eps, name="norm")(y, zxbcdt)
        return _run_proj(cfg, cfg.dim, "out_proj", y, adapter_ix)
