"""The degree-2 power-retention mixer of a decoder (Brumby-14B-Base: Qwen3's
projections, its attention replaced by power retention). H query heads and
G key-value groups of width P; query head h reads group h // (H / G):

    q = RoPE(RMSNorm_P(q_proj u) * w_qn)      k = RoPE(RMSNorm_P(k_proj u) * w_kn)      v = v_proj u
    log g = logsigmoid(gate_proj u + GATE_BIAS)     one scalar a query head and position
    y_t = sum_{j<=t} exp(sum_{j<l<=t} log g_l) (q_t . k_j / sqrt(P))^2 v_j / (the same sum without v_j + EPS)
    out = o_proj y                                  (ops/power_retention.py)

The shape decides the scan (`ops/power_retention_fused.scan_plan`): the
Pallas kernels read q, k and v merged, `[B, S, heads x P]` as the
projections and the norms hand them over; every other shape runs the
`jax.numpy` scan on `[B, S, heads, P]`.

The norm of a head's queries and keys (Qwen3's `q_norm` and `k_norm`) and
the rotation at the layer's `rope_theta` come before the square, as one
checkpointed module (`mla._NormedRotated`: the backward keeps the
projection's output). `gate_proj` is a plain bias-free `Dense`, never a LoRA
target; the gate's logit is centred at `GATE_BIAS`, so that a gate of a
logit of unit spread keeps about 660 positions on average (`log g` near
-1.5e-3) and the state carries across chunks. The four wide projections go
through the decoder's `_proj`, so `lora_targets` may name `q_proj`,
`k_proj`, `v_proj` and `o_proj`, and the gradient reaches their adapters
through the scan's backward. Per step the layer sows into `retention_stats`
the most negative in-chunk running sum of `log g` (how far the in-chunk
decays underflow) and the smallest denominator `phi(q)^T z` (how near the
readout comes to EPS).

Serving is not built: a retention layer carries a state `[H, D, P]` (D =
8,256 at P = 128) and a normaliser a sequence, which the KV manager, the
paged pool and the step engine do not know.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.power_retention import retention_scan
from ..ops.power_retention_fused import scan as fused_scan
from ..ops.power_retention_fused import scan_plan

GATE_BIAS = 7.0  # added to the gate's logit: sigmoid(7) = 1 - 9.1e-4


class PowerRetention(nn.Module):
    cfg: "TransformerConfig"  # noqa: F821 - models/transformer.py imports this module
    spec: "LayerSpec"  # noqa: F821

    @nn.compact
    def __call__(self, u, *, decode: bool = False, adapter_ix=None):
        from .mla import _NormedRotated
        from .transformer import _run_proj, rope_table_for

        cfg, heads = self.cfg, self.spec.n_heads
        if decode:
            raise NotImplementedError(
                "a power-retention layer has no decode path: serving it needs a "
                "state [heads, 8,256 features, head width] and a normaliser a "
                "sequence, kept beside the KV pages (serving/kv.py, "
                "models/kv_pages.py and the step engine know neither); train it, "
                "or serve a model of attention layers"
            )
        groups, p = cfg.n_kv_heads, cfg.head_size
        bsz, seq, _ = u.shape
        q = _run_proj(cfg, heads * p, "q_proj", u, adapter_ix).reshape(bsz, seq, heads, p)
        k = _run_proj(cfg, groups * p, "k_proj", u, adapter_ix).reshape(bsz, seq, groups, p)
        v = _run_proj(cfg, groups * p, "v_proj", u, adapter_ix)
        cos_np, sin_np = rope_table_for(cfg.seq_len, p, self.spec.rope)
        cos = jnp.asarray(cos_np)[None, :seq, None, :]
        sin = jnp.asarray(sin_np)[None, :seq, None, :]
        shaped = functools.partial(nn.remat(_NormedRotated), cfg.norm_eps, 0, True)
        q, k = shaped(name="q_norm")(q, cos, sin), shaped(name="k_norm")(k, cos, sin)
        log_g = jax.nn.log_sigmoid(
            nn.Dense(heads, use_bias=False, name="gate_proj")(u).astype(jnp.float32) + GATE_BIAS
        )
        chunk = cfg.retention_chunk_size
        plan = scan_plan(bsz, seq, chunk, heads, groups, p, q.dtype)
        if plan["path"] == "pallas":  # q, k, v merged, as the kernels read them
            y, denominator_min = fused_scan(
                q.reshape(bsz, seq, heads * p), k.reshape(bsz, seq, groups * p), v, log_g,
                chunk=chunk, run=plan["run"])
        else:
            y, denominator_min = retention_scan(
                q, k, v.reshape(bsz, seq, groups, p), log_g, chunk=chunk)
        # the log-gate is <= 0: a chunk's most negative running sum is its whole sum
        whole = jnp.pad(log_g, [(0, 0), (0, -seq % chunk), (0, 0)])
        whole = whole.reshape(bsz, -1, chunk, heads).sum(axis=2)
        self.sow("retention_stats", "log_gate_min", jnp.min(whole))
        self.sow("retention_stats", "denominator_min", denominator_min)
        return _run_proj(cfg, cfg.dim, "o_proj", y.reshape(bsz, seq, heads * p), adapter_ix)
