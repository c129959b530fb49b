"""Decoder-only transformer (Llama-family) — the flagship JAXJob model.

Reference parity: BASELINE config #5 (Llama-3-8B LoRA multi-host) — the
reference orchestrated this in user containers (SURVEY.md §1); here the
model is in-repo and TPU-shaped:

- RMSNorm + RoPE + grouped-query attention + SwiGLU (Llama architecture),
  all expressed as large batched matmuls/einsums the MXU tiles natively.
- Megatron-style tensor-parallel sharding rules: QKV/gate/up kernels split
  output-dim over the `model` axis, o/down kernels split input-dim — one
  all-reduce per block, inserted by XLA from the shardings.
- `fsdp` axis shards the complementary kernel dim (ZeRO-3 style); rules
  degrade to replication on meshes without those axes (parallel/sharding.py).
- `scan_layers`: stack the blocks with `nn.scan` so compile time is O(1) in
  depth (XLA sees one block body; params gain a leading layer axis).
- `keep=` of `__call__` (static, training): what the backward finds kept.
  `"all"` what the forward left; `"block"` each block's input only, the
  block (or the scan body) run again from it under `nn.remat`. The Trainer
  chooses it under `train.remat: true` from what the device holds.
- Attention backend selectable: `xla` (einsum softmax, fine for short seq),
  `flash` (Pallas blockwise kernel, ops/flash_attention.py), `ring`, `ulysses`
  (context-parallel blockwise over the `context` axis, parallel/ring.py).
- Layers that differ (`layers`: heads, window, rotary table, dense or routed
  MLP by layer), a head width of its own (`head_dim`), a per-head output
  gate (`attn_gate`) and top-k routing over the experts held here
  (models/moe.py): what a published config of mixed window/full attention
  and sparse experts asks of one decoder. Unrolled layers only.
- A layer's mixer is attention or a Mamba-2 state-space mixer (models/ssm.py,
  ops/ssd.py), by `layer_types`; attention may go without rotation (`nope`);
  the four muP constants of a published config (`embedding_multiplier`,
  `attention_multiplier`, `residual_multiplier`, `logits_scaling`). A Mamba
  layer trains; it has no decode path.
- Two more mixers by `layer_types`: `kda`, a delta-rule linear-attention
  layer with a decay a key channel (models/kda.py, ops/kda.py), and `mla`,
  latent attention whose scores are wider than its values (models/mla.py:
  the flash kernels take the two widths); a router that scores by a sigmoid,
  chooses with a frozen bias and within the best groups (models/moe.py).
  They train; neither has a decode path.
- A fifth mixer by `layer_types`: `power_retention`, degree-2 power
  retention (models/retention.py, ops/power_retention.py): gated linear
  attention whose scores are the square of q . k, read out normalised. It
  trains; it has no decode path.
- Optional LoRA (`lora_rank > 0`): frozen base kernels + trainable A/B
  adapters on all projections; the trainer masks the optimizer to adapter
  params via `ModelBundle.trainable_patterns`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .registry import ModelBundle, i32_tokens, register


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One rotary table. `rotary_factor` is the share of a head's width that
    rotates (the first `rotary_factor * head_dim` of it; the rest passes).
    `yarn_factor` > 0 blends each frequency between theta^(-2i/d) and that
    over `yarn_factor`, by the linear ramp between the correction dims of
    `beta_fast` and `beta_slow` over `original_len` positions (YaRN as
    `transformers` computes it); cos and sin are multiplied by
    `attention_factor`."""

    theta: float = 10000.0
    rotary_factor: float = 1.0
    yarn_factor: float = 0.0
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one layer is, where layers differ: its mixer (`attention`,
    `mamba`, `kda`, `mla` or `power_retention`), and for attention its query
    heads, its window (0 = causal attention over the whole sequence) and its
    rotary table (a `rotary_factor` of 0 rotates nothing); `kda`, `mla` and
    `power_retention` read their heads (and `mla` and `power_retention` their
    rotary table) from here too; whether its MLP is routed or dense."""

    n_heads: int
    window: int = 0
    rope: RopeSpec = RopeSpec()
    routed: bool = False
    mixer: str = "attention"  # attention | mamba | kda | mla | power_retention

    def describe(self, cfg: "TransformerConfig") -> dict:
        mlp = {
            "mlp": "routed" if self.routed else "dense",
            "experts_held": cfg.held if self.routed else 0,
            "experts_published": cfg.n_experts if self.routed else 0,
        }
        if self.routed:
            mlp.update(  # `groups` alone is a Mamba layer's count of B and C groups
                score=cfg.router_score, router_groups=cfg.router_groups,
                router_groups_kept=cfg.router_groups_kept,
            )
        if self.mixer == "kda":
            return {
                "mixer": "kda",
                "heads": self.n_heads,
                "key_width": cfg.head_size,
                "value_width": cfg.head_size,
                "conv": cfg.kda_conv,
                "chunk": cfg.kda_chunk_size,
                "gate_bound": cfg.kda_gate_bound,
                **mlp,
            }
        if self.mixer == "power_retention":
            from ..ops.power_retention import DEGREE, EPS
            from .retention import GATE_BIAS

            p = cfg.head_size
            return {
                "mixer": "power_retention",
                "heads": self.n_heads,
                "kv_heads": cfg.n_kv_heads,
                "head_width": p,
                "degree": DEGREE,
                "feature_width": p * (p + 1) // 2,  # the symmetric square's
                "qk_norm": True,  # Qwen3's q_norm and k_norm, always
                "gate_width": self.n_heads,  # one log-gate a query head
                "gate_bias": GATE_BIAS,
                "eps": EPS,
                "rope_theta": self.rope.theta,
                **mlp,
            }
        if self.mixer == "mla":
            return {
                "mixer": "mla",
                "heads": self.n_heads,
                "latent": cfg.mla_latent,
                "nope_width": cfg.mla_nope_dim,
                "rope_width": cfg.mla_rope_dim,
                "value_width": cfg.mla_value_dim,
                "rope_theta": self.rope.theta,
                "gate": cfg.attn_gate,
                **mlp,
            }
        if self.mixer == "mamba":
            return {
                "mixer": "mamba",
                "heads": cfg.mamba_n_heads,
                "head_width": cfg.mamba_d_head,
                "state": cfg.mamba_d_state,
                "conv": cfg.mamba_d_conv,
                "chunk": cfg.mamba_chunk_size,
                "groups": cfg.mamba_n_groups,
                **mlp,
            }
        return {
            "mixer": "attention",
            "kind": "sliding" if self.window else "full",
            "window": self.window,
            "heads": self.n_heads,
            "rope": (
                "none" if not self.rope.rotary_factor
                else "yarn" if self.rope.yarn_factor else "default"
            ),
            "rope_theta": self.rope.theta,
            **mlp,
        }


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    # width of one head; None = dim // n_heads (read it as `head_size`)
    head_dim: Optional[int] = None
    hidden_dim: Optional[int] = None  # default 8/3 * dim rounded up to 128
    seq_len: int = 512
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dropout_rate: float = 0.0
    # auto = flash on TPU past ~2k tokens (O(S^2) score matrix starts to
    # dominate HBM traffic), xla otherwise; explicit values force a backend
    attention: str = "auto"  # auto | xla | flash | ring | ulysses
    # None = the flash kernels choose their blocks (ops/flash_attention.
    # choose_blocks) and the ring/ulysses chunk is 512; a number is the kv
    # block of the flash kernels and that chunk
    attention_block: Optional[int] = None
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple = ()  # projection names; empty = all projections
    # weight-only quantized projections for the serving decode path:
    # "int8" swaps every _proj for models/quant.Int8Dense (int8 kernel +
    # per-output-channel scale, dequant-free mixed matmul). Set by
    # quant.quantize_module at serving load — not a training config.
    quant: str = "none"  # none | int8
    # multi-tenant adapter multiplexing (ISSUE 19): > 0 stacks every
    # LoRA A/B pair to [slots, ...] and each projection gathers a
    # PER-ROW adapter by index (`adapter_ix` [B]), so one coalesced
    # decode batch mixes tenants. Slot 0 is the checkpoint's own
    # resident adapter (the serving layer broadcasts the restored
    # lora_a/lora_b there and zero-fills slots 1..N for the
    # AdapterRegistry to hot-swap). Set by serving stack-on-load
    # (serving/adapters.stack_adapter_params) — not a training config.
    adapter_slots: int = 0
    tie_embeddings: bool = False
    scan_layers: bool = False
    # layers that differ from one another: one LayerSpec a layer, built by
    # _make_config from published-style keys (`layer_types`,
    # `num_attention_heads_per_layer`, `sliding_window`, `rope_parameters`,
    # `mlp_only_layers`). Empty = n_layers copies of the layer the fields
    # above describe.
    layers: tuple = ()
    # per-head output gate: sigmoid of a bias-free linear map of the
    # attention block's input, one scalar a head and token, on the
    # attention output before o_proj
    attn_gate: bool = False
    # the four muP constants of a published config, under their published
    # names: the embeddings times `embedding_multiplier`; softmax of
    # q k^T times `attention_multiplier` (None = 1 / sqrt(head width)); each
    # residual branch times `residual_multiplier`; the logits over
    # `logits_scaling`
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # the Mamba-2 mixer of the layers whose `layer_types` entry is "mamba"
    # (models/ssm.py), under their published names
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # the delta-rule mixer of the layers whose `layer_types` entry is "kda"
    # (models/kda.py): heads of `head_size` for keys and values alike, the
    # short convolutions' taps, the scan's chunk, and the bound of the gate
    # (log-decay a channel in (kda_gate_bound, 0); ops/kda.py needs 16 x its
    # magnitude under float32's exp range)
    kda_conv: int = 4
    kda_chunk_size: int = 64
    kda_gate_bound: float = -5.0
    # latent attention of the layers whose entry is "mla" (models/mla.py): the
    # latent keys and values are expanded from, a head's score columns
    # without and with rotation (theta: the layer's rope), a head's values
    mla_latent: int = 512
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_value_dim: int = 128
    mla_qk_norm: bool = False
    # the chunk of the power-retention scan of the layers whose entry is
    # "power_retention" (models/retention.py, ops/power_retention.py): a
    # choice of the scan's, the readout is the same at every chunk
    retention_chunk_size: int = 256
    # MoE (models/moe.py): the router's width (the PUBLISHED count of
    # experts); 0 = dense MLPs. This process holds experts
    # [expert_offset, expert_offset + experts_held) of each routed layer
    # (experts_held 0 = all of them) and computes their part of the result.
    n_experts: int = 0
    experts_held: int = 0
    expert_offset: int = 0
    experts_per_token: int = 1
    routed_scale: float = 1.0
    norm_topk: bool = False
    # how the router scores and chooses: `softmax` or `sigmoid` scores; a
    # frozen selection bias an expert; the choice limited to the
    # `router_groups_kept` best of `router_groups` consecutive groups
    router_score: str = "softmax"
    router_bias: bool = False
    router_groups: int = 1
    router_groups_kept: int = 1
    expert_dim: Optional[int] = None  # an expert's width; None = ffn_dim
    shared_expert_dim: int = 0  # > 0: a dense SwiGLU beside the routed ones
    moe_aux_weight: float = 0.01  # Switch load-balancing loss; 0 = none
    # rows of the routed layer's buffer, over the expected count of local
    # assignments (tokens x experts_per_token x held / n_experts); never
    # more than the worst case. What does not fit is counted (moe.overflow)
    # and the Trainer stops on it.
    expert_buffer_factor: float = 2.0
    # pipeline parallelism: stage count (mesh `pipeline` axis size must match)
    pipeline_stages: int = 0
    pipeline_microbatches: int = 0
    # small-draft sub-config (ISSUE 15): field overrides applied to THIS
    # config to shape the speculative draft model (models/draft.py) —
    # fewer layers/dims, same architecture and tokenizer. Normalized to
    # a sorted (key, value) tuple by _make_config (the `draft:` section
    # of the model config) so the frozen config stays hashable; () means
    # "use the draft defaults" (n_layers // 2).
    draft: tuple = ()
    # fuse the lm head into the loss (ops/losses.fused_linear_masked_lm):
    # the [B,S,V] logits never materialize — the big activation-memory win
    # at llama vocab sizes on DP/FSDP meshes. Leave off under tensor
    # parallelism (the per-device logit shard is already V/tp small).
    fused_lm_loss: bool = False
    fused_loss_chunk: int = 8192

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts

    def layer(self, index: Optional[int]) -> LayerSpec:
        """The spec of layer `index`; without `layers` every layer is the
        one the uniform fields describe (and a scanned or pipelined block,
        which has no index, can ask for it)."""
        if not self.layers:
            return LayerSpec(
                n_heads=self.n_heads,
                rope=RopeSpec(theta=self.rope_theta),
                routed=self.n_experts > 0,
            )
        if index is None:
            raise ValueError(
                "layers that differ (heads, window, rope or MLP by layer) "
                "have no scanned or pipelined form: each needs its own index"
            )
        return self.layers[index]

    @property
    def ffn_dim(self) -> int:
        if self.hidden_dim:
            return self.hidden_dim
        h = int(8 * self.dim / 3)
        return ((h + 127) // 128) * 128  # MXU-friendly multiple of 128


def rope_table(seq_len: int, head_dim: int, theta: float):
    """Precomputed cos/sin [seq, head_dim/2] — static numpy, so they enter
    the jaxpr as constants shared across layers (scan broadcasts them)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = np.outer(np.arange(seq_len, dtype=np.float32), freqs)
    return np.cos(ang), np.sin(ang)


def rope_table_for(seq_len: int, head_dim: int, spec: RopeSpec):
    """cos/sin [seq, rot/2] of a RopeSpec, rot = rotary_factor * head_dim.
    The plain full-width case is `rope_table` itself."""
    rot = int(head_dim * spec.rotary_factor)
    if not spec.yarn_factor:
        cos, sin = rope_table(seq_len, rot, spec.theta)
    else:
        half = rot // 2
        pos_freqs = spec.theta ** (np.arange(0, half, dtype=np.float64) / half)
        extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (spec.yarn_factor * pos_freqs)

        def correction_dim(rotations):
            return (
                rot * np.log(spec.original_len / (rotations * 2 * np.pi))
            ) / (2 * np.log(spec.theta))

        low = max(np.floor(correction_dim(spec.beta_fast)), 0)
        high = min(np.ceil(correction_dim(spec.beta_slow)), rot - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1)
        freqs = (interpolation * ramp + extrapolation * (1 - ramp)).astype(np.float32)
        ang = np.outer(np.arange(seq_len, dtype=np.float32), freqs)
        cos, sin = np.cos(ang), np.sin(ang)
    if spec.attention_factor != 1.0:
        f = np.float32(spec.attention_factor)
        cos, sin = cos * f, sin * f
    return cos, sin


def _rotate(x, c, s):
    """(first-half, second-half) pairs of the leading 2 * c.shape[-1] of
    each head rotated; what lies beyond them (partial rotary) passes."""
    rot = 2 * c.shape[-1]
    if rot == x.shape[-1]:
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2 : rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1
    ).astype(x.dtype)


def apply_rope(x: jnp.ndarray, cos, sin, offset: int = 0):
    """x: [B, S, H, D]. Rotates the (first-half, second-half) pairs."""
    seq = x.shape[1]
    c = jax.lax.dynamic_slice_in_dim(cos, offset, seq)[None, :, None, :]
    s = jax.lax.dynamic_slice_in_dim(sin, offset, seq)[None, :, None, :]
    return _rotate(x, c, s)


def apply_rope_at(x: jnp.ndarray, cos, sin, positions: jnp.ndarray):
    """x: [B, S, H, D]; positions: [B, S] per-row absolute rotary positions.

    The left-padded decode path: rows of one batch sit at DIFFERENT true
    positions for the same cache slot (slot - row_pad), so the table lookup
    is a gather instead of apply_rope's shared slice."""
    c = jnp.take(cos, positions, axis=0)[:, :, None, :]  # [B, S, 1, half]
    s = jnp.take(sin, positions, axis=0)[:, :, None, :]
    return _rotate(x, c, s)


def _times(x, constant: float):
    """x times a published constant; a constant of 1 multiplies nothing, so
    a model without it traces as it did."""
    return x if constant == 1.0 else x * constant


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


class LoRADense(nn.Module):
    """Dense whose base kernel is frozen (optimizer-masked) with a trainable
    low-rank delta: y = x W + (alpha/r)(x A)B. Param names carry `lora_` so
    the bundle's trainable_patterns select them.

    With quant="int8" (serving quantize-on-load, ISSUE 15) the frozen
    base kernel rides the same dequant-free mixed matmul as Int8Dense —
    int8 kernel + per-output-channel f32 scale — while the adapter
    deltas stay at checkpoint precision: the base carries the bulk of
    the HBM traffic, the rank-r adapters carry the tenant signal.

    With slots > 0 (multi-tenant serving, ISSUE 19) the A/B pair is
    stacked to [slots, ...] and each batch row gathers ITS adapter by
    `adapter_ix` — one matmul group serves many tenants. The gathered
    weights are value-identical regardless of which slot a tenant's
    adapter happens to occupy, so a mixed-tenant batch row computes the
    same bytes as a solo server holding that adapter alone. adapter_ix
    defaults to slot 0 for every row (the base/resident adapter), which
    is also what pad rows ride."""

    features: int
    rank: int
    alpha: float
    quant: str = "none"
    slots: int = 0

    @nn.compact
    def __call__(self, x, adapter_ix=None):
        in_dim = x.shape[-1]
        if self.quant == "int8":
            kernel = self.param(
                "kernel", lambda _, s: jnp.zeros(s, jnp.int8),
                (in_dim, self.features),
            )
            scale = self.param(
                "scale", nn.initializers.ones, (self.features,)
            )
            y = jax.lax.dot_general(
                x,
                kernel,
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            y = (y * scale).astype(x.dtype)
        else:
            kernel = self.param(
                "kernel", nn.initializers.lecun_normal(),
                (in_dim, self.features),
            )
            y = x @ kernel.astype(x.dtype)
        if self.slots > 0:
            a = self.param(
                "lora_a", nn.initializers.normal(1e-2),
                (self.slots, in_dim, self.rank),
            )
            b = self.param(
                "lora_b", nn.initializers.zeros,
                (self.slots, self.rank, self.features),
            )
            ix = (
                jnp.zeros((x.shape[0],), jnp.int32)
                if adapter_ix is None
                else jnp.asarray(adapter_ix, jnp.int32)
            )
            # per-row gather of the stacked adapters: rank-r slivers, so
            # the gathered copies are activation-sized, not weight-sized
            aa = jnp.take(a.astype(x.dtype), ix, axis=0)  # [B, in, r]
            bb = jnp.take(b.astype(x.dtype), ix, axis=0)  # [B, r, out]
            delta = jnp.einsum("b...i,bir->b...r", x, aa)
            delta = jnp.einsum("b...r,bro->b...o", delta, bb)
        else:
            a = self.param("lora_a", nn.initializers.normal(1e-2), (in_dim, self.rank))
            b = self.param("lora_b", nn.initializers.zeros, (self.rank, self.features))
            delta = (x @ a.astype(x.dtype)) @ b.astype(x.dtype)
        return y + (self.alpha / self.rank) * delta


def _proj(cfg: TransformerConfig, features: int, name: str):
    if cfg.lora_rank > 0 and (not cfg.lora_targets or name in cfg.lora_targets):
        return LoRADense(features, rank=cfg.lora_rank, alpha=cfg.lora_alpha,
                         quant=cfg.quant, slots=cfg.adapter_slots, name=name)
    if cfg.quant == "int8":
        from .quant import Int8Dense

        return Int8Dense(features, name=name)
    return nn.Dense(features, use_bias=False, name=name)


def _run_proj(cfg: TransformerConfig, features: int, name: str, x,
              adapter_ix=None):
    """Apply a projection, routing the per-row adapter index only to
    LoRADense — nn.Dense/Int8Dense signatures stay untouched."""
    mod = _proj(cfg, features, name)
    if isinstance(mod, LoRADense):
        return mod(x, adapter_ix)
    return mod(x)


class Attention(nn.Module):
    cfg: TransformerConfig
    spec: Optional[LayerSpec] = None  # None = the uniform layer of cfg

    @nn.compact
    def __call__(
        self,
        x,
        *,
        train: bool = False,
        decode: bool = False,
        pad=None,
        pages=None,  # [B, n_pages] page table → block-paged KV (ISSUE 6)
        pos=None,  # traced int32 scalar — or [B] per-row vector on the
        # speculative verify path — first cache slot this call writes
        kv_layout=None,  # kv_pages.PagedKVLayout (static pool shape)
        prefix_len: int = 0,  # static: slots [0, prefix_len) hold a shared
        # prefilled prefix; the row's own tokens start (left-padded) after it
        prefix_lens=None,  # traced [B] per-row prefix widths — the step
        # scheduler (ISSUE 14) packs rows with DIFFERENT cached-prefix
        # lengths into one compiled program, so the mask's prefix boundary
        # must be a runtime argument there; overrides `prefix_len`
        adapter_ix=None,  # traced [B] per-row adapter slot (ISSUE 19);
        # None = slot 0 (the base/resident adapter) for every row
    ):
        cfg = self.cfg
        spec = self.spec or cfg.layer(None)
        B, S, _ = x.shape
        hd, nh, nkv = cfg.head_size, spec.n_heads, cfg.n_kv_heads
        window = spec.window or None
        from ..parallel.sharding import constrain

        gate = None
        if cfg.attn_gate:
            # one scalar a head and token, from the block's own input; a
            # plain Dense (never a LoRA target)
            gate = nn.sigmoid(
                nn.Dense(nh, use_bias=False, name="gate_proj")(x).astype(jnp.float32)
            )[..., None].astype(x.dtype)

        q = _run_proj(cfg, nh * hd, "q_proj", x, adapter_ix).reshape(B, S, nh, hd)
        k = _run_proj(cfg, nkv * hd, "k_proj", x, adapter_ix).reshape(B, S, nkv, hd)
        v = _run_proj(cfg, nkv * hd, "v_proj", x, adapter_ix).reshape(B, S, nkv, hd)
        # heads on the model axis (column-parallel QKV output)
        q = constrain(q, BATCH, "context", "model", None)
        k = constrain(k, BATCH, "context", "model", None)
        v = constrain(v, BATCH, "context", "model", None)
        rotates = spec.rope.rotary_factor > 0  # "nope": positions from causality alone
        if rotates:
            cos_np, sin_np = rope_table_for(cfg.seq_len, hd, spec.rope)
            cos, sin = jnp.asarray(cos_np), jnp.asarray(sin_np)
        sm_scale = cfg.attention_multiplier  # None: 1 / sqrt(hd)

        if decode:
            # autoregressive step: append this token's K/V into a per-layer
            # cache and attend the single query against the filled prefix.
            # Standard flax recipe — variables materialize on the first
            # mutable("cache") apply; cache holds nkv (pre-GQA) heads.
            #
            # Two cache layouts share the math below:
            #  * dense (pages=None): per-request [B, seq_len] slabs with a
            #    cache_index variable — every admitted row pays worst-case
            #    seq_len of HBM for its whole lifetime;
            #  * paged (pages=[B, n_pages]): one POOL of page-sized blocks
            #    [pool_pages, page_tokens, nkv, hd] shared by all requests,
            #    indexed through the per-row page table. The pool persists
            #    across batches, so the write position `pos` is a traced
            #    argument instead of a cache variable, and the attention
            #    window is the table span (n_pages * page_tokens), not
            #    seq_len. Slot semantics are unchanged — slot s holds the
            #    row's true position s - pad[b] — so the masked-softmax
            #    output is byte-identical to the dense path (dead slots
            #    score -1e30, whose exp underflows to exact 0.0).
            is_step = self.has_variable("cache", "cached_key")
            paged = pages is not None
            if window and (paged or prefix_len or prefix_lens is not None):
                raise NotImplementedError(
                    "a windowed layer decodes over the dense cache only: the "
                    "paged pool and the shared-prefix layouts keep every slot "
                    "of every layer and their masks know no window"
                )
            kv_int8 = paged and getattr(kv_layout, "kv_quant", "none") == "int8"
            if paged:
                pt_sz, pool_sz = kv_layout.page_tokens, kv_layout.pool_pages
                # int8 pool (ISSUE 15): the POOL holds int8 payloads plus
                # one f32 scale per (slot, kv head); the fp K/V window
                # only ever exists activation-sized after the gather, so
                # HBM residency is ~hd/(hd*bytes+4) of the fp pool.
                # Quantization is per-slot (quant.quantize_kv — a pure
                # function of that token's own K/V vector), so the pool
                # bytes are write-order independent: chunked prefill,
                # one-shot prefill and COW prefix reuse produce the SAME
                # quantized payload, keeping content-hash prefix reuse
                # and the chunked≡one-shot byte-identity contract valid
                # on a quantized pool.
                pool_dt = jnp.int8 if kv_int8 else k.dtype
                cached_k = self.variable(
                    "cache", "cached_key",
                    lambda: jnp.zeros((pool_sz, pt_sz, nkv, hd), pool_dt),
                )
                cached_v = self.variable(
                    "cache", "cached_value",
                    lambda: jnp.zeros((pool_sz, pt_sz, nkv, hd), pool_dt),
                )
                if kv_int8:
                    cached_ks = self.variable(
                        "cache", "cached_key_scale",
                        lambda: jnp.zeros((pool_sz, pt_sz, nkv), jnp.float32),
                    )
                    cached_vs = self.variable(
                        "cache", "cached_value_scale",
                        lambda: jnp.zeros((pool_sz, pt_sz, nkv), jnp.float32),
                    )
            else:
                cached_k = self.variable(
                    "cache", "cached_key",
                    lambda: jnp.zeros((B, cfg.seq_len, nkv, hd), k.dtype),
                )
                cached_v = self.variable(
                    "cache", "cached_value",
                    lambda: jnp.zeros((B, cfg.seq_len, nkv, hd), v.dtype),
                )
                cache_index = self.variable(
                    "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
                )
            if is_step:
                # S == 1: one sampled token; S > 1: batched PREFILL — the
                # whole prompt in one pass that also fills the cache, so
                # generation costs 1 forward + (new-1) cached steps instead
                # of (P + new - 1) sequential steps
                if paged:
                    if pos is None:
                        raise ValueError(
                            "paged decode needs pos (the pool has no "
                            "cache_index — write position is per group)"
                        )
                    pos = jnp.asarray(pos, jnp.int32)
                else:
                    pos = (
                        cache_index.value
                        if pos is None
                        else jnp.asarray(pos, jnp.int32)
                    )
                # speculative verify windows pass per-row [B] frontiers:
                # once accept lengths diverge, rows of one group sit at
                # different write positions, so slots/rope/mask below work
                # over a [B, S] slot grid instead of one shared [S] row
                per_row = pos.ndim == 1
                if per_row and pad is None:
                    raise ValueError(
                        "per-row pos needs pad (bucketed-row decode)"
                    )
                row_slots = (
                    pos[:, None] if per_row else pos
                ) + jnp.arange(S)[None, :]
                if rotates and pad is None:
                    q = apply_rope(q, cos, sin, offset=pos)
                    k = apply_rope(k, cos, sin, offset=pos)
                elif rotates:
                    # left-padded rows: cache slot s holds the row's true
                    # position s - pad[b]. Pad slots clamp to 0 — their K/V
                    # never attend (masked below), only the table index
                    # must stay in range. With a shared prefix the row's
                    # own region starts at prefix_len, so the same formula
                    # holds (writes only ever target slots >= prefix_len).
                    positions = jnp.maximum(row_slots - pad[:, None], 0)
                    q = apply_rope_at(q, cos, sin, positions)
                    k = apply_rope_at(k, cos, sin, positions)
                if paged:
                    # scatter this call's S slots through the page table:
                    # slot s lives at (pages[b, s // pt], s % pt). Rows
                    # never share their WRITE pages (copy-on-write: shared
                    # prefix pages sit below pos and are read-only here).
                    # A draft window may overrun the row's table span
                    # (slots the verify step will reject): those map to
                    # the out-of-range page id pool_sz and the scatter
                    # drops them, so the pool is never written past the
                    # row's own pages.
                    slots = jnp.broadcast_to(row_slots, (B, S))
                    pp = jnp.take_along_axis(
                        pages, slots // pt_sz, axis=1,
                        mode="fill", fill_value=pool_sz,
                    )
                    off = slots % pt_sz
                    win = pages.shape[1] * pt_sz
                    if kv_int8:
                        # quantize-on-write: per-slot per-head int8 +
                        # f32 scale. The fresh K/V are read back DEQUANT
                        # through the same gather as the history, so one
                        # value of a slot exists — whichever path wrote
                        # it, attention sees identical bytes.
                        from .quant import dequantize_kv, quantize_kv

                        kq, ks = quantize_kv(k)
                        vq, vs = quantize_kv(v)
                        k_all = cached_k.value.at[pp, off].set(
                            kq, mode="drop"
                        )
                        v_all = cached_v.value.at[pp, off].set(
                            vq, mode="drop"
                        )
                        ks_all = cached_ks.value.at[pp, off].set(
                            ks, mode="drop"
                        )
                        vs_all = cached_vs.value.at[pp, off].set(
                            vs, mode="drop"
                        )
                        cached_k.value, cached_v.value = k_all, v_all
                        cached_ks.value, cached_vs.value = ks_all, vs_all
                        k_all = dequantize_kv(
                            k_all[pages].reshape(B, win, nkv, hd),
                            ks_all[pages].reshape(B, win, nkv),
                            k.dtype,
                        )
                        v_all = dequantize_kv(
                            v_all[pages].reshape(B, win, nkv, hd),
                            vs_all[pages].reshape(B, win, nkv),
                            v.dtype,
                        )
                    else:
                        k_all = cached_k.value.at[pp, off].set(
                            k, mode="drop"
                        )
                        v_all = cached_v.value.at[pp, off].set(
                            v, mode="drop"
                        )
                        cached_k.value, cached_v.value = k_all, v_all
                        # gather the row's whole window back out of the
                        # pool; unallocated tail entries alias a scratch
                        # page whose garbage is masked dead below
                        # (slot > pos + i)
                        k_all = k_all[pages].reshape(B, win, nkv, hd)
                        v_all = v_all[pages].reshape(B, win, nkv, hd)
                elif per_row:
                    # rows at different frontiers: dynamic_update_slice's
                    # shared offset no longer applies, scatter per row
                    # instead; slots past seq_len (rejected draft tail at
                    # the cache edge) drop harmlessly. The caller drives
                    # pos explicitly, so cache_index is left alone.
                    b_ix = jnp.arange(B)[:, None]
                    k_all = cached_k.value.at[b_ix, row_slots].set(
                        k, mode="drop"
                    )
                    v_all = cached_v.value.at[b_ix, row_slots].set(
                        v, mode="drop"
                    )
                    cached_k.value, cached_v.value = k_all, v_all
                    win = cfg.seq_len
                else:
                    k_all = jax.lax.dynamic_update_slice(
                        cached_k.value, k, (0, pos, 0, 0)
                    )
                    v_all = jax.lax.dynamic_update_slice(
                        cached_v.value, v, (0, pos, 0, 0)
                    )
                    cached_k.value, cached_v.value = k_all, v_all
                    cache_index.value = pos + S
                    win = cfg.seq_len
                # Scores straight against the grouped cache: the full-cache
                # K/V read dominates each decode step, and expanding it
                # (jnp.repeat) multiplied that read by nh/nkv for identical
                # math. Head order h = kv*G + g matches repeat's; MHA is
                # just G == 1 through the same einsums.
                G = nh // nkv
                scores = jnp.einsum(
                    "bqkgd,bskd->bkgqs",
                    q.reshape(B, S, nkv, G, hd),
                    k_all,
                    preferred_element_type=jnp.float32,
                ).reshape(B, nh, S, win)
                scores = (
                    scores / np.sqrt(hd) if sm_scale is None else scores * sm_scale
                )
                # query row i may see cache positions <= pos + i (with a
                # per-row pos the comparison broadcasts to [B, S, win])
                live = (
                    jnp.arange(win)[None, None, :] <= row_slots[:, :, None]
                )
                if window:
                    # slots and positions differ by the row's pad alone, so
                    # a distance in slots is a distance in positions
                    live = live & (
                        jnp.arange(win)[None, None, :]
                        > row_slots[:, :, None] - window
                    )
                mask = live[:, None, :, :]
                if pad is not None:
                    if prefix_lens is not None:
                        # per-row traced prefix boundary (step scheduler):
                        # same [prefix | dead pad | own] layout as the
                        # static branch below, with the boundary broadcast
                        # per row. prefix_lens[b] == 0 degrades to the
                        # plain left-pad mask, so one compiled program
                        # serves warm and cold rows alike.
                        ar = jnp.arange(win)[None, :]
                        pl = prefix_lens[:, None]
                        valid = (ar < pl) | (ar >= pl + pad[:, None])
                    elif prefix_len:
                        # row layout: [shared prefix 0..prefix_len) |
                        # dead left-pad | own tokens]. Prefix slots are
                        # live for every row; the dead window shifts right.
                        ar = jnp.arange(win)[None, :]
                        valid = (ar < prefix_len) | (
                            ar >= prefix_len + pad[:, None]
                        )
                    else:
                        # left-pad slots are dead for every query of that row
                        valid = jnp.arange(win)[None, :] >= pad[:, None]
                    mask = mask & valid[:, None, None, :]
                scores = jnp.where(mask, scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
                out = jnp.einsum(
                    "bkgqs,bskd->bqkgd",
                    probs.reshape(B, nkv, G, S, win),
                    v_all,
                ).reshape(B, S, nh, hd)
                if gate is not None:
                    out = out * gate
                out = out.reshape(B, S, nh * hd)
                return _run_proj(cfg, cfg.dim, "o_proj", out, adapter_ix)
            # cache creation pass (first mutable apply): fall through to the
            # ordinary full-sequence attention so output shapes are normal

        if rotates:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        # GQA expansion is the attention dispatch's concern: flash consumes
        # grouped kv natively (no repeated K/V in HBM), ring rotates it and
        # ulysses scatters it at true kv-head width; only the plain einsum
        # gets kv expanded inside dot_product_attention. Do NOT pre-expand
        # here — that would forfeit those bandwidth savings.

        from ..ops.attention import dot_product_attention

        out = dot_product_attention(
            q, k, v, causal=True, backend=cfg.attention,
            block_kv=cfg.attention_block, window=window, scale=sm_scale,
        )
        if gate is not None:
            out = out * gate
        out = constrain(out.reshape(B, S, nh * hd), BATCH, "context", "model")
        return _run_proj(cfg, cfg.dim, "o_proj", out, adapter_ix)


# logical axes the batch dim may be split over: training meshes carry
# data/fsdp, a serving decode mesh carries `batch` — constrain() degrades
# whichever axes the live mesh lacks, so one set serves both paths
BATCH = ("batch", "data", "fsdp")


class FeedForward(nn.Module):
    cfg: TransformerConfig
    width: Optional[int] = None  # None = cfg.ffn_dim (a shared expert has its own)

    @nn.compact
    def __call__(self, x, adapter_ix=None):
        from ..parallel.sharding import constrain

        cfg = self.cfg
        width = self.width or cfg.ffn_dim
        gate = _run_proj(cfg, width, "gate_proj", x, adapter_ix)
        up = _run_proj(cfg, width, "up_proj", x, adapter_ix)
        # column-parallel output: hidden dim lives on the model axis until
        # the row-parallel down projection reduces it
        h = constrain(nn.silu(gate) * up, BATCH, "context", "model")
        return _run_proj(cfg, cfg.dim, "down_proj", h, adapter_ix)


class Block(nn.Module):
    cfg: TransformerConfig
    train: bool = False
    decode: bool = False
    # paged-KV statics (ISSUE 6): the pool shape and shared-prefix width
    # are compile-time, so they ride as module attributes; the traced page
    # table / write position arrive as call arguments
    kv_layout: Optional[Any] = None
    prefix_len: int = 0
    index: Optional[int] = None  # which layer (only layers that differ ask)

    @nn.compact
    def __call__(self, x, pad=None, pages=None, pos=None, prefix_lens=None,
                 adapter_ix=None):
        from ..parallel.sharding import constrain

        cfg = self.cfg
        spec = cfg.layer(self.index)
        x = constrain(x, BATCH, "context", None)
        normed = RMSNorm(cfg.norm_eps, name="attention_norm")(x)
        if spec.mixer == "mamba":
            from .ssm import Mamba2

            h = Mamba2(cfg, name="mamba")(
                normed, decode=self.decode, adapter_ix=adapter_ix
            )
        elif spec.mixer == "kda":
            from .kda import KimiDeltaAttention

            h = KimiDeltaAttention(cfg, spec.n_heads, name="kda")(
                normed, decode=self.decode, adapter_ix=adapter_ix
            )
        elif spec.mixer == "power_retention":
            from .retention import PowerRetention

            h = PowerRetention(cfg, spec, name="retention")(
                normed, decode=self.decode, adapter_ix=adapter_ix
            )
        elif spec.mixer == "mla":
            from .mla import LatentAttention

            h = LatentAttention(cfg, spec, name="mla")(
                normed, decode=self.decode, adapter_ix=adapter_ix
            )
        else:
            h = Attention(cfg, spec, name="attention")(
                normed,
                train=self.train,
                decode=self.decode,
                pad=pad,
                pages=pages,
                pos=pos,
                kv_layout=self.kv_layout,
                prefix_len=self.prefix_len,
                prefix_lens=prefix_lens,
                adapter_ix=adapter_ix,
            )
        if cfg.dropout_rate:
            h = nn.Dropout(cfg.dropout_rate, deterministic=not self.train)(h)
        x = x + _times(h, cfg.residual_multiplier)
        if spec.routed:
            from .moe import MoEFeedForward

            normed = RMSNorm(cfg.norm_eps, name="mlp_norm")(x)
            h = MoEFeedForward(
                cfg.dim,
                cfg.expert_dim or cfg.ffn_dim,
                cfg.n_experts,
                held=cfg.held,
                offset=cfg.expert_offset,
                top_k=cfg.experts_per_token,
                routed_scale=cfg.routed_scale,
                norm_topk=cfg.norm_topk,
                aux_weight=cfg.moe_aux_weight,
                buffer_factor=cfg.expert_buffer_factor,
                score=cfg.router_score,
                bias=cfg.router_bias,
                groups=cfg.router_groups,
                groups_kept=cfg.router_groups_kept,
                name="moe",
            )(normed, train=self.train)
            if cfg.shared_expert_dim:
                # every chip of the deployment computes it alike, ungated
                h = h + FeedForward(
                    cfg, cfg.shared_expert_dim, name="shared_expert"
                )(normed, adapter_ix)
        else:
            h = FeedForward(cfg, name="mlp")(
                RMSNorm(cfg.norm_eps, name="mlp_norm")(x), adapter_ix
            )
        if cfg.dropout_rate:
            h = nn.Dropout(cfg.dropout_rate, deterministic=not self.train)(h)
        return x + _times(h, cfg.residual_multiplier)


class _ScanBlock(nn.Module):
    """Scan body: (carry, _) → (carry, None) signature nn.scan requires.

    The carry is either the activations alone, an (activations, pad) tuple
    on the left-padded decode path, or (activations, pad, pages, pos) on
    the paged-KV path — the traced per-row arrays ride in the carry
    (unchanged by every layer) because a traced array cannot be a module
    attribute; the static paged knobs are module attributes."""

    cfg: TransformerConfig
    train: bool = False
    decode: bool = False
    kv_layout: Optional[Any] = None
    prefix_len: int = 0

    @nn.compact
    def __call__(self, carry, _):
        block = Block(
            self.cfg, self.train, self.decode,
            kv_layout=self.kv_layout, prefix_len=self.prefix_len,
            name="block",
        )
        if isinstance(carry, tuple):
            if len(carry) == 6:
                # multi-tenant decode (ISSUE 19): the per-row adapter
                # slots ride the carry next to pad/pages/pos/prefix_lens
                x, pad, pages, pos, prefix_lens, adapter_ix = carry
                return (
                    block(
                        x, pad=pad, pages=pages, pos=pos,
                        prefix_lens=prefix_lens, adapter_ix=adapter_ix,
                    ),
                    pad, pages, pos, prefix_lens, adapter_ix,
                ), None
            if len(carry) == 5:
                x, pad, pages, pos, prefix_lens = carry
                return (
                    block(
                        x, pad=pad, pages=pages, pos=pos,
                        prefix_lens=prefix_lens,
                    ),
                    pad, pages, pos, prefix_lens,
                ), None
            if len(carry) == 4:
                x, pad, pages, pos = carry
                return (block(x, pad=pad, pages=pages, pos=pos), pad, pages, pos), None
            x, pad = carry
            return (block(x, pad=pad), pad), None
        return block(carry), None


class PipelinedLayers(nn.Module):
    """The block stack with stage-stacked params [P, Lp, ...] executed as a
    GPipe pipeline over the mesh `pipeline` axis (parallel/pipeline.py).

    Params are created functionally (vmapped Block.init) so their tree
    matches an ordinary per-layer stack with two extra leading dims — the
    PIPELINE_RULES shardings place dim 0 on the pipeline axis. Without a
    pipeline mesh axis in scope the same params run as a plain nested scan,
    so init/dry-run on one device is identical math. Dropout and MoE aux
    losses are unsupported inside the pipelined stack."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        n_stages = cfg.pipeline_stages
        if cfg.n_layers % n_stages:
            raise ValueError(
                f"n_layers {cfg.n_layers} not divisible by "
                f"pipeline_stages {n_stages}"
            )
        per_stage = cfg.n_layers // n_stages
        block = Block(cfg, False)
        template = jnp.zeros((1, cfg.seq_len, cfg.dim), x.dtype)

        def init_stacked(rng):
            def one(r):
                return block.init({"params": r}, template)["params"]

            stacked = jax.vmap(one)(jax.random.split(rng, cfg.n_layers))
            return jax.tree.map(
                lambda a: a.reshape(n_stages, per_stage, *a.shape[1:]), stacked
            )

        params = self.param("stages", init_stacked)

        def stage_fn(stage_params, h):
            def layer(carry, layer_params):
                return block.apply({"params": layer_params}, carry), None

            h, _ = jax.lax.scan(layer, h, stage_params)
            return h

        from ..parallel.ring import current_mesh

        mesh = current_mesh()
        if mesh is not None and mesh.shape.get("pipeline", 1) > 1:
            from ..parallel.pipeline import pipeline_apply

            n_micro = cfg.pipeline_microbatches or n_stages
            return pipeline_apply(
                stage_fn, params, x, mesh=mesh, n_micro=n_micro
            )
        # no pipeline axis (init, dry-run, single device): same math, nested scan
        h, _ = jax.lax.scan(lambda c, p: (stage_fn(p, c), None), x, params)
        return h


# The rungs of the Trainer's remat ladder that `Transformer.__call__(keep=)`
# understands beyond "all", most kept first (`ModelBundle.keep_rungs`).
KEEP_RUNGS = ("block",)


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens,
        *,
        train: bool = False,
        decode: bool = False,
        return_features: bool = False,
        keep: str = "all",  # what the backward finds kept (the Trainer's
        # remat ladder, KEEP_RUNGS): "all" = what the forward left, "block" =
        # each block's input, the block run again from it
        pad=None,  # [B] left-pad widths for bucketed decode (serving path)
        pages=None,  # [B, n_pages] page table → block-paged KV decode
        pos=None,  # traced int32 scalar (or [B] per-row speculative
        # frontiers): first cache slot written this call
        kv_layout=None,  # kv_pages.PagedKVLayout (static pool shape)
        prefix_len: int = 0,  # static shared-prefix width (paged path)
        prefix_lens=None,  # traced [B] per-row prefix widths (step
        # scheduler mixed-prefix programs); overrides prefix_len
        adapter_ix=None,  # traced [B] per-row adapter slot (ISSUE 19):
        # gathers each row's stacked lora_a/lora_b so one batch mixes
        # tenants; None = slot 0 (base/resident adapter) for all rows
    ):
        cfg = self.cfg
        if adapter_ix is not None and cfg.adapter_slots <= 0:
            raise ValueError(
                "adapter_ix needs a slot-stacked model (adapter_slots > 0 "
                "— serving/adapters.stack_adapter_params)"
            )
        if adapter_ix is not None and cfg.pipeline_stages > 1:
            raise ValueError(
                "adapter_ix is not supported with pipeline_stages > 1"
            )
        if decode and cfg.pipeline_stages > 1:
            raise ValueError(
                "KV-cache decode is not supported with pipeline_stages > 1 "
                "(the stage-stacked weights have no per-layer cache slots); "
                "generate with a non-pipelined copy of the params"
            )
        if pad is not None and not decode:
            raise ValueError(
                "pad (left-pad widths) only applies to the KV-cache decode "
                "path; training/eval should mask via labels instead"
            )
        if pages is not None:
            if not decode:
                raise ValueError(
                    "pages (block-paged KV) only applies to the decode path"
                )
            if kv_layout is None:
                raise ValueError("paged decode needs kv_layout (pool shape)")
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.dim,
            name="embed",
            embedding_init=nn.initializers.normal(0.02),
        )
        x = _times(embed(tokens), cfg.embedding_multiplier)
        from ..parallel.sharding import constrain

        # Pin the gather output to the blocks' activation layout HERE:
        # the table is dim-sharded over fsdp, so the lookup's output
        # inherits that — left unpinned, GSPMD defers the reshard into
        # layer_0's boundary where (with an expert axis in the mesh) it
        # gives up and fully rematerializes (SPMD warnings, r4 verdict
        # weakness #2). An explicit constraint at the producer turns it
        # into one all-gather over fsdp at a well-defined point.
        x = constrain(x, BATCH, "context", None)
        if keep not in ("all", *KEEP_RUNGS):
            raise ValueError(f"keep={keep!r}: one of 'all', {KEEP_RUNGS}")
        if cfg.pipeline_stages > 1:
            x = PipelinedLayers(cfg, name="pipeline")(x)
        elif cfg.scan_layers:
            Layers = nn.scan(
                # the scan keeps each iteration's residuals apart already
                nn.remat(_ScanBlock, prevent_cse=False)
                if keep == "block" else _ScanBlock,
                variable_axes={"params": 0, "losses": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layers,
            )
            layers = Layers(
                cfg, train, decode,
                kv_layout=kv_layout, prefix_len=prefix_len, name="layers",
            )
            if adapter_ix is not None:
                # 6-tuple carry: per-row adapter slots alongside the other
                # traced row arrays (tenant-mixed programs only, so every
                # legacy carry keeps its compiled identity)
                (x, _, _, _, _, _), _ = layers(
                    (x, pad, pages, pos, prefix_lens, adapter_ix), None
                )
            elif prefix_lens is not None:
                # 5-tuple carry: the traced per-row prefix widths ride
                # alongside pad/pages/pos (step-scheduler programs only,
                # so the legacy 4-tuple carry keeps its compiled identity)
                (x, _, _, _, _), _ = layers(
                    (x, pad, pages, pos, prefix_lens), None
                )
            elif pages is not None or pos is not None:
                # pos rides the 4-tuple carry on the dense speculative
                # path too (pages is then a None leafless subtree)
                (x, _, _, _), _ = layers((x, pad, pages, pos), None)
            elif pad is not None:
                (x, _), _ = layers((x, pad), None)
            else:
                x, _ = layers(x, None)
        else:
            block_cls = nn.remat(Block) if keep == "block" else Block
            for i in range(cfg.n_layers):
                x = block_cls(
                    cfg, train, decode,
                    kv_layout=kv_layout, prefix_len=prefix_len,
                    index=i,
                    name=f"layer_{i}",
                )(x, pad=pad, pages=pages, pos=pos, prefix_lens=prefix_lens,
                  adapter_ix=adapter_ix)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        if cfg.logits_scaling != 1.0:
            # the head is linear: the features over it are the logits over it,
            # on the fused-loss path too
            x = x / cfg.logits_scaling
        if return_features:
            # fused-loss path: the caller computes head+loss from features;
            # the head params must still exist in the tree, so touch the
            # module without using its output (init-time only — dead code
            # after tracing)
            if not cfg.tie_embeddings and self.is_initializing():
                nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head")(x)
            return x
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head")(x)


# -------------------------------------------------------------- sharding rules
# Megatron TP: column-parallel (out-dim on `model`) for q/k/v/gate/up, row-
# parallel (in-dim on `model`) for o/down; fsdp shards the complementary dim.
# Patterns are unanchored so they match both `layer_3/...` and the scan
# layout `layers/block/...` (where kernels gain a leading layer axis — the
# rule axes then apply to the trailing dims via the sharding resolver).
TRANSFORMER_RULES = (
    # hidden dim sharded (model+fsdp): the token lookup stays a LOCAL gather
    # — vocab-sharding instead makes GSPMD emit a cross-shard gather with
    # involuntary full rematerialization
    (r"embed/embedding", (None, ("model", "fsdp"))),
    (r"(q_proj|k_proj|v_proj|gate_proj|up_proj|in_proj)/kernel", ("fsdp", "model")),
    (r"(o_proj|down_proj|out_proj)/kernel", ("model", "fsdp")),
    (r"(q_proj|k_proj|v_proj|gate_proj|up_proj|in_proj)/lora_a", ("fsdp", None)),
    (r"(q_proj|k_proj|v_proj|gate_proj|up_proj|in_proj)/lora_b", (None, "model")),
    (r"(o_proj|down_proj|out_proj)/lora_a", ("model", None)),
    (r"(o_proj|down_proj|out_proj)/lora_b", (None, "fsdp")),
    (r"lm_head/kernel", ("fsdp", "model")),
)

# Under nn.scan, kernels are [layers, in, out]: shift rules right by one dim.
SCAN_RULES = tuple(
    (pat, (None, *axes)) if "embedding" not in pat and "lm_head" not in pat else (pat, axes)
    for pat, axes in TRANSFORMER_RULES
)

# Pipelined stack: kernels are [stages, layers_per_stage, in, out] under
# `pipeline/stages/...` — stage dim on the pipeline axis. Listed before the
# base rules so the anchored prefix wins the first-match resolution.
PIPELINE_RULES = tuple(
    (r"stages/.*" + pat, ("pipeline", None, *axes))
    for pat, axes in TRANSFORMER_RULES
    if "embedding" not in pat and "lm_head" not in pat
)

PRESETS: dict[str, dict] = {
    # tiny flagship used by tests / graft entry / chip_smoke's rehearsal
    "tiny": dict(
        dim=256, n_layers=4, n_heads=8, n_kv_heads=4, vocab_size=4096, seq_len=256
    ),
    "llama3-8b": dict(
        dim=4096, n_layers=32, n_heads=32, n_kv_heads=8, hidden_dim=14336,
        vocab_size=128256, seq_len=8192, rope_theta=500000.0,
    ),
    "llama3-1b": dict(
        dim=2048, n_layers=16, n_heads=32, n_kv_heads=8, hidden_dim=8192,
        vocab_size=128256, seq_len=8192, rope_theta=500000.0,
    ),
}


def _rope_spec(published: dict) -> RopeSpec:
    """A RopeSpec from one published `rope_parameters` group."""
    kind = published.get("rope_type", "default")
    base = dict(
        theta=float(published.get("rope_theta", 10000.0)),
        rotary_factor=float(published.get("partial_rotary_factor", 1.0)),
    )
    if kind == "default":
        return RopeSpec(**base)
    if kind != "yarn":
        raise ValueError(f"unknown rope_type {kind!r} (known: default, yarn)")
    factor = float(published["factor"])
    return RopeSpec(
        **base,
        yarn_factor=factor,
        original_len=int(published["original_max_position_embeddings"]),
        beta_fast=float(published.get("beta_fast", 32.0)),
        beta_slow=float(published.get("beta_slow", 1.0)),
        attention_factor=float(
            published.get("attention_factor") or 0.1 * np.log(factor) + 1.0
        ),
    )


_LAYER_KEYS = (
    "layer_types", "num_attention_heads_per_layer", "sliding_window",
    "rope_parameters", "mlp_only_layers", "position_embedding_type",
)
_ATTENTION_KINDS = ("full_attention", "sliding_attention", "attention")
_OTHER_MIXERS = ("mamba", "kda", "mla", "power_retention")  # an entry that names its mixer


def _layer_specs(pub: dict, base: dict) -> tuple:
    """One LayerSpec a layer from the published-style keys in `pub`; what a
    key leaves unsaid is the uniform layer of `base` (the other fields)."""
    n = int(base.get("n_layers", TransformerConfig.n_layers))

    def per_layer(key, default):
        vals = pub.get(key)
        if vals is None:
            return [default] * n
        if len(vals) < n:
            raise ValueError(f"{key} names {len(vals)} layers, n_layers is {n}")
        return list(vals[:n])  # a published list may run to the uncut depth

    kinds = per_layer("layer_types", "full_attention")
    heads = per_layer(
        "num_attention_heads_per_layer",
        int(base.get("n_heads", TransformerConfig.n_heads)),
    )
    window = int(pub.get("sliding_window") or 0)
    ropes = pub.get("rope_parameters") or {}
    if ropes and not all(isinstance(v, dict) for v in ropes.values()):
        ropes = {kind: ropes for kind in set(kinds)}  # one group for all
    dense = set(pub.get("mlp_only_layers") or ())
    routed = int(base.get("n_experts") or 0) > 0
    positions = pub.get("position_embedding_type") or "rope"
    if positions not in ("rope", "nope"):
        raise ValueError(
            f"unknown position_embedding_type {positions!r} (known: rope, nope)"
        )
    specs = []
    for i, (kind, h) in enumerate(zip(kinds, heads)):
        if kind not in (*_ATTENTION_KINDS, *_OTHER_MIXERS):
            raise ValueError(f"unknown layer type {kind!r} at layer {i}")
        if kind == "sliding_attention" and window < 1:
            raise ValueError("sliding_attention layers need sliding_window")
        if positions == "nope":
            rope = RopeSpec(rotary_factor=0.0)
        elif kind in ropes:
            rope = _rope_spec(ropes[kind])
        else:
            rope = RopeSpec(theta=float(base.get("rope_theta", 10000.0)))
        specs.append(LayerSpec(
            n_heads=int(h),
            window=window if kind == "sliding_attention" else 0,
            rope=rope,
            routed=routed and i not in dense,
            mixer=kind if kind in _OTHER_MIXERS else "attention",
        ))
    return tuple(specs)


def _make_config(config: dict) -> TransformerConfig:
    config = dict(config)
    published = {k: config.pop(k) for k in _LAYER_KEYS if k in config}
    # Polyaxonfile aliases (examples/llama_lora.yaml): variant → preset,
    # max_len → seq_len, lora: {rank, alpha, targets} → lora_* fields
    variant = config.pop("variant", None)
    if variant is not None:
        config.setdefault("preset", f"llama3-{str(variant).lower()}")
    if "max_len" in config:
        config.setdefault("seq_len", config.pop("max_len"))
    lora = config.pop("lora", None)
    if isinstance(lora, dict):
        config.setdefault("lora_rank", int(lora.get("rank", 8)))
        config.setdefault("lora_alpha", float(lora.get("alpha", 16.0)))
        if lora.get("targets"):
            config.setdefault("lora_targets", tuple(lora["targets"]))
    draft = config.pop("draft", None)
    if draft:
        # `draft:` sub-config (ISSUE 15): a dict of TransformerConfig
        # overrides for the small draft model, normalized to a hashable
        # sorted tuple (the frozen config must ride jit keys)
        if hasattr(draft, "items"):
            draft = tuple(sorted(
                (str(k), tuple(v) if isinstance(v, list) else v)
                for k, v in draft.items()
            ))
        config["draft"] = tuple(draft)
    preset = config.pop("preset", None)
    if preset is not None and preset not in PRESETS:
        raise ValueError(f"unknown preset {preset!r}; known: {sorted(PRESETS)}")
    base: dict = dict(PRESETS.get(preset, {}))
    base.update({k: v for k, v in config.items() if v is not None})
    if published:
        base["layers"] = _layer_specs(published, base)
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    cfg = TransformerConfig(**{k: v for k, v in base.items() if k in fields})
    if cfg.layers and (cfg.scan_layers or cfg.pipeline_stages > 1):
        raise ValueError(
            "scan_layers and pipeline_stages stack one block's parameters "
            "along a layer axis and cannot hold layers that differ (heads, "
            "window, rope, attention, Mamba, KDA, MLA or power-retention mixer, "
            "dense/routed MLP by layer: "
            "layer_types, num_attention_heads_per_layer, rope_parameters, "
            "mlp_only_layers, position_embedding_type)"
        )
    for spec in cfg.layers or (cfg.layer(None),):
        if spec.n_heads % cfg.n_kv_heads:
            raise ValueError(
                f"{spec.n_heads} query heads do not divide over "
                f"{cfg.n_kv_heads} kv heads"
            )
    if cfg.n_experts > 0:
        if not 0 < cfg.experts_per_token <= cfg.n_experts:
            raise ValueError(
                f"experts_per_token {cfg.experts_per_token} of "
                f"{cfg.n_experts} experts"
            )
        groups, kept = cfg.router_groups, cfg.router_groups_kept
        if groups < 1 or cfg.n_experts % groups or not 0 < kept <= groups or (
            groups > 1 and cfg.experts_per_token > kept * (cfg.n_experts // groups)
        ):
            raise ValueError(
                f"router_groups {groups} with {kept} kept do not divide "
                f"{cfg.n_experts} experts or hold fewer than experts_per_token "
                f"{cfg.experts_per_token}"
            )
        if cfg.expert_offset < 0 or cfg.expert_offset + cfg.held > cfg.n_experts:
            raise ValueError(
                f"experts [{cfg.expert_offset}, {cfg.expert_offset + cfg.held}) "
                f"are not among the router's {cfg.n_experts}"
            )
    if cfg.pipeline_stages > 1:
        # the pipelined stack applies blocks functionally: no dropout rngs,
        # no mutable collections — reject rather than silently change the
        # training objective
        if cfg.dropout_rate > 0:
            raise ValueError("pipeline_stages > 1 does not support dropout_rate > 0")
        if cfg.n_experts > 0:
            raise ValueError(
                "pipeline_stages > 1 does not support MoE (n_experts > 0): "
                "the load-balancing aux loss cannot be sown through the "
                "pipelined stack"
            )
    return cfg


_STEP_STATS = {  # collection -> how each sown name is reduced over the layers
    "moe_stats": {"assignments_local": jnp.mean, "load_max_over_mean": jnp.max,
                  "overflow": jnp.sum},
    "ssm_stats": {"dt_max": jnp.max, "chunk_decay_min": jnp.min},
    "kda_stats": {"log_decay_min": jnp.min, "beta_max": jnp.max},
    "retention_stats": {"log_gate_min": jnp.min, "denominator_min": jnp.min},
}


def step_metrics(sown: dict) -> dict:
    """What the layers sowed in one step, reduced over the layers. Routed
    layers (`moe_stats` -> `moe.*`): the mean count of assignments to experts
    held here, the fullest held expert against the mean one (the worst
    layer's), and the assignments that did not fit their buffer (0, or the
    Trainer stops). Mamba layers (`ssm_stats` -> `ssm.*`): the largest step
    size and the most negative in-chunk running sum of `dt A` (the worst
    layer's: how far the in-chunk decays underflow). KDA layers (`kda_stats`
    -> `kda.*`): the most negative in-chunk running sum of the log-decay and
    the largest step size, the worst layer's. Power-retention layers
    (`retention_stats` -> `retention.*`): the most negative in-chunk running
    sum of the log-gate and the smallest denominator of the readout, the
    worst layer's."""
    from flax.traverse_util import flatten_dict

    out = {}
    for collection, how in _STEP_STATS.items():
        by_name: dict = {}
        # layer_i/<module>/<name>: (value,)
        for path, sown_here in flatten_dict(sown.get(collection, {})).items():
            by_name.setdefault(path[-1], []).extend(
                jnp.asarray(v, jnp.float32).reshape(()) for v in sown_here
            )
        prefix = collection.removesuffix("_stats")
        out.update(
            (f"{prefix}.{name}", how[name](jnp.stack(vals)))
            for name, vals in by_name.items()
        )
    return out


@register("transformer_lm")
def build_transformer(config: dict) -> ModelBundle:
    cfg = _make_config(config)
    module = Transformer(cfg)
    trainable = (r"lora_[ab]$",) if cfg.lora_rank > 0 else ()
    rules = SCAN_RULES if cfg.scan_layers else TRANSFORMER_RULES
    if cfg.pipeline_stages > 1:
        rules = PIPELINE_RULES + TRANSFORMER_RULES
    if cfg.adapter_slots > 0:
        # slot-stacked adapters gain a leading [slots] axis: replicate it
        # (each gather pulls one rank-r sliver; sharding the slot axis
        # would turn every per-row gather into a collective)
        rules = tuple(
            (pat, (None, *axes)) if "lora_" in pat else (pat, axes)
            for pat, axes in rules
        )
    if cfg.n_experts > 0:
        from .moe import MOE_RULES

        moe_rules = (
            tuple((pat, (None, *axes)) for pat, axes in MOE_RULES)
            if cfg.scan_layers
            else MOE_RULES
        )
        # Expert meshes: keep fsdp OFF the embed/lm_head dims. With an
        # expert axis present, XLA's spmd partitioner cannot reshard the
        # dim-over-fsdp gather output to the batch-sharded activation
        # layout and falls back to involuntary full rematerialization
        # (b/433785288 in its own warning; r4 verdict weakness #2). The
        # table is a small fraction of MoE params — the experts, which
        # dominate, still shard over expert×fsdp. First match wins, so
        # these override the base embed/lm_head rules.
        edge = (
            (r"embed/embedding", (None, ("model",))),
            (r"lm_head/kernel", (None, "model")),
        )
        rules = edge + moe_rules + rules
    stats = tuple(
        name for name, has in (
            ("moe_stats", cfg.n_experts > 0),
            ("ssm_stats", any(spec.mixer == "mamba" for spec in cfg.layers)),
            ("kda_stats", any(spec.mixer == "kda" for spec in cfg.layers)),
            ("retention_stats", any(spec.mixer == "power_retention" for spec in cfg.layers)),
        ) if has
    )
    fused = None
    if cfg.fused_lm_loss:
        from ..ops.losses import fused_linear_masked_lm

        def fused(params, features, batch):  # noqa: F811
            kernel = (
                params["embed"]["embedding"].T
                if cfg.tie_embeddings
                else params["lm_head"]["kernel"]
            )
            return fused_linear_masked_lm(
                features,
                kernel,
                batch["labels"],
                chunk_size=cfg.fused_loss_chunk,
            )

    return ModelBundle(
        name="transformer_lm",
        module=module,
        example_inputs=i32_tokens(cfg.seq_len),
        loss="masked_lm",
        sharding_rules=rules,
        task="lm",
        trainable_patterns=trainable,
        aux_losses=cfg.n_experts > 0 and cfg.moe_aux_weight > 0,
        step_metrics=step_metrics if stats else None,
        step_collections=stats,
        fused_loss=fused,
        # the pipelined stack applies its blocks functionally, in stages: no
        # block boundary that `keep=` could name
        keep_rungs=() if cfg.pipeline_stages > 1 else KEEP_RUNGS,
    )


@register("llama")
def build_llama(config: dict) -> ModelBundle:
    if "preset" not in config and "variant" not in config:
        config["preset"] = "llama3-8b"
    bundle = build_transformer(config)
    return dataclasses.replace(bundle, name="llama")
