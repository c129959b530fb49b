"""Operations and bytes a hybrid decoder of delta-rule linear-attention (KDA)
and latent-attention (MLA) layers with sigmoid-routed experts requires, from
published shapes alone (`flops.py`, `flops_routed.py` and `flops_ssm.py` know
neither mixer).

Required work only, as in `flops.py`: forward and backward, never what a
program recomputes (remat, a checkpointed scan) or computes and throws away.
A frozen weight costs 4 operations a token it touches (the three depthwise
convolutions' taps among them), an adapter weight 6. A token touches, of a
layer's routed experts, `top_k x held / published` of them in expectation.
The MLA layer is the causal triangle at `qk_nope_head_dim + qk_rope_head_dim`
for the scores and `v_head_dim` for the values, whatever tiles a kernel
holds. The delta rule is counted in its chunked form at `KDA_CHUNK` = 64, the
products only (decays, gates, norms are elementwise and not counted): per
chunk and head the strict causal half of `K K^T`, the causal half of
`Q K^T`, the forward substitution of `(I + A) [W | U] = [K | V]` (the
cheapest way to the triangular solve: no inverse is required), the causal
half of the scores' product with the pseudo-values, and the three products
with the carried state (`W S`, `Q S`, `K^T U'`); the backward twice the
forward. `num_experts` and `vocab_size` are the counts held on this chip;
`router_width` the router's published width; `layer_types` names each
layer's mixer.
"""

from __future__ import annotations

from cellbench.flops import head_params  # hidden x the rows held

KDA_CHUNK = 64


def _dims(c: dict) -> dict:
    n = int(c["num_hidden_layers"])
    return {
        "d": int(c["hidden_size"]),
        "layers": n,
        "kinds": list(c["layer_types"])[:n],
        "dense": int(c["first_k_dense_replace"]),
        "heads": int(c["num_attention_heads"]),
        "p": int(c["head_dim"]),
        "taps": int(c["short_conv_kernel_size"]),
        "latent": int(c["kv_lora_rank"]),
        "nope": int(c["qk_nope_head_dim"]),
        "rope": int(c["qk_rope_head_dim"]),
        "val": int(c["v_head_dim"]),
        "f": int(c["intermediate_size"]),
        "fe": int(c["moe_intermediate_size"]),
        "fs": int(c["moe_shared_expert_intermediate_size"]),
        "held": int(c["num_experts"]),
        "router": int(c.get("router_width") or c["num_experts"]),
        "top_k": int(c["num_experts_per_tok"]),
        "v": int(c["vocab_size"]),
    }


def mixer_shapes(c: dict, layer: int) -> dict:
    """(in, out) of layer `layer`'s mixer's projections a LoRA may target,
    under the names the cell's `reference.lora.targets` use."""
    m = _dims(c)
    inner = m["heads"] * m["p"]
    if m["kinds"][layer] == "kda":
        return {"q": (m["d"], inner), "k": (m["d"], inner), "v": (m["d"], inner),
                "o": (inner, m["d"])}
    return {
        "q": (m["d"], m["heads"] * (m["nope"] + m["rope"])),
        "kv_a": (m["d"], m["latent"] + m["rope"]),
        "kv_b": (m["latent"], m["heads"] * (m["nope"] + m["val"])),
        "o": (m["heads"] * m["val"], m["d"]),
    }


def mixer_other_params(c: dict, layer: int) -> dict:
    """What a mixer holds beside those: `products` enter a product with every
    token (the gates' projections, the step size's, the convolutions' taps),
    `small` do not (norm scales, `A_log`, `dt_bias`)."""
    m = _dims(c)
    inner = m["heads"] * m["p"]
    if m["kinds"][layer] == "kda":
        return {
            "products": 2 * m["d"] * inner + m["d"] * m["heads"] + 3 * m["taps"] * inner,
            "small": m["heads"] + inner + m["p"],
        }
    return {
        "products": m["d"] * m["heads"],  # the per-head output gate
        "small": m["latent"] + 2 * (m["nope"] + m["rope"]),
    }


def expert_params(c: dict) -> int:
    m = _dims(c)
    return 3 * m["d"] * m["fe"]


def layer_params(c: dict, layer: int) -> dict:
    """Of one layer: `held` (every parameter on this chip, norms and the
    selection bias included), `touched` (weights one token meets in a
    product, the routed experts in expectation)."""
    m = _dims(c)
    other = mixer_other_params(c, layer)
    mixer = sum(i * o for i, o in mixer_shapes(c, layer).values()) + other["products"]
    if layer < m["dense"]:
        mlp_held = mlp_touched = 3 * m["d"] * m["f"]
    else:
        fixed = 3 * m["d"] * m["fs"] + m["d"] * m["router"]
        mlp_held = fixed + m["router"] + m["held"] * expert_params(c)
        mlp_touched = fixed + m["top_k"] * m["held"] / m["router"] * expert_params(c)
    return {
        "held": mixer + other["small"] + 2 * m["d"] + mlp_held,
        "touched": mixer + mlp_touched,
    }


def held_params(c: dict) -> int:
    """Every frozen parameter on this chip: the layers, the final norm, the
    table and the untied head."""
    m = _dims(c)
    return (
        sum(layer_params(c, i)["held"] for i in range(m["layers"]))
        + m["d"] + 2 * head_params(c)
    )


def touched_params(c: dict) -> float:
    m = _dims(c)
    return sum(layer_params(c, i)["touched"] for i in range(m["layers"])) + head_params(c)


def lora_params(c: dict, rank: int, targets) -> int:
    """Adapter weights: of each layer, the targets its own mixer has."""
    m = _dims(c)
    return sum(
        rank * sum(shape)
        for i in range(m["layers"])
        for t, shape in mixer_shapes(c, i).items() if t in targets
    )


def mla_attention_flops(c: dict, seq: int) -> float:
    """QK^T and PV of one MLA layer for one sequence, forward: the causal
    triangle, scores at nope + rope, values at v_head_dim."""
    m = _dims(c)
    return 2 * (seq * (seq + 1) // 2) * m["heads"] * (m["nope"] + m["rope"] + m["val"])


def mla_attention_call(c: dict, rows: int, seq: int, act_bytes: int = 2) -> dict:
    """One call of a fused causal attention kernel over rows x seq at the
    published widths, forward and backward: required operations (backward:
    four products to forward's two), and bytes that must cross HBM once (q,
    k at the score width, v, o at the value width in; gradients out; the row
    statistics in float32)."""
    m = _dims(c)
    fwd = rows * mla_attention_flops(c, seq)
    tokens = rows * seq * m["heads"]
    qk_b = tokens * (m["nope"] + m["rope"]) * act_bytes
    vo_b = tokens * m["val"] * act_bytes
    stat_b = tokens * 4
    return {
        "fwd": {"flops": fwd, "bytes": 2 * qk_b + 2 * vo_b + stat_b},
        "bwd": {"flops": 2 * fwd, "bytes": 4 * qk_b + 4 * vo_b + 2 * stat_b},
    }


def kda_scan_flops(c: dict, seq: int, chunk: int = KDA_CHUNK) -> float:
    """The delta rule's products of one KDA layer for one sequence, forward,
    in the chunked form (closed form in the module's docstring)."""
    m = _dims(c)
    k = v = m["p"]
    strict, causal = chunk * (chunk - 1) // 2, chunk * (chunk + 1) // 2
    per_chunk = (
        2 * k * strict  # K K^T
        + 2 * k * causal  # Q K^T
        + 2 * (k + v) * strict  # (I + A) [W | U] = [K | V] by substitution
        + 2 * v * causal  # scores x pseudo-values
        + 3 * 2 * chunk * k * v  # W S, Q S, K^T U'
    )
    return (seq // chunk) * m["heads"] * per_chunk


def kda_scan_call(c: dict, rows: int, seq: int, act_bytes: int = 2) -> dict:
    """One call of the scan over rows x seq (one KDA layer), forward and
    backward: required operations, and the bytes that must cross HBM once
    (q, k, v in the activations' type, the log-decay and the step size in
    float32 in, o out; backward the same again with do in and the five
    gradients out). For the roofline reader of the kernel to come."""
    m = _dims(c)
    tokens = rows * seq * m["heads"]
    wide = tokens * m["p"] * act_bytes  # one of q, k, v, o
    gate = tokens * m["p"] * 4
    beta = tokens * 4
    fwd = rows * kda_scan_flops(c, seq)
    return {
        "fwd": {"flops": fwd, "bytes": 4 * wide + gate + beta},
        "bwd": {"flops": 2 * fwd, "bytes": 8 * wide + 2 * gate + 2 * beta},
    }


def local_assignments(c: dict, tokens: int) -> float:
    """Expected (token, held expert) pairs of one routed layer."""
    m = _dims(c)
    return tokens * m["top_k"] * m["held"] / m["router"]


def train_step_flops(c: dict, rows: int, seq: int, lora_rank: int, lora_targets) -> dict:
    m = _dims(c)
    tokens = rows * seq
    n_kda = m["kinds"].count("kda")
    out = {
        "frozen_matmul": 4.0 * touched_params(c) * tokens,
        "trainable_matmul": 6.0 * lora_params(c, lora_rank, lora_targets) * tokens,
        # backward has four products to forward's two
        "attention": 3.0 * rows * mla_attention_flops(c, seq) * (m["layers"] - n_kda),
        "scan": 3.0 * rows * kda_scan_flops(c, seq) * n_kda,
    }
    out["total"] = sum(out.values())
    return out
