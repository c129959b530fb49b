"""Operations and bytes a hybrid decoder of Mamba-2 and attention layers with
routed experts requires, from published shapes alone (`granitemoehybrid`
keys; `flops.py` and `flops_routed.py` know no state-space layer).

Required work only, as in `flops.py`: forward and backward, never what a
program recomputes (remat, a checkpointed scan) or computes and throws away.
A frozen weight costs 4 operations a token it touches (the depthwise
convolution's taps among them), an adapter weight 6. A token touches, of a
layer's routed experts, `top_k x held / published` of them in expectation.
Attention is the causal triangle, without rotation. The state-space scan is
counted in its dual form at the published chunk, the products only (decays,
sums and gates are elementwise and not counted): per chunk the causal half of
`C . B^T` (once a group) and of its product with `x` (once a head), the
chunk's state `x^T B` and the carried state's `H C`; the backward twice the
forward. `num_local_experts` and `vocab_size` are the counts held on this
chip; `router_width` the router's published width.
"""

from __future__ import annotations

from cellbench.flops import head_params  # hidden x the rows held


def _dims(c: dict) -> dict:
    n = int(c["num_hidden_layers"])
    heads = int(c["num_attention_heads"])
    d = int(c["hidden_size"])
    return {
        "d": d,
        "layers": n,
        "kinds": list(c["layer_types"])[:n],
        "heads": heads,
        "kv": int(c["num_key_value_heads"]),
        "hd": int(c.get("head_dim") or d // heads),
        "mh": int(c["mamba_n_heads"]),
        "mp": int(c["mamba_d_head"]),
        "mn": int(c["mamba_d_state"]),
        "mg": int(c["mamba_n_groups"]),
        "mk": int(c["mamba_d_conv"]),
        "chunk": int(c["mamba_chunk_size"]),
        "fe": int(c["intermediate_size"]),
        "fs": int(c["shared_intermediate_size"]),
        "held": int(c["num_local_experts"]),
        "router": int(c.get("router_width") or c["num_local_experts"]),
        "top_k": int(c["num_experts_per_tok"]),
        "v": int(c["vocab_size"]),
    }


def mixer_shapes(c: dict, layer: int) -> dict:
    """(in, out) of layer `layer`'s mixer's projections, under the names the
    cell's `reference.lora.targets` use."""
    m = _dims(c)
    if m["kinds"][layer] == "mamba":
        inner, bc = m["mh"] * m["mp"], m["mg"] * m["mn"]
        return {"in_proj": (m["d"], 2 * inner + 2 * bc + m["mh"]), "out_proj": (inner, m["d"])}
    q, kv = m["heads"] * m["hd"], m["kv"] * m["hd"]
    return {"q": (m["d"], q), "k": (m["d"], kv), "v": (m["d"], kv), "o": (q, m["d"])}


def mamba_small_params(c: dict) -> dict:
    """What a Mamba layer holds beside its two projections: the conv's taps
    and bias, `dt_bias`, `A_log`, `D` and the gated norm's scale."""
    m = _dims(c)
    conv_dim = m["mh"] * m["mp"] + 2 * m["mg"] * m["mn"]
    return {
        "conv_taps": conv_dim * m["mk"], "conv_bias": conv_dim,
        "per_head": 3 * m["mh"], "gated_norm": m["mh"] * m["mp"],
    }


def expert_params(c: dict) -> int:
    m = _dims(c)
    return 3 * m["d"] * m["fe"]


def layer_params(c: dict, layer: int) -> dict:
    """Of one layer: `held` (every parameter on this chip, norms included),
    `touched` (weights one token meets in a product, the routed experts in
    expectation, the conv's taps among them)."""
    m = _dims(c)
    mixer = sum(i * o for i, o in mixer_shapes(c, layer).values())
    small = mamba_small_params(c) if m["kinds"][layer] == "mamba" else {}
    shared, router = 3 * m["d"] * m["fs"], m["d"] * m["router"]
    fixed = mixer + shared + router
    return {
        "held": fixed + sum(small.values()) + 2 * m["d"] + m["held"] * expert_params(c),
        "touched": fixed + small.get("conv_taps", 0)
        + m["top_k"] * m["held"] / m["router"] * expert_params(c),
    }


def held_params(c: dict) -> int:
    """Every frozen parameter on this chip: the layers, the final norm and
    the table (tied: the head is the table, held once)."""
    m = _dims(c)
    return sum(layer_params(c, i)["held"] for i in range(m["layers"])) + m["d"] + head_params(c)


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


def attention_flops(c: dict, seq: int) -> float:
    """QK^T and PV of one attention layer for one sequence, forward: the
    causal triangle."""
    m = _dims(c)
    return 2 * 2 * (seq * (seq + 1) // 2) * m["heads"] * m["hd"]


def scan_flops(c: dict, seq: int) -> float:
    """The scan's products of one Mamba layer for one sequence, forward, in
    the dual form at the published chunk (closed form in the module's
    docstring)."""
    m = _dims(c)
    q = m["chunk"]
    half = (seq // q) * (q * (q + 1) // 2)  # (i, j <= i) pairs of all chunks
    in_chunk = half * (2 * m["mn"] * m["mg"] + 2 * m["mh"] * m["mp"])
    states = 2 * (2 * seq * m["mh"] * m["mp"] * m["mn"])  # x^T B and H C
    return in_chunk + states


def ssd_scan_call(c: dict, rows: int, seq: int, act_bytes: int = 2) -> dict:
    """One call of the scan over rows x seq (one Mamba layer), forward and
    backward: required operations, and the bytes that must cross HBM once
    (x, B, C in the activations' type and dt in float32 in, y out; backward
    the same again with dy in and the four gradients out). For the roofline
    reader of the kernel to come."""
    m = _dims(c)
    tokens = rows * seq
    xy = tokens * m["mh"] * m["mp"] * act_bytes
    bc = 2 * tokens * m["mg"] * m["mn"] * act_bytes
    dt = tokens * m["mh"] * 4
    fwd = rows * scan_flops(c, seq)
    return {
        "fwd": {"flops": fwd, "bytes": 2 * xy + bc + dt},
        "bwd": {"flops": 2 * fwd, "bytes": 3 * xy + 2 * bc + 2 * dt},
    }


def local_assignments(c: dict, tokens: int) -> float:
    """Expected (token, held expert) pairs of one layer."""
    m = _dims(c)
    return tokens * m["top_k"] * m["held"] / m["router"]


def train_step_flops(c: dict, rows: int, seq: int, lora_rank: int, lora_targets) -> dict:
    m = _dims(c)
    tokens = rows * seq
    n_mamba = m["kinds"].count("mamba")
    out = {
        "frozen_matmul": 4.0 * touched_params(c) * tokens,
        "trainable_matmul": 6.0 * lora_params(c, lora_rank, lora_targets) * tokens,
        # backward has four products to forward's two
        "attention": 3.0 * rows * attention_flops(c, seq) * (m["layers"] - n_mamba),
        "scan": 3.0 * rows * scan_flops(c, seq) * n_mamba,
    }
    out["total"] = sum(out.values())
    return out
