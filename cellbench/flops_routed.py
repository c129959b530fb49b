"""Operations and bytes a decoder of mixed window/full attention with routed
experts requires, from published shapes alone (`flops.py` counts one head
count and one MLP width for every layer and cannot count this).

Required work only, as in `flops.py`: forward and backward, not what remat
recomputes. A frozen weight costs 4 operations a token it touches, an adapter
weight 6. A token touches, of a sparse layer's routed experts, `top_k x held /
published` of them **in expectation** (uniform routing over the router's
published width): the program's own count of local assignments
(`train.moe.assignments_local`) stands beside it in PERF.md. `num_experts`
and `vocab_size` are the counts held on this chip; `router_width` the
router's published width.
"""

from __future__ import annotations

from cellbench import flops
from cellbench.flops import head_params  # noqa: F401 - hidden x the rows held


def _dims(c: dict) -> dict:
    n = int(c["num_hidden_layers"])
    kinds = list(c["layer_types"])[:n]
    dense = set(c.get("mlp_only_layers") or ())
    return {
        "d": int(c["hidden_size"]),
        "layers": n,
        "kinds": kinds,
        "heads": [int(h) for h in c["num_attention_heads_per_layer"][:n]],
        "kv": int(c["num_key_value_heads"]),
        "hd": int(c["head_dim"]),
        "f": int(c["intermediate_size"]),
        "fe": int(c["moe_intermediate_size"]),
        "fs": int(c["shared_expert_intermediate_size"]),
        "held": int(c["num_experts"]),
        "router": int(c.get("router_width") or c["num_experts"]),
        "top_k": int(c["num_experts_per_tok"]),
        "v": int(c["vocab_size"]),
        "window": int(c["sliding_window"]),
        "sparse": [i not in dense for i in range(n)],
    }


def attention_shapes(c: dict, layer: int) -> dict:
    """(in, out) of layer `layer`'s attention projections, gate included."""
    m = _dims(c)
    q, kv = m["heads"][layer] * m["hd"], m["kv"] * m["hd"]
    return {
        "q": (m["d"], q), "k": (m["d"], kv), "v": (m["d"], kv), "o": (q, m["d"]),
        "attn_gate": (m["d"], m["heads"][layer]),
    }


def sparse_layers(c: dict) -> int:
    return sum(_dims(c)["sparse"])


def expert_params(c: dict) -> int:
    m = _dims(c)
    return 3 * m["d"] * m["fe"]


def layer_params(c: dict, layer: int) -> dict:
    """Weights of one layer that enter a product: `held` (on this chip) and
    `touched` (by one token, the routed experts in expectation)."""
    m = _dims(c)
    attn = sum(i * o for i, o in attention_shapes(c, layer).values())
    if not m["sparse"][layer]:
        mlp = 3 * m["d"] * m["f"]
        return {"held": attn + mlp, "touched": attn + mlp}
    shared, router = 3 * m["d"] * m["fs"], m["d"] * m["router"]
    fixed = attn + shared + router
    return {
        "held": fixed + m["held"] * expert_params(c),
        "touched": fixed + m["top_k"] * m["held"] / m["router"] * expert_params(c),
    }


def held_params(c: dict) -> int:
    """Every weight held on this chip: the layers' products, the head, the
    embedding table (looked up, not multiplied) and the norms."""
    m = _dims(c)
    layers = sum(layer_params(c, i)["held"] for i in range(m["layers"]))
    norms = m["layers"] * 2 * m["d"] + m["d"]
    return layers + 2 * head_params(c) + norms


def touched_params(c: dict) -> float:
    m = _dims(c)
    return sum(layer_params(c, i)["touched"] for i in range(m["layers"])) + head_params(c)


def lora_params(c: dict, rank: int, targets) -> int:
    m = _dims(c)
    return sum(
        rank * sum(attention_shapes(c, i)[t]) for i in range(m["layers"]) for t in targets
    )


def attended_pairs(seq: int, window: int = 0) -> int:
    """(query, key) pairs of one causal sequence: the lower triangle, or with
    a window sum_i min(i + 1, window)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops(c: dict, layer: int, seq: int) -> float:
    """QK^T and PV of layer `layer` for one sequence, forward."""
    m = _dims(c)
    window = m["window"] if m["kinds"][layer] == "sliding_attention" else 0
    return 2 * 2 * attended_pairs(seq, window) * m["heads"][layer] * m["hd"]


def local_assignments(c: dict, tokens: int) -> float:
    """Expected (token, held expert) pairs of one sparse layer."""
    m = _dims(c)
    return tokens * m["top_k"] * m["held"] / m["router"]


def train_step_flops(c: dict, rows: int, seq: int, lora_rank: int, lora_targets) -> dict:
    m = _dims(c)
    tokens = rows * seq
    attn_fwd = sum(rows * attention_flops(c, i, seq) for i in range(m["layers"]))
    out = {
        "frozen_matmul": 4.0 * touched_params(c) * tokens,
        "trainable_matmul": 6.0 * lora_params(c, lora_rank, lora_targets) * tokens,
        "attention": 3.0 * attn_fwd,  # backward has four products to forward's two
    }
    out["total"] = sum(out.values())
    return out


def flash_window_call(c: dict, rows: int, seq: int, act_bytes: int = 2) -> dict:
    """One call of the windowed attention kernels over rows x seq (a sliding
    layer), forward and backward: required operations over the window's
    pairs, and bytes that must cross HBM once (q, k, v, o in; gradients out;
    the row statistics in float32)."""
    m = _dims(c)
    layer = m["kinds"].index("sliding_attention")
    # the bytes are a full layer's of the sliding layers' heads; the window
    # takes operations away, not operands
    call = flops.flash_attention_call(
        {**c, "num_attention_heads": m["heads"][layer]}, rows, seq, act_bytes
    )
    fwd_flops = rows * attention_flops(c, layer, seq)
    return {
        "fwd": {"flops": fwd_flops, "bytes": call["fwd"]["bytes"]},
        "bwd": {"flops": 2 * fwd_flops, "bytes": call["bwd"]["bytes"]},
    }


def grouped_products_layer_step(c: dict, rows: int, seq: int, passes: int = 3,
                                weight_bytes: int = 2, act_bytes: int = 2) -> dict:
    """The grouped expert products of ONE sparse layer in one step: required
    operations (forward and the gradient of the input: 4 x 3 x D x Fe a local
    assignment, in expectation), and bytes: the held experts' kernels once a
    pass (forward, remat's forward, backward) plus each product's rows in and
    out."""
    m = _dims(c)
    n = local_assignments(c, rows * seq)
    per_pass = (
        m["held"] * expert_params(c) * weight_bytes
        + n * 3 * (m["d"] + m["fe"]) * act_bytes
    )
    return {"flops": 4.0 * expert_params(c) * n, "bytes": passes * per_pass}
