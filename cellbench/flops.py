"""Operations and bytes a cell's work requires, from published shapes alone.

Required work only: what the forward and backward passes need, not what a
program chooses to recompute (remat, the flash backward's second QK^T) and
not what it computes and throws away (weight gradients of frozen kernels).
One multiply-add is two operations. An embedding look-up is not a product.
All functions take the configuration's published keys (`hidden_size`, ...).
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    heads = int(c["num_attention_heads"])
    d = int(c["hidden_size"])
    return {
        "d": d,
        "layers": int(c["num_hidden_layers"]),
        "heads": heads,
        "kv": int(c["num_key_value_heads"]),
        "hd": int(c.get("head_dim") or d // heads),
        "f": int(c["intermediate_size"]),
        "v": int(c["vocab_size"]),
    }


def proj_shapes(c: dict) -> dict:
    """(in, out) of each linear layer of one block."""
    m = _dims(c)
    q, kv = m["heads"] * m["hd"], m["kv"] * m["hd"]
    return {
        "q": (m["d"], q), "k": (m["d"], kv), "v": (m["d"], kv), "o": (q, m["d"]),
        "gate": (m["d"], m["f"]), "up": (m["d"], m["f"]), "down": (m["f"], m["d"]),
    }


def layer_matmul_params(c: dict) -> int:
    return sum(i * o for i, o in proj_shapes(c).values())


def head_params(c: dict) -> int:
    m = _dims(c)
    return m["d"] * m["v"]


def matmul_params(c: dict) -> int:
    """Weights that enter a product: every block's linear layers and the
    head. The embedding table is looked up, not multiplied."""
    return _dims(c)["layers"] * layer_matmul_params(c) + head_params(c)


def total_params(c: dict) -> int:
    m = _dims(c)
    norms = m["layers"] * 2 * m["d"] + m["d"]
    return matmul_params(c) + m["v"] * m["d"] + norms


def lora_params(c: dict, rank: int, targets) -> int:
    shapes = proj_shapes(c)
    per_layer = sum(rank * (shapes[t][0] + shapes[t][1]) for t in targets)
    return _dims(c)["layers"] * per_layer


def attention_flops(c: dict, q_len: int, kv_len: int, causal: bool) -> float:
    """QK^T and PV of one layer for one sequence, forward. Causal with
    q_len == kv_len counts the lower triangle, diagonal included."""
    m = _dims(c)
    pairs = q_len * (q_len + 1) / 2 if causal else q_len * kv_len
    return 2 * 2 * pairs * m["heads"] * m["hd"]


def train_step_flops(c: dict, rows: int, seq: int, lora_rank: int = 0,
                     lora_targets=()) -> dict:
    """One optimizer step on rows x seq tokens. Frozen kernels: forward and
    the gradient of their input (4 per weight and token). Trainable ones add
    their own gradient (6). With no LoRA every kernel is trainable."""
    m = _dims(c)
    tokens = rows * seq
    n = matmul_params(c)
    if lora_rank:
        frozen, trainable = n, lora_params(c, lora_rank, lora_targets)
    else:
        frozen, trainable = 0, n
    attn_fwd = m["layers"] * rows * attention_flops(c, seq, seq, True)
    out = {
        "frozen_matmul": 4.0 * frozen * tokens,
        "trainable_matmul": 6.0 * trainable * tokens,
        "attention": 3.0 * attn_fwd,  # backward has four products to forward's two
    }
    out["total"] = sum(out.values())
    return out


def flash_attention_call(c: dict, rows: int, seq: int, act_bytes: int = 2) -> dict:
    """One call of a fused causal attention kernel over rows x seq, forward
    and backward: required operations, and bytes that must cross HBM once
    (q, k, v, o in; gradients out; the row statistics in float32)."""
    m = _dims(c)
    fwd_flops = rows * attention_flops(c, seq, seq, True)
    q_b = rows * seq * m["heads"] * m["hd"] * act_bytes
    kv_b = rows * seq * m["kv"] * m["hd"] * act_bytes
    stat_b = rows * seq * m["heads"] * 4
    return {
        "fwd": {"flops": fwd_flops, "bytes": 2 * q_b + 2 * kv_b + stat_b},
        "bwd": {"flops": 2 * fwd_flops,
                "bytes": 4 * q_b + 4 * kv_b + 2 * stat_b},
    }


def serve_token_flops(c: dict, position: int, sampled: bool) -> float:
    """One token at 0-based `position` through the decoder: every block's
    linear layers, attention against position + 1 keys, and the head where a
    token is sampled from it."""
    m = _dims(c)
    f = 2.0 * m["layers"] * layer_matmul_params(c)
    f += m["layers"] * 2 * 2 * (position + 1) * m["heads"] * m["hd"]
    if sampled:
        f += 2.0 * head_params(c)
    return f


def serve_span_flops(c: dict, start: int, stop: int, sampled: int) -> float:
    """Tokens at positions [start, stop) of one sequence, `sampled` of them
    through the head."""
    m = _dims(c)
    n = stop - start
    f = 2.0 * m["layers"] * layer_matmul_params(c) * n
    keys = (stop * (stop + 1) - start * (start + 1)) / 2
    f += m["layers"] * 2 * 2 * keys * m["heads"] * m["hd"]
    return f + 2.0 * head_params(c) * sampled


def kv_bytes_per_token(c: dict, kv_bytes: int = 2) -> int:
    m = _dims(c)
    return 2 * m["layers"] * m["kv"] * m["hd"] * kv_bytes


def decode_step_bytes(c: dict, live_kv_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """A decode-only step must read every matmul weight once and every live
    key and value once."""
    return (
        matmul_params(c) * weight_bytes
        + live_kv_tokens * kv_bytes_per_token(c, kv_bytes)
    )
