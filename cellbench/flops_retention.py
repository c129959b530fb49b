"""Operations a decoder of degree-2 power-retention layers requires (the
Brumby configuration), from published shapes alone (`flops.py` and its
siblings know no such mixer).

Required work only, as in `flops.py`: forward and backward, never what a
program recomputes (remat, a checkpointed scan) or computes and throws away.
A frozen weight costs 4 operations a token it touches (the gate's projection
among them), an adapter weight 6. The scan is counted in its chunked form
over the EXACT symmetric square, `D = P (P + 1) / 2` features a head
(whatever features a program builds), at the chunk the configuration's model
block names, the products only (decays, gates, norms and the feature map are
elementwise and not counted): per chunk of `C` positions and query head, the
read of the carried value state and normaliser, `phi(Q) [S | z]` (`2 C D (P +
1)`), the update `phi(K)^T [V | 1]` (the same), the causal half of `Q K^T`
and the causal half of the squared scores' product with `[V | 1]`; the
backward twice the forward. A sequence off the chunk counts its last chunk
whole, as a program pads it.
"""

from __future__ import annotations

from cellbench.flops import head_params, layer_matmul_params, lora_params


def _dims(c: dict) -> dict:
    n = int(c["num_hidden_layers"])
    return {
        "d": int(c["hidden_size"]),
        "layers": n,
        "kinds": list(c["layer_types"])[:n],
        "heads": int(c["num_attention_heads"]),
        "p": int(c["head_dim"]),
        "chunk": int(c["model"]["retention_chunk_size"]),
    }


def features(c: dict) -> int:
    """The symmetric square's width for one head: C(P + 1, 2)."""
    p = _dims(c)["p"]
    return p * (p + 1) // 2


def layer_params(c: dict) -> dict:
    """Of one layer: `held` (every parameter, the norms among them) and
    `touched` (the weights one token meets in a product)."""
    m = _dims(c)
    touched = layer_matmul_params(c) + m["d"] * m["heads"]  # + the gate's projection
    return {"held": touched + 2 * m["d"] + 2 * m["p"], "touched": touched}


def held_params(c: dict) -> int:
    """Every frozen parameter on this chip: the layers, the final norm, the
    table and the untied head."""
    m = _dims(c)
    return m["layers"] * layer_params(c)["held"] + m["d"] + 2 * head_params(c)


def touched_params(c: dict) -> int:
    return _dims(c)["layers"] * layer_params(c)["touched"] + head_params(c)


def scan_flops(c: dict, seq: int) -> float:
    """The scan's products of one layer for one sequence, forward."""
    m = _dims(c)
    p, chunk, d = m["p"], m["chunk"], features(c)
    causal = chunk * (chunk + 1) // 2
    per_chunk = (
        2 * 2 * chunk * d * (p + 1)  # phi(Q) [S | z] and phi(K)^T [V | 1]
        + 2 * p * causal  # Q K^T
        + 2 * (p + 1) * causal  # squared scores x [V | 1]
    )
    return -(-seq // chunk) * m["heads"] * per_chunk


def train_step_flops(c: dict, rows: int, seq: int, lora_rank: int, lora_targets) -> dict:
    m = _dims(c)
    tokens = rows * seq
    out = {
        "frozen_matmul": 4.0 * touched_params(c) * tokens,
        "trainable_matmul": 6.0 * lora_params(c, lora_rank, lora_targets) * tokens,
        "scan": 3.0 * rows * scan_flops(c, seq) * m["kinds"].count("power_retention"),
    }
    out["total"] = sum(out.values())
    return out
