"""Operations and bytes one call of the power-retention scan requires (one
layer, forward and backward), from published shapes alone, for
`layer_metrics/retention_scan_roofline.train.py`.

    forward    operations `flops_retention.scan_flops` x rows: the products
               over the EXACT symmetric square, whatever features a program
               builds; q, k, v (the activations' type) and the log-gate
               (float32) in, y out
    backward   twice the forward's operations; q, k, v, the log-gate and dy
               in, their four gradients out

Required work only: what a checkpoint or a backward computes again (a second
forward, the states a run starts from built again) is not counted, and each
operand crosses HBM once.
"""

from __future__ import annotations

from cellbench.flops_retention import scan_flops


def retention_scan_call(c: dict, rows: int, seq: int, act_bytes: int = 2) -> dict:
    """{"fwd": {"flops", "bytes"}, "bwd": {...}} of one layer's scan over
    rows x seq."""
    tokens = rows * seq
    p = int(c["head_dim"])
    wide = tokens * int(c["num_attention_heads"]) * p * act_bytes  # q, y, dy or dq
    narrow = tokens * int(c["num_key_value_heads"]) * p * act_bytes  # k, v or theirs
    gate = tokens * int(c["num_attention_heads"]) * 4
    fwd = rows * scan_flops(c, seq)
    return {
        "fwd": {"flops": fwd, "bytes": 2 * wide + 2 * narrow + gate},
        "bwd": {"flops": 2 * fwd, "bytes": 3 * wide + 4 * narrow + 2 * gate},
    }
