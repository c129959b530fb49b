"""Bytes the two fused elementwise chains of a Mamba-2 mixer must move, from
published shapes alone (`granitemoehybrid` keys): `conv_silu` over the
convolution's width `H*P + 2*G*N` and `gated_rmsnorm` over the inner width
`H*P`, each large operand across HBM once in the activations' type.

    forward    conv: x in, silu(conv(x)) out            2 x [tokens, conv width]
               gate+norm: y, z in, the normed rows out  3 x [tokens, inner]
    backward   conv: x, d out in, dx out                3 x [tokens, conv width]
               gate+norm: y, z, d out in, dy, dz out    5 x [tokens, inner]

Required work only: what a checkpoint reads again, the rows' float32
`rsqrt` (4 bytes a token), the taps, the bias and the scale are not counted,
and no operation is: every one of them is elementwise, and the chains are
bound by the bytes (`flops_ssm.py` counts the taps' products for the step).
"""

from __future__ import annotations


def widths(c: dict) -> dict:
    inner = int(c["mamba_n_heads"]) * int(c["mamba_d_head"])
    return {"inner": inner,
            "conv": inner + 2 * int(c["mamba_n_groups"]) * int(c["mamba_d_state"])}


def fused_chains_call(c: dict, rows: int, seq: int, act_bytes: int = 2) -> dict:
    """One Mamba layer's two chains over rows x seq: bytes of the forward and
    of the backward."""
    w, tokens = widths(c), rows * seq
    return {
        "fwd": {"bytes": tokens * act_bytes * (2 * w["conv"] + 3 * w["inner"])},
        "bwd": {"bytes": tokens * act_bytes * (3 * w["conv"] + 5 * w["inner"])},
    }
