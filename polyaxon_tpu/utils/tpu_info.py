"""TPU hardware facts: per-chip peak bf16 matmul FLOPs by device kind
(public spec sheets). The program's one copy: `telemetry.stats.mfu`
divides by it for the trainer's `train.mfu` gauge."""

from __future__ import annotations

from typing import Optional

PEAK_BF16_FLOPS: list[tuple[str, float]] = [
    ("v6", 918e12),  # Trillium
    ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
]


def peak_bf16_flops(device_kind: str) -> Optional[float]:
    """Peak bf16 FLOPs/sec for a jax device_kind string; None if unknown
    (CPU, unrecognized generation) — MFU is then unreportable, not 0."""
    dk = device_kind.lower()
    for key, val in PEAK_BF16_FLOPS:
        if key in dk:
            return val
    return None
