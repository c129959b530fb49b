"""Exact percentile math + the program's count of a train step's work.

The ONE implementation of exact sample quantiles for the repo (the
scenario driver's and the twin's reports read `quantile`; histograms
estimate theirs from bucket counts in `registry.py`).

`required_train_step_flops` is the program's one count of what an
optimizer step requires; the trainer's `train.mfu` gauge divides it by
the step time and the chip's peak (`mfu`). (XLA's cost_analysis would
need a second full compile of the step for a number this gives
analytically.)
"""

from __future__ import annotations

from typing import Optional, Sequence


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """Exact sample quantile with linear interpolation between order
    statistics (numpy's default / type-7), q in [0, 1]. None on empty
    input rather than raising — a tail sample is often empty."""
    if not values:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q must be in [0, 1], got {q}")
    s = sorted(float(v) for v in values)
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    return s[lo] * (1.0 - frac) + s[hi] * frac


def required_train_step_flops(
    frozen: int,
    trainable: int,
    n_layers: int,
    attn_width: int,
    seq_len: int,
    tokens: int,
    causal: bool = True,
) -> float:
    """What one optimizer step on `tokens` tokens REQUIRES, as the
    benchmark counts it (`cellbench/flops.py::train_step_flops`; a test
    pins the two equal): a frozen weight needs its forward product and
    the gradient of its input (4 per weight and token), a trainable one
    its own gradient too (6); attention's QK^T and PV over the causal
    triangle (diagonal included), backward twice the forward.
    `frozen`/`trainable` count weights that enter a product; `attn_width`
    is heads x head size. 6 per weight for all of a LoRA model would
    overstate the requirement by half."""
    pairs = seq_len * (seq_len + 1) / 2 if causal else seq_len * seq_len
    attn_fwd = n_layers * (tokens / seq_len) * 2 * 2 * pairs * attn_width
    return float(4.0 * frozen * tokens + 6.0 * trainable * tokens + 3.0 * attn_fwd)


def mfu(flops_per_sec: float, device_kind: str, n_devices: int = 1) -> Optional[float]:
    """Model FLOPs utilization against the device generation's peak bf16
    throughput; None when the peak is unknown (CPU, unrecognized chip) —
    MFU is then unreportable, not 0."""
    from ..utils.tpu_info import peak_bf16_flops

    peak = peak_bf16_flops(device_kind)
    if not peak or flops_per_sec <= 0:
        return None
    return flops_per_sec / (peak * max(1, n_devices))
