"""The windowed flash-attention kernels' share of their roofline in a
training step: as `flash_attn_roofline.train`, on the events of
`flash_window_fwd`, `_dq`, `_dkv` (the names `ops/flash_attention.py` gives
its three `pallas_call`s when a layer has a window).

    kernel time   = sum of the three kernels' event durations, whole programs
                    inside the window only
    required work = (number of dq calls) x (forward + backward of one call,
                    `flops_routed.flash_window_call`: operations over the
                    window's pairs and the sliding layers' heads; q, k, v, o,
                    gradients and row statistics across HBM once)
    roofline time = per call, forward and backward each at the larger of
                    operations / peak FLOP/s and bytes / peak HBM bytes/s
    value         = 100 x roofline time / kernel time

None where the trace holds no such kernel (a program without a window, a
parent without these names): never 0.
"""

import re

from cellbench import flops_routed, trace_kernels

KERNEL = re.compile(r"^%?[\w.\-]*?flash_window_(fwd|dq|dkv)[\w\-]*?(?:\.\d+)? = .* custom-call\(")


def read(obs):
    found = trace_kernels.window_ops(obs)
    cfg = obs.get("config") or {}
    if found is None or "sliding_attention" not in cfg.get("layer_types", ()):
        return None
    kernels = trace_kernels.kernel_seconds(found[0], KERNEL)
    kernel_s = sum(s for _, s in kernels.values())
    calls = kernels.get("dq", [0])[0]
    if not calls or kernel_s <= 0:
        return None
    peaks = obs["peaks"]
    work = flops_routed.flash_window_call(cfg, obs["rows"], obs["seq_len"])
    per_call = sum(
        max(w["flops"] / peaks["flops_per_s"], w["bytes"] / peaks["hbm_bytes_per_s"])
        for w in (work["fwd"], work["bwd"])
    )
    return 100.0 * calls * per_call / kernel_s
