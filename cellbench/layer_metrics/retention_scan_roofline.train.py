"""The power-retention scan's kernels' share of their roofline in a training
step: the Pallas kernels of `polyaxon_tpu/ops/power_retention_fused.py` carry
their names into the trace (`%retention_scan_fwd.N`, `%retention_scan_bwd.N`;
a further kernel of a split backward would start with `retention_scan_` too).
As `kda_scan_roofline.train`:

    kernel time   = sum of the `retention_scan_*` kernels' event durations,
                    whole programs inside the window only
    required work = (number of `retention_scan_bwd` calls: one a retention
                    layer and step) x (one forward + one backward of the
                    scan, `cellbench/retention_scan_call.py`: the products
                    over the exact symmetric square, and q, k, v, the
                    log-gate, y and the gradients across HBM once)
    roofline time = per call, forward and backward each at the larger of
                    operations / peak FLOP/s and bytes / peak HBM bytes/s
                    (the operations bind at the cell's shape: 28.8 + 57.5 ms)
    value         = 100 x roofline time / kernel time

A second forward under a checkpoint adds time and no work: on rung `block`
the value cannot pass forward + backward over two forwards + backward (75
for the Brumby cell), and a backward that builds the run's states again
before it walks them back adds more. None where the configuration has no
`power_retention` layer or the trace holds no such kernel (a parent without
them, a shape on the `xla` path): never 0.
"""

import re

from cellbench import trace_kernels
from cellbench.retention_scan_call import retention_scan_call

KERNEL = re.compile(r"^%?[\w.\-]*?retention_scan_(\w+?)(?:\.\d+)? = .* custom-call\(")


def read(obs):
    cfg = obs.get("config") or {}
    if "power_retention" not in (cfg.get("layer_types") or ()):
        return None
    found = trace_kernels.window_ops(obs)
    if found is None:
        return None
    seconds = trace_kernels.kernel_seconds(found[0], KERNEL)
    kernel_s = sum(s for _, s in seconds.values())
    calls = seconds.get("bwd", [0])[0]
    if not calls or kernel_s <= 0:
        return None
    peaks = obs["peaks"]
    work = retention_scan_call(cfg, obs["rows"], obs["seq_len"])
    per_call = sum(
        max(w["flops"] / peaks["flops_per_s"], w["bytes"] / peaks["hbm_bytes_per_s"])
        for w in (work["fwd"], work["bwd"])
    )
    return 100.0 * calls * per_call / kernel_s
