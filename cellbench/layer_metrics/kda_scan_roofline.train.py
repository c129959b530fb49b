"""The delta-rule scan's kernels' share of their roofline in a training step:
the Pallas kernels of `polyaxon_tpu/ops/kda_fused.py` carry their names into
the trace (`%kda_scan_fwd.N`, `%kda_scan_bwd.N`; a further kernel of a split
backward would start with `kda_scan_` too). As `mamba_fused_roofline.train`:

    kernel time   = sum of the `kda_scan_*` kernels' event durations, whole
                    programs inside the window only
    required work = (number of `kda_scan_bwd` calls: one a KDA layer and
                    step) x (one forward + one backward of the scan,
                    `flops_kda.kda_scan_call(config, rows, seq)`: the chunked
                    form's products, and q, k, v, g, beta, o and the
                    gradients across HBM once)
    roofline time = per call, forward and backward each at the larger of
                    operations / peak FLOP/s and bytes / peak HBM bytes/s
                    (the bytes bind at the cell's shape: 0.99 + 1.97 ms)
    value         = 100 x roofline time / kernel time

A second forward under a checkpoint adds time and no work: on rung `block`
the value cannot pass forward + backward over two forwards + backward (about
75 for the Ling cell). None where the configuration has no `kda` layer or
the trace holds no such kernel (a parent without them, a shape on the `xla`
path): never 0.
"""

import re

from cellbench import flops_kda, trace_kernels

KERNEL = re.compile(r"^%?[\w.\-]*?kda_scan_(\w+?)(?:\.\d+)? = .* custom-call\(")


def read(obs):
    cfg = obs.get("config") or {}
    if "kda" not in (cfg.get("layer_types") or ()):
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
    work = flops_kda.kda_scan_call(cfg, obs["rows"], obs["seq_len"])
    per_call = sum(
        max(w["flops"] / peaks["flops_per_s"], w["bytes"] / peaks["hbm_bytes_per_s"])
        for w in (work["fwd"], work["bwd"])
    )
    return 100.0 * calls * per_call / kernel_s
