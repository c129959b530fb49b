"""The fused Mamba kernels' share of their roofline in a training step: the
four Pallas kernels of `polyaxon_tpu/ops/mamba_fused.py` carry their names
into the trace (`%mamba_conv_silu_fwd.N`, `mamba_conv_silu_bwd`,
`mamba_gate_norm_fwd`, `mamba_gate_norm_bwd`). As `flash_window_roofline.train`:

    kernel time   = sum of the four kernels' event durations, whole programs
                    inside the window only
    required work = (number of `mamba_gate_norm_bwd` calls: one a Mamba layer
                    and step) x (one forward + one backward of both chains,
                    `mamba_fused_bytes.fused_chains_call`: each operand
                    across HBM once; elementwise, so bytes bound it)
    roofline time = required bytes / peak HBM bytes/s
    value         = 100 x roofline time / kernel time

A second forward under a checkpoint adds time and no work: on rung `block`
the value cannot pass forward + backward over two forwards + backward of the
bytes (about 72 for the Granite cell). None where the trace holds no such
kernel (a configuration without a Mamba layer, a shape the kernels refuse, a
parent without these names): never 0.
"""

import re

from cellbench import mamba_fused_bytes, trace_kernels

KERNEL = re.compile(
    r"^%?[\w.\-]*?mamba_(conv_silu_fwd|conv_silu_bwd|gate_norm_fwd|gate_norm_bwd)"
    r"[\w\-]*?(?:\.\d+)? = .* custom-call\("
)


def read(obs):
    found = trace_kernels.window_ops(obs)
    cfg = obs.get("config") or {}
    if found is None or "mamba" not in (cfg.get("layer_types") or ()):
        return None
    kernels = trace_kernels.kernel_seconds(found[0], KERNEL)
    kernel_s = sum(s for _, s in kernels.values())
    calls = kernels.get("gate_norm_bwd", [0])[0]
    if not calls or kernel_s <= 0:
        return None
    work = mamba_fused_bytes.fused_chains_call(cfg, obs["rows"], obs["seq_len"])
    per_call = (work["fwd"]["bytes"] + work["bwd"]["bytes"]) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * calls * per_call / kernel_s
