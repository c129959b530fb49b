"""The flash-attention kernels' share of their roofline in a training step of
a model whose attention is latent (MLA): scores wider than values.

The three Pallas kernels of `polyaxon_tpu/ops/flash_attention.py` carry their
names into the trace (`%flash_attention_fwd.N`, `_dq`, `_dkv`). Read from
the first chip's operations, over the whole programs inside the window:

    kernel time   = sum of the three kernels' event durations
    required work = (number of dq calls) x (forward + backward of one call,
                    `flops_kda.mla_attention_call(config, rows, seq)`: the
                    causal triangle at nope + rope for the scores and
                    v_head_dim for the values, whatever a tile holds); one dq
                    call per MLA layer and step, so a checkpoint's second
                    forward adds time and no work
    roofline time = per call, forward and backward each at the larger of
                    operations / peak FLOP/s and bytes / peak HBM bytes/s
    value         = 100 x roofline time / kernel time

`flash_attn_roofline.train` counts one head width (`flops.flash_attention_call`)
and would be wrong here; this reader is its twin for two widths.

None where the configuration has no `mla` layer, or the trace holds no such
kernel: never 0."""

import re

from cellbench import flops_kda, trace_kernels

KERNEL = re.compile(
    r"^%?[\w.\-]*?flash_attention_(fwd|dq|dkv)[\w\-]*?(?:\.\d+)? = .* custom-call\("
)


def read(obs):
    cfg = obs.get("config") or {}
    if "mla" not in (cfg.get("layer_types") or ()):
        return None
    found = trace_kernels.window_ops(obs)
    if found is None:
        return None
    seconds = trace_kernels.kernel_seconds(found[0], KERNEL)
    kernel_s = sum(s for _, s in seconds.values())
    calls = seconds.get("dq", [0])[0]
    if not calls or kernel_s <= 0:
        return None
    peaks = obs["peaks"]
    work = flops_kda.mla_attention_call(cfg, obs["rows"], obs["seq_len"])
    per_call = sum(
        max(w["flops"] / peaks["flops_per_s"], w["bytes"] / peaks["hbm_bytes_per_s"])
        for w in (work["fwd"], work["bwd"])
    )
    return 100.0 * calls * per_call / kernel_s
