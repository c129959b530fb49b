"""The grouped expert products' share of their roofline in a training step.

The routed layer (`polyaxon_tpu/models/moe.py`) takes its three SwiGLU
products as `jax.lax.ragged_dot` over the experts held; the chip's compiler
makes each a grouped-matmul custom call whose HLO instruction is named
`%ragged-dot...` (PERF.md has the names the first traced run showed, and
`tests/data/laguna_train_extract.json.gz` an extract of it).

    kernel time   = sum of those events' durations, whole programs inside
                    the window only (the small `ragged-dot-metadata` calls
                    that prepare the group offsets included: they are part of
                    the product's cost)
    required work = (whole programs) x (sparse layers) x
                    `flops_routed.grouped_products_layer_step`: operations
                    4 x 3 x D x Fe a local assignment in expectation; bytes
                    the held experts' bf16 kernels once a pass (forward,
                    remat's forward, backward) plus the routed rows
    value         = 100 x max(operations / peak, bytes / HBM peak) / kernel time

None where nothing matches (a dense model, a parent without the routed
layer): never 0.
"""

import re

from cellbench import flops_routed, trace_kernels

KERNEL = re.compile(r"^%?(ragged-dot)[\w\-]*?(?:\.\d+)? = ")


def read(obs):
    found = trace_kernels.window_ops(obs)
    cfg = obs.get("config") or {}
    if found is None or "moe_intermediate_size" not in cfg:
        return None
    ops, programs = found
    kernels = trace_kernels.kernel_seconds(ops, KERNEL)
    kernel_s = sum(s for _, s in kernels.values())
    if not programs or kernel_s <= 0:
        return None
    peaks = obs["peaks"]
    sparse = flops_routed.sparse_layers(cfg)
    work = flops_routed.grouped_products_layer_step(cfg, obs["rows"], obs["seq_len"])
    per_layer_step = max(
        work["flops"] / peaks["flops_per_s"], work["bytes"] / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * programs * sparse * per_layer_step / kernel_s
