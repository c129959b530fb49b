"""The flash-attention kernels' share of their roofline in a training step.

The three Pallas kernels of `polyaxon_tpu/ops/flash_attention.py` carry
their names into the trace (`%flash_attention_fwd.N`, `_dq`, `_dkv`: the
stem of the custom call's HLO instruction). Read from the first chip's
operations, over the whole programs that lie inside the window (so that no
step counts with half its kernels):

    kernel time   = sum of the three kernels' event durations
    required work = (number of dq calls) x (forward + backward of one call,
                    `flops.flash_attention_call(config, rows, seq)`): the
                    count is the trace's own, one dq call per layer and step,
                    so remat's second forward adds time and no work
    roofline time = per call, forward and backward each at the larger of
                    operations / peak FLOP/s and bytes / peak HBM bytes/s
    value         = 100 x roofline time / kernel time

None where the trace holds no such kernel (a program without these names,
a cell that runs no flash attention): never 0.
"""

import re

from cellbench import flops

KERNEL = re.compile(r"^%?[\w.\-]*?flash_attention_(fwd|dq|dkv)[\w\-]*?(?:\.\d+)? = ")


def kernel_seconds(ops) -> dict:
    """kernel -> [calls, seconds] of the events named as the kernels are."""
    out: dict = {}
    for name, _, dur in ops:
        m = KERNEL.match(name)
        if m and " custom-call(" in name:
            c = out.setdefault(m.group(1), [0, 0.0])
            c[0] += 1
            c[1] += dur * 1e-9
    return out


def whole_programs(dev: dict, lo, hi) -> list:
    """The chip's operations inside the programs that ran wholly in
    [lo, hi); inside [lo, hi) itself where the trace names no program."""
    if lo is None:
        return dev["ops"]
    inside = [(s, s + d) for _, s, d in dev.get("modules") or [] if s >= lo and s + d <= hi]
    if inside:
        lo, hi = min(s for s, _ in inside), max(e for _, e in inside)
    return [e for e in dev["ops"] if e[1] >= lo and e[1] + e[2] <= hi]


def read(obs):
    raw, peaks, red = obs.get("trace_raw"), obs.get("peaks"), obs.get("trace")
    if not raw or not peaks or not red or not raw.get("devices"):
        return None
    found = kernel_seconds(whole_programs(raw["devices"][0], red.get("lo"), red.get("hi")))
    kernel_s = sum(s for _, s in found.values())
    calls = found.get("dq", [0])[0]
    if not calls or kernel_s <= 0:
        return None
    work = flops.flash_attention_call(obs["config"], obs["rows"], obs["seq_len"])
    per_call = sum(
        max(w["flops"] / peaks["flops_per_s"], w["bytes"] / peaks["hbm_bytes_per_s"])
        for w in (work["fwd"], work["bwd"])
    )
    return 100.0 * calls * per_call / kernel_s
