"""`mamba_fused_roofline.train` on a synthetic extract: one whole `jit_step_fn`
of a step on rung `block` (two Mamba layers: every forward kernel twice, every
backward once, under the names the compiler gives the custom calls) with half
a step after it, and on the extracts recorded on the chip before the kernels
existed, where it finds nothing to read."""

import gzip
import json
from pathlib import Path

import pytest

from cellbench import mamba_fused_bytes, trace_kernels
from cellbench.common import HERE, load_cell, load_module
from cellbench.peaks import peaks_for

DATA = Path(__file__).parent / "data"
CELL = "granite-4.0-h-small-ep8.lora-train-8k"
reader = load_module(
    HERE / "layer_metrics" / "mamba_fused_roofline.train.py", "mamba_fused_roofline_under_test"
)
# ms of one call, as a rung-`block` step of two Mamba layers would run them
CALLS = {"mamba_conv_silu_fwd": (4, 0.5), "mamba_gate_norm_fwd": (4, 0.6),
         "mamba_conv_silu_bwd": (2, 0.9), "mamba_gate_norm_bwd": (2, 1.0)}


def event(stem, n, start_ms, ms):
    shape = "bf16[1,8192,8448]{2,1,0:T(8,128)(2,1)}"
    head = f"({shape}, {shape})" if stem == "mamba_gate_norm_bwd" else shape
    name = f"%{stem}.{n} = {head} custom-call({shape} %fusion.{n}), custom_call_target=\"tpu_custom_call\""
    return [name, int(start_ms * 1e6), int(ms * 1e6)]


def synthetic():
    ops, t = [], 1.0
    for stem, (calls, ms) in CALLS.items():
        for n in range(calls):
            ops.append(event(stem, n, t, ms))
            ops.append([f"%fusion.{n} = bf16[8192,4096] fusion(...)", int((t + ms) * 1e6), 100_000])
            t += ms + 0.2
    whole = ["jit_step_fn(1)", 500_000, int(t * 1e6)]
    # half a step more: its kernels must not count
    cut = ["jit_step_fn(1)", int((t + 1) * 1e6), int(50 * 1e6)]
    ops.append(event("mamba_conv_silu_fwd", 9, t + 2, 0.5))
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops, "modules": [whole, cut]}],
            "host": [], "lo": 0, "hi": int((t + 10) * 1e6)}


def obs_of(raw, cell_name=CELL):
    _, _, cell, config = load_cell(cell_name)
    return {
        "trace_raw": raw, "trace": {"lo": raw["lo"], "hi": raw["hi"]},
        "peaks": peaks_for("TPU v5 lite"), "config": config, "cell": cell,
        "rows": cell["traffic"]["rows"], "seq_len": cell["traffic"]["seq_len"], "chips": 1,
    }


def test_the_bytes_are_the_issues():
    """0.68 GB forward and 1.09 GB backward a layer at the cell's shape."""
    _, _, cell, config = load_cell(CELL)
    assert mamba_fused_bytes.widths(config) == {"inner": 8192, "conv": 8448}
    work = mamba_fused_bytes.fused_chains_call(config, 1, 8192)
    assert work["fwd"]["bytes"] == 8192 * 2 * (2 * 8448 + 3 * 8192) == 679_477_248
    assert work["bwd"]["bytes"] == 8192 * 2 * (3 * 8448 + 5 * 8192) == 1_086_324_736


def test_the_four_kernels_are_told_apart_in_whole_programs_only():
    obs = obs_of(synthetic())
    ops, programs = trace_kernels.window_ops(obs)
    assert programs == 1
    found = trace_kernels.kernel_seconds(ops, reader.KERNEL)
    assert {k: v[0] for k, v in found.items()} == {
        "conv_silu_fwd": 4, "gate_norm_fwd": 4, "conv_silu_bwd": 2, "gate_norm_bwd": 2}


def test_the_value_is_worked_by_hand():
    """Two layers' forward + backward bytes at the peak over the kernels'
    time, the second forwards in the time and not in the work."""
    value = reader.read(obs_of(synthetic()))
    kernel_ms = sum(calls * ms for calls, ms in CALLS.values())
    want = 100.0 * 2 * (679_477_248 + 1_086_324_736) / 819e9 / (kernel_ms * 1e-3)
    assert value == pytest.approx(want)
    assert 50 < value < 72  # under the ceiling of a step that runs its forwards twice


@pytest.mark.parametrize(
    "extract,cell",
    [("laguna_train_extract.json.gz", "laguna-s-2.1-ep8.lora-train"),
     ("train_flash_extract.json.gz", "internlm2-1.8b.lora-train-2k"),
     ("laguna_train_extract.json.gz", CELL)],
    ids=["laguna", "internlm2", "granite-config-without-the-kernels"],
)
def test_nothing_to_read_is_none_not_nought(extract, cell):
    with gzip.open(DATA / extract, "rt") as f:
        raw = json.load(f)
    assert reader.read(obs_of(raw, cell)) is None


def test_no_trace_is_none():
    obs = obs_of(synthetic())
    assert reader.read({**obs, "trace_raw": None}) is None
    assert reader.read({**obs, "trace": None}) is None
