"""`retention_scan_roofline.train` on a synthetic extract: one whole
`jit_step_fn` of a step on rung `block` (two retention layers: the forward
kernel twice a layer, the backward once, under the names the compiler gives
the custom calls) with half a step after it, and on the extracts recorded on
the chip before the kernels existed, where it finds nothing to read; and
`cellbench/retention_scan_call.py` by hand."""

import gzip
import json
from pathlib import Path

import pytest

from cellbench import trace_kernels
from cellbench.retention_scan_call import retention_scan_call
from cellbench.common import HERE, load_cell, load_module
from cellbench.peaks import peaks_for

DATA = Path(__file__).parent / "data"
CELL = "brumby-14b-base-pp10.lora-train-32k"
reader = load_module(
    HERE / "layer_metrics" / "retention_scan_roofline.train.py",
    "retention_scan_roofline_under_test",
)
# (calls, ms of one) as a rung-`block` step of two retention layers would run them
CALLS = {"retention_scan_fwd": (4, 60.0), "retention_scan_bwd": (2, 150.0)}


def event(stem, n, start_ms, ms):
    shape = "bf16[1,32768,5120]{2,1,0:T(8,128)(2,1)}"
    name = (f"%{stem}.{n} = ({shape}, {shape}) custom-call({shape} %fusion.{n}), "
            "custom_call_target=\"tpu_custom_call\"")
    return [name, int(start_ms * 1e6), int(ms * 1e6)]


def synthetic(calls=CALLS):
    ops, t = [], 1.0
    for stem, (count, ms) in calls.items():
        for n in range(count):
            ops.append(event(stem, n, t, ms))
            ops.append([f"%fusion.{n} = bf16[32768,5120] fusion(...)", int((t + ms) * 1e6), 100_000])
            t += ms + 0.2
    whole = ["jit_step_fn(1)", 500_000, int(t * 1e6)]
    # half a step more: its kernels must not count
    cut = ["jit_step_fn(1)", int((t + 1) * 1e6), int(5000 * 1e6)]
    ops.append(event("retention_scan_fwd", 9, t + 2, 60.0))
    ops.append(event("retention_scan_bwd", 9, t + 70, 150.0))
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops, "modules": [whole, cut]}],
            "host": [], "lo": 0, "hi": int((t + 1000) * 1e6)}


def obs_of(raw, cell_name=CELL):
    _, _, cell, config = load_cell(cell_name)
    return {
        "trace_raw": raw, "trace": {"lo": raw["lo"], "hi": raw["hi"]},
        "peaks": peaks_for("TPU v5 lite"), "config": config, "cell": cell,
        "rows": cell["traffic"]["rows"], "seq_len": cell["traffic"]["seq_len"], "chips": 1,
    }


def roofline_ms():
    """(forward, backward) of one layer's scan at the cell's shape."""
    _, _, cell, config = load_cell(CELL)
    peaks = peaks_for("TPU v5 lite")
    work = retention_scan_call(
        config, cell["traffic"]["rows"], cell["traffic"]["seq_len"])
    return tuple(
        1e3 * max(work[p]["flops"] / peaks["flops_per_s"], work[p]["bytes"] / peaks["hbm_bytes_per_s"])
        for p in ("fwd", "bwd")
    )


def test_the_operations_bind_at_the_cells_shape():
    """28.8 ms forward and 57.5 ms backward a layer: the operations bind,
    17.0 TFLOP a layer forward and backward."""
    fwd, bwd = roofline_ms()
    assert fwd == pytest.approx(28.8, abs=0.05) and bwd == pytest.approx(57.5, abs=0.1)
    _, _, cell, config = load_cell(CELL)
    work = retention_scan_call(config, 1, 32768)
    assert work["fwd"]["flops"] / 197e12 > 10 * work["fwd"]["bytes"] / 819e9
    assert (work["fwd"]["flops"] + work["bwd"]["flops"]) / 1e12 == pytest.approx(17.0, abs=0.05)


def test_a_scan_call_by_hand():
    """One layer's scan, forward and backward: the operations of the exact
    symmetric square (8,256 features, never the 9,216 a program builds), the
    backward twice the forward; q, k, v, the log-gate and y across HBM once
    forward, and with dy and the four gradients backward."""
    tiny = {"hidden_size": 8, "num_hidden_layers": 1, "layer_types": ["power_retention"],
            "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
            "intermediate_size": 6, "vocab_size": 10, "model": {"retention_chunk_size": 2}}
    # 5 positions, 2 heads of 4 over one group: q and y 40 values each, k and v
    # 20; 3 chunks (the last padded) of 454 operations a head
    assert retention_scan_call(tiny, 1, 5) == {
        "fwd": {"flops": 2 * 3 * 454, "bytes": 2 * 80 + 2 * 40 + 40},
        "bwd": {"flops": 2 * 2 * 3 * 454, "bytes": 3 * 80 + 4 * 40 + 80}}
    _, _, _, config = load_cell(CELL)
    cell = retention_scan_call(config, 1, 32768)
    assert cell["fwd"]["flops"] == 32768 * 40 * 4_326_145  # 5.67 TFLOP
    assert round(cell["fwd"]["flops"] / 128 / 40 / 1e9, 3) == 1.107  # a chunk and head
    assert cell["fwd"]["bytes"] == 32768 * (2 * 2 * 5120 + 2 * 2 * 1024 + 4 * 40)
    assert cell["bwd"]["bytes"] == 32768 * (3 * 2 * 5120 + 4 * 2 * 1024 + 2 * 4 * 40)


def test_the_kernels_are_told_apart_in_whole_programs_only():
    obs = obs_of(synthetic())
    ops, programs = trace_kernels.window_ops(obs)
    assert programs == 1
    found = trace_kernels.kernel_seconds(ops, reader.KERNEL)
    assert {k: v[0] for k, v in found.items()} == {"fwd": 4, "bwd": 2}


def test_the_value_is_worked_by_hand():
    """Two layers' forward + backward at the roofline over the kernels' time,
    the second forwards in the time and not in the work."""
    value = reader.read(obs_of(synthetic()))
    fwd, bwd = roofline_ms()
    kernel_ms = sum(calls * ms for calls, ms in CALLS.values())
    assert value == pytest.approx(100.0 * 2 * (fwd + bwd) / kernel_ms)
    assert 25 < value < 75


def test_a_further_kernel_of_a_split_backward_counts_in_the_time():
    split = {**CALLS, "retention_scan_rebuild": (2, 50.0)}
    fwd, bwd = roofline_ms()
    kernel_ms = sum(calls * ms for calls, ms in split.values())
    assert reader.read(obs_of(synthetic(split))) == pytest.approx(
        100.0 * 2 * (fwd + bwd) / kernel_ms)


def test_the_ceiling_on_rung_block_is_75():
    """Kernels at their roofline, the forward run twice a layer."""
    fwd, bwd = roofline_ms()
    at_roofline = {"retention_scan_fwd": (4, fwd), "retention_scan_bwd": (2, bwd)}
    value = reader.read(obs_of(synthetic(at_roofline)))
    assert value == pytest.approx(100.0 * (fwd + bwd) / (2 * fwd + bwd), rel=1e-3)
    assert value == pytest.approx(75.0, abs=0.1)


@pytest.mark.parametrize(
    "extract,cell",
    [("laguna_train_extract.json.gz", "laguna-s-2.1-ep8.lora-train"),
     ("train_flash_extract.json.gz", "internlm2-1.8b.lora-train-2k"),
     ("laguna_train_extract.json.gz", CELL)],
    ids=["laguna", "internlm2", "brumby-config-without-the-kernels"],
)
def test_nothing_to_read_is_none_not_nought(extract, cell):
    with gzip.open(DATA / extract, "rt") as f:
        raw = json.load(f)
    assert reader.read(obs_of(raw, cell)) is None


def test_another_configuration_is_none():
    """The Ling cell's `kda_scan_*` kernels are no retention scan's."""
    raw = synthetic({"kda_scan_fwd": (4, 6.0), "kda_scan_bwd": (2, 20.0)})
    assert reader.read(obs_of(raw)) is None
    assert reader.read(obs_of(synthetic(), "ling-3.0-flash-vl-ep16.lora-train-16k")) is None


def test_forwards_alone_are_none():
    """A program that never differentiates the scan has no call to count."""
    raw = synthetic({"retention_scan_fwd": (4, 60.0)})
    raw["devices"][0]["ops"] = [e for e in raw["devices"][0]["ops"]
                                if "retention_scan_bwd" not in e[0]]
    assert reader.read(obs_of(raw)) is None


def test_no_trace_is_none():
    obs = obs_of(synthetic())
    assert reader.read({**obs, "trace_raw": None}) is None
    assert reader.read({**obs, "trace": None}) is None
