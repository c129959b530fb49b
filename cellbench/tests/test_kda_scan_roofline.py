"""`kda_scan_roofline.train` on a synthetic extract: one whole `jit_step_fn`
of a step on rung `block` (two KDA layers: the forward kernel twice a layer,
the backward once, under the names the compiler gives the custom calls) with
half a step after it, and on the extracts recorded on the chip before the
kernels existed, where it finds nothing to read."""

import gzip
import json
from pathlib import Path

import pytest

from cellbench import flops_kda, trace_kernels
from cellbench.common import HERE, load_cell, load_module
from cellbench.peaks import peaks_for

DATA = Path(__file__).parent / "data"
CELL = "ling-3.0-flash-vl-ep16.lora-train-16k"
reader = load_module(
    HERE / "layer_metrics" / "kda_scan_roofline.train.py", "kda_scan_roofline_under_test"
)
# (calls, ms of one) as a rung-`block` step of two KDA layers would run them
CALLS = {"kda_scan_fwd": (4, 6.0), "kda_scan_bwd": (2, 20.0)}


def event(stem, n, start_ms, ms):
    shape = "bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)}"
    name = (f"%{stem}.{n} = ({shape}, {shape}) custom-call({shape} %fusion.{n}), "
            "custom_call_target=\"tpu_custom_call\"")
    return [name, int(start_ms * 1e6), int(ms * 1e6)]


def synthetic(calls=CALLS):
    ops, t = [], 1.0
    for stem, (count, ms) in calls.items():
        for n in range(count):
            ops.append(event(stem, n, t, ms))
            ops.append([f"%fusion.{n} = bf16[16384,4096] fusion(...)", int((t + ms) * 1e6), 100_000])
            t += ms + 0.2
    whole = ["jit_step_fn(1)", 500_000, int(t * 1e6)]
    # half a step more: its kernels must not count
    cut = ["jit_step_fn(1)", int((t + 1) * 1e6), int(500 * 1e6)]
    ops.append(event("kda_scan_fwd", 9, t + 2, 6.0))
    ops.append(event("kda_scan_bwd", 9, t + 10, 20.0))
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops, "modules": [whole, cut]}],
            "host": [], "lo": 0, "hi": int((t + 100) * 1e6)}


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
    work = flops_kda.kda_scan_call(config, cell["traffic"]["rows"], cell["traffic"]["seq_len"])
    return tuple(
        1e3 * max(work[p]["flops"] / peaks["flops_per_s"], work[p]["bytes"] / peaks["hbm_bytes_per_s"])
        for p in ("fwd", "bwd")
    )


def test_the_bytes_bind_and_are_the_issues():
    """0.99 ms forward and 1.97 ms backward a layer: the bytes bind."""
    fwd, bwd = roofline_ms()
    assert fwd == pytest.approx(0.99, abs=0.01) and bwd == pytest.approx(1.97, abs=0.01)
    _, _, cell, config = load_cell(CELL)
    work = flops_kda.kda_scan_call(config, 1, 16384)
    assert work["fwd"]["flops"] / 197e12 < work["fwd"]["bytes"] / 819e9


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
    assert 5 < value < 75


def test_a_further_kernel_of_a_split_backward_counts_in_the_time():
    split = {**CALLS, "kda_scan_rebuild": (2, 5.0)}
    fwd, bwd = roofline_ms()
    kernel_ms = sum(calls * ms for calls, ms in split.values())
    assert reader.read(obs_of(synthetic(split))) == pytest.approx(
        100.0 * 2 * (fwd + bwd) / kernel_ms)


def test_the_ceiling_on_rung_block_is_75():
    """Kernels at their roofline, the forward run twice a layer."""
    fwd, bwd = roofline_ms()
    at_roofline = {"kda_scan_fwd": (4, fwd), "kda_scan_bwd": (2, bwd)}
    value = reader.read(obs_of(synthetic(at_roofline)))
    assert value == pytest.approx(100.0 * (fwd + bwd) / (2 * fwd + bwd), rel=1e-3)
    assert value == pytest.approx(75.0, abs=0.5)


@pytest.mark.parametrize(
    "extract,cell",
    [("laguna_train_extract.json.gz", "laguna-s-2.1-ep8.lora-train"),
     ("train_flash_extract.json.gz", "internlm2-1.8b.lora-train-2k"),
     ("laguna_train_extract.json.gz", CELL)],
    ids=["laguna", "internlm2", "ling-config-without-the-kernels"],
)
def test_nothing_to_read_is_none_not_nought(extract, cell):
    with gzip.open(DATA / extract, "rt") as f:
        raw = json.load(f)
    assert reader.read(obs_of(raw, cell)) is None


def test_forwards_alone_are_none():
    """A program that never differentiates the scan has no call to count."""
    raw = synthetic({"kda_scan_fwd": (4, 6.0)})
    raw["devices"][0]["ops"] = [e for e in raw["devices"][0]["ops"] if "kda_scan_bwd" not in e[0]]
    assert reader.read(obs_of(raw)) is None


def test_no_trace_is_none():
    obs = obs_of(synthetic())
    assert reader.read({**obs, "trace_raw": None}) is None
    assert reader.read({**obs, "trace": None}) is None
