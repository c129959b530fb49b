"""`flash_window_roofline.train` and `moe_grouped_roofline.train` on an extract
of a trace recorded on the chip (one whole `jit_step_fn` of the Laguna cell
and the first 25 ms of the next: TPU v5 lite, seed 29201, PR 29; kernel events
under their whole names cut to 400 characters, the rest under their kind),
and on the InternLM2 cell's extract, where both find nothing to read."""

import copy
import gzip
import json
from pathlib import Path

import pytest

from cellbench import flops_routed, trace_kernels
from cellbench.common import HERE, load_cell, load_module
from cellbench.peaks import peaks_for

DATA = Path(__file__).parent / "data"
CELL = "laguna-s-2.1-ep8.lora-train"
window = load_module(
    HERE / "layer_metrics" / "flash_window_roofline.train.py", "test_flash_window_roofline"
)
grouped = load_module(
    HERE / "layer_metrics" / "moe_grouped_roofline.train.py", "test_moe_grouped_roofline"
)
mfu = load_module(HERE / "layer_metrics" / "step_mfu.routed_train.py", "test_step_mfu_routed")
full = load_module(
    HERE / "layer_metrics" / "flash_attn_roofline.train.py", "test_flash_attn_roofline_laguna"
)


def obs_of(extract, cell_name):
    with gzip.open(DATA / extract, "rt") as f:
        raw = json.load(f)
    _, _, cell, config = load_cell(cell_name)
    return {
        "trace_raw": raw, "trace": {"lo": raw["lo"], "hi": raw["hi"]},
        "peaks": peaks_for("TPU v5 lite"), "config": config, "cell": cell,
        "rows": cell["traffic"]["rows"], "seq_len": cell["traffic"]["seq_len"],
        "chips": 1,
    }


@pytest.fixture
def obs():
    return obs_of("laguna_train_extract.json.gz", CELL)


@pytest.fixture
def dense_obs():
    return obs_of("train_flash_extract.json.gz", "internlm2-1.8b.lora-train-2k")


def test_the_kernels_are_told_apart(obs):
    ops, programs = trace_kernels.window_ops(obs)
    assert programs == 1
    # one whole step: three sliding layers and two full ones, the forward
    # twice under remat; four sparse layers x three products x three passes
    found = trace_kernels.kernel_seconds(ops, window.KERNEL)
    assert {k: v[0] for k, v in found.items()} == {"fwd": 6, "dq": 3, "dkv": 3}
    plain = full.kernel_seconds(ops)
    assert {k: v[0] for k, v in plain.items()} == {"fwd": 4, "dq": 2, "dkv": 2}
    products = trace_kernels.kernel_seconds(ops, grouped.KERNEL)
    assert list(products) == ["ragged-dot"]
    names = [e[0] for e in ops if grouped.KERNEL.match(e[0])]
    assert sum("ragged-dot-none" in n[:40] for n in names) == 36
    assert sum("ragged-dot-metadata" in n[:40] for n in names) == 8
    assert products["ragged-dot"][0] == 44


def test_flash_window_value_is_worked_by_hand(obs):
    ops, _ = trace_kernels.window_ops(obs)
    found = trace_kernels.kernel_seconds(ops, window.KERNEL)
    kernel_s = sum(s for _, s in found.values())
    work = flops_routed.flash_window_call(obs["config"], 2, 4096)
    # operations bound both passes at 72 heads of 128 (bytes are 2x below)
    assert work["fwd"]["flops"] / 197e12 > work["fwd"]["bytes"] / 819e9
    want = 100.0 * 3 * (work["fwd"]["flops"] + work["bwd"]["flops"]) / 197e12 / kernel_s
    assert window.read(obs) == pytest.approx(want)
    assert 5.0 < window.read(obs) < 12.0


def test_grouped_value_is_worked_by_hand(obs):
    ops, _ = trace_kernels.window_ops(obs)
    kernel_s = sum(s for _, s in trace_kernels.kernel_seconds(ops, grouped.KERNEL).values())
    work = flops_routed.grouped_products_layer_step(obs["config"], 2, 4096)
    want = 100.0 * 1 * 4 * (work["bytes"] / 819e9) / kernel_s  # bytes bound it
    assert grouped.read(obs) == pytest.approx(want)
    assert 20.0 < grouped.read(obs) < 50.0


def test_the_full_layers_are_counted_by_the_accepted_reader(obs):
    # its count takes the configuration's top-level 48 heads of 128: the full layers'
    value = full.read(obs)
    assert value is not None and 8.0 < value < 20.0


@pytest.mark.parametrize("reader", [window, grouped], ids=["flash_window", "moe_grouped"])
def test_nothing_to_read_is_none_never_zero(reader, obs, dense_obs):
    assert reader.read(dense_obs) is None  # the dense cell's trace and configuration
    # the dense program's trace under this configuration (a parent without the kernels)
    assert reader.read({**dense_obs, "config": obs["config"]}) is None
    assert reader.read({**obs, "trace_raw": None}) is None
    assert reader.read({**obs, "peaks": None}) is None
    stripped = copy.deepcopy(obs)
    dev = stripped["trace_raw"]["devices"][0]
    dev["ops"] = [e for e in dev["ops"] if not reader.KERNEL.match(e[0])]
    assert reader.read(stripped) is None


def test_kernels_twice_as_slow_halve_the_share(obs):
    slow = copy.deepcopy(obs)
    for e in slow["trace_raw"]["devices"][0]["ops"]:
        if window.KERNEL.match(e[0]) or grouped.KERNEL.match(e[0]):
            e[2] *= 2
    assert window.read(slow) == pytest.approx(window.read(obs) / 2)
    assert grouped.read(slow) == pytest.approx(grouped.read(obs) / 2)


def test_step_mfu_of_the_routed_step(obs, dense_obs):
    timed = {**obs, "steps": 44, "window_s": 22.299}  # my chip run, PR 29, seed 29004
    want = 100.0 * 20_860_624_699_392 * 44 / 22.299 / 197e12
    assert mfu.read(timed) == pytest.approx(want) == pytest.approx(20.894, abs=1e-3)
    assert mfu.read({**dense_obs, "steps": 44, "window_s": 22.299}) is None
    assert mfu.read({**timed, "steps": 0}) is None
