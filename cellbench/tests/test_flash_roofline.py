"""`flash_attn_roofline.train` on an extract of a trace recorded on the chip
(one whole `jit_step_fn` of the training cell and the first 25 ms of the
next: TPU v5 lite, seed 27001, PR 27; kernel events under their whole names,
the rest under their kind)."""

import copy
import gzip
import json
from pathlib import Path

import pytest

from cellbench import flops
from cellbench.common import HERE, load_cell, load_module
from cellbench.peaks import peaks_for

EXTRACT = Path(__file__).parent / "data" / "train_flash_extract.json.gz"
reader = load_module(
    HERE / "layer_metrics" / "flash_attn_roofline.train.py", "test_flash_attn_roofline"
)


@pytest.fixture
def obs():
    with gzip.open(EXTRACT, "rt") as f:
        raw = json.load(f)
    _, _, cell, config = load_cell("internlm2-1.8b.lora-train-2k")
    return {
        "trace_raw": raw, "trace": {"lo": raw["lo"], "hi": raw["hi"]},
        "peaks": peaks_for("TPU v5 lite"), "config": config, "cell": cell,
        "rows": cell["traffic"]["rows"], "seq_len": cell["traffic"]["seq_len"],
    }


def test_the_three_kernels_are_told_apart(obs):
    dev = obs["trace_raw"]["devices"][0]
    found = reader.kernel_seconds(reader.whole_programs(dev, obs["trace"]["lo"], obs["trace"]["hi"]))
    # one whole step: 24 layers, the forward twice under remat; the six
    # kernel events of the next step that the extract holds are left out,
    # and so are the operations that only NAME a kernel among their operands
    assert {k: v[0] for k, v in found.items()} == {"fwd": 48, "dq": 24, "dkv": 24}
    assert found["fwd"][1] == pytest.approx(0.048041136)
    assert found["dq"][1] == pytest.approx(0.026987600)
    assert found["dkv"][1] == pytest.approx(0.031934830)
    assert reader.kernel_seconds(dev["ops"])["fwd"][0] == 54


def test_value_on_the_recorded_step_is_pinned(obs):
    work = flops.flash_attention_call(obs["config"], 2, 2048)
    # worked by hand: operations bound both passes (bytes are 40x below)
    assert work["fwd"]["flops"] == 2 * 4 * (2048 * 2049 / 2) * 16 * 128
    per_call = (work["fwd"]["flops"] + work["bwd"]["flops"]) / 197e12
    want = 100.0 * 24 * per_call / (0.048041136 + 0.026987600 + 0.031934830)
    assert reader.read(obs) == pytest.approx(want)
    assert reader.read(obs) == pytest.approx(11.74606, abs=1e-4)


def test_no_kernel_event_reads_none_never_zero(obs):
    dev = obs["trace_raw"]["devices"][0]
    dev["ops"] = [e for e in dev["ops"] if not reader.KERNEL.match(e[0])]
    assert reader.read(obs) is None
    assert reader.read({**obs, "trace_raw": None}) is None
    assert reader.read({**obs, "peaks": None}) is None
    # the parent's names: a kernel the flax scope named, not the program
    old = ('%attention.96 = (bf16[32,2048,128]{2,1,0}, f32[32,2048,1]{2,1,0}) custom-call('
           'bf16[32,2048,128]{2,1,0} %bitcast.8167), custom_call_target="tpu_custom_call"')
    dev["ops"].append([old, dev["modules"][0][1] + 10, 1000])
    assert reader.read(obs) is None


def test_kernels_twice_as_slow_halve_the_share(obs):
    slow = copy.deepcopy(obs)
    for e in slow["trace_raw"]["devices"][0]["ops"]:
        if reader.KERNEL.match(e[0]):
            e[2] *= 2  # durations only: the events may now overlap, the sum is what is read
    assert reader.read(slow) == pytest.approx(reader.read(obs) / 2)
