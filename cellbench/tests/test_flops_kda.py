"""cellbench/flops_kda.py against counts worked by hand for
Ling-3.0-flash-VL's language model as one chip's sixteenth of each routed
layer (ISSUE 35's arithmetic: 1,733.8 M frozen, 569 M weights touched a
token, about 47 TFLOP a step), and the two readers it feeds."""

import json
from pathlib import Path

import pytest

from cellbench import flops_kda as fk
from cellbench.common import HERE, load_module

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TARGETS = ["q", "k", "v", "o", "kv_a", "kv_b"]


@pytest.fixture
def c():
    return json.loads((CONFIGS / "ling-3.0-flash-vl-ep16.json").read_text())


def test_what_the_chip_holds(c):
    wide = 2560 * 4096
    assert fk.mixer_shapes(c, 0) == {"q": (2560, 4096), "k": (2560, 4096), "v": (2560, 4096),
                                     "o": (4096, 2560)}
    # f_proj and g_proj full rank, b_proj 2560 x 32, three convs of 4 taps
    other = fk.mixer_other_params(c, 0)
    assert other == {"products": 2 * wide + 2560 * 32 + 3 * 4 * 4096, "small": 32 + 4096 + 128}
    kda = 4 * wide + other["products"] + other["small"]
    assert kda == 63_049_888
    assert fk.mixer_shapes(c, 4) == {"q": (2560, 32 * 192), "kv_a": (2560, 576),
                                     "kv_b": (512, 32 * 256), "o": (4096, 2560)}
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + wide + 2560 * 32 + 512 + 2 * 192
    assert mla == 31_966_080
    expert = 3 * 2560 * 768
    assert fk.expert_params(c) == expert == 5_898_240
    routed = 2560 * 512 + 512 + expert + 32 * expert  # router, bias, shared, 32 held
    dense = 3 * 2560 * 6144
    assert fk.layer_params(c, 0)["held"] == kda + dense + 2 * 2560 == 110_240_928
    assert fk.layer_params(c, 1)["held"] == kda + routed + 2 * 2560 == 259_008_160
    assert fk.layer_params(c, 4)["held"] == mla + routed + 2 * 2560 == 227_924_352
    assert fk.head_params(c) == 19648 * 2560 == 50_298_880
    assert fk.held_params(c) == (
        110_240_928 + 5 * 259_008_160 + 227_924_352 + 2 * 50_298_880 + 2560
    ) == 1_733_806_400


def test_what_a_token_touches(c):
    half_an_expert = 8 * 32 / 512 * 5_898_240
    fixed = 2560 * 512 + 5_898_240  # router and shared expert
    kda = 63_049_888 - (32 + 4096 + 128)
    mla = 31_966_080 - (512 + 2 * 192)
    assert fk.layer_params(c, 1)["touched"] == kda + fixed + half_an_expert
    assert fk.layer_params(c, 4)["touched"] == mla + fixed + half_an_expert
    assert fk.layer_params(c, 0)["touched"] == kda + 3 * 2560 * 6144
    assert round(fk.touched_params(c) / 1e6) == 569
    assert fk.local_assignments(c, 16384) == 8192  # 256 a held expert


def test_adapters(c):
    kda = 16 * 4 * (2560 + 4096)
    mla = 16 * ((2560 + 6144) + (2560 + 576) + (512 + 8192) + (4096 + 2560))
    assert fk.lora_params(c, 16, TARGETS) == 6 * kda + mla == 2_991_104
    assert fk.lora_params(c, 16, ["q", "o"]) == 6 * 16 * 2 * 6656 + 16 * (8704 + 6656)


def test_the_triangle_and_the_delta_rule(c):
    pairs = 16384 * 16385 // 2
    assert fk.mla_attention_flops(c, 16384) == 2 * pairs * 32 * (192 + 128)
    call = fk.mla_attention_call(c, 1, 16384)
    assert call["bwd"]["flops"] == 2 * call["fwd"]["flops"]
    tokens = 16384 * 32
    assert call["fwd"]["bytes"] == tokens * (2 * 192 * 2 + 2 * 128 * 2 + 4)
    # a chunk of 64 and a head of 128 x 128
    strict, causal = 64 * 63 // 2, 64 * 65 // 2
    per_chunk = 256 * strict + 256 * causal + 512 * strict + 256 * causal + 6 * 64 * 128 * 128
    assert fk.kda_scan_flops(c, 16384) == 256 * 32 * per_chunk
    scan = fk.kda_scan_call(c, 1, 16384)
    assert scan["fwd"]["flops"] == fk.kda_scan_flops(c, 16384)
    assert scan["fwd"]["bytes"] == tokens * (4 * 128 * 2 + 128 * 4 + 4)


def test_the_step(c):
    step = fk.train_step_flops(c, 1, 16384, 16, TARGETS)
    assert step["frozen_matmul"] == 4.0 * fk.touched_params(c) * 16384
    assert step["attention"] == 3.0 * fk.mla_attention_flops(c, 16384)
    assert step["scan"] == 3.0 * 6 * fk.kda_scan_flops(c, 16384)
    # ISSUE 35: frozen products 37, attention 8, scan 1.5, the step about 47 TFLOP
    tera = {k: round(v / 1e12, 1) for k, v in step.items()}
    assert tera == {"frozen_matmul": 37.3, "trainable_matmul": 0.3, "attention": 8.2,
                    "scan": 1.3, "total": 47.1}


def observation(c, **over):
    cell = json.loads((HERE / "workloads" / "ling-3.0-flash-vl-ep16.lora-train-16k.json").read_text())
    obs = {"cell": cell, "config": c, "window_s": 20.0, "steps": 20, "rows": 1, "seq_len": 16384,
           "chips": 1, "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    obs.update(over)
    return obs


def test_step_mfu_reads_a_kda_configuration_only(c):
    reader = load_module(HERE / "layer_metrics" / "step_mfu.kda_train.py", "t_step_mfu_kda")
    # a step a second of 47.1 TFLOP on a chip of 197
    assert reader.read(observation(c)) == pytest.approx(100 * 47.122435 / 197, rel=1e-6)
    granite = json.loads((CONFIGS / "granite-4.0-h-small-ep8.json").read_text())
    assert reader.read(observation(granite)) is None
    assert reader.read(observation(c, steps=0)) is None


def test_flash_mla_roofline_counts_two_widths_per_dq_call(c):
    reader = load_module(HERE / "layer_metrics" / "flash_mla_roofline.train.py", "t_flash_mla")
    call = fk.mla_attention_call(c, 1, 16384)
    at_peak = (call["fwd"]["flops"] + call["bwd"]["flops"]) / 197e12  # compute-bound both
    assert at_peak == pytest.approx(0.04186, rel=1e-3)

    def op(name, start, ms):
        return (f"%{name} = bf16[1]{{0}} custom-call(%a), custom_call_target=\"tpu_custom_call\"",
                start, int(ms * 1e6))

    # two steps, each: a forward, the checkpoint's second forward, dq, dk/dv
    ops, t = [], 0
    for _ in range(2):
        for name, ms in (("flash_attention_fwd.1", 20), ("flash_attention_fwd.2", 20),
                         ("flash_attention_dq.3", 30), ("flash_attention_dkv.4", 30),
                         ("fusion.9", 500)):
            ops.append(op(name, t, ms))
            t += int(ms * 1e6)
    raw = {"devices": [{"ops": ops, "modules": []}]}
    obs = observation(c, trace_raw=raw, trace={"lo": None, "hi": None})
    assert reader.read(obs) == pytest.approx(100 * 2 * at_peak / 0.200, rel=1e-6)
    granite = json.loads((CONFIGS / "granite-4.0-h-small-ep8.json").read_text())
    assert reader.read(observation(granite, trace_raw=raw, trace={"lo": None, "hi": None})) is None
    assert reader.read(observation(c)) is None  # no trace
    empty = {"devices": [{"ops": [op("fusion.1", 0, 5)], "modules": []}]}
    assert reader.read(observation(c, trace_raw=empty, trace={"lo": None, "hi": None})) is None
