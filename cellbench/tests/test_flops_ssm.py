"""cellbench/flops_ssm.py against counts worked by hand for
Granite-4.0-H-Small as one chip's eighth of each layer (ISSUE 33's table, its
1,323 M weights touched a token and its 46.7 TFLOP a step)."""

import json
from pathlib import Path

import pytest

from cellbench import flops_ssm as fs

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TARGETS = ["q", "k", "v", "o", "in_proj", "out_proj"]


@pytest.fixture
def c():
    return json.loads((CONFIGS / "granite-4.0-h-small-ep8.json").read_text())


def test_what_the_chip_holds(c):
    # in_proj 4096 x (z 8192 + xBC 8448 + dt 128), out_proj 8192 x 4096
    assert fs.mixer_shapes(c, 0) == {"in_proj": (4096, 16768), "out_proj": (8192, 4096)}
    in_proj, out_proj = 4096 * 16768, 8192 * 4096
    assert (in_proj, out_proj) == (68_681_728, 33_554_432)
    # conv 8448 x 4 taps + 8448 bias; dt_bias, A_log, D of 128; the gated norm's 8192
    small = 8448 * 4 + 8448 + 3 * 128 + 8192
    assert sum(fs.mamba_small_params(c).values()) == small == 50_816
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert sum(i * o for i, o in fs.mixer_shapes(c, 5).values()) == attention == 41_943_040
    # router over 72, shared MLP of 1536, two norms
    outside = 4096 * 72 + 3 * 4096 * 1536 + 2 * 4096
    assert outside == 19_177_472
    expert = 3 * 4096 * 768
    assert fs.expert_params(c) == expert == 9_437_184 and 9 * expert == 84_934_656
    mamba = in_proj + small + out_proj + outside + 9 * expert
    assert fs.layer_params(c, 0)["held"] == mamba == 206_399_104
    assert fs.layer_params(c, 5)["held"] == attention + outside + 9 * expert == 146_055_168
    # nine Mamba layers, one attention layer, the table's 12,544 rows (tied), the final norm
    assert fs.head_params(c) == 12544 * 4096 == 51_380_224
    assert fs.held_params(c) == 9 * mamba + 146_055_168 + 51_380_224 + 4096 == 2_055_031_424


def test_what_a_token_touches(c):
    expected_experts = 10 * 9 / 72 * 9_437_184  # top-10 of 72, 9 held: 1.25 experts
    fixed = 4096 * 72 + 3 * 4096 * 1536
    mamba = 68_681_728 + 33_554_432 + 8448 * 4 + fixed + expected_experts
    attention = 41_943_040 + fixed + expected_experts
    assert fs.layer_params(c, 0)["touched"] == mamba
    assert fs.layer_params(c, 5)["touched"] == attention
    touched = 9 * mamba + attention + 51_380_224
    assert fs.touched_params(c) == touched
    assert round(touched / 1e6) == 1323
    assert fs.local_assignments(c, 8192) == 10_240  # 1,138 a held expert


def test_adapters(c):
    # r16: q and o 4096 + 4096, k and v 4096 + 1024; in_proj 4096 + 16768, out_proj 8192 + 4096
    attention = 16 * (2 * 8192 + 2 * 5120)
    mamba = 16 * (4096 + 16768) + 16 * (8192 + 4096)
    assert (attention, mamba) == (425_984, 530_432)
    assert fs.lora_params(c, 16, TARGETS) == attention + 9 * mamba == 5_199_872
    assert fs.lora_params(c, 16, ["q", "k", "v", "o"]) == attention


def test_attention_and_scan_from_closed_forms(c):
    seq = 8192
    pairs = seq * (seq + 1) // 2
    # QK^T and PV: 2 products x 2 operations x 32 heads x 128
    assert fs.attention_flops(c, seq) == 4 * pairs * 32 * 128
    # 32 chunks of 256: the causal half has 256 * 257 / 2 = 32,896 pairs a chunk
    half = 32 * 32_896
    in_chunk = half * (2 * 128 + 2 * 128 * 64)  # C.B^T once a group; its product with x once a head
    states = 2 * 2 * seq * 128 * 64 * 128  # x^T B and H C: every position, head, width, state
    assert fs.scan_flops(c, seq) == in_chunk + states == 51_876_200_448
    call = fs.ssd_scan_call(c, 1, seq)
    assert call["fwd"]["flops"] == 51_876_200_448 and call["bwd"]["flops"] == 2 * 51_876_200_448
    x = seq * 128 * 64 * 2
    bc, dt = 2 * seq * 128 * 2, seq * 128 * 4
    assert call["fwd"]["bytes"] == 2 * x + bc + dt
    assert call["bwd"]["bytes"] == 3 * x + 2 * bc + 2 * dt


def test_a_step(c):
    step = fs.train_step_flops(c, 1, 8192, 16, TARGETS)
    assert step["frozen_matmul"] == 4 * fs.touched_params(c) * 8192
    assert step["trainable_matmul"] == 6 * 5_199_872 * 8192
    assert step["attention"] == 3 * fs.attention_flops(c, 8192)
    assert step["scan"] == 3 * 9 * 51_876_200_448
    assert step["total"] == sum(v for k, v in step.items() if k != "total")
    assert 46.6e12 < step["total"] < 46.8e12  # 237 ms at 197 TFLOP/s
    # two rows are twice one
    assert fs.train_step_flops(c, 2, 8192, 16, TARGETS)["total"] == pytest.approx(2 * step["total"])


def test_reader_is_none_for_a_decoder_without_mamba_layers(c):
    from cellbench.common import HERE, load_module

    reader = load_module(HERE / "layer_metrics" / "step_mfu.ssm_train.py", "ssm_reader_test")
    cell = {"reference": {"lora": {"rank": 16, "targets": TARGETS}}}
    obs = {"peaks": {"flops_per_s": 197e12}, "steps": 20, "window_s": 20.0, "rows": 1,
           "seq_len": 8192, "chips": 1, "cell": cell, "config": c}
    want = 100 * fs.train_step_flops(c, 1, 8192, 16, TARGETS)["total"] / 197e12
    assert reader.read(obs) == pytest.approx(want) and 23 < want < 24
    laguna = json.loads((CONFIGS / "laguna-s-2.1-ep8.json").read_text())
    assert reader.read({**obs, "config": laguna}) is None
    assert reader.read({**obs, "peaks": None}) is None
