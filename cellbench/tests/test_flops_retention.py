"""cellbench/flops_retention.py against counts worked by hand: a tiny
decoder of one power-retention layer, and Brumby-14B-Base as one stage of a
ten-stage pipeline (330,516,736 parameters a layer,
4.33 MFLOP a head and token of the scan's forward at chunk 256, about 344
TFLOP a step), and the reader it feeds."""

import json
from pathlib import Path

import pytest

from cellbench import flops_retention as fr
from cellbench.common import HERE, load_module

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
CELL = "brumby-14b-base-pp10.lora-train-32k"


@pytest.fixture
def c():
    return json.loads((CONFIGS / "brumby-14b-base-pp10.json").read_text())


def tiny():
    """One layer: hidden 8, two query heads over one group of 4, MLP 6,
    vocabulary 10, chunk 2."""
    return {"hidden_size": 8, "num_hidden_layers": 1, "layer_types": ["power_retention"],
            "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
            "intermediate_size": 6, "vocab_size": 10, "model": {"retention_chunk_size": 2}}


def test_a_tiny_layer_by_hand():
    t = tiny()
    # q 8x8, k and v 8x4, o 8x8, gate and up 8x6, down 6x8, the log-gate's 8x2
    touched = 64 + 32 + 32 + 64 + 3 * 48 + 16
    assert fr.layer_params(t) == {"touched": touched, "held": touched + 2 * 8 + 2 * 4}
    assert fr.held_params(t) == touched + 24 + 8 + 2 * 80
    assert fr.touched_params(t) == touched + 80
    assert fr.features(t) == 10  # C(5, 2)
    # a chunk of 2, a head: phi(Q)[S|z] and phi(K)^T[V|1] 2x2x10x5 each, the
    # causal halves (3 pairs) of QK^T at 4 and of the scores x [V|1] at 5
    per_chunk = 2 * 2 * 2 * 10 * 5 + 2 * 4 * 3 + 2 * 5 * 3
    assert per_chunk == 454
    assert fr.scan_flops(t, 5) == 3 * 2 * per_chunk  # 5 positions: 3 chunks, the last padded
    assert fr.lora_params(t, 2, ["q", "k", "v", "o"]) == 2 * (16 + 12 + 12 + 16)
    step = fr.train_step_flops(t, 1, 5, 2, ["q", "o"])
    assert step == {"frozen_matmul": 4.0 * (touched + 80) * 5, "trainable_matmul": 6.0 * 64 * 5,
                    "scan": 3.0 * 2724, "total": 4.0 * (touched + 80) * 5 + 6.0 * 64 * 5 + 3.0 * 2724}


def test_the_counts_at_the_cells_size(c):
    assert fr.layer_params(c)["held"] == 330_516_736
    assert fr.held_params(c) == 2_877_896_704
    assert fr.lora_params(c, 16, ["q", "k", "v", "o"]) == 2_097_152
    assert fr.features(c) == 8256
    per_head_token = fr.scan_flops(c, 32768) / 32768 / 40
    assert per_head_token == 4_326_145  # 4 x 8,256 x 129 + 257 x 257
    per_layer_token = 3 * 40 * per_head_token
    assert round(per_layer_token / 1e9, 2) == 0.52
    assert round(4 * fr.layer_params(c)["touched"] / 1e9, 2) == 1.32
    assert round(4 * 151936 * 5120 / 1e9, 2) == 3.11
    step = fr.train_step_flops(c, 1, 32768, 16, ["q", "k", "v", "o"])
    assert round(step["total"] / 1e12) == 344
    assert round(step["scan"] / step["total"], 2) == 0.2  # retention a fifth of the work
    assert step["total"] / 197e12 == pytest.approx(1.75, abs=0.01)  # s at the bf16 peak


def observation(c, **over):
    cell = json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
    obs = {"cell": cell, "config": c, "window_s": 20.0, "steps": 4, "rows": 1, "seq_len": 32768,
           "chips": 1, "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    obs.update(over)
    return obs


def test_step_mfu_reads_a_retention_configuration_only(c):
    reader = load_module(HERE / "layer_metrics" / "step_mfu.retention_train.py", "t_mfu_ret")
    per_step = fr.train_step_flops(c, 1, 32768, 16, ["q", "k", "v", "o"])["total"]
    assert reader.read(observation(c)) == pytest.approx(100 * per_step * 4 / 20.0 / 197e12)
    ling = json.loads((CONFIGS / "ling-3.0-flash-vl-ep16.json").read_text())
    internlm = json.loads((CONFIGS / "internlm2-1.8b.json").read_text())
    assert reader.read(observation(ling)) is None
    assert reader.read(observation(internlm)) is None
    assert reader.read(observation(c, steps=0)) is None
