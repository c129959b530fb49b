"""cellbench/flops.py against counts worked by hand for both configurations."""

import json
from pathlib import Path

import pytest

from cellbench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_internlm2_parameter_counts():
    c = cfg("internlm2-1.8b")
    # q 2048x2048, k and v 2048x1024, o 2048x2048; gate, up, down 2048x8192
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
    assert layer == 62_914_560
    assert flops.layer_matmul_params(c) == layer
    assert flops.head_params(c) == 2048 * 92544 == 189_530_112
    assert flops.matmul_params(c) == 24 * layer + 189_530_112 == 1_699_479_552
    # + embedding + 49 norm vectors of 2048
    assert flops.total_params(c) == 1_699_479_552 + 189_530_112 + 49 * 2048 == 1_889_110_016


def test_internlm2_lora_train_step():
    c = cfg("internlm2-1.8b")
    # rank 16 on q (2048+2048), k and v (2048+1024 each), o (2048+2048)
    lora = 24 * 16 * (4096 + 3072 + 3072 + 4096)
    assert flops.lora_params(c, 16, ["q", "k", "v", "o"]) == lora == 5_505_024
    f = flops.train_step_flops(c, rows=4, seq=2048, lora_rank=16, lora_targets=["q", "k", "v", "o"])
    tokens = 8192
    assert f["frozen_matmul"] == 4 * 1_699_479_552 * tokens
    assert f["trainable_matmul"] == 6 * lora * tokens
    # forward: two products over the lower triangle (2048*2049/2 pairs), 16
    # heads of 128, 24 layers, 4 rows; backward twice that
    fwd = 24 * 4 * 4 * (2048 * 2049 // 2) * 16 * 128
    assert f["attention"] == 3 * fwd
    assert f["total"] == pytest.approx(6.10e13, rel=0.01)


def test_full_fine_tune_counts_six_per_weight():
    c = cfg("internlm2-1.8b")
    f = flops.train_step_flops(c, rows=1, seq=128)
    assert f["frozen_matmul"] == 0
    assert f["trainable_matmul"] == 6 * 1_699_479_552 * 128


def test_flash_attention_call():
    c = cfg("internlm2-1.8b")
    call = flops.flash_attention_call(c, rows=4, seq=2048)
    pairs = 2048 * 2049 // 2
    assert call["fwd"]["flops"] == 4 * 4 * pairs * 16 * 128
    assert call["bwd"]["flops"] == 2 * call["fwd"]["flops"]
    q = 4 * 2048 * 16 * 128 * 2
    kv = 4 * 2048 * 8 * 128 * 2
    assert call["fwd"]["bytes"] == 2 * q + 2 * kv + 4 * 2048 * 16 * 4


def test_mistral_pp2_counts():
    c = cfg("mistral-7b-v0.3-pp2")
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert flops.layer_matmul_params(c) == layer
    assert flops.head_params(c) == 4096 * 32768 == 134_217_728
    assert flops.matmul_params(c) == 16 * layer + 134_217_728 == 3_623_878_656
    assert flops.total_params(c) == 3_623_878_656 + 134_217_728 + 33 * 4096 == 3_758_231_552
    # keys and values of one token: 2 x 16 layers x 8 heads x 128 x 2 bytes
    assert flops.kv_bytes_per_token(c) == 65536
    # one decoded token at position 999, sampled: every weight twice, the
    # head twice, attention against 1000 keys
    f = flops.serve_token_flops(c, 999, True)
    assert f == 2 * 16 * layer + 16 * 4 * 1000 * 32 * 128 + 2 * 134_217_728
    # a span is the sum of its tokens
    span = flops.serve_span_flops(c, 10, 14, sampled=1)
    assert span == pytest.approx(
        sum(flops.serve_token_flops(c, p, False) for p in range(10, 14)) + 2 * 134_217_728
    )
    assert flops.decode_step_bytes(c, 1000) == 3_623_878_656 * 2 + 1000 * 65536
