"""cellbench/flops_routed.py against counts worked by hand for Laguna-S-2.1
as one chip's eighth of each layer (ISSUE 29's table and its 20.86 TFLOP)."""

import json
from pathlib import Path

import pytest

from cellbench import flops_routed as fr

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
QKVO = ["q", "k", "v", "o"]


@pytest.fixture
def c():
    return json.loads((CONFIGS / "laguna-s-2.1-ep8.json").read_text())


def test_what_the_chip_holds(c):
    # attention, gate included: q 3072 x H*128, k and v 3072 x 1024, o back, gate 3072 x H
    attn48 = 3072 * 6144 * 2 + 3072 * 1024 * 2 + 3072 * 48
    attn72 = 3072 * 9216 * 2 + 3072 * 1024 * 2 + 3072 * 72
    assert (attn48, attn72) == (44_187_648, 63_135_744)
    expert = 3 * 3072 * 1024
    assert fr.expert_params(c) == expert == 9_437_184
    dense = attn48 + 3 * 3072 * 12288
    assert fr.layer_params(c, 0) == {"held": dense, "touched": dense}
    assert dense == 157_433_856
    # a sparse layer: attention + shared expert + router over 256 + 32 of 256 experts
    sliding = attn72 + expert + 3072 * 256 + 32 * expert
    full = attn48 + expert + 3072 * 256 + 32 * expert
    assert [fr.layer_params(c, i)["held"] for i in (1, 2, 3, 4)] == [sliding] * 3 + [full]
    assert (sliding, full) == (375_349_248, 356_401_152)
    # embedding and head at 12,544 rows each, 11 norm vectors
    assert fr.head_params(c) == 3072 * 12544 == 38_535_168
    held = dense + 3 * sliding + full + 2 * 38_535_168 + 11 * 3072
    assert fr.held_params(c) == held == 1_716_986_880  # the table's 1,716.99 M


def test_what_a_token_touches(c):
    # of a layer's 32 held experts a token reaches 10 x 32 / 256 = 1.25 in expectation
    assert fr.local_assignments(c, 8192) == 10_240
    sliding = 63_135_744 + 9_437_184 + 786_432 + 1.25 * 9_437_184
    assert fr.layer_params(c, 1)["touched"] == sliding == 85_155_840
    assert fr.layer_params(c, 4)["touched"] == sliding - (63_135_744 - 44_187_648)
    assert fr.touched_params(c) == 157_433_856 + 3 * 85_155_840 + 66_207_744 + 38_535_168
    assert fr.touched_params(c) == 517_644_288  # the issue's 517.6 M


def test_lora_adapters(c):
    # rank 16 on q (3072 + H*128), k and v (3072 + 1024 each), o (H*128 + 3072)
    by_heads = {h: 16 * (2 * (3072 + h * 128) + 2 * (3072 + 1024)) for h in (48, 72)}
    assert fr.lora_params(c, 16, QKVO) == 2 * by_heads[48] + 3 * by_heads[72] == 2_424_832


def test_attended_pairs():
    assert fr.attended_pairs(4096) == 4096 * 4097 // 2 == 8_390_656
    # sum_i min(i + 1, 512): the first 512 rows a triangle, the rest the window whole
    assert fr.attended_pairs(4096, 512) == sum(min(i + 1, 512) for i in range(4096)) == 1_966_336
    assert fr.attended_pairs(4096, 4096) == fr.attended_pairs(4096, 9000) == 8_390_656
    assert fr.attended_pairs(2048, 512) / fr.attended_pairs(2048) == pytest.approx(0.4374, abs=1e-4)  # 56 % removed
    assert 1 - fr.attended_pairs(4096, 512) / fr.attended_pairs(4096) == pytest.approx(0.7657, abs=1e-4)  # 77 %


def test_train_step(c):
    f = fr.train_step_flops(c, rows=2, seq=4096, lora_rank=16, lora_targets=QKVO)
    assert f["frozen_matmul"] == 4 * 517_644_288 * 8192
    assert f["trainable_matmul"] == 6 * 2_424_832 * 8192
    # forward: two products a pair, 2 operations each, heads x 128; two rows
    full = 2 * 4 * 8_390_656 * 48 * 128
    sliding = 2 * 4 * 1_966_336 * 72 * 128
    assert f["attention"] == 3 * (2 * full + 3 * sliding)
    assert f["attention"] == pytest.approx(3.78e12, rel=0.001)
    assert f["total"] == pytest.approx(20.86e12, rel=0.0005)
    # run as full layers the sliding ones would execute four times the pairs
    as_full = 3 * (2 * full + 3 * 2 * 4 * 8_390_656 * 72 * 128)
    assert as_full == pytest.approx(8.04e12, rel=0.001)


def test_flash_window_call(c):
    call = fr.flash_window_call(c, rows=2, seq=4096)
    assert call["fwd"]["flops"] == 2 * 4 * 1_966_336 * 72 * 128
    assert call["bwd"]["flops"] == 2 * call["fwd"]["flops"]
    q = 2 * 4096 * 72 * 128 * 2
    kv = 2 * 4096 * 8 * 128 * 2
    stats = 2 * 4096 * 72 * 4
    assert call["fwd"]["bytes"] == 2 * q + 2 * kv + stats  # q, o; k, v; lse
    assert call["bwd"]["bytes"] == 4 * q + 4 * kv + 2 * stats


def test_grouped_products(c):
    work = fr.grouped_products_layer_step(c, rows=2, seq=4096)
    assert work["flops"] == 4 * 9_437_184 * 10_240
    # a pass: 32 experts' kernels in bf16, and each of the three products'
    # rows in and out (3072 + 1024 wide) in bf16; three passes a step
    a_pass = 32 * 9_437_184 * 2 + 10_240 * 3 * (3072 + 1024) * 2
    assert work["bytes"] == 3 * a_pass
    # bytes bound it on a v5e: 3.13 ms against 1.96 ms
    assert work["bytes"] / 819e9 == pytest.approx(3.134e-3, rel=1e-3)
    assert work["flops"] / 197e12 == pytest.approx(1.962e-3, rel=1e-3)


def test_the_uncut_layer_counts_every_expert_once(c):
    whole = dict(c, num_experts=256, router_width=256)
    assert fr.local_assignments(whole, 8192) == 81_920
    assert fr.layer_params(whole, 1)["held"] == 63_135_744 + 9_437_184 + 786_432 + 256 * 9_437_184
