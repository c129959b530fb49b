"""The two serving readers PR 27 adds as files, on hand-made observations."""

import pytest

from cellbench import flops
from cellbench.common import HERE, load_cell, load_module

host_ms = load_module(HERE / "layer_metrics" / "step_host_ms.serve.py", "test_step_host_ms")
roofline = load_module(
    HERE / "layer_metrics" / "decode_step_hbm_roofline.serve.py", "test_decode_roofline"
)


def test_step_host_ms_is_every_phase_but_fetch_over_steps():
    s0 = {"chunked": {"steps": 10, "phase_s": {"intake": 1.0, "prepare": 2.0, "dispatch": 3.0,
                                               "fetch": 40.0, "emit": 4.0}}}
    s1 = {"chunked": {"steps": 30, "phase_s": {"intake": 1.02, "prepare": 2.06, "dispatch": 3.10,
                                               "fetch": 41.0, "emit": 4.02}}}
    assert host_ms.read({"stats0": s0, "stats1": s1}) == pytest.approx(1e3 * 0.20 / 20)
    # a program without the spans (the parent), or a window without a step
    assert host_ms.read({"stats0": {"chunked": {"steps": 1}}, "stats1": {"chunked": {"steps": 5}}}) is None
    assert host_ms.read({"stats0": s0, "stats1": s0}) is None
    assert host_ms.read({}) is None


def test_live_kv_tokens_is_a_time_average_over_decoding_requests():
    # prompt 100; first frame at t=1 carries 1 token, then one at 2 and 3
    r = {"prompt_len": 100, "frames": [[1.0, 1], [2.0, 1], [3.0, 1]]}
    # decoding from 1 to 3 at lengths 101 then 102; window [0, 4)
    assert roofline.live_kv_tokens([r], 0.0, 4.0) == pytest.approx((101 + 102) / 4)
    assert roofline.live_kv_tokens([r], 1.5, 2.5) == pytest.approx(101 * 0.5 + 102 * 0.5)
    assert roofline.live_kv_tokens([r, r], 0.0, 4.0) == pytest.approx(2 * (101 + 102) / 4)
    assert roofline.live_kv_tokens([{"prompt_len": 9, "frames": []}], 0.0, 1.0) == 0.0


def test_decode_roofline_counts_only_the_decode_step_programs():
    _, _, cell, config = load_cell("mistral-7b-v0.3-pp2.serve-closed32")
    peaks = {"hbm_bytes_per_s": 819e9}
    mods = [["jit_decode_step(123)", 1_000, 40_000_000], ["jit_prefill_slice(7)", 50_000_000, 30_000_000],
            ["jit_decode_step(456)", 90_000_000, 20_000_000], ["jit_decode_step(123)", 999_000_000, 40_000_000]]
    rec = {"prompt_len": 1000, "frames": [[0.0, 1], [10.0, 1]]}
    obs = {"trace_raw": {"devices": [{"modules": mods, "ops": []}]}, "trace": {"lo": 0, "hi": 1_000_000_000},
           "peaks": peaks, "records": [rec] * 8, "t_open": 0.0, "t_close": 1.0,
           "cell": cell, "config": config}
    need = flops.decode_step_bytes(config, 8 * 1001)
    # the last decode step runs past the window and is left out
    assert roofline.read(obs) == pytest.approx(100.0 * 2 * need / 819e9 / 0.060)
    assert roofline.read({**obs, "records": []}) is None
    assert roofline.read({**obs, "trace_raw": {"devices": [{"modules": mods[1:2], "ops": []}]}}) is None
