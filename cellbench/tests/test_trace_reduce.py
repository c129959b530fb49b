"""The reduction from a trace to numbers, on hand-made events and on a small
extract of a trace recorded on the chip (the first 400 events of each line of
a traced run of the training cell: TPU v5 lite, PR 26)."""

from pathlib import Path

import pytest

from cellbench import trace_reduce as t

SAMPLE = Path(__file__).parent / "data" / "train_trace_sample.json.gz"


def test_busy_is_a_union_not_a_sum():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 31, 2]]
    assert t.merged(ev) == [[0, 15], [30, 35]]
    assert t.busy_ns(ev) == 20
    assert t.gaps(ev, 0, 40) == [[15, 30], [35, 40]]
    assert t.gaps(ev, 0, 40, floor_ns=6) == [[15, 30]]
    assert t.clip(ev, 8, 32) == [["a", 8, 2], ["b", 8, 7], ["c", 30, 2], ["d", 31, 1]]


def test_totals_and_kinds():
    name = ('%attention.177 = (bf16[16,2048,128]{2,1,0}, bf16[16,2048,128]{2,1,0}) '
            'custom-call(bf16[32,2048,128]{2,1,0} %bitcast.8117), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert t.short_name(name) == "attention custom-call:tpu_custom_call"
    assert t.short_name("%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8,128] %p), kind=kLoop") == "fusion fusion"
    assert t.short_name("jit_step_fn(123)") == "jit_step_fn(123)"
    ev = [[name, 0, 5], [name.replace("177", "178"), 9, 7], ["jit_step_fn(123)", 0, 20]]
    assert t.by_kind(ev)["attention custom-call:tpu_custom_call"] == [2, 12]
    assert t.totals(ev, strip=r"\(\d+\)$")["jit_step_fn"] == [1, 20]
    assert t.matching_ns(ev, "tpu_custom_call") == (2, 12)


def test_gaps_go_to_the_host_span_that_covers_them():
    host = [["cellbench.window", 0, 10_000_000], ["cellbench.prepare", 1_000_000, 2_000_000],
            ["cellbench.fetch", 5_000_000, 1_000_000]]
    gaps = [[1_200_000, 2_800_000], [5_100_000, 5_900_000], [7_000_000, 8_000_000], [10, 500]]
    out = t.attribute(gaps, host)
    assert out == {"cellbench.prepare": 1_600_000, "cellbench.fetch": 800_000,
                   t.NO_SPAN: 1_000_000, t.BETWEEN_OPS: 490}


def test_recorded_sample_reduces():
    trace = t.load_sample(str(SAMPLE))
    dev = trace["devices"][0]
    assert dev["plane"] == "/device:TPU:0" and len(dev["ops"]) == 400
    lo, hi = dev["ops"][0][1], dev["ops"][-1][1] + dev["ops"][-1][2]
    red = t.reduce(trace, lo, hi)
    assert red["chips"] == 1
    assert red["span_s"] == pytest.approx((hi - lo) * 1e-9)
    # worked by hand from the recorded events: 304 busy intervals, 303 gaps
    # of 1 to 3 ns between operations, nothing else idle
    assert len(t.merged(dev["ops"])) == 304
    assert red["busy_s"] == pytest.approx(0.014537456)
    assert red["span_s"] - red["busy_s"] == pytest.approx(462e-9, abs=1e-12)
    assert set(red["idle"]) == {t.BETWEEN_OPS}
    kinds = red["kinds"]
    assert kinds["attention custom-call:tpu_custom_call"][0] == 3
    assert "jit_step_fn" in red["modules"]
    assert t.window_of(trace) == (44296086, 44296086 + 8003831493)
