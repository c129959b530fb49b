"""The driver-facing evidence scripts must emit parseable output: bench.py
one JSON line with the contract fields, decode/attention benches one JSON
object per config. They run here on the CPU because the CPU is asked for by
name (`POLYAXON_JAX_PLATFORM=cpu`); bench.py refuses a CPU it fell back to."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.slow  # each drives a real (small) training loop


def _run(script, env_extra, timeout=420):
    import os

    env = dict(
        os.environ,
        POLYAXON_JAX_PLATFORM="cpu",
        POLYAXON_NUM_CPU_DEVICES="1",
        **env_extra,
    )
    return subprocess.run(
        [sys.executable, str(REPO / script)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_bench_emits_contract_line(tmp_home):
    proc = _run("bench.py", {"POLYAXON_BENCH_TIMEOUT": "360"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip().startswith("{")]
    assert lines, proc.stdout
    rec = json.loads(lines[-1])
    assert rec["metric"] == "transformer_tokens_per_sec"
    assert rec["unit"] == "tok/s"
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0
    assert "device_kind" in rec and "bare_tokens_per_sec" in rec


def test_bench_refuses_a_cpu_nobody_asked_for(tmp_home):
    """No accelerator and no `POLYAXON_JAX_PLATFORM=cpu`: non-zero exit and
    no record — a CPU number must not appear under a device metric's name."""
    import os

    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "POLYAXON_JAX_PLATFORM")
    }
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        env=dict(env, POLYAXON_BENCH_TIMEOUT="120"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert not [l for l in proc.stdout.splitlines() if l.strip().startswith("{")]
    assert "refusing to report a CPU number" in proc.stderr


def test_decode_bench_emits_json(tmp_home):
    proc = _run("benchmarks/decode_bench.py", {})
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [
        json.loads(l)
        for l in proc.stdout.splitlines()
        if l.strip().startswith("{")
    ]
    metrics = {r["metric"] for r in recs}
    assert "decode_tokens_per_sec" in metrics
    assert "beam4_decode_tokens_per_sec" in metrics
    for r in recs:
        assert "error" not in r, r
        assert r["value"] > 0, r
        assert r["platform"] in ("cpu", "tpu")
    decode = [r for r in recs if r["metric"] == "decode_tokens_per_sec"]
    # the sweep must characterize the grouped cache: at least one GQA row
    # (kv < q heads) and one extended-cache row, each pricing its cache
    for r in decode:
        assert {"n_kv_heads", "cache_len", "kv_cache_bytes"} <= r.keys(), r
        assert r["kv_cache_bytes"] > 0
    assert any(r["n_kv_heads"] < r["n_heads"] for r in decode)
    base_len = decode[0]["cache_len"]
    assert any(r["cache_len"] > base_len for r in decode)
    # grouping shrinks the cache: bytes scale with n_kv_heads at equal len
    by_len = [r for r in decode if r["cache_len"] == base_len]
    mha = max(by_len, key=lambda r: r["n_kv_heads"])
    gqa = min(by_len, key=lambda r: r["n_kv_heads"])
    assert gqa["kv_cache_bytes"] * mha["n_kv_heads"] == pytest.approx(
        mha["kv_cache_bytes"] * gqa["n_kv_heads"]
    )


def test_attention_bench_emits_json(tmp_home):
    proc = _run("benchmarks/attention_bench.py", {})
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [
        json.loads(l)
        for l in proc.stdout.splitlines()
        if l.strip().startswith("{")
    ]
    assert recs
    for r in recs:
        assert "error" not in r, r
        assert {"seq", "backend", "mode", "kv_heads", "platform"} <= r.keys(), r
        assert r["tokens_per_sec"] > 0


def test_update_baseline_md_sections_merge_and_skip(tmp_path, monkeypatch):
    """The BASELINE.md updater is consumed UNATTENDED by the TPU canary:
    pin its contract — device sections are isolated, rows merge by config
    across partial runs, errored rows never become evidence."""
    import benchmarks.run_baselines as rb

    md = tmp_path / "BASELINE.md"
    md.write_text("# header\n")
    monkeypatch.setattr(rb, "REPO", tmp_path)

    def row(config, value, device, error=None):
        r = {"config": config, "value": value, "unit": "tok/s", "mfu": None,
             "device_kind": device, "final_loss": 1.0}
        if error:
            r["error"] = error
        return r

    # a TPU run writes the tpu section only
    rb.update_baseline_md([row("bert", 100.0, "TPU v5 lite")])
    text = md.read_text()
    assert "TPU-measured" in text and "| bert | 100.0 |" in text
    assert "CPU smoke" not in text

    # a CPU run adds its own section without touching the TPU rows
    rb.update_baseline_md([row("bert", 5.0, "cpu"), row("mnist", 9.0, "cpu")])
    text = md.read_text()
    assert "| bert | 100.0 |" in text  # TPU row preserved
    assert "| bert | 5.0 |" in text and "| mnist | 9.0 |" in text

    # partial re-run merges by config; errored rows are skipped
    rb.update_baseline_md([
        row("mnist", 11.0, "cpu"),
        row("bert", 0.0, "cpu", error="OOM"),
    ])
    text = md.read_text()
    assert "| mnist | 11.0 |" in text  # updated
    assert "| bert | 5.0 |" in text  # untouched by the errored row
    assert "| bert | 0.0 |" not in text
    assert "| bert | 100.0 |" in text  # TPU section still intact
