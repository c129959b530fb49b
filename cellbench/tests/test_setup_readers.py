"""The five set-up readers (ISSUE 38): each gives its gauge of the program's
process-global registry, None where the program has none (a parent without
them), `setup_refused_compile_s.train` 0.0 where a ladder refused no rung;
and the rehearsal of one cell with `--trace 1` takes every reader through a
real Trainer's gauges without raising."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from cellbench.common import HERE, load_json, load_module

ROOT = Path(__file__).resolve().parents[2]
READERS = {
    "setup_before_trainer_s.train": "train.startup.before_trainer_seconds",
    "setup_trainer_build_s.train": "train.startup.build_seconds",
    "setup_step_lower_s.train": "train.startup.step_lower_seconds",
    "setup_step_compile_s.train": "train.startup.step_compile_seconds",
    "setup_refused_compile_s.train": "train.startup.refused_compile_seconds",
}


def reader(metric):
    return load_module(
        HERE / "layer_metrics" / f"{metric}.py", "test_reader_" + metric.replace(".", "_")
    )


@pytest.fixture
def registry(monkeypatch):
    """A process-global registry of the test's own."""
    import polyaxon_tpu.telemetry as telemetry

    fresh = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "get_registry", lambda: fresh)
    return fresh


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_gives_its_gauge_and_none_without_it(metric, registry):
    read = reader(metric).read
    assert read({}) is None  # the parent commit: no such gauge
    registry.gauge(READERS[metric])  # registered, never set
    assert read({}) is None
    for name in READERS.values():  # each reads its own, not a neighbour's
        registry.gauge(name).set(1.0)
    registry.gauge(READERS[metric]).set(41.625)
    assert read({}) == 41.625


def test_no_refusal_reads_zero(registry):
    """43 -> 0 in the ledger, not 43 -> null, for the PR that stops paying it."""
    read = reader("setup_refused_compile_s.train").read
    registry.gauge(READERS["setup_refused_compile_s.train"]).set(0.0)
    value = read({})
    assert value == 0.0 and value is not None


def test_the_metrics_are_listed_for_the_cells_that_have_them():
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(listed) == set(READERS)
    for name, m in listed.items():
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            "s", "lower", "program_counter", "set-up", "setup_s")
        # a refused rung: only the two cells that run rung `block`
        want = [c for c in cells if c.startswith(("granite", "ling"))] if "refused" in name else cells
        assert m["workloads"] == want


def test_rehearsal_takes_every_reader_through_a_real_trainer(tmp_path):
    """`--rehearse --trace 1` of one cell, then the readers in a process
    that ran the cell's Trainer: the four listed for the cell give a value
    (the refused one is listed for the Granite and Ling cells only), none
    raises."""
    cell = "internlm2-1.8b.lora-train-2k"
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path), TMPDIR=str(tmp_path))
    done = subprocess.run(
        [sys.executable, "cellbench/run.py", "--workload", cell, "--rehearse",
         "--seed", str(2**31 + 37), "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["metrics"] == {}
    withheld = next(ln for ln in done.stdout.splitlines() if "metrics withheld" in ln)
    for metric in READERS:
        assert (metric in withheld) == ("refused" not in metric), withheld


def test_readers_after_a_trainer_of_this_process(make_ctx):
    import jax

    from cellbench.drivers import train as drv
    from polyaxon_tpu.telemetry import process_age

    ctx = make_ctx("internlm2-1.8b.lora-train-2k", 2**31 + 38)
    res = drv.run(ctx, jax.devices()[:1])
    assert res["correct"]
    values = {m: reader(m).read(res["observations"]) for m in READERS}
    assert values.pop("setup_refused_compile_s.train") == 0.0
    if process_age() is None:  # off Linux the process's age is not told
        assert values.pop("setup_before_trainer_s.train") is None
    assert all(v is not None and v > 0 for v in values.values()), values
