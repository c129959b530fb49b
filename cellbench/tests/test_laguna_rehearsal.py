"""The Laguna cell end to end at its `rehearse` size on the CPU, through the
benchmark's own entry point: `correct` true, every metric withheld."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "laguna-s-2.1-ep8.lora-train"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_withholds_its_metrics(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path), TMPDIR=str(tmp_path),
               BENCH_RUN="ignored")
    done = subprocess.run(
        [sys.executable, "cellbench/run.py", "--workload", CELL, "--rehearse",
         "--seed", str(2**31 + 29), "--seconds", "1", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"], line["compared"]
    assert line["rehearsal"] and line["metrics"] == {} and line["failed"] == 0
    assert set(line["compared"]) == {"grad1_direction", "grad1_worst_leaf", "change_worst_leaf"}
    assert ("breakdown" in line) == bool(trace)
