"""These tests run by hand (`pytest cellbench/tests`), on the CPU, and are
not part of tier-1."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import argparse  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def make_ctx():
    """A run's context at the cell's tiny `rehearse` size, for a cell that
    is listed in BENCHMARK.json or only has its files here."""
    from cellbench.common import Ctx, load_cell

    def make(name, seed, seconds=0.3):
        bench, entry, cell, config = load_cell(name, rehearse=True)
        args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse=True,
                                  t_process=time.perf_counter())
        ctx = Ctx(args, bench, entry, cell, config)
        ctx.tag = "[test platform=cpu]"
        return ctx

    return make
