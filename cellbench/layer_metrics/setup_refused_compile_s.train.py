"""Seconds of `.compile()` of the rungs the device's compiler refused
(`RESOURCE_EXHAUSTED`) before the one that runs: what a Trainer that
remembered the rung would not pay. 0.0 where a ladder was tried and no rung
refused, so the PR that stops paying it reads 43 -> 0 and not 43 -> null.

The program's gauge `train.startup.refused_compile_seconds` (cellbench/startup_gauges.py);
None where the program sets none."""

from cellbench import startup_gauges


def read(obs):
    return startup_gauges.gauge("train.startup.refused_compile_seconds")
