"""Seconds every rung the first step tried took to trace to a jaxpr and
lower to StableHLO (`polyaxon.train.first_step` > `rung` > `lower`): paid
once a rung with or without a compile cache.

The program's gauge `train.startup.step_lower_seconds` (cellbench/startup_gauges.py);
None where the program sets none."""

from cellbench import startup_gauges


def read(obs):
    return startup_gauges.gauge("train.startup.step_lower_seconds")
