"""Seconds of set-up before the Trainer exists: the process's age at the
entry of `Trainer.__init__` (`/proc/uptime` less the process's start, in
steps of 10 ms). In the benchmark that is the interpreter, the imports,
JAX's start of the TPU runtime and the look for the chip.

The program's gauge `train.startup.before_trainer_seconds` (cellbench/startup_gauges.py);
None where the program sets none."""

from cellbench import startup_gauges


def read(obs):
    return startup_gauges.gauge("train.startup.before_trainer_seconds")
