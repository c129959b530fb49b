"""Seconds of `.compile()` of the rung that runs (`polyaxon.train.first_step`
> `rung` > `compile`): the device's compiler, or the load of its executable
from the persistent cache (the span's `cache` attribute and `xla.cache_hits`
/ `xla.cache_misses` say which).

The program's gauge `train.startup.step_compile_seconds` (cellbench/startup_gauges.py);
None where the program sets none."""

from cellbench import startup_gauges


def read(obs):
    return startup_gauges.gauge("train.startup.step_compile_seconds")
