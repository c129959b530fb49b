"""Seconds of `Trainer.__init__` from its entry to its return (span
`polyaxon.train.build`, written after the fact): the model, data, optimizer
and mesh, the shapes and shardings, the program's own `init_fn` and
`tx.init` traced, compiled or loaded and dispatched (their results are not
waited for), and the `jax.jit` wrappers of the step.

The program's gauge `train.startup.build_seconds` (cellbench/startup_gauges.py);
None where the program sets none."""

from cellbench import startup_gauges


def read(obs):
    return startup_gauges.gauge("train.startup.build_seconds")
