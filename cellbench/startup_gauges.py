"""What the five set-up readers share (`layer_metrics/setup_*.train.py`).

The program tells its own time to the first step (ISSUE 38): when the
executable of the training step exists, the Trainer sets the
`train.startup.*` gauges of its PROCESS-GLOBAL registry, each a difference
of clock reads taken on calls set-up makes anyway
(`Trainer._report_startup`). A reader runs in the Trainer's process after
the Trainer was closed and takes the gauge from there; `obs` is the
driver's and holds none of this.

None where the program has no such gauge (a parent without them) or never
set it: the metric is then left out of the line.
"""


def gauge(name: str):
    try:
        from polyaxon_tpu.telemetry import get_registry
    except ImportError:
        return None
    return get_registry().snapshot().get(name)
