"""Process-level fault injection (failpoint style).

Instrumented sites in the runtime call `inject("<point>", **ctx)` — a
module-global None check when no plan is armed, so production runs pay one
attribute load per step. Arming a `FaultPlan` (typically via the `active`
context manager in tests) makes those sites fire the plan's faults:

    trainer.step      ctx: step           — each training-loop iteration
    checkpoint.save   ctx: step, directory, manager — after a save is queued

Actions are deliberately *real*: "sigterm" sends an actual SIGTERM to this
process (exercising the preemption handler end-to-end), "corrupt_checkpoint"
scrambles the bytes orbax just wrote. Only "kill" is simulated — a raised
`SimulatedKill` stands in for SIGKILL, which no in-process harness can
survive to observe.
"""

from __future__ import annotations

import contextlib
import os
import signal
import time
from pathlib import Path
from typing import Optional

from ..retry import PermanentError, TransientError
from .plan import Fault, FaultPlan


class ChaosError(TransientError):
    """Generic injected transient fault."""


class SimulatedKill(TransientError):
    """Stand-in for an abrupt process death (SIGKILL / node loss) mid-step:
    no cleanup ran, no checkpoint was flushed — recovery must come entirely
    from previously persisted state."""


_active: Optional[FaultPlan] = None


def arm(plan: FaultPlan) -> None:
    global _active
    _active = plan


def disarm() -> None:
    global _active
    _active = None


@contextlib.contextmanager
def active(plan: FaultPlan):
    arm(plan)
    try:
        yield plan
    finally:
        disarm()


def inject(point: str, **ctx) -> None:
    """Fault-injection site. No-op unless a plan is armed."""
    plan = _active
    if plan is None:
        return
    fault = plan.fire(point, **ctx)
    if fault is not None:
        # record BEFORE performing: several actions raise/kill, and an
        # injection that took the process down must still be visible
        from ..telemetry import get_registry

        get_registry().counter(
            "chaos.injections", help="Chaos faults actually fired"
        ).inc()
        _perform(fault, point, ctx)


def _perform(fault: Fault, point: str, ctx: dict) -> None:
    if fault.action == "raise":
        raise ChaosError(f"{fault.message} [{point} {ctx.get('step', '')}]")
    if fault.action == "raise_permanent":
        raise PermanentError(f"{fault.message} [{point}]")
    if fault.action == "kill":
        raise SimulatedKill(fault.message)
    if fault.action == "sigterm":
        os.kill(os.getpid(), signal.SIGTERM)
        return
    if fault.action == "sleep":
        # brownout: stall the instrumented site (decode latency injection)
        time.sleep(max(0.0, fault.delay_ms) / 1e3)
        return
    if fault.action == "corrupt_checkpoint":
        mgr = ctx.get("manager")
        if mgr is not None:
            # the save is async — corrupting before the bytes land would
            # race the writer and corrupt nothing (or worse, get repaired)
            mgr.wait_until_finished()
        corrupt_checkpoint(ctx["directory"], step=ctx.get("step"))
        return
    if fault.action == "scramble_tail":
        # a crash mid-append as the DISK sees it: some garbage bytes made
        # it into the segment, then the process died. Recovery must
        # truncate exactly back to the last whole frame.
        scramble_tail(ctx["path"], _active.rng("scramble_tail"))
        raise SimulatedKill(fault.message)
    if fault.action == "corrupt_segment":
        # bit rot inside an already-committed frame (no crash): later
        # recovery must QUARANTINE the segment, never wedge a reader
        corrupt_segment_frame(ctx["path"])
        return
    raise ValueError(f"unknown chaos action {fault.action!r}")


def scramble_tail(path: str, rng) -> int:
    """Append 5-40 seeded garbage bytes to a log segment — the torn tail a
    power cut leaves. Returns the number of bytes appended."""
    n = rng.randrange(5, 40)
    garbage = bytes(rng.randrange(256) for _ in range(n))
    with open(path, "ab") as f:
        f.write(garbage)
    return n


def corrupt_segment_frame(path: str) -> None:
    """Flip one payload byte of the FIRST frame in a framed segment (CRC
    now mismatches with valid data after it → the 'corrupt' verdict, not
    'torn'). No-op on segments without a whole first frame."""
    import struct

    header = struct.Struct("<II")
    p = Path(path)
    try:
        data = bytearray(p.read_bytes())
    except OSError:
        return
    if len(data) < header.size:
        return
    length, _ = header.unpack_from(data, 0)
    if length <= 0 or header.size + length > len(data):
        return
    data[header.size] ^= 0xFF
    p.write_bytes(bytes(data))


def corrupt_checkpoint(directory: str, step: Optional[int] = None) -> int:
    """Overwrite every file of one checkpoint step with garbage bytes
    (the newest step when `step` is None). Returns the corrupted step.
    Directory layout is orbax's: <directory>/<step>/..."""
    root = Path(directory)
    steps = sorted(
        (int(p.name) for p in root.iterdir() if p.is_dir() and p.name.isdigit()),
        reverse=True,
    )
    if not steps:
        raise FileNotFoundError(f"no checkpoint steps under {directory}")
    target = int(step) if step is not None else steps[0]
    if target not in steps:
        raise FileNotFoundError(f"no checkpoint step {target} under {directory}")
    n = 0
    for f in sorted((root / str(target)).rglob("*")):
        if f.is_file():
            f.write_bytes(b"chaos: corrupted checkpoint bytes")
            n += 1
    if n == 0:
        raise FileNotFoundError(f"checkpoint step {target} has no files")
    return target
