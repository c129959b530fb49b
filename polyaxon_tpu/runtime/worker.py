"""Distributed JAXJob worker: one process of the gang.

Launched by the native supervisor (native/launcher.cpp), which injects the
rendezvous env (JAX_COORDINATOR_ADDRESS / JAX_PROCESS_ID /
JAX_NUM_PROCESSES) — the TPU-native replacement for the reference's
TF_CONFIG / MASTER_ADDR wiring (SURVEY.md §5 comm backend). Every process
runs the same program (SPMD); jax.distributed.initialize makes all hosts'
devices one global mesh, and XLA routes collectives over ICI/DCN.

Process 0 is the only writer: metrics/logs/summary go to the run store the
coordinator shares with the supervisor.
"""

from __future__ import annotations

import json
import os
import sys


def main() -> int:
    coord = os.environ["JAX_COORDINATOR_ADDRESS"]
    process_id = int(os.environ["JAX_PROCESS_ID"])
    num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    spec_path = os.environ["POLYAXON_PROGRAM_SPEC"]

    import jax

    # Platform selection for a FRESH interpreter: without this every local
    # gang worker grabs the one real TPU chip and deadlocks in rendezvous.
    # The executor injects these for local gangs; the k8s converter leaves
    # them unset on real TPU pods.
    from ..utils.jax_platform import (
        apply_platform_env,
        device_memory,
        enable_cpu_collectives,
    )

    platform = apply_platform_env()

    # SIGTERM = preemption notice (spot reclaim, node drain): flag it so the
    # training loop can checkpoint-and-exit instead of dying mid-write.
    from . import preemption

    preemption.install()

    if num_processes > 1:
        if platform == "cpu":
            enable_cpu_collectives()  # gloo: XLA:CPU has no native ones
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=num_processes,
            process_id=process_id,
        )

    # fail fast, before the queue slot is spent: a half-alive slice must
    # surface here, not as a hang inside the first training collective
    from .health import check_slice

    health = check_slice()

    with open(spec_path) as f:
        payload = json.load(f)

    from ..retry import Preempted
    from ..schemas.run_kinds import V1Program
    from .trainer import Trainer

    program = V1Program.model_validate(payload["program"])
    run_uuid = payload["runUuid"]
    is_chief = process_id == 0

    store = None
    log_fn = None
    if is_chief:
        from ..store.local import RunStore

        store = RunStore()
        store.log_event(run_uuid, "slice_health", health)

        def log_fn(step: int, metrics: dict):
            store.log_metrics(run_uuid, step, metrics)
            line = f"step {step}: " + " ".join(
                f"{k}={v:.6g}" for k, v in metrics.items()
            )
            store.append_log(run_uuid, line)

    event_fn = None
    if is_chief and store is not None:
        def event_fn(kind: str, body: dict):
            store.log_event(run_uuid, kind, body)

    trainer = Trainer(
        program,
        mesh_axes=payload.get("mesh"),
        slices=int(payload.get("slices") or 1),
        log_fn=log_fn,
        event_fn=event_fn,
        # all processes participate in (multi-host) checkpointing
        checkpoint_dir=payload.get("checkpointDir"),
    )
    try:
        result = trainer.run()
    except Preempted as e:
        # clean preemption exit: checkpoint already flushed by the trainer.
        # 75 (EX_TEMPFAIL) tells the launcher/executor "restart me warm" —
        # distinguishable from a real crash, so no retry budget is burned.
        if is_chief and store is not None:
            store.log_event(
                run_uuid,
                "worker_preempted",
                {"process_id": process_id, "step": e.step},
            )
        return 75
    finally:
        trainer.close()
    if is_chief and store is not None:
        store.log_event(
            run_uuid,
            "run_summary",
            {
                "steps_per_sec": result.steps_per_sec,
                "final_metrics": result.final_metrics,
                "num_processes": num_processes,
                "device_memory": device_memory(trainer.mesh.local_devices),
            },
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
