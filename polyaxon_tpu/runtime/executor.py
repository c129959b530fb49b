"""Local executor: CompiledOperation → a run in the store, executed.

This is the in-process execution path (SURVEY.md §7 step 2) — the analogue
of stack (a) in §3 with the control plane collapsed to the local store:
create run → status transitions (compiled→…→running→succeeded/failed) →
execute (native program via runtime/trainer.py, or a container command as a
local subprocess) → metrics/logs into the store.

The same Executor is reused by the scheduler's worker and by the tuner for
child trials; only the process placement differs.
"""

from __future__ import annotations

import io
import os
import subprocess
import sys
import time
import traceback
from pathlib import Path
from typing import Optional

from ..compiler.resolver import CompiledOperation
from ..schemas.lifecycle import V1Statuses
from ..store.local import RunStore


class ExecutionError(Exception):
    pass


class StopRequested(Exception):
    """Raised inside the run body when a stop arrived (remote POST /stop or
    `polyaxon ops stop`) — observed at log points, the executor's
    cooperative cancellation boundary."""


class Executor:
    def __init__(
        self,
        store: Optional[RunStore] = None,
        devices: Optional[list] = None,
        catalog=None,
    ):
        from ..connections.schemas import ConnectionCatalog

        self.store = store or RunStore()
        self.devices = devices
        self.catalog = catalog if catalog is not None else ConnectionCatalog()

    def execute(self, compiled: CompiledOperation) -> str:
        """Run to completion; returns final status. Retries per termination
        spec (maxRetries) — restart-from-checkpoint comes free because the
        trainer resumes from the run's outputs dir. With `cache:` enabled, a
        prior succeeded run with the same spec fingerprint short-circuits:
        its metrics/events are linked in and the run succeeds immediately."""
        from ..compiler.resolver import spec_fingerprint

        store = self.store
        run_uuid = compiled.run_uuid
        fingerprint = spec_fingerprint(compiled)
        store.create_run(
            run_uuid,
            compiled.name,
            compiled.project,
            compiled.to_dict(),
            tags=compiled.operation.tags,
            meta={"fingerprint": fingerprint},
        )
        cache = compiled.operation.cache or compiled.component.cache
        if cache is not None and not cache.disable:
            hit = self._find_cached(fingerprint, cache.ttl, exclude=run_uuid)
            if hit is not None:
                return self._finish_from_cache(compiled, hit)
        # advance through the pre-run lifecycle; skip stages already passed
        # (agent-submitted runs arrive here in QUEUED, direct runs in CREATED)
        from ..schemas.lifecycle import can_transition

        for s in (V1Statuses.COMPILED, V1Statuses.QUEUED, V1Statuses.SCHEDULED):
            current = V1Statuses(store.get_status(run_uuid)["status"])
            # strict inequality: don't append duplicate conditions for the
            # stage an agent-submitted run is already in
            if current != s and can_transition(current, s):
                store.set_status(run_uuid, s)

        from ..retry import PERMANENT, PREEMPTED, RetryPolicy, classify

        term = compiled.component.termination
        policy = RetryPolicy.from_termination(term)
        max_retries = policy.max_retries
        timeout = term.timeout if term else None

        attempt = 0  # budgeted retries consumed (transient failures)
        # all restarts, including free preemption restarts. Seeded from
        # meta: a run evicted by the scheduler (checkpoint-and-requeue)
        # arrives back here as a fresh execute() call — preempt_restarts
        # carries the count across, so resume=restarts>0 restores the
        # checkpoint instead of restarting from step 0.
        restarts = int(
            (store.get_status(run_uuid).get("meta") or {}).get(
                "preempt_restarts", 0
            )
        )
        while True:
            if self._stopped(run_uuid):  # stop landed between attempts
                return V1Statuses.STOPPED
            store.set_status(run_uuid, V1Statuses.STARTING)
            try:
                self._run_once(compiled, timeout=timeout, resume=restarts > 0)
                if self._stopped(run_uuid):  # stop raced the finish line
                    return V1Statuses.STOPPED
                store.set_status(run_uuid, V1Statuses.SUCCEEDED)
                self._run_hooks(compiled, V1Statuses.SUCCEEDED)
                return V1Statuses.SUCCEEDED
            except BaseException as e:  # noqa: BLE001 — record, then decide
                store.append_log(run_uuid, f"ERROR: {e}\n{traceback.format_exc()}")
                if isinstance(e, StopRequested):
                    self._stopped(run_uuid)  # settles STOPPING → STOPPED
                    return V1Statuses.STOPPED
                if self._stopped(run_uuid):
                    return V1Statuses.STOPPED
                if isinstance(e, KeyboardInterrupt):
                    store.request_stop(run_uuid)
                    raise
                from ..telemetry import get_registry

                kind = classify(e)
                if kind == PREEMPTED:
                    # scheduler eviction rides the same machinery as machine
                    # preemption (flag → boundary checkpoint → Preempted),
                    # but the chips are wanted by someone else: yield them
                    # and go back to the queue instead of restarting here.
                    meta = store.get_status(run_uuid).get("meta") or {}
                    if meta.get("preempt_requested"):
                        return self._requeue_preempted(compiled, e, restarts)
                    # the program was healthy; the machine went away. Restart
                    # from checkpoint WITHOUT burning the retry budget.
                    restarts += 1
                    get_registry().counter(
                        "runs.preemptions",
                        help="Budget-free preemption restarts",
                    ).inc()
                    store.log_event(
                        run_uuid,
                        "preempted",
                        {
                            "step": getattr(e, "step", None),
                            "restart": restarts,
                        },
                    )
                    store.set_status(
                        run_uuid, V1Statuses.RETRYING, reason="preempted",
                        message=str(e),
                    )
                    store.set_status(run_uuid, V1Statuses.QUEUED)
                    store.set_status(run_uuid, V1Statuses.SCHEDULED)
                    continue
                if kind != PERMANENT and attempt < max_retries:
                    delay = policy.delay(attempt, seed=run_uuid)
                    attempt += 1
                    restarts += 1
                    get_registry().counter(
                        "runs.retries", help="Budgeted transient-failure retries"
                    ).inc()
                    store.log_event(
                        run_uuid,
                        "retry",
                        {"attempt": attempt, "delay": delay, "error": str(e)},
                    )
                    store.set_status(
                        run_uuid,
                        V1Statuses.RETRYING,
                        reason=f"retry {attempt}/{max_retries}"
                        + (f" after {delay:.3g}s" if delay > 0 else ""),
                        message=str(e),
                    )
                    store.set_status(run_uuid, V1Statuses.QUEUED)
                    store.set_status(run_uuid, V1Statuses.SCHEDULED)
                    if delay > 0:
                        time.sleep(delay)
                    continue
                store.set_status(
                    run_uuid, V1Statuses.FAILED, reason=type(e).__name__, message=str(e)
                )
                self._run_hooks(compiled, V1Statuses.FAILED)
                return V1Statuses.FAILED

    def _requeue_preempted(
        self, compiled: CompiledOperation, exc: BaseException, restarts: int
    ) -> str:
        """Scheduler-initiated eviction: the admission controller flagged
        this run to yield its chips to a higher-priority gang, the trainer
        flushed a checkpoint at the step boundary and raised Preempted.
        Release the reservation, re-enqueue at the ORIGINAL priority, and
        let a later admission pass restart it (resume comes free because
        preempt_restarts makes the next execute() pass resume=True)."""
        store, run_uuid = self.store, compiled.run_uuid
        meta = store.get_status(run_uuid).get("meta") or {}
        store.set_meta(
            run_uuid, preempt_requested=False, preempt_restarts=restarts + 1
        )
        store.log_event(
            run_uuid,
            "preempted",
            {
                "step": getattr(exc, "step", None),
                "restart": restarts + 1,
                "scheduler": True,
                # the gang size this attempt actually ran at — the next
                # admission pass may grant a different rung of the ladder
                "granted_chips": meta.get("granted_chips"),
            },
        )
        store.set_status(
            run_uuid,
            V1Statuses.RETRYING,
            reason="evicted",
            message=str(exc),
        )
        store.set_status(run_uuid, V1Statuses.QUEUED)
        from ..scheduler.fleet import (
            Fleet,
            chips_demand,
            min_chips_demand,
            topology_request,
        )
        from ..scheduler.queue import RunQueue

        Fleet(store).release(run_uuid)  # chips go to the preemptor
        # re-stamp the FULL demand (not the shrunk grant): the next pass
        # tries the whole block first and walks the ladder down again
        op = compiled.operation
        block = topology_request(op)
        RunQueue(store, name=meta.get("queue") or "default").push(
            run_uuid,
            {
                "operation": op.to_dict(),
                "project": compiled.project,
            },
            priority=int(meta.get("priority", 0)),
            chips=chips_demand(op),
            min_chips=min_chips_demand(op),
            block=list(block) if block else None,
        )
        return V1Statuses.QUEUED

    def _apply_elastic_grant(self, compiled: CompiledOperation, program):
        """Resize the attempt to the gang the scheduler actually granted.

        Admission stamps `granted_chips` on the run meta when it places an
        elastic run on a rung below its full request. The trainer then
        builds its mesh over that many devices (restore reshards for free)
        and gradient accumulation scales by the shrink ratio so the global
        batch — and per-device microbatch footprint — hold constant.

        Returns (program, devices): untouched when the grant matches the
        request (or the run is not elastic)."""
        from ..scheduler.fleet import chips_demand, min_chips_demand

        store, run_uuid = self.store, compiled.run_uuid
        meta = store.get_status(run_uuid).get("meta") or {}
        granted = meta.get("granted_chips")
        if granted is None or min_chips_demand(compiled.operation) is None:
            return program, self.devices
        granted = int(granted)
        requested = chips_demand(compiled.operation)
        devices = self.devices
        if devices is None:
            import jax

            devices = list(jax.devices())
        if granted >= min(requested, len(devices)):
            return program, self.devices
        ratio = max(1, requested // granted)
        devices = list(devices)[:granted]
        tspec = program.train
        accum = int(tspec.grad_accum) if tspec and tspec.grad_accum else 1
        new_accum = accum * ratio
        if tspec is not None:
            program = program.model_copy(
                update={
                    "train": tspec.model_copy(
                        update={"grad_accum": new_accum}
                    )
                }
            )
        from ..telemetry import get_registry

        get_registry().counter(
            "trainer.elastic_resizes",
            help="Training attempts started at a resized gang",
        ).inc()
        store.log_event(
            run_uuid,
            "elastic_resize",
            {
                "granted": granted,
                "requested": requested,
                "grad_accum": new_accum,
            },
        )
        return program, devices

    def _stopped(self, run_uuid: str) -> bool:
        """True when a stop request landed; settles STOPPING → STOPPED."""
        current = self.store.get_status(run_uuid).get("status")
        if current == V1Statuses.STOPPING:
            self.store.set_status(run_uuid, V1Statuses.STOPPED)
            return True
        return current == V1Statuses.STOPPED

    # ------------------------------------------------------------------ hooks
    def _run_hooks(self, compiled: CompiledOperation, status: str) -> None:
        """Post-run hooks (SURVEY.md §2: notifier auxiliaries / op hooks).
        A pathRef hook compiles+executes that component as its own run with
        the parent's status injected; hook failures are logged, never
        propagated into the parent's status."""
        hooks = compiled.operation.hooks or []
        store, run_uuid = self.store, compiled.run_uuid
        for hook in hooks:
            trigger = hook.trigger or "done"
            fire = (
                trigger == "done"
                or (trigger == "succeeded" and status == V1Statuses.SUCCEEDED)
                or (trigger == "failed" and status == V1Statuses.FAILED)
            )
            if not fire:
                continue
            try:
                if hook.path_ref:
                    from ..compiler.resolver import compile_operation
                    from ..schemas.operation import V1Operation

                    params = dict(hook.params or {})
                    child = V1Operation.model_validate(
                        {
                            "name": f"{compiled.name}-hook",
                            "pathRef": hook.path_ref,
                            "params": {
                                **{k: v.to_dict() for k, v in params.items()},
                                # .value: str() on a str-Enum renders the
                                # member name, not the lifecycle value
                                "status": {"value": getattr(status, "value", str(status))},
                                "run_uuid": {"value": run_uuid},
                            },
                        }
                    )
                    hook_compiled = compile_operation(
                        child, project=compiled.project
                    )
                    store.append_log(
                        run_uuid,
                        f"hook {hook.path_ref}: run {hook_compiled.run_uuid[:8]}",
                    )
                    self.execute(hook_compiled)
                else:
                    # notifier hooks: deliver to the webhook connection when
                    # one is named; always record the notification event
                    delivered = None
                    if hook.connection:
                        from ..connections.notifier import (
                            NotificationError,
                            notify,
                        )

                        payload = {
                            "run_uuid": run_uuid,
                            "name": compiled.name,
                            "project": compiled.project,
                            "status": getattr(status, "value", str(status)),
                            "hook": hook.hub_ref or "notifier",
                        }
                        try:
                            notify(self.catalog.get(hook.connection), payload)
                            delivered = True
                        except (NotificationError, KeyError) as e:
                            delivered = False
                            store.append_log(
                                run_uuid,
                                f"notification to {hook.connection} failed: {e}",
                            )
                    store.log_event(
                        run_uuid,
                        "notification",
                        {
                            "hook": hook.hub_ref or "notifier",
                            "status": getattr(status, "value", str(status)),
                            "connection": hook.connection,
                            **({} if delivered is None else {"delivered": delivered}),
                        },
                    )
            except Exception as e:  # noqa: BLE001 — hooks never fail the run
                store.append_log(run_uuid, f"hook error ({hook.path_ref or hook.hub_ref}): {e}")

    # ------------------------------------------------------------------ cache
    def _find_cached(self, fingerprint: str, ttl, exclude: str):
        """Most recent succeeded run with the same fingerprint (within ttl)."""
        import time as _time

        best = None
        for rec in self.store.list_runs():
            uuid = rec["uuid"]
            if uuid == exclude:
                continue
            if ttl and rec.get("created_at", 0) < _time.time() - ttl:
                continue
            status = self.store.get_status(uuid)
            if status.get("status") != V1Statuses.SUCCEEDED:
                continue
            if status.get("meta", {}).get("fingerprint") != fingerprint:
                continue
            if best is None or rec.get("created_at", 0) > best[1]:
                best = (uuid, rec.get("created_at", 0))
        return best[0] if best else None

    def _finish_from_cache(self, compiled: CompiledOperation, source_uuid: str) -> str:
        """Link the cached run's results and succeed without executing."""
        import shutil

        from ..schemas.lifecycle import can_transition

        store, run_uuid = self.store, compiled.run_uuid
        for s in (
            V1Statuses.COMPILED,
            V1Statuses.QUEUED,
            V1Statuses.SCHEDULED,
            V1Statuses.STARTING,
            V1Statuses.RUNNING,
        ):
            current = V1Statuses(store.get_status(run_uuid)["status"])
            if current != s and can_transition(current, s):
                store.set_status(run_uuid, s)
        for fname in ("metrics.jsonl", "events.jsonl"):
            src = store.run_dir(source_uuid) / fname
            if src.exists():
                shutil.copy(src, store.run_dir(run_uuid) / fname)
        store.log_event(
            run_uuid, "cache_hit", {"source_run": source_uuid}
        )
        store.append_log(
            run_uuid, f"cache hit: reusing results of run {source_uuid[:8]}"
        )
        store.set_status(run_uuid, V1Statuses.SUCCEEDED, reason="cached")
        self._run_hooks(compiled, V1Statuses.SUCCEEDED)
        return V1Statuses.SUCCEEDED

    # ------------------------------------------------------------------
    def _run_once(self, compiled: CompiledOperation, timeout=None, resume=False):
        run = compiled.run
        run_uuid = compiled.run_uuid
        store = self.store
        # init semantics (SURVEY.md §3 stack (a): init container provisions
        # the context dir before the main work starts)
        if getattr(run, "init", None):
            self._run_init(compiled)
        sidecars = self._start_sidecars(compiled)
        body_exc: Optional[BaseException] = None
        try:
            if run.kind == "jaxjob" and run.program is not None:
                self._run_program(compiled, resume=resume)
            elif run.kind == "service" and run.container is not None:
                self._run_service(compiled, timeout=timeout)
            elif run.kind in ("job", "jaxjob") and run.container is not None:
                self._run_container(compiled, timeout=timeout)
            elif run.kind == "dag":
                from ..scheduler.dag import execute_dag

                store.set_status(run_uuid, V1Statuses.RUNNING)
                execute_dag(compiled, self)
            else:
                raise ExecutionError(f"cannot execute run kind {run.kind!r} locally")
        except BaseException as e:
            body_exc = e
            raise
        finally:
            # aux failures must never mask the run's real failure; when the
            # run itself succeeded, a failed outputs upload IS the failure
            # (results that never reached the store don't exist)
            try:
                self._stop_sidecars(compiled, sidecars)
            except Exception as e:  # noqa: BLE001
                store.append_log(run_uuid, f"sidecar teardown failed: {e}")
            try:
                # sidecar semantics: outputs sync to the run's artifact
                # store happens win or lose, like upstream's upload sidecar
                self._sync_outputs(compiled)
            except Exception as e:  # noqa: BLE001
                store.append_log(run_uuid, f"outputs sync failed: {e}")
                if body_exc is None:
                    raise ExecutionError(f"outputs sync failed: {e}") from e

    # ------------------------------------------------------------- init/aux
    def context_dir(self, run_uuid: str):
        d = self.store.run_dir(run_uuid) / "context"
        d.mkdir(parents=True, exist_ok=True)
        return d

    def _run_init(self, compiled: CompiledOperation):
        """Execute every V1Init entry into the run's context dir: git clone,
        artifact pull (connection store or another run's outputs), literal
        files, host paths, or a custom container. Init failure fails the
        run (same as an init-container crash on k8s)."""
        import shutil
        from pathlib import Path

        run, store, run_uuid = compiled.run, self.store, compiled.run_uuid
        ctx = self.context_dir(run_uuid)
        for i, init in enumerate(run.init or []):
            try:
                if init.git:
                    self._init_git(init, ctx, run_uuid)
                if init.artifacts:
                    self._init_artifacts(compiled, init, ctx)
                if init.file:
                    f = init.file
                    dst = ctx / str(f.get("name") or f.get("path") or "file")
                    dst.parent.mkdir(parents=True, exist_ok=True)
                    dst.write_text(str(f.get("content", "")))
                for p in init.paths or ():
                    src = Path(p)
                    dst = ctx / src.name
                    if src.is_dir():
                        shutil.copytree(src, dst, dirs_exist_ok=True)
                    elif src.is_file():
                        dst.parent.mkdir(parents=True, exist_ok=True)
                        shutil.copy2(src, dst)
                    else:
                        raise ExecutionError(f"init path not found: {p}")
                if init.container:
                    self._run_aux_container(
                        compiled, init.container, cwd=str(ctx), tag="init"
                    )
            except ExecutionError:
                raise
            except Exception as e:  # noqa: BLE001 — wrap with which entry failed
                raise ExecutionError(f"init[{i}] failed: {e}") from e
            store.append_log(run_uuid, f"init[{i}] done")

    def _init_git(self, init, ctx, run_uuid):
        git = init.git
        url = str(git.get("url", ""))
        dest = ctx / (git.get("dest") or url.rstrip("/").split("/")[-1].removesuffix(".git") or "repo")
        cmd = ["git", "clone", "--quiet", url, str(dest)]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise ExecutionError(f"git clone {url}: {proc.stderr.strip()}")
        if git.get("revision"):
            proc = subprocess.run(
                ["git", "-C", str(dest), "checkout", "--quiet", str(git["revision"])],
                capture_output=True,
                text=True,
            )
            if proc.returncode != 0:
                raise ExecutionError(
                    f"git checkout {git['revision']}: {proc.stderr.strip()}"
                )
        self.store.append_log(run_uuid, f"init: cloned {url} -> {dest.name}")

    def _init_artifacts(self, compiled, init, ctx):
        """Pull artifacts into the context: from a named connection's store
        (init.connection) or from another run's outputs ({'run': uuid})."""
        from ..connections.fs import build_artifact_store

        art = init.artifacts
        if art.get("run"):
            src_uuid = self.store.resolve(str(art["run"]))
            src = self.store.outputs_dir(src_uuid)
            import shutil

            names = list(art.get("files") or []) + list(art.get("dirs") or [])
            for name in names or [""]:
                s = src / name if name else src
                d = ctx / (name or src_uuid[:8])
                if s.is_dir():
                    shutil.copytree(s, d, dirs_exist_ok=True)
                elif s.is_file():
                    d.parent.mkdir(parents=True, exist_ok=True)
                    shutil.copy2(s, d)
                else:
                    raise ExecutionError(f"run {src_uuid[:8]} has no output {name!r}")
            return
        if not init.connection:
            raise ExecutionError("init.artifacts needs 'run' or a connection")
        astore = build_artifact_store(self.catalog.get(init.connection))
        for key in art.get("files") or ():
            astore.get(key, ctx / key)
        for prefix in art.get("dirs") or ():
            astore.get_tree(prefix, ctx / prefix)

    def _start_sidecars(self, compiled: CompiledOperation) -> list:
        """Custom sidecar containers run alongside the main work as local
        subprocesses; a drain thread streams each one's output into the run
        log live (an undrained pipe would block the sidecar after ~64KB).
        They are terminated when the run finishes."""
        import threading

        run = compiled.run
        procs = []
        for c in getattr(run, "sidecars", None) or []:
            cmd = list(c.command or []) + list(c.args or [])
            if not cmd:
                continue
            env = self._container_env(compiled, c)
            proc = subprocess.Popen(
                cmd,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                cwd=c.working_dir or None,
                env=env,
            )

            def _drain(p=proc):
                for line in iter(p.stdout.readline, ""):
                    self.store.append_log(
                        compiled.run_uuid, "[sidecar] " + line.rstrip("\n")
                    )

            t = threading.Thread(target=_drain, daemon=True)
            t.start()
            procs.append((proc, t))
        return procs

    def _stop_sidecars(self, compiled: CompiledOperation, procs: list):
        for proc, drain in procs:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()
            drain.join(timeout=5)

    def _sync_outputs(self, compiled: CompiledOperation):
        """Upload the run's outputs tree to its artifact-store connection
        (first artifact store named in run.connections). No connection → the
        local outputs dir IS the store; nothing to do."""
        run = compiled.run
        names = getattr(run, "connections", None) or []
        store, run_uuid = self.store, compiled.run_uuid
        for name in names:
            conn = self.catalog.get(name)  # unknown name = config error
            if not conn.is_artifact_store:
                continue
            from ..connections.fs import build_artifact_store

            astore = build_artifact_store(conn)
            prefix = f"{compiled.project}/{run_uuid}/outputs"
            keys = astore.put_tree(store.outputs_dir(run_uuid), prefix)
            store.log_event(
                run_uuid,
                "outputs_uploaded",
                {"connection": name, "prefix": prefix, "files": len(keys)},
            )
            store.append_log(
                run_uuid, f"sidecar: uploaded {len(keys)} outputs to {name}:{prefix}"
            )
            return

    def _container_env(self, compiled, c) -> dict[str, str]:
        """Process env for any container: inherited + run-context vars +
        the container's own env (dict or k8s list form)."""
        env = dict(os.environ)
        env.update(_context_env(compiled, self.store))
        if isinstance(c.env, dict):
            env.update({k: str(v) for k, v in c.env.items()})
        elif isinstance(c.env, list):
            env.update({e["name"]: str(e.get("value", "")) for e in c.env})
        return env

    def _run_aux_container(self, compiled, c, cwd: str, tag: str):
        cmd = list(c.command or []) + list(c.args or [])
        if not cmd:
            raise ExecutionError(f"{tag} container has no command")
        env = self._container_env(compiled, c)
        proc = subprocess.run(
            cmd, capture_output=True, text=True, cwd=c.working_dir or cwd, env=env
        )
        for line in (proc.stdout or "").splitlines():
            self.store.append_log(compiled.run_uuid, f"[{tag}] " + line)
        if proc.returncode != 0:
            raise ExecutionError(
                f"{tag} container exited with code {proc.returncode}: "
                f"{(proc.stderr or '').strip()[-500:]}"
            )

    def _run_program(self, compiled: CompiledOperation, resume: bool):
        from . import preemption
        from .trainer import Trainer

        # SIGTERM = preemption grace notice: the trainer loop observes the
        # flag at step boundaries and checkpoints before exiting. Clear any
        # stale flag from a previous attempt in this process.
        preemption.install()
        preemption.clear()

        run = compiled.run
        store, run_uuid = self.store, compiled.run_uuid
        mesh_axes = run.mesh.axis_sizes() if run.mesh else None
        from ..schemas.run_kinds import run_num_slices

        n_slices = run_num_slices(run)

        ckpt_dir = None
        local_ckpt_dir = None
        tspec = run.program.train
        if tspec and (tspec.checkpoint_every or tspec.resume):
            ckpt_dir = str(store.outputs_dir(run_uuid) / "checkpoints")
            if tspec.checkpoint_local_dir:
                # fast tier, scoped per run so two runs on one host never
                # share a step namespace
                local_ckpt_dir = str(
                    Path(tspec.checkpoint_local_dir) / run_uuid / "checkpoints"
                )
        program = run.program
        if resume and ckpt_dir is None:
            # retry without explicit checkpointing: restart from scratch
            pass
        if resume and tspec is not None:
            program = program.model_copy(
                update={"train": tspec.model_copy(update={"resume": True})}
            )
        program, devices = self._apply_elastic_grant(compiled, program)

        replicas = int(getattr(run, "replicas", 1) or 1)
        if replicas > 1:
            # resume/ckpt handling above is shared: workers receive the
            # already-resumed program and the same checkpoint dir
            return self._run_distributed(compiled, replicas, program, ckpt_dir)

        import jax

        from ..utils.jax_platform import require_declared_tpu

        # before the model is built: a TPU spec on a silent CPU fallback
        # fails in seconds, not after a 1B-parameter init
        require_declared_tpu(run, (devices or jax.devices())[0].platform)

        def log_fn(step: int, metrics: dict):
            store.log_metrics(run_uuid, step, metrics)
            line = f"step {step}: " + " ".join(
                f"{k}={v:.6g}" for k, v in metrics.items()
            )
            store.append_log(run_uuid, line)
            # log points are the cooperative cancellation boundary
            data = store.get_status(run_uuid)
            status = data.get("status")
            if status in (V1Statuses.STOPPING, V1Statuses.STOPPED):
                raise StopRequested(f"stop requested at step {step}")
            # scheduler eviction rides the SIGTERM machinery: raise the
            # preemption flag and the trainer checkpoints at the next step
            # boundary before raising Preempted
            if (data.get("meta") or {}).get("preempt_requested"):
                preemption.trigger()

        trainer = Trainer(
            program,
            mesh_axes=mesh_axes,
            devices=devices,
            slices=n_slices,
            log_fn=log_fn,
            event_fn=lambda kind, body: store.log_event(run_uuid, kind, body),
            checkpoint_dir=ckpt_dir,
            local_checkpoint_dir=local_ckpt_dir,
            artifacts_dir=str(store.outputs_dir(run_uuid)),
        )
        store.set_status(run_uuid, V1Statuses.RUNNING)
        # opt-in system sampling: an `observability:` section in the spec
        # starts the host/HBM monitor at its cadence for this run
        monitor = None
        obs = program.observability
        if obs is not None:
            from ..tracking.monitors import SystemMonitor

            monitor = SystemMonitor(
                store, run_uuid, interval=float(obs.sample_interval)
            ).start()
        try:
            result = trainer.run()
        finally:
            if monitor is not None:
                monitor.stop()
            trainer.close()
        from ..utils.jax_platform import device_memory

        store.log_event(
            run_uuid,
            "run_summary",
            {
                "steps_per_sec": result.steps_per_sec,
                "final_metrics": result.final_metrics,
                "device_memory": device_memory(trainer.mesh.local_devices),
            },
        )
        store.append_log(
            run_uuid,
            f"done: {result.steps_per_sec:.2f} steps/s, "
            f"final {result.final_metrics}",
        )

    def _run_distributed(
        self, compiled: CompiledOperation, replicas: int, program, ckpt_dir
    ):
        """Multi-process gang via the native C++ supervisor: each worker is
        a `runtime.worker` process; rendezvous env is injected by the
        launcher; gang semantics restart all-or-nothing. On real multi-host
        TPU the k8s converter schedules one such gang per host; locally the
        gang runs on this host (multi-process jax.distributed over CPU)."""
        import json as _json
        import tempfile

        from ..native import launcher_path, pick_port

        run = compiled.run
        store, run_uuid = self.store, compiled.run_uuid
        from ..schemas.run_kinds import run_num_slices

        payload = {
            "runUuid": run_uuid,
            "program": program.to_dict(),
            "mesh": run.mesh.axis_sizes() if run.mesh else None,
            "slices": run_num_slices(run),
        }
        if ckpt_dir is not None:
            payload["checkpointDir"] = ckpt_dir
        spec_file = tempfile.NamedTemporaryFile(
            "w", suffix=".json", delete=False
        )
        _json.dump(payload, spec_file)
        spec_file.close()
        term = compiled.component.termination
        # Local gangs are the CPU stand-in for a multi-host pod: N processes
        # on one host cannot share its chips, so workers go to virtual CPU
        # devices unless POLYAXON_JAX_PLATFORM says otherwise (worker.py
        # applies it through jax.config). On a real cluster, workers go
        # through the k8s converter, not this path.
        from ..utils.jax_platform import (
            env_n_cpu,
            env_platform,
            require_declared_tpu,
        )

        platform = env_platform() or "cpu"
        n_cpu = env_n_cpu()  # validated here: one clear error, not N worker crashes
        store.log_event(
            run_uuid,
            "gang_platform",
            {
                "platform": platform,
                "asked": env_platform() is not None,
                "num_cpu_devices": n_cpu,
                "replicas": replicas,
            },
        )
        require_declared_tpu(run, platform)
        cmd = [
            launcher_path(),
            "--num-workers", str(replicas),
            "--coordinator", f"127.0.0.1:{pick_port(run_uuid)}",
            "--max-restarts", "0",  # retries handled by execute()'s loop
            *(
                ["--timeout", str(int(term.timeout))]
                if term and term.timeout
                else []
            ),
            "--env", f"POLYAXON_PROGRAM_SPEC={spec_file.name}",
            "--env", f"POLYAXON_HOME={store.home}",
            "--env", f"POLYAXON_JAX_PLATFORM={platform}",
            "--env", f"POLYAXON_NUM_CPU_DEVICES={n_cpu}",
            "--", sys.executable, "-m", "polyaxon_tpu.runtime.worker",
        ]
        store.set_status(run_uuid, V1Statuses.RUNNING)
        try:
            proc = subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True
            )
            for line in iter(proc.stdout.readline, ""):
                store.append_log(run_uuid, "[launcher] " + line.rstrip("\n"))
            code = proc.wait()
        finally:
            os.unlink(spec_file.name)
        if code in (75, 143):
            # 75 = EX_TEMPFAIL: a worker caught SIGTERM, checkpointed, and
            # exited clean (worker.py); 143 = the launcher itself was
            # SIGTERMed. Either way the gang was preempted, not broken —
            # the retry loop restarts it without burning budget.
            from ..retry import Preempted

            raise Preempted(f"distributed gang preempted (exit code {code})")
        if code != 0:
            raise ExecutionError(f"distributed gang exited with code {code}")

    def _spawn_container(
        self, compiled: CompiledOperation, c, extra_env: Optional[dict] = None
    ) -> subprocess.Popen:
        """One launch recipe for main containers and services."""
        cmd = list(c.command or []) + list(c.args or [])
        if not cmd:
            raise ExecutionError("container has no command")
        env = self._container_env(compiled, c)
        if extra_env:
            env.update(extra_env)
        return subprocess.Popen(
            cmd,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=c.working_dir or None,
            env=env,
        )

    def _run_service(self, compiled: CompiledOperation, timeout=None):
        """Service semantics: the process is SUPPOSED to stay up. RUNNING
        until a stop request lands (then terminated → STOPPED) or the
        optional timeout expires; a service that exits by itself is a
        FAILURE (0 or not — services don't 'finish'). Ports and run
        identity are injected via env (POLYAXON_SERVICE_PORT[S])."""
        import time as _time

        run = compiled.run
        store, run_uuid = self.store, compiled.run_uuid
        ports = [int(p) for p in (getattr(run, "ports", None) or [])]
        extra_env = {}
        if ports:
            extra_env["POLYAXON_SERVICE_PORT"] = str(ports[0])
            extra_env["POLYAXON_SERVICE_PORTS"] = ",".join(str(p) for p in ports)
        store.set_status(run_uuid, V1Statuses.RUNNING)
        store.log_event(run_uuid, "service_started", {"ports": ports})
        proc = self._spawn_container(compiled, run.container, extra_env)
        import threading

        def _drain():
            for line in iter(proc.stdout.readline, ""):
                store.append_log(run_uuid, line.rstrip("\n"))

        drain = threading.Thread(target=_drain, daemon=True)
        drain.start()
        deadline = _time.time() + timeout if timeout else None
        try:
            while proc.poll() is None:
                status = store.get_status(run_uuid).get("status")
                if status in (V1Statuses.STOPPING, V1Statuses.STOPPED):
                    raise StopRequested("service stop requested")
                if deadline and _time.time() > deadline:
                    raise ExecutionError(f"service exceeded timeout of {timeout}s")
                _time.sleep(0.5)
        finally:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()
            drain.join(timeout=5)
        raise ExecutionError(
            f"service exited unexpectedly with code {proc.returncode}"
        )

    def _run_container(self, compiled: CompiledOperation, timeout=None):
        """Local-subprocess stand-in for the k8s pod path: runs the container
        command on this host (image is ignored locally; the k8s converter in
        scheduler/converter.py is the cluster path)."""
        run = compiled.run
        store, run_uuid = self.store, compiled.run_uuid
        store.set_status(run_uuid, V1Statuses.RUNNING)
        proc = self._spawn_container(compiled, run.container)
        deadline = time.time() + timeout if timeout else None
        for line in iter(proc.stdout.readline, ""):
            store.append_log(run_uuid, line.rstrip("\n"))
            if deadline and time.time() > deadline:
                proc.kill()
                raise ExecutionError(f"run exceeded timeout of {timeout}s")
        code = proc.wait()
        if code != 0:
            raise ExecutionError(f"container command exited with code {code}")


def _context_env(compiled: CompiledOperation, store: RunStore) -> dict[str, str]:
    """Env the reference's converter injects into pods (run identity + paths),
    which the tracking client (tracking/run.py) reads to auto-attach."""
    return {
        "POLYAXON_RUN_UUID": compiled.run_uuid,
        "POLYAXON_RUN_NAME": compiled.name,
        "POLYAXON_PROJECT": compiled.project,
        "POLYAXON_RUN_OUTPUTS_PATH": str(store.outputs_dir(compiled.run_uuid)),
        "POLYAXON_RUN_CONTEXT_PATH": str(store.run_dir(compiled.run_uuid) / "context"),
        "POLYAXON_HOME": str(store.home),
    }
