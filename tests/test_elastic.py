"""Elastic training: multi-tier checkpointing + gang resize on preemption.

Three layers, mirroring the implementation:

- **Tiers unit layer** — `CheckpointTiers` semantics pinned directly:
  boundary saves land on the local tier and replicate to the durable tier
  through a fsynced staging dir + atomic rename; restore prefers the
  durable copy of a step and falls back to the local copy of the SAME
  step with per-tier quarantine; a kill mid-upload surfaces at the next
  save/wait barrier while the durable tier never lists the torn step.

- **Scheduler layer** — elastic admission walks the halving ladder to the
  `minChips` floor instead of parking in WAIT; the reservation records
  the full request so `consider_expansion` can grow the run back; the
  simulator replays a seeded shrink→grow round trip with invariants
  asserted at every event.

- **Executor layer (chaos)** — seeded scenarios through the REAL run
  lifecycle: eviction at peak lost work resumes at a smaller admissible
  gang with byte-stable state versus a non-preempted reference; a kill
  during a durable upload recovers from the local tier within the
  `checkpoint_every` bound; a durable-tier outage degrades to local-only
  saves without failing the run.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu import chaos
from polyaxon_tpu.chaos import Fault, FaultPlan
from polyaxon_tpu.runtime import checkpoint as ck
from polyaxon_tpu.runtime.checkpoint import CheckpointTiers
from polyaxon_tpu.telemetry import get_registry


def _state(scale: float = 1.0):
    return {
        "w": jnp.arange(8, dtype=jnp.float32) * scale,
        "b": jnp.ones((4,), dtype=jnp.float32) * scale,
    }


def _digit_dirs(path: str) -> set[int]:
    try:
        return {int(n) for n in os.listdir(path) if n.isdigit()}
    except OSError:
        return set()


def _corrupt_copy(directory: str, step: int) -> None:
    from polyaxon_tpu.chaos.injector import corrupt_checkpoint

    corrupt_checkpoint(directory, step=step)


# ------------------------------------------------------------ tiers unit
class TestCheckpointTiers:
    def test_save_replicates_and_restore_prefers_durable(self, tmp_path):
        tiers = CheckpointTiers(
            str(tmp_path / "durable"), local=str(tmp_path / "local")
        )
        tiers.save(2, _state(1.0))
        tiers.save(4, _state(2.0), wait=True)
        by_tier = tiers.steps_by_tier()
        assert by_tier["local"] == [2, 4]
        assert by_tier["durable"] == [2, 4]
        state, step, corrupt, tier = tiers.restore_latest_intact(_state(0.0))
        assert (step, tier, corrupt) == (4, "durable", [])
        np.testing.assert_array_equal(np.asarray(state["w"]),
                                      np.arange(8, dtype=np.float32) * 2.0)

    def test_corrupt_durable_falls_back_to_local_copy_of_same_step(
        self, tmp_path
    ):
        durable, local = str(tmp_path / "durable"), str(tmp_path / "local")
        tiers = CheckpointTiers(durable, local=local)
        tiers.save(2, _state(1.0))
        tiers.save(4, _state(2.0), wait=True)
        _corrupt_copy(durable, 4)
        state, step, corrupt, tier = tiers.restore_latest_intact(_state(0.0))
        # same step, other tier — the torn durable copy costs nothing
        assert (step, tier) == (4, "local")
        assert corrupt == [("durable", 4)]
        # the poisoned copy is quarantined in ITS tier only
        assert os.path.isdir(os.path.join(durable, "4.corrupt"))
        assert os.path.isdir(os.path.join(local, "4"))

    def test_without_local_tier_degrades_to_single_directory(self, tmp_path):
        tiers = CheckpointTiers(str(tmp_path / "durable"))
        tiers.save(2, _state(), wait=True)
        assert "local" not in tiers.steps_by_tier()
        assert tiers.latest_step() == 2
        _, step, _, tier = tiers.restore_latest_intact(_state(0.0))
        assert (step, tier) == (2, "durable")

    def test_upload_failure_counts_and_step_stays_local_only(self, tmp_path):
        durable = str(tmp_path / "durable")
        tiers = CheckpointTiers(durable, local=str(tmp_path / "local"))
        failures = get_registry().counter("checkpoint.upload_failures")
        base = failures.value
        plan = FaultPlan(
            [Fault("checkpoint.upload", "raise", at=0,
                   message="chaos: durable tier unavailable")]
        )
        with chaos.active(plan):
            tiers.save(2, _state(1.0), wait=True)  # wait() must NOT raise
        assert failures.value == base + 1
        assert tiers.steps_by_tier() == {"durable": [], "local": [2]}
        # the outage over, the next boundary replicates normally
        tiers.save(4, _state(2.0), wait=True)
        assert tiers.steps_by_tier()["durable"] == [4]
        assert tiers.latest_step() == 4

    def test_kill_mid_upload_surfaces_at_barrier_durable_never_torn(
        self, tmp_path
    ):
        from polyaxon_tpu.chaos.injector import SimulatedKill

        durable = str(tmp_path / "durable")
        tiers = CheckpointTiers(durable, local=str(tmp_path / "local"))
        plan = FaultPlan([Fault("checkpoint.upload", "kill", step=2)])
        with chaos.active(plan):
            tiers.save(2, _state(1.0))
            with pytest.raises(SimulatedKill):
                tiers.wait()
        # the durable tier never lists the torn step — no dir, no staging
        assert _digit_dirs(durable) == set()
        residue = os.listdir(durable) if os.path.isdir(durable) else []
        assert not any(n.endswith(".uploading") for n in residue)
        # the local copy is intact: a restart restores step 2 from it
        state, step, corrupt, tier = tiers.restore_latest_intact(_state(0.0))
        assert (step, tier, corrupt) == (2, "local", [])

    def test_durable_retention_mirrors_keep(self, tmp_path):
        tiers = CheckpointTiers(
            str(tmp_path / "durable"), local=str(tmp_path / "local"), keep=2
        )
        for i, step in enumerate((2, 4, 6), start=1):
            tiers.save(step, _state(float(i)), wait=True)
        assert _digit_dirs(tiers.durable) == {4, 6}


# ------------------------------------------- manager cache + quarantine
class TestManagerLifecycle:
    def test_keep_mismatch_rebuilds_manager_and_retention_tracks(
        self, tmp_path
    ):
        d = str(tmp_path / "ckpt")
        first = ck._manager(d)  # pins the default keep=3
        assert ck._manager(d) is first  # keep=None reuses
        assert ck._manager(d, keep=3) is first  # agreeing keep reuses
        rebuilt = ck._manager(d, keep=2)  # disagreeing keep REBUILDS
        assert rebuilt is not first
        assert ck._manager(d, keep=2) is rebuilt
        for step in (1, 2, 3, 4):
            ck.save_checkpoint(d, step, _state(), wait=True, keep=2)
        assert ck.all_steps(d) == [3, 4]  # the later keep won

    def test_quarantine_fsyncs_parent_directory(self, tmp_path, monkeypatch):
        d = tmp_path / "ckpt"
        (d / "5").mkdir(parents=True)
        (d / "5" / "data").write_bytes(b"x")
        synced = []
        monkeypatch.setattr(ck, "_fsync_dir", lambda p: synced.append(p))
        ck._quarantine(str(d), 5)
        assert (d / "5.corrupt").is_dir() and not (d / "5").exists()
        # the rename is made durable through the PARENT directory
        assert synced == [str(d)]

    def test_restart_with_save_in_flight_never_quarantines(
        self, tmp_path, monkeypatch
    ):
        """Satellite 3: an async save still writing at restart must be
        waited for, not judged mid-write — the restore path barriers on
        `wait_until_finished` BEFORE listing steps, so an in-flight
        checkpoint is never seen half-written and quarantined."""
        d = str(tmp_path / "ckpt")
        ck.save_checkpoint(d, 2, _state(1.0))  # async, no wait
        mgr = ck._manager(d)
        order = []
        real_wait = mgr.wait_until_finished
        real_all = ck.all_steps
        monkeypatch.setattr(
            mgr, "wait_until_finished",
            lambda: (order.append("wait"), real_wait())[1],
        )
        monkeypatch.setattr(
            ck, "all_steps",
            lambda *a, **k: (order.append("list"), real_all(*a, **k))[1],
        )
        state, step, corrupt = ck.restore_latest_intact(d, _state(0.0))
        assert (step, corrupt) == (2, [])
        assert not os.path.isdir(os.path.join(d, "2.corrupt"))
        assert "wait" in order and order.index("wait") < order.index("list")


# ----------------------------------------------------- scheduler layer
@pytest.mark.scheduler
class TestElasticAdmission:
    def _entry(self, uuid, chips, min_chips=None, priority=0):
        return {
            "uuid": uuid,
            "payload": {"project": "default"},
            "priority": priority,
            "chips": chips,
            "min_chips": min_chips,
            "block": None,
        }

    def test_shrink_ladder_halves_to_floor(self):
        from polyaxon_tpu.scheduler.fleet import shrink_candidates

        assert shrink_candidates(8, None, 2) == [(4, None), (2, None)]
        assert shrink_candidates(8, (2, 4), 1) == [
            (4, (2, 2)), (2, (1, 2)), (1, (1, 1))
        ]
        assert shrink_candidates(4, None, 4) == []  # floor == full: rigid

    def test_min_chips_demand_reads_resources(self):
        from polyaxon_tpu.schemas.operation import V1Operation
        from polyaxon_tpu.scheduler.fleet import min_chips_demand

        op = V1Operation.model_validate(
            {
                "name": "el",
                "environment": {"resources": {"chips": 4, "minChips": 2}},
                "component": {
                    "name": "c",
                    "run": {"kind": "job", "container": {"command": ["true"]}},
                },
            }
        )
        assert min_chips_demand(op) == 2
        rigid = V1Operation.model_validate(
            {
                "name": "r",
                "environment": {"resources": {"chips": 4}},
                "component": {
                    "name": "c",
                    "run": {"kind": "job", "container": {"command": ["true"]}},
                },
            }
        )
        assert min_chips_demand(rigid) is None

    def test_elastic_admits_shrunk_grant_instead_of_wait(self, tmp_home):
        from polyaxon_tpu.scheduler.admission import (
            ADMIT,
            WAIT,
            AdmissionController,
        )
        from polyaxon_tpu.scheduler.fleet import Fleet
        from polyaxon_tpu.store import RunStore

        store = RunStore()
        fleet = Fleet(store)
        fleet.configure(chips=4)
        fleet.reserve("busy", chips=3)
        ac = AdmissionController(store, fleet=fleet)

        rigid = ac.try_admit(self._entry("rigid", chips=4))
        assert rigid.outcome == WAIT  # the old behavior: park until free

        decision = ac.try_admit(self._entry("el1", chips=4, min_chips=1))
        assert decision.outcome == ADMIT  # the elastic run never parks
        assert decision.reservation["chips"] == 1
        rec = fleet.ledger.get("el1")
        assert rec["requested_chips"] == 4  # full demand on the ledger

    def test_unplaceable_floor_rejects(self, tmp_home):
        from polyaxon_tpu.scheduler.admission import (
            REJECT,
            AdmissionController,
        )
        from polyaxon_tpu.scheduler.fleet import Fleet
        from polyaxon_tpu.store import RunStore

        store = RunStore()
        fleet = Fleet(store)
        fleet.configure(chips=4)
        ac = AdmissionController(store, fleet=fleet)
        decision = ac.try_admit(self._entry("huge", chips=8, min_chips=6))
        assert decision.outcome == REJECT

    def test_consider_expansion_flags_shrunk_run_when_space_frees(
        self, tmp_home
    ):
        from polyaxon_tpu.schemas.lifecycle import V1Statuses
        from polyaxon_tpu.scheduler.admission import (
            ADMIT,
            AdmissionController,
        )
        from polyaxon_tpu.scheduler.fleet import Fleet
        from polyaxon_tpu.store import RunStore

        store = RunStore()
        fleet = Fleet(store)
        fleet.configure(chips=4)
        fleet.reserve("busy", chips=3)
        ac = AdmissionController(store, fleet=fleet)
        store.create_run("el1", "el1", "default", {})
        store.set_status("el1", V1Statuses.COMPILED)
        store.set_status("el1", V1Statuses.QUEUED)
        assert ac.try_admit(
            self._entry("el1", chips=4, min_chips=1)
        ).outcome == ADMIT
        assert ac.consider_expansion() == []  # no space yet: stay shrunk

        fleet.release("busy")
        assert ac.consider_expansion() == ["el1"]
        meta = store.get_status("el1")["meta"]
        assert meta["preempt_requested"] is True
        kinds = [e["kind"] for e in store.read_events("el1")]
        assert "elastic_expand_requested" in kinds


@pytest.mark.scheduler
def test_sim_shrink_then_grow_round_trip(tmp_home):
    """Seeded round trip through the REAL admission stack under SimClock:
    a full-fleet elastic job yields to a higher-priority rigid arrival by
    shrinking (not waiting), then grows back to full size the moment the
    rigid job's chips free — grants [4, 2, 4], chip-second accounting
    exact at every rung, invariants asserted at every event."""
    from polyaxon_tpu.scheduler.sim import FleetSimulator, SimJob

    elastic = SimJob(
        "elastic", duration=8.0, arrival=0.0, chips=4, min_chips=1
    )
    rigid = SimJob("rigid", duration=4.0, arrival=2.0, chips=2, priority=1)
    sim = FleetSimulator(
        [elastic, rigid],
        chips=4,
        invariant_fn=lambda s: s.check_invariants(),
    )
    report = sim.run()
    assert report["succeeded"] == 2
    assert elastic.grants == [4, 2, 4]
    # only the shrunk grant counts as a resize: the grow-back IS the
    # requested size
    assert elastic.resizes == 1
    assert report["elastic_resizes"] == 1
    # never parked: every (re)admission happened the instant it queued
    assert all(w == 0.0 for w in elastic.waits)
    # work accounting is exact across rungs: 2s at full rate + 4s at half
    # rate + 4s at full rate = 8s of full-size work, finishing at t=10
    assert elastic.finished_at == pytest.approx(10.0)
    assert rigid.finished_at == pytest.approx(6.0)


# ---------------------------------------------------- executor layer
def _elastic_train_op(
    name: str,
    *,
    steps: int,
    checkpoint_every: int = 2,
    max_retries: int = 0,
    chips: int | None = None,
    min_chips: int | None = None,
    local_dir: str | None = None,
):
    from polyaxon_tpu.schemas.operation import V1Operation

    train = {
        "steps": steps,
        "logEvery": 1,
        "precision": "float32",
        "checkpointEvery": checkpoint_every,
    }
    if local_dir:
        train["checkpointLocalDir"] = local_dir
    spec = {
        "kind": "operation",
        "name": name,
        "component": {
            "kind": "component",
            "name": "c",
            "termination": {"maxRetries": max_retries},
            "run": {
                "kind": "jaxjob",
                "program": {
                    "model": {
                        "name": "mlp",
                        "config": {
                            "input_dim": 8, "num_classes": 2, "hidden": [4]
                        },
                    },
                    "data": {
                        "name": "synthetic",
                        "batchSize": 8,
                        "config": {"shape": [8], "num_classes": 2},
                    },
                    "optimizer": {"name": "sgd", "learningRate": 0.01},
                    "train": train,
                },
            },
        },
    }
    if chips is not None:
        resources = {"chips": chips}
        if min_chips is not None:
            resources["minChips"] = min_chips
        spec["environment"] = {"resources": resources}
    return V1Operation.model_validate(spec)


def _events(store, uuid, kind):
    return [e for e in store.read_events(uuid) if e["kind"] == kind]


@pytest.mark.chaos
class TestElasticChaos:
    def test_kill_mid_upload_recovers_from_local_tier(self, tmp_home, tmp_path):
        from polyaxon_tpu.compiler import compile_operation
        from polyaxon_tpu.runtime import Executor
        from polyaxon_tpu.schemas.lifecycle import V1Statuses
        from polyaxon_tpu.store import RunStore

        steps, every = 8, 2
        plan = FaultPlan.kill_mid_upload(seed=7, steps=steps,
                                         checkpoint_every=every)
        upload_step = plan.params["upload_step"]
        store = RunStore()
        compiled = compile_operation(
            _elastic_train_op(
                "chaos-upload", steps=steps, checkpoint_every=every,
                max_retries=1, local_dir=str(tmp_path / "fast"),
            )
        )
        with chaos.active(plan):
            status = Executor(store, devices=jax.devices()[:1]).execute(
                compiled
            )
        assert status == V1Statuses.SUCCEEDED
        # the kill surfaced as ONE transient retry and resume lost at most
        # the steps since the boundary the upload was carrying
        retrying = [
            c for c in store.get_status(compiled.run_uuid)["conditions"]
            if c["type"] == "retrying"
        ]
        assert len(retrying) == 1
        resumed = _events(store, compiled.run_uuid, "resumed")
        assert resumed
        assert resumed[0]["step"] >= upload_step
        # WHICH later boundary save surfaces the uploader's death is a race
        # between the upload and the step loop; that save lands first
        # (CheckpointTiers.save), so the resume is from that boundary's
        # local copy and no step is trained twice
        assert resumed[0]["tier"] == "local"
        assert resumed[0]["step"] % every == 0
        logged = [r["step"] for r in store.read_metrics(compiled.run_uuid)]
        assert logged == sorted(set(logged)) and logged[-1] == steps
        # the durable tier never lists a torn step — no staging residue
        durable = str(store.outputs_dir(compiled.run_uuid) / "checkpoints")
        assert not any(
            n.endswith(".uploading") for n in os.listdir(durable)
        )
        # the fast tier is per-run scoped and took the boundary saves
        local = tmp_path / "fast" / compiled.run_uuid / "checkpoints"
        assert _digit_dirs(str(local))

    def test_durable_tier_outage_degrades_to_local_only(
        self, tmp_home, tmp_path
    ):
        from polyaxon_tpu.compiler import compile_operation
        from polyaxon_tpu.runtime import Executor
        from polyaxon_tpu.schemas.lifecycle import V1Statuses
        from polyaxon_tpu.store import RunStore

        steps, every, fails = 8, 2, 2
        plan = FaultPlan.durable_tier_outage(
            seed=11, steps=steps, checkpoint_every=every, fails=fails
        )
        outage_steps = set(plan.params["outage_steps"])
        failures = get_registry().counter("checkpoint.upload_failures")
        base = failures.value
        store = RunStore()
        compiled = compile_operation(
            _elastic_train_op(
                "chaos-outage", steps=steps, checkpoint_every=every,
                local_dir=str(tmp_path / "fast"),
            )
        )
        with chaos.active(plan):
            status = Executor(store, devices=jax.devices()[:1]).execute(
                compiled
            )
        assert status == V1Statuses.SUCCEEDED
        # the outage was absorbed, not retried and not fatal
        assert failures.value == base + fails
        conds = store.get_status(compiled.run_uuid)["conditions"]
        assert all(c["type"] != "retrying" for c in conds)
        assert store.read_metrics(compiled.run_uuid)[-1]["step"] == steps
        # the refused steps stayed local-only; later boundaries replicated
        durable = str(store.outputs_dir(compiled.run_uuid) / "checkpoints")
        assert _digit_dirs(durable).isdisjoint(outage_steps)
        assert max(_digit_dirs(durable)) == steps
        # async checkpointing kept the step loop moving: the stall
        # histogram observed every boundary
        stall = get_registry().histogram("trainer.checkpoint_stall_ms")
        assert stall.count >= steps // every

    def test_preempt_at_peak_resumes_within_checkpoint_bound(self, tmp_home):
        from polyaxon_tpu.compiler import compile_operation
        from polyaxon_tpu.runtime import Executor
        from polyaxon_tpu.schemas.lifecycle import V1Statuses
        from polyaxon_tpu.store import RunStore

        steps, every = 8, 2
        plan = FaultPlan.preempt_at_peak(seed=5, steps=steps,
                                         checkpoint_every=every)
        peak = plan.params["preempt_step"]
        store = RunStore()
        compiled = compile_operation(
            _elastic_train_op("chaos-peak", steps=steps,
                              checkpoint_every=every, max_retries=0)
        )
        with chaos.active(plan):
            status = Executor(store, devices=jax.devices()[:1]).execute(
                compiled
            )
        assert status == V1Statuses.SUCCEEDED
        # the cooperative preemption flushes a save at the preempt step
        # itself, so even the worst-case notice (one step shy of the next
        # boundary) loses ZERO completed steps — well inside the
        # `<= checkpoint_every` acceptance bound
        resumed = _events(store, compiled.run_uuid, "resumed")
        assert resumed and resumed[0]["step"] == peak
        preempted = _events(store, compiled.run_uuid, "preempted")
        assert preempted and preempted[0]["resume_step"] == peak
        assert peak - plan.params["last_boundary"] <= every
        assert store.read_metrics(compiled.run_uuid)[-1]["step"] == steps


@pytest.mark.chaos
def test_eviction_shrinks_gang_and_resumes_byte_stable(tmp_home, monkeypatch):
    """The acceptance round trip: an elastic 2-chip run is evicted at
    peak, its freed chips are partially stolen (a 1-chip hog appears the
    instant they release), and re-admission grants the 1-chip rung of the
    ladder instead of parking — the trainer rebuilds the mesh at 1 device,
    doubles grad accumulation to hold the global batch, and resumes from a
    checkpoint whose parameters are byte-identical to a non-preempted
    reference run at the same step."""
    from polyaxon_tpu.compiler import compile_operation
    from polyaxon_tpu.runtime import Executor
    from polyaxon_tpu.scheduler.agent import Agent
    from polyaxon_tpu.scheduler.fleet import Fleet
    from polyaxon_tpu.schemas.lifecycle import V1Statuses
    from polyaxon_tpu.store import RunStore

    steps, every, evict_logged_step = 6, 2, 4

    class EvictAtPeak(RunStore):
        """Raise the scheduler's eviction flag when the victim logs the
        step just before a boundary save — peak uncheckpointed work."""

        target: str | None = None

        def log_metrics(self, run_uuid, step, metrics):
            super().log_metrics(run_uuid, step, metrics)
            if run_uuid == self.target and step == evict_logged_step:
                meta = (self.get_status(run_uuid) or {}).get("meta") or {}
                if not meta.get("preempt_restarts"):
                    self.set_meta(run_uuid, preempt_requested=True)

    store = EvictAtPeak()
    Fleet(store).configure(chips=2)
    agent = Agent(store=store)
    op = _elastic_train_op(
        "elastic-victim", steps=steps, checkpoint_every=every,
        max_retries=0, chips=2, min_chips=1,
    )
    uid = agent.submit(op)
    store.target = uid

    # the hog: the moment the evicted run releases its 2 chips, 1 of them
    # is reserved away — the original block can never re-place, so the
    # only way forward is the smaller rung of the ladder
    hogged = []
    real_release = Fleet.release

    def release_and_hog(self, run_uuid):
        rec = real_release(self, run_uuid)
        if run_uuid == uid and not hogged:
            hogged.append(1)
            assert self.reserve("hog", chips=1, project="hog") is not None
        return rec

    monkeypatch.setattr(Fleet, "release", release_and_hog)

    # one drain: claim(2 chips) → run → evict at peak → hog steals a chip
    # → re-admit(1 chip) → resume → done. If the elastic run ever parked
    # in WAIT the drain would leave it QUEUED.
    resizes = get_registry().counter("trainer.elastic_resizes")
    shrinks = get_registry().counter("scheduler.elastic_shrinks")
    base_resizes, base_shrinks = resizes.value, shrinks.value
    agent.drain()

    status = store.get_status(uid)
    assert status["status"] == V1Statuses.SUCCEEDED
    meta = status["meta"]
    assert meta["preempt_restarts"] == 1
    assert meta["granted_chips"] == 1 and meta["requested_chips"] == 2
    assert shrinks.value == base_shrinks + 1
    assert resizes.value == base_resizes + 1

    # the first attempt ran at the full gang; the eviction recorded it
    # (the trainer also emits its own un-flagged preempted event)
    evictions = [
        e for e in _events(store, uid, "preempted") if e.get("scheduler")
    ]
    assert len(evictions) == 1
    assert evictions[0]["granted_chips"] == 2
    shrink_ev = _events(store, uid, "elastic_shrink")
    assert shrink_ev
    assert shrink_ev[-1]["granted"] == 1 and shrink_ev[-1]["requested"] == 2
    resize_ev = _events(store, uid, "elastic_resize")
    assert resize_ev
    assert resize_ev[0]["granted"] == 1 and resize_ev[0]["requested"] == 2
    # global batch held constant: grad accumulation doubled for the
    # half-width mesh
    assert resize_ev[0]["grad_accum"] == 2
    # the flag logged at step 4 is observed at the head of step 5, where
    # the cooperative exit flushes a step-5 save: zero completed steps lost
    resumed = _events(store, uid, "resumed")
    assert resumed and resumed[0]["step"] == evict_logged_step + 1
    assert store.read_metrics(uid)[-1]["step"] == steps
    # terminal transition released the shrunk reservation; only the hog
    # remains
    assert Fleet(store).reserved_chips() == 1

    # ---- byte-stability: a never-preempted reference run's checkpoint at
    # the restore step must match the elastic run's bit for bit
    ref = compile_operation(
        _elastic_train_op("reference", steps=steps, checkpoint_every=every)
    )
    assert Executor(store, devices=jax.devices()).execute(ref) == (
        V1Statuses.SUCCEEDED
    )
    el_dir = str(store.outputs_dir(uid) / "checkpoints")
    ref_dir = str(store.outputs_dir(ref.run_uuid) / "checkpoints")
    el_tree = ck._manager(el_dir).restore(evict_logged_step)
    ref_tree = ck._manager(ref_dir).restore(evict_logged_step)
    el_leaves = jax.tree.leaves(el_tree)
    ref_leaves = jax.tree.leaves(ref_tree)
    assert len(el_leaves) == len(ref_leaves) > 0
    for a, b in zip(el_leaves, ref_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the losses logged up to the eviction are byte-equal too
    el_metrics = {
        m["step"]: m["loss"] for m in store.read_metrics(uid)
    }
    ref_metrics = {
        m["step"]: m["loss"] for m in store.read_metrics(ref.run_uuid)
    }
    for s in range(1, evict_logged_step + 1):
        assert el_metrics[s] == ref_metrics[s]


def test_grad_accum_auto_adjusts_to_mesh_width(tmp_home):
    """The divisibility contract is an automatic adjustment, not an
    error: a microbatch count the requested accumulation doesn't divide
    picks the next feasible value and announces it."""
    from polyaxon_tpu.compiler import compile_operation
    from polyaxon_tpu.runtime import Executor
    from polyaxon_tpu.schemas.lifecycle import V1Statuses
    from polyaxon_tpu.store import RunStore

    from polyaxon_tpu.schemas.operation import V1Operation

    op = _elastic_train_op("accum-adjust", steps=2)
    program = op.component.run.program
    train = program.train.model_copy(update={"grad_accum": 3})
    op = op.model_copy(
        update={
            "component": op.component.model_copy(
                update={
                    "run": op.component.run.model_copy(
                        update={
                            "program": program.model_copy(
                                update={"train": train}
                            )
                        }
                    )
                }
            )
        }
    )
    assert isinstance(op, V1Operation)
    store = RunStore()
    compiled = compile_operation(op)
    status = Executor(store, devices=jax.devices()[:1]).execute(compiled)
    assert status == V1Statuses.SUCCEEDED
    adjusted = _events(store, compiled.run_uuid, "grad_accum_adjusted")
    # batch 8 on 1 device → 8 microbatches; 3 ∤ 8 → next divisor is 4
    assert adjusted and adjusted[0]["requested"] == 3
    assert adjusted[0]["effective"] == 4


def test_min_chips_schema_validation():
    import pydantic

    from polyaxon_tpu.schemas.environment import V1Resources

    ok = V1Resources.model_validate({"chips": 4, "minChips": 2})
    assert ok.min_chips == 2
    with pytest.raises(pydantic.ValidationError):
        V1Resources.model_validate({"chips": 4, "minChips": 0})
    with pytest.raises(pydantic.ValidationError):
        V1Resources.model_validate({"chips": 4, "minChips": 8})
