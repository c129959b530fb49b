"""Fleet scheduler: inventory block math, gang reservations, quotas,
admission ordering, priority preemption, and the deterministic simulator.

The acceptance core lives in TestSimulationAcceptance: a seeded workload
replayed through the REAL admission stack with invariants asserted at
EVERY simulation event — quotas never exceeded at any instant, gangs
all-or-nothing, a high-priority arrival evicts the cheapest lower-
priority victim set, and every preempted run resumes from its checkpoint
and reaches SUCCEEDED.
"""

from __future__ import annotations

import json
import multiprocessing
from pathlib import Path

import pytest

from polyaxon_tpu.schemas.lifecycle import V1Statuses
from polyaxon_tpu.schemas.quota import V1QuotaSpec
from polyaxon_tpu.scheduler.admission import (
    ADMIT,
    REJECT,
    WAIT,
    AdmissionController,
    QuotaManager,
)
from polyaxon_tpu.scheduler.clock import SimClock
from polyaxon_tpu.scheduler.fleet import (
    DeviceInventory,
    Fleet,
    chips_demand,
    topology_request,
)
from polyaxon_tpu.scheduler.queue import RunQueue
from polyaxon_tpu.scheduler.sim import (
    FleetSimulator,
    SimJob,
    synthetic_workload,
)
from polyaxon_tpu.scheduler.topology import (
    choose_block_shape,
    fits_torus,
    grid_blocks,
    parse_topology,
)
from polyaxon_tpu.store.local import RunStore

pytestmark = pytest.mark.scheduler


# ------------------------------------------------------------ topology
def test_parse_topology_forms():
    assert parse_topology("4x8") == (4, 8)
    assert parse_topology("2X2x2") == (2, 2, 2)
    assert parse_topology((4, 4)) == (4, 4)
    assert parse_topology(None) is None
    assert parse_topology("4x") is None
    assert parse_topology("0x4") is None


def test_block_math_shared_with_placement():
    # the tuner's placement module re-exports the same helpers — one
    # implementation of the torus math, not two drifting copies
    from polyaxon_tpu.tuner import placement

    assert placement.choose_block_shape is choose_block_shape
    assert placement.parse_topology is parse_topology
    blocks = grid_blocks((4, 4), (2, 2))
    assert len(blocks) == 4
    assert all(len(b) == 4 for b in blocks)
    assert fits_torus((4, 4), (2, 4))
    assert not fits_torus((4, 4), (3, 2))  # 3 does not divide 4
    assert fits_torus((4, 4), (4,))  # right-padded with 1s


# ----------------------------------------------------------- inventory
def test_inventory_flat_and_torus_placement():
    inv = DeviceInventory(chips=4)
    got = inv.place(3, used=set())
    assert got is not None and len(got) == 3
    assert inv.place(2, used=set(got)) is None  # only 1 free: all-or-nothing
    assert inv.fits(4) and not inv.fits(5)

    torus = DeviceInventory(topology=(4, 4))
    a = torus.place(8, used=set(), block=(2, 4))
    assert a is not None and len(a) == 8
    b = torus.place(8, used=set(a), block=(2, 4))
    assert b is not None and not (set(a) & set(b))
    assert torus.place(8, used=set(a) | set(b), block=(2, 4)) is None
    # a block that cannot tile the torus can never fit
    assert not torus.fits(6, block=(3, 2))
    assert torus.fits(8, block=(2, 4))


def test_reservations_all_or_nothing_and_persistent(tmp_home):
    store = RunStore()
    fleet = Fleet(store)
    fleet.configure(topology="4x4")
    r = fleet.reserve("a", chips=8, block=(2, 4))
    assert r is not None and len(r["coords"]) == 8
    # idempotent: same run re-reserving returns the SAME record
    assert fleet.reserve("a", chips=8, block=(2, 4))["coords"] == r["coords"]
    # a second handle on the same home sees the reservation (persistence)
    assert Fleet(RunStore()).ledger.get("a") is not None
    assert fleet.reserve("b", chips=16) is None  # 8 free < 16: nothing
    assert fleet.reserved_chips() == 8
    fleet.release("a")
    assert fleet.reserved_chips() == 0


def test_store_releases_reservation_on_every_terminal_transition(tmp_home):
    store = RunStore()
    fleet = Fleet(store)
    fleet.configure(chips=4)
    for status in (V1Statuses.SUCCEEDED, V1Statuses.FAILED, V1Statuses.STOPPED):
        uid = f"run-{status}"
        store.create_run(uid, uid, "p", {})
        fleet.reserve(uid, chips=2)
        assert fleet.ledger.get(uid) is not None
        for s in (
            V1Statuses.COMPILED,
            V1Statuses.QUEUED,
            V1Statuses.SCHEDULED,
            V1Statuses.STARTING,
            V1Statuses.RUNNING,
        ):
            store.set_status(uid, s)
        if status == V1Statuses.STOPPED:
            store.set_status(uid, V1Statuses.STOPPING)
        store.set_status(uid, status)
        assert fleet.ledger.get(uid) is None, f"leaked on {status}"


# --------------------------------------------------------------- demand
def test_chips_demand_resolution_order():
    assert chips_demand({}) == 1
    assert chips_demand(
        {"environment": {"resources": {"chips": 4}}}
    ) == 4
    spec = {"environment": {"resources": {"tpu": {"topology": "2x4"}}}}
    assert chips_demand(spec) == 8  # tpu wins
    assert topology_request(spec) == (2, 4)
    multi = {
        "environment": {
            "resources": {"tpu": {"topology": "2x4", "slices": 2}}
        }
    }
    assert chips_demand(multi) == 16
    assert topology_request(multi) is None  # multi-slice: flat grab
    nested = {
        "component": {
            "run": {"environment": {"resources": {"chips": 3}}}
        }
    }
    assert chips_demand(nested) == 3


# --------------------------------------------------------------- quotas
def test_quota_spec_validation():
    q = V1QuotaSpec(scope="queue:bulk", max_chips=8)
    assert q.is_queue_scope and q.scope_name == "bulk"
    with pytest.raises(Exception):
        V1QuotaSpec(scope="p", weight=0)
    with pytest.raises(Exception):
        V1QuotaSpec(scope="", max_chips=1)


def test_quota_check_reject_vs_wait(tmp_home):
    qm = QuotaManager(RunStore())
    qm.set(V1QuotaSpec(scope="p1", max_chips=8, max_runs=2))
    # ceiling: can NEVER fit → reject
    assert qm.check("p1", "default", 16, {})[0] == REJECT
    # over only because of current usage → wait
    assert (
        qm.check("p1", "default", 4, {"p1": {"chips": 6, "runs": 1}})[0]
        == WAIT
    )
    assert (
        qm.check("p1", "default", 4, {"p1": {"chips": 2, "runs": 2}})[0]
        == WAIT  # run-count limit
    )
    assert qm.check("p1", "default", 4, {})[0] == ADMIT
    assert qm.check("other", "default", 99, {})[0] == ADMIT  # no quota
    # queue-scoped quotas gate by routed queue
    qm.set(V1QuotaSpec(scope="queue:bulk", max_runs=1))
    assert (
        qm.check("other", "bulk", 1, {"queue:bulk": {"chips": 1, "runs": 1}})[0]
        == WAIT
    )


def test_admission_decisions(tmp_home):
    store = RunStore()
    fleet = Fleet(store)
    fleet.configure(topology="4x4")
    adm = AdmissionController(store, fleet=fleet)
    assert adm.active

    def entry(uuid, chips, priority=0, block=None, project="p"):
        return {
            "uuid": uuid,
            "priority": priority,
            "seq": 0,
            "chips": chips,
            "block": block,
            "payload": {"project": project},
        }

    d = adm.try_admit(entry("a", 8, block=[2, 4]))
    assert d.outcome == ADMIT and len(d.reservation["coords"]) == 8
    # bigger than the fleet: UNSCHEDULABLE, not queued forever
    assert adm.try_admit(entry("big", 32)).outcome == REJECT
    # un-tileable block: likewise
    assert adm.try_admit(entry("odd", 6, block=[3, 2])).outcome == REJECT
    # fits the fleet but not right now: WAIT
    d = adm.try_admit(entry("b", 16))
    assert d.outcome == WAIT and not d.preempt  # equal priority: no eviction


def test_fair_share_ordering(tmp_home):
    store = RunStore()
    fleet = Fleet(store)
    fleet.configure(chips=16)
    qm = QuotaManager(store)
    qm.set(V1QuotaSpec(scope="heavy", weight=4.0))
    adm = AdmissionController(store, fleet=fleet, quotas=qm)
    # heavy already holds 8 chips but weight 4 → share 2; light holds 4
    # at weight 1 → share 4. heavy goes first at equal priority.
    fleet.reserve("h1", chips=8, project="heavy")
    fleet.reserve("l1", chips=4, project="light")
    entries = [
        {"uuid": "l2", "priority": 0, "seq": 1, "payload": {"project": "light"}},
        {"uuid": "h2", "priority": 0, "seq": 2, "payload": {"project": "heavy"}},
        {"uuid": "hi", "priority": 9, "seq": 3, "payload": {"project": "light"}},
    ]
    ordered = [e["uuid"] for e in adm.order(entries)]
    assert ordered == ["hi", "h2", "l2"]  # priority first, then fair share


def test_cheapest_victim_selection(tmp_home):
    store = RunStore()
    fleet = Fleet(store)
    fleet.configure(chips=8)
    adm = AdmissionController(store, fleet=fleet)
    fleet.reserve("small", chips=2, priority=0)
    fleet.reserve("large", chips=4, priority=0)
    fleet.reserve("important", chips=2, priority=5)
    # need 4 chips at priority 3: evict ONLY `large` (cheapest sufficient
    # set among strictly-lower-priority holders; `important` untouchable)
    victims = adm.pick_victims(4, None, priority=3)
    assert [v["uuid"] for v in victims] == ["large"]
    # nothing below priority 0 → no victims for an equal-priority gang
    assert adm.pick_victims(4, None, priority=0) == []
    # even evicting all lower-priority holders can't make room → []
    assert adm.pick_victims(8, None, priority=3) == []


# ---------------------------------------------------------------- queue
def test_fifo_within_priority_across_push_pop_remove(tmp_home):
    q = RunQueue(RunStore(), name="fifo")
    for i in range(4):
        q.push(f"a{i}", {}, priority=0)
    q.push("hot", {}, priority=5)
    # remove from the middle, re-add: the re-add goes to the BACK of its
    # priority band (fresh seq), everyone else keeps relative order
    assert q.remove("a1")
    q.push("a1", {}, priority=0)
    assert [e["uuid"] for e in q.peek_all()] == [
        "hot", "a0", "a2", "a3", "a1",
    ]
    assert q.pop()["uuid"] == "hot"
    q.push("late-hot", {}, priority=5)
    assert q.pop()["uuid"] == "late-hot"
    assert [q.pop()["uuid"] for _ in range(4)] == ["a0", "a2", "a3", "a1"]


def test_queue_entries_carry_seq_and_enqueued_at(tmp_home):
    q = RunQueue(RunStore(), name="meta")
    e1 = q.push("u1", {}, priority=0)
    e2 = q.push("u2", {}, priority=0, chips=4, enqueued_at=123.0)
    assert e2["seq"] == e1["seq"] + 1
    assert e1["enqueued_at"] > 0
    assert e2["enqueued_at"] == 123.0 and e2["chips"] == 4
    # seq survives drain-to-empty: later pushes never recycle seq numbers
    q.pop(), q.pop()
    e3 = q.push("u3", {}, priority=0)
    assert e3["seq"] == e2["seq"] + 1


def _queue_worker(home: str, worker: int, n: int, out_path: str):
    from polyaxon_tpu.scheduler.queue import RunQueue
    from polyaxon_tpu.store.local import RunStore

    q = RunQueue(RunStore(home), name="mp")
    popped = []
    for i in range(n):
        q.push(f"w{worker}-{i}", {}, priority=i % 3)
        got = q.pop()
        if got is not None:
            popped.append(got["uuid"])
    Path(out_path).write_text(json.dumps(popped))


def test_multiprocess_push_pop_under_fcntl_lock(tmp_home, tmp_path):
    """N processes hammering one queue file: every pushed entry is popped
    exactly once (the fcntl lock serializes read-modify-write cycles)."""
    n_workers, n_each = 4, 25
    ctx = multiprocessing.get_context("spawn")
    outs = [tmp_path / f"out-{w}.json" for w in range(n_workers)]
    procs = [
        ctx.Process(
            target=_queue_worker, args=(str(tmp_home), w, n_each, str(outs[w]))
        )
        for w in range(n_workers)
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0
    popped = []
    for o in outs:
        popped.extend(json.loads(o.read_text()))
    q = RunQueue(RunStore(), name="mp")
    remaining = [e["uuid"] for e in q.peek_all()]
    all_seen = popped + remaining
    assert len(all_seen) == n_workers * n_each
    assert len(set(all_seen)) == len(all_seen)  # nothing lost or doubled
    # the survivors are still a well-formed priority queue
    seqs = [(e["priority"], e["seq"]) for e in q.peek_all()]
    assert seqs == sorted(seqs, key=lambda t: (-t[0], t[1]))


# ------------------------------------------------------- agent admission
def _chip_op(name: str, chips: int, queue: str = "default"):
    from polyaxon_tpu.schemas.operation import V1Operation

    return V1Operation.model_validate(
        {
            "name": name,
            "queue": queue,
            "environment": {"resources": {"chips": chips}},
            "component": {
                "name": "c",
                "run": {
                    "kind": "job",
                    "container": {"command": ["true"]},
                },
            },
        }
    )


def test_agent_without_fleet_keeps_old_claiming(tmp_home):
    from polyaxon_tpu.scheduler.agent import Agent

    store = RunStore()
    agent = Agent(store=store)
    assert not agent.admission.active
    uid = agent.submit(_chip_op("plain", chips=999))  # no fleet: no gating
    assert agent.drain() == 1
    assert store.get_status(uid)["status"] == V1Statuses.SUCCEEDED


def test_agent_admission_gates_and_rejects(tmp_home):
    from polyaxon_tpu.scheduler.agent import Agent

    store = RunStore()
    Fleet(store).configure(chips=4)
    agent = Agent(store=store)
    assert agent.admission.active
    ok = agent.submit(_chip_op("fits", chips=2))
    huge = agent.submit(_chip_op("huge", chips=8))
    assert agent.drain() == 1  # only the schedulable one is claimed
    assert store.get_status(ok)["status"] == V1Statuses.SUCCEEDED
    assert store.get_status(huge)["status"] == V1Statuses.UNSCHEDULABLE
    # terminal transition released the chips
    assert Fleet(store).reserved_chips() == 0


def test_agent_quota_throttles_but_backfills(tmp_home):
    from polyaxon_tpu.scheduler.agent import Agent

    store = RunStore()
    Fleet(store).configure(chips=8)
    QuotaManager(store).set(V1QuotaSpec(scope="capped", max_runs=0))
    agent = Agent(store=store)
    blocked = agent.submit(_chip_op("blocked", chips=1), project="capped")
    free = agent.submit(_chip_op("free", chips=1), project="open")
    agent.drain()
    # maxRuns=0 is a hard ceiling → the capped run is UNSCHEDULABLE, the
    # open-project run backfilled past it and succeeded
    assert store.get_status(blocked)["status"] == V1Statuses.UNSCHEDULABLE
    assert store.get_status(free)["status"] == V1Statuses.SUCCEEDED


def test_executor_eviction_checkpoints_requeues_and_resumes(tmp_home):
    """The REAL eviction path end to end: the admission flag is observed
    at a log boundary, the trainer checkpoints at the step boundary and
    raises Preempted, the executor releases chips and requeues at the
    original priority, and the re-claimed run RESUMES from the checkpoint
    (not step 0) to SUCCEEDED."""
    from polyaxon_tpu.schemas.operation import V1Operation
    from polyaxon_tpu.scheduler.agent import Agent

    store = RunStore()
    Fleet(store).configure(chips=2)
    agent = Agent(store=store)
    op = V1Operation.model_validate(
        {
            "name": "victim",
            "component": {
                "name": "c",
                "run": {
                    "kind": "jaxjob",
                    "program": {
                        "model": {
                            "name": "mlp",
                            "config": {
                                "input_dim": 8,
                                "num_classes": 2,
                                "hidden": [4],
                            },
                        },
                        "data": {
                            "name": "synthetic",
                            # divisible by the 8-device virtual slice the
                            # test harness fakes (conftest.py)
                            "batchSize": 8,
                            "config": {"shape": [8], "num_classes": 2},
                        },
                        "optimizer": {"name": "sgd", "learningRate": 0.01},
                        "train": {
                            "steps": 6,
                            "logEvery": 1,
                            "checkpointEvery": 2,
                            "precision": "float32",
                        },
                    },
                },
            },
        }
    )
    uid = agent.submit(op, priority=2)
    # flag the eviction BEFORE the agent claims the run: the very first
    # log boundary observes it and routes through the SIGTERM machinery
    store.set_meta(uid, preempt_requested=True)
    # one drain: claim → run → evict+requeue → re-claim → resume → done
    agent.drain()
    status = store.get_status(uid)
    assert status["status"] == V1Statuses.SUCCEEDED
    meta = status["meta"]
    assert meta["preempt_restarts"] == 1
    assert meta["preempt_requested"] is False
    events = store.read_events(uid)
    evictions = [
        e for e in events if e["kind"] == "preempted" and e.get("scheduler")
    ]
    assert len(evictions) == 1
    assert evictions[0]["step"] is not None  # checkpoint flushed at eviction
    # lifecycle shows the round trip: RETRYING(evicted) → QUEUED → ... →
    # SUCCEEDED, and the re-enqueued entry kept the original priority
    reasons = [c.get("reason") for c in status["conditions"]]
    assert "evicted" in reasons
    # chips released at the end
    assert Fleet(store).reserved_chips() == 0


# ---------------------------------------------------- simulator acceptance
class TestSimulationAcceptance:
    def test_invariants_every_event_and_all_jobs_finish(self):
        jobs = synthetic_workload(seed=11, n_jobs=60, topology="4x4")
        quotas = [
            V1QuotaSpec(scope="alpha", max_chips=12, weight=2.0),
            V1QuotaSpec(scope="beta", max_chips=8),
        ]
        sim = FleetSimulator(
            jobs,
            topology="4x4",
            quotas=quotas,
            invariant_fn=lambda s: s.check_invariants(),
        )
        report = sim.run()
        assert report["succeeded"] + report["unschedulable"] == report["jobs"]
        assert report["events"] > 0
        assert {"makespan_s", "wait_p50_s", "wait_p95_s", "utilization",
                "preemptions"} <= report.keys()
        # re-running the same seed reproduces the schedule exactly
        sim2 = FleetSimulator(
            synthetic_workload(seed=11, n_jobs=60, topology="4x4"),
            topology="4x4",
            quotas=quotas,
        )
        assert sim2.run() == report

    def test_high_priority_preempts_cheapest_victims_and_they_resume(self):
        jobs = [
            SimJob("low-small", duration=100, arrival=0, chips=2, priority=0),
            SimJob("low-large", duration=100, arrival=0, chips=6, priority=0),
            # arrives while the fleet is full; needs the chips low-large
            # holds, and low-large (not low-small + something) is the
            # cheapest sufficient victim set
            SimJob("high", duration=50, arrival=10, chips=6, priority=10),
        ]
        sim = FleetSimulator(
            jobs, chips=8, invariant_fn=lambda s: s.check_invariants()
        )
        report = sim.run()
        by_name = {j.name: j for j in sim.jobs}
        assert by_name["high"].preemptions == 0
        assert by_name["low-large"].preemptions == 1
        assert by_name["low-small"].preemptions == 0  # cheapest set only
        # the victim checkpointed at eviction (t=10), resumed, and did NOT
        # restart from scratch: progress at eviction is preserved work
        victim = by_name["low-large"]
        assert victim.final_status == V1Statuses.SUCCEEDED
        assert victim.finished_at == pytest.approx(10 + 50 + 90)
        # high ran immediately after eviction
        assert by_name["high"].started_at == pytest.approx(10)
        assert report["preemptions"] == 1
        # store agrees: the victim's run carries the preempt counter and
        # ended SUCCEEDED via the normal lifecycle
        status = sim.store.get_status(victim.uuid)
        assert status["status"] == V1Statuses.SUCCEEDED
        assert status["meta"]["preempt_restarts"] == 1

    def test_gang_all_or_nothing_waits_for_whole_slice(self):
        jobs = [
            SimJob("half-a", duration=40, arrival=0, chips=4,
                   block=(2, 2), priority=0),
            SimJob("half-b", duration=60, arrival=0, chips=4,
                   block=(2, 2), priority=0),
            SimJob("whole", duration=10, arrival=5, chips=16,
                   block=(4, 4), priority=0),
        ]
        sim = FleetSimulator(
            jobs, topology="4x4", invariant_fn=lambda s: s.check_invariants()
        )
        sim.run()
        by_name = {j.name: j for j in sim.jobs}
        # `whole` needs every chip: it starts only after BOTH halves end —
        # never a partial grab of the free half of the torus
        assert by_name["whole"].started_at == pytest.approx(60)
        assert by_name["whole"].final_status == V1Statuses.SUCCEEDED

    def test_unschedulable_over_quota_ceiling(self):
        jobs = [SimJob("too-big", duration=10, chips=8, project="tiny")]
        sim = FleetSimulator(
            jobs,
            chips=16,
            quotas=[V1QuotaSpec(scope="tiny", max_chips=4)],
        )
        report = sim.run()
        assert report["unschedulable"] == 1
        assert sim.jobs[0].final_status == V1Statuses.UNSCHEDULABLE


# ------------------------------------------------------------- surfaces
def test_fleetz_endpoint_and_metrics(tmp_home):
    from polyaxon_tpu.streams.server import make_server

    store = RunStore()
    Fleet(store).configure(topology="2x2")
    Fleet(store).reserve("r1", chips=2, project="p")
    server = make_server(store, port=0)
    import threading

    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        import urllib.request

        port = server.server_address[1]
        body = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleetz", timeout=5
            ).read()
        )
        assert body["configured"] is True
        assert body["chips_total"] == 4 and body["chips_reserved"] == 2
        assert body["reservations"][0]["uuid"] == "r1"
        metrics = (
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metricsz", timeout=5
            )
            .read()
            .decode()
        )
        assert "fleet_chips_total" in metrics
        assert "fleet_chips_reserved" in metrics
    finally:
        server.shutdown()


def test_openapi_documents_fleetz():
    from polyaxon_tpu.streams.openapi import spec

    assert "/fleetz" in spec()["paths"]
