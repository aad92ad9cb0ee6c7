"""Plan applier tests: per-node verification with partial commit, the
group of queued plans that is verified against one overlay and
committed in one raft entry, and the pipelined
verify-(N+1)-while-committing-(N) path with its failed-commit refresh
(mirror plan_apply.go:41-118,194-313)."""

import json
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import plan_apply
from nomad_tpu.server.fsm import FSM, DevLog
from nomad_tpu.server.plan_apply import OptimisticSnapshot, PlanApplier
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.structs import Allocation, Plan, consts
from nomad_tpu.utils.ids import generate_uuid


def build_world(n_nodes=2, cpu=1000):
    fsm = FSM()
    log = DevLog(fsm)
    nodes = []
    for _ in range(n_nodes):
        node = mock.node()
        node.resources.cpu = cpu
        log.apply("node_register", {"node": node})
        nodes.append(node)
    return fsm, log, nodes


def make_alloc(node, cpu, job):
    alloc = Allocation(
        id=generate_uuid(), job_id=job.id, job=job, node_id=node.id,
        task_group="web", desired_status=consts.ALLOC_DESIRED_RUN,
    )
    alloc.task_resources = {"web": mock.job().task_groups[0].tasks[0].resources.copy()}
    alloc.task_resources["web"].cpu = cpu
    alloc.task_resources["web"].networks = []
    return alloc


def make_plan(node, cpu, job=None, priority=None, count=1):
    job = job or mock.job()
    plan = Plan(job=job)
    if priority is not None:
        plan.priority = priority
    for _ in range(count):
        plan.append_alloc(make_alloc(node, cpu, job))
    return plan


class SlowLog:
    """DevLog wrapper with injectable commit latency/failures; keeps
    what was applied."""

    def __init__(self, inner, delay=0.0):
        self.inner = inner
        self.delay = delay
        self.fail_next = False
        self.applies = []
        self.payloads = []
        self.entered = threading.Semaphore(0)  # one release an apply

    def apply(self, msg_type, payload):
        self.entered.release()
        if self.delay:
            time.sleep(self.delay)
        if self.fail_next:
            self.fail_next = False
            raise TimeoutError("injected commit failure")
        self.applies.append((msg_type, time.monotonic()))
        self.payloads.append(payload)
        return self.inner.apply(msg_type, payload)

    def last_index(self):
        return self.inner.last_index()


def wait_all(pendings):
    results = []
    for pending in pendings:
        try:
            results.append(pending.wait(timeout=20.0))
        except Exception as e:  # noqa: BLE001
            results.append(e)
    return results


def run_applier(fsm, log, plans):
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, log)
    applier.start()
    results = wait_all([queue.enqueue(p) for p in plans])
    applier.stop()
    return results


def run_group(fsm, log, plans):
    """The plans are all in the queue when the applier first looks, so
    they are one group (up to the bound). Returns (results, stats)."""
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, log)
    pendings = [queue.enqueue(p) for p in plans]
    applier.start()
    results = wait_all(pendings)
    applier.stop()
    return results, applier.stats()


def test_plan_applies_and_commits():
    fsm, log, nodes = build_world()
    plan = make_plan(nodes[0], 100)
    (result,) = run_applier(fsm, log, [plan])
    assert not result.is_no_op()
    assert result.alloc_index > 0
    stored = fsm.state.allocs_by_node(nodes[0].id)
    assert len(stored) == 1


def test_partial_commit_rejects_overcommitted_node():
    """Node B can't fit; only node A's placement commits and the result
    carries a refresh index (plan_apply.go partial commit)."""
    fsm, log, nodes = build_world(n_nodes=2, cpu=300)
    job = mock.job()
    plan = Plan(job=job)
    for node, cpu in ((nodes[0], 100), (nodes[1], 10_000)):
        alloc = Allocation(
            id=generate_uuid(), job_id=job.id, job=job, node_id=node.id,
            task_group="web", desired_status=consts.ALLOC_DESIRED_RUN,
        )
        alloc.task_resources = {
            "web": mock.job().task_groups[0].tasks[0].resources.copy()}
        alloc.task_resources["web"].cpu = cpu
        alloc.task_resources["web"].networks = []
        plan.append_alloc(alloc)
    (result,) = run_applier(fsm, log, [plan])
    assert nodes[0].id in result.node_allocation
    assert nodes[1].id not in result.node_allocation
    assert result.refresh_index > 0


def test_pipelined_verification_overlaps_commit():
    """With a slow commit, group N+1's verification runs BEFORE group
    N's commit finishes — the pipelining the reference documents at
    plan_apply.go:19-39 — and the two plans that queued up behind that
    commit share one entry."""
    fsm, devlog, nodes = build_world(n_nodes=3)
    log = SlowLog(devlog, delay=0.3)

    eval_times = []
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, log)
    orig_eval = applier._evaluate_plan

    def traced_eval(snapshot, plan):
        eval_times.append(time.monotonic())
        return orig_eval(snapshot, plan)

    applier._evaluate_plan = traced_eval
    applier.start()
    p1 = queue.enqueue(make_plan(nodes[0], 100))
    assert log.entered.acquire(timeout=10.0)  # commit 1 is in flight
    p2 = queue.enqueue(make_plan(nodes[1], 100))
    p3 = queue.enqueue(make_plan(nodes[2], 100))
    r1, r2, r3 = wait_all([p1, p2, p3])
    applier.stop()
    assert r1.alloc_index > 0
    assert r2.alloc_index == r3.alloc_index > r1.alloc_index
    assert len(eval_times) == 3 and len(log.applies) == 2
    assert [len(p["plans"]) for p in log.payloads] == [1, 2]
    # plans 2 and 3 were verified before plan 1's commit landed
    commit1_done = log.applies[0][1]
    assert eval_times[2] < commit1_done, (
        f"no overlap: eval3 at {eval_times[2]}, commit1 done {commit1_done}")


def test_optimistic_view_sees_inflight_allocs():
    """Two plans placing on the SAME nearly-full node: the second must
    be rejected because the optimistic view includes the first's
    in-flight alloc (no double-commit of the same capacity)."""
    fsm, devlog, nodes = build_world(n_nodes=1, cpu=500)
    log = SlowLog(devlog, delay=0.2)
    plans = [make_plan(nodes[0], 250), make_plan(nodes[0], 250)]
    r1, r2 = run_applier(fsm, log, plans)
    assert r1.alloc_index > 0
    # second plan rejected at verification: partial-commit empty result
    assert r2.is_no_op() or not r2.node_allocation
    assert r2.refresh_index > 0
    stored = fsm.state.allocs_by_node(nodes[0].id)
    assert len(stored) == 1  # capacity was never double-committed


@pytest.mark.parametrize("doomed", [1, 2])
def test_failed_commit_forces_fresh_verification(doomed):
    """The first entry fails: every plan of its group is told so. The
    plan behind it was verified against a view holding the group's
    phantom allocs (which fill the node); it re-verifies on fresh
    state, fits and commits."""
    fsm, devlog, nodes = build_world(n_nodes=1, cpu=500)
    log = SlowLog(devlog, delay=0.1)
    log.fail_next = True  # first commit blows up
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, log)
    group = [queue.enqueue(make_plan(nodes[0], 250 // doomed))
             for _ in range(doomed)]
    applier.start()
    assert log.entered.acquire(timeout=10.0)  # the doomed commit runs
    behind = queue.enqueue(make_plan(nodes[0], 250))
    *failed, survivor = wait_all(group + [behind])
    applier.stop()
    assert len(failed) == doomed
    assert all(isinstance(r, TimeoutError) for r in failed)
    assert not isinstance(survivor, Exception)
    assert survivor.alloc_index > 0
    stored = fsm.state.allocs_by_node(nodes[0].id)
    assert len(stored) == 1
    assert applier.stats()["commits"] == 1


# --------- the group: what is queued when the applier looks ----------


@pytest.mark.parametrize("n_plans", [1, 3, 8])
def test_queued_plans_share_one_entry(n_plans):
    """N plans queued together: one raft entry, N results of their own
    with the entry's index, each holding its own plan's allocations
    stamped with it."""
    fsm, devlog, nodes = build_world(n_nodes=n_plans)
    log = SlowLog(devlog)
    plans = [make_plan(node, 100) for node in nodes]
    results, stats = run_group(fsm, log, plans)
    assert len(log.applies) == 1
    assert len(log.payloads[0]["plans"]) == n_plans
    assert len({id(r) for r in results}) == n_plans
    assert {r.alloc_index for r in results} == {devlog.last_index()}
    for plan, node, result in zip(plans, nodes, results):
        (alloc,) = result.node_allocation[node.id]
        assert alloc.id == plan.node_allocation[node.id][0].id
        assert alloc.create_index == alloc.modify_index == result.alloc_index
        assert result.refresh_index == 0
        stored = fsm.state.alloc_by_id(alloc.id)
        assert stored.job is plan.job and stored.create_index == alloc.create_index
    assert (stats["commits"], stats["plans_committed"],
            stats["largest_group"]) == (1, n_plans, n_plans)


def test_group_mates_overcommitting_one_node():
    """Two plans of one group on the same nearly-full node: the second
    loses the node to the first, which has not landed yet, and its
    refresh index is past the entry that holds the first."""
    fsm, devlog, nodes = build_world(n_nodes=2, cpu=500)
    log = SlowLog(devlog)
    loser = make_plan(nodes[0], 250)
    loser.append_alloc(make_alloc(nodes[1], 100, loser.job))
    (r1, r2), stats = run_group(fsm, log, [make_plan(nodes[0], 250), loser])
    assert len(log.applies) == 1 and stats["plans_committed"] == 2
    assert nodes[0].id in r1.node_allocation
    assert nodes[0].id not in r2.node_allocation
    assert nodes[1].id in r2.node_allocation  # partial commit, as ever
    assert r2.alloc_index == r1.alloc_index
    assert r2.refresh_index >= r1.alloc_index
    assert len(fsm.state.allocs_by_node(nodes[0].id)) == 1
    assert stats["plans_rejected"] == 1 and stats["nodes_rejected"] == 1


@pytest.mark.parametrize("priorities, winner", [
    ((50, 90), 1),   # the later, higher-priority plan is verified first
    ((90, 50), 0),
    ((50, 50), 0),   # equal priority: arrival order
])
def test_priority_order_inside_a_group(priorities, winner):
    fsm, devlog, nodes = build_world(n_nodes=1, cpu=500)
    plans = [make_plan(nodes[0], 250, priority=p) for p in priorities]
    results, stats = run_group(fsm, SlowLog(devlog), plans)
    assert nodes[0].id in results[winner].node_allocation
    assert not results[1 - winner].node_allocation
    assert results[1 - winner].refresh_index > 0
    assert stats["commits"] == 1 and stats["plans_committed"] == 1


@pytest.mark.parametrize("sizes, bound, want", [
    ([1, 1, 1, 1], 3, [[1, 1, 1], [1]]),      # the bound cuts a group
    ([2, 2], 4, [[2, 2]]),                    # up to the bound, inclusive
    ([5], 3, [[5]]),                          # oversized: alone
    ([1, 5, 1], 3, [[1], [5], [1]]),          # and order is kept around it
    ([1, 1], plan_apply.MAX_GROUP_ALLOCS, [[1, 1]]),
])
def test_allocation_bound_cuts_a_group(monkeypatch, sizes, bound, want):
    """The bound is on the summed allocations of the group; the first
    plan is always taken, so one larger than the bound goes alone."""
    monkeypatch.setattr(plan_apply, "MAX_GROUP_ALLOCS", bound)
    fsm, devlog, nodes = build_world(n_nodes=1, cpu=100_000)
    log = SlowLog(devlog)
    plans = [make_plan(nodes[0], 10, count=n) for n in sizes]
    results, stats = run_group(fsm, log, plans)
    assert [[len(part["allocs"]) for part in p["plans"]]
            for p in log.payloads] == want
    assert all(r.alloc_index > 0 for r in results)
    assert stats["largest_group"] == max(len(g) for g in want)
    assert len(fsm.state.allocs_by_node(nodes[0].id)) == sum(sizes)


def test_lone_plan_is_answered_without_waiting():
    """The applier never waits to fill a group: a plan that arrives at
    an idle applier is a group of one, committed alone and answered
    at once (the bound is loose for a loaded test machine; the loop
    has no window to wait out)."""
    fsm, devlog, nodes = build_world(n_nodes=1)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, devlog)
    applier.start()
    try:
        time.sleep(0.05)  # the loop is parked in the queue
        start = time.monotonic()
        result = queue.enqueue(make_plan(nodes[0], 100)).wait(timeout=10.0)
        elapsed = time.monotonic() - start
    finally:
        applier.stop()
    assert result.alloc_index > 0
    assert elapsed < 1.0, elapsed
    stats = applier.stats()
    assert (stats["commits"], stats["largest_group"]) == (1, 1)


def store_view(fsm):
    """What an alloc_update entry leaves in a store, to compare two."""
    return sorted(
        (a.id, a.node_id, a.job_id, a.job.id if a.job else None,
         a.job.priority if a.job else None, a.desired_status,
         a.client_status, a.create_index, a.modify_index)
        for a in fsm.state.allocs())


def replica_of(nodes, jobs):
    fsm = FSM()
    log = DevLog(fsm)
    for node in nodes:
        log.apply("node_register", {"node": node})
    for job in jobs:
        log.apply("job_register", {"job": job})
    return fsm, log


@pytest.mark.parametrize("form", ["allocs", "allocs_job", "plans"])
def test_every_alloc_update_form_applies(form):
    """`{"allocs"}` with the jobs attached (as benchmark/fleet.py sends
    its fillers), `{"allocs", "job"}` (one plan, job detached) and the
    applier's `{"plans"}` leave the same store."""
    from nomad_tpu.server.fsm import ALLOC_UPDATE

    nodes = [mock.node() for _ in range(2)]
    jobs = [mock.job(), mock.job()]
    allocs = [make_alloc(nodes[i], 100, jobs[i]) for i in range(2)]
    want_fsm, want_log = replica_of(nodes, jobs)
    for alloc in allocs:
        want_log.apply(ALLOC_UPDATE, {"allocs": [alloc.copy()]})
    fsm, log = replica_of(nodes, jobs)
    detached = [a.copy() for a in allocs]
    if form != "allocs":
        for alloc in detached:
            alloc.job = None
    if form == "allocs":
        log.apply(ALLOC_UPDATE, {"allocs": detached})
    elif form == "allocs_job":
        for alloc, job in zip(detached, jobs):
            log.apply(ALLOC_UPDATE, {"allocs": [alloc], "job": job})
    else:
        log.apply(ALLOC_UPDATE, {"plans": [
            {"allocs": [alloc], "job": job}
            for alloc, job in zip(detached, jobs)]})
    strip = [row[:7] for row in store_view(fsm)]  # indexes differ by form
    assert strip == [row[:7] for row in store_view(want_fsm)]
    assert all(row[3] == row[2] for row in store_view(fsm))  # job attached


def test_group_entry_over_the_wire_and_on_replay():
    """The group's entry, as the applier wrote it, through the raft
    transport's codec (and JSON, as the TCP frames and the log store
    carry it): a follower that applies the decoded entry, and a replay
    of it, hold the leader's store."""
    from nomad_tpu.server.transport import (_encode_payload,
                                            fsm_payload_decoder)

    nodes = [mock.node() for _ in range(3)]
    jobs = [mock.job() for _ in range(3)]
    jobs[2].priority = 70
    leader, devlog = replica_of(nodes, jobs)
    log = SlowLog(devlog)
    plans = [make_plan(node, 100, job=job, count=2)
             for node, job in zip(nodes, jobs)]
    # The applier's plans carry allocations without their job: the
    # entry's own `job` of each part is what re-attaches it.
    for plan in plans:
        for allocs in plan.node_allocation.values():
            for alloc in allocs:
                alloc.job = None
    results, _stats = run_group(leader, log, plans)
    assert len(log.payloads) == 1 and all(r.alloc_index for r in results)
    wire = json.dumps(_encode_payload(log.payloads[0]))
    views = []
    for _ in ("follower", "replay"):
        fsm, replica_log = replica_of(nodes, jobs)
        replica_log.apply(
            "alloc_update",
            fsm_payload_decoder("alloc_update", json.loads(wire)))
        views.append(store_view(fsm))
    assert views[0] == views[1] == store_view(leader)
    assert len(views[0]) == 6
    assert {row[4] for row in views[0]} == {50, 70}  # each its own job


@pytest.mark.parametrize("kind", ["all_at_once", "gang"])
def test_plan_rejected_whole_leaves_nothing_in_the_overlay(kind):
    """A plan that verification removes whole (`all_at_once`, or a gang
    with one member on a node that does not fit) has a part that DID
    fit, on node 0. The next plan of the group needs that room: it gets
    it, so nothing of the rejected plan stayed in the view."""
    from nomad_tpu.gang import gang_key

    fsm, devlog, nodes = build_world(n_nodes=2, cpu=500)
    job = mock.job()
    whole = Plan(job=job, all_at_once=(kind == "all_at_once"))
    for node, cpu in ((nodes[0], 250), (nodes[1], 10_000)):
        alloc = make_alloc(node, cpu, job)
        if kind == "gang":
            whole.append_gang_alloc(gang_key(job.id, "web"), alloc)
        else:
            whole.append_alloc(alloc)
    (r1, r2), stats = run_group(
        fsm, SlowLog(devlog), [whole, make_plan(nodes[0], 250)])
    assert r1.is_no_op() and r1.refresh_index > 0
    assert nodes[0].id in r2.node_allocation and r2.refresh_index == 0
    assert stats["commits"] == 1 and stats["plans_committed"] == 1
    assert stats["gangs_rejected"] == (1 if kind == "gang" else 0)
    assert len(fsm.state.allocs_by_node(nodes[0].id)) == 1


def test_optimistic_snapshot_reads():
    fsm, log, nodes = build_world(n_nodes=1)
    base = fsm.state.snapshot()
    opt = OptimisticSnapshot(base)
    assert opt.node_by_id(nodes[0].id) is not None
    assert opt.allocs_by_node_terminal(nodes[0].id, False) == []

    from nomad_tpu.structs import PlanResult

    alloc = Allocation(id="a1", node_id=nodes[0].id, job_id="j")
    opt.add_result(PlanResult(node_allocation={nodes[0].id: [alloc]}))
    live = opt.allocs_by_node_terminal(nodes[0].id, False)
    assert [a.id for a in live] == ["a1"]
    # eviction hides an alloc from the base view
    opt2 = OptimisticSnapshot(base)
    opt2.add_result(PlanResult(node_update={nodes[0].id: [alloc]}))
    assert all(a.id != "a1"
               for a in opt2.allocs_by_node_terminal(nodes[0].id, False))


def test_base_refreshes_after_each_commit():
    """External state changes applied between commits are visible to
    later plans (the base rebases per commit, bounding staleness)."""
    fsm, devlog, nodes = build_world(n_nodes=2)
    log = SlowLog(devlog, delay=0.05)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, log)
    applier.start()
    try:
        p1 = queue.enqueue(make_plan(nodes[0], 100))
        assert p1.wait(timeout=10.0).alloc_index > 0
        # Drain node 1 OUTSIDE the plan pipeline while the queue idles.
        devlog.apply("node_update_drain",
                     {"node_id": nodes[1].id, "drain": True})
        p2 = queue.enqueue(make_plan(nodes[1], 100))
        r2 = p2.wait(timeout=10.0)
        # the applier saw the drain: nothing placed on the drained node
        assert not r2.node_allocation
    finally:
        applier.stop()


def test_rejected_plan_refresh_index_covers_inflight_commit():
    """A plan rejected because of an IN-FLIGHT plan's allocs gets a
    refresh_index beyond the pre-commit state, so the worker actually
    waits for the commit instead of spinning."""
    fsm, devlog, nodes = build_world(n_nodes=1, cpu=500)
    log = SlowLog(devlog, delay=0.2)
    pre_index = fsm.state.latest_index()
    plans = [make_plan(nodes[0], 250), make_plan(nodes[0], 250)]
    r1, r2 = run_applier(fsm, log, plans)
    assert r1.alloc_index > 0
    assert not r2.node_allocation
    assert r2.refresh_index > pre_index


def test_rejected_plan_does_not_pin_stale_base():
    """A rejection with no commit in flight must not stick the NEXT
    plan to the same stale snapshot: capacity freed between plans is
    seen (the pre-pipelining fresh-snapshot-per-plan invariant)."""
    fsm, devlog, nodes = build_world(n_nodes=1, cpu=500)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, devlog)
    applier.start()
    try:
        # Fill the node.
        p1 = queue.enqueue(make_plan(nodes[0], 250))
        r1 = p1.wait(timeout=10.0)
        assert r1.alloc_index > 0
        big_alloc_id = next(iter(r1.node_allocation.values()))[0].id
        # Second plan rejected: node is full.
        p2 = queue.enqueue(make_plan(nodes[0], 250))
        r2 = p2.wait(timeout=10.0)
        assert not r2.node_allocation
        # Free the capacity OUTSIDE the plan pipeline (client update).
        stored = fsm.state.alloc_by_id(big_alloc_id)
        freed = stored.copy()
        freed.desired_status = consts.ALLOC_DESIRED_STOP
        freed.client_status = consts.ALLOC_CLIENT_COMPLETE
        devlog.apply("alloc_update", {"allocs": [freed], "job": stored.job})
        # The next plan must see the freed capacity and commit.
        p3 = queue.enqueue(make_plan(nodes[0], 250))
        r3 = p3.wait(timeout=10.0)
        assert r3.alloc_index > 0, "stale base pinned after rejection"
    finally:
        applier.stop()


# --------- evaluate_node_plan edges (plan_apply.go:318 test family) ---


def test_eval_node_plan_not_ready():
    from nomad_tpu.server.plan_apply import evaluate_node_plan

    fsm, log, nodes = build_world(n_nodes=1)
    log.apply("node_update_status",
              {"node_id": nodes[0].id, "status": consts.NODE_STATUS_DOWN})
    snap = fsm.state.snapshot()
    assert evaluate_node_plan(snap, make_plan(nodes[0], 100), nodes[0].id) is False


def test_eval_node_plan_draining():
    from nomad_tpu.server.plan_apply import evaluate_node_plan

    fsm, log, nodes = build_world(n_nodes=1)
    log.apply("node_update_drain", {"node_id": nodes[0].id, "drain": True})
    snap = fsm.state.snapshot()
    assert evaluate_node_plan(snap, make_plan(nodes[0], 100), nodes[0].id) is False


def test_eval_node_plan_missing_node():
    from nomad_tpu.server.plan_apply import evaluate_node_plan

    fsm, log, nodes = build_world(n_nodes=1)
    plan = make_plan(nodes[0], 100)
    # rewrite the plan to target a node that does not exist
    plan.node_allocation = {"ghost": plan.node_allocation[nodes[0].id]}
    for a in plan.node_allocation["ghost"]:
        a.node_id = "ghost"
    snap = fsm.state.snapshot()
    assert evaluate_node_plan(snap, plan, "ghost") is False


def test_eval_node_plan_evictions_only_always_safe():
    """A plan that only stops allocs passes even on a down node
    (plan_apply.go:318 early return)."""
    from nomad_tpu.server.plan_apply import evaluate_node_plan
    from nomad_tpu.structs import Plan

    fsm, log, nodes = build_world(n_nodes=1)
    job = mock.job()
    alloc = mock.alloc()
    alloc.node_id = nodes[0].id
    log.apply("node_update_status",
              {"node_id": nodes[0].id, "status": consts.NODE_STATUS_DOWN})
    plan = Plan(job=job)
    plan.node_update = {nodes[0].id: [alloc]}
    snap = fsm.state.snapshot()
    assert evaluate_node_plan(snap, plan, nodes[0].id) is True


def test_eval_node_plan_update_existing_in_place():
    """Evicting an alloc and re-placing its replacement on the same
    node in one plan fits (the in-place update shape,
    TestPlanApply_EvalNodePlan_UpdateExisting)."""
    from nomad_tpu.server.plan_apply import evaluate_node_plan
    from nomad_tpu.structs import Plan

    fsm, log, nodes = build_world(n_nodes=1, cpu=500)
    job = mock.job()
    old = make_plan(nodes[0], 300, job=job).node_allocation[nodes[0].id][0]
    log.apply("alloc_update", {"allocs": [old], "job": job})

    replacement = make_plan(nodes[0], 300, job=job)
    replacement.node_update = {nodes[0].id: [old]}
    snap = fsm.state.snapshot()
    # without the eviction the node would be full; with it, it fits
    assert evaluate_node_plan(snap, replacement, nodes[0].id) is True


def test_eval_node_plan_node_full():
    from nomad_tpu.server.plan_apply import evaluate_node_plan

    fsm, log, nodes = build_world(n_nodes=1, cpu=500)
    job = mock.job()
    old = make_plan(nodes[0], 300, job=job).node_allocation[nodes[0].id][0]
    log.apply("alloc_update", {"allocs": [old], "job": job})
    snap = fsm.state.snapshot()
    assert evaluate_node_plan(
        snap, make_plan(nodes[0], 300), nodes[0].id) is False


def test_gang_commit_all_at_once_rejects_whole_plan():
    """TestPlanApply_EvalPlan_Partial_AllAtOnce: with all_at_once, one
    failing node rejects the entire plan."""
    fsm, log, nodes = build_world(n_nodes=2, cpu=300)
    job = mock.job()
    from nomad_tpu.structs import Allocation, Plan
    from nomad_tpu.utils.ids import generate_uuid

    plan = Plan(job=job, all_at_once=True)
    for node, cpu in ((nodes[0], 100), (nodes[1], 10_000)):
        alloc = Allocation(
            id=generate_uuid(), job_id=job.id, job=job, node_id=node.id,
            task_group="web", desired_status=consts.ALLOC_DESIRED_RUN,
        )
        alloc.task_resources = {
            "web": mock.job().task_groups[0].tasks[0].resources.copy()}
        alloc.task_resources["web"].cpu = cpu
        alloc.task_resources["web"].networks = []
        plan.append_alloc(alloc)
    (result,) = run_applier(fsm, log, [plan])
    assert result.node_allocation == {} and result.node_update == {}
    assert result.refresh_index > 0


def test_rejection_past_matrix_watermark_is_ordinary_conflict():
    """A rejection explained by allocs that landed AFTER the plan's
    matrix watermark is an ordinary optimistic-concurrency loss: the
    device-resident chain must NOT be marked stale for it (a
    conflict-heavy storm would otherwise purge the base cache per
    rejection and degenerate into rebuild-per-snapshot)."""
    from nomad_tpu.models.resident import get_tracker

    fsm, log, nodes = build_world(n_nodes=1, cpu=500)
    get_tracker().consume_stale()  # clear any leftover flag
    wm = fsm.state.latest_index()
    (first,) = run_applier(fsm, log, [make_plan(nodes[0], 300)])
    assert not first.is_no_op()
    loser = make_plan(nodes[0], 300)
    loser.matrix_index = wm  # planned before the winner committed
    (result,) = run_applier(fsm, log, [loser])
    assert nodes[0].id not in result.node_allocation
    assert not get_tracker().consume_stale()


def test_rejection_at_own_watermark_marks_resident_chain_stale():
    """A rejection with NO node/alloc change past the watermark means
    the matrix claimed a fit its own snapshot refutes — only resident
    staleness explains that, so the safety net must fire."""
    from nomad_tpu.models.resident import get_tracker

    fsm, log, nodes = build_world(n_nodes=1, cpu=500)
    (first,) = run_applier(fsm, log, [make_plan(nodes[0], 300)])
    assert not first.is_no_op()
    get_tracker().consume_stale()
    doomed = make_plan(nodes[0], 300)
    doomed.matrix_index = fsm.state.latest_index()  # saw everything
    (result,) = run_applier(fsm, log, [doomed])
    assert nodes[0].id not in result.node_allocation
    assert get_tracker().consume_stale()


def test_rejection_without_watermark_stays_conservative():
    """Plans minted off the host path carry no watermark: a rejection
    keeps marking the chain suspect (the safe pre-watermark default)."""
    from nomad_tpu.models.resident import get_tracker

    fsm, log, nodes = build_world(n_nodes=1, cpu=500)
    (first,) = run_applier(fsm, log, [make_plan(nodes[0], 300)])
    assert not first.is_no_op()
    get_tracker().consume_stale()
    (result,) = run_applier(fsm, log, [make_plan(nodes[0], 300)])
    assert nodes[0].id not in result.node_allocation
    assert get_tracker().consume_stale()
