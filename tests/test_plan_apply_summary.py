"""The applier's node summaries against the rule they restate.

Parity: seeded random sequences of plans go through the applier's own
loop (`PlanApplier._turn`, the commit pool made lazy so that a commit
lands when the test says), and every verification is held against
`evaluate_node_plan` run over the full proposed list on the same view:
the accepted and rejected node sets and `refresh_index` are equal, plan
for plan, and after every turn each summary the applier carries equals
one built afresh from the view.

Cost: ten consecutive 1,000-allocation plans read the store's per-node
index at most once a node, whatever stands there.
"""

import random
import time
from types import SimpleNamespace

import pytest

from nomad_tpu import mock, trace
from nomad_tpu.server import fsm as fsm_mod
from nomad_tpu.server.fsm import FSM, DevLog
from nomad_tpu.server.plan_apply import (
    NodeSummary,
    OptimisticSnapshot,
    PlanApplier,
    evaluate_node_plan,
)
from nomad_tpu.server.plan_queue import PendingPlan, PlanQueue
from nomad_tpu.state import store as store_mod
from nomad_tpu.structs import (
    Allocation,
    NetworkResource,
    Plan,
    Port,
    Resources,
    allocs_fit,
    consts,
    usage_fits,
)
from nomad_tpu.utils.ids import generate_uuid


class _Later:
    """A commit in flight that lands when it is asked for, or when the
    test lands it early (the FSM applied, the applier not yet told)."""

    def __init__(self, fn, fail: bool):
        self._fn = fn
        self._fail = fail
        self._done = None

    def land(self):
        if self._done is None:
            if self._fail:
                self._done = (None, RuntimeError("commit lost"))
            else:
                self._done = (self._fn(), None)
        return self._done

    def result(self):
        index, error = self.land()
        if error is not None:
            raise error
        return index


class _LazyPool:
    def __init__(self):
        self.fail_next = False
        self.failed = 0

    def submit(self, fn, accepted):
        fail, self.fail_next = self.fail_next, False
        self.failed += fail
        if fail:
            # What _commit does for its waiters when the apply fails.
            for pending, _result in accepted:
                pending.respond(None, RuntimeError("commit lost"))
        return _Later(lambda: fn(accepted), fail)

    def shutdown(self, *a, **k):
        pass


def small_node() -> "mock.Node":
    node = mock.node()
    node.resources.cpu = 1000
    node.resources.memory_mb = 1000
    node.resources.disk_mb = 1000
    node.resources.iops = 100
    node.resources.networks[0].mbits = 100
    node.reserved.cpu = 100
    node.reserved.memory_mb = 100
    node.reserved.disk_mb = 100
    node.reserved.iops = 10
    return node


# What each case turns on. `tight` names the dimension the asks are
# large in; the other keys are chances a turn (or a plan) takes.
FLAVOURS = {
    "cpu": {"tight": "cpu"},
    "memory": {"tight": "memory_mb"},
    "disk": {"tight": "disk_mb"},
    "iops": {"tight": "iops"},
    "bandwidth": {"tight": "mbits"},
    "ports": {"tight": "ports"},
    "port_twice": {"tight": "ports", "port_twice": 0.4},
    "stops": {"tight": "cpu", "stops": 0.8},
    "preemptions": {"tight": "cpu", "preempt": 0.7},
    "inplace": {"tight": "memory_mb", "inplace": 0.6},
    "all_at_once": {"tight": "cpu", "all_at_once": 0.5},
    "drain": {"tight": "cpu", "drain": 0.4},
    "node_change": {"tight": "disk_mb", "reregister": 0.4},
    "client_terminal": {"tight": "cpu", "client": 0.7, "inplace": 0.3},
    "collected": {"tight": "cpu", "collect": 0.5},
    "foreign_upsert": {"tight": "cpu", "upsert": 0.5},
    "failed_commit": {"tight": "cpu", "fail": 0.3},
    "journal_short": {"tight": "cpu", "journal_cap": 6},
    "mixed": {"tight": "any", "stops": 0.5, "preempt": 0.3, "inplace": 0.3,
              "all_at_once": 0.1, "drain": 0.15, "reregister": 0.1,
              "client": 0.3, "collect": 0.15, "upsert": 0.15, "fail": 0.1,
              "port_twice": 0.1},
}
TIGHT = ("cpu", "memory_mb", "disk_mb", "iops", "mbits", "ports")


def _by_id(alloc: Allocation) -> str:
    return alloc.id


class Harness:
    def __init__(self, seed: int, flavour: dict, n_nodes: int = 5):
        self.rng = random.Random(seed)
        self.flavour = flavour
        self.fsm = FSM()
        self.log = DevLog(self.fsm)
        self.nodes = []
        # Every id comes from the seed, and every list is sorted before
        # it is drawn from: a case is the same sequence in every run.
        for _ in range(n_nodes):
            node = small_node()
            node.id = self._id()
            self.log.apply(fsm_mod.NODE_REGISTER, {"node": node})
            self.nodes.append(node)
        self.jobs = []
        for priority in (30, 50, 70):
            job = mock.job()
            job.id = self._id()
            job.priority = priority
            self.jobs.append(job)
        self.applier = PlanApplier(PlanQueue(), self.fsm, self.log)
        self.pool = self.applier._commit_pool = _LazyPool()
        self.inflight = None
        self.overlay = None
        self.seen = {"accepted": 0, "rejected": 0, "plans": 0}
        self.raised = []
        self._check_verdicts()

    def _id(self) -> str:
        return f"{self.rng.getrandbits(64):016x}"

    # ---------------------------------------------------------- oracle

    def _check_verdicts(self) -> None:
        applier, seen = self.applier, self.seen
        verify = applier._evaluate_plan

        def checked(view, plan):
            try:
                return compared(view, plan)
            except BaseException as e:
                # The applier answers the plan with what verifying it
                # raised and goes on: the turn raises it again.
                self.raised.append(e)
                raise

        def compared(view, plan):
            nodes = (set(plan.node_update) | set(plan.node_allocation)
                     | set(plan.node_preemptions))
            lost = {n for n in nodes
                    if not evaluate_node_plan(view, plan, n)}
            result = verify(view, plan)
            seen["plans"] += 1
            seen["rejected"] += len(lost)
            seen["accepted"] += len(nodes - lost)
            if plan.all_at_once and lost:
                assert result.is_no_op()
            else:
                for got, asked in (
                        (result.node_update, plan.node_update),
                        (result.node_allocation, plan.node_allocation),
                        (result.node_preemptions, plan.node_preemptions)):
                    assert set(got) == set(asked) - lost
                    for node_id, allocs in got.items():
                        assert allocs is asked[node_id]
            assert result.refresh_index == (
                view.latest_index() if lost else 0)
            return result

        applier._evaluate_plan = checked

    def check_summaries(self) -> None:
        """Every summary the applier carries, against one built from
        the view's own list."""
        view = self.overlay
        if view is None:
            return
        for node_id, carried in self.applier._summaries._by_node.items():
            node = view.node_by_id(node_id)
            if carried.node is not node:
                continue  # outdated by the node's own write: `of` rebuilds
            fresh = NodeSummary(
                node, view.allocs_by_node_terminal(node_id, False))
            assert set(carried.shares) == set(fresh.shares)
            for field in ("cpu", "memory_mb", "disk_mb", "iops",
                          "collisions", "ports", "avail"):
                assert getattr(carried, field) == getattr(fresh, field), field
            used = {d: v for d, v in carried.bandwidth.items() if v}
            assert used == {d: v for d, v in fresh.bandwidth.items() if v}

    # ------------------------------------------------------- generator

    def _ask(self, node_id: str, job, alloc_id: str = "") -> Allocation:
        rng = self.rng
        tight = self.flavour["tight"]
        if tight == "any":
            tight = rng.choice(TIGHT)
        size = {d: rng.randint(5, 40) for d in ("cpu", "memory_mb",
                                                "disk_mb")}
        size["iops"] = rng.randint(0, 3)
        if tight in size:
            size[tight] = (rng.randint(15, 45) if tight == "iops"
                           else rng.randint(150, 450))
        net = []
        if tight in ("mbits", "ports") or rng.random() < 0.2:
            n = NetworkResource(device="eth0", ip="192.168.0.100",
                                mbits=rng.randint(1, 5))
            if tight == "mbits":
                n.mbits = rng.randint(15, 45)
            if tight == "ports":
                n.reserved_ports = [
                    Port("p", rng.choice((22, 8000, 8001, 8002, 8003, 8004)))]
                n.dynamic_ports = [Port("d", rng.randint(20000, 20006))]
                if rng.random() < self.flavour.get("port_twice", 0):
                    twice = rng.choice(n.reserved_ports + n.dynamic_ports)
                    n.dynamic_ports.append(Port("again", twice.value))
                if rng.random() < 0.03:
                    n.reserved_ports.append(Port("bad", 70000))
            net = [n]
        task = Resources(cpu=size["cpu"], memory_mb=size["memory_mb"],
                         iops=size["iops"], networks=net)
        alloc = Allocation(
            id=alloc_id or self._id(), eval_id="e", job_id=job.id,
            node_id=node_id, task_group="web", name=f"{job.id}.web[0]",
            task_resources={"web": task},
            shared_resources=Resources(disk_mb=size["disk_mb"]),
            desired_status=consts.ALLOC_DESIRED_RUN,
            client_status=consts.ALLOC_CLIENT_PENDING)
        if rng.random() < 0.3:
            # An allocation that carries its combined resources.
            alloc.resources = Resources(
                cpu=task.cpu, memory_mb=task.memory_mb,
                disk_mb=size["disk_mb"], iops=task.iops,
                networks=[n.copy() for n in net])
        return alloc

    def _plan(self) -> Plan:
        rng, flavour = self.rng, self.flavour
        state = self.fsm.state.snapshot()  # what a scheduler would see
        job = rng.choice(self.jobs)
        plan = Plan(job=job, eval_id=self._id(),
                    priority=rng.choice((40, 60, 80)))
        plan.all_at_once = rng.random() < flavour.get("all_at_once", 0)
        for node in rng.sample(self.nodes, rng.randint(1, 3)):
            every = sorted(state.allocs_by_node(node.id), key=_by_id)
            live = [a for a in every if not a.terminal_status()]
            if every and rng.random() < flavour.get("stops", 0.25):
                # Mostly the live; now and then one that is gone already.
                for alloc in rng.sample(live or every,
                                        min(len(live or every),
                                            rng.randint(1, 2))):
                    plan.append_update(alloc, consts.ALLOC_DESIRED_STOP, "")
                if rng.random() < 0.2:
                    plan.append_update(rng.choice(every),
                                       consts.ALLOC_DESIRED_STOP, "")
            if every and rng.random() < flavour.get("preempt", 0):
                for alloc in rng.sample(every, min(len(every),
                                                   rng.randint(1, 2))):
                    plan.append_preemption(
                        alloc, consts.ALLOC_DESIRED_EVICT, "")
            if rng.random() < 0.85:
                for _ in range(rng.randint(1, 2)):
                    plan.append_alloc(self._ask(node.id, job))
            if live and rng.random() < flavour.get("inplace", 0):
                held = rng.choice(live)
                plan.append_alloc(self._ask(node.id, job, held.id))
            if rng.random() < flavour.get("port_twice", 0) * 0.5:
                # The same allocation staged twice: the later one counts.
                again = rng.choice(plan.node_allocation.get(node.id)
                                   or [self._ask(node.id, job)])
                plan.append_alloc(self._ask(node.id, job, again.id))
        return plan

    def _others_write(self) -> None:
        """What reaches the store past the applier, between turns."""
        rng, flavour, log = self.rng, self.flavour, self.log
        state = self.fsm.state.snapshot()
        if rng.random() < flavour.get("drain", 0):
            node = state.node_by_id(rng.choice(self.nodes).id)
            if rng.random() < 0.5:
                log.apply(fsm_mod.NODE_UPDATE_DRAIN,
                          {"node_id": node.id, "drain": not node.drain})
            else:
                status = (consts.NODE_STATUS_DOWN
                          if node.status == consts.NODE_STATUS_READY
                          else consts.NODE_STATUS_READY)
                log.apply(fsm_mod.NODE_UPDATE_STATUS,
                          {"node_id": node.id, "status": status})
        if rng.random() < flavour.get("reregister", 0):
            node = state.node_by_id(rng.choice(self.nodes).id).copy()
            node.resources.cpu = rng.choice((600, 1000, 1400))
            node.resources.disk_mb = rng.choice((700, 1000, 1300))
            node.reserved.memory_mb = rng.choice((50, 100, 300))
            log.apply(fsm_mod.NODE_REGISTER, {"node": node})
        allocs = sorted(state.allocs(), key=_by_id)
        # A client reports on what it runs: nothing comes back to life.
        running = [a for a in allocs if a.client_status in (
            consts.ALLOC_CLIENT_PENDING, consts.ALLOC_CLIENT_RUNNING)]
        if running and rng.random() < flavour.get("client", 0):
            updates = []
            for alloc in rng.sample(running, min(len(running),
                                                 rng.randint(1, 3))):
                update = alloc.copy()
                update.client_status = rng.choice((
                    consts.ALLOC_CLIENT_RUNNING, consts.ALLOC_CLIENT_FAILED,
                    consts.ALLOC_CLIENT_COMPLETE))
                updates.append(update)
            log.apply(fsm_mod.ALLOC_CLIENT_UPDATE, {"allocs": updates})
        if rng.random() < flavour.get("upsert", 0):
            # Allocations written past the applier (another leader's
            # entries, an operator's tool): new ones, and one that
            # stands with other resources.
            job = rng.choice(self.jobs)
            written = [self._ask(rng.choice(self.nodes).id, job)]
            live = [a for a in allocs if not a.terminal_status()]
            if live:
                held = rng.choice(live)
                written.append(self._ask(held.node_id, job, held.id))
            log.apply(fsm_mod.ALLOC_UPDATE, {"allocs": written, "job": job})
        if allocs and rng.random() < flavour.get("collect", 0):
            gone = rng.sample(allocs, min(len(allocs), rng.randint(1, 2)))
            log.apply(fsm_mod.EVAL_DELETE,
                      {"eval_ids": [], "alloc_ids": [a.id for a in gone]})

    # ------------------------------------------------------------ loop

    def turn(self) -> None:
        rng = self.rng
        if self.inflight is not None and rng.random() < 0.5:
            # The entry has applied and the applier has not heard: what
            # the next plans are made from holds it, the view does not.
            self.inflight.land()
        self._others_write()
        if rng.random() < 0.15:
            group = []  # the queue stood empty
        else:
            group = [PendingPlan(self._plan())
                     for _ in range(rng.randint(1, 3))]
        self.pool.fail_next = rng.random() < self.flavour.get("fail", 0)
        self.inflight, self.overlay = self.applier._turn(
            group, self.inflight, self.overlay)
        if self.raised:
            raise self.raised[0]
        self.check_summaries()


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
def test_summary_verdicts_equal_the_full_list(flavour, seed, monkeypatch):
    spec = FLAVOURS[flavour]
    if "journal_cap" in spec:
        monkeypatch.setattr(store_mod, "_ALLOC_JOURNAL_CAP",
                            spec["journal_cap"])
    h = Harness(seed * 7919 + len(flavour), spec)
    for _ in range(60):
        h.turn()
    stats = h.applier.stats()
    # The sequence met both verdicts, and the summaries did serve.
    assert h.seen["accepted"] and h.seen["rejected"], h.seen
    assert stats["summary_hits"] and stats["summary_builds"], stats
    if flavour == "failed_commit":
        assert h.pool.failed
    if flavour in ("failed_commit", "journal_short", "collected",
                   "client_terminal", "node_change", "foreign_upsert"):
        assert stats["summary_dropped"], stats
    # Everything that landed fits where it stands: the safety net held.
    state = h.fsm.state.snapshot()
    # (Where a node shrinks or somebody else fills it, that is theirs.)
    if flavour not in ("node_change", "foreign_upsert", "mixed"):
        for node in h.nodes:
            live = state.allocs_by_node_terminal(node.id, False)
            assert allocs_fit(state.node_by_id(node.id), live)[0]


def test_usage_fits_states_allocs_fit_rule_in_order():
    cap = Resources(cpu=10, memory_mb=10, disk_mb=10, iops=10)
    over = {"eth0": 11}
    avail = {"eth0": 10}
    # Every dimension over at once: the first in allocs_fit's order wins.
    assert usage_fits(cap, 11, 11, 11, 11, True, over, avail) == (False, "cpu")
    assert usage_fits(cap, 10, 11, 11, 11, True, over, avail) == (
        False, "memory")
    assert usage_fits(cap, 10, 10, 11, 11, True, over, avail) == (
        False, "disk")
    assert usage_fits(cap, 10, 10, 10, 11, True, over, avail) == (
        False, "iops")
    assert usage_fits(cap, 10, 10, 10, 10, True, over, avail) == (
        False, "reserved port collision")
    assert usage_fits(cap, 10, 10, 10, 10, False, over, avail) == (
        False, "bandwidth exceeded")
    assert usage_fits(cap, 10, 10, 10, 10, False, {"eth1": 1}, avail) == (
        False, "bandwidth exceeded")
    assert usage_fits(cap, 10, 10, 10, 10, False, {"eth0": 10}, avail) == (
        True, "")


@pytest.mark.parametrize("tight,exhausted", [
    ("cpu", "cpu"), ("memory_mb", "memory"), ("disk_mb", "disk"),
    ("iops", "iops"), ("mbits", "bandwidth exceeded"),
    ("ports", "reserved port collision")])
def test_summary_dimension_equals_allocs_fit(tight, exhausted):
    """The verdict AND the dimension exhausted, on lists that run out
    of each dimension."""
    rng = random.Random(len(tight))
    h = Harness(17, {"tight": tight})
    node = h.nodes[0]
    job = h.jobs[0]
    seen = set()
    for _ in range(200):
        standing = [h._ask(node.id, job) for _ in range(rng.randint(0, 4))]
        placed = [h._ask(node.id, job) for _ in range(rng.randint(1, 3))]
        removed = rng.sample(standing, rng.randint(0, len(standing)))
        if standing and rng.random() < 0.3:
            placed.append(h._ask(node.id, job, rng.choice(standing).id))
        proposed = {a.id: a for a in standing
                    if a.id not in {r.id for r in removed}}
        proposed.update({a.id: a for a in placed})
        fit, dimension, _ = allocs_fit(node, list(proposed.values()))
        assert NodeSummary(node, standing).fits(removed, placed) == (
            fit, dimension)
        seen.add(dimension)
    assert seen >= {"", exhausted}


# ------------------------------------------------------------------ cost


class _CountingSnapshot:
    """A snapshot that counts the reads of its per-node index."""

    def __init__(self, inner, reads: dict):
        self._inner = inner
        self._reads = reads

    def allocs_by_node_terminal(self, node_id, terminal):
        self._reads[node_id] = self._reads.get(node_id, 0) + 1
        return self._inner.allocs_by_node_terminal(node_id, terminal)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def container(node_id: str, job) -> Allocation:
    return Allocation(
        id=generate_uuid(), eval_id="e", name="c1m.c1m[0]", job_id=job.id,
        task_group="c1m", node_id=node_id,
        task_resources={"container": Resources(cpu=19, memory_mb=32)},
        shared_resources=Resources(disk_mb=300),
        desired_status=consts.ALLOC_DESIRED_RUN,
        client_status=consts.ALLOC_CLIENT_PENDING)


@pytest.mark.parametrize("standing", [0, 150])
def test_thousand_alloc_plans_read_each_node_once(standing):
    fsm = FSM()
    log = DevLog(fsm)
    job = mock.batch_job()
    nodes = []
    for _ in range(250):
        node = mock.node()
        log.apply(fsm_mod.NODE_REGISTER, {"node": node})
        nodes.append(node)
    for _ in range(standing):
        log.apply(fsm_mod.ALLOC_UPDATE,
                  {"allocs": [container(n.id, job) for n in nodes],
                   "job": job})
    reads: dict = {}
    store = fsm.state
    counting = SimpleNamespace(
        snapshot=lambda: _CountingSnapshot(store.snapshot(), reads))
    applier = PlanApplier(PlanQueue(), SimpleNamespace(state=counting), log)
    applier._commit_pool = _LazyPool()
    rec = trace.get_recorder()
    rec.set_enabled(True)
    inflight = overlay = None
    spans = []
    for _ in range(10):
        plan = Plan(job=job, eval_id=generate_uuid())
        for node in nodes:
            for _ in range(4):
                plan.append_alloc(container(node.id, job))
        trace.record_span(plan.eval_id, "broker.wait", time.monotonic())
        inflight, overlay = applier._turn(
            [PendingPlan(plan)], inflight, overlay)
        trace.complete(plan.eval_id)
        spans.append(next(
            s for s in rec.trace_for(plan.eval_id)["spans"]
            if s["name"] == "plan.evaluate"))
    applier._turn([], inflight, overlay)
    assert len(store.snapshot().allocs()) == 250 * (standing + 40)
    # Once a node, over all ten plans: the first found nothing carried.
    assert set(reads) == {n.id for n in nodes}
    assert set(reads.values()) == {1}
    stats = applier.stats()
    assert stats["summary_builds"] == 250
    assert stats["summary_hits"] == 250 * 9
    assert stats["summary_dropped"] == 0
    assert stats["plans_rejected"] == 0
    first = spans[0]["annotations"]
    later = [s["annotations"] for s in spans[1:]]
    assert (first["nodes"], first["built"], first["standing"]) == (
        250, 250, 250 * standing)
    assert all((a["nodes"], a["built"], a["standing"]) == (250, 0, 0)
               for a in later)


def test_bare_snapshot_is_read_and_nothing_carried():
    """`_evaluate_plan` over a plain snapshot: a view of its own, every
    node read from the store, the applier's summaries untouched."""
    fsm = FSM()
    log = DevLog(fsm)
    node = mock.node()
    log.apply(fsm_mod.NODE_REGISTER, {"node": node})
    job = mock.batch_job()
    applier = PlanApplier(PlanQueue(), fsm, log)
    plan = Plan(job=job)
    plan.append_alloc(container(node.id, job))
    for _ in range(2):
        result = applier._evaluate_plan(fsm.state.snapshot(), plan)
        assert node.id in result.node_allocation
    assert len(applier._summaries) == 0
    assert applier.stats()["summary_builds"] == 0
    view = OptimisticSnapshot(fsm.state.snapshot())
    applier._evaluate_plan(view, plan)
    assert (view.summaries.builds, view.summaries.hits) == (1, 0)
