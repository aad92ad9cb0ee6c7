"""Device-resident incremental node state (models/resident.py):

1. **Parity, property-style**: randomized sequences of plan commits
   (alloc creations / terminal transitions) and node up/down/drain
   events, asserting at EVERY raft index that the incrementally
   maintained base — host mirror AND the device-resident tensor the
   batcher scatters into — is bit-identical to a matrix built from
   scratch on the same snapshot.

2. **Staleness safety net**: chaos site ``matrix.stale_delta`` drops
   one delta record, leaving the resident state wrong; the plan
   applier's exact per-node verification must then REJECT the
   resulting bad placement (nothing wrong commits), and the rejection
   must force the next build to pay a full rebuild that restores
   parity (``stale_rebuilds`` counter)."""

import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import FaultSpec, chaos
from nomad_tpu.models import resident
from nomad_tpu.models.matrix import ClusterMatrix, _ClusterBase
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Allocation, Plan, consts
from nomad_tpu.utils.ids import generate_uuid

BASE_FIELDS = ("capacity", "sched_capacity", "util", "bw_avail",
               "bw_used", "ports_free", "node_ok")


@pytest.fixture(autouse=True)
def clean_staleness():
    """The tracker is process-global: a clean staleness flag for every
    test here."""
    tracker = resident.get_tracker()
    tracker.consume_stale()
    yield tracker
    tracker.consume_stale()


def make_alloc(node, job, cpu=100, mem=128):
    alloc = mock.alloc()
    alloc.node_id = node.id
    alloc.job_id = job.id
    alloc.job = job
    alloc.desired_status = consts.ALLOC_DESIRED_RUN
    alloc.client_status = consts.ALLOC_CLIENT_RUNNING
    for tr in alloc.task_resources.values():
        tr.cpu = cpu
        tr.memory_mb = mem
        tr.networks = []
    alloc.resources = None
    return alloc


def assert_parity(m, snap, msg=""):
    """Host mirror of the resident base == a from-scratch build over
    the same node universe."""
    base = m._cached_base()
    oracle = _ClusterBase(
        m.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    for f in BASE_FIELDS:
        np.testing.assert_array_equal(
            getattr(base, f), getattr(oracle, f), err_msg=f"{f} {msg}")
    return base


def assert_device_parity(m):
    """The actual device-resident tensor (scattered into by
    apply_base_delta across generations) == the host mirror."""
    from nomad_tpu.scheduler.batcher import get_batcher

    b = get_batcher()
    b.prefetch_base(m)
    with b._lock:
        dev = b._device_bases.get(m.base_token)
    assert dev is not None
    for i, f in enumerate(BASE_FIELDS):
        np.testing.assert_array_equal(
            np.asarray(dev[i]), getattr(m, f),
            err_msg=f"device {f} (token {m.base_token})")


def assert_class_parity(m):
    """The compression plane rides the same parity contract: the
    (possibly delta-chain-shared) class index == a fresh interning of
    the same node list. Construction is deterministic in row order, so
    equality is array-for-array — including after a class SPLIT (meta
    edit) forced a rebuild that re-interned."""
    from nomad_tpu.models.classes import ClassIndex

    base = m._cached_base()
    fresh = ClassIndex(m.nodes, base.n)
    np.testing.assert_array_equal(base.class_index.ids, fresh.ids)
    assert base.class_index.reps == fresh.reps
    np.testing.assert_array_equal(base.class_index.counts, fresh.counts)
    assert base.class_index.signatures == fresh.signatures


def test_incremental_vs_rebuild_parity_randomized():
    """52 randomized steps of plan commits / node up-down / drain /
    meta-edit / register / deregister events; the resident tensor must
    equal a fresh build at every raft index, on host and on device —
    and the interned class index must equal a fresh interning (the
    class-split path: a meta edit moves the node's signature, refuses
    the delta, and the rebuild re-interns)."""
    rng = random.Random(0xA11C)
    store = StateStore()
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    nodes = []
    index = 0
    for _ in range(24):
        node = mock.node()
        node.compute_class()
        nodes.append(node)
        index += 1
        store.upsert_node(index, node)
    live = []
    for i in range(12):
        a = make_alloc(nodes[i % 24], job, cpu=60 + i)
        live.append(a)
    index += 1
    store.upsert_allocs(index, live)

    tracker = resident.get_tracker()
    before = tracker.stats()

    # Alloc/readiness churn dominates (the delta steady state); the
    # class-splitting ops — meta edit, register, deregister — are the
    # rare structural transitions that must fall back to a rebuild.
    ops = (("create", "stop", "down", "up", "drain") * 2
           + ("meta", "register", "deregister"))
    ops_seen = set()
    for step in range(52):
        op = rng.choice(ops)
        index += 1
        if op == "create":
            fresh = make_alloc(rng.choice(nodes), job,
                               cpu=20 + rng.randrange(50))
            live.append(fresh)
            store.upsert_allocs(index, [fresh])
        elif op == "stop" and live:
            victim = live.pop(rng.randrange(len(live)))
            victim.desired_status = consts.ALLOC_DESIRED_STOP
            victim.client_status = consts.ALLOC_CLIENT_COMPLETE
            store.upsert_allocs(index, [victim])
        elif op == "down":
            node = rng.choice(nodes)
            node.status = consts.NODE_STATUS_DOWN
            store.upsert_node(index, node)
        elif op == "up":
            node = rng.choice(nodes)
            node.status = consts.NODE_STATUS_READY
            node.drain = False
            store.upsert_node(index, node)
        elif op == "drain":
            node = rng.choice(nodes)
            node.drain = not node.drain
            store.upsert_node(index, node)
        elif op == "meta":
            # Non-unique meta edit: moves the computed class AND the
            # signature — the class-split path (delta refused, rebuild
            # re-interns).
            node = rng.choice(nodes)
            node.meta["tier"] = f"t{step}"
            node.compute_class()
            store.upsert_node(index, node)
        elif op == "register":
            node = mock.node()
            node.compute_class()
            nodes.append(node)
            store.upsert_node(index, node)
        else:  # deregister
            if len(nodes) <= 8:
                continue
            gone = nodes.pop(rng.randrange(len(nodes)))
            live = [a for a in live if a.node_id != gone.id]
            store.delete_node(index, gone.id)
        ops_seen.add(op)
        snap = store.snapshot()
        m = ClusterMatrix(snap, job)
        assert_parity(m, snap, msg=f"step {step} op {op}")
        assert_device_parity(m)
        assert_class_parity(m)
    # The seeded walk must actually exercise the structural ops.
    assert {"meta", "register", "deregister"} <= ops_seen

    after = tracker.stats()
    # The point of the design: the steady state rode deltas, including
    # NODE-axis deltas for the up/down/drain flips — not rebuilds.
    assert after["delta_updates"] > before["delta_updates"]
    assert after["node_delta_updates"] > before["node_delta_updates"]


def test_down_nodes_masked_not_dropped():
    """With resident state on, a down node stays IN the matrix with
    node_ok masked (readiness is row state, not matrix shape) — the
    matrix keeps one shape across the transition, so the device base
    delta-updates instead of rebuilding the node axis."""
    store = StateStore()
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    nodes = []
    index = 0
    for _ in range(8):
        node = mock.node()
        node.compute_class()
        nodes.append(node)
        index += 1
        store.upsert_node(index, node)
    m1 = ClusterMatrix(store.snapshot(), job)
    assert m1.n_real == 8
    assert bool(m1.node_ok[:8].all())

    victim = nodes[3]
    victim.status = consts.NODE_STATUS_DOWN
    index += 1
    store.upsert_node(index, victim)
    m2 = ClusterMatrix(store.snapshot(), job)
    assert m2.n_real == 8  # same shape: the node was masked, not dropped
    assert not bool(m2.node_ok[3])
    assert bool(np.delete(m2.node_ok[:8], 3).all())
    # And it was a delta against m1's base, not a new family.
    base2 = m2._cached_base()
    assert base2.delta_parent is not None
    assert base2.delta_parent[0] == m1.base_token


def test_device_state_stats_surface():
    """server.stats()["device_state"] carries the resident counters +
    the batcher's jit compile-cache size, so recompile storms and
    staleness rebuilds are observable on a live agent (the /v1/metrics
    gauges read the same dict in the telemetry loop)."""
    from nomad_tpu.server import Server, ServerConfig

    st = Server(ServerConfig()).stats()["device_state"]
    for key in ("full_rebuilds", "delta_updates",
                "node_delta_updates", "stale_rebuilds",
                "universe_rebuilds", "jit_cache_size", "base_uploads",
                "base_delta_updates", "upload_bytes", "journal_deltas",
                "journal_misses", "journal_allocs",
                "positions_patched_jobs"):
        assert key in st, key


def test_journal_counters_say_how_the_delta_learnt_what_changed(
        clean_staleness, monkeypatch):
    """journal_deltas: deltas the store's journal of allocation writes
    served; journal_allocs: the changed allocations it handed over (so
    allocs / deltas is the allocations a commit); journal_misses: deltas
    it could not serve, each followed by a full build."""
    from nomad_tpu.state import store as store_mod

    monkeypatch.setattr(store_mod, "_ALLOC_JOURNAL_CAP", 16)
    store = StateStore()
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    nodes = []
    index = 0
    for _ in range(8):
        node = mock.node()
        node.compute_class()
        nodes.append(node)
        index += 1
        store.upsert_node(index, node)
    index += 1
    store.upsert_allocs(index, [make_alloc(nodes[0], job)])
    ClusterMatrix(store.snapshot(), job)

    def moved(then):
        now = clean_staleness.stats()
        return tuple(now[k] - then[k] for k in (
            "journal_deltas", "journal_allocs", "journal_misses",
            "full_rebuilds"))

    then = clean_staleness.stats()
    for commit in (3, 5):     # two commits, two deltas
        index += 1
        store.upsert_allocs(
            index, [make_alloc(nodes[i], job) for i in range(commit)])
        assert ClusterMatrix(store.snapshot(), job).build_kind == "delta"
    assert moved(then) == (2, 8, 0, 0)
    # the same snapshot again is a cache hit: nobody asks the journal
    assert ClusterMatrix(store.snapshot(), job).build_kind == "hit"
    assert moved(then) == (2, 8, 0, 0)
    # two commits between two bases: one delta, both commits' allocs
    for _ in range(2):
        index += 1
        store.upsert_allocs(index, [make_alloc(nodes[1], job)])
    assert ClusterMatrix(store.snapshot(), job).build_kind == "delta"
    assert moved(then) == (3, 10, 0, 0)
    # more writes than the journal keeps: a miss, and a full build
    for _ in range(20):
        index += 1
        store.upsert_allocs(index, [make_alloc(nodes[2], job)])
    assert ClusterMatrix(store.snapshot(), job).build_kind == "full"
    assert moved(then) == (3, 10, 1, 1)


# --------------------------------------------------------- staleness


def build_world(n_nodes=4, cpu=1000):
    from nomad_tpu.server.fsm import FSM, DevLog

    fsm = FSM()
    log = DevLog(fsm)
    nodes = []
    for _ in range(n_nodes):
        node = mock.node()
        node.resources.cpu = cpu
        node.compute_class()
        log.apply("node_register", {"node": node})
        nodes.append(node)
    return fsm, log, nodes


def make_plan(node, cpu, job=None):
    job = job or mock.job()
    alloc = Allocation(
        id=generate_uuid(), job_id=job.id, job=job, node_id=node.id,
        task_group="web", desired_status=consts.ALLOC_DESIRED_RUN,
    )
    alloc.task_resources = {
        "web": mock.job().task_groups[0].tasks[0].resources.copy()}
    alloc.task_resources["web"].cpu = cpu
    alloc.task_resources["web"].networks = []
    plan = Plan(job=job)
    plan.append_alloc(alloc)
    return plan


def run_applier(fsm, log, plans):
    from nomad_tpu.server.plan_apply import PlanApplier
    from nomad_tpu.server.plan_queue import PlanQueue

    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, log)
    applier.start()
    try:
        pendings = [queue.enqueue(p) for p in plans]
        return [p.wait(timeout=20.0) for p in pendings]
    finally:
        applier.stop()


def test_stale_delta_forces_rebuild_not_wrong_placement(clean_staleness):
    """End to end through the REAL plan applier: a chaos-dropped delta
    record leaves the resident matrix believing a nearly-full node is
    empty; the placement that belief produces is REJECTED by exact
    verification (nothing wrong commits), the rejection marks the
    chain, and the very next build pays a full rebuild that restores
    parity."""
    fsm, log, nodes = build_world(n_nodes=4, cpu=1000)
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    target = nodes[0]

    m1 = ClusterMatrix(fsm.state.snapshot(), job)  # anchor the family
    row = next(i for i, n in enumerate(m1.nodes) if n.id == target.id)

    # Commit an 800-cpu alloc on the target through the applier while
    # the NEXT delta application is scheduled to drop.
    with chaos.armed(7, [FaultSpec("matrix.stale_delta", "drop")]):
        (res1,) = run_applier(fsm, log, [make_plan(target, 800)])
        assert target.id in res1.node_allocation  # committed for real
        snap = fsm.state.snapshot()
        m2 = ClusterMatrix(snap, job)
        fired = chaos.firing_log()
    assert fired, "the stale-delta site never fired"
    # The span's annotation counts what was written, not what was lost.
    assert m2._base.delta_stats == {"rows": 0, "adds": 0, "refills": 0,
                                    "patched_jobs": 0}
    assert m2.delta_rows == 1  # the lost row is shipped un-recomputed

    # The resident state is now WRONG: the 800-cpu commit is invisible.
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert float(m2.util[row, 0]) < float(oracle.util[row, 0])

    # The stale matrix says a 900-cpu ask fits on the target; exact
    # verification must reject it — the wrong placement never commits.
    (res2,) = run_applier(fsm, log, [make_plan(target, 900)])
    assert target.id not in res2.node_allocation
    assert res2.refresh_index > 0
    assert len(fsm.state.allocs_by_node(target.id)) == 1  # only the 800

    # The rejection forced a re-anchor: the next build (same snapshot —
    # the rejected plan committed nothing) full-rebuilds and matches.
    tracker = clean_staleness
    stale_before = tracker.stats()["stale_rebuilds"]
    snap3 = fsm.state.snapshot()
    m3 = ClusterMatrix(snap3, job)
    assert tracker.stats()["stale_rebuilds"] == stale_before + 1
    assert_parity(m3, snap3, msg="post-rebuild")
    assert float(m3.util[row, 0]) >= 800.0

    # And the re-anchored matrix routes the 900 ask elsewhere: a fresh
    # placement decision against it would not pick the full node.
    assert float(m3.capacity[row, 0] - m3.util[row, 0]) < 900.0
