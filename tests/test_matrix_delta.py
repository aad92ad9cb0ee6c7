"""Incremental cluster-base updates (models/matrix.py _BASE_FAMILY +
_ClusterBase.delta_update): a snapshot that only advanced the allocs
table recomputes touched node rows instead of a full O(N x allocs)
rebuild, and the delta result must be bit-identical to a fresh build —
the live pipeline's per-apply snapshot churn rides this path."""

import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.models.matrix import ClusterMatrix, _ClusterBase
from nomad_tpu.state import StateStore
from nomad_tpu.structs import consts


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


@pytest.fixture
def cluster():
    store = StateStore()
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    nodes = []
    index = 0
    for _ in range(16):
        node = mock.node()
        node.compute_class()
        nodes.append(node)
        index += 1
        store.upsert_node(index, node)
    allocs = [make_alloc(nodes[i % 16], job) for i in range(32)]
    index += 1
    store.upsert_allocs(index, allocs)
    return store, job, nodes, allocs, index


def assert_bases_equal(a, b):
    for f in ("capacity", "sched_capacity", "util", "bw_avail",
              "bw_used", "ports_free", "node_ok"):
        np.testing.assert_array_equal(
            getattr(a, f), getattr(b, f), err_msg=f)
    assert a.alloc_groups == b.alloc_groups


def positions_of(base, jid):
    """One job's rows by task group, as sorted lists: the order of a
    task group's rows is no part of job_positions' contract."""
    return {tg: sorted(rows.tolist())
            for tg, rows in base.job_positions(jid).items()}


def test_delta_update_matches_full_rebuild(cluster):
    store, job, nodes, allocs, index = cluster
    m1 = ClusterMatrix(store.snapshot(), job)
    tok1 = m1.base_token

    # Stop some allocs and add new ones: the allocs index advances.
    stopped = allocs[:5]
    for a in stopped:
        a.desired_status = consts.ALLOC_DESIRED_STOP
        a.client_status = consts.ALLOC_CLIENT_COMPLETE
    index += 1
    store.upsert_allocs(index, stopped)
    fresh = [make_alloc(nodes[3], job, cpu=250), make_alloc(nodes[7], job)]
    index += 1
    store.upsert_allocs(index, fresh)

    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)  # delta path (family hit)
    assert m2.base_token != tok1
    # Oracle: a from-scratch base on the same snapshot.
    oracle = _ClusterBase(
        m2.nodes,
        lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(m2._cached_base(), oracle)


def test_unchanged_allocs_reuse_token(cluster):
    """An allocs-index bump that touches no node in this matrix's node
    set keeps the SAME base token — the device-cached upload stays
    valid with zero new transfers."""
    store, job, nodes, allocs, index = cluster
    m1 = ClusterMatrix(store.snapshot(), job)
    tok1 = m1.base_token
    # Touch an alloc on a node in another datacenter (outside this
    # job's node set).
    other = mock.node()
    other.datacenter = "dc-elsewhere"
    other.compute_class()
    index += 1
    store.upsert_node(index, other)
    m_after_node = ClusterMatrix(store.snapshot(), job)
    # nodes index moved: new family -> full rebuild is expected here.
    far_job = mock.job()
    far_job.id = "far"
    index += 1
    store.upsert_allocs(index, [make_alloc(other, far_job)])
    m2 = ClusterMatrix(store.snapshot(), job)
    assert m2.base_token == m_after_node.base_token


def test_many_changed_rows_falls_back_to_full_rebuild(cluster):
    store, job, nodes, allocs, index = cluster
    ClusterMatrix(store.snapshot(), job)
    # Touch every node (> n/4 rows): delta declines, full rebuild runs.
    for a in allocs:
        a.client_status = consts.ALLOC_CLIENT_COMPLETE
        a.desired_status = consts.ALLOC_DESIRED_STOP
    index += 1
    store.upsert_allocs(index, allocs)
    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(m2._cached_base(), oracle)
    # All allocs stopped: utilization back to reserved-only.
    assert float(m2.util[: m2.n_real, 0].max()) <= max(
        (n.reserved.cpu if n.reserved else 0) for n in m2.nodes)


def test_delta_patches_positions_index(cluster):
    """A delta base carries the parent's job-positions index forward,
    writing only the jobs of what the delta wrote; the result must equal
    a from-scratch index (same multiset of rows per job/task-group)."""
    store, job, nodes, allocs, index = cluster
    m1 = ClusterMatrix(store.snapshot(), job)
    parent = m1._cached_base()
    parent.job_positions(job.id)  # force the parent index to exist

    stopped = allocs[:3]
    for a in stopped:
        a.desired_status = consts.ALLOC_DESIRED_STOP
        a.client_status = consts.ALLOC_CLIENT_COMPLETE
    index += 1
    store.upsert_allocs(index, stopped)
    other = mock.job()
    other.id = "other-job"
    index += 1
    store.upsert_allocs(index, [make_alloc(nodes[5], other)])

    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)
    base2 = m2._cached_base()
    assert base2.delta_parent is not None  # took the delta path
    # Patched index was installed without a lazy rebuild.
    assert base2._positions is not None
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    for jid in (job.id, other.id, "no-such-job"):
        assert positions_of(base2, jid) == positions_of(oracle, jid), jid


def test_gc_deletion_forces_full_rebuild(cluster):
    """Deleted allocs leave no modify_index trace; the delta path must
    detect the shrinking table and rebuild, or the deleted usage stays
    baked into the base forever (GC via delete_evals pops allocs)."""
    store, job, nodes, allocs, index = cluster
    m1 = ClusterMatrix(store.snapshot(), job)
    util_before = m1.util[: m1.n_real].sum()
    victims = allocs[:4]
    index += 1
    store.delete_evals(index, [], [a.id for a in victims])
    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(m2._cached_base(), oracle)
    assert m2.util[: m2.n_real].sum() < util_before


def test_explicit_node_subsets_do_not_collide(cluster):
    """Two equal-sized but different pinned-node subsets on one
    snapshot (the dense system scheduler's shape) must get distinct
    bases — round-3 bug: the cache keyed node identity by len()."""
    store, job, nodes, allocs, index = cluster
    snap = store.snapshot()
    sub_a, sub_b = nodes[:4], nodes[4:8]
    ma = ClusterMatrix(snap, job, nodes=sub_a)
    mb = ClusterMatrix(snap, job, nodes=sub_b)
    assert ma.base_token != mb.base_token
    for m, subset in ((ma, sub_a), (mb, sub_b)):
        oracle = _ClusterBase(
            subset, lambda nid: snap.allocs_by_node_terminal(nid, False))
        assert_bases_equal(m._cached_base(), oracle)
    # Same subset again: cache hit, same token.
    ma2 = ClusterMatrix(snap, job, nodes=sub_a)
    assert ma2.base_token == ma.base_token


def test_off_set_creations_keep_delta_path_alive(cluster):
    """Alloc creations OUTSIDE the family's node set must rekey without
    poisoning table_len — a stale length tripped the deletion check and
    degraded every later delta to a full rebuild."""
    store, job, nodes, allocs, index = cluster
    far = mock.node()
    far.datacenter = "dc-elsewhere"
    far.compute_class()
    index += 1
    store.upsert_node(index, far)
    far_job = mock.job()
    far_job.id = "far"
    m = ClusterMatrix(store.snapshot(), job)
    token = m.base_token
    for step in range(5):
        # Creation on the out-of-set node: rekey (token unchanged) ...
        index += 1
        store.upsert_allocs(index, [make_alloc(far, far_job)])
        m = ClusterMatrix(store.snapshot(), job)
        assert m.base_token == token, f"rekey broke at step {step}"
    # ... and an in-set change afterwards still takes the DELTA path
    # (correct base, new token) rather than a full rebuild with drift.
    index += 1
    store.upsert_allocs(index, [make_alloc(nodes[2], job, cpu=75)])
    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)
    assert m2.base_token != token
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(m2._cached_base(), oracle)


def test_chained_deltas_stay_correct(cluster):
    """Repeated small changes (the live pipeline's per-apply churn)
    accumulate through chained delta updates without drift."""
    store, job, nodes, allocs, index = cluster
    rng_nodes = nodes
    for step in range(6):
        ClusterMatrix(store.snapshot(), job)
        index += 1
        store.upsert_allocs(index, [
            make_alloc(rng_nodes[(step * 3) % 16], job, cpu=50 + step)])
    snap = store.snapshot()
    m = ClusterMatrix(snap, job)
    oracle = _ClusterBase(
        m.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(m._cached_base(), oracle)


def test_additive_delta_for_pure_creations(cluster):
    """A placement storm is pure CREATIONS: even when they touch most
    nodes (past the refill cap), the delta path must survive by
    scatter-adding the new allocs' usage — the quadratic-storm fix —
    and stay bit-identical to a fresh build."""
    store, job, nodes, allocs, index = cluster
    m1 = ClusterMatrix(store.snapshot(), job)
    tok1 = m1.base_token

    # New allocs on EVERY node (16 rows > the 16//4 refill cap, and
    # far over it proportionally at scale).
    fresh = [make_alloc(n, job, cpu=30 + i) for i, n in enumerate(nodes)]
    index += 1
    store.upsert_allocs(index, fresh)
    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)
    base = m2._cached_base()
    # Delta, not rebuild: the chain to the parent is recorded.
    assert m2.base_token != tok1
    assert base.delta_parent is not None and base.delta_parent[0] == tok1
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(base, oracle)


def test_additive_delta_skips_created_then_terminal(cluster):
    """An alloc created AND terminated since the base was built never
    consumed capacity the base saw: it must contribute nothing."""
    store, job, nodes, allocs, index = cluster
    m1 = ClusterMatrix(store.snapshot(), job)
    tok1 = m1.base_token

    ghost = make_alloc(nodes[4], job, cpu=999)
    ghost.client_status = consts.ALLOC_CLIENT_COMPLETE
    live = make_alloc(nodes[9], job, cpu=40)
    index += 1
    store.upsert_allocs(index, [ghost, live])
    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(m2._cached_base(), oracle)
    assert m2.base_token != tok1


def test_mixed_creations_and_modifications(cluster):
    """Creations on some nodes + a terminal transition on another in
    ONE index step: the modified node refills, the created ones
    scatter-add, and the result matches a fresh build."""
    store, job, nodes, allocs, index = cluster
    ClusterMatrix(store.snapshot(), job)

    stopped = allocs[0]
    stopped.desired_status = consts.ALLOC_DESIRED_STOP
    stopped.client_status = consts.ALLOC_CLIENT_COMPLETE
    fresh = [make_alloc(nodes[i], job, cpu=20) for i in (2, 5, 11)]
    index += 1
    store.upsert_allocs(index, [stopped] + fresh)
    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(m2._cached_base(), oracle)


def test_addition_on_refilled_node_not_double_counted(cluster):
    """A creation landing on the SAME node as a modification must ride
    the refill (which already reads current allocs), not also
    scatter-add — double-counting would inflate utilization and cause
    phantom capacity exhaustion."""
    store, job, nodes, allocs, index = cluster
    ClusterMatrix(store.snapshot(), job)

    target = nodes[6]
    stopped = next(a for a in allocs if a.node_id == target.id)
    stopped.desired_status = consts.ALLOC_DESIRED_STOP
    stopped.client_status = consts.ALLOC_CLIENT_COMPLETE
    fresh = make_alloc(target, job, cpu=70)
    index += 1
    store.upsert_allocs(index, [stopped, fresh])
    snap = store.snapshot()
    m2 = ClusterMatrix(snap, job)
    oracle = _ClusterBase(
        m2.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert_bases_equal(m2._cached_base(), oracle)


# ---------------------------------------------------------------------
# The delta reads the store's journal of allocation writes
# (StateSnapshot.allocs_changed_since), never the whole table.
# ---------------------------------------------------------------------


class _NoWalk:
    """A snapshot whose allocs() raises: the O(all allocations) walk
    cannot come back to the base's path unnoticed. Everything else is
    the snapshot's own."""

    def __init__(self, snap):
        self._snap = snap

    def __getattr__(self, name):
        return getattr(self._snap, name)

    def allocs(self):
        raise AssertionError("the cluster base walked every allocation")


def assert_same_base(base, snap, nodes):
    """`base` equals a fresh build of `snap`, array for array, with an
    equal positions index and (where the chain carries one) an equal
    victim table."""
    from nomad_tpu.models.matrix import _VictimTable

    oracle = _ClusterBase(
        nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    for f in ("capacity", "sched_capacity", "util", "bw_avail",
              "bw_used", "ports_free", "node_ok"):
        np.testing.assert_array_equal(
            getattr(base, f), getattr(oracle, f), err_msg=f)
    assert [sorted(g) for g in base.alloc_groups] \
        == [sorted(g) for g in oracle.alloc_groups]
    jobs = {jid for g in oracle.alloc_groups for jid, _tg in g}
    for jid in jobs | {"no-such-job"}:
        assert positions_of(base, jid) == positions_of(oracle, jid), jid
    if base._victims is not None:
        want = _VictimTable.build(base.n, nodes, snap)
        for f in ("res", "bw", "ports", "prio", "ok"):
            np.testing.assert_array_equal(
                getattr(base._victims, f), getattr(want, f), err_msg=f)
        assert [[a.id for a in lst or []] for lst in base._victims.lists] \
            == [[a.id for a in lst or []] for lst in want.lists]


@pytest.mark.parametrize("seed", range(2900, 2910))
def test_journal_deltas_equal_a_fresh_build_over_random_histories(seed):
    """Creations, in-place updates, terminal transitions, evictions,
    client status updates, GC deletions and node ready/drain flips in a
    seeded order: at every index the base reached through the journal's
    deltas (or, after a deletion, the full build) is the fresh build of
    the same snapshot, and no step walks the table."""
    import random

    from nomad_tpu.models import resident

    rng = random.Random(seed)
    store = StateStore()
    jobs = []
    for i, priority in enumerate((10, 30, 70)):
        job = mock.job()
        job.id = f"job-{i}"
        job.priority = priority
        job.task_groups[0].tasks[0].resources.networks = []
        jobs.append(job)
    nodes = []
    index = 0
    for _ in range(20):
        node = mock.node()
        node.compute_class()
        nodes.append(node)
        index += 1
        store.upsert_node(index, node)
    live = [make_alloc(nodes[i % 20], jobs[i % 3], cpu=40 + i)
            for i in range(30)]
    index += 1
    store.upsert_allocs(index, live)
    dead = []

    tracker = resident.get_tracker()
    first = ClusterMatrix(_NoWalk(store.snapshot()), jobs[2])
    first._base.job_positions(jobs[0].id)   # the chain carries
    first._base.victim_table(                # both along
        first.nodes, store.snapshot())
    before = tracker.stats()
    kinds = []
    ops = ("create", "update", "stop", "evict", "client", "flip") * 3 \
        + ("gc",)
    for step in range(40):
        op = rng.choice(ops)
        index += 1
        if op == "create":
            fresh = [make_alloc(rng.choice(nodes), rng.choice(jobs),
                                cpu=10 + rng.randrange(40))
                     for _ in range(rng.randrange(1, 5))]
            live.extend(fresh)
            store.upsert_allocs(index, fresh)
        elif op == "update":
            a = rng.choice(live)
            for tr in a.task_resources.values():
                tr.cpu = 10 + rng.randrange(60)
            a.__dict__.pop("_dense_usage", None)
            store.upsert_allocs(index, [a])
        elif op in ("stop", "evict") and len(live) > 5:
            a = live.pop(rng.randrange(len(live)))
            a.desired_status = (consts.ALLOC_DESIRED_STOP if op == "stop"
                                else consts.ALLOC_DESIRED_EVICT)
            dead.append(a)
            store.upsert_allocs(index, [a])
        elif op == "client" and len(live) > 5:
            a = live.pop(rng.randrange(len(live)))
            a.client_status = consts.ALLOC_CLIENT_FAILED
            dead.append(a)
            store.update_allocs_from_client(index, [a])
        elif op == "gc" and dead:
            gone = [dead.pop() for _ in range(min(2, len(dead)))]
            store.delete_evals(index, [], [a.id for a in gone])
        else:
            node = rng.choice(nodes)
            if rng.random() < 0.5:
                node.drain = not node.drain
            else:
                node.status = (consts.NODE_STATUS_DOWN
                               if node.status == consts.NODE_STATUS_READY
                               else consts.NODE_STATUS_READY)
            store.upsert_node(index, node)
            op = "flip"
        snap = store.snapshot()
        m = ClusterMatrix(_NoWalk(snap), rng.choice(jobs))
        kinds.append((op, m.build_kind))
        assert_same_base(m._base, snap, m.nodes)
        if m.build_kind == "full":
            # a full build starts a chain without the lazy tables
            m._base.job_positions(jobs[0].id)
            m._base.victim_table(m.nodes, snap)
    # a deletion ends in a full build; the rest ride the journal's
    # deltas, but for a flip of a node that stands for its class
    assert {kind for op, kind in kinds if op == "gc"} <= {"full"}, kinds
    assert {kind for op, kind in kinds if op != "flip" and op != "gc"} \
        <= {"delta", "rekey"}, kinds
    served = sum(kind != "full" for _op, kind in kinds)
    assert served >= 25, kinds
    after = tracker.stats()
    assert after["journal_misses"] == before["journal_misses"]
    assert after["journal_deltas"] - before["journal_deltas"] >= served


def test_a_journal_that_does_not_reach_back_ends_in_a_full_build(
        cluster, monkeypatch):
    """Trimmed past the family's base, the store answers None and the
    base is built in full (as after a restore): never a delta from a
    partial answer."""
    from nomad_tpu.models import resident
    from nomad_tpu.state import store as store_mod

    store, job, nodes, allocs, index = cluster
    monkeypatch.setattr(store_mod, "_ALLOC_JOURNAL_CAP", 8)
    ClusterMatrix(_NoWalk(store.snapshot()), job)
    before = resident.get_tracker().stats()
    for i in range(12):
        index += 1
        store.upsert_allocs(index, [make_alloc(nodes[i], job, cpu=11 + i)])
    snap = store.snapshot()
    assert snap.allocs_changed_since(index - 12) is None
    m = ClusterMatrix(_NoWalk(snap), job)
    assert m.build_kind == "full"
    assert_same_base(m._base, snap, m.nodes)
    after = resident.get_tracker().stats()
    assert after["journal_misses"] == before["journal_misses"] + 1
    assert after["full_rebuilds"] == before["full_rebuilds"] + 1
    # the chain goes on from the full build
    index += 1
    store.upsert_allocs(index, [make_alloc(nodes[0], job, cpu=7)])
    snap = store.snapshot()
    m = ClusterMatrix(_NoWalk(snap), job)
    assert m.build_kind == "delta"
    assert_same_base(m._base, snap, m.nodes)


def test_a_state_without_a_journal_gets_a_full_build(cluster):
    """A state object that cannot say what changed (no
    allocs_changed_since) is never walked: its base is built in full."""
    store, job, nodes, allocs, index = cluster

    class Bare:
        def __init__(self, snap):
            self._snap = snap
            for name in ("store_id", "index", "nodes", "node_by_id",
                         "alloc_count", "allocs_by_node",
                         "allocs_by_node_terminal", "allocs_by_job",
                         "job_by_id"):
                setattr(self, name, getattr(snap, name))

    ClusterMatrix(Bare(store.snapshot()), job)
    index += 1
    store.upsert_allocs(index, [make_alloc(nodes[1], job, cpu=9)])
    snap = store.snapshot()
    m = ClusterMatrix(Bare(snap), job)
    assert m.build_kind == "full"
    assert_same_base(m._base, snap, m.nodes)


# ---------------------------------------------------------------------
# The positions index is carried at a cost of what the delta wrote
# (_patch_positions, _JobRows): an added allocation touches its own job
# only, a refilled row the (job, task group) pairs whose count differs.
# ---------------------------------------------------------------------


def chain_job(i, groups=("web",)):
    job = mock.job()
    job.id = f"chain-{i}"
    job.task_groups[0].tasks[0].resources.networks = []
    job.task_groups[0].name = groups[0]
    for name in groups[1:]:
        tg = job.task_groups[0].copy()
        tg.name = name
        job.task_groups.append(tg)
    return job


def group_alloc(node, job, group, cpu=20):
    alloc = make_alloc(node, job, cpu=cpu)
    alloc.task_group = group
    return alloc


@pytest.mark.parametrize("fold_after", [64, 0])
@pytest.mark.parametrize("seed", range(3900, 3908))
def test_positions_of_every_job_equal_a_fresh_build_along_random_chains(
        seed, fold_after, monkeypatch):
    """Chains of deltas that mix additive rows, refilled rows, evictions,
    a node flip and replans of jobs with live allocations, some jobs
    read along the way and most never: after every link job_positions of
    EVERY job the chain has seen (those left with nothing too) is the
    fresh build's. With `_FOLD_AFTER` 0 every edit folds at once; with
    64 the edits of an unread job pile up across links."""
    import random

    from nomad_tpu.models import matrix as matrix_mod

    monkeypatch.setattr(matrix_mod, "_FOLD_AFTER", fold_after)
    rng = random.Random(seed)
    store = StateStore()
    nodes = []
    index = 0
    for _ in range(24):
        node = mock.node()
        node.compute_class()
        nodes.append(node)
        index += 1
        store.upsert_node(index, node)
    standing = chain_job("standing")
    jobs = [standing] + [chain_job(i, ("web", "db")) for i in range(6)]
    # the standing job holds several allocations a row: repeats
    live = [group_alloc(node, standing, "web") for node in nodes
            for _ in range(3)]
    live += [group_alloc(rng.choice(nodes), job, rng.choice(("web", "db")))
             for job in jobs[1:] for _ in range(4)]
    index += 1
    store.upsert_allocs(index, live)
    first = ClusterMatrix(store.snapshot(), standing)
    first._base.job_positions(standing.id)   # the chain carries the index
    seen = {job.id for job in jobs}
    kinds = []
    ops = ("adds", "adds", "replan", "stop", "evict", "update", "flip",
           "mixed")
    for step in range(30):
        op = rng.choice(ops)
        index += 1
        if op == "adds":
            # a window's arrivals: new jobs stacked on a few rows
            fresh = []
            for _ in range(rng.randrange(1, 4)):
                job = chain_job(f"{seed}-{step}-{len(jobs)}", ("web", "db"))
                jobs.append(job)
                seen.add(job.id)
                row = rng.randrange(len(nodes))
                fresh += [group_alloc(nodes[(row + k) % 3], job,
                                      rng.choice(("web", "db")))
                          for k in range(rng.randrange(1, 5))]
            live += fresh
            store.upsert_allocs(index, fresh)
        elif op == "replan":
            # a job with live allocations is read, loses one and gains
            job = rng.choice([j for j in jobs if j is not standing])
            ClusterMatrix(store.snapshot(), job)
            mine = [a for a in live if a.job_id == job.id]
            writes = [group_alloc(rng.choice(nodes), job, "web")
                      for _ in range(2)]
            live += writes
            if mine:
                lost = rng.choice(mine)
                lost.desired_status = consts.ALLOC_DESIRED_STOP
                live.remove(lost)
                writes.append(lost)
            store.upsert_allocs(index, writes)
        elif op in ("stop", "evict"):
            a = live.pop(rng.randrange(len(live)))
            a.desired_status = (consts.ALLOC_DESIRED_STOP if op == "stop"
                                else consts.ALLOC_DESIRED_EVICT)
            store.upsert_allocs(index, [a])
        elif op == "update":
            a = rng.choice(live)
            for tr in a.task_resources.values():
                tr.cpu = 10 + rng.randrange(30)
            a.__dict__.pop("_dense_usage", None)
            store.upsert_allocs(index, [a])
        elif op == "flip":
            node = rng.choice(nodes)
            node.drain = not node.drain
            store.upsert_node(index, node)
        else:
            # one commit that evicts from the standing job and places a
            # new job on the same row and on another
            victim = next(a for a in live if a.job_id == standing.id)
            victim.desired_status = consts.ALLOC_DESIRED_EVICT
            live.remove(victim)
            job = chain_job(f"{seed}-{step}-m")
            jobs.append(job)
            seen.add(job.id)
            placed = [make_alloc(n, job, cpu=15) for n in (
                next(n for n in nodes if n.id == victim.node_id),
                rng.choice(nodes))]
            live += placed
            store.upsert_allocs(index, [victim] + placed)
        snap = store.snapshot()
        m = ClusterMatrix(snap, rng.choice(jobs))
        kinds.append(m.build_kind)
        oracle = _ClusterBase(
            m.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
        for jid in sorted(seen):
            assert positions_of(m._base, jid) == positions_of(oracle, jid), \
                (step, op, jid)
        if m.build_kind == "full":
            m._base.job_positions(standing.id)
    assert kinds.count("delta") >= 20, kinds


def crowded_rows():
    """Four rows of a 32-node cell that hold 200 other jobs each, and a
    standing job of 50,000 entries over the cell: what a window's end
    looks like to a delta."""
    import copy

    store = StateStore()
    nodes = []
    index = 0
    for _ in range(32):
        node = mock.node()
        node.compute_class()
        nodes.append(node)
        index += 1
        store.upsert_node(index, node)
    standing = chain_job("standing")
    template = make_alloc(nodes[0], standing, cpu=0, mem=0)
    allocs = []
    for k in range(50_000):
        a = copy.copy(template)
        a.id = f"standing-{k}"
        a.node_id = nodes[k % 32].id
        allocs.append(a)
    others = [chain_job(f"other-{i}") for i in range(200)]
    for job in others:
        allocs += [make_alloc(nodes[row], job, cpu=1, mem=1)
                   for row in range(4)]
    index += 1
    store.upsert_allocs(index, allocs)
    parent = ClusterMatrix(store.snapshot(), standing)._base
    parent.job_positions(standing.id)
    return store, nodes, standing, others, allocs, index, parent


def test_an_adds_only_delta_rewrites_the_jobs_of_its_adds_and_no_other():
    """The cost guard, by counting work and not time: `patched_jobs` is
    the distinct jobs among the adds, and the entry of every job that
    merely lives on the touched rows (the standing job's 50,000 entries
    first) is the parent's object."""
    store, nodes, standing, others, _allocs, index, parent = crowded_rows()
    arrivals = [chain_job(f"arrival-{i}", ("web", "db")) for i in range(3)]
    adds = [group_alloc(nodes[row], job, group, cpu=1)
            for job in arrivals for row in range(4)
            for group in ("web", "db")]
    index += 1
    store.upsert_allocs(index, adds)
    snap = store.snapshot()
    m = ClusterMatrix(snap, arrivals[0])
    base = m._base
    assert m.build_kind == "delta"
    assert base.delta_stats == {"rows": 4, "adds": 24, "refills": 0,
                                "patched_jobs": 3}
    assert base.job_positions(standing.id)["web"] \
        is parent.job_positions(standing.id)["web"]
    for job in [standing] + others:
        assert base._positions[job.id] \
            is parent._positions[job.id], job.id
    for job in arrivals:
        assert positions_of(base, job.id) == {
            "web": [0, 1, 2, 3], "db": [0, 1, 2, 3]}
    assert len(base.job_positions(standing.id)["web"]) == 50_000


def test_a_refill_writes_the_pairs_that_differ_and_scans_no_array():
    """A standing job that loses one row of 50,000: one edit on its
    entry, over the parent's arrays untouched; the 200 other jobs of
    the refilled row keep the parent's entries; a reader folds the edit
    in and gets the fresh build's rows."""
    store, nodes, standing, others, allocs, index, parent = crowded_rows()
    victim = allocs[1]            # the standing job's, on row 1
    victim.desired_status = consts.ALLOC_DESIRED_EVICT
    arrival = chain_job("arrival")
    index += 1
    store.upsert_allocs(index, [victim, make_alloc(nodes[1], arrival)])
    snap = store.snapshot()
    m = ClusterMatrix(snap, arrival)
    base = m._base
    assert m.build_kind == "delta"
    assert base.delta_stats == {"rows": 1, "adds": 0, "refills": 1,
                                "patched_jobs": 2}
    entry = base._positions[standing.id]
    assert entry.arrays is parent._positions[standing.id].arrays
    assert entry.pending == 1 and entry._folded is None
    for job in others:
        assert base._positions[job.id] \
            is parent._positions[job.id], job.id
    oracle = _ClusterBase(
        m.nodes, lambda nid: snap.allocs_by_node_terminal(nid, False))
    assert positions_of(base, standing.id) == positions_of(oracle, standing.id)
    assert len(base.job_positions(standing.id)["web"]) == 49_999
    assert positions_of(base, arrival.id) == {"web": [1]}


def test_a_chain_of_deltas_shares_the_entries_it_did_not_write(cluster):
    """Down a chain of deltas the index stays one flat dict of every
    job; an entry no delta wrote is the first base's object still."""
    store, job, nodes, _allocs, index = cluster
    many = [chain_job(f"resident-{i}") for i in range(64)]
    index += 1
    store.upsert_allocs(index, [make_alloc(nodes[i % 16], j, cpu=1)
                                for i, j in enumerate(many)])
    parent = ClusterMatrix(store.snapshot(), job)._base
    parent.job_positions(job.id)
    first = dict(parent._positions)
    assert len(first) == 65
    for step in range(6):
        index += 1
        store.upsert_allocs(index, [make_alloc(
            nodes[step], chain_job(f"new-{step}"), cpu=1)])
        base = ClusterMatrix(store.snapshot(), job)._base
        assert base.delta_stats["patched_jobs"] == 1
        assert len(base._positions) == 65 + step + 1
        assert all(base._positions[jid] is entry
                   for jid, entry in first.items())
    assert parent._positions == first
    assert positions_of(base, "chain-new-3") == {"web": [3]}
    assert positions_of(base, many[5].id) == {"web": [5]}


@pytest.mark.parametrize("kind", ["full", "hit", "rekey", "delta"])
def test_the_span_of_a_base_delta_is_carried_only_by_a_derived_delta(
        cluster, kind):
    """ClusterMatrix.base_delta_span (what the scheduler records as the
    span `base.delta` for an inline replan) is set where this very
    build derived the delta, with the four annotations, and on no other
    kind of build."""
    store, job, nodes, _allocs, index = cluster
    if kind != "full":
        ClusterMatrix(store.snapshot(), job)
    if kind == "delta":
        index += 1
        store.upsert_allocs(index, [make_alloc(nodes[2], job)])
    elif kind == "rekey":
        far = mock.node()
        far.datacenter = "dc-elsewhere"
        far.compute_class()
        index += 1
        store.upsert_node(index, far)
        ClusterMatrix(store.snapshot(), job)
        index += 1
        store.upsert_allocs(index, [make_alloc(far, chain_job("far"))])
    t0 = time.monotonic()
    m = ClusterMatrix(store.snapshot(), job)
    t1 = time.monotonic()
    assert m.build_kind == kind
    if kind != "delta":
        assert m.base_delta_span is None
        return
    start, end, ann = m.base_delta_span
    assert t0 <= start <= end <= t1
    assert ann == {"rows": 1, "adds": 1, "refills": 0, "patched_jobs": 1}
    assert ann is m._base.delta_stats
