"""The dense feasibility mask against the host checkers, its memo and
its compact form (PR 38).

Seeded random fleets of random attributes and seeded random constraints
over all five operand kinds (kernels/differential.py
build_constraint_scenario): the dense mask equals the host
ConstraintChecker's verdict node by node and every dense placement lies
on a host-feasible node; the same past 128 and past 256 computed
classes, where the eval has to stay on the compact path; the mask of a
signature is built once along a chain of allocation deltas and again
after a class split; `unique.`-escaped constraints and classless nodes;
lanes of different signatures in one dispatch get their own masks."""

import random
import threading

import numpy as np
import pytest

from nomad_tpu import mock, trace
from nomad_tpu.kernels.differential import (
    build_constraint_scenario,
    random_constraint,
    run_differential,
)
from nomad_tpu.models import matrix as matrix_mod
from nomad_tpu.models.matrix import CLASS_BUCKETS, ClusterMatrix
from nomad_tpu.ops.binpack import PlacementConfig, host_prng_key, make_asks
from nomad_tpu.scheduler.batcher import PlacementBatcher
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.feasible import (
    ConstraintChecker,
    DriverChecker,
    _compiled_regexp,
    _parsed_constraints,
)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Constraint, Plan, consts

CONFIG = PlacementConfig(anti_affinity_penalty=10.0)


def host_mask(snap, job, nodes) -> np.ndarray:
    """[N, G] by the host checkers alone, node by node: every
    constraint of the job, the task group and its tasks, and the
    drivers, on the node itself (no class, no memo)."""
    ctx = EvalContext(snap, Plan())
    out = np.zeros((len(nodes), len(job.task_groups)), bool)
    for gi, tg in enumerate(job.task_groups):
        cons = list(job.constraints) + list(tg.constraints)
        drivers = set()
        for task in tg.tasks:
            cons.extend(task.constraints)
            drivers.add(task.driver)
        checker = ConstraintChecker(ctx, cons)
        driver_checker = DriverChecker(ctx, drivers)
        for i, node in enumerate(nodes):
            out[i, gi] = (driver_checker.feasible(node)
                          and checker.feasible(node))
    return out


def store_of(nodes, job=None):
    store, index = StateStore(), 0
    for node in nodes:
        index += 1
        store.upsert_node(index, node)
    if job is not None:
        index += 1
        store.upsert_job(index, job)
    return store, index


def running_alloc(node, job):
    alloc = mock.alloc()
    alloc.node_id, alloc.job_id, alloc.job = node.id, job.id, job
    alloc.desired_status = consts.ALLOC_DESIRED_RUN
    alloc.client_status = consts.ALLOC_CLIENT_RUNNING
    for tr in alloc.task_resources.values():
        tr.networks = []
    alloc.resources = None
    return alloc


def place_through_batcher(batcher, matrix, seed=7, asks=8):
    asks = make_asks(*matrix.build_asks([0] * asks))
    choices, _scores = batcher.place(matrix, asks, host_prng_key(seed),
                                     CONFIG)
    return [int(c) for c in np.asarray(choices) if c >= 0]


@pytest.fixture(autouse=True)
def fresh_memos():
    """The memo is process-wide and keyed on tokens that are too."""
    matrix_mod._FEAS_CACHE.clear()
    yield


# ------------------------------------------------------ mask vs checkers


@pytest.mark.parametrize("seed", range(3800, 3812))
def test_the_dense_mask_is_the_host_checkers_verdict_node_by_node(seed):
    _seed_state, job, nodes = build_constraint_scenario(seed)
    store, _ = store_of(nodes, job)
    snap = store.snapshot()
    matrix = ClusterMatrix(snap, job)
    want = host_mask(snap, job, matrix.nodes)
    np.testing.assert_array_equal(matrix.feasible[: matrix.n_real], want)
    assert not matrix.feasible[matrix.n_real:].any()


def test_the_rig_draws_all_five_operand_kinds_and_both_verdicts():
    rng = random.Random(38)
    kinds, verdicts = set(), set()
    for seed in range(3800, 3812):
        _s, job, nodes = build_constraint_scenario(seed)
        store, _ = store_of(nodes, job)
        snap = store.snapshot()
        verdicts.update(host_mask(snap, job, snap.nodes()).ravel().tolist())
    for _ in range(200):
        op = random_constraint(rng).operand
        kinds.add("order" if op in ("<", "<=", ">", ">=") else op)
    assert kinds == {"=", "!=", "order", "version", "regexp"}
    assert verdicts == {True, False}


@pytest.mark.parametrize("seed", range(3820, 3828))
def test_every_dense_placement_lies_on_a_host_feasible_node(seed):
    report = run_differential("greedy", seeds=[seed],
                              scenario=build_constraint_scenario)
    assert report["green"], report["violations"]


@pytest.mark.parametrize("seed", range(3830, 3834))
def test_escaped_constraints_and_classless_nodes_on_the_rig(seed):
    def scenario(s):
        return build_constraint_scenario(s, escaped=True, classless=True)

    report = run_differential("greedy", seeds=[seed], scenario=scenario)
    assert report["green"], report["violations"]


# ------------------------------------------ past the old top class bucket


@pytest.mark.parametrize("classes", [130, 300, 600])
def test_a_fleet_of_many_computed_classes_stays_compact(classes):
    """Past 128 and past 256 computed classes (the old ladder's top and
    twice it) the eval keeps its compact overlay, dispatches the
    compact program, and places where the dense path places: on
    host-feasible nodes only."""
    _s, job, nodes = build_constraint_scenario(3840 + classes,
                                               min_classes=classes)
    store, _ = store_of(nodes, job)
    snap = store.snapshot()
    matrix = ClusterMatrix(snap, job)
    n_classes = len({n.computed_class for n in matrix.nodes})
    assert n_classes > classes
    assert matrix.compact_overlay is not None
    bucket = matrix.compact_overlay.verdicts.shape[0]
    assert bucket in CLASS_BUCKETS and bucket >= n_classes
    want = host_mask(snap, job, matrix.nodes)
    np.testing.assert_array_equal(matrix.feasible[: matrix.n_real], want)

    batcher = PlacementBatcher(window=0.0)
    chosen = place_through_batcher(batcher, matrix)
    stats = batcher.stats()
    assert stats["compact_dispatches"] == 1 and stats["dispatches"] == 1
    assert all(want[row, 0] for row in chosen)
    assert len(chosen) == min(8, int(want[:, 0].sum())) or not want.any()

    dense = ClusterMatrix(snap, job)
    dense.compact_overlay = None
    batcher2 = PlacementBatcher(window=0.0)
    assert place_through_batcher(batcher2, dense) == chosen
    assert batcher2.stats()["compact_dispatches"] == 0


def test_past_the_top_class_bucket_the_dense_overlay_still_serves():
    top = CLASS_BUCKETS[-1]
    nodes = []
    for i in range(top + 5):
        node = mock.node()
        node.meta["rack"] = f"r{i}"
        node.compute_class()
        nodes.append(node)
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    store, _ = store_of(nodes, job)
    matrix = ClusterMatrix(store.snapshot(), job)
    assert matrix.compact_overlay is None
    assert matrix.feasible[: matrix.n_real].all()


# ----------------------------------------------------------- the memo


def pinned_cluster(n=48):
    nodes = []
    for i in range(n):
        node = mock.node()
        node.attributes["platform"] = "ABC"[i % 3]
        node.meta["rack"] = f"r{i // 4}"
        node.compute_class()
        nodes.append(node)
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    job.constraints.append(Constraint(
        ltarget="${attr.platform}", operand="=", rtarget="B"))
    return nodes, job


def test_one_signature_is_built_once_along_a_chain_of_allocation_deltas():
    nodes, job = pinned_cluster()
    store, index = store_of(nodes, job)
    other = mock.job()
    first = ClusterMatrix(store.snapshot(), job)
    assert first.feas_build is not None
    assert first.feas_build[2] == {
        "classes": 36, "groups": 1, "constraints": 2, "escaped": 0}
    tokens, builds = {first.base_token}, 1
    for step in range(6):
        index += 1
        store.upsert_allocs(index, [running_alloc(
            store.nodes()[step * 5], other)])
        snap = store.snapshot()
        # a new job of the same constraint structure, as a storm's next
        # eval is, and the first job again
        twin = job.copy()
        twin.id = f"twin-{step}"
        for j in (twin, job):
            m = ClusterMatrix(snap, j)
            assert m.build_kind in ("delta", "hit")
            builds += m.feas_build is not None
            tokens.add(m.base_token)
            assert m.compact_overlay is not None
            np.testing.assert_array_equal(m.feasible, first.feasible)
            assert m.feasible is first.feasible
    assert len(tokens) == 7      # every commit minted a new base token
    assert builds == 1           # and none of them built the mask again
    # a job with live allocations of its own takes the other path
    # through the memo and finds the same mask
    index += 1
    store.upsert_allocs(index, [running_alloc(store.nodes()[1], job)])
    own = ClusterMatrix(store.snapshot(), job)
    assert own.feas_build is None and own.feasible is first.feasible
    assert (own.compact_overlay.job_rows < own.n).sum() == 1
    # another constraint structure is another mask
    loose = job.copy()
    loose.id, loose.constraints = "loose", []
    assert ClusterMatrix(store.snapshot(), loose).feas_build is not None


def test_a_class_split_rebuilds_the_mask():
    nodes, job = pinned_cluster()
    store, index = store_of(nodes, job)
    before = ClusterMatrix(store.snapshot(), job)
    assert ClusterMatrix(store.snapshot(), job).feas_build is None
    # a meta edit moves one node of platform B into a class of its own
    # and out of the job's reach
    edited = next(n for n in store.nodes()
                  if n.attributes["platform"] == "B").copy()
    edited.attributes["platform"] = "C"
    index += 1
    store.upsert_node(index, edited)
    snap = store.snapshot()
    after = ClusterMatrix(snap, job)
    assert after.build_kind == "full"
    assert after.feas_build is not None
    want = host_mask(snap, job, after.nodes)
    np.testing.assert_array_equal(after.feasible[: after.n_real], want)
    assert after.feasible.sum() == before.feasible.sum() - 1
    assert ClusterMatrix(snap, job).feas_build is None


def test_an_escaped_mask_is_rebuilt_when_the_nodes_table_moves():
    """A mask that read `unique.` attributes pins the nodes-table index
    it was built at; one of class verdicts alone rides a readiness flip
    (a row delta that keeps the node axis)."""
    nodes, job = pinned_cluster()
    for i, node in enumerate(nodes):
        node.attributes["unique.hostname"] = f"host-{i:02d}"
    escaped = job.copy()
    escaped.id = "escaped"
    escaped.constraints.append(Constraint(
        ltarget="${attr.unique.hostname}", operand="regexp",
        rtarget="[02468]$"))
    store, index = store_of(nodes, job)
    snap = store.snapshot()
    first = ClusterMatrix(snap, escaped)
    assert first.feas_build[2]["escaped"] == 1
    np.testing.assert_array_equal(
        first.feasible[: first.n_real], host_mask(snap, escaped, first.nodes))
    assert first.compact_overlay is not None     # as patch rows
    assert (first.compact_overlay.patch_rows < first.n).any()
    assert ClusterMatrix(snap, job).feas_build is not None
    # a node that is no class representative goes down: a row delta
    row = next(i for i in range(first.n_real)
               if i not in first._base.class_reps)
    down = first.nodes[row]
    index += 1
    store.update_node_status(index, down.id, consts.NODE_STATUS_DOWN)
    snap = store.snapshot()
    plain = ClusterMatrix(snap, job)
    assert plain.build_kind == "delta" and plain.feas_build is None
    again = ClusterMatrix(snap, escaped)
    assert again.feas_build is not None
    np.testing.assert_array_equal(
        again.feasible[: again.n_real], host_mask(snap, escaped, again.nodes))
    assert ClusterMatrix(snap, escaped).feas_build is None


def test_compiled_operands_outlive_one_build():
    nodes, job = pinned_cluster()
    job.constraints.append(Constraint(
        ltarget="${attr.kernel.name}", operand="regexp", rtarget="^lin"))
    job.constraints.append(Constraint(
        ltarget="${attr.nomad.version}", operand="version",
        rtarget=">= 0.1"))
    store, _ = store_of(nodes, job)
    _compiled_regexp.cache_clear()
    _parsed_constraints.cache_clear()
    ClusterMatrix(store.snapshot(), job)
    matrix_mod._FEAS_CACHE.clear()
    ClusterMatrix(store.snapshot(), job)     # a second build
    assert _compiled_regexp.cache_info().misses == 1
    assert _parsed_constraints.cache_info().misses == 1
    assert _compiled_regexp("(") is None and _parsed_constraints("x y") is None


# ------------------------------------------- lanes of one dispatch


def test_lanes_of_different_signatures_in_one_dispatch_get_their_own_masks():
    nodes, _job = pinned_cluster(96)
    store, _ = store_of(nodes)
    snap = store.snapshot()
    jobs = []
    for i, (op, value) in enumerate([
            ("=", "A"), ("=", "B"), ("regexp", "^(A|C)$"), ("!=", "A"),
            (">=", "B"), ("=", "C")]):
        job = mock.job()
        job.id = f"lane-{i}"
        job.task_groups[0].tasks[0].resources.networks = []
        job.constraints = [Constraint(
            ltarget="${attr.platform}", operand=op, rtarget=value)]
        jobs.append(job)
    matrices = [ClusterMatrix(snap, job) for job in jobs]
    assert len({m.base_token for m in matrices}) == 1
    assert len({id(m.feasible) for m in matrices}) == len(jobs)

    batcher = PlacementBatcher(window=0.0)
    units = batcher.open_cohort(len(jobs))
    chosen = [None] * len(jobs)

    def lane(i):
        asks = make_asks(*matrices[i].build_asks([0] * 8))
        choices, _ = batcher.place(matrices[i], asks, host_prng_key(i),
                                   CONFIG, cohort=units[i])
        chosen[i] = [int(c) for c in np.asarray(choices) if c >= 0]

    threads = [threading.Thread(target=lane, args=(i,))
               for i in range(len(jobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    stats = batcher.stats()
    assert stats["dispatches"] == 1 and stats["compact_dispatches"] == 1
    assert stats["batched_requests"] == len(jobs)
    for job, matrix, rows in zip(jobs, matrices, chosen):
        want = host_mask(snap, job, matrix.nodes)[:, 0]
        assert len(rows) == 8 and all(want[row] for row in rows), job.id
    # pre-resolution: lanes of one dispatch see each other's claims, so
    # the masks are what separates them, not luck
    assert set(chosen[0]).isdisjoint(chosen[1])


# ------------------------------------------------ the span and the stats


def test_the_span_is_recorded_on_a_miss_only_and_the_agent_states_classes():
    from nomad_tpu.api.http import HTTPServer
    from nomad_tpu.scheduler.testing import Harness, seed_harness_cluster
    from nomad_tpu.structs import new_eval

    assert trace.STAGE_FEASIBILITY_BUILD in trace.ALL_STAGES
    recorder = trace.get_recorder()
    recorder.reset()
    nodes, job = pinned_cluster()
    job.task_groups[0].count = 6
    h = Harness(seed=38)
    seed_harness_cluster(h, nodes=nodes, jobs=[job.copy()])
    twin = job.copy()
    twin.id = "twin"
    h.state.upsert_job(h.next_index(), twin)
    for j in (job, twin):
        ev = new_eval(h.state.job_by_id(j.id),
                      consts.EVAL_TRIGGER_JOB_REGISTER)
        trace.mark(ev.id, ev.trace_id)
        h.process("service-tpu", ev)
        trace.complete(ev.id)
    stages = recorder.stage_stats()
    assert stages["matrix.build"]["count"] == 2
    assert stages[trace.STAGE_FEASIBILITY_BUILD]["count"] == 1

    stats = matrix_mod.compress_stats()
    assert stats["nodes"] == 48 and stats["computed_classes"] == 36
    assert stats["classes"] >= stats["computed_classes"]
    http = HTTPServer(None, host="127.0.0.1", port=0)
    out = http._agent_self("GET", {}, None)
    assert out["matrix_compress"] == stats
