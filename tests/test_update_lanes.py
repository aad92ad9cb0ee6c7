"""An update of a running job is a lane of its batch's dispatch
(`kernels/differential.py` `build_update_scenario`).

An eval whose plan stops something and places something used to build
an uncacheable cluster base by a walk over every node and dispatched
alone. Its matrix now keeps the snapshot's base token and states the
plan as a patch on the rows it touches; the shared-base programs put a
lane's patch into that lane's view alone. Held here, on seeded data:

- the same stops, the same in-place set and as many placements as the
  host `GenericScheduler` gives on the same snapshot, and placements
  the oracle accepts after the stops;
- the patched view equals, row for row and exactly, what the walk over
  every node gives for the same plan (the walk is the test's plain
  reference: the program no longer reaches it);
- a matrix under a plan with stops has the base token and a compact
  overlay and rides ONE dispatch with two arrival lanes;
- no other lane places on capacity only the update lane's stops free,
  and the carry handed on holds the update lane's placements; the
  control leaves the patch in the carry and is caught.
"""

import numpy as np
import pytest

from nomad_tpu.kernels.differential import (
    UPDATE_SEEDS,
    build_update_scenario,
    judge_shared_snapshot,
    judge_update_plan,
    patched_view,
    place_on_one_snapshot,
    plan_summary,
    walked_view,
)
from nomad_tpu.models.matrix import PLAN_BUCKETS, ClusterMatrix
from nomad_tpu.structs import Plan, consts
from nomad_tpu.structs.eval import new_eval

VIEW = ("util", "bw_used", "ports_free", "job_count", "tg_count")


def planned(h, job, factory):
    """The plan `factory` makes for `job`'s registered version on the
    scenario's store (the harness records plans and writes none)."""
    h.plans.clear()
    h.evals.clear()
    h.process(factory, new_eval(job, consts.EVAL_TRIGGER_JOB_REGISTER))
    assert h.evals[-1].status == consts.EVAL_STATUS_COMPLETE
    assert len(h.plans) == 1
    return h.plans[0]


def stops_of(snap, job) -> Plan:
    """The plan of a destructive update before anything is placed:
    every live allocation of the job stopped."""
    plan = Plan(eval_id="update", priority=job.priority, job=job)
    for a in snap.allocs_by_job(job.id):
        if not a.terminal_status():
            plan.append_update(a, consts.ALLOC_DESIRED_STOP, "update")
    return plan


@pytest.mark.parametrize("kind", ["push", "scale", "touch"])
@pytest.mark.parametrize("seed", UPDATE_SEEDS)
def test_an_update_plans_as_the_host_scheduler_does(seed, kind):
    h, updates, _arrivals, _tight = build_update_scenario(seed)
    job = updates[kind]
    snap = h.state.snapshot()
    count = job.task_groups[0].count
    host = planned(h, job, "service")
    dense = planned(h, job, "service-tpu")
    want = plan_summary(snap, host)
    assert plan_summary(snap, dense) == want
    assert {"push": (count, 0, count), "scale": (0, count - 2, 2),
            "touch": (0, count, 0)}[kind] == (
        len(want["stops"]), len(want["inplace"]), want["placed"])
    for plan in (host, dense):
        assert judge_update_plan(snap, plan, job, seed) == []


@pytest.mark.parametrize("seed", UPDATE_SEEDS)
def test_the_patched_view_is_the_walk_over_every_node(seed):
    h, updates, _arrivals, _tight = build_update_scenario(seed)
    snap = h.state.snapshot()
    for kind, job in updates.items():
        plans = [planned(h, job, "service-tpu")]
        if kind == "push":
            plans.append(stops_of(snap, job))
        for plan in plans:
            walk = walked_view(snap, job, plan)
            lane = ClusterMatrix(snap, job, plan,
                                 rows_floor=job.task_groups[0].count)
            assert lane.base_token is not None
            dense = ClusterMatrix(snap, job, plan, plan_overlay=True)
            assert dense.base_token is None and dense.plan_patch is None
            assert dense.compact_overlay is None
            views = {"lane": patched_view(lane), "dense": patched_view(dense)}
            for name in VIEW:
                for which, view in views.items():
                    np.testing.assert_array_equal(
                        view[name], walk[name],
                        err_msg=f"{kind} {which} {name}")
            # the cached base itself was not written to
            plain = ClusterMatrix(snap, job, None)
            assert plain.base_token == lane.base_token
            assert plain.util is lane.util
            rows = lane.plan_patch[0]
            assert len(rows) in PLAN_BUCKETS
            assert lane.plan_patch_span[2] == {
                "rows": int((rows < lane.n).sum()), "bucket": len(rows)}


@pytest.mark.parametrize("seed", UPDATE_SEEDS)
def test_an_arrival_carries_the_empty_patch(seed):
    h, _updates, arrivals, _tight = build_update_scenario(seed)
    snap = h.state.snapshot()
    job = arrivals[0]
    for plan in (None, Plan(eval_id="e", priority=50, job=job)):
        matrix = ClusterMatrix(snap, job, plan)
        rows, vals = matrix.plan_patch
        assert len(rows) == PLAN_BUCKETS[0] and (rows == matrix.n).all()
        assert not vals.any() and matrix.plan_patch_span is None
        assert matrix.base_token is not None
        assert matrix.compact_overlay is not None


@pytest.mark.parametrize("seed", UPDATE_SEEDS)
def test_an_update_lane_rides_one_dispatch_with_two_arrivals(seed):
    h, updates, arrivals, tight = build_update_scenario(seed)
    snap = h.state.snapshot()
    push = updates["push"]
    plan = stops_of(snap, push)
    probe = ClusterMatrix(snap, push, plan,
                          rows_floor=push.task_groups[0].count)
    assert probe.base_token == ClusterMatrix(snap, arrivals[0]).base_token
    assert probe.compact_overlay is not None
    # the stopped allocations no longer count on their nodes
    assert not (probe.compact_overlay.job_rows < probe.n).any()
    assert not probe.job_count.any()

    jobs = [push] + arrivals[:2]
    lanes, batcher = place_on_one_snapshot(
        snap, jobs, seed, plans={push.id: plan})
    assert batcher.dispatches == 1 and batcher.batched_requests == 3
    assert batcher.compact_dispatches == 1
    by_job = {job.id: (matrix, choices) for job, matrix, choices in lanes}
    row_of = {node.id: i for i, node in enumerate(probe.nodes)}
    tight_rows = {row_of[node_id] for node_id in tight}
    # the update lane places on room only its own stops free ...
    assert set(by_job[push.id][1]) & tight_rows
    # ... the arrivals, which scan after it, do not
    for job in arrivals[:2]:
        assert not set(by_job[job.id][1]) & tight_rows
    arrived = [lane for lane in lanes if lane[0].id != push.id]
    assert judge_shared_snapshot(snap, arrived, seed) == []
    stops = [a for v in plan.node_update.values() for a in v]
    assert judge_shared_snapshot(snap, lanes, seed, stops=stops) == []


@pytest.mark.parametrize("seed", UPDATE_SEEDS)
def test_the_carry_handed_on_holds_placements_and_no_stops(seed):
    """Two queues on the token (the wide arrival is of the next ask
    rung): the second dispatch starts from the first one's carry."""
    h, updates, arrivals, tight = build_update_scenario(seed)
    snap = h.state.snapshot()
    push = updates["push"]
    plan = stops_of(snap, push)
    lanes, batcher = place_on_one_snapshot(
        snap, [push] + arrivals, seed, plans={push.id: plan})
    assert batcher.dispatches == 2 and batcher.plain_handovers == 1
    matrix = lanes[0][1]
    row_of = {node.id: i for i, node in enumerate(matrix.nodes)}
    tight_rows = {row_of[node_id] for node_id in tight}
    arrived = [lane for lane in lanes if lane[0].id != push.id]
    assert len(arrived) == 3
    for _job, _matrix, choices in arrived:
        assert not set(choices) & tight_rows
    # nobody places on what the stops would free: the arrivals fit
    # beside everything that is live, the stopped allocations included
    assert judge_shared_snapshot(snap, arrived, seed) == []
    # and everybody saw the update lane's placements: the whole batch
    # fits once its plans have committed
    stops = [a for v in plan.node_update.values() for a in v]
    assert judge_shared_snapshot(snap, lanes, seed, stops=stops) == []


def test_control_a_patch_left_in_the_carry_is_caught(monkeypatch):
    """The same batch with the lane's patch never taken out again: the
    arrivals that scan after the update lane place on the room its
    stops would free, and the judge says so."""
    import jax

    from nomad_tpu.ops import binpack

    real = binpack._patched
    monkeypatch.setattr(
        binpack, "_patched",
        lambda carry, patch, sign: real(carry, patch, sign)
        if sign > 0 else carry)
    jax.clear_caches()
    try:
        caught = 0
        for seed in list(UPDATE_SEEDS)[:3]:
            h, updates, arrivals, _tight = build_update_scenario(seed)
            snap = h.state.snapshot()
            push = updates["push"]
            lanes, _batcher = place_on_one_snapshot(
                snap, [push] + arrivals, seed,
                plans={push.id: stops_of(snap, push)})
            arrived = [lane for lane in lanes if lane[0].id != push.id]
            caught += bool(judge_shared_snapshot(snap, arrived, seed))
        assert caught == 3
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_a_plan_past_the_ladder_takes_a_state_of_its_own(monkeypatch):
    from nomad_tpu.models import matrix as matrix_mod

    h, updates, _arrivals, _tight = build_update_scenario(UPDATE_SEEDS[0])
    snap = h.state.snapshot()
    job = updates["push"]
    plan = stops_of(snap, job)
    monkeypatch.setattr(matrix_mod, "PLAN_BUCKETS", [2])
    matrix = ClusterMatrix(snap, job, plan)
    assert matrix.base_token is None and matrix.plan_patch is None
    walk = walked_view(snap, job, plan)
    view = patched_view(matrix)
    for name in VIEW:
        np.testing.assert_array_equal(view[name], walk[name], err_msg=name)


def test_an_in_place_update_makes_no_dispatch():
    """An eval whose every update is in place stays on the host: it
    stages no placement, builds no patch and makes no dispatch; a push
    on the same store makes one."""
    from nomad_tpu.scheduler.batcher import get_batcher

    h, updates, _arrivals, _tight = build_update_scenario(UPDATE_SEEDS[1])
    snap = h.state.snapshot()
    before = get_batcher().stats()
    plan = planned(h, updates["touch"], "service-tpu")
    after = get_batcher().stats()
    assert plan_summary(snap, plan)["placed"] == 0
    assert not plan.node_update
    for name in ("dispatches", "batched_requests"):
        assert after[name] == before[name]
    planned(h, updates["push"], "service-tpu")
    assert get_batcher().stats()["batched_requests"] \
        == after["batched_requests"] + 1
