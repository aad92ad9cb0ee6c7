"""Drain-storm graceful migration (nomad_tpu/migrate + the dense drain
path): migration-budget governor units, the budget-deferral follow-up
eval, the nodes-table-index regression that keeps drain flips visible
to the device-resident base, and the drain-storm soak — drain 30% of a
100-node cluster mid-batch (with seeded faults) and assert exactly-once
displaced-alloc terminals, zero placements on draining nodes, bounded
in-flight migrations, and occupancy recovery."""

import time
from collections import Counter

import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import FaultSpec, chaos
from nomad_tpu.migrate import MigrationGovernor, configure, get_governor
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import consts
from nomad_tpu.structs.eval import new_eval


@pytest.fixture(autouse=True)
def _restore_globals():
    """Governor and chaos registry are process-global; leave them the
    way the defaults have them."""
    yield
    chaos.disarm()
    configure(migrate_max_parallel=32, preemption_enabled=False)


def wait_until(fn, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------
# governor units


def test_governor_grants_up_to_budget_and_tracks_high_water():
    g = MigrationGovernor(max_parallel=5)
    assert g.acquire(3) == 3
    assert g.acquire(4) == 2  # only 2 slots left
    assert g.acquire(1) == 0  # full
    s = g.stats()
    assert s["in_flight"] == 5 and s["high_water"] == 5
    assert s["deferred_total"] == 3  # 2 + 1 deferred
    g.release(5)
    assert g.stats()["in_flight"] == 0
    assert g.acquire(2) == 2
    g.release(2)
    assert g.stats()["released_total"] == 7


def test_governor_unbounded_still_observes():
    g = MigrationGovernor(max_parallel=0)
    assert g.acquire(100) == 100
    assert g.stats()["high_water"] == 100
    g.release(100)
    assert g.stats()["deferred_total"] == 0


def test_governor_release_never_goes_negative():
    g = MigrationGovernor(max_parallel=4)
    g.release(3)
    assert g.stats()["in_flight"] == 0
    assert g.acquire(4) == 4


# ---------------------------------------------------------------------
# satellite regression: update_node_drain must bump the nodes-table
# index so the resident base family observes drain flips as deltas
# (a silently stale node_ok bit would place onto draining nodes)


def test_update_node_drain_bumps_nodes_table_index():
    h = Harness()
    node = mock.node()
    node.compute_class()
    h.state.upsert_node(h.next_index(), node)
    before = h.state.snapshot()
    idx_before = before.index("nodes")
    h.state.update_node_drain(h.next_index(), node.id, True)
    after = h.state.snapshot()
    assert after.index("nodes") > idx_before
    stored = after.node_by_id(node.id)
    assert stored.drain and stored.modify_index == after.index("nodes")


def test_drain_flip_rides_resident_node_delta():
    """A drain transition between two cacheable matrix builds must
    arrive as a node-axis DELTA (node_ok row flip), not a rebuild —
    and the flipped bit must actually be False."""
    from nomad_tpu.models.matrix import ClusterMatrix

    h = Harness()
    nodes = []
    for _ in range(8):
        n = mock.node()
        n.compute_class()
        h.state.upsert_node(h.next_index(), n)
        nodes.append(n)
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    sjob = h.state.job_by_id(job.id)

    m1 = ClusterMatrix(h.state.snapshot(), sjob)
    row = m1.nodes.index(next(n for n in m1.nodes if n.id == nodes[3].id))
    assert bool(m1.node_ok[row])

    h.state.update_node_drain(h.next_index(), nodes[3].id, True)
    m2 = ClusterMatrix(h.state.snapshot(), sjob)
    assert m2.build_kind == "delta", m2.build_kind
    assert not bool(m2.node_ok[row])
    # un-drain flips it back, again as a delta
    h.state.update_node_drain(h.next_index(), nodes[3].id, False)
    m3 = ClusterMatrix(h.state.snapshot(), sjob)
    assert m3.build_kind == "delta"
    assert bool(m3.node_ok[row])


# ---------------------------------------------------------------------
# budget-deferral follow-up eval (harness level)


def _seed_displaced(h, n_nodes=6, count=8):
    """Cluster where `count` allocs sit on ONE node that then drains:
    the next eval sees them all in diff.migrate."""
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        n.resources.cpu = 4000
        n.resources.memory_mb = 8192
        n.compute_class()
        h.state.upsert_node(h.next_index(), n)
        nodes.append(n)
    job = mock.job()
    job.task_groups[0].count = count
    task = job.task_groups[0].tasks[0]
    task.resources.networks = []
    task.resources.cpu = 20
    task.resources.memory_mb = 16
    h.state.upsert_job(h.next_index(), job)
    sjob = h.state.job_by_id(job.id)
    allocs = []
    for i in range(count):
        a = mock.alloc()
        a.job = sjob
        a.job_id = sjob.id
        a.node_id = nodes[0].id
        a.name = f"{sjob.name}.{sjob.task_groups[0].name}[{i}]"
        a.task_group = sjob.task_groups[0].name
        allocs.append(a)
    h.state.upsert_allocs(h.next_index(), allocs)
    h.state.update_node_drain(h.next_index(), nodes[0].id, True)
    return sjob, nodes


def test_budget_defers_migrations_to_follow_up_eval():
    configure(migrate_max_parallel=3)
    h = Harness(seed=11)
    sjob, nodes = _seed_displaced(h, count=8)
    ev = new_eval(sjob, consts.EVAL_TRIGGER_NODE_UPDATE)
    h.process("service", ev)

    plan = h.plans[0]
    stops = [a for lst in plan.node_update.values() for a in lst]
    migrating = [a for a in stops
                 if a.desired_description == "alloc is being migrated"]
    assert len(migrating) == 3  # exactly the budget
    follow = [e for e in h.create_evals
              if e.triggered_by == consts.EVAL_TRIGGER_MIGRATION]
    assert len(follow) == 1
    assert follow[0].job_id == sjob.id and follow[0].previous_eval == ev.id
    # slots were released when the attempt's submit finished
    assert get_governor().stats()["in_flight"] == 0
    # driving the follow-up evals to completion drains the backlog
    for _ in range(5):
        nxt = [e for e in h.create_evals
               if e.triggered_by == consts.EVAL_TRIGGER_MIGRATION][-1]
        before = len(h.create_evals)
        h.process("service", nxt)
        if len(h.create_evals) == before:
            break
    live = [a for a in h.state.allocs_by_job(sjob.id)
            if not a.terminal_status()]
    assert len(live) == 8
    assert all(a.node_id != nodes[0].id for a in live)


def test_unbounded_budget_migrates_in_one_wave():
    configure(migrate_max_parallel=0)
    h = Harness(seed=12)
    sjob, nodes = _seed_displaced(h, count=8)
    h.process("service", new_eval(sjob, consts.EVAL_TRIGGER_NODE_UPDATE))
    assert not [e for e in h.create_evals
                if e.triggered_by == consts.EVAL_TRIGGER_MIGRATION]
    live = [a for a in h.state.allocs_by_job(sjob.id)
            if not a.terminal_status()]
    assert len(live) == 8
    assert all(a.node_id != nodes[0].id for a in live)


def test_mid_migration_chaos_error_leaves_nothing_staged():
    """drain.mid_migration 'error' fires BEFORE any budget claim or
    staged eviction: the eval dies (redelivery in a live cluster), the
    plan never submits, and no displaced alloc is half-evicted."""
    configure(migrate_max_parallel=8)
    h = Harness(seed=13)
    sjob, nodes = _seed_displaced(h, count=4)
    ev = new_eval(sjob, consts.EVAL_TRIGGER_NODE_UPDATE)
    from nomad_tpu.chaos import ChaosInjectedError

    with chaos.armed(7, [FaultSpec("drain.mid_migration", "error")]):
        # The fault surfaces out of the scheduler like any worker-side
        # crash: the live pipeline nacks and the broker redelivers.
        with pytest.raises(ChaosInjectedError):
            h.process("service", ev)
        stops = [a for a in h.state.allocs_by_job(sjob.id)
                 if a.desired_status == consts.ALLOC_DESIRED_STOP]
        assert stops == []
        assert get_governor().stats()["in_flight"] == 0
    # disarmed, the same eval replans cleanly (the redelivery analog)
    h2 = Harness(state=h.state, seed=14)
    h2._next_index = h._next_index
    h2.process("service", new_eval(sjob, consts.EVAL_TRIGGER_NODE_UPDATE))
    live = [a for a in h2.state.allocs_by_job(sjob.id)
            if not a.terminal_status()]
    assert len(live) == 4
    assert all(a.node_id != nodes[0].id for a in live)


# ---------------------------------------------------------------------
# the acceptance soak: drain 30% of a 100-node cluster mid-batch under
# seeded faults


@pytest.mark.slow
def test_drain_storm_soak_100_nodes():
    _drain_storm_soak(n_nodes=100, n_jobs=10, count=6, drain_frac=0.3,
                      budget=8,
                      schedule=[
                          FaultSpec("broker.deliver", "drop", prob=0.2,
                                    count=6),
                          FaultSpec("drain.mid_migration", "error",
                                    count=2),
                      ])


def test_drain_storm_soak_tier1():
    """Tier-1 sized arm of the acceptance soak: same invariants, 100
    nodes, smaller job set, seeded mid-migration faults."""
    _drain_storm_soak(n_nodes=100, n_jobs=6, count=5, drain_frac=0.3,
                      budget=6,
                      schedule=[
                          FaultSpec("drain.mid_migration", "error",
                                    count=2),
                      ])


def _drain_storm_soak(n_nodes, n_jobs, count, drain_frac, budget,
                      schedule):
    server = Server(ServerConfig(
        num_schedulers=4,
        scheduler_factories={"service": "service-tpu"},
        eval_batch_size=16,
        eval_nack_timeout=2.0,
        eval_delivery_limit=8,
        migrate_max_parallel=budget,
    ))
    server.start()
    try:
        nodes = []
        for _ in range(n_nodes):
            node = mock.node()
            node.compute_class()
            server.node_register(node)
            nodes.append(node)

        jobs = []
        for i in range(n_jobs):
            job = mock.job()
            job.id = f"drain-{i}"
            job.task_groups[0].count = count
            task = job.task_groups[0].tasks[0]
            task.resources.cpu = 20
            task.resources.memory_mb = 16
            task.resources.networks = []
            server.job_register(job)
            jobs.append(job)

        def live(job_id):
            return [a for a in server.fsm.state.allocs_by_job(job_id)
                    if not a.terminal_status()]

        assert wait_until(
            lambda: all(len(live(j.id)) == count for j in jobs), 90.0), {
                j.id: len(live(j.id)) for j in jobs}

        pre_by_node = {a.id: a.node_id
                       for j in jobs for a in live(j.id)}

        # Re-baseline the process-global governor's window counters:
        # this soak measures THIS storm's high-water, not the suite's.
        get_governor().reset_stats()
        # Drain 30% of the cluster mid-batch under the seeded faults.
        drained = [n.id for n in nodes[: int(n_nodes * drain_frac)]]
        displaced = {aid for aid, nid in pre_by_node.items()
                     if nid in set(drained)}
        chaos.arm(424242, schedule)
        for nid in drained:
            server.node_update_drain(nid, True)

        assert wait_until(
            lambda: all(len(live(j.id)) == count for j in jobs)
            and all(a.node_id not in set(drained)
                    for j in jobs for a in live(j.id))
            and server.broker.ready_count() == 0
            and server.broker.unacked_count() == 0
            # wait-delayed migration follow-ups sit in neither queue
            # until their timer fires: settle means every eval reached
            # a terminal, not just that the queues look empty.
            and not [e for e in server.fsm.state.evals()
                     if not e.terminal_status()], 120.0), (
                server.broker.stats(),
                {j.id: len(live(j.id)) for j in jobs},
                [e for e in server.fsm.state.evals()
                 if not e.terminal_status()])
        fired = chaos.firing_log()
        unfired = chaos.unfired()
        chaos.disarm()
        assert fired and not unfired, (fired,
                                       [s.to_dict() for s in unfired])

        state = server.fsm.state
        # Exactly-once terminals: every displaced alloc reached exactly
        # one terminal (stop/migrated) — its single store record is
        # desired-stop, and no duplicate ids exist.
        for aid in displaced:
            a = state.alloc_by_id(aid)
            assert a is not None and a.desired_status == \
                consts.ALLOC_DESIRED_STOP, (aid, a)
        # Zero placements on draining nodes; no duplicate live slots.
        all_live = [a for j in jobs for a in live(j.id)]
        assert all(a.node_id not in set(drained) for a in all_live)
        dup = {k: c for k, c in Counter(
            (a.job_id, a.name) for a in all_live).items() if c > 1}
        assert not dup, dup
        # Occupancy recovery: the live set is back to the pre-drain
        # baseline in size.
        assert len(all_live) == len(pre_by_node)
        # Bounded in-flight migrations, and the budget actually engaged.
        g = get_governor().stats()
        assert g["high_water"] <= budget, g
        assert g["granted_total"] >= len(displaced), (g, len(displaced))
        assert g["in_flight"] == 0
        # Every eval reached exactly one terminal.
        evals = state.evals()
        assert not [e.id for e in evals if not e.terminal_status()]
        assert len({e.id for e in evals}) == len(evals)
    finally:
        chaos.disarm()
        server.shutdown()
