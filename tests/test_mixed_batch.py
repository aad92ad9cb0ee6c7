"""A pipeline batch that holds gangs AND plain evals (scheduler/batcher.py,
scheduler/tpu.py, ops/gang.py): the deployment `pai-gpu-1800` is made of
such batches.

The contract under test:

- the batch is two dispatches, PLAIN LANES FIRST, and the plain
  program's final carry (utilisation, bandwidth, free ports after every
  lane's claims) is where the gang lanes start: on a fleet the batch
  fills, every gang is placed whole, every plan passes the applier first
  time, and the same batch with the hand-over cut (a monkeypatch; the
  program has no switch) loses whole gangs to blind choices;
- the two dispatches equal the single-lane programs (the plain
  references) applied in the batch's order on one carried state;
- placed-or-rejected agrees with the host stack applied in that order,
  and every gang's plan passes the oracle (judge_gang_plan);
- an eval of one to three asks rides its batch's plain dispatch as a
  lane; alone (a cohort of one) or replanning (its unit has ridden its
  dispatch) it still takes the host iterators;
- free gangs of 8, 32 and 128 share one dispatch; a constrained gang
  lands only on feasible machines and its job's second eval builds no
  mask; every member of a gang holds the ask's third axis (disk, the
  axis `pai-gpu-1800` carries GPUs on).
"""

import random
import time

import numpy as np
import pytest

from nomad_tpu import mock, trace
from nomad_tpu.gang import gang_stats, reset_gang_stats
from nomad_tpu.ops.binpack import (
    Asks,
    NodeState,
    PlacementConfig,
    batched_placement_program_overlay,
    host_prng_key,
    placement_program_jit,
)
from nomad_tpu.ops.gang import (
    GangBase,
    GangConfig,
    batched_gang_placement_program_jit,
    gang_placement_program_jit,
    make_gang_lane,
    make_gang_state,
)
from nomad_tpu.scheduler.batcher import PlacementBatcher, get_batcher
from nomad_tpu.scheduler.testing import Harness, seed_harness_cluster
from nomad_tpu.structs import Constraint, Gang, consts
from nomad_tpu.structs.eval import new_eval
from nomad_tpu.utils.metrics import get_metrics

from serial_reference import no_patches
from test_gang_batched import (
    dense_server,
    live_allocs,
    run_as_one_batch,
)

SLOT_CPU, SLOT_MEM = 1000, 1024
GPU_TYPES = ("T4", "P100", "MISC", "V100M32", "V100")


@pytest.fixture(autouse=True)
def _hygiene():
    from nomad_tpu.admission import get_breaker
    from nomad_tpu.chaos import chaos

    reset_gang_stats()
    yield
    chaos.disarm()
    reset_gang_stats()
    b = get_breaker()
    b.reset()
    b.configure_defaults()


def gpu_nodes(slots, disk_mb=100_000):
    """One node a slot count, its class one of five in turn: the i-th
    node is of GPU type i mod 5 (`meta.gpu_type`, and a node class of
    that name)."""
    nodes = []
    for i, s in enumerate(slots):
        node = mock.node()
        node.resources.cpu = int(s) * SLOT_CPU
        node.resources.memory_mb = int(s) * SLOT_MEM
        node.resources.disk_mb = disk_mb
        node.reserved.cpu = node.reserved.memory_mb = 0
        node.reserved.disk_mb = 0
        node.node_class = GPU_TYPES[i % 5].lower()
        node.meta["gpu_type"] = GPU_TYPES[i % 5]
        node.compute_class()
        nodes.append(node)
    return nodes


def slot_job(jid, count, gang=False, disk_mb=10, constraint=None):
    """`count` one-slot instances, a free gang or a plain task group."""
    job = mock.job()
    job.id = job.name = jid
    job.type = consts.JOB_TYPE_BATCH
    tg = job.task_groups[0]
    tg.count = count
    tg.gang = Gang() if gang else None
    tg.ephemeral_disk.size_mb = disk_mb
    task = tg.tasks[0]
    task.resources.cpu = SLOT_CPU
    task.resources.memory_mb = SLOT_MEM
    task.resources.networks = []
    if constraint is not None:
        job.constraints.append(Constraint(
            ltarget="${meta.gpu_type}", operand=constraint[0],
            rtarget=constraint[1]))
    return job


def counted(suffix):
    life = get_metrics().inmem._life.counters
    return sum(c[1] for name, c in list(life.items())
               if name.endswith(suffix))


# ---------------------------------------------------------------------
# the two dispatches against the single-lane programs, in order


N_PAD = 128


def seeded_state(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(40, 90))
    slots = rng.integers(1, 5, n)
    capacity = np.zeros((N_PAD, 4), np.float32)
    capacity[:n, 0] = slots * SLOT_CPU
    capacity[:n, 1] = slots * SLOT_MEM
    capacity[:n, 2] = slots * 1000
    capacity[:n, 3] = 10_000
    util = np.zeros((N_PAD, 4), np.float32)
    used = rng.integers(0, slots + 1)
    util[:n, 0] = used * SLOT_CPU
    util[:n, 1] = used * SLOT_MEM
    util[:n, 2] = used * 1000
    node_ok = np.zeros(N_PAD, bool)
    node_ok[:n] = True
    return {"capacity": capacity, "util": util, "node_ok": node_ok,
            "bw_avail": np.full(N_PAD, 1000, np.float32),
            "bw_used": np.zeros(N_PAD, np.float32),
            "ports_free": np.full(N_PAD, 100, np.float32),
            "topo": np.where(node_ok, np.arange(N_PAD), -1).astype(np.int32),
            "n": n}


@pytest.mark.parametrize("seed", [4201, 4202, 4203, 4204])
def test_the_two_dispatches_equal_the_single_lane_programs_in_order(seed):
    """Gang lanes through the batched gang program, then plain lanes
    through the batched plain program started from the gang program's
    carry (the order of a later plain dispatch on a token gangs have
    claimed on; the plain program's own carry, the gangs' start in a
    mixed batch, is held to the same sum below): choice for choice what
    the single-gang and single-eval programs give when applied one after
    the other on one carried state, under the same keys."""
    fs = seeded_state(seed)
    rng = np.random.default_rng(seed + 1)
    ask = np.asarray([SLOT_CPU, SLOT_MEM, 1000, 0], np.float32)
    gang_cfg = GangConfig(anti_affinity_penalty=5.0, g_pad=256)
    plain_cfg = PlacementConfig(anti_affinity_penalty=5.0)
    sizes = [int(k) for k in rng.choice([2, 5, 8], 3)]
    lanes, gkeys = [], []
    for i, k in enumerate(sizes):
        active = np.zeros(8, bool)
        active[:k] = True
        lanes.append(make_gang_lane(
            feas_row=rng.random(N_PAD) < 0.85,
            job_count=np.zeros(N_PAD, np.int32),
            dh_presence=np.zeros(N_PAD, np.int32),
            ask_res=ask, ask_bw=0, ask_ports=0, active=active))
        gkeys.append(host_prng_key(seed * 17 + i))
    idle = lanes[0]._replace(active=np.zeros(8, bool))
    stacked = [np.stack(xs) for xs in zip(*(lanes + [idle] * (16 - 3)))]
    base = GangBase(fs["capacity"], fs["capacity"], fs["util"],
                    fs["bw_avail"], fs["bw_used"], fs["ports_free"],
                    fs["node_ok"], fs["topo"])
    out = batched_gang_placement_program_jit(
        base, type(lanes[0])(*stacked),
        np.stack(gkeys + [gkeys[0]] * (16 - 3)), gang_cfg)
    gang_choices, carry = np.asarray(out[0]), out[3:6]

    # the plain lanes: four evals of one ask each, on the carry
    b = 4
    asks = Asks(
        resources=np.tile(np.concatenate(
            [ask[None], np.zeros((7, 4), np.float32)])[None], (b, 1, 1)),
        bw=np.zeros((b, 8), np.float32), ports=np.zeros((b, 8), np.float32),
        tg_index=np.zeros((b, 8), np.int32),
        active=np.tile(np.arange(8) < 1, (b, 1)),
        job_distinct_hosts=np.zeros(b, bool),
        tg_distinct_hosts=np.zeros((b, 1), bool))
    feasible = (rng.random((b, N_PAD, 1)) < 0.85) & fs["node_ok"][None, :, None]
    pkeys = np.stack([host_prng_key(seed * 31 + i) for i in range(b)])
    state = NodeState(
        capacity=fs["capacity"], sched_capacity=fs["capacity"],
        util=carry[0], bw_avail=fs["bw_avail"], bw_used=carry[1],
        ports_free=carry[2], job_count=np.zeros((b, N_PAD), np.int32),
        tg_count=np.zeros((b, N_PAD, 1), np.int32), feasible=feasible,
        node_ok=fs["node_ok"])
    plain_out = batched_placement_program_overlay(
        state, asks, pkeys, plain_cfg, no_patches(b, N_PAD))
    plain_choices = np.asarray(plain_out[0])

    # the reference: one lane at a time on one carried state
    util = fs["util"].copy()
    for i, lane in enumerate(lanes):
        gs = make_gang_state(
            fs["capacity"], fs["capacity"], util, fs["bw_avail"],
            fs["bw_used"], fs["ports_free"], lane.feas_row & fs["node_ok"],
            lane.job_count, lane.dh_presence, fs["topo"])
        choices = np.asarray(gang_placement_program_jit(
            gs, lane.ask_res, lane.ask_bw, lane.ask_ports, lane.active,
            gkeys[i], gang_cfg)[0])
        assert (choices == gang_choices[i]).all()
        assert (choices[:sizes[i]] >= 0).all() or (choices == -1).all()
        for c in choices[choices >= 0]:
            util[c] += ask
    assert np.array_equal(np.asarray(carry[0]), util)
    for i in range(b):
        one = NodeState(
            capacity=fs["capacity"], sched_capacity=fs["capacity"],
            util=util, bw_avail=fs["bw_avail"], bw_used=fs["bw_used"],
            ports_free=fs["ports_free"],
            job_count=np.zeros(N_PAD, np.int32),
            tg_count=np.zeros((N_PAD, 1), np.int32), feasible=feasible[i],
            node_ok=fs["node_ok"])
        choices = np.asarray(placement_program_jit(
            one, Asks(*(x[i] for x in asks)), pkeys[i], plain_cfg)[0])
        assert (choices == plain_choices[i]).all()
        if choices[0] >= 0:
            util = util.copy()
            util[choices[0]] += ask
    # the plain program's carry is the state after its lanes too: what
    # it hands the gangs of a mixed batch
    assert np.array_equal(np.asarray(plain_out[2][0]), util)
    # no plain lane took a slot a gang had claimed: nothing over capacity
    assert (util <= fs["capacity"] + 1e-3).all()


# ---------------------------------------------------------------------
# a dev server: the order, the carried claims, the applier


def full_fleet_batch(server):
    """80 one-slot machines of five classes; two free gangs of 16, two
    plain jobs of 4 and 32 one-ask jobs: 72 slots of 80, in one batch."""
    for node in gpu_nodes([1] * 80):
        server.node_register(node)
    gangs = [slot_job(f"mix-g{i}", 16, gang=True) for i in range(2)]
    plain = [slot_job(f"mix-p{i}", 4) for i in range(2)]
    ones = [slot_job(f"mix-o{i}", 1) for i in range(32)]
    jobs = ones[:8] + gangs[:1] + plain + ones[8:20] + gangs[1:] + ones[20:]
    return gangs, plain, ones, jobs


def test_a_mixed_batch_places_every_gang_whole_with_no_rejection():
    from nomad_tpu.kernels.differential import judge_gang_plan

    server = dense_server()
    try:
        gangs, plain, ones, jobs = full_fleet_batch(server)
        snap = server.fsm.state.snapshot()
        before = get_batcher().stats()
        small = counted("scheduler.small_route_host_evals")
        trace.get_recorder().reset()
        plans = run_as_one_batch(server, jobs)
        after = get_batcher().stats()

        for job in gangs:
            assert len(live_allocs(server, job)) == 16
        for job in plain:
            assert len(live_allocs(server, job)) == 4
        for job in ones:
            assert len(live_allocs(server, job)) == 1
        # every plan passed the applier first time: no replan
        assert len(plans) == len(jobs)
        applier = server.plan_applier.stats()
        assert applier["gangs_rejected"] == 0
        assert applier["plans_rejected"] == 0
        assert server.dispatch.stats()["plan_conflicts"] == 0
        by_job = {job.id: job for job in gangs}
        for plan in plans:
            if plan.gang_groups:
                job_id = next(iter(plan.gang_groups)).split("/")[0]
                assert judge_gang_plan(snap, plan, by_job[job_id],
                                       seed=1) == []
        # two dispatches, the plain lanes' first and the gangs' from its
        # carry; the one-ask evals rode theirs as lanes
        assert after["dispatches"] - before["dispatches"] == 2
        assert after["batched_requests"] - before["batched_requests"] == 36
        assert after["mixed_batches"] - before["mixed_batches"] == 1
        assert after["claims_wait_expired"] == before["claims_wait_expired"]
        assert counted("scheduler.small_route_host_evals") == small
        stats = gang_stats()
        assert stats["mixed_batches"] == 1 and "rejected_whole" not in stats
        assert server.stats()["gang"]["mixed_batches"] == 1
        stages = trace.get_recorder().stage_stats()
        assert stages[trace.STAGE_BATCH_CLAIMS]["count"] == 1
        assert trace.STAGE_GANG_REJECTED not in stages
        carried = [s for t in trace.get_recorder().traces(limit=100)
                   for s in t["spans"]
                   if s["name"] == trace.STAGE_BATCH_CLAIMS]
        assert [s["annotations"] for s in carried] == [
            {"gang_lanes": 2, "plain_lanes": 34, "kind": "plain>gang",
             "from_rung": 8, "rung": 32}]
    finally:
        server.shutdown()


@pytest.mark.parametrize("cut", ["claims", "as_the_parent"])
def test_without_the_hand_over_the_same_batch_conflicts(monkeypatch, cut):
    """The control (a monkeypatch: the program has no switch). `claims`:
    the gang lanes start from the base, blind to the 40 slots the plain
    lanes claimed of 80, so about half of their 32 choices are a plain
    lane's node; the order is kept, the plain plans reach the applier
    first and a gang loses all sixteen at once. `as_the_parent`: the
    order is cut too and an eval of one ask takes the host iterators
    whatever its batch (the rule before PR 42): the host walk's plan
    commits first, and the applier rejects the gang. In both the test
    holds the gang dispatch until the plain plans are committed, so
    that "first" is no race."""
    from nomad_tpu.scheduler.batcher import CohortUnit

    monkeypatch.setattr(PlacementBatcher, "_take_claims",
                        lambda self, token, kind: None)
    if cut == "as_the_parent":
        monkeypatch.setattr(PlacementBatcher, "_await_turn",
                            lambda self, first: None)
        monkeypatch.setattr(CohortUnit, "batch_mates", lambda self: 0)
    server = dense_server()
    try:
        gangs, plain, ones, jobs = full_fleet_batch(server)
        # Which plan reaches the applier first is a race the control
        # must not depend on: the gang dispatch (blind either way) goes
        # once half of the batch's 40 plain allocations are committed
        # (not all: a conflicted host-route plan replans in the NEXT
        # pipeline batch, which waits for this one's gangs).
        run_gangs = PlacementBatcher._run_gang_batch

        def after_the_plain_commits(self, batch, config, closed):
            deadline = time.monotonic() + 10.0
            while (sum(len(live_allocs(server, job))
                       for job in plain + ones) < 20
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            return run_gangs(self, batch, config, closed)

        monkeypatch.setattr(PlacementBatcher, "_run_gang_batch",
                            after_the_plain_commits)
        trace.get_recorder().reset()
        plans = run_as_one_batch(server, jobs)
        applier = server.plan_applier.stats()
        assert applier["plans_rejected"] >= 1
        assert len(plans) > len(jobs)
        stats = gang_stats()
        assert "mixed_batches" not in stats
        stages = trace.get_recorder().stage_stats()
        assert trace.STAGE_BATCH_CLAIMS not in stages
        assert applier["gangs_rejected"] >= 1
        assert stats["rejected_whole"] == applier["gangs_rejected"]
        assert stages[trace.STAGE_GANG_REJECTED]["count"] == \
            applier["gangs_rejected"]
        marks = [s for t in trace.get_recorder().traces(limit=200)
                 for s in t["spans"]
                 if s["name"] == trace.STAGE_GANG_REJECTED]
        assert marks and all(
            s["annotations"]["width"] == 16 and s["annotations"]["node"]
            for s in marks)
        # nothing partial was ever committed
        for job in gangs:
            assert len(live_allocs(server, job)) in (0, 16)
    finally:
        server.shutdown()


@pytest.mark.parametrize("seed", range(4301, 4313))
def test_a_mixed_batch_agrees_with_the_host_stack_in_the_batchs_order(seed):
    """A seeded five-class fleet and a seeded batch of free gangs (one
    of them pinned to a GPU type), plain jobs and one-ask jobs that the
    fleet holds: the dense server places the batch as two dispatches,
    the host stack places the same jobs one after the other, plain jobs
    first; both place every job whole, and every gang plan of the dense
    side passes the oracle on the snapshot it was planned on."""
    from nomad_tpu.kernels.differential import judge_gang_plan

    rng = random.Random(seed)
    slots = [rng.randint(1, 4) for _ in range(rng.randint(40, 60))]

    def batch():
        r = random.Random(seed)
        gangs = [slot_job(f"s{seed}-g{i}", r.choice([2, 4, 8]), gang=True)
                 for i in range(r.randint(2, 4))]
        gangs.append(slot_job(f"s{seed}-gv", 4, gang=True,
                              constraint=("regexp", "^V100")))
        rest = [slot_job(f"s{seed}-p{i}", r.choice([1, 1, 1, 2, 6]))
                for i in range(r.randint(4, 10))]
        return gangs, rest

    gangs, rest = batch()
    assert sum(j.task_groups[0].count for j in gangs + rest) <= sum(slots)

    server = dense_server()
    try:
        for node in gpu_nodes(slots):
            server.node_register(node)
        snap = server.fsm.state.snapshot()
        order = gangs + rest
        rng.shuffle(order)
        plans = run_as_one_batch(server, order)
        for job in gangs + rest:
            assert len(live_allocs(server, job)) == job.task_groups[0].count
        assert server.plan_applier.stats()["gangs_rejected"] == 0
        by_job = {job.id: job for job in gangs}
        judged = 0
        for plan in plans:
            if plan.gang_groups:
                job_id = next(iter(plan.gang_groups)).split("/")[0]
                assert judge_gang_plan(snap, plan, by_job[job_id],
                                       seed=seed) == []
                judged += 1
        assert judged == len(gangs)
        by_id = {n.id: n for n in server.fsm.state.nodes()}
        assert all(by_id[a.node_id].meta["gpu_type"].startswith("V100")
                   for a in live_allocs(server, gangs[-1]))
    finally:
        server.shutdown()

    # the host stack, the batch's order: plain jobs first
    h = Harness(seed=seed)
    seed_harness_cluster(h, nodes=gpu_nodes(slots), jobs=[])
    host_gangs, host_rest = batch()
    for job in host_rest + host_gangs:
        h.state.upsert_job(h.next_index(), job)
        h.process("batch", new_eval(h.state.job_by_id(job.id),
                                    consts.EVAL_TRIGGER_JOB_REGISTER))
        live = [a for a in h.state.allocs_by_job(job.id)
                if not a.terminal_status()]
        assert len(live) == job.task_groups[0].count


def test_free_gangs_of_8_32_and_128_share_a_dispatch():
    server = dense_server()
    try:
        for node in gpu_nodes([4] * 60):
            server.node_register(node)
        gangs = [slot_job(f"wide-g{k}", k, gang=True) for k in (8, 32, 128)]
        ones = [slot_job(f"wide-o{i}", 1) for i in range(5)]
        before = get_batcher().stats()
        run_as_one_batch(server, [ones[0], gangs[2], ones[1], gangs[0],
                                  ones[2], gangs[1], ones[3], ones[4]])
        after = get_batcher().stats()
        stats = gang_stats()
        assert stats["dispatches"] == 1 and stats["dispatched_gangs"] == 3
        assert stats["members_placed"] == 168
        assert after["dispatches"] - before["dispatches"] == 2
        assert after["mixed_batches"] - before["mixed_batches"] == 1
        for job in gangs + ones:
            assert len(live_allocs(server, job)) == job.task_groups[0].count
        assert server.plan_applier.stats()["gangs_rejected"] == 0
    finally:
        server.shutdown()


def test_a_constrained_gang_is_feasible_and_builds_no_mask_twice():
    from nomad_tpu.models import matrix

    matrix._FEAS_CACHE.clear()
    server = dense_server()
    try:
        nodes = gpu_nodes([4] * 40)
        for node in nodes:
            server.node_register(node)
        by_id = {n.id: n for n in nodes}
        trace.get_recorder().reset()

        def builds():
            row = trace.get_recorder().stage_stats().get(
                trace.STAGE_FEASIBILITY_BUILD)
            return row["count"] if row else 0

        for wave in range(2):
            pinned = slot_job(f"pin-g{wave}", 8, gang=True,
                              constraint=("!=", "T4"))
            ones = [slot_job(f"pin-o{wave}x{i}", 1, constraint=("=", "T4"))
                    for i in range(3)]
            run_as_one_batch(server, [ones[0], pinned, ones[1], ones[2]])
            assert len(live_allocs(server, pinned)) == 8
            assert all(by_id[a.node_id].meta["gpu_type"] != "T4"
                       for a in live_allocs(server, pinned))
            for job in ones:
                (alloc,) = live_allocs(server, job)
                assert by_id[alloc.node_id].meta["gpu_type"] == "T4"
            if wave == 0:
                # one mask a constraint signature, the gang's and the
                # one-ask jobs' (the three one-ask evals run side by
                # side, and two that miss the memo at once both build)
                first_wave = builds()
                assert 2 <= first_wave <= 4
        # the second wave's evals, the gang's too, built none
        assert builds() == first_wave
    finally:
        server.shutdown()
        matrix._FEAS_CACHE.clear()


def test_every_member_of_a_gang_holds_the_asks_third_axis():
    """`pai-gpu-1800` carries GPUs on the disk axis: a member's ask there
    is its group's ephemeral disk plus its task's (canonicalize gives a
    task 300). Five machines of 8 slots whose disk holds four members
    each: a gang of 16 and a one-ask job are 17 of the 20 the axis
    allows (the slots would allow 40), so some machine holds four and
    none holds five."""
    server = dense_server()
    try:
        nodes = gpu_nodes([8] * 5, disk_mb=4_000)
        for node in nodes:
            server.node_register(node)
        gang = slot_job("axis-g", 16, gang=True, disk_mb=700)
        one = slot_job("axis-o", 1, disk_mb=700)
        run_as_one_batch(server, [one, gang])
        live = live_allocs(server, gang)
        assert len(live) == 16
        per_node = {}
        for alloc in live + live_allocs(server, one):
            per_node[alloc.node_id] = per_node.get(alloc.node_id, 0) + 1
        assert max(per_node.values()) == 4 and sum(per_node.values()) == 17
        assert server.plan_applier.stats()["gangs_rejected"] == 0
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# the route of an eval of one to three asks


def test_a_units_batch_mates():
    batcher = PlacementBatcher()
    a, b, c = batcher.open_cohort(3)
    assert a.batch_mates() == 2
    a.settle()
    assert a.batch_mates() == 0 and b.batch_mates() == 2
    (alone,) = batcher.open_cohort(1)
    assert alone.batch_mates() == 0
    b.settle()
    c.settle()
    alone.settle()
    assert batcher.stats()["open_cohorts"] == 0


@pytest.mark.parametrize("case", ["rides_its_batch", "alone", "replan",
                                  "requeued"])
def test_the_route_of_an_eval_of_one_to_three_asks(case):
    """With batch-mates the eval is a lane of the plain dispatch; a
    cohort of one, a unit that has ridden its dispatch (the inline
    replan of a conflicted plan) and a run the pipeline requeued after a
    conflict (a replan in a later batch) take the host iterators as
    before."""
    batcher = get_batcher()
    h = Harness(seed=92)
    for _ in range(6):
        h.state.upsert_node(h.next_index(), mock.node())
    job = mock.job()
    job.task_groups[0].count = 2
    h.state.upsert_job(h.next_index(), job)
    units = batcher.open_cohort(1 if case == "alone" else 2)
    for mate in units[1:]:
        mate.settle()
    if case == "replan":
        units[0].settle()       # as after its first dispatch
    h.cohort = units[0]
    h.settle_cohort = units[0].settle
    h.requeued = case == "requeued"
    small = counted("scheduler.small_route_host_evals")
    served = batcher.stats()["batched_requests"]
    h.process("service-tpu", new_eval(job, consts.EVAL_TRIGGER_JOB_REGISTER))
    assert len(h.state.allocs_by_job(job.id)) == 2
    on_host = counted("scheduler.small_route_host_evals") - small
    on_device = batcher.stats()["batched_requests"] - served
    assert (on_host, on_device) == \
        ((0, 1) if case == "rides_its_batch" else (1, 0))
    assert units[0].batch_mates() == 0
    assert batcher.stats()["open_cohorts"] == 0


# ---------------------------------------------------------------------
# the order at the batcher: a plain dispatch waits for the gangs ahead


def test_a_gang_dispatch_waits_for_the_plain_dispatch_ahead_of_it():
    import threading
    from types import SimpleNamespace

    from nomad_tpu.scheduler.batcher import _rank, _Request

    batcher = PlacementBatcher()
    token = object()
    first = _Request(token, None, None,
                     SimpleNamespace(active=np.zeros(8, bool)), None)
    lead = _Request(token, None, None, None, None, topo=("k", None))
    with batcher._lock:     # both popped, as _dispatch pops them
        for seq, req in enumerate((lead, first)):
            req.order = _rank(req) + (seq,)
            batcher._unissued.setdefault(token, {})[id(req)] = req.order
    batcher._await_turn(first)      # nothing ahead of the plain lanes
    assert batcher._going == {token: id(first)}
    released = []

    def gang():
        batcher._await_turn(lead)
        released.append(batcher._take_claims(token, "gang"))

    t = threading.Thread(target=gang)
    t.start()
    time.sleep(0.05)
    assert not released
    carry = (np.ones((4, 4), np.float32), np.zeros(4, np.float32),
             np.ones(4, np.float32))
    # the plain program is issued: its carry is published, the gang
    # dispatch goes on
    batcher._publish_claims(first, carry, "plain", 3, 8)
    t.join(5.0)
    (claims, crossing, handed), = released
    assert (claims.kind, claims.lanes, claims.rung, crossing, handed) == (
        "plain", 3, 8, True, False)
    assert batcher._unissued == {token: {id(lead): lead.order}}
    assert batcher._going == {token: id(lead)}
    batcher._release(first)             # idempotent
    # the crossing is counted once, and only across kinds: a later
    # plain dispatch starts from the plain carry as a plain hand-over
    assert batcher._take_claims(token, "gang")[1:] == (False, False)
    assert batcher._take_claims(token, "plain")[1:] == (False, True)
    stats = batcher.stats()
    assert (stats["mixed_batches"], stats["plain_handovers"]) == (1, 1)
    assert batcher._take_claims(object(), "gang") is None
    # a gang dispatch is counted in while it waits, and out at its issue
    batcher._publish_claims(lead, carry, "gang", 2, 32)
    assert batcher._unissued == {} and batcher._going == {}
    assert batcher.stats()["claims_wait_expired"] == 0
    claims, crossing, handed = batcher._take_claims(token, "plain")
    assert (claims.kind, claims.lanes, crossing, handed) == (
        "gang", 2, True, False)
