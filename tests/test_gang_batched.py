"""Gangs on the normal dense path (nomad_tpu/gang, scheduler/batcher.py
place_gang, ops/gang.py batched_gang_placement_program).

The contract under test:

- the batched program on B gangs equals, choice for choice and slice for
  slice under the same noise, the single-gang program (the plain
  reference of one lane) applied B times in order with each gang's
  claims added to the state before the next, in all four modes, with
  the lanes padded up a batch bucket;
- a burst of gangs that all fit only by taking different racks is placed
  whole in one dispatch with no applier rejection, because each lane
  sees the claims of those before it;
- every plan passes the oracle (kernels/differential.py judge_gang_plan)
  and agrees with the host gang stack on placed-or-rejected;
- a gang dispatched through a dev server is a request of the batcher
  like any other: counted, closed by its cohort, served from the
  resident base, and traced (gang.select with its parts);
- a pipeline batch of gang evals and plain evals places both, each
  through its own program: the gangs' dispatch first, the plain lanes
  from its carried claims (tests/test_mixed_batch.py has the rest).
"""

import random
import time

import numpy as np
import pytest

from nomad_tpu import mock, trace
from nomad_tpu.gang import gang_stats, reset_gang_stats
from nomad_tpu.ops.binpack import host_prng_key
from nomad_tpu.ops.gang import (
    GANG_MODE_AFFINITY,
    GANG_MODE_FREE,
    GANG_MODE_SLICE,
    GANG_MODE_SPREAD,
    GangBase,
    GangConfig,
    batched_gang_placement_program_jit,
    gang_placement_program_jit,
    make_gang_lane,
    make_gang_state,
)
from nomad_tpu.scheduler.batcher import (
    MAX_BATCH,
    _pad_gang_batch,
    _pad_gang_members,
    get_batcher,
)
from nomad_tpu.scheduler.testing import Harness, seed_harness_cluster
from nomad_tpu.structs import Gang, consts
from nomad_tpu.structs.eval import new_eval

SLOT_CPU, SLOT_MEM = 1000, 1024
SHAPES = (2, 8)         # slots a node: two node shapes
N_PAD = 128


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


# ---------------------------------------------------------------------
# the program against its plain reference


def seeded_fleet(seed):
    """(slots [n], racks [n], used slots [n]): 48-96 nodes of two shapes
    in 4-8 racks, one shape a rack, with seeded fillers."""
    rng = random.Random(seed)
    n = rng.randint(48, 96)
    n_racks = rng.randint(4, 8)
    rack_shape = [SHAPES[r % 2] for r in range(n_racks)]
    racks = np.asarray(sorted(rng.randrange(n_racks) for _ in range(n)),
                       np.int32)
    slots = np.asarray([rack_shape[r] for r in racks])
    used = np.asarray([rng.randint(0, s - 1) if rng.random() < 0.6 else 0
                       for s in slots])
    return slots, racks, used


def fleet_arrays(seed):
    slots, racks, used = seeded_fleet(seed)
    n = len(slots)
    capacity = np.zeros((N_PAD, 4), np.float32)
    capacity[:n, 0] = slots * SLOT_CPU
    capacity[:n, 1] = slots * SLOT_MEM
    capacity[:n, 2] = 100_000
    capacity[:n, 3] = 10_000
    util = np.zeros((N_PAD, 4), np.float32)
    util[:n, 0] = used * SLOT_CPU
    util[:n, 1] = used * SLOT_MEM
    node_ok = np.zeros(N_PAD, bool)
    node_ok[:n] = True
    topo = np.full(N_PAD, -1, np.int32)
    topo[:n] = racks
    return {
        "capacity": capacity, "sched_capacity": capacity, "util": util,
        "bw_avail": np.full(N_PAD, 1000, np.float32),
        "bw_used": np.zeros(N_PAD, np.float32),
        "ports_free": np.full(N_PAD, 100, np.float32),
        "node_ok": node_ok, "topo_ids": topo, "n": n}


def seeded_lanes(seed, b, k, k_pad):
    rng = np.random.default_rng(seed)
    lanes, keys = [], []
    for i in range(b):
        active = np.zeros(k_pad, bool)
        active[:k] = True
        lanes.append(make_gang_lane(
            feas_row=rng.random(N_PAD) < 0.9,
            job_count=np.zeros(N_PAD, np.int32),
            dh_presence=np.zeros(N_PAD, np.int32),
            ask_res=[SLOT_CPU, SLOT_MEM, 0, 0], ask_bw=0, ask_ports=0,
            active=active))
        keys.append(host_prng_key(seed * 131 + i))
    return lanes, keys


def dispatch(fa, lanes, keys, config):
    """The batched program on `lanes` as the batcher stacks them: every
    member mask padded to the largest gang's bucket, the batch padded up
    its bucket with lanes that have no member active. Returns (lanes as
    dispatched, choices, scores, info)."""
    k_pad = _pad_gang_members(max(len(lane.active) for lane in lanes))

    def members(active):
        out = np.zeros(k_pad, bool)
        out[:len(active)] = active
        return out

    lanes = [lane._replace(active=members(lane.active)) for lane in lanes]
    pad_to = _pad_gang_batch(len(lanes), MAX_BATCH)
    idle = lanes[0]._replace(active=np.zeros(k_pad, bool))
    extra = pad_to - len(lanes)
    stacked = type(idle)(*(
        np.stack(cols) for cols in zip(*(lanes + [idle] * extra))))
    base = GangBase(**{f: fa[f] for f in GangBase._fields})
    out = batched_gang_placement_program_jit(
        base, stacked, np.stack(keys + [keys[0]] * extra), config)
    # choices, scores, info; the carry after them is
    # tests/test_mixed_batch.py's
    return (lanes,) + tuple(np.asarray(x) for x in out[:3])


def reference(fa, lanes, keys, config):
    """The single-gang program on each lane in turn, its claims added to
    the state before the next: [(choices, scores, slice group)]."""
    util, bw_used = fa["util"].copy(), fa["bw_used"].copy()
    ports_free = fa["ports_free"].copy()
    out = []
    for lane, key in zip(lanes, keys):
        state = make_gang_state(
            fa["capacity"], fa["sched_capacity"], util, fa["bw_avail"],
            bw_used, ports_free, lane.feas_row & fa["node_ok"],
            lane.job_count, lane.dh_presence, fa["topo_ids"])
        choices, scores, group = (np.asarray(x) for x in (
            gang_placement_program_jit(
                state, lane.ask_res, lane.ask_bw, lane.ask_ports,
                lane.active, key, config)))
        out.append((choices, scores, int(group)))
        taken = choices[choices >= 0]
        np.add.at(util, taken, lane.ask_res)
        np.add.at(bw_used, taken, lane.ask_bw)
        np.add.at(ports_free, taken, -lane.ask_ports)
    return out


@pytest.mark.parametrize("b", [1, 3, 8])
@pytest.mark.parametrize("k", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("mode", [GANG_MODE_SLICE, GANG_MODE_SPREAD,
                                  GANG_MODE_AFFINITY, GANG_MODE_FREE])
def test_batched_program_equals_the_reference_applied_in_order(mode, k, b):
    seed = 9000 + 100 * k + b
    fa = fleet_arrays(seed)
    k_pad = 8 if k <= 8 else 16
    config = GangConfig(anti_affinity_penalty=10.0, mode=mode, g_pad=16)
    lanes, keys = seeded_lanes(seed, b, k, k_pad)

    lanes, choices, scores, info = dispatch(fa, lanes, keys, config)
    want = reference(fa, lanes, keys, config)

    placed = 0
    for i, (w_choices, w_scores, w_group) in enumerate(want):
        assert choices[i].tolist() == w_choices.tolist(), (mode, k, b, i)
        np.testing.assert_allclose(scores[i], w_scores, rtol=1e-5, atol=1e-4)
        assert int(info[i, 0]) == w_group
        members = w_choices[:k]
        # whole or not at all, and only real nodes
        assert (members >= 0).all() or (w_choices < 0).all()
        assert (members < fa["n"]).all()
        placed += bool((members >= 0).all())
        if mode == GANG_MODE_SLICE and w_group >= 0:
            assert set(fa["topo_ids"][members]) == {w_group}
    # a padding lane places nothing
    assert (choices[b:] < 0).all()
    assert placed >= 1, "the fleet held no gang at all: nothing compared"
    if b == 1 or mode != GANG_MODE_SLICE:
        assert not info[:b, 1].any()


@pytest.mark.parametrize("mode", [GANG_MODE_SLICE, GANG_MODE_FREE])
def test_gangs_of_every_size_share_a_dispatch(mode):
    """One queue for every gang size: a dispatch's member axis is its
    largest gang's bucket, and each lane equals the reference run on
    that axis, the claims of the larger and smaller gangs before it
    included."""
    fa = fleet_arrays(5151)
    config = GangConfig(anti_affinity_penalty=5.0, mode=mode, g_pad=16)
    lanes, keys = [], []
    for i, (k, k_pad) in enumerate([(1, 8), (16, 16), (2, 8), (32, 32),
                                    (8, 8), (4, 8)]):
        lane, key = seeded_lanes(5151 + i, 1, k, k_pad)
        lanes += lane
        keys += key
    lanes, choices, scores, info = dispatch(fa, lanes, keys, config)
    assert choices.shape == (16, 32)
    assert [_pad_gang_members(k) for k in (1, 8, 9, 16, 32, 33, 1024)] == [
        8, 8, 32, 32, 32, 128, 1024]
    want = reference(fa, lanes, keys, config)
    placed = 0
    for i, (w_choices, w_scores, w_group) in enumerate(want):
        assert choices[i].tolist() == w_choices.tolist(), (mode, i)
        np.testing.assert_allclose(scores[i], w_scores, rtol=1e-5, atol=1e-4)
        assert int(info[i, 0]) == w_group
        placed += bool((w_choices[lanes[i].active] >= 0).all())
    assert placed >= 4
    assert (choices[len(want):] < 0).all()


def test_claims_move_a_later_gang_off_the_rack_it_would_take_alone():
    """Eight identical slice gangs on one snapshot: solved alone each
    picks the tightest covering rack, the same one; in one dispatch the
    later lanes see it taken and `moved` says so."""
    fa = fleet_arrays(4242)
    config = GangConfig(anti_affinity_penalty=10.0, mode=GANG_MODE_SLICE,
                        g_pad=16)
    lanes, keys = seeded_lanes(4242, 8, 4, 8)
    _lanes, _c, _s, info = dispatch(fa, lanes, keys, config)
    info = np.asarray(info)[:8]
    assert (info[:, 0] >= 0).sum() >= 2
    assert not info[0, 1]
    assert info[:, 1].any()


# ---------------------------------------------------------------------
# plans: the oracle and the host gang stack


def rack_nodes(slots, racks):
    nodes = []
    for s, r in zip(slots, racks):
        node = mock.node()
        node.resources.cpu = int(s) * SLOT_CPU
        node.resources.memory_mb = int(s) * SLOT_MEM
        node.reserved.cpu = 0
        node.reserved.memory_mb = 0
        node.meta["rack"] = f"r{int(r)}"
        node.compute_class()
        nodes.append(node)
    return nodes


def gang_job(jid, k, **gang):
    job = mock.job()
    job.id = job.name = jid
    tg = job.task_groups[0]
    tg.count = k
    tg.gang = Gang(**gang)
    tg.ephemeral_disk.size_mb = 10
    task = tg.tasks[0]
    task.resources.cpu = SLOT_CPU
    task.resources.memory_mb = SLOT_MEM
    task.resources.networks = []
    return job


def filler_job(jid, count):
    job = gang_job(jid, count)
    job.task_groups[0].gang = None
    return job


def seeded_harness(seed):
    """A fleet of seeded_fleet's shape with its fillers as running
    one-slot allocations."""
    slots, racks, used = seeded_fleet(seed)
    nodes = rack_nodes(slots, racks)
    h = Harness(seed=seed)
    filler = filler_job("filler", int(used.sum()))
    seed_harness_cluster(h, nodes=nodes, jobs=[filler])
    allocs = []
    i = 0
    for node, u in zip(nodes, used):
        for _ in range(int(u)):
            a = mock.alloc()
            a.job = filler
            a.job_id = filler.id
            a.node_id = node.id
            a.name = f"filler.web[{i}]"
            a.task_group = filler.task_groups[0].name
            a.resources.cpu = SLOT_CPU
            a.resources.memory_mb = SLOT_MEM
            a.resources.networks = []
            a.task_resources = {"web": a.resources.copy()}
            a.desired_status = consts.ALLOC_DESIRED_RUN
            a.client_status = consts.ALLOC_CLIENT_RUNNING
            allocs.append(a)
            i += 1
    h.state.upsert_allocs(h.next_index(), allocs)
    return h


MODES = {"slice": {"slice": "rack"}, "spread": {"spread": "rack"},
         "affinity": {"affinity": "rack"}, "free": {}}


@pytest.mark.parametrize("seed", [7101, 7102, 7103])
@pytest.mark.parametrize("mode", sorted(MODES))
def test_plans_pass_the_oracle_and_agree_with_the_host_stack(mode, seed):
    """A run of gangs of 2-16 on one fleet, each through the dense
    factory (the batcher's gang dispatch) and, on a twin fleet, through
    the host gang stack: every plan passes judge_gang_plan, and the two
    agree on which gangs were placed and which rejected whole."""
    from nomad_tpu.kernels.differential import judge_gang_plan

    dense, host = seeded_harness(seed), seeded_harness(seed)
    rng = random.Random(seed)
    before = get_batcher().stats()
    outcomes = []
    for i in range(6):
        k = rng.choice([2, 4, 8, 16])
        verdicts = []
        for h, factory in ((dense, "service-tpu"), (host, "service")):
            job = gang_job(f"g{i}", k, **MODES[mode])
            h.state.upsert_job(h.next_index(), job)
            snap = h.state.snapshot()
            n_plans = len(h.plans)
            h.process(factory, new_eval(h.state.job_by_id(job.id),
                                        consts.EVAL_TRIGGER_JOB_REGISTER))
            for plan in h.plans[n_plans:]:
                assert judge_gang_plan(snap, plan, job, seed=seed) == []
            live = [a for a in h.state.allocs_by_job(job.id)
                    if not a.terminal_status()]
            assert len(live) in (0, k)
            verdicts.append(len(live) == k)
        assert verdicts[0] == verdicts[1], (mode, seed, i, k, verdicts)
        outcomes.append(verdicts[0])
    after = get_batcher().stats()
    assert after["batched_requests"] - before["batched_requests"] >= 6
    assert any(outcomes)


# ---------------------------------------------------------------------
# a dev server: the batcher's counters, the cohort, the resident base


def dense_server():
    from nomad_tpu.server import Server, ServerConfig

    server = Server(ServerConfig(
        num_schedulers=2,
        scheduler_factories={"service": "service-tpu",
                             "batch": "batch-tpu"},
        eval_nack_timeout=5.0))
    server.start()
    return server


def pause(server, paused):
    from nomad_tpu.server.worker import DEQUEUE_TIMEOUT

    for w in server.workers:
        w.set_pause(paused)
    if paused:
        deadline = time.monotonic() + 4 * DEQUEUE_TIMEOUT + 30.0
        while time.monotonic() < deadline and not all(
                w.parked() for w in server.workers):
            time.sleep(0.02)


def run_as_one_batch(server, jobs):
    """Register `jobs` while the workers are parked, so that their evals
    reach the pipeline as one batch; the plans it submitted, in order."""
    plans = []
    submit = server.plan_submit

    def recording(plan):
        plans.append(plan)
        return submit(plan)

    server.plan_submit = recording
    pause(server, True)
    evals = [server.job_register(job)[0] for job in jobs]
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline \
            and server.broker.ready_count() < len(evals):
        time.sleep(0.02)
    pause(server, False)
    state = server.fsm.state

    def done():
        evs = [state.eval_by_id(e) for e in evals]
        return all(e is not None and e.terminal_status() for e in evs)

    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline and not done():
        time.sleep(0.02)
    assert done()
    return plans


def live_allocs(server, job):
    return [a for a in server.fsm.state.allocs_by_job(job.id)
            if not a.terminal_status()]


def test_a_burst_of_colliding_slice_gangs_is_placed_whole_in_one_dispatch():
    """Eight racks of four one-slot-free servers and eight `slice` gangs
    of four at once: every gang covers a rack exactly, so all eight fit
    only by taking different racks. One dispatch places them all and the
    applier rejects none.

    The parent solved each gang alone on the burst's one snapshot: the
    tie between eight equal racks fell to each gang's own noise, so two
    or more gangs took one rack in nearly every burst (the chance that
    eight independent draws from eight racks are all different is
    8!/8^8 = 0.0024), and the applier rejected all but one of them
    whole."""
    from nomad_tpu.kernels.differential import judge_gang_plan

    server = dense_server()
    try:
        nodes = rack_nodes([1] * 32, [i // 4 for i in range(32)])
        for node in nodes:
            server.node_register(node)
        jobs = [gang_job(f"burst-{i}", 4, slice="rack") for i in range(8)]
        snap = server.fsm.state.snapshot()
        rejected = server.plan_applier.stats()["gangs_rejected"]
        reset_gang_stats()
        plans = run_as_one_batch(server, jobs)

        stats = gang_stats()
        assert stats["dispatches"] == 1, stats
        assert stats["dispatched_gangs"] == 8
        assert stats["gangs_per_dispatch"] == 8.0
        assert stats["path_device"] == 8 and "path_host" not in stats
        assert stats["moved_by_claims"] >= 1
        assert server.plan_applier.stats()["gangs_rejected"] == rejected
        assert len(plans) == 8
        by_id = {n.id: n for n in nodes}
        racks = []
        for job, plan in zip(jobs, plans):
            job = next(j for j in jobs if j.id == plan.job.id)
            assert judge_gang_plan(snap, plan, job) == []
            live = live_allocs(server, job)
            assert len(live) == 4
            mine = {by_id[a.node_id].meta["rack"] for a in live}
            assert len(mine) == 1
            racks.append(mine.pop())
        assert sorted(racks) == sorted(f"r{i}" for i in range(8))
    finally:
        server.shutdown()


def test_a_gang_dispatch_is_a_request_of_the_batcher():
    """Counted in `batched_requests` and `dispatches`, closed by its
    cohort (which settles), served from a resident base with the
    topology column beside it, and traced: gang.select with gang.build
    and the gang's device.dispatch, gang.solve inside that."""
    server = dense_server()
    try:
        nodes = rack_nodes([8] * 8, [i // 4 for i in range(8)])
        for node in nodes:
            server.node_register(node)
        batcher = get_batcher()
        before = batcher.stats()
        recorder = trace.get_recorder()
        spans_before = {s: recorder.stage_stats().get(s, {}).get("count", 0)
                        for s in (trace.STAGE_GANG_SELECT,
                                  trace.STAGE_GANG_BUILD,
                                  trace.STAGE_GANG_SOLVE)}
        jobs = [gang_job(f"req-{i}", 4, slice="rack") for i in range(3)]
        run_as_one_batch(server, jobs)
        after = batcher.stats()

        assert after["batched_requests"] - before["batched_requests"] == 3
        assert after["dispatches"] - before["dispatches"] == 1
        assert after["closed_by_cohort"] - before["closed_by_cohort"] == 1
        assert after["closed_by_window"] == before["closed_by_window"]
        assert after["open_cohorts"] == 0
        assert after["topo_uploads"] - before["topo_uploads"] <= 1
        with batcher._lock:
            assert batcher._device_bases and batcher._device_topos
        for job in jobs:
            assert len(live_allocs(server, job)) == 4
        stage = recorder.stage_stats()
        for name, was in spans_before.items():
            assert stage[name]["count"] - was == 3, name

        tree = recorder.trace_for(
            server.fsm.state.evals_by_job(jobs[0].id)[0].id)
        spans = {s["name"]: s for s in tree["spans"]}
        assert spans["gang.build"]["parent"] == "gang.select"
        assert spans["matrix.build"]["parent"] == "gang.build"
        assert spans["device.dispatch"]["parent"] == "gang.select"
        assert spans["gang.solve"]["parent"] == "device.dispatch"
        assert spans["device.solve"]["parent"] == "gang.solve"
        assert spans["device.dispatch"]["annotations"] == {
            "lanes": 4, "rung": 8, "closed_by": "cohort"}
        assert spans["gang.solve"]["annotations"] == {"gangs": 3}
        select = spans["gang.select"]["annotations"]
        assert select["members"] == 4 and select["mode"] == "slice"
        assert select["slice_group"] >= 0 and "moved" in select

        # a second wave on the committed state: the base moves by a
        # delta, the topology column is the one already resident
        more = [gang_job(f"req2-{i}", 4, slice="rack") for i in range(2)]
        run_as_one_batch(server, more)
        final = batcher.stats()
        assert final["topo_uploads"] == after["topo_uploads"]
        assert final["base_uploads"] == after["base_uploads"]
        assert final["base_delta_updates"] > after["base_delta_updates"]
        assert final["open_cohorts"] == 0
        for job in more:
            assert len(live_allocs(server, job)) == 4
        served = server.stats()["gang"]
        assert served["dispatches"] >= 2
        assert served["gangs_per_dispatch"] >= 2.0
    finally:
        server.shutdown()


def test_a_mixed_batch_places_gangs_and_plain_jobs_each_by_its_program():
    server = dense_server()
    try:
        nodes = rack_nodes([8] * 16, [i // 4 for i in range(16)])
        for node in nodes:
            server.node_register(node)
        gangs = [gang_job(f"mix-g{i}", 4, slice="rack") for i in range(3)]
        plain = [filler_job(f"mix-p{i}", 8) for i in range(3)]
        before = get_batcher().stats()
        reset_gang_stats()
        trace.get_recorder().reset()
        run_as_one_batch(server, [gangs[0], plain[0], gangs[1], plain[1],
                                  gangs[2], plain[2]])
        after = get_batcher().stats()
        stats = gang_stats()

        assert stats["path_device"] == 3 and stats["members_placed"] == 12
        assert stats["dispatched_gangs"] == 3
        assert after["batched_requests"] - before["batched_requests"] == 6
        # one dispatch of the gang program, one of the plain one
        assert after["dispatches"] - before["dispatches"] == 2
        assert after["overlay_dispatches"] - before["overlay_dispatches"] == 2
        assert after["open_cohorts"] == 0
        # in that order: the plain lanes started from the gangs' claims,
        # which stayed on the device (one hand-over, one batch.claims)
        assert after["mixed_batches"] - before["mixed_batches"] == 1
        assert stats["mixed_batches"] == 1
        spans = [s for t in trace.get_recorder().traces(limit=50)
                 for s in t["spans"]]
        carried = [s for s in spans if s["name"] == trace.STAGE_BATCH_CLAIMS]
        assert [s["annotations"] for s in carried] == [
            {"gang_lanes": 3, "plain_lanes": 3, "kind": "plain>gang",
             "from_rung": 8, "rung": 8}]
        assert carried[0]["parent"] == trace.STAGE_DEVICE_DISPATCH
        assert server.plan_applier.stats()["gangs_rejected"] == 0
        by_id = {n.id: n for n in nodes}
        for job in gangs:
            live = live_allocs(server, job)
            assert len(live) == 4
            assert len({by_id[a.node_id].meta["rack"] for a in live}) == 1
        for job in plain:
            assert len(live_allocs(server, job)) == 8
        assert server.dispatch.stats()["routed_host"] == 0
    finally:
        server.shutdown()
