"""The dispatches of one base token (scheduler/batcher.py): a pipeline
batch whose plain asks fall into several queues (ask rungs, a `service`
job beside `batch` ones, a queue past its dispatch's lane cap) is
several dispatches that go one after another, shortest rung first, each
from the claims of those before it. The deployment `alibaba-colo-4k` is
made of such batches.

The contract under test:

- (a) on one snapshot, lanes in three ask rungs and two PlacementConfigs
  through `PlacementBatcher.place` under one cohort: the union of their
  choices, judged by the host's own `allocs_fit` and `NetworkIndex`
  (`kernels/differential.py` `judge_shared_snapshot`), overcommits no
  node on any axis and no port; the same lanes dispatched blind to each
  other DO, on a fleet built so that they must (the rig is red on the
  parent's batcher);
- (b) the order is total: the dispatches publish in the order of their
  rungs, four queues released at once all end, nobody waits
  `CLAIMS_WAIT_MAX` out;
- (c) a batch of one queue on a token nobody else touches takes the
  program it took before (the one that fuses the base's delta in), takes
  part in no hand-over and publishes nothing; a dispatch that does hand
  on runs the same compiled program (the jit cache does not grow);
- (d) an eval of 2,000 asks and its replan of a few to nearly all of
  them run one program: the replan pads to its first attempt's rung and
  its job's positions bucket, and compiles nothing.
"""

import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock, trace
from nomad_tpu.kernels.differential import (
    HANDOVER_SEEDS,
    build_handover_scenario,
    judge_shared_snapshot,
    place_on_one_snapshot,
)
from nomad_tpu.models.matrix import ASK_BUCKETS, ClusterMatrix, bucket_size
from nomad_tpu.ops.binpack import (
    PlacementConfig,
    batched_placement_program_compact,
    batched_placement_program_compact_delta,
    host_prng_key,
    jit_cache_size,
    make_asks,
)
from nomad_tpu.scheduler import batcher as batcher_mod
from nomad_tpu.scheduler.batcher import (
    BATCH_BUCKETS,
    PlacementBatcher,
    _lane_cap,
    _rank,
    _Request,
    get_batcher,
)
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.state import StateStore
from nomad_tpu.structs import consts
from nomad_tpu.structs.eval import new_eval
from nomad_tpu.structs.plan import PlanResult

from test_gang_batched import dense_server, live_allocs, run_as_one_batch
from test_mixed_batch import gpu_nodes, slot_job

CONFIG = PlacementConfig(anti_affinity_penalty=5.0)


# ---------------------------------------------------------------------
# (a) the rig


@pytest.mark.parametrize("seed", HANDOVER_SEEDS)
def test_lanes_of_three_rungs_and_two_configs_overcommit_no_node(seed):
    store, jobs = build_handover_scenario(seed)
    snap = store.snapshot()
    assert {bucket_size(j.task_groups[0].count, ASK_BUCKETS)
            for j in jobs} == {8, 16, 32}
    assert {j.type for j in jobs} == {"batch", "service"}
    lanes, batcher = place_on_one_snapshot(snap, jobs, seed)
    assert judge_shared_snapshot(snap, lanes, seed=seed) == []
    stats = batcher.stats()
    # four queues, four dispatches, each after the first from a carry
    assert stats["dispatches"] == 4 and stats["batched_requests"] == 4
    assert stats["plain_handovers"] == 3 and stats["mixed_batches"] == 0
    assert stats["token_queues"] == 4
    assert stats["claims_wait_expired"] == 0
    with batcher._lock:
        assert not batcher._unissued and not batcher._going


@pytest.mark.parametrize("seed", HANDOVER_SEEDS)
def test_the_same_lanes_dispatched_blind_do_overcommit(seed):
    """The control: every job's dispatch on a batcher of its own, so
    none starts from another's claims. The hot machines hold fewer free
    slots than any two jobs put there."""
    store, jobs = build_handover_scenario(seed)
    snap = store.snapshot()
    lanes, _ = place_on_one_snapshot(snap, jobs, seed, blind=True)
    bad = judge_shared_snapshot(snap, lanes, seed=seed)
    assert any("overcommitted: cpu" in line for line in bad), bad


# ---------------------------------------------------------------------
# (b) the order


def test_the_dispatches_of_a_token_publish_in_the_order_of_their_rungs(
        monkeypatch):
    published = []
    publish = PlacementBatcher._publish_claims

    def recording(self, first, carry, kind, lanes, rung):
        published.append((kind, rung))
        return publish(self, first, carry, kind, lanes, rung)

    monkeypatch.setattr(PlacementBatcher, "_publish_claims", recording)
    monkeypatch.setattr(batcher_mod, "CLAIMS_WAIT_MAX", 20.0)
    for seed in list(HANDOVER_SEEDS)[:3]:
        del published[:]
        store, jobs = build_handover_scenario(seed)
        t0 = time.monotonic()
        _lanes, batcher = place_on_one_snapshot(
            store.snapshot(), jobs, seed)
        assert time.monotonic() - t0 < 20.0
        assert published == [("plain", 8), ("plain", 8), ("plain", 16),
                             ("plain", 32)]
        assert batcher.stats()["claims_wait_expired"] == 0


def test_the_order_is_total_and_read_off_the_requests():
    def request(k=None, topo=None):
        asks = None if k is None else type("A", (), {
            "active": np.zeros(k, bool)})()
        return _Request(object(), None, None, asks, None, topo=topo)

    ranks = [_rank(request(k)) for k in (8, 16, 2048)] \
        + [_rank(request(topo=("k", None)))]
    assert ranks == sorted(ranks) and len(set(ranks)) == 4
    # the first rung keeps the batch ladder, the rest go four to a
    # dispatch; gangs keep theirs
    assert _lane_cap(request(8), 64) == 64
    assert [_lane_cap(request(k), 64) for k in ASK_BUCKETS[1:]] == \
        [BATCH_BUCKETS[0]] * (len(ASK_BUCKETS) - 1)
    assert _lane_cap(request(topo=("k", None)), 64) == 64
    assert ASK_BUCKETS[-2:] == [1024, 2048]
    # past the ladder: multiples of its top, as before
    assert bucket_size(2049, ASK_BUCKETS) == 4096


def test_a_dispatch_that_waits_its_bound_out_goes_blind_and_is_counted(
        monkeypatch):
    monkeypatch.setattr(batcher_mod, "CLAIMS_WAIT_MAX", 0.05)
    batcher = PlacementBatcher()
    token = object()
    ahead = _Request(token, None, None, type("A", (), {
        "active": np.zeros(8, bool)})(), None)
    mine = _Request(token, None, None, type("A", (), {
        "active": np.zeros(16, bool)})(), None)
    with batcher._lock:
        for seq, req in enumerate((ahead, mine)):
            req.order = _rank(req) + (seq,)
            batcher._unissued.setdefault(token, {})[id(req)] = req.order
    batcher._await_turn(mine)           # `ahead` never issues
    assert batcher.stats()["claims_wait_expired"] == 1
    batcher._release(mine)
    batcher._release(ahead)
    assert not batcher._unissued and not batcher._going


def fleet_batch(server):
    """80 one-slot machines; `batch` jobs of 5, 12 and 28 instances and
    a `service` job of 4, beside 20 one-ask jobs: 69 slots of 80 in one
    pipeline batch, in four queues (rungs 8, 16, 32, and the service
    job's config)."""
    for node in gpu_nodes([1] * 80):
        server.node_register(node)
    tasks = [slot_job(f"ord-t{n}", n) for n in (5, 12, 28)]
    app = slot_job("ord-app", 4)
    app.type = consts.JOB_TYPE_SERVICE
    ones = [slot_job(f"ord-o{i}", 1) for i in range(20)]
    return tasks, app, ones, ones[:9] + tasks[2:] + [app] + tasks[:2] \
        + ones[9:]


def test_a_pipeline_batch_of_four_queues_passes_the_applier_first_time():
    server = dense_server()
    try:
        tasks, app, ones, jobs = fleet_batch(server)
        before = get_batcher().stats()
        trace.get_recorder().reset()
        plans = run_as_one_batch(server, jobs)
        after = get_batcher().stats()
        for job in tasks + [app] + ones:
            assert len(live_allocs(server, job)) == \
                job.task_groups[0].count
        # every plan passed the applier first time: no replan
        assert len(plans) == len(jobs)
        assert server.plan_applier.stats()["plans_rejected"] == 0
        assert server.dispatch.stats()["plan_conflicts"] == 0

        def moved(key):
            return after[key] - before[key]

        assert moved("dispatches") == 4
        assert moved("batched_requests") == len(jobs)
        assert moved("plain_handovers") == 3
        assert moved("token_queues") == 4
        assert moved("mixed_batches") == 0
        assert moved("claims_wait_expired") == 0
        stages = trace.get_recorder().stage_stats()
        assert stages[trace.STAGE_BATCH_HANDOVER]["count"] == 3
        assert stages[trace.STAGE_BATCH_QUEUES]["count"] == 4
        assert trace.STAGE_BATCH_CLAIMS not in stages
        handed = sorted(
            (s["annotations"] for t in trace.get_recorder().traces(limit=100)
             for s in t["spans"] if s["name"] == trace.STAGE_BATCH_HANDOVER),
            key=lambda ann: (ann["rung"], ann["from_rung"]))
        assert [(a["kind"], a["from_rung"], a["rung"]) for a in handed] == [
            ("plain>plain", 8, 8), ("plain>plain", 8, 16),
            ("plain>plain", 16, 32)]
        rungs = {s["annotations"]["rung"]
                 for t in trace.get_recorder().traces(limit=100)
                 for s in t["spans"] if s["name"] == "device.dispatch"}
        assert rungs == {8, 16, 32}
    finally:
        server.shutdown()


def test_a_queue_past_its_lane_cap_is_chained_dispatches():
    """Six jobs of 12 instances on 80 one-slot machines: one queue (rung
    16), two dispatches of four and two lanes, the second from the
    first's claims; no plan is rejected."""
    server = dense_server()
    try:
        for node in gpu_nodes([1] * 80):
            server.node_register(node)
        jobs = [slot_job(f"cap-{i}", 12) for i in range(6)]
        before = get_batcher().stats()
        plans = run_as_one_batch(server, jobs)
        after = get_batcher().stats()
        for job in jobs:
            assert len(live_allocs(server, job)) == 12
        assert len(plans) == len(jobs)
        assert server.dispatch.stats()["plan_conflicts"] == 0
        assert after["dispatches"] - before["dispatches"] == 2
        assert after["plain_handovers"] - before["plain_handovers"] == 1
        assert after["token_queues"] - before["token_queues"] == 1
        assert after["claims_wait_expired"] == before["claims_wait_expired"]
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# (c) a batch of one queue


def snapshots():
    """Two snapshots of one store, the second a delta child of the
    first; and a job to place on them."""
    store = StateStore()
    nodes = [mock.node() for _ in range(48)]
    for i, node in enumerate(nodes):
        store.upsert_node(i + 1, node)
    job = mock.job()
    job.task_groups[0].count = 8
    store.upsert_job(100, job)

    def allocs(on):
        out = []
        for node in on:
            a = mock.alloc()
            a.node_id = node.id
            a.desired_status = consts.ALLOC_DESIRED_RUN
            a.client_status = consts.ALLOC_CLIENT_RUNNING
            out.append(a)
        return out

    store.upsert_allocs(101, allocs(nodes[:5]))
    snap1 = store.snapshot()
    store.upsert_allocs(102, allocs(nodes[40:43]))
    snap2 = store.snapshot()
    store.upsert_allocs(103, allocs(nodes[30:33]))
    return job, snap1, snap2, store.snapshot()


@pytest.mark.parametrize("count", [8, 12])
def test_a_batch_of_one_queue_takes_the_program_it_took_before(count):
    """`count` 8: lanes on the ask ladder's first rung; 12: past it."""
    job, snap1, snap2, snap3 = snapshots()
    b = PlacementBatcher(window=0.0)

    def place_two(snap, seed):
        """A cohort of two lanes of one shape on `snap`: one queue."""
        units = b.open_cohort(2)
        out = []

        def lane(i):
            m = ClusterMatrix(snap, job)
            asks = make_asks(*m.build_asks([0] * count))
            out.append(b.place(m, asks, host_prng_key(seed + i), CONFIG,
                               cohort=units[i]))

        threads = [threading.Thread(target=lane, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert len(out) == 2

    place_two(snap1, 3)
    plain = batched_placement_program_compact._cache_size()
    fused = batched_placement_program_compact_delta._cache_size()
    place_two(snap2, 5)
    # the child's base was derived inside the dispatch itself
    assert b.base_uploads == 1 and b.base_delta_updates == 1
    assert batched_placement_program_compact_delta._cache_size() == fused + 1
    assert batched_placement_program_compact._cache_size() == plain
    stats = b.stats()
    assert stats["dispatches"] == 2 and stats["token_queues"] == 2
    # no hand-over: nothing was published, nobody took a turn
    assert stats["plain_handovers"] == 0 and not b._claims
    assert not b._unissued and not b._going and b._popped == 0

    # A batch of TWO queues on the next snapshot: its first dispatch
    # fuses the delta in as well and hands its carry on. Past the first
    # rung it runs the program compiled above (the jit cache key is the
    # shapes and the config, whoever follows); on the first rung a
    # dispatch of a hand-over pads its batch axis to BATCH_BUCKETS[1]
    # (_batch_bucket), one program more, once.
    fused = batched_placement_program_compact_delta._cache_size()
    units = b.open_cohort(2)
    out = []

    def lane(i, asked):
        m = ClusterMatrix(snap3, job)
        asks = make_asks(*m.build_asks([0] * asked))
        out.append(b.place(m, asks, host_prng_key(7 + i), CONFIG,
                           cohort=units[i]))

    threads = [threading.Thread(target=lane, args=(i, asked))
               for i, asked in enumerate((count, 40))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
    assert len(out) == 2
    assert b.base_delta_updates == 2
    assert batched_placement_program_compact_delta._cache_size() \
        == fused + (count <= ASK_BUCKETS[0])
    stats = b.stats()
    assert stats["dispatches"] == 4 and stats["plain_handovers"] == 1
    assert stats["claims_wait_expired"] == 0


# ---------------------------------------------------------------------
# (d) 2,000 asks and their replan


class RejectsSome:
    """A planner that commits all but the last `rejected` allocations of
    the first plan and hands the scheduler a fresh snapshot (what the
    applier does with a partly rejected plan); later plans commit
    whole."""

    def __init__(self, harness, rejected):
        self.h, self.rejected, self.plans = harness, rejected, 0

    def submit_plan(self, plan):
        self.plans += 1
        allocs = [a for placed in plan.node_allocation.values()
                  for a in placed]
        keep = allocs if self.plans > 1 else allocs[:-self.rejected]
        index = self.h.next_index()
        by_node = {}
        for a in keep:
            a.job = plan.job
            a.create_index = a.modify_index = index
            by_node.setdefault(a.node_id, []).append(a)
        self.h.state.upsert_allocs(index, keep)
        result = PlanResult(node_allocation=by_node, alloc_index=index)
        if len(keep) == len(allocs):
            return result, None
        result.refresh_index = index
        return result, self.h.state.snapshot()

    def update_eval(self, ev):
        pass

    def create_eval(self, ev):
        pass


@pytest.mark.parametrize("rejected", [5, 37, 700, 1999])
def test_a_replan_of_an_eval_of_2000_asks_compiles_nothing(rejected):
    h = Harness(seed=45)
    for _ in range(40):
        node = mock.node()
        node.resources.cpu = 64 * 100 + 100
        node.resources.memory_mb = 64 * 128 + 256
        node.compute_class()
        h.state.upsert_node(h.next_index(), node)

    def task(name, count):
        job = mock.job()
        job.id = name
        job.type = consts.JOB_TYPE_BATCH
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = 100
        tg.tasks[0].resources.memory_mb = 128
        tg.tasks[0].resources.networks = []
        tg.ephemeral_disk.size_mb = 0
        h.state.upsert_job(h.next_index(), job)
        return job

    # warm-up, as a cell has one: a small task's eval and its replan
    # (the program that derives a replan's base is compiled here)
    small = task(f"t12-{rejected}", 12)
    h.planner = RejectsSome(h, 5)
    h.process("batch-tpu", new_eval(small, consts.EVAL_TRIGGER_JOB_REGISTER))
    assert h.planner.plans == 2
    job = task(f"t2000-{rejected}", 2000)
    planner = h.planner = RejectsSome(h, rejected)
    # the launch prologue's part: the snapshot's base is resident
    # before the batch's evals place (dispatch/pipeline.py)
    get_batcher().prefetch_base(ClusterMatrix(h.state.snapshot(), job))

    sizes = []
    issue = PlacementBatcher._issue

    def recording(self, batch, config, closed, program, *args, **kw):
        out = issue(self, batch, config, closed, program, *args, **kw)
        sizes.append((program.__name__, int(np.shape(batch[0].asks.active)[0]),
                      int(np.shape(batch[0].compact.job_rows)[0]),
                      jit_cache_size()))
        return out

    PlacementBatcher._issue = recording
    try:
        h.process("batch-tpu", new_eval(job, consts.EVAL_TRIGGER_JOB_REGISTER))
    finally:
        PlacementBatcher._issue = issue
    assert planner.plans == 2
    assert len([a for a in h.state.allocs_by_job(job.id)
                if not a.terminal_status()]) == 2000
    # the first attempt and its replan: one program, one shape, and the
    # second dispatch compiled nothing
    assert [row[:3] for row in sizes] == [
        ("batched_placement_program_compact", 2048, 2048)] * 2
    assert sizes[1][3] == sizes[0][3]
