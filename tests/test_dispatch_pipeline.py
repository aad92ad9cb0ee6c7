"""Central dispatch pipeline (nomad_tpu/dispatch): occupancy under a
multi-worker drain storm, device-side in-batch conflict pre-resolution
parity vs serial placement, conflict requeues landing in the
ACCUMULATING batch, and the stats surface through the agent metrics
endpoint."""

import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import consts


def wait_until(fn, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def seed_nodes(server, n=8, cpu=None, mem=None):
    nodes = []
    for _ in range(n):
        node = mock.node()
        if cpu is not None:
            node.resources.cpu = cpu
        if mem is not None:
            node.resources.memory_mb = mem
        node.compute_class()
        server.node_register(node)
        nodes.append(node)
    return nodes


def make_server(**over):
    defaults = dict(
        num_schedulers=4,
        scheduler_factories={"service": "service-tpu"},
        eval_batch_size=16,
        eval_nack_timeout=60.0,
    )
    defaults.update(over)
    server = Server(ServerConfig(**defaults))
    server.start()
    return server


def quiesce(server):
    """Pause every worker and wait out any in-flight blocking dequeue
    (DEQUEUE_TIMEOUT) so a storm registered next stays in the broker
    until release."""
    from nomad_tpu.server.worker import DEQUEUE_TIMEOUT

    for w in server.workers:
        w.set_pause(True)
    time.sleep(DEQUEUE_TIMEOUT + 0.3)


# ---------------------------------------------------------------------
# occupancy: the storm regime the pipeline exists for


def test_storm_packs_toward_full_batches():
    """A multi-worker drain storm must coalesce into FEW, FULL batches:
    the central drain packs every ready eval across all workers into
    one accumulator instead of per-worker fragments (r05: 9.4/64
    lanes)."""
    server = make_server()
    try:
        seed_nodes(server, 8)
        quiesce(server)
        jobs = []
        for _ in range(16):
            job = mock.job()
            job.task_groups[0].count = 5  # >3 so the dense path engages
            job.task_groups[0].tasks[0].resources.cpu = 20
            job.task_groups[0].tasks[0].resources.memory_mb = 16
            server.job_register(job)
            jobs.append(job)
        assert wait_until(lambda: server.broker.ready_count() >= 16, 10.0)
        for w in server.workers:
            w.set_pause(False)
        assert wait_until(
            lambda: all(
                len(server.fsm.state.allocs_by_job(j.id)) == 5
                for j in jobs),
            timeout=120.0)
        # Allocs become visible at plan COMMIT, the eval's ack lands
        # moments later on the stage thread — settle before reading.
        assert wait_until(
            lambda: (lambda s: s["acked"] + s["nacked"] == 16
                     and s["in_flight"] == 0)(server.dispatch.stats()),
            timeout=10.0), server.dispatch.stats()
        stats = server.dispatch.stats()
        assert stats["acked"] == 16
        # Launch prologues run on stage threads, so an early partial
        # batch can snapshot before a prior batch's commit lands — a
        # bounded conflict requeue re-dispatches its eval (exactly once
        # per requeue), which pre-resolve keeps rare.
        assert stats["dispatched_evals"] == 16 + stats["requeues"], stats
        assert stats["requeues"] <= 3, stats
        # The whole storm was ready at release: it must ride a handful
        # of packed batches, not 16 fragments. Occupancy is the
        # headline metric (r05 baseline: 9.4 lanes) — asserted
        # directly, degraded proportionally when a requeue adds a
        # small follow-up batch (requeues=0 keeps the strict >= 8).
        assert stats["largest_batch"] >= 12, stats
        assert stats["batches"] <= 4 + stats["requeues"], stats
        assert stats["occupancy"] >= 16 / (2 + stats["requeues"]), stats
    finally:
        server.shutdown()


def test_open_breaker_routes_the_batch_to_the_host_and_counts_it():
    """The one route to the host factories the pipeline has left: an
    OPEN device-path breaker inside its cool-down sends the whole batch
    there, no cohort opened, counted in routed_host and in
    breaker_routed; with the breaker closed again the next batch is
    dense and neither counter moves."""
    from nomad_tpu.admission import get_breaker
    from nomad_tpu.scheduler.batcher import get_batcher

    server = make_server(num_schedulers=1)
    breaker = get_breaker()
    try:
        seed_nodes(server, 8)
        breaker.configure(failure_threshold=1, cooldown=60.0)
        breaker.record_failure()
        assert breaker.should_route_host()
        served = get_batcher().batched_requests
        jobs = [_sized_job(f"tripped-{i}") for i in range(3)]
        _settle(server, _storm(server, jobs))
        for job in jobs:
            assert len(_live(server, job.id)) == 5
        stats = server.dispatch.stats()
        assert stats["routed_host"] == 3, stats
        assert stats["breaker_routed"] == 3, stats
        assert get_batcher().batched_requests == served
        assert get_batcher().stats()["open_cohorts"] == 0
        breaker.reset()
        job = _sized_job("closed-again")
        _settle(server, _storm(server, [job]))
        assert len(_live(server, job.id)) == 5
        stats = server.dispatch.stats()
        assert stats["routed_host"] == 3, stats
        assert stats["breaker_routed"] == 3, stats
        assert get_batcher().batched_requests == served + 1
    finally:
        breaker.reset()
        breaker.configure_defaults()
        server.shutdown()


def _per_node(allocs):
    """{node id: live allocations} of `allocs`."""
    placed = {}
    for a in allocs:
        if not a.terminal_status():
            placed[a.node_id] = placed.get(a.node_id, 0) + 1
    return placed


def _host_twin(nodes, job, factory):
    """What the HOST factory places for `job` on the same fleet, in a
    Harness of its own: {node id: live allocations}."""
    from nomad_tpu.scheduler.testing import Harness, seed_harness_cluster
    from nomad_tpu.structs import new_eval

    h = Harness(seed=46)
    seed_harness_cluster(h, nodes=[n.copy() for n in nodes],
                         jobs=[job.copy()])
    h.process(factory, new_eval(h.state.job_by_id(job.id),
                                consts.EVAL_TRIGGER_JOB_REGISTER))
    return _per_node(h.state.allocs_by_job(job.id))


def test_lone_gang_eval_takes_the_dense_gang_path():
    """A lone gang eval on an idle pipeline is one lane of the gang
    program (before PR 46 a batch of one went to the host factory's
    gang path): every member or none, inside one rack, as the host
    factory places the same job on the same fleet. The tie-breaks may
    differ, the constraints may not."""
    from nomad_tpu.gang import gang_stats, reset_gang_stats
    from nomad_tpu.structs import Gang

    server = make_server(num_schedulers=1)
    try:
        nodes = []
        for i in range(8):
            node = mock.node()
            node.resources.cpu = node.resources.memory_mb = 3000
            node.meta["rack"] = f"r{i // 4}"
            node.compute_class()
            server.node_register(node)
            nodes.append(node)
        job = _sized_job("lone-gang", count=4, cpu=400, mem=256)
        job.task_groups[0].gang = Gang(slice="rack")
        reset_gang_stats()
        ev, _ = server.job_register(job)
        _settle(server, [ev])
        rack = {n.id: n.meta["rack"] for n in nodes}
        live = _live(server, job.id)
        assert len(live) == 4
        assert len({rack[a.node_id] for a in live}) == 1
        stats = gang_stats()
        assert stats.get("path_device", 0) == 1, stats
        assert stats.get("path_host", 0) == 0, stats
        assert server.dispatch.stats()["routed_host"] == 0
        twin = _host_twin(nodes, job, "service")
        assert sum(twin.values()) == 4
        assert len({rack[node_id] for node_id in twin}) == 1
    finally:
        reset_gang_stats()
        server.shutdown()


def test_lone_system_eval_takes_the_dense_system_scheduler():
    """A lone system eval on an idle pipeline runs DenseSystemScheduler
    (before PR 46 the host system scheduler): one allocation on every
    eligible node and none on the others, the host factory's set; it
    never meets the batcher and opens no cohort."""
    from nomad_tpu.scheduler.batcher import get_batcher
    from nomad_tpu.structs import Constraint

    server = make_server(
        num_schedulers=1,
        scheduler_factories={"service": "service-tpu",
                             "system": "system-tpu"})
    try:
        nodes = []
        for i in range(6):
            node = mock.node()
            if i < 2:
                node.attributes["kernel.name"] = "windows"
            node.compute_class()
            server.node_register(node)
            nodes.append(node)
        job = mock.system_job()
        for task in job.task_groups[0].tasks:
            task.resources.networks = []
        job.constraints.append(Constraint(
            ltarget="${attr.kernel.name}", rtarget="linux", operand="="))
        before = get_batcher().stats()
        ev, _ = server.job_register(job)
        _settle(server, [ev])
        placed = _per_node(server.fsm.state.allocs_by_job(job.id))
        assert placed == {n.id: 1 for n in nodes[2:]}
        assert placed == _host_twin(nodes, job, "system")
        stats = server.dispatch.stats()
        assert stats["routed_host"] == 0 and stats["batches"] >= 1, stats
        after = get_batcher().stats()
        assert after["batched_requests"] == before["batched_requests"]
        assert after["open_cohorts"] == 0
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# in-batch conflict pre-resolution (device-side eval-axis scan)


def _shared_batch_inputs(n, k, g, b, node_cpu=1000.0, ask_cpu=400.0,
                         ask_mem=64.0, bw=10.0, ports=1.0,
                         distinct_hosts=False):
    from nomad_tpu.ops.binpack import make_asks, make_node_state

    state = make_node_state(
        capacity=np.tile([node_cpu, 8192, 100000, 150], (n, 1)),
        sched_capacity=np.tile([node_cpu, 8192, 100000, 150], (n, 1)),
        util=np.zeros((n, 4)),
        bw_avail=np.full(n, 1000.0),
        bw_used=np.zeros(n),
        ports_free=np.full(n, 100.0),
        job_count=np.zeros((b, n), np.int32),
        tg_count=np.zeros((b, n, g), np.int32),
        feasible=np.ones((b, n, g), bool),
        node_ok=np.ones(n, bool),
    )
    asks = make_asks(
        resources=np.tile([ask_cpu, ask_mem, 100, 0], (b, k, 1)),
        bw=np.full((b, k), bw),
        ports=np.full((b, k), ports),
        tg_index=np.zeros((b, k), np.int32),
        active=np.ones((b, k), bool),
        job_distinct_hosts=np.full(b, distinct_hosts, bool),
        tg_distinct_hosts=np.zeros((b, g), bool),
    )
    return state, asks


# The default mock shape, then the job shapes the non-preempting cells
# send (benchmark/configs/*.json), each as the dense scheduler builds
# its config (penalty by job type, uniform_dh where one task group is
# under distinct_hosts): northstar's count=8 with two dynamic ports,
# 50 Mbit and distinct_hosts; c1m's batch container at a count tier-1
# can afford; borg's `fill`.
PARITY_SHAPES = {
    "mock": dict(b=6, n=16, k=4, penalty=10.0),
    "northstar": dict(b=6, n=32, k=8, penalty=10.0, node_cpu=4000.0,
                      ask_cpu=20.0, ask_mem=16.0, bw=50.0, ports=2.0,
                      distinct_hosts=True),
    "c1m": dict(b=3, n=16, k=64, penalty=5.0, node_cpu=4000.0,
                ask_cpu=19.0, ask_mem=32.0, bw=0.0, ports=0.0),
    "borg-fill": dict(b=4, n=16, k=24, penalty=5.0, node_cpu=4000.0,
                      ask_cpu=100.0, ask_mem=128.0, bw=0.0, ports=0.0),
}


@pytest.mark.parametrize("shape", sorted(PARITY_SHAPES))
def test_pre_resolve_parity_vs_serial_placement(shape):
    """The device-side eval-axis scan must equal placing the evals one
    at a time while carrying the shared capacity state host-side — the
    exact serialization the plan applier would impose."""
    import jax
    from serial_reference import no_patches, serial_placement

    from nomad_tpu.ops.binpack import (
        PlacementConfig,
        batched_placement_program_overlay,
        host_prng_key,
        uniform_dh_flag,
    )

    spec = dict(PARITY_SHAPES[shape])
    b, n, k, g = spec.pop("b"), spec.pop("n"), spec.pop("k"), 1
    penalty = spec.pop("penalty")
    state, asks = _shared_batch_inputs(n, k, g, b, **spec)
    keys = np.stack([host_prng_key(i) for i in range(b)])
    cfg = PlacementConfig(
        anti_affinity_penalty=penalty,
        uniform_dh=uniform_dh_flag(
            [0] * k, spec.get("distinct_hosts", False), [False]))

    choices, scores, _ = batched_placement_program_overlay(
        state, asks, keys, cfg, no_patches(b, n))
    choices, scores = np.asarray(choices), np.asarray(scores)
    assert (choices >= 0).all()  # every shape fits its cluster

    serial_choices, serial_scores = serial_placement(
        state,
        [(state.job_count[i], state.tg_count[i], state.feasible[i],
          jax.tree.map(lambda x: x[i], asks), keys[i]) for i in range(b)],
        cfg)
    assert (choices == serial_choices).all()
    assert np.allclose(scores, serial_scores)
    if spec.get("distinct_hosts"):
        for row in choices:
            assert len(set(row.tolist())) == k


def test_pre_resolve_eliminates_in_batch_overcommit():
    """A/B at the kernel: independent evals (the unshared program, on
    the lanes' full states) over a tight cluster overcommit node
    capacity — every overcommit is a plan the applier would reject,
    i.e. a retry round-trip. The shared-base program's scan over the
    eval axis produces claims that ALL verify, so in-batch retries
    drop to zero."""
    from nomad_tpu.ops.binpack import (
        PlacementConfig,
        batched_placement_program,
        batched_placement_program_overlay,
        host_prng_key,
    )
    from serial_reference import no_patches

    # 8 evals x 2 asks x 400 cpu over 8 nodes of 800: demand exactly
    # equals capacity (16 asks, 16 slots), so a PERFECT serialization
    # places everything — but independent evals tie-break over
    # identical empty nodes and collide (every claim that fails the
    # applier-style sequential re-check is a retry round-trip).
    b, n, k, g = 8, 8, 2, 1
    node_cpu, ask_cpu = 800.0, 400.0
    state, asks = _shared_batch_inputs(n, k, g, b, node_cpu=node_cpu,
                                       ask_cpu=ask_cpu)
    keys = np.stack([host_prng_key(100 + i) for i in range(b)])
    cfg = PlacementConfig(anti_affinity_penalty=10.0)
    # The lanes' full states: the shared columns once a lane.
    full = state._replace(**{
        f: np.broadcast_to(getattr(state, f),
                           (b,) + getattr(state, f).shape)
        for f in ("capacity", "sched_capacity", "util", "bw_avail",
                  "bw_used", "ports_free", "node_ok")})

    def overcommits(program, lanes, *patches):
        choices = np.asarray(program(lanes, asks, keys, cfg, *patches)[0])
        claimed = np.zeros(n)
        rejected = 0
        for i in range(b):
            bad = False
            for j in range(k):
                c = int(choices[i, j])
                if c < 0:
                    bad = True  # a serialized pass would have placed it
                    continue
                if claimed[c] + ask_cpu > node_cpu:
                    bad = True
                    continue
                claimed[c] += ask_cpu
            rejected += bad
        return rejected

    off = overcommits(batched_placement_program, full)
    on = overcommits(batched_placement_program_overlay, state,
                     no_patches(b, n))
    # BestFit steers independent evals to the same packed nodes: the
    # independent batch must show the collision pathology for the A/B
    # to mean anything.
    assert off > 0, "expected overcommit between independent lanes"
    assert on == 0, f"the carry left {on} in-batch overcommits"


# ---------------------------------------------------------------------
# conflict requeue: rejected evals rejoin the ACCUMULATING batch


def test_requeue_joins_accumulating_batch():
    """A conflict-requeued eval must land in the batch that is
    CURRENTLY accumulating (and launch alongside new evals), not in a
    fresh lone dispatch. Exercised at the accumulator level on an
    UNSTARTED pipeline (no dispatcher thread to race): while every
    in-flight slot is busy, a requeued entry and fresh evals arrive;
    the close that happens when a slot frees must contain all of
    them."""
    import threading

    from nomad_tpu.dispatch import DispatchPipeline
    from nomad_tpu.dispatch.pipeline import _Pending

    server = make_server(num_schedulers=0)
    try:
        pipe = DispatchPipeline(server)  # not started: we drive it
        assert pipe.enabled
        with pipe._cond:
            pipe._inflight = pipe.max_inflight  # all slots busy
        got = []
        t = threading.Thread(
            target=lambda: got.append(pipe._accumulate()), daemon=True)

        requeued = _Pending(mock.eval(), "tok-requeue", requeues=1)
        pipe._admit(requeued)
        t.start()
        time.sleep(0.3)  # accumulator is open, waiting on a slot
        fresh = [_Pending(mock.eval(), f"tok-{i}") for i in range(3)]
        for entry in fresh:
            pipe._admit(entry)
        time.sleep(0.2)
        assert not got, "batch closed while every slot was busy"
        with pipe._cond:
            pipe._inflight = 0  # the in-flight batch completed
            pipe._cond.notify_all()
        t.join(timeout=5.0)
        assert got, "accumulator never closed after the slot freed"
        ids = {e.eval.id for e in got[0]}
        assert requeued.eval.id in ids, "requeue missed the accumulating batch"
        for entry in fresh:
            assert entry.eval.id in ids
        stats = pipe.stats()
        assert stats["requeues_batched"] == 1, stats
    finally:
        server.shutdown()


def test_plan_conflicts_requeue_and_resolve_live():
    """Live conflict path: 4 single-node-sized jobs racing over 2 nodes
    in TWO batches in flight on different snapshots (the first batch's
    plans are held before the plan queue while the second launches
    beside it, blind to them) must produce plan-applier rejections
    whose retries are requeued through the pipeline — and the cluster
    still converges (2 jobs placed, 2 blocked)."""
    from nomad_tpu.chaos import FaultSpec, chaos

    server = make_server()
    jobs = []

    def register_pair():
        for _ in range(2):
            job = mock.job()
            tg = job.task_groups[0]
            tg.count = 4
            tg.tasks[0].resources.cpu = 100  # 4x100: one job per node
            tg.tasks[0].resources.memory_mb = 64
            tg.tasks[0].resources.networks = []
            server.job_register(job)
            jobs.append(job)

    try:
        seed_nodes(server, 2, cpu=500, mem=4096)
        quiesce(server)
        register_pair()
        assert wait_until(lambda: server.broker.ready_count() >= 2, 10.0)
        # The first two submits, the first batch's, wait before the
        # plan queue: a slow plan queue, as the site defines 'delay'.
        held = FaultSpec("dispatch.submit", "delay", count=2, delay=3.0)
        chaos.arm(7, [held])
        for w in server.workers:
            w.set_pause(False)
        # Both of the first batch's plans are made (registered sooner,
        # the second pair would ride the first's device dispatch and be
        # resolved in it). The second batch snapshots without them,
        # wants the same two nodes and commits first.
        assert wait_until(lambda: held.fired >= 2, 60.0)
        register_pair()

        def placed_jobs():
            return sum(
                1 for j in jobs
                if len(server.fsm.state.allocs_by_job(j.id)) == 4)

        assert wait_until(lambda: placed_jobs() >= 2, timeout=120.0)
        # Give the losers time to finish their requeued replans.
        assert wait_until(
            lambda: server.dispatch.stats()["pending"] == 0
            and server.dispatch.stats()["in_flight"] == 0,
            timeout=60.0)
        stats = server.dispatch.stats()
        applier = server.plan_applier.stats()
        # 4 plans over 2 one-job nodes from two snapshots: the applier
        # MUST have rejected some, and those retries must have ridden
        # the pipeline's requeue (or, past the bound, its inline path).
        assert applier["plans_rejected"] >= 1, (stats, applier)
        assert stats["plan_conflicts"] >= 1, stats
        assert stats["requeues"] + stats["inline_retries"] >= 1, stats
        assert stats["retries_per_eval"] > 0.0, stats
        assert placed_jobs() == 2
    finally:
        chaos.disarm()
        server.shutdown()


def test_pre_resolve_cuts_live_conflicts():
    """Four jobs that fit together in ONE batch: the in-batch
    serialization should keep applier rejections at (near) zero — the
    twin of the kernel-level test, through the REAL control plane."""
    server = make_server()
    try:
        seed_nodes(server, 4, cpu=500, mem=4096)
        quiesce(server)
        jobs = []
        for _ in range(4):
            job = mock.job()
            tg = job.task_groups[0]
            tg.count = 4
            tg.tasks[0].resources.cpu = 100
            tg.tasks[0].resources.memory_mb = 64
            tg.tasks[0].resources.networks = []
            server.job_register(job)
            jobs.append(job)
        assert wait_until(lambda: server.broker.ready_count() >= 4, 10.0)
        for w in server.workers:
            w.set_pause(False)
        assert wait_until(
            lambda: all(
                len(server.fsm.state.allocs_by_job(j.id)) == 4
                for j in jobs),
            timeout=120.0)
        stats = server.dispatch.stats()
        # One batch, serialized claims: every plan verifies, no retry
        # round-trips. (Batch fragmentation could allow a stray
        # conflict; zero requeued evals is the contract that matters.)
        assert stats["retries_per_eval"] <= 0.25, stats
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# stats surface


def test_agent_metrics_endpoint_exposes_pipeline_stats():
    """/v1/agent/self must carry the pipeline stats (occupancy,
    retries/eval, in-flight batches) — the acceptance surface for the
    dispatch subsystem — and its stage latencies, which are rows of the
    trace table beside them (the pipeline's own cumulative
    drain/process/submit microseconds said the same with less)."""
    from nomad_tpu.api import Client, HTTPServer

    server = make_server(num_schedulers=1)
    http = HTTPServer(server)
    http.start()
    try:
        seed_nodes(server, 4)
        job = mock.job()
        job.task_groups[0].count = 5
        server.job_register(job)
        assert wait_until(
            lambda: len(server.fsm.state.allocs_by_job(job.id)) == 5)
        client = Client(http.addr, timeout=10.0)
        out = client.agent.self()
        pipe = out.get("dispatch_pipeline")
        assert pipe is not None, sorted(out)
        for key in ("occupancy", "occupancy_frac", "retries_per_eval",
                    "in_flight", "batches", "dispatched_evals"):
            assert key in pipe, (key, pipe)
        assert pipe["enabled"] is True
        # The server-stats block carries them too (plus the applier's
        # conflict counters).
        assert "dispatch_pipeline" in out["stats"]
        for stage in ("dispatch.accumulate", "scheduler.process",
                      "plan.submit"):
            assert out["stats"]["trace"][stage]["count"] >= 1, stage
        assert "plans_rejected" in out["stats"]["plan_applier"]
    finally:
        http.stop()
        server.shutdown()


# ---------------------------------------------------------------------
# dispatcher never blocks (ntalint dispatcher-blocking-call regression)


def test_dispatcher_keeps_accumulating_while_launch_blocks():
    """The launch prologue (FSM catch-up via _wait_for_index, up to
    WAIT_INDEX_TIMEOUT of sleep-polling, then snapshotting) runs on a
    STAGE thread, never the dispatcher: with the first batch's launch
    wedged on a lagging follower, the accumulator must keep packing and
    launching further batches into the remaining in-flight slots.

    Regression for the ntalint `dispatcher-blocking-call` finding: the
    dispatcher used to call _launch inline, so one stalled catch-up
    froze every lane for the full timeout."""
    import threading

    from nomad_tpu.dispatch.pipeline import DispatchPipeline
    from nomad_tpu.server import ServerConfig
    from nomad_tpu.structs import Evaluation
    from nomad_tpu.utils.pool import WorkPool

    release = threading.Event()
    stalled = threading.Event()

    class FakeStore:
        def latest_index(self):
            return 0

        def snapshot(self):
            raise AssertionError("snapshot before catch-up released")

    class FakeFSM:
        state = FakeStore()

    class FakeServer:
        config = ServerConfig(
            scheduler_factories={"service": "service-tpu"},
            eval_batch_size=2,
            dispatch_max_inflight=2,
            dispatch_idle_grace=0.002,
            dispatch_window=0.005,
        )
        fsm = FakeFSM()
        eval_pool = WorkPool(4, name="test-dispatch")

        def __init__(self):
            self.nacked = []

        def eval_dequeue_many(self, types, max_n):
            return []

        def registers_on_the_way(self):
            return 0

        def eval_ack(self, eval_id, token):
            pass

        def eval_nack(self, eval_id, token):
            self.nacked.append(eval_id)

    server = FakeServer()
    pipeline = DispatchPipeline(server)
    assert pipeline.enabled

    # Wedge every launch in its FSM catch-up until released (the
    # follower-lag scenario _wait_for_index exists for).
    def stalled_wait(index, timeout):
        stalled.set()
        release.wait(20.0)
        return False  # timed out: batch naks, slot frees

    pipeline._wait_for_index = stalled_wait
    pipeline.start()
    try:
        for i in range(4):
            ev = Evaluation(id=f"ev-{i}", type="service",
                            job_id=f"job-{i}")
            ev.modify_index = 7  # ahead of the fake FSM: forces catch-up
            pipeline.submit(ev, token=f"tok-{i}")
        assert wait_until(lambda: stalled.is_set(), timeout=5.0)
        # Both batches must LAUNCH while the first launch is still
        # blocked: the dispatcher handed off and kept accumulating.
        assert wait_until(
            lambda: pipeline.stats()["batches"] == 2, timeout=5.0), \
            pipeline.stats()
        assert pipeline.stats()["in_flight"] == 2
        assert not server.nacked  # still wedged, nothing given up yet
    finally:
        # Cleanup ONLY: an assert here would mask the body's failure
        # and skip stop(), leaking the dispatcher into later tests.
        release.set()
        pipeline.stop()
    # Timed-out catch-up naks all four evals and frees both slots.
    assert wait_until(
        lambda: len(server.nacked) == 4
        and pipeline.stats()["in_flight"] == 0, timeout=10.0), \
        (server.nacked, pipeline.stats())



def test_saturated_pipeline_backpressures_worker_drain():
    """Intake backpressure (nomad_tpu/admission): once the accumulator
    holds two full batches, workers stop draining the broker — backlog
    must stay in the BOUNDED ready queues where priority shedding and
    deadline enforcement can see it, not migrate into the pipeline's
    unbounded pending list."""
    server = make_server(eval_batch_size=2)  # saturation bound = 4
    try:
        seed_nodes(server)
        quiesce(server)
        # Freeze the dispatcher so submitted evals stay pending.
        server.dispatch._stop.set()
        with server.dispatch._cond:
            server.dispatch._cond.notify_all()
        if server.dispatch._thread is not None:
            server.dispatch._thread.join(timeout=5.0)

        # Saturate: 4 evals >= 2 * max_batch(2).
        for _ in range(4):
            ev = mock.eval()
            server.eval_update([ev])
        assert wait_until(lambda: server.broker.ready_count() == 4, 5.0)
        for _ in range(4):
            got, token = server.broker.dequeue(["service"], timeout=1.0)
            assert got is not None
            server.dispatch.submit(got, token)
        assert server.dispatch.saturated()

        # A fresh storm lands in the broker; released workers must NOT
        # drain it while the pipeline stays saturated.
        for _ in range(6):
            server.eval_update([mock.eval()])
        assert wait_until(lambda: server.broker.ready_count() == 6, 5.0)
        for w in server.workers:
            w.set_pause(False)
        time.sleep(0.8)  # > DEQUEUE_TIMEOUT: plenty of drain chances
        assert server.broker.ready_count() == 6
        assert server.dispatch.pending_count() == 4
    finally:
        server.shutdown()


def test_pipeline_drops_expired_evals_before_matrix_build():
    """Deadline enforcement at batch launch (nomad_tpu/admission): an
    eval whose deadline passed while accumulating is terminalized with
    a structured reason BEFORE any matrix build — and the live
    remainder of the batch still dispatches."""
    server = make_server(num_schedulers=0)  # manual submit control
    try:
        seed_nodes(server)
        entries = []
        for _ in range(3):
            ev = mock.eval()
            server.eval_update([ev])
        # The live fourth eval belongs to a REAL job so its dispatch
        # can complete with placements.
        job = mock.job()
        job.id = "live-job"
        job.task_groups[0].tasks[0].resources.networks = []
        server.job_register(job)
        assert wait_until(lambda: server.broker.ready_count() == 4, 5.0)
        for _ in range(4):
            got, token = server.broker.dequeue(["service"], timeout=1.0)
            assert got is not None
            entries.append(got)
            # Expire three of them AFTER the broker's dequeue-side
            # check — the window this launch-time drop exists for.
            if len(entries) < 4:
                got.deadline = time.time() - 1.0
            server.dispatch.submit(got, token)

        assert wait_until(
            lambda: server.dispatch.stats()["expired_dropped"] == 3, 10.0)
        state = server.fsm.state
        assert wait_until(
            lambda: all(
                state.eval_by_id(e.id) is not None
                and state.eval_by_id(e.id).terminal_status()
                for e in entries), 10.0)
        for e in entries[:3]:
            stored = state.eval_by_id(e.id)
            assert stored.status == consts.EVAL_STATUS_FAILED
            assert "deadline expired" in stored.status_description
        # The live fourth eval dispatched and placed.
        live = state.eval_by_id(entries[3].id)
        assert live.job_id == "live-job"
        assert live.status == consts.EVAL_STATUS_COMPLETE
        assert state.allocs_by_job("live-job")
        # Leases released: nothing left unacked, nothing re-delivers.
        assert wait_until(lambda: server.broker.unacked_count() == 0, 5.0)
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# the one dense driver: what a dense BATCH must do beyond pure placement
# (a destructive update, exhaustion into a blocked eval, a device fault)
# and what is left for the worker when the operator turns batching off


def _sized_job(jid, count=5, cpu=20, mem=16):
    job = mock.job()
    job.id = jid
    job.task_groups[0].count = count
    res = job.task_groups[0].tasks[0].resources
    res.cpu, res.memory_mb, res.networks = cpu, mem, []
    return job


def _storm(server, jobs):
    """Register `jobs` against parked workers and release them at once,
    so they reach the pipeline as ONE batch. Returns the eval ids."""
    quiesce(server)
    evals = [server.job_register(job)[0] for job in jobs]
    assert wait_until(
        lambda: server.broker.ready_count() >= len(jobs), 15.0)
    for w in server.workers:
        w.set_pause(False)
    return evals


def _settle(server, evals, timeout=120.0):
    state = server.fsm.state

    def done():
        got = [state.eval_by_id(e) for e in evals]
        return all(e is not None and e.terminal_status() for e in got)

    assert wait_until(done, timeout), {
        e: getattr(state.eval_by_id(e), "status", None) for e in evals}


def _live(server, job_id):
    return [a for a in server.fsm.state.allocs_by_job(job_id)
            if not a.terminal_status()]


def _host_fallbacks() -> float:
    from nomad_tpu.utils.metrics import format_prometheus

    for line in format_prometheus().splitlines():
        name, _, value = line.partition(" ")
        if name.endswith("_scheduler_host_fallback_total"):
            return float(value)
    return 0.0


def test_dense_batch_commits_destructive_update_and_stops_old_allocs():
    from nomad_tpu.scheduler.batcher import get_batcher

    server = make_server(num_schedulers=2)
    try:
        seed_nodes(server, 8)
        # count > 3: fewer asks and the dense scheduler itself hands
        # them to the host iterators
        jobs = [_sized_job(f"upd-{i}", count=5) for i in range(4)]
        _settle(server, _storm(server, jobs))
        old = {j.id: {a.id for a in _live(server, j.id)} for j in jobs}
        assert all(len(ids) == 5 for ids in old.values())
        routed = server.dispatch.stats()["routed_host"]
        served = get_batcher().batched_requests
        # More cpu a task: the diff has an update bucket, every old
        # allocation must stop and a new one take its place.
        updates = [_sized_job(j.id, count=5, cpu=30) for j in jobs]
        _settle(server, _storm(server, updates))
        assert server.dispatch.stats()["routed_host"] == routed
        assert get_batcher().batched_requests > served
        for job in jobs:
            live = _live(server, job.id)
            assert len(live) == 5
            assert not {a.id for a in live} & old[job.id]
            assert all(res.cpu == 30 for a in live
                       for res in a.task_resources.values())
            for alloc_id in old[job.id]:
                gone = server.fsm.state.alloc_by_id(alloc_id)
                assert gone.desired_status == consts.ALLOC_DESIRED_STOP
    finally:
        server.shutdown()


def test_dense_batch_exhaustion_blocks_then_unblocks_on_new_nodes():
    server = make_server(num_schedulers=2)
    try:
        seed_nodes(server, 2, cpu=100, mem=256)
        # 2 jobs x 8 allocations x 30 cpu do not fit two tiny nodes.
        jobs = [_sized_job(f"blk-{i}", count=8, cpu=30) for i in range(2)]
        _settle(server, _storm(server, jobs))
        blocked = [e for e in server.fsm.state.evals()
                   if e.status == consts.EVAL_STATUS_BLOCKED]
        assert {e.job_id for e in blocked} == {j.id for j in jobs}, [
            (e.job_id, e.status, e.triggered_by)
            for e in server.fsm.state.evals()]
        seed_nodes(server, 6)  # capacity arrives
        assert wait_until(
            lambda: all(len(_live(server, j.id)) == 8 for j in jobs),
            90.0), {j.id: len(_live(server, j.id)) for j in jobs}
    finally:
        server.shutdown()


def test_device_fault_in_a_dense_batch_falls_back_to_the_host_path():
    from nomad_tpu.admission import get_breaker
    from nomad_tpu.chaos import FaultSpec, chaos

    server = make_server(num_schedulers=2)
    try:
        seed_nodes(server, 8)
        warm = [_sized_job(f"warm-{i}") for i in range(4)]
        _settle(server, _storm(server, warm))
        before = _host_fallbacks()
        chaos.arm(7, [FaultSpec("binpack.device", "error", count=1)])
        jobs = [_sized_job(f"faulted-{i}") for i in range(6)]
        _settle(server, _storm(server, jobs))
        fired = chaos.firing_log()
        chaos.disarm()
        assert any(site == "binpack.device" for site, _n, _k, _d in fired)
        assert _host_fallbacks() - before >= 1
        for job in jobs:
            assert len(_live(server, job.id)) == 5
    finally:
        chaos.disarm()
        breaker = get_breaker()
        breaker.reset()
        breaker.configure_defaults()
        server.shutdown()


def test_batching_off_worker_runs_dense_eval_on_its_dense_factory():
    """eval_batch_size=1 is the operator turning batching off: the
    pipeline stands down and the worker runs each dense eval itself on
    the dense factory configured, one eval a dispatch, no host
    routing."""
    from nomad_tpu.scheduler.batcher import get_batcher

    server = make_server(num_schedulers=1, eval_batch_size=1)
    try:
        assert server.dispatch.enabled is False
        assert server.dispatch.stats()["enabled"] is False
        seed_nodes(server, 8)
        served = get_batcher().batched_requests
        job = _sized_job("alone", count=5)
        ev, _ = server.job_register(job)
        _settle(server, [ev])
        assert len(_live(server, job.id)) == 5
        assert get_batcher().batched_requests == served + 1
        stats = server.dispatch.stats()
        assert stats["batches"] == 0 and stats["routed_host"] == 0, stats
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# the batch's cohort at the batcher: every way an announced eval can end
# settles its unit, so no batch-mate's dispatch waits for a request that
# is not coming and nothing is left for the cap to release


def _run_batch_by_hand(server, pipe, jobs, extra=()):
    """Register `jobs` (no worker runs: num_schedulers=0), take their
    evals from the broker and run them as ONE batch of the UNSTARTED
    pipeline `pipe`: the launch prologue, then the stage function for
    every entry on a thread of its own, as the pool would. `extra`
    entries (a requeued one) join the batch first. Returns the entries
    and their units."""
    import threading

    from nomad_tpu.dispatch.pipeline import _Pending

    for job in jobs:
        server.job_register(job)
    assert wait_until(
        lambda: server.broker.ready_count() >= len(jobs), 15.0)
    got = server.eval_dequeue_many(pipe.types, len(jobs))
    assert len(got) == len(jobs)
    batch = list(extra) + [_Pending(ev, token) for ev, token in got]
    with pipe._cond:
        pipe._inflight += 1  # the slot _accumulate would have taken
    snapshot, route_host, units = pipe._launch_prologue(batch)
    assert not route_host and len(units) == len(batch)
    remaining = [len(batch)]
    threads = [
        threading.Thread(
            target=pipe._process_entry, daemon=True,
            args=(entry, snapshot, route_host, remaining,
                  time.monotonic(), unit))
        for entry, unit in zip(batch, units)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not any(t.is_alive() for t in threads), "a stage thread hangs"
    assert pipe.stats()["in_flight"] == 0
    return batch, units


@pytest.mark.parametrize("way", [
    "host_fallback", "breaker_rejected", "no_placements",
    "scheduler_raises", "requeue"])
def test_an_announced_eval_that_never_places_settles_its_unit(
        way, monkeypatch):
    from nomad_tpu.admission import get_breaker
    from nomad_tpu.chaos import FaultSpec, chaos
    from nomad_tpu.dispatch import DispatchPipeline
    from nomad_tpu.dispatch import pipeline as pipeline_mod
    from nomad_tpu.scheduler.batcher import get_batcher

    server = make_server(num_schedulers=0)
    breaker = get_breaker()
    try:
        seed_nodes(server, 8)
        pipe = DispatchPipeline(server)  # not started: driven by hand
        before = get_batcher().stats()
        fallbacks = _host_fallbacks()
        # Three evals, one batch. The first job is the one that never
        # reaches place().
        jobs = [_sized_job(f"{way}-{i}",
                           count=2 if (way, i) == ("requeue", 0) else 5)
                for i in range(3)]
        if way == "no_placements":
            # Registered again unchanged: its second eval has nothing
            # to place, and the dense scheduler never computes any.
            standing = [_sized_job(f"{way}-standing-{i}") for i in range(2)]
            _run_batch_by_hand(server, pipe, [jobs[0]] + standing)
            assert len(_live(server, jobs[0].id)) == 5
            before = get_batcher().stats()
        if way == "host_fallback":
            # A fault at the breaker's gate, BEFORE place(): the dense
            # scheduler's except path, then the host iterators.
            chaos.arm(7, [FaultSpec("device.breaker_trip", "error",
                                    count=1)])
        elif way == "breaker_rejected":
            verdicts = iter([False])
            real_acquire = breaker.acquire
            monkeypatch.setattr(
                breaker, "acquire",
                lambda: next(verdicts, None) is None and real_acquire())
        elif way == "scheduler_raises":
            chaos.arm(7, [FaultSpec("admission.slow_consumer", "error",
                                    count=1)])
        elif way == "requeue":
            # Two asks go to the host iterators inside the dense
            # scheduler; the plan then meets a conflict.
            real_submit = pipeline_mod.PipelineSession.submit_plan
            conflicts = iter([True])

            def submit_plan(session, plan):
                if (session.eval.job_id == jobs[0].id
                        and next(conflicts, False)):
                    raise pipeline_mod._RequeueConflict()
                return real_submit(session, plan)

            monkeypatch.setattr(pipeline_mod.PipelineSession,
                                "submit_plan", submit_plan)

        batch, units = _run_batch_by_hand(server, pipe, jobs)
        chaos.disarm()
        assert all(u is not None and not u.open for u in units)
        assert units[0].cohort is units[1].cohort is units[2].cohort

        def settled():
            after = get_batcher().stats()
            assert after["open_cohorts"] == before["open_cohorts"], after
            assert after["closed_by_cap"] == before["closed_by_cap"], after
            assert (after["closed_by_window"]
                    == before["closed_by_window"]), after

        settled()
        stats = pipe.stats()
        placed = {j.id: len(_live(server, j.id)) for j in jobs}
        if way == "host_fallback":
            assert _host_fallbacks() - fallbacks >= 1
            assert placed == {j.id: 5 for j in jobs} and stats["acked"] == 3
        elif way == "breaker_rejected":
            assert placed == {j.id: 5 for j in jobs} and stats["acked"] == 3
        elif way == "no_placements":
            assert placed == {j.id: 5 for j in jobs} and stats["acked"] == 6
            after = get_batcher().stats()
            assert (after["batched_requests"] - before["batched_requests"]
                    == 2), after
        elif way == "scheduler_raises":
            assert sorted(placed.values()) == [0, 5, 5]
            assert stats["acked"] == 2 and stats["nacked"] == 1
        else:
            assert placed == {jobs[0].id: 0, jobs[1].id: 5, jobs[2].id: 5}
            assert stats["requeues"] == 1 and stats["acked"] == 2
            # The requeued eval is back in the accumulator; it joins the
            # NEXT batch's cohort with a unit of its own.
            with pipe._cond:
                (requeued,) = pipe._pending
                pipe._pending.clear()
            assert requeued is batch[0] and requeued.requeues == 1
            more = [_sized_job(f"{way}-more-{i}") for i in range(2)]
            _batch2, units2 = _run_batch_by_hand(
                server, pipe, more, extra=[requeued])
            assert units2[0] is not units[0]
            assert units2[0].cohort is units2[1].cohort
            assert units2[0].cohort is not units[0].cohort
            settled()
            assert len(_live(server, jobs[0].id)) == 2
            assert all(len(_live(server, j.id)) == 5 for j in more)
            assert pipe.stats()["acked"] == 5
    finally:
        chaos.disarm()
        breaker.reset()
        breaker.configure_defaults()
        server.shutdown()


# ---------------------------------------------------------------------
# a batch that forms beside one in flight is announced to the batcher,
# so that the in-flight batch's dispatch waits for it: the two go
# together whatever the prologue's length


@pytest.mark.parametrize("in_flight, announced", [
    (0, False),   # nobody to go with: the idle grace, no placeholder
    (1, True),    # a slot is free: announced while the window runs
    (2, True),    # every slot busy: announced when it takes its slot
])
def test_a_batch_forming_beside_one_in_flight_is_announced(
        in_flight, announced):
    import threading

    from nomad_tpu.dispatch import DispatchPipeline
    from nomad_tpu.dispatch.pipeline import _Pending
    from nomad_tpu.scheduler.batcher import get_batcher

    server = make_server(num_schedulers=0)
    try:
        pipe = DispatchPipeline(server)  # not started: we drive it
        assert pipe.max_inflight == 2
        batcher = get_batcher()
        opened = batcher.stats()["open_cohorts"]
        with pipe._cond:
            pipe._inflight = in_flight
        got = []
        t = threading.Thread(
            target=lambda: got.append(pipe._accumulate()), daemon=True)
        pipe._admit(_Pending(mock.eval(), "tok-0"))
        t.start()
        if in_flight == 1:
            # inside the window, before the batch is cut
            assert wait_until(lambda: pipe._forming is not None, 2.0)
            assert not got
        elif in_flight == 2:
            time.sleep(0.2)
            assert not got and pipe._forming is None
            with pipe._cond:
                pipe._inflight = 1  # one of the two finished
                pipe._cond.notify_all()
        t.join(timeout=5.0)
        assert got and len(got[0]) == 1
        forming = pipe._forming
        assert (forming is not None) == announced
        assert batcher.stats()["open_cohorts"] == opened + announced

        # Whatever way the launch ends, the placeholder is settled: a
        # prologue that fails aborts the batch and must leave no cohort
        # for the cap to release.
        def boom(batch, forming=None):
            raise RuntimeError("prologue")

        pipe._launch_prologue = boom
        pipe._launch(got[0], forming)
        assert batcher.stats()["open_cohorts"] == opened
        assert pipe.stats()["in_flight"] == in_flight - (in_flight == 2)
    finally:
        server.shutdown()


@pytest.mark.parametrize("lagging", [True, False])
def test_nobody_waits_a_lagging_index_out_on_the_placeholder(lagging):
    """The placeholder covers the forming batch's window and its base
    prefetch, never _wait_for_index: where the FSM is behind the
    batch's index (a follower that lags its leader), the prologue
    settles it before it starts to wait, so the batch in flight goes
    alone instead of sitting out COHORT_WAIT_MAX."""
    from nomad_tpu.dispatch import DispatchPipeline
    from nomad_tpu.dispatch.pipeline import _Pending
    from nomad_tpu.scheduler.batcher import get_batcher

    server = make_server(num_schedulers=0)
    try:
        pipe = DispatchPipeline(server)  # not started: we drive it
        batcher = get_batcher()
        opened = batcher.stats()["open_cohorts"]
        pipe._announce_forming()
        forming = pipe._forming
        assert batcher.stats()["open_cohorts"] == opened + 1
        ev = mock.eval()
        ev.modify_index = server.fsm.state.latest_index() + (
            5 if lagging else 0)
        seen = []

        def never(index, timeout):
            seen.append((index, forming.open,
                         batcher.stats()["open_cohorts"]))
            return False  # the FSM never caught up: the batch aborts

        pipe._wait_for_index = never
        assert pipe._launch_prologue(
            [_Pending(ev, "tok-0")], forming) is None
        assert seen == [(ev.modify_index, not lagging,
                         opened + (not lagging))]
        forming.settle()  # what _launch's finally does
        assert batcher.stats()["open_cohorts"] == opened
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# the second slot is earned: a plan conflict sends batches through alone


def _unstarted_pipe(server):
    from nomad_tpu.dispatch import DispatchPipeline

    pipe = DispatchPipeline(server)  # not started: we drive it
    assert pipe.enabled and pipe.max_inflight == 2
    return pipe


def _cut_in_thread(pipe):
    import threading

    got = []
    t = threading.Thread(
        target=lambda: got.append(pipe._accumulate()), daemon=True)
    t.start()
    return t, got


@pytest.mark.parametrize("conflicted", [False, True])
def test_a_batch_launches_beside_one_in_flight_unless_a_plan_conflicted(
        conflicted):
    """With a slot free a forming batch launches after the window,
    beside the batch in flight. After a plan conflict the next batch
    waits for the pipeline to be EMPTY (its snapshot then holds every
    plan of its predecessor), and the one after it may share again."""
    from nomad_tpu.dispatch.pipeline import _Pending

    server = make_server(num_schedulers=0)
    try:
        pipe = _unstarted_pipe(server)
        with pipe._cond:
            pipe._inflight = 1
        pipe._note_conflict()  # among one batch's own plans: no cost
        assert pipe.stats()["alone"] == 0
        if conflicted:
            with pipe._lock:  # a batch was cut beside the one in flight
                pipe._shared_mark = pipe.plan_conflicts
            pipe._note_conflict()
            assert pipe.stats()["alone"] == 1
        pipe._admit(_Pending(mock.eval(), "tok-0"))
        t, got = _cut_in_thread(pipe)
        if conflicted:
            time.sleep(4 * pipe.window)
            assert not got, "launched beside a batch after a conflict"
            assert pipe._forming is None  # nobody waits on a placeholder
            pipe._release_slot([1])  # the batch in flight finished
        t.join(timeout=5.0)
        assert got and len(got[0]) == 1
        stats = pipe.stats()
        assert stats["in_flight"] == (1 if conflicted else 2)
        assert stats["alone"] == 0
        assert stats["alone_batches"] == (1 if conflicted else 0)
        if conflicted:
            # The penalty is spent: the next batch shares again.
            pipe._admit(_Pending(mock.eval(), "tok-1"))
            t, got = _cut_in_thread(pipe)
            t.join(timeout=5.0)
            assert got and pipe.stats()["in_flight"] == 2
        for forming in (pipe._forming,):
            if forming is not None:
                forming.settle()
    finally:
        server.shutdown()


@pytest.mark.parametrize("tries", [1, 2, 4, 9])
def test_every_shared_stretch_that_conflicts_doubles_the_batches_alone(
        tries):
    """The first stretch of shared slots that conflicts costs one batch
    alone, the next two, then four, up to ALONE_MAX; conflicts that
    come while batches already go alone (the in-flight batches' other
    plans) cost nothing more."""
    from nomad_tpu.dispatch.pipeline import ALONE_MAX

    server = make_server(num_schedulers=0)
    try:
        pipe = _unstarted_pipe(server)
        for n in range(tries):
            with pipe._lock:  # a batch is cut beside one in flight
                pipe._shared_mark = pipe.plan_conflicts
            pipe._note_conflict()
            want = min(2 ** n, ALONE_MAX)
            assert pipe.stats()["alone"] == want
            pipe._note_conflict()  # the same stretch's next plan
            pipe._note_conflict()
            assert pipe.stats()["alone"] == want
            assert pipe.stats()["alone_next"] == min(2 * want, ALONE_MAX)
            with pipe._lock:
                pipe._alone = 0  # those batches have gone
        assert pipe.stats()["plan_conflicts"] == 3 * tries
    finally:
        server.shutdown()


@pytest.mark.parametrize("conflicts", [0, 1])
def test_a_stretch_of_shared_slots_without_a_conflict_resets_the_cost(
        conflicts):
    """The cost of the next conflict falls back to one batch when two
    batches have shared the slots and nothing conflicted (a ramp of
    jobs that do not want the same nodes keeps its overlap after a
    storm has passed); a stretch with a conflict leaves it."""
    from nomad_tpu.dispatch.pipeline import _Pending

    server = make_server(num_schedulers=0)
    try:
        pipe = _unstarted_pipe(server)
        with pipe._lock:
            pipe._alone_next = 8  # what an earlier storm left
            pipe._inflight = 1
        pipe._admit(_Pending(mock.eval(), "tok-0"))
        t, got = _cut_in_thread(pipe)
        t.join(timeout=5.0)
        assert got and pipe.stats()["in_flight"] == 2
        if pipe._forming is not None:
            pipe._forming.settle()
        for _ in range(conflicts):
            pipe._note_conflict()
        pipe._release_slot([1])
        assert pipe.stats()["alone_next"] == (16 if conflicts else 8)
        pipe._release_slot([1])  # the pipeline is empty: stretch over
        stats = pipe.stats()
        assert stats["in_flight"] == 0
        assert stats["alone_next"] == (16 if conflicts else 1)
        assert stats["alone"] == (8 if conflicts else 0)
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# the idle close reads whether a register is on the way: an idle
# pipeline cuts a lone eval at once, and `dispatch_idle_grace` is the
# cap on waiting for arrivals that are known to be coming


def _idle_pipe(grace):
    """A server without workers whose pipeline is an unstarted one we
    drive: the server's own wake (the last register's return) reaches
    it."""
    server = make_server(num_schedulers=0, dispatch_idle_grace=grace)
    server.dispatch.stop()
    server.dispatch = _unstarted_pipe(server)
    return server, server.dispatch


def _idle_counts(pipe):
    stats = pipe.stats()
    return (stats["idle_closes"], stats["idle_closes_at_once"],
            stats["idle_closes_at_cap"])


def test_an_idle_pipeline_cuts_a_lone_eval_at_once():
    from nomad_tpu.dispatch.pipeline import _Pending

    server, pipe = _idle_pipe(0.5)
    try:
        assert pipe.stats()["registers_on_the_way"] == 0
        pipe._admit(_Pending(mock.eval(), "tok-0"))
        t0 = time.monotonic()
        batch = pipe._accumulate()
        assert time.monotonic() - t0 < 0.1, "waited for nobody"
        assert len(batch) == 1
        assert pipe._forming is None
        assert _idle_counts(pipe) == (1, 1, 0)
    finally:
        server.shutdown()


def test_a_register_on_the_way_holds_the_batch_until_it_has_returned(
        monkeypatch):
    """With a register on the way the accumulator holds the eval, a
    second eval admitted meanwhile rides the same batch, and the batch
    is cut when the last register returns: woken by that return (the
    slice is out of the way here), well before the cap."""
    import contextlib

    from nomad_tpu.dispatch import pipeline as pipeline_mod
    from nomad_tpu.dispatch.pipeline import _Pending

    monkeypatch.setattr(pipeline_mod, "DEQUEUE_TOPUP_SLICE", 5.0)
    server, pipe = _idle_pipe(30.0)
    try:
        on_the_way = contextlib.ExitStack()
        on_the_way.enter_context(server._registering())
        assert pipe.stats()["registers_on_the_way"] == 1
        pipe._admit(_Pending(mock.eval(), "tok-0"))
        t, got = _cut_in_thread(pipe)
        time.sleep(0.1)
        assert not got, "cut while a register was on the way"
        pipe._admit(_Pending(mock.eval(), "tok-1"))
        time.sleep(0.1)
        assert not got
        t0 = time.monotonic()
        on_the_way.close()
        t.join(timeout=5.0)
        assert time.monotonic() - t0 < 1.0, "the return woke nobody"
        assert got and len(got[0]) == 2
        assert pipe.stats()["registers_on_the_way"] == 0
        assert _idle_counts(pipe) == (1, 0, 0)
    finally:
        server.shutdown()


def test_a_register_that_never_returns_is_waited_for_up_to_the_cap():
    from nomad_tpu.dispatch.pipeline import _Pending

    server, pipe = _idle_pipe(0.1)
    try:
        with server._registering():
            pipe._admit(_Pending(mock.eval(), "tok-0"))
            t0 = time.monotonic()
            batch = pipe._accumulate()
            waited = time.monotonic() - t0
            assert len(batch) == 1
            assert 0.1 <= waited < 1.0, waited
            assert _idle_counts(pipe) == (1, 0, 1)
    finally:
        server.shutdown()


def _count_at_eval_update(server):
    """The count as each `eval_update` sees it, from here on."""
    seen = []
    eval_update = server.eval_update

    def watched(evals, token=""):
        seen.append(server.registers_on_the_way())
        return eval_update(evals, token)

    server.eval_update = watched
    return seen


def _registered_job(server):
    job = _sized_job("held")
    server.job_register(job)
    return job


@pytest.mark.parametrize("call", [
    lambda server, job: server.job_register(_sized_job("fresh")),
    lambda server, job: server.job_deregister(job.id),
    lambda server, job: server.job_evaluate(job.id),
    lambda server, job: server.node_update_drain(
        seed_nodes(server, 1)[0].id, True),
], ids=["register", "deregister", "evaluate", "node-drain"])
def test_every_call_that_creates_an_eval_is_on_the_way_until_it_returns(
        call):
    server = make_server(num_schedulers=0)
    try:
        seed_nodes(server, 2)
        job = _registered_job(server)
        server.job_register(mock.system_job())  # what a drain evaluates
        seen = _count_at_eval_update(server)
        call(server, job)
        assert seen == [1], "the eval went on the broker uncounted"
        assert server.registers_on_the_way() == 0
        assert server.dispatch.stats()["registers_on_the_way"] == 0
    finally:
        server.shutdown()


def _invalid_job(server):
    job = _sized_job("invalid")
    job.task_groups = []
    return job, {}


def _stale_index(server):
    job = _registered_job(server)
    return job, {"enforce_index": True,
                 "job_modify_index": job.job_modify_index + 7}


def _apply_raises(server):
    def boom(*args, **kwargs):
        raise RuntimeError("raft apply")

    server.log.apply = boom
    return _sized_job("lost"), {}


@pytest.mark.parametrize("refused, error", [
    (_invalid_job, ValueError),
    (_stale_index, ValueError),
    (_apply_raises, RuntimeError),
], ids=["invalid-job", "enforce-index", "apply-raises"])
def test_a_register_that_raises_leaves_nobody_on_the_way(refused, error):
    server = make_server(num_schedulers=0)
    try:
        job, kwargs = refused(server)
        with pytest.raises(error):
            server.job_register(job, **kwargs)
        assert server.registers_on_the_way() == 0
    finally:
        server.shutdown()


def test_a_lone_registration_is_cut_at_once_on_a_live_server():
    """Through the whole path (register, broker, worker, accumulator)
    with a grace no test would sit out: the register has returned when
    its eval reaches the accumulator, so nothing is waited for."""
    server = make_server(dispatch_idle_grace=30.0)
    try:
        seed_nodes(server, 4)
        job = _sized_job("lone")
        server.job_register(job)
        assert wait_until(
            lambda: len(server.fsm.state.allocs_by_job(job.id)) == 5,
            timeout=120.0), server.dispatch.stats()
        stats = server.dispatch.stats()
        assert stats["batches"] == stats["idle_closes"] == 1, stats
        assert stats["idle_closes_at_cap"] == 0, stats
    finally:
        server.shutdown()


def test_agent_self_serves_the_count_and_the_idle_closes():
    """A register over HTTP is on the way while its eval goes to the
    broker and not after its reply, a refused body leaves nobody on
    the way, and `/v1/agent/self` serves the count and the idle
    closes."""
    from nomad_tpu.api import Client, HTTPServer

    server = make_server(num_schedulers=0)
    http = HTTPServer(server)
    http.start()
    try:
        seen = _count_at_eval_update(server)
        client = Client(http.addr, timeout=10.0)
        client.jobs.register(_sized_job("over-http"))
        assert seen == [1]
        assert server.registers_on_the_way() == 0
        with pytest.raises(Exception):
            client.jobs.register(_invalid_job(server)[0])
        assert server.registers_on_the_way() == 0
        pipe = client.agent.self()["stats"]["dispatch_pipeline"]
        assert pipe["registers_on_the_way"] == 0
        assert {"idle_closes", "idle_closes_at_once",
                "idle_closes_at_cap"} <= set(pipe)
    finally:
        http.stop()
        server.shutdown()
