"""Factory routing: with a dense (TPU) factory configured every eval
the pipeline launches runs on the dense factory, alone or in a batch;
only the device-path circuit breaker sends a batch to the host
factories. The ONE size rule left is the dense scheduler's own
(scheduler/tpu.py `_compute_placements`): an eval of one to three asks
with no batch to ride walks the host iterators, everything else is a
lane of a device dispatch."""

import time

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.batcher import get_batcher
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.worker import (
    DEQUEUE_TIMEOUT, host_factory, is_dense_factory)


def wait_until(fn, timeout=30.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def make_server(**over):
    cfg = ServerConfig(
        num_schedulers=1,
        scheduler_factories={"service": "service-tpu"},
        eval_batch_size=16,
        **over,
    )
    server = Server(cfg)
    server.start()
    return server


def seed_nodes(server, n=8):
    for _ in range(n):
        node = mock.node()
        node.compute_class()
        server.node_register(node)


def test_host_factory_mapping():
    assert host_factory("service-tpu") == "service"
    assert host_factory("batch-tpu") == "batch"
    assert host_factory("service") == "service"
    assert is_dense_factory("system-tpu")
    assert not is_dense_factory("system")
    # Kernel-pinned dense variants (nomad_tpu/kernels) fall back to
    # the SAME host factory: the kernel infix strips with the suffix.
    assert host_factory("service-convex-tpu") == "service"
    assert host_factory("batch-greedy-tpu") == "batch"
    assert is_dense_factory("service-convex-tpu")


def counted(suffix):
    from nomad_tpu.utils.metrics import get_metrics

    life = get_metrics().inmem._life.counters
    return sum(c[1] for name, c in list(life.items())
               if name.endswith(suffix))


# The one size rule that is left, scheduler/tpu.py _compute_placements:
# (asks of the eval, what it finds at the batcher) -> host walk?
# Priority 70 may preempt under a threshold of 50 and then stays dense
# at any size (the host iterators cannot evict).
SITUATIONS = ("no_cohort", "cohort_of_one", "batch_mates", "replan",
              "requeued", "preemption_eligible")
SMALL_ASK_TABLE = {
    f"{asks}_asks_{situation}":
        (asks, situation,
         asks <= 3 and situation not in ("batch_mates",
                                         "preemption_eligible"))
    for asks in (1, 3, 4) for situation in SITUATIONS}


@pytest.mark.parametrize("case", sorted(SMALL_ASK_TABLE))
def test_small_ask_rule_truth_table(case):
    """One to three asks walk the host iterators only when the eval has
    no batch to ride (no cohort, a cohort of one, a unit that has
    ridden its dispatch as an inline replan's has, a run the pipeline
    requeued after a conflict) and may not preempt; four asks are a
    lane whatever the eval finds."""
    from nomad_tpu import migrate
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs import consts, new_eval

    asks, situation, want_host = SMALL_ASK_TABLE[case]
    before = migrate.preempt_stats()
    migrate.configure(
        preemption_enabled=situation == "preemption_eligible",
        preempt_priority_threshold=50)
    batcher = get_batcher()
    try:
        h = Harness(seed=93)
        for _ in range(6):
            h.state.upsert_node(h.next_index(), mock.node())
        job = mock.job()
        job.task_groups[0].count = asks
        if situation == "preemption_eligible":
            job.priority = 70
        h.state.upsert_job(h.next_index(), job)
        units = []
        if situation != "no_cohort":
            units = batcher.open_cohort(
                1 if situation in ("cohort_of_one",
                                   "preemption_eligible") else 2)
            for mate in units[1:]:
                mate.settle()
            if situation == "replan":
                units[0].settle()  # as after its first dispatch
            h.cohort = units[0]
            h.settle_cohort = units[0].settle
        h.requeued = situation == "requeued"
        small = counted("scheduler.small_route_host_evals")
        served = batcher.stats()["batched_requests"]
        h.process("service-tpu",
                  new_eval(job, consts.EVAL_TRIGGER_JOB_REGISTER))
        assert len(h.state.allocs_by_job(job.id)) == asks
        on_host = counted("scheduler.small_route_host_evals") - small
        on_device = batcher.stats()["batched_requests"] - served
        assert (on_host, on_device) == ((1, 0) if want_host else (0, 1))
        assert batcher.stats()["open_cohorts"] == 0
    finally:
        migrate.configure(
            preemption_enabled=before["enabled"],
            preempt_priority_threshold=before["priority_threshold"])


def test_tpu_suffix_fallback_registers_lazily():
    """scheduler/__init__.py:52: an unregistered `*-tpu` name triggers
    lazy TPU-factory registration (including every kernel's pinned
    variants) instead of failing — and a name that is neither
    registered nor a -tpu factory fails loudly."""
    import logging

    import pytest as _pytest

    from nomad_tpu import scheduler as sched_mod
    from nomad_tpu.scheduler.testing import Harness

    # Force the lazy path even if another test already registered the
    # dense factories in this process.
    for name in [n for n in sched_mod.scheduler_names()
                 if n.endswith("-tpu")]:
        sched_mod._BUILTIN.pop(name)
    h = Harness()
    logger = logging.getLogger("test")

    s = sched_mod.new_scheduler("service-tpu", logger, h.snapshot(), h)
    assert type(s).__name__ == "BatchedTPUScheduler"
    assert s.kernel is None  # defers to the process-global kernel
    # Kernel-pinned variant, also via the fallback.
    for name in [n for n in sched_mod.scheduler_names()
                 if n.endswith("-tpu")]:
        sched_mod._BUILTIN.pop(name)
    s2 = sched_mod.new_scheduler(
        "batch-convex-tpu", logger, h.snapshot(), h)
    assert type(s2).__name__ == "BatchedTPUScheduler"
    assert s2.kernel == "convex"
    assert s2.batch is True

    with _pytest.raises(ValueError, match="unknown scheduler"):
        sched_mod.new_scheduler("service-xyz", logger, h.snapshot(), h)
    # An unknown KERNEL variant: the -tpu fallback registers the real
    # kernels, the typo'd name stays unknown and fails loudly.
    with _pytest.raises(ValueError, match="unknown scheduler"):
        sched_mod.new_scheduler(
            "service-convexx-tpu", logger, h.snapshot(), h)


def test_unknown_placement_kernel_fails_at_server_init():
    """A typo'd `placement_kernel` must abort Server construction with
    the registered-kernel list — not surface at the first eval."""
    import pytest as _pytest

    from nomad_tpu.kernels import active_kernel, configure

    before = active_kernel()
    try:
        with _pytest.raises(ValueError, match="unknown placement kernel"):
            Server(ServerConfig(num_schedulers=1,
                                placement_kernel="convexx"))
        # The valid names configure cleanly (no server needed).
        configure("convex")
        assert active_kernel() == "convex"
        configure("greedy")
    finally:
        configure(before)


def test_placement_kernel_knob_reaches_stats_surface():
    """ServerConfig.placement_kernel = "convex" routes dense evals
    through the convex kernel, and the quality scoreboard surfaces it
    in server.stats()["placement_quality"]."""
    from nomad_tpu.kernels import active_kernel, configure
    from nomad_tpu.kernels.quality import get_board

    before = active_kernel()
    get_board().reset()
    server = make_server(placement_kernel="convex")
    try:
        seed_nodes(server)
        for w in server.workers:
            w.set_pause(True)
        # as below: the ack, so no dequeue in flight steals an eval
        assert wait_until(
            lambda: all(w.parked() for w in server.workers),
            timeout=4 * DEQUEUE_TIMEOUT + 30.0)
        jobs = []
        for _ in range(4):
            job = mock.job()
            job.task_groups[0].count = 5  # >3 so the dense path engages
            server.job_register(job)
            jobs.append(job)
        assert wait_until(lambda: server.broker.ready_count() >= 4)
        for w in server.workers:
            w.set_pause(False)
        assert wait_until(
            lambda: all(
                len(server.fsm.state.allocs_by_job(j.id)) == 5
                for j in jobs),
            timeout=60.0,
        )
        pq = server.stats()["placement_quality"]
        assert "convex" in pq["kernels"], pq
        entry = pq["kernels"]["convex"]
        assert entry["samples"] > 0
        assert 0.0 <= entry["fragmentation"] <= 1.0
        assert 0.0 <= entry["binpack_score"] <= 1.0
        assert "queueing_delay_ms" in pq
    finally:
        server.shutdown()
        configure(before)


def test_lone_small_eval_walks_the_host_iterators():
    """One job of three asks registered on an idle broker: the pipeline
    launches it dense, a cohort of one, and the dense scheduler's
    small-ask rule hands it to the host iterators: no batcher traffic,
    and the pipeline routed nothing."""
    server = make_server()
    try:
        seed_nodes(server)
        batcher = get_batcher()
        before = batcher.batched_requests
        small = counted("scheduler.small_route_host_evals")
        job = mock.job()
        job.task_groups[0].count = 3
        server.job_register(job)
        assert wait_until(
            lambda: len(server.fsm.state.allocs_by_job(job.id)) == 3)
        assert batcher.batched_requests == before
        assert counted("scheduler.small_route_host_evals") == small + 1
        assert server.dispatch.stats()["routed_host"] == 0
    finally:
        server.shutdown()


def test_eval_storm_routes_to_dense_path():
    """Many ready evals drain as one batch and ride the device
    batcher."""
    server = make_server()
    try:
        seed_nodes(server)
        batcher = get_batcher()
        before_req = batcher.batched_requests
        for w in server.workers:
            w.set_pause(True)
        # a worker inside its dequeue long-poll takes one more eval
        # before it parks: wait for the ack, not a fixed sleep
        assert wait_until(
            lambda: all(w.parked() for w in server.workers),
            timeout=4 * DEQUEUE_TIMEOUT + 30.0)
        jobs = []
        for _ in range(6):
            job = mock.job()
            job.task_groups[0].count = 5  # >3 so the dense path engages
            server.job_register(job)
            jobs.append(job)
        assert wait_until(lambda: server.broker.ready_count() >= 6)
        for w in server.workers:
            w.set_pause(False)
        assert wait_until(
            lambda: all(
                len(server.fsm.state.allocs_by_job(j.id)) == 5 for j in jobs),
            timeout=60.0,
        )
        # The drained batch went dense: batcher served its requests.
        assert batcher.batched_requests > before_req
    finally:
        server.shutdown()


def test_lone_eval_is_served_by_the_batcher():
    """A lone eval of six asks on an idle pipeline is a dispatch of one
    lane: the batcher serves it, the pipeline routes nothing to the
    host."""
    server = make_server()
    try:
        seed_nodes(server)
        batcher = get_batcher()
        before = batcher.batched_requests
        job = mock.job()
        job.task_groups[0].count = 6  # >3: past the small-ask rule
        server.job_register(job)
        assert wait_until(
            lambda: len(server.fsm.state.allocs_by_job(job.id)) == 6,
            timeout=60.0,
        )
        assert batcher.batched_requests == before + 1
        stats = server.dispatch.stats()
        assert stats["routed_host"] == 0 and stats["batches"] >= 1, stats
        assert batcher.stats()["open_cohorts"] == 0
    finally:
        server.shutdown()
