"""Latency-aware factory routing: with a dense (TPU) factory
configured, a LONE eval runs on the host iterator pipeline
(millisecond latency — it must not pay the batch window + device RTT),
while a drained batch runs dense and coalesces into shared device
dispatches. VERDICT r2 ask #8."""

import time

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.batcher import get_batcher
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.worker import (
    host_factory,
    is_dense_factory,
    routes_host,
)


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


# (priorities of the batch, dense_min_batch, preemption on) -> host?
# Threshold 50: priority 70 may preempt, 50 may not (strictly above).
ROUTES_HOST_TABLE = {
    "below_min_batch": ([50], 2, False, True),
    "at_min_batch": ([50, 50], 2, False, False),
    "min_batch_one_forces_dense": ([50], 1, False, False),
    "empty_batch_below_min": ([], 2, False, True),
    "none_eligible": ([50], 2, True, True),
    "one_eligible_stays_dense": ([50, 70, 50], 4, True, False),
    "all_eligible_stay_dense": ([70], 2, True, False),
    "eligible_priority_but_preemption_off": ([70], 2, False, True),
}


@pytest.mark.parametrize("case", sorted(ROUTES_HOST_TABLE))
def test_routes_host_truth_table(case):
    """The dispatch pipeline's one routing rule: a batch under
    dense_min_batch goes to the host factories unless one of its evals
    may preempt (the host iterators cannot evict)."""
    from nomad_tpu import migrate

    priorities, min_batch, preempt_on, want = ROUTES_HOST_TABLE[case]
    before = migrate.preempt_stats()
    migrate.configure(preemption_enabled=preempt_on,
                      preempt_priority_threshold=50)
    try:
        # a generator, as the pipeline passes it
        assert routes_host((p for p in priorities), min_batch) is want
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


def test_lone_eval_routes_to_host_path():
    """One job registered on an idle broker: placements must NOT go
    through the device batcher."""
    server = make_server()
    try:
        seed_nodes(server)
        batcher = get_batcher()
        before = batcher.batched_requests
        job = mock.job()
        job.task_groups[0].count = 3
        server.job_register(job)
        assert wait_until(
            lambda: len(server.fsm.state.allocs_by_job(job.id)) == 3)
        # Placed by the host pipeline: zero new batcher traffic.
        assert batcher.batched_requests == before
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


def test_dense_min_batch_one_forces_dense():
    """Operators can force the dense path for every eval."""
    server = make_server(dense_min_batch=1)
    try:
        seed_nodes(server)
        batcher = get_batcher()
        before = batcher.batched_requests
        job = mock.job()
        job.task_groups[0].count = 6  # >3: small-K host fallback skipped
        server.job_register(job)
        assert wait_until(
            lambda: len(server.fsm.state.allocs_by_job(job.id)) == 6,
            timeout=60.0,
        )
        assert batcher.batched_requests > before
    finally:
        server.shutdown()
