"""Deterministic chaos soak (nomad_tpu/chaos): a mock 100-node cluster
run under a seeded fault schedule — leader flap mid-batch, worker crash
holding an unacked eval, RPC-delivery drop, forced host-fallback burst
— asserting the recovery invariants after settle:

- every eval reaches a terminal state (exactly once: one eval id, one
  terminal status, no eval stranded pending/unacked);
- no duplicate allocations per (node, task) — reconciliation + the
  plan-queue token guard keep redeliveries from double-placing;
- dense-lane occupancy recovers to the pre-fault level once the fault
  schedule is exhausted;
- the dispatcher thread never stalls (liveness contract read from
  ntalint's NTA_DISPATCHER_ENTRYPOINTS manifest, proven functionally
  by the post-fault probe storm).

The tier-1 subset runs a fixed seed + bounded schedule; the `slow`
variant widens the storm and the fault budget. Registry determinism
itself (same seed -> identical firing log) is tested directly below.
"""

import time
from collections import Counter

import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import ChaosInjectedError, FaultSpec, chaos
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.worker import DEQUEUE_TIMEOUT
from nomad_tpu.structs import consts

N_NODES = 100


@pytest.fixture(autouse=True)
def _always_disarm():
    """The chaos registry AND the device-path breaker are
    process-global: a schedule leaked past one test would inject
    faults into whatever runs next, and a breaker tripped by one
    soak's injected device faults (per-eval host fallbacks count
    consecutively) host-routes the NEXT soak's dense path through its
    cool-down — that soak's own device fault specs then provably
    never fire and its `unfired` assert trips (the long-standing
    randomized-wide flake signature)."""
    yield
    chaos.disarm()
    from nomad_tpu.admission import get_breaker

    b = get_breaker()
    b.reset()
    b.configure_defaults()


def wait_until(fn, timeout=90.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def make_server(**over):
    defaults = dict(
        num_schedulers=4,
        scheduler_factories={"service": "service-tpu"},
        eval_batch_size=16,
        # Short enough that the soak's worker-crash reclaim settles in
        # seconds; long enough that first-dispatch jit compiles don't
        # spuriously fire it (phase A warms every program).
        eval_nack_timeout=2.0,
        # Headroom over the default 3: injected delivery drops burn
        # leases, and the soak asserts completion, not dead-lettering.
        eval_delivery_limit=8,
    )
    defaults.update(over)
    server = Server(ServerConfig(**defaults))
    server.start()
    return server


def seed_nodes(server, n=N_NODES):
    for _ in range(n):
        node = mock.node()
        node.compute_class()
        server.node_register(node)


def quiesce(server):
    for w in server.workers:
        w.set_pause(True)
    time.sleep(DEQUEUE_TIMEOUT + 0.3)


def run_storm(server, n_jobs, prefix, count=5):
    """Register a storm against paused workers, release, and return the
    jobs; the caller asserts on completion/occupancy."""
    quiesce(server)
    jobs = []
    for i in range(n_jobs):
        job = mock.job()
        job.id = f"{prefix}-{i}"
        job.task_groups[0].count = count  # >3 so the dense path engages
        job.task_groups[0].tasks[0].resources.cpu = 20
        job.task_groups[0].tasks[0].resources.memory_mb = 16
        job.task_groups[0].tasks[0].resources.networks = []
        server.job_register(job)
        jobs.append(job)
    assert wait_until(lambda: server.broker.ready_count() >= n_jobs, 15.0)
    for w in server.workers:
        w.set_pause(False)
    return jobs


def settle(server, jobs, count=5, timeout=120.0):
    """Wait until every job's placements land and the control plane is
    quiet: broker drained, pipeline idle."""
    assert wait_until(
        lambda: all(
            len([a for a in server.fsm.state.allocs_by_job(j.id)
                 if not a.terminal_status()]) == count
            for j in jobs),
        timeout), {
            j.id: len(server.fsm.state.allocs_by_job(j.id)) for j in jobs}
    assert wait_until(
        lambda: (server.broker.ready_count() == 0
                 and server.broker.unacked_count() == 0
                 and server.dispatch.stats()["in_flight"] == 0
                 and server.dispatch.stats()["pending"] == 0),
        timeout), (server.broker.stats(), server.dispatch.stats())


def assert_invariants(server, jobs, count=5):
    state = server.fsm.state
    # Every eval terminal, exactly one terminal status per eval id.
    evals = state.evals()
    non_terminal = [e.id for e in evals if not e.terminal_status()]
    assert not non_terminal, non_terminal
    assert len({e.id for e in evals}) == len(evals)
    # No duplicate (node, task): at most one live alloc per placement
    # name, and per (node, name) — a redelivered eval must reconcile,
    # never double-place.
    live = [a for j in jobs for a in state.allocs_by_job(j.id)
            if not a.terminal_status()]
    by_task = Counter((a.job_id, a.name) for a in live)
    dup_tasks = {k: c for k, c in by_task.items() if c > 1}
    assert not dup_tasks, dup_tasks
    by_node_task = Counter((a.node_id, a.job_id, a.name) for a in live)
    dups = {k: c for k, c in by_node_task.items() if c > 1}
    assert not dups, dups
    assert len(live) == len(jobs) * count


def assert_dispatcher_live(server):
    """ntalint's lock-discipline manifest names the pipeline threads
    that must never block; the soak reuses it as the liveness roster:
    each entrypoint's thread must still be running after the faults."""
    from nomad_tpu.dispatch.pipeline import NTA_DISPATCHER_ENTRYPOINTS

    assert NTA_DISPATCHER_ENTRYPOINTS  # the manifest is the contract
    for entry in NTA_DISPATCHER_ENTRYPOINTS:
        cls_name, _meth = entry.split(".")
        assert cls_name == "DispatchPipeline", entry
        thread = server.dispatch._thread
        assert thread is not None and thread.is_alive(), (
            f"dispatcher thread for {entry} stalled/died")


def _occupancy_delta(before, after):
    batches = after["batches"] - before["batches"]
    dispatched = after["dispatched_evals"] - before["dispatched_evals"]
    return (dispatched / batches) if batches else 0.0


def _run_soak(seed, n_jobs, schedule, flaps=1):
    server = make_server()
    try:
        seed_nodes(server)

        # Phase A (clean): warms every jitted program and provides the
        # pre-fault occupancy baseline.
        jobs_a = run_storm(server, n_jobs, f"clean{seed}")
        settle(server, jobs_a)
        pre = server.dispatch.stats()
        pre_occ = pre["occupancy"]

        # Phase B (faulted): arm the schedule, release a storm, flap
        # leadership mid-batch.
        chaos.arm(seed, schedule)
        jobs_b = run_storm(server, n_jobs, f"chaos{seed}")
        assert wait_until(
            lambda: server.dispatch.stats()["batches"] > pre["batches"],
            30.0)
        for _ in range(flaps):
            server.revoke_leadership()  # drains the pipeline's pending
            time.sleep(0.15)
            server.establish_leadership()  # re-seeds from raft state
        settle(server, jobs_b)
        fired = chaos.firing_log()
        unfired = chaos.unfired()
        chaos.disarm()
        # The schedule must actually have exercised its paths — an
        # unfired spec means the soak proved nothing about that site.
        assert fired, "no faults fired"
        assert not unfired, [s.to_dict() for s in unfired]

        # Phase C (probe): faults gone — occupancy must recover to the
        # pre-fault level and the dispatcher must still be packing.
        mid = server.dispatch.stats()
        jobs_c = run_storm(server, n_jobs, f"probe{seed}")
        settle(server, jobs_c)
        post = server.dispatch.stats()
        probe_batches = post["batches"] - mid["batches"]
        probe_requeues = post["requeues"] - mid["requeues"]
        probe_occ = _occupancy_delta(mid, post)
        # Recovery: the probe storm packs like the pre-fault one — a
        # handful of batches, not per-eval fragments (a wedged
        # accumulator degrades occupancy toward 1). Conflict-requeue
        # follow-up batches are legitimate small batches: discounted.
        assert probe_batches <= 4 + probe_requeues, (pre, mid, post)
        assert probe_occ >= max(pre_occ * 0.5 - probe_requeues, 4.0), (
            pre_occ, probe_occ, probe_requeues)

        assert_invariants(server, jobs_a + jobs_b + jobs_c)
        assert_dispatcher_live(server)
        return fired
    finally:
        chaos.disarm()
        server.shutdown()


def test_chaos_soak_fixed_seed():
    """Tier-1 deterministic subset: fixed seed, bounded schedule —
    delivery drops (the in-process RPC-loss analog), two worker
    crashes holding unacked evals, a forced host-fallback burst, plus
    a leader flap mid-batch driven by the harness."""
    schedule = [
        FaultSpec("broker.deliver", "drop", prob=0.3, count=8),
        FaultSpec("dispatch.finish", "drop", count=2),
        FaultSpec("binpack.device", "error", count=2),
    ]
    fired = _run_soak(seed=1337, n_jobs=12, schedule=schedule)
    # The nack timer reclaimed the crash-held evals (finish_dropped
    # evals still reached terminal state — settle asserted that).
    assert sum(1 for s, _n, kind, _d in fired
               if s == "dispatch.finish" and kind == "drop") == 2


@pytest.mark.slow
def test_chaos_soak_randomized_wide():
    """Full soak: bigger storm, heavier drop rate, injected submit
    failures and nack-timer loss, two leader flaps. Seeded — a failure
    reproduces by rerunning the same seed."""
    schedule = [
        FaultSpec("broker.deliver", "drop", prob=0.3, count=24),
        FaultSpec("dispatch.finish", "drop", count=4),
        FaultSpec("dispatch.submit", "error", count=3),
        FaultSpec("dispatch.launch", "error", count=1),
        # (broker.nack_timer is covered by its unit test: the leader
        # flap flushes the broker, cancelling unack timers — a timer
        # spec here can deterministically never fire.)
        FaultSpec("binpack.device", "error", count=3),
    ]
    _run_soak(seed=20260803, n_jobs=24, schedule=schedule, flaps=2)


# ---------------------------------------------------------------------
# registry determinism + guards


def test_same_seed_produces_identical_firing_log():
    """The acceptance bar: replaying a seed against the same per-site
    call sequence yields an IDENTICAL firing log."""
    schedule = [
        FaultSpec("broker.deliver", "drop", prob=0.4, count=5),
        FaultSpec("transport.send", "drop", prob=0.2),
        FaultSpec("raft.apply", "delay", delay=0.0, prob=0.5, start=3),
    ]

    def drive():
        for i in range(30):
            chaos.fire("broker.deliver", eval_id=f"e{i}")
            chaos.fire("transport.send", peer="p1")
            try:
                chaos.fire("raft.apply", node="n1")
            except ChaosInjectedError:
                pass
        return chaos.firing_log()

    with chaos.armed(42, schedule):
        log1 = drive()
    with chaos.armed(42, [
        FaultSpec("broker.deliver", "drop", prob=0.4, count=5),
        FaultSpec("transport.send", "drop", prob=0.2),
        FaultSpec("raft.apply", "delay", delay=0.0, prob=0.5, start=3),
    ]):
        log2 = drive()
    assert log1 and log1 == log2
    # A different seed diverges (the schedule is probabilistic).
    with chaos.armed(43, schedule):
        log3 = drive()
    assert log3 != log1


def test_unknown_site_is_a_typo_guard():
    with pytest.raises(ValueError, match="unknown chaos site"):
        chaos.arm(1, [FaultSpec("broker.delivr", "drop")])


def test_match_filter_targets_context():
    schedule = [FaultSpec("client.heartbeat", "drop",
                          match={"node": "n-a"})]
    with chaos.armed(5, schedule):
        assert chaos.fire("client.heartbeat", node="n-b") is None
        assert chaos.fire("client.heartbeat", node="n-a") == "drop"


def test_error_kind_raises_with_site_context():
    with chaos.armed(5, [FaultSpec("binpack.device", "error", count=1)]):
        with pytest.raises(ChaosInjectedError) as exc:
            chaos.fire("binpack.device")
        assert exc.value.site == "binpack.device"
        assert chaos.fire("binpack.device") is None  # budget spent


def test_disarmed_fire_is_a_noop():
    assert not chaos.enabled
    before = len(chaos.firing_log())  # prior runs' replay artifact stays
    assert chaos.fire("broker.deliver") is None
    assert len(chaos.firing_log()) == before


# ---------------------------------------------------------------------
# drain-on-leadership-loss: the pipeline's accumulated evals survive


def test_drain_on_leadership_loss_requeues_pending():
    """Leadership loss must hand the pipeline's accumulated evals back:
    drain() nacks them (broker still up at that point in revoke), the
    flush wipes the queues, and re-establishment re-seeds every
    still-pending eval from raft state — nothing is lost with the
    batch, and the stale tokens cannot double-place (plan-queue token
    guard)."""
    server = make_server(num_schedulers=0)
    try:
        # Freeze the dispatcher so submissions stay in the pending list.
        server.dispatch._stop.set()
        with server.dispatch._cond:
            server.dispatch._cond.notify_all()
        if server.dispatch._thread is not None:
            server.dispatch._thread.join(timeout=5.0)

        evs = []
        for _ in range(3):
            ev = mock.eval()
            server.eval_update([ev])
            evs.append(ev)
        assert wait_until(lambda: server.broker.ready_count() == 3, 5.0)
        for _ in range(3):
            got, token = server.broker.dequeue(["service"], timeout=1.0)
            assert got is not None
            server.dispatch.submit(got, token)
        assert server.dispatch.pending_count() == 3

        server.revoke_leadership()
        assert server.dispatch.pending_count() == 0
        assert server.dispatch.stats()["drained"] == 3

        server.establish_leadership()
        # All three evals are still pending in raft state: restored.
        assert wait_until(lambda: server.broker.ready_count() == 3, 5.0)
        assert server.broker.unacked_count() == 0
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# churn-PR sites: registered, deterministic, documented


def test_churn_sites_registered_and_deterministic():
    """drain.mid_migration + preempt.victim_lost are first-class sites:
    arm() accepts them and the same seed reproduces the identical
    firing log (the registry acceptance bar applied to the new rows)."""
    from nomad_tpu.chaos.registry import KNOWN_SITES

    assert "drain.mid_migration" in KNOWN_SITES
    assert "preempt.victim_lost" in KNOWN_SITES

    schedule = [
        FaultSpec("drain.mid_migration", "error", prob=0.5, count=3),
        FaultSpec("preempt.victim_lost", "drop", prob=0.4),
    ]

    def drive():
        for i in range(25):
            try:
                chaos.fire("drain.mid_migration", eval_id=f"e{i}")
            except ChaosInjectedError:
                pass
            chaos.fire("preempt.victim_lost", eval_id=f"e{i}",
                       alloc=f"a{i}")
        return chaos.firing_log()

    with chaos.armed(2026, schedule):
        log1 = drive()
    with chaos.armed(2026, [
        FaultSpec("drain.mid_migration", "error", prob=0.5, count=3),
        FaultSpec("preempt.victim_lost", "drop", prob=0.4),
    ]):
        log2 = drive()
    assert log1 and log1 == log2
    assert {s for s, _n, _k, _d in log1} == {"drain.mid_migration",
                                             "preempt.victim_lost"}


def test_churn_sites_documented_in_failure_model_table():
    """The README Failure-model table carries a row for every new
    churn site (doc drift guard, same shape as the trace stage table
    check)."""
    import os

    readme = open(os.path.join(os.path.dirname(__file__), "..",
                               "README.md")).read()
    for site in ("drain.mid_migration", "preempt.victim_lost"):
        assert f"`{site}`" in readme, site
