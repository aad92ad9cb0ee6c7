"""Unit tests for nomad_tpu/admission: token buckets, the admission
controller's level-driven policy, the pressure monitor, the device-path
circuit breaker, deadline derivation, and the new chaos sites."""

import time
from types import SimpleNamespace

import pytest

from nomad_tpu.admission import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    LEVEL_GREEN,
    LEVEL_RED,
    LEVEL_YELLOW,
    ROUTE_EXEMPT,
    ROUTE_READ,
    ROUTE_WRITE,
    RPC_EXEMPT_KINDS,
    AdmissionController,
    AdmissionRejected,
    CircuitBreaker,
    PressureMonitor,
    TokenBucket,
    classify_http,
    deadline_for,
    get_breaker,
    priority_factor,
)
from nomad_tpu.admission import deadline as deadline_mod
from nomad_tpu.server.config import ServerConfig
from nomad_tpu.structs import Evaluation, consts


@pytest.fixture(autouse=True)
def _reset_global_breaker():
    """The breaker is process-global (it guards the one shared device
    path); a tripped state leaked from one test would reroute the
    next test's dense dispatches."""
    yield
    get_breaker().reset()
    get_breaker().configure(failure_threshold=5, slow_ms=0.0,
                            slow_batches=8, cooldown=5.0, enabled=True)


def stub_server(cfg=None, ready=0, unacked=0, blocked=0, shed=0,
                expired=0, in_flight=0, pending=0, max_batch=64,
                max_inflight=2, dispatch_enabled=True,
                ready_by_queue=None, slots=None):
    cfg = cfg or ServerConfig()
    if ready_by_queue is None:
        # Default: all ready depth on the 'service' queue.
        ready_by_queue = {"service": ready} if ready else {}
    broker = SimpleNamespace(stats=lambda: {
        "ready_by_queue": dict(ready_by_queue),
        "total_ready": ready, "total_unacked": unacked,
        "total_blocked": blocked, "total_waiting": 0,
        "dead_lettered": 0, "shed": shed, "expired": expired,
    })
    stats = {"enabled": dispatch_enabled, "in_flight": in_flight,
             "pending": pending, "max_batch": max_batch}
    if slots is not None:
        stats["slots"] = slots
    dispatch = SimpleNamespace(stats=lambda: dict(stats),
                               max_inflight=max_inflight)
    return SimpleNamespace(config=cfg, broker=broker, dispatch=dispatch)


# ---------------------------------------------------------------- bucket


def test_token_bucket_burst_then_deficit_hint():
    b = TokenBucket(rate=10.0, burst=2.0)
    ok1, _ = b.try_acquire()
    ok2, _ = b.try_acquire()
    assert ok1 and ok2
    ok3, retry = b.try_acquire()
    assert not ok3
    assert 0.0 < retry <= 0.2  # ~1 token deficit at 10/s
    st = b.stats()
    assert st["granted"] == 2 and st["rejected"] == 1


def test_token_bucket_refills_at_rate():
    b = TokenBucket(rate=100.0, burst=1.0)
    assert b.try_acquire()[0]
    assert not b.try_acquire()[0]
    time.sleep(0.05)  # 100/s refills a full token in 10ms
    assert b.try_acquire()[0]


def test_token_bucket_zero_rate_never_grants_after_burst():
    b = TokenBucket(rate=0.0, burst=1.0)
    assert b.try_acquire()[0]
    ok, retry = b.try_acquire()
    assert not ok and retry > 0


# --------------------------------------------------------------- breaker


def test_breaker_trips_after_k_consecutive_failures_only():
    br = CircuitBreaker(failure_threshold=3, cooldown=60.0)
    br.record_failure()
    br.record_failure()
    br.record_success()  # resets the consecutive count
    br.record_failure()
    br.record_failure()
    assert br.state() == BREAKER_CLOSED
    br.record_failure()
    assert br.state() == BREAKER_OPEN
    assert br.stats()["trips"] == 1
    assert not br.acquire()
    assert br.should_route_host()


def test_breaker_cooldown_half_open_single_probe_then_reclose():
    br = CircuitBreaker(failure_threshold=1, cooldown=0.05)
    br.record_failure()
    assert br.state() == BREAKER_OPEN
    assert not br.acquire()
    time.sleep(0.08)
    # Cool-down elapsed: routing hint goes quiet so traffic reaches
    # the gate, and the FIRST acquire becomes the half-open probe.
    assert not br.should_route_host()
    assert br.acquire()
    assert br.state() == BREAKER_HALF_OPEN
    assert not br.acquire()  # one probe at a time
    br.record_success(duration_ms=1.0)
    assert br.state() == BREAKER_CLOSED
    st = br.stats()
    assert st["half_opens"] == 1 and st["recloses"] == 1
    seq = [(a, b) for (_t, a, b) in br.transitions()]
    assert seq == [
        (BREAKER_CLOSED, BREAKER_OPEN),
        (BREAKER_OPEN, BREAKER_HALF_OPEN),
        (BREAKER_HALF_OPEN, BREAKER_CLOSED),
    ]


def test_breaker_probe_failure_reopens():
    br = CircuitBreaker(failure_threshold=1, cooldown=0.03)
    br.record_failure()
    time.sleep(0.05)
    assert br.acquire()  # probe
    br.record_failure()
    assert br.state() == BREAKER_OPEN
    assert br.stats()["trips"] == 2
    assert not br.acquire()  # cool-down re-armed


def test_breaker_slow_probe_reopens():
    br = CircuitBreaker(failure_threshold=1, cooldown=0.03, slow_ms=10.0)
    br.record_failure()
    time.sleep(0.05)
    assert br.acquire()
    br.record_success(duration_ms=500.0)  # answered, but at 50x budget
    assert br.state() == BREAKER_OPEN


def test_breaker_consecutive_slow_batches_trip():
    br = CircuitBreaker(failure_threshold=99, slow_ms=10.0,
                        slow_batches=2, cooldown=60.0)
    br.record_success(duration_ms=50.0)
    br.record_success(duration_ms=1.0)  # fast success resets
    br.record_success(duration_ms=50.0)
    assert br.state() == BREAKER_CLOSED
    br.record_success(duration_ms=50.0)
    assert br.state() == BREAKER_OPEN


def test_breaker_disabled_is_transparent():
    br = CircuitBreaker(failure_threshold=1, enabled=False)
    br.record_failure()
    br.record_failure()
    assert br.acquire()
    assert br.state() == BREAKER_CLOSED
    assert not br.should_route_host()


# ------------------------------------------------------------- classify


def test_classify_http_route_classes():
    assert classify_http("POST", "/v1/internal/eval/ack") == ROUTE_EXEMPT
    assert classify_http("GET", "/v1/agent/self") == ROUTE_EXEMPT
    assert classify_http("GET", "/v1/metrics") == ROUTE_EXEMPT
    assert classify_http("GET", "/v1/status/leader") == ROUTE_EXEMPT
    # Client control traffic: shedding heartbeats would turn overload
    # into node-down cascades.
    assert classify_http(
        "PUT", "/v1/node/n1/heartbeat", "node_heartbeat") == ROUTE_EXEMPT
    assert classify_http(
        "POST", "/v1/node/n1/allocs", "node_update_allocs") == ROUTE_EXEMPT
    assert classify_http("PUT", "/v1/jobs", "jobs") == ROUTE_WRITE
    assert classify_http("DELETE", "/v1/job/x", "job") == ROUTE_WRITE
    assert classify_http("GET", "/v1/jobs", "jobs") == ROUTE_READ
    assert classify_http("GET", "/v1/allocations") == ROUTE_READ


# ------------------------------------------------------------- pressure


def test_pressure_green_when_quiet():
    mon = PressureMonitor(stub_server(), ServerConfig())
    snap = mon.snapshot(refresh=True)
    assert snap["level"] == LEVEL_GREEN
    assert snap["reasons"] == []


def test_pressure_absolute_depth_thresholds_when_uncapped():
    cfg = ServerConfig(admission_depth_yellow=10, admission_depth_red=20)
    mon = PressureMonitor(stub_server(cfg, ready=8, unacked=3), cfg)
    assert mon.snapshot(refresh=True)["level"] == LEVEL_YELLOW
    mon = PressureMonitor(stub_server(cfg, ready=18, unacked=3), cfg)
    snap = mon.snapshot(refresh=True)
    assert snap["level"] == LEVEL_RED
    assert any("depth" in r for r in snap["reasons"])


def test_pressure_capped_queues_use_cap_fractions():
    cfg = ServerConfig(eval_ready_cap=100)
    # 4 enabled schedulers x 100 = 400 total budget; 300/400 = 75%.
    mon = PressureMonitor(stub_server(cfg, ready=300), cfg)
    assert mon.snapshot(refresh=True)["level"] == LEVEL_YELLOW
    mon = PressureMonitor(stub_server(cfg, ready=395), cfg)
    assert mon.snapshot(refresh=True)["level"] == LEVEL_RED


def test_pressure_uncapped_backlog_is_not_cap_pressure():
    """Backlog on a deliberately-UNCAPPED queue must not read as
    pressure against another queue's cap (it used to: total ready
    across all queues was divided by only the capped budget, so 500
    batch evals drove a false red that shed healthy service traffic).
    It is still visible — through the absolute depth thresholds."""
    cfg = ServerConfig(eval_ready_cap=0, eval_ready_caps={"service": 100})
    mon = PressureMonitor(
        stub_server(cfg, ready=500, ready_by_queue={"batch": 500}), cfg)
    snap = mon.snapshot(refresh=True)
    assert not any("of cap" in r for r in snap["reasons"]), snap
    # Defaults: depth_yellow=256 — the backlog reads as absolute depth.
    assert snap["level"] == LEVEL_YELLOW
    assert any("broker depth" in r for r in snap["reasons"])
    assert snap["inputs"]["ready_capped"] == 0
    # The capped queue itself still drives the fraction.
    mon = PressureMonitor(
        stub_server(cfg, ready=99, ready_by_queue={"service": 99}), cfg)
    snap = mon.snapshot(refresh=True)
    assert snap["level"] == LEVEL_RED
    assert any("of cap" in r for r in snap["reasons"])


def test_pressure_blocked_and_unacked_count_toward_absolute_depth():
    cfg = ServerConfig(admission_depth_yellow=10, admission_depth_red=20)
    mon = PressureMonitor(stub_server(cfg, unacked=6, blocked=6), cfg)
    snap = mon.snapshot(refresh=True)
    assert snap["level"] == LEVEL_YELLOW
    assert snap["inputs"]["blocked"] == 6


def test_pressure_dispatch_saturation():
    cfg = ServerConfig()
    mon = PressureMonitor(
        stub_server(cfg, in_flight=2, pending=64, max_batch=64,
                    max_inflight=2), cfg)
    assert mon.snapshot(refresh=True)["level"] == LEVEL_YELLOW
    mon = PressureMonitor(
        stub_server(cfg, in_flight=2, pending=128, max_batch=64,
                    max_inflight=2), cfg)
    assert mon.snapshot(refresh=True)["level"] == LEVEL_RED


@pytest.mark.parametrize("slots, level", [(1, LEVEL_YELLOW),
                                          (2, LEVEL_GREEN)])
def test_pressure_dispatch_saturation_counts_the_slots_in_use(slots, level):
    """While the pipeline sends batches through one at a time (after a
    plan conflict), ONE batch in flight with a full batch pending is
    all it can do: saturated, as two are when slots are shared."""
    cfg = ServerConfig()
    mon = PressureMonitor(
        stub_server(cfg, in_flight=1, pending=64, max_batch=64,
                    max_inflight=2, slots=slots), cfg)
    assert mon.snapshot(refresh=True)["level"] == level


def test_pressure_e2e_p99_input(monkeypatch):
    from nomad_tpu.trace.recorder import FlightRecorder

    monkeypatch.setattr(FlightRecorder, "e2e_p99", lambda self: 900.0)
    cfg = ServerConfig(admission_p99_yellow_ms=500.0,
                       admission_p99_red_ms=2000.0)
    mon = PressureMonitor(stub_server(cfg), cfg)
    snap = mon.snapshot(refresh=True)
    assert snap["level"] == LEVEL_YELLOW
    assert any("p99" in r for r in snap["reasons"])
    assert snap["inputs"]["e2e_p99_ms"] == 900.0


# ------------------------------------------------------------ controller


def make_controller(**cfg_over):
    cfg = ServerConfig(**cfg_over)
    return AdmissionController(stub_server(cfg), cfg)


def test_controller_green_admits_everything():
    ctl = make_controller(admission_write_rate=0.0,
                          admission_write_burst=0.0)
    ctl.check_http("PUT", "/v1/jobs", "jobs")  # no raise even at 0 rate
    ctl.check_rpc("bulk_query")


def test_controller_yellow_rate_limits_writes_429():
    ctl = make_controller(admission_write_rate=100.0,
                          admission_write_burst=1.0)
    ctl.force_level(LEVEL_YELLOW)
    ctl.check_http("PUT", "/v1/jobs", "jobs")  # burst token
    with pytest.raises(AdmissionRejected) as exc:
        ctl.check_http("PUT", "/v1/jobs", "jobs")
    assert exc.value.status == 429
    assert exc.value.retry_after > 0
    # Reads pass under yellow.
    ctl.check_http("GET", "/v1/jobs", "jobs")
    assert ctl.snapshot()["http_rejected"] == 1


def test_controller_red_sheds_writes_503_limits_reads():
    ctl = make_controller(admission_read_rate=100.0,
                          admission_read_burst=1.0,
                          admission_red_retry_after=2.5)
    ctl.force_level(LEVEL_RED)
    with pytest.raises(AdmissionRejected) as exc:
        ctl.check_http("POST", "/v1/jobs", "jobs")
    assert exc.value.status == 503
    assert exc.value.retry_after == 2.5
    ctl.check_http("GET", "/v1/jobs", "jobs")  # read burst token
    with pytest.raises(AdmissionRejected) as exc:
        ctl.check_http("GET", "/v1/jobs", "jobs")
    assert exc.value.status == 429


def test_controller_red_degrades_reads_to_stale_with_replica():
    """With replica state on hand, an over-budget red read degrades to
    the 'stale' verdict (serve local replica) instead of a 429; the
    stub-server path without an fsm keeps the old 429 behavior."""
    cfg = ServerConfig(admission_read_rate=100.0,
                       admission_read_burst=1.0)
    server = stub_server(cfg)
    server.fsm = SimpleNamespace(
        state=SimpleNamespace(latest_index=lambda: 7))
    ctl = AdmissionController(server, cfg)
    ctl.force_level(LEVEL_RED)
    assert ctl.check_http("GET", "/v1/jobs", "jobs") is None  # burst token
    assert ctl.check_http("GET", "/v1/jobs", "jobs") == "stale"
    # No replica yet (index 0) → the 429 path stands.
    server.fsm.state = SimpleNamespace(latest_index=lambda: 0)
    with pytest.raises(AdmissionRejected) as exc:
        ctl.check_http("GET", "/v1/jobs", "jobs")
    assert exc.value.status == 429


def test_controller_exemptions_hold_under_red():
    ctl = make_controller()
    ctl.force_level(LEVEL_RED)
    ctl.check_http("POST", "/v1/internal/plan/submit", "internal_plan_submit")
    ctl.check_http("PUT", "/v1/node/n/heartbeat", "node_heartbeat")
    ctl.check_http("GET", "/v1/metrics", "metrics")
    for kind in sorted(RPC_EXEMPT_KINDS):
        ctl.check_rpc(kind)
    with pytest.raises(AdmissionRejected) as exc:
        ctl.check_rpc("bulk_query")
    assert exc.value.status == 503


def test_controller_disabled_is_transparent():
    ctl = make_controller(admission_enabled=False)
    ctl.force_level(LEVEL_RED)
    ctl.check_http("PUT", "/v1/jobs", "jobs")
    ctl.check_rpc("bulk_query")


# -------------------------------------------------------------- deadline


def test_deadline_priority_scaling():
    assert priority_factor(consts.JOB_DEFAULT_PRIORITY) == 1.0
    assert priority_factor(100) == 1.5
    assert priority_factor(consts.CORE_JOB_PRIORITY) == 2.5
    assert priority_factor(-1000) == 0.25  # floor
    now = 1000.0
    assert deadline_for(50, 30.0, now) == pytest.approx(1030.0)
    assert deadline_for(100, 30.0, now) == pytest.approx(1045.0)
    assert deadline_for(50, 0.0, now) == 0.0  # disabled


def test_deadline_stamp_semantics():
    now = 5000.0
    ev = Evaluation(id="e1", priority=50,
                    status=consts.EVAL_STATUS_PENDING)
    deadline_mod.stamp(ev, 30.0, now)
    assert ev.deadline == pytest.approx(5030.0)
    # Idempotent: a re-commit through the funnel keeps the original.
    deadline_mod.stamp(ev, 99.0, now + 100)
    assert ev.deadline == pytest.approx(5030.0)
    # Terminal evals are never stamped.
    done = Evaluation(id="e2", priority=50,
                      status=consts.EVAL_STATUS_COMPLETE)
    deadline_mod.stamp(done, 30.0, now)
    assert done.deadline == 0.0
    assert not ev.expired(now + 10)
    assert ev.expired(now + 31)


def test_server_eval_update_stamps_fresh_pending_evals():
    from nomad_tpu.server import Server, ServerConfig as SC

    server = Server(SC(num_schedulers=0, eval_deadline_ttl=30.0))
    server.start()
    try:
        ev = Evaluation(id="stamped", priority=50, type="service",
                        job_id="j1", status=consts.EVAL_STATUS_PENDING)
        before = time.time()
        server.eval_update([ev])
        stored = server.fsm.state.eval_by_id("stamped")
        assert stored.deadline == pytest.approx(before + 30.0, abs=2.0)
    finally:
        server.shutdown()


# ------------------------------------------------------------ chaos sites


def test_new_chaos_sites_are_known_and_fire():
    from nomad_tpu.chaos import ChaosInjectedError, FaultSpec, chaos

    schedule = [
        FaultSpec("admission.slow_consumer", "delay", delay=0.0, count=1),
        FaultSpec("device.breaker_trip", "error", count=1),
    ]
    with chaos.armed(11, schedule):
        assert chaos.fire("admission.slow_consumer", eval_id="e") == "delay"
        with pytest.raises(ChaosInjectedError) as exc:
            chaos.fire("device.breaker_trip", eval_id="e")
        assert exc.value.site == "device.breaker_trip"
        log = chaos.firing_log()
    assert {s for s, _n, _k, _d in log} == {
        "admission.slow_consumer", "device.breaker_trip"}


# ----------------------------------------------------- server stats surface


def test_server_stats_expose_admission_surface():
    from nomad_tpu.server import Server, ServerConfig as SC

    server = Server(SC(num_schedulers=0))
    server.start()
    try:
        adm = server.stats()["admission"]
        assert adm["enabled"] is True
        assert adm["pressure"]["level"] == LEVEL_GREEN
        assert "write_bucket" in adm and "read_bucket" in adm
        assert adm["breaker"]["state"] == BREAKER_CLOSED
        broker_stats = server.stats()["broker"]
        assert broker_stats["shed"] == 0 and broker_stats["expired"] == 0
    finally:
        server.shutdown()
