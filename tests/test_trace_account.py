"""The account the flight recorder keeps of an eval's life (self time,
uncovered time, e2e as a stage), the spans where requests wait, the
device's idle time by cause, and the `nomad.*` annotations that put the
host on the device trace's clock."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from nomad_tpu import mock, trace
from nomad_tpu.scheduler import batcher as batcher_mod
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.trace.recorder import FlightRecorder, _account, _union_ms
from nomad_tpu.utils.metrics import hist_percentile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = os.path.join(REPO, "benchmark", "tests", "data",
                     "tpu_probe.xplane.pb")


def wait_until(fn, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------
# the account, on hand-built trees


@pytest.mark.parametrize("intervals, lo, hi, want_ms", [
    ([(0.0, 1.0), (2.0, 3.0)], 0.0, 10.0, 2000.0),          # apart
    ([(0.0, 2.0), (1.0, 3.0)], 0.0, 10.0, 3000.0),          # overlapping
    ([(0.0, 5.0), (1.0, 2.0), (3.0, 4.0)], 0.0, 10.0, 5000.0),  # nested
    ([(0.0, 2.0), (1.0, 3.0)], 0.5, 2.5, 2000.0),           # clipped
    ([], 0.0, 1.0, 0.0),
])
def test_union_counts_overlap_once(intervals, lo, hi, want_ms):
    assert _union_ms(intervals, lo, hi) == pytest.approx(want_ms)


def test_self_time_is_duration_minus_union_of_children():
    """Two children that overlap by 10 ms: the parent's self time is its
    duration less the 40 ms they cover together, not less their sum."""
    spans = [("scheduler.process", 0.000, 0.100, None),
             ("matrix.build", 0.010, 0.040, None),
             ("device.dispatch", 0.030, 0.050, None)]
    rows, uncovered_ms = _account(spans, [None, 0, 0], 0.0, 0.100)
    assert rows[0] == ("scheduler.process", pytest.approx(60.0), True)
    assert rows[1] == ("matrix.build", pytest.approx(30.0), False)
    assert rows[2] == ("device.dispatch", pytest.approx(20.0), False)
    assert uncovered_ms == pytest.approx(0.0)


def build_trace(recorder, eval_id, base):
    """broker.wait [0,5], nothing [5,10], scheduler.process [10,100]
    holding matrix.build [20,40] and device.dispatch [30,60] (they
    overlap), then complete() some time later."""
    ms = 1e-3
    recorder.record_span(eval_id, "broker.wait", base, base + 5 * ms)
    recorder.record_span(eval_id, "scheduler.process", base + 10 * ms,
                         base + 100 * ms)
    recorder.record_span(eval_id, "matrix.build", base + 20 * ms,
                         base + 40 * ms)
    recorder.record_span(eval_id, "device.dispatch", base + 30 * ms,
                         base + 60 * ms)
    recorder.complete(eval_id)
    return recorder.trace_for(eval_id)


def test_uncovered_and_self_of_a_hand_built_trace():
    recorder = FlightRecorder()
    done = build_trace(recorder, "e1", time.monotonic() - 0.2)
    by_name = {s["name"]: s for s in done["spans"]}
    assert by_name["scheduler.process"]["self_ms"] == pytest.approx(50.0,
                                                                    abs=0.01)
    assert "self_ms" not in by_name["matrix.build"]  # childless
    # e2e is origin to complete(): all of it but the 95 ms under a span
    assert done["uncovered_ms"] == pytest.approx(
        done["duration_ms"] - 95.0, abs=0.01)
    stats = recorder.stage_stats()
    assert stats["scheduler.process.self"]["count"] == 1
    assert stats["scheduler.process.self"]["mean_ms"] == pytest.approx(
        50.0, abs=0.01)
    assert stats[trace.STAGE_EVAL_UNCOVERED]["mean_ms"] == pytest.approx(
        done["uncovered_ms"], abs=0.01)
    assert "matrix.build.self" not in stats
    assert "broker.wait.self" not in stats


def test_childless_instance_of_a_parent_stage_still_feeds_self():
    """Once a stage has had children its `.self` histogram takes every
    instance, so its mass is the stage's exclusive time."""
    recorder = FlightRecorder()
    build_trace(recorder, "e1", time.monotonic() - 0.2)
    base = time.monotonic() - 0.1
    recorder.record_span("e2", "scheduler.process", base, base + 0.030)
    recorder.complete("e2")
    stats = recorder.stage_stats()
    assert stats["scheduler.process.self"]["count"] == 2
    assert stats["scheduler.process.self"]["max_ms"] == pytest.approx(
        50.0, abs=0.01)
    assert stats["scheduler.process.self"]["mean_ms"] == pytest.approx(
        40.0, abs=0.01)


def test_stage_buckets_of_e2e():
    recorder = FlightRecorder()
    assert recorder.stage_buckets("e2e") is None
    build_trace(recorder, "e1", time.monotonic() - 0.2)
    count, buckets = recorder.stage_buckets("e2e")
    assert count == 1 and sum(buckets) == 1
    assert hist_percentile(buckets, count, 0.5) == pytest.approx(
        recorder.stage_stats()["e2e"]["p50_ms"], abs=0.001)
    # a copy, as for every other stage
    buckets[0] += 5
    assert sum(recorder.stage_buckets("e2e")[1]) == 1


def test_complete_feeds_the_account_in_the_stripes_critical_section():
    """The stage table lives in the stripes: complete() takes the
    trace's stripe lock twice (finalize with the account, publish) and
    the tail lock once (e2e, tail ring), and no other lock; a record
    call takes the stripe lock once."""
    recorder = FlightRecorder()

    class Counting:
        def __init__(self, lock):
            self.lock, self.entered = lock, 0

        def __enter__(self):
            self.entered += 1
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    stripe = recorder._stripe_for("e1")
    locks = [Counting(st.lock) for st in recorder._stripes]
    for st, lock in zip(recorder._stripes, locks):
        st.lock = lock
    tail = recorder._tail_lock = Counting(recorder._tail_lock)
    base = time.monotonic() - 0.2
    recorder.record_span("e1", "scheduler.process", base, base + 0.1)
    assert stripe.lock.entered == 1
    recorder.record_span("e1", "plan.submit", base + 0.01, base + 0.05)
    recorder.record_span("e1", "plan.commit", base + 0.02, base + 0.04)
    recorder.record_span("e1", "fsm.alloc_upsert", base + 0.025,
                         base + 0.03)
    before = stripe.lock.entered
    recorder.complete("e1")
    assert stripe.lock.entered - before == 2 and tail.entered == 1
    assert sum(lock.entered for lock in locks) == stripe.lock.entered
    stats = recorder.stage_stats()
    for stage in ("scheduler.process.self", "plan.submit.self",
                  "plan.commit.self", trace.STAGE_EVAL_UNCOVERED, "e2e"):
        assert stats[stage]["count"] == 1, stage


def test_stage_table_merges_the_stripes():
    """Samples of one stage land in the stripes of their evals and read
    back as one row; a span past SPAN_CAP still counts in it."""
    from nomad_tpu.trace.recorder import N_STRIPES, SPAN_CAP

    recorder = FlightRecorder()
    base = time.monotonic() - 1.0
    evals = [f"eval-{i}" for i in range(4 * N_STRIPES)]
    assert len({id(recorder._stripe_for(e)) for e in evals}) > 1
    for i, eval_id in enumerate(evals):
        recorder.record_span(eval_id, "matrix.build", base,
                             base + 0.001 * (i + 1))
    recorder.observe_stage("device.idle.no_work", 5.0)
    count, buckets = recorder.stage_buckets("matrix.build")
    assert count == len(evals) == sum(buckets)
    stats = recorder.stage_stats()
    assert stats["matrix.build"]["count"] == len(evals)
    assert stats["matrix.build"]["max_ms"] == pytest.approx(
        len(evals), abs=0.01)
    assert stats["device.idle.no_work"]["count"] == 1
    for i in range(SPAN_CAP + 3):
        recorder.record_span("eval-0", "plan.evaluate", base, base + 0.001)
    assert recorder.stage_stats()["plan.evaluate"]["count"] == SPAN_CAP + 3
    recorder.reset()
    assert recorder.stage_stats() == {}
    assert recorder.stage_buckets("matrix.build") is None


def test_new_stage_names_are_stages():
    for stage in (trace.STAGE_API_REGISTER, trace.STAGE_DISPATCH_POOL_WAIT,
                  trace.STAGE_PLAN_QUEUE_WAIT, trace.STAGE_EVAL_UPDATE):
        assert stage in trace.ALL_STAGES
    from nomad_tpu.trace.recorder import MAX_STAGES

    # every eval stage with a `.self` twin, the derived and the
    # observe_stage ones (`read.park` and `read.serve` among the
    # client's path since PR 40), still fit the table
    assert (2 * len(trace.ALL_STAGES) + 1 + len(trace.DEVICE_IDLE_STAGES)
            + len(trace.CLIENT_PATH_STAGES)) <= MAX_STAGES


# ---------------------------------------------------------------------
# plans that ride in one group of the applier


def test_account_of_an_eval_whose_plan_rode_in_a_group():
    """Four plans are queued when the applier looks: one group, one
    raft entry. Each eval's trace holds the applier's four spans once,
    `plan.queue_wait` ends where its own `plan.evaluate` starts (so a
    plan's wait covers the verification of those ahead), `plan.commit` and
    `fsm.alloc_upsert` are the group's one apply in every trace, and
    self times plus uncovered still come to the trace's duration."""
    from nomad_tpu.server.fsm import FSM, DevLog
    from nomad_tpu.server.plan_apply import PlanApplier
    from nomad_tpu.server.plan_queue import PlanQueue
    from nomad_tpu.structs import Allocation, Plan, Resources, consts
    from nomad_tpu.utils.ids import generate_uuid

    recorder = trace.get_recorder()
    recorder.reset()
    fsm = FSM()
    log = DevLog(fsm)
    nodes = [mock.node() for _ in range(4)]
    for node in nodes:
        log.apply("node_register", {"node": node})
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, fsm, log)
    evals, pendings, starts = [], [], []
    for node in nodes:
        job = mock.job()
        eval_id = generate_uuid()
        plan = Plan(job=job, eval_id=eval_id)
        plan.append_alloc(Allocation(
            id=generate_uuid(), eval_id=eval_id, job_id=job.id, job=job,
            node_id=node.id, task_group="web",
            task_resources={"web": Resources(cpu=100, memory_mb=64)},
            desired_status=consts.ALLOC_DESIRED_RUN))
        starts.append(time.monotonic())
        trace.record_span(eval_id, trace.STAGE_BROKER_WAIT, starts[-1])
        evals.append(eval_id)
        pendings.append(queue.enqueue(plan))
    applier.start()
    try:
        for eval_id, pending, start in zip(evals, pendings, starts):
            assert pending.wait(timeout=20.0).alloc_index > 0
            # the worker's side of the submit, as server/worker.py has it
            trace.record_span(eval_id, trace.STAGE_PLAN_SUBMIT, start)
            trace.record_span(eval_id, trace.STAGE_SCHED_PROCESS, start)
            trace.complete(eval_id)
    finally:
        applier.stop()
    assert applier.stats()["largest_group"] == 4
    traces = [recorder.trace_for(e) for e in evals]
    wait_ends = []
    for done, pending in zip(traces, pendings):
        by_name = {}
        for span in done["spans"]:
            by_name.setdefault(span["name"], []).append(span)
        for stage in (trace.STAGE_PLAN_QUEUE_WAIT, trace.STAGE_PLAN_EVALUATE,
                      trace.STAGE_PLAN_COMMIT, trace.STAGE_ALLOC_UPSERT):
            assert len(by_name[stage]) == 1, (stage, sorted(by_name))
        wait = by_name[trace.STAGE_PLAN_QUEUE_WAIT][0]
        assert wait["parent"] == trace.STAGE_PLAN_SUBMIT
        assert wait["end_ms"] <= by_name[
            trace.STAGE_PLAN_EVALUATE][0]["start_ms"] + 0.002
        commit = by_name[trace.STAGE_PLAN_COMMIT][0]
        assert commit["annotations"] == {"allocs": 1, "plans": 4}
        assert by_name[trace.STAGE_ALLOC_UPSERT][0]["parent"] == \
            trace.STAGE_PLAN_COMMIT
        wait_ends.append(pending.enqueue_time + wait["duration_ms"] / 1e3)
        accounted = done["uncovered_ms"] + sum(
            s.get("self_ms", s["duration_ms"]) for s in done["spans"])
        assert done["duration_ms"] <= accounted + 0.01
        assert accounted <= done["duration_ms"] * 1.0003 + 0.01, done
    # queue order: each plan's wait ends after that of the plan ahead
    assert wait_ends == sorted(wait_ends)
    recorder.reset()


# ---------------------------------------------------------------------
# a placing eval through HTTP and the pipeline


def seed_nodes(server, n=8):
    for _ in range(n):
        node = mock.node()
        node.compute_class()
        server.node_register(node)


def dense_job():
    job = mock.job()
    job.task_groups[0].count = 5  # >3, so the dense path engages
    job.task_groups[0].tasks[0].resources.cpu = 20
    job.task_groups[0].tasks[0].resources.memory_mb = 16
    return job


@pytest.fixture(scope="module")
def storm(tmp_path_factory):
    """Eight jobs registered over HTTP at once against a dense server,
    twice (the second wave has a last dispatch to measure its idle gap
    from), under a profile on the CPU backend. Yields the finished
    traces, the recorder's table and the profile's directory."""
    import jax
    from concurrent.futures import ThreadPoolExecutor

    from nomad_tpu.api import Client, HTTPServer

    recorder = trace.get_recorder()
    recorder.reset()
    batcher_mod.get_batcher()._busy_until = 0.0
    server = Server(ServerConfig(
        num_schedulers=2, scheduler_factories={"service": "service-tpu"},
        eval_batch_size=16, eval_nack_timeout=60.0))
    server.start()
    http = HTTPServer(server)
    http.start()
    profile_dir = str(tmp_path_factory.mktemp("profile"))
    try:
        seed_nodes(server, 8)

        def wave():
            jobs = [dense_job() for _ in range(8)]
            with ThreadPoolExecutor(8) as pool:
                evals = list(pool.map(
                    lambda job: Client(http.addr, timeout=30.0)
                    .jobs.register(job), jobs))
            assert wait_until(lambda: all(
                len(server.fsm.state.allocs_by_job(j.id)) == 5
                for j in jobs), timeout=120.0)
            assert wait_until(lambda: all(
                recorder.trace_for(e) is not None for e in evals), 20.0)
            return evals

        wave()  # compiles
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # what the benchmark takes on the chip
        jax.profiler.start_trace(profile_dir, profiler_options=options)
        try:
            evals = wave()
        finally:
            jax.profiler.stop_trace()
        yield {"traces": [recorder.trace_for(e) for e in evals],
               "stages": recorder.stage_stats(),
               "batcher": batcher_mod.get_batcher().stats(),
               "profile_dir": profile_dir}
    finally:
        http.stop()
        server.shutdown()


PARENTS = {
    trace.STAGE_API_REGISTER: (None,),
    trace.STAGE_DISPATCH_POOL_WAIT: (None,),
    trace.STAGE_PLAN_QUEUE_WAIT: (trace.STAGE_PLAN_SUBMIT,),
    trace.STAGE_EVAL_UPDATE: (trace.STAGE_SCHED_PROCESS,),
}


@pytest.mark.parametrize("stage", sorted(PARENTS))
def test_placing_eval_carries_the_new_span(storm, stage):
    dense = [t for t in storm["traces"]
             if any(s["name"] == trace.STAGE_DEVICE_DISPATCH
                    for s in t["spans"])]
    assert dense, "no eval of the storm took the dense path"
    for done in dense:
        spans = [s for s in done["spans"] if s["name"] == stage]
        assert spans, (stage, [s["name"] for s in done["spans"]])
        for span in spans:
            assert span["parent"] in PARENTS[stage], span


def test_pool_wait_lies_between_launch_and_process(storm):
    for done in storm["traces"]:
        by_name = {s["name"]: s for s in done["spans"]}
        if trace.STAGE_DISPATCH_POOL_WAIT not in by_name:
            continue
        wait = by_name[trace.STAGE_DISPATCH_POOL_WAIT]
        assert wait["start_ms"] == pytest.approx(
            by_name[trace.STAGE_DISPATCH_LAUNCH]["end_ms"], abs=0.002)
        assert wait["end_ms"] <= by_name[
            trace.STAGE_SCHED_PROCESS]["start_ms"] + 0.002


def test_e2e_starts_at_the_request(storm):
    for done in storm["traces"]:
        first = min(done["spans"], key=lambda s: s["start_ms"])
        assert first["name"] == trace.STAGE_API_REGISTER
        assert first["start_ms"] == 0.0


def test_uncovered_is_under_a_twentieth_of_e2e(storm):
    shares = [t["uncovered_ms"] / t["duration_ms"] for t in storm["traces"]]
    assert max(shares) < 0.05, shares
    stages = storm["stages"]
    assert stages[trace.STAGE_EVAL_UNCOVERED]["count"] == \
        stages["e2e"]["count"]
    for parent in (trace.STAGE_SCHED_PROCESS, trace.STAGE_PLAN_SUBMIT,
                   trace.STAGE_DEVICE_DISPATCH, trace.STAGE_PLAN_COMMIT):
        assert stages[parent + trace.SELF_SUFFIX]["count"] > 0, parent


def test_device_solve_is_on_the_recorders_clock(storm):
    """Both ends of device.solve are monotonic() instants taken where
    the issue and the results happened, so it lies inside its
    device.dispatch with no slack to allow for."""
    for done in storm["traces"]:
        by_name = {s["name"]: s for s in done["spans"]}
        if trace.STAGE_DEVICE_SOLVE not in by_name:
            continue
        assert by_name[trace.STAGE_DEVICE_SOLVE]["parent"] == \
            trace.STAGE_DEVICE_DISPATCH


@pytest.mark.parametrize("name", ["nomad.dispatch", "nomad.plan_apply",
                                  "nomad.stack", "nomad.launch_prologue"])
def test_profile_holds_the_host_annotation(storm, name):
    from nomad_tpu.profile.xplane import read_xplane

    _device, annotations = read_xplane(storm["profile_dir"])
    assert name in annotations, sorted(annotations)
    assert all(end > start for start, end in annotations[name])


def test_idle_parts_were_fed_per_dispatch(storm):
    stages = storm["stages"]
    counts = {stages[s]["count"] for s in trace.DEVICE_IDLE_STAGES}
    assert len(counts) == 1 and counts.pop() >= 1
    assert storm["batcher"]["dispatches"] >= 2
    assert "stack_us" not in storm["batcher"]
    assert "upload_us" not in storm["batcher"]


# ---------------------------------------------------------------------
# the device's idle time by cause


@pytest.mark.parametrize("last_end, arrival, closed, issue, want", [
    # the usual order: results, arrival, close, issue
    (10.0, 12.0, 12.5, 12.6, (2.0, 0.5, 0.1)),
    # the request was waiting before the last program ended
    (10.0, 9.0, 10.2, 10.3, (0.0, 0.2, 0.1)),
    # closed before the last program ended too: all of it is stacking
    (10.0, 9.0, 9.5, 10.3, (0.0, 0.0, 0.3)),
    # issued before the last results were back: no gap
    (10.0, 9.0, 9.5, 9.9, (0.0, 0.0, 0.0)),
])
def test_idle_parts_sum_to_the_gap(last_end, arrival, closed, issue, want):
    parts = batcher_mod._idle_parts(last_end, arrival, closed, issue)
    assert parts == pytest.approx(want)
    assert sum(parts) == pytest.approx(max(0.0, issue - last_end))


def solo_request():
    from nomad_tpu.ops.binpack import (PlacementConfig, host_prng_key,
                                       make_asks, make_node_state)

    n, g, k = 128, 1, 4
    state = make_node_state(
        np.full((n, 4), 1000.0, np.float32),
        np.full((n, 4), 1000.0, np.float32),
        np.zeros((n, 4), np.float32), np.full(n, 1000.0, np.float32),
        np.zeros(n, np.float32), np.full(n, 100.0, np.float32),
        np.zeros(n, np.int32), np.zeros((n, g), np.int32),
        np.ones((n, g), bool), np.ones(n, bool))
    asks = make_asks(
        np.full((k, 4), 10.0, np.float32), np.zeros(k, np.float32),
        np.zeros(k, np.float32), np.zeros(k, np.int32), np.ones(k, bool),
        False, np.zeros(g, bool))
    return state, asks, host_prng_key(7), PlacementConfig(10.0)


def test_idle_gap_of_a_dispatch_and_none_while_another_is_in_flight():
    recorder = trace.get_recorder()
    recorder.reset()
    batcher = batcher_mod.PlacementBatcher(window=0.0)
    state, asks, key, config = solo_request()
    batcher.place(state, asks, key, config)  # the first has no gap yet
    assert trace.STAGE_IDLE_NO_WORK not in recorder.stage_stats()
    with batcher._lock:
        first_end = batcher._busy_until
    assert first_end > 0.0 and batcher._in_flight == 0

    time.sleep(0.05)
    batcher.place(state, asks, key, config)
    stats = recorder.stage_stats()
    parts = [stats[s] for s in trace.DEVICE_IDLE_STAGES]
    assert [p["count"] for p in parts] == [1, 1, 1]
    gap_ms = sum(p["mean_ms"] for p in parts)
    assert 50.0 <= gap_ms < 5000.0
    assert parts[0]["mean_ms"] >= 50.0  # nothing was waiting: no_work

    with batcher._lock:
        batcher._in_flight += 1  # a program of another shape key
    try:
        time.sleep(0.02)
        batcher.place(state, asks, key, config)
    finally:
        with batcher._lock:
            batcher._in_flight -= 1
    stats = recorder.stage_stats()
    assert [stats[s]["count"] for s in trace.DEVICE_IDLE_STAGES] == \
        [1, 1, 1]
    assert batcher._in_flight == 0
    recorder.reset()


def test_small_route_host_counts_evals_too():
    """A dense eval of three placements or fewer takes the host
    iterators: counted in allocations and, beside, in evals."""
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs import consts, new_eval
    from nomad_tpu.utils.metrics import get_metrics

    def counted(suffix):
        life = get_metrics().inmem._life.counters
        return sum(c[1] for name, c in list(life.items())
                   if name.endswith(suffix))

    before = (counted("scheduler.small_route_host"),
              counted("scheduler.small_route_host_evals"))
    h = Harness(seed=91)
    for _ in range(6):
        h.state.upsert_node(h.next_index(), mock.node())
    job = mock.job()
    job.task_groups[0].count = 3
    h.state.upsert_job(h.next_index(), job)
    ev = new_eval(job, consts.EVAL_TRIGGER_JOB_REGISTER)
    h.process("service-tpu", ev)
    assert len(h.state.allocs_by_job(job.id)) == 3
    assert counted("scheduler.small_route_host") - before[0] == 3
    assert counted("scheduler.small_route_host_evals") - before[1] == 1


def test_placement_phases_carry_named_scopes():
    """The scopes are metadata on the compiled program's operations
    (`op_name`), which is where a profile with the HLO proto, and
    xprof's `Framework Name Scope` line, take them from."""
    from nomad_tpu.ops.binpack import placement_program_jit

    state, asks, key, config = solo_request()
    compiled = placement_program_jit.lower(
        state, asks, key, config).compile().as_text()
    for scope in ("claim_scan", "score_and_mask", "claim"):
        assert f"/{scope}/" in compiled, scope


# ---------------------------------------------------------------------
# traceconv --xplane


HOST_PLANE = """
planes {
  id: 77
  name: "/host:CPU"
  lines {
    id: 1
    name: "plan-applier/1"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 130000000000 duration_ps: 20000000000 }
    events { metadata_id: 2 offset_ps: 70000000000 duration_ps: 2000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "nomad.plan_apply" } }
  event_metadata { key: 2 value { id: 2 name: "nomad.stack#lanes=3#" } }
}
"""


@pytest.fixture
def probe_with_host_events(tmp_path):
    """The recorded v5e trace, with a host plane added: a
    nomad.plan_apply region over 130-150 ms, where the device sat idle
    between its runs at 121.9 and 153.2 ms, and a short nomad.stack at
    70 ms. Two serialized XSpaces appended are one XSpace with the
    planes of both."""
    from jax.profiler import ProfileData

    extra = ProfileData.text_proto_to_serialized_xspace(HOST_PLANE)
    path = tmp_path / "probe.xplane.pb"
    with open(PROBE, "rb") as f:
        path.write_bytes(f.read() + extra)
    return str(path)


def test_traceconv_xplane_names_a_gap(probe_with_host_events):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "traceconv.py"),
         "--xplane", probe_with_host_events],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout)
    assert out["plane"] == "/device:TPU:0"
    assert out["modules"][0][0] == "jit__lambda" and out["modules"][0][2] == 12
    gaps = dict((name, s) for name, s in reversed(out["idle_gaps"]))
    assert set(gaps) == {"nomad.plan_apply", "nomad.stack",
                         "no host annotation"}
    # the gap the region lies in is one of the ~31 ms ones, named whole
    assert 0.030 < gaps["nomad.plan_apply"] < 0.033
    named = [g for g in out["idle_gaps"] if g[0] == "nomad.plan_apply"]
    assert len(named) == 1
    assert all(len(g) == 2 and g[1] > 0 for g in out["idle_gaps"])
    cover = out["idle_gap_cover"][out["idle_gaps"].index(named[0])]
    assert cover[0][0] == "nomad.plan_apply"
    assert cover[0][1] == pytest.approx(0.020, abs=1e-6)
    by_name = dict((n, s) for n, s in out["idle_by_name"])
    assert sum(by_name.values()) == pytest.approx(
        out["span_s"] - out["busy_s"], rel=1e-6)
    assert [a[0] for a in out["annotations"]] == ["nomad.plan_apply",
                                                  "nomad.stack"]


def test_traceconv_xplane_without_annotations():
    from nomad_tpu.profile.xplane import reduce_xplane

    out = reduce_xplane(PROBE)
    assert out["annotations"] == []
    assert {g[0] for g in out["idle_gaps"]} == {"no host annotation"}
    assert out["idle_gaps"][0][1] > 0.01
    assert out["scopes"] == []  # the probe's programs have no named scope


def test_traceconv_xplane_refuses_what_is_no_profile(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "traceconv.py"),
         "--xplane", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 2
    assert "cannot read" in proc.stderr


@pytest.mark.parametrize("gap, spans, want", [
    ((10.0, 20.0), [(0.0, 12.0), (11.0, 13.0), (19.0, 30.0)], 4.0),
    ((10.0, 20.0), [(0.0, 5.0), (25.0, 30.0)], 0.0),
    ((10.0, 20.0), [(0.0, 30.0)], 10.0),
])
def test_xplane_overlap(gap, spans, want):
    from nomad_tpu.profile.xplane import merged, overlap

    assert overlap(gap, merged(spans)) == pytest.approx(want)
