"""Eval-lifecycle tracing (nomad_tpu/trace): ring-buffer bounds under
concurrent writers, span-tree completeness through the real control
plane, chaos (site, ordinal) annotations landing on the covering span,
tail-keep of past-p99 traces, and the HTTP surfaces
(/v1/agent/trace, /v1/metrics Prometheus exposition)."""

import importlib.util
import json
import os
import re
import sys
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.structs import consts
from nomad_tpu.trace import get_recorder
from nomad_tpu.trace.recorder import (
    ACTIVE_PER_STRIPE,
    FlightRecorder,
    N_STRIPES,
    RING_PER_STRIPE,
    SPAN_CAP,
    TAIL_KEEP,
    TAIL_MIN_SAMPLES,
)
from nomad_tpu.trace.span import (
    LIFECYCLE_CORE_STAGES,
    STAGE_DEVICE_DISPATCH,
    STAGE_DISPATCH_ACCUMULATE,
    STAGE_DISPATCH_LAUNCH,
    STAGE_MATRIX_BUILD,
    STAGE_PLAN_SUBMIT,
)


def wait_until(fn, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


@pytest.fixture(autouse=True)
def fresh_recorder():
    """The recorder is process-global; every test starts it empty and
    enabled."""
    rec = get_recorder()
    rec.reset()
    rec.set_enabled(True)
    yield rec
    rec.reset()


# ---------------------------------------------------------------------
# unit: span trees


def test_span_tree_parents_and_ordering():
    rec = FlightRecorder()
    t0 = time.monotonic()
    rec.record_span("e1", "scheduler.process", t0, t0 + 0.100)
    rec.record_span("e1", "plan.submit", t0 + 0.040, t0 + 0.090)
    rec.record_span("e1", "plan.evaluate", t0 + 0.050, t0 + 0.060)
    rec.record_span("e1", "matrix.build", t0 + 0.010, t0 + 0.020)
    rec.complete("e1")
    tr = rec.trace_for("e1")
    assert tr is not None
    names = [s["name"] for s in tr["spans"]]
    assert names == ["scheduler.process", "matrix.build", "plan.submit",
                     "plan.evaluate"]  # sorted by start
    by_name = {s["name"]: s for s in tr["spans"]}
    assert by_name["scheduler.process"]["parent"] is None
    assert by_name["matrix.build"]["parent"] == "scheduler.process"
    assert by_name["plan.submit"]["parent"] == "scheduler.process"
    assert by_name["plan.evaluate"]["parent"] == "plan.submit"
    for s in tr["spans"]:
        assert s["end_ms"] >= s["start_ms"] >= 0.0
    assert tr["duration_ms"] >= 100.0


def test_trace_id_carried_and_eval_id_fallback():
    rec = FlightRecorder()
    rec.record_span("e1", "x", time.monotonic(), trace_id="tr-42")
    rec.complete("e1")
    assert rec.trace_for("e1")["trace_id"] == "tr-42"
    rec.record_span("e2", "x", time.monotonic())
    rec.complete("e2")
    assert rec.trace_for("e2")["trace_id"] == "e2"


def test_disabled_recorder_records_nothing():
    rec = FlightRecorder()
    rec.set_enabled(False)
    rec.record_span("e1", "x", time.monotonic())
    rec.complete("e1")
    assert rec.traces() == []
    assert rec.stats()["completed"] == 0


# ---------------------------------------------------------------------
# ring buffer: concurrency + bounds


def test_concurrent_writers_no_torn_spans_bounded_memory():
    """Hammer one recorder from many threads: every completed trace
    must read back internally consistent (no torn spans), and every
    storage structure must stay at its cap."""
    rec = FlightRecorder()
    threads = 8
    evals_per_thread = 300
    spans_per_eval = 6
    errors = []

    def writer(tid):
        try:
            for i in range(evals_per_thread):
                eid = f"t{tid}-e{i}"
                t0 = time.monotonic()
                for k in range(spans_per_eval):
                    rec.record_span(eid, f"stage.{k}", t0, t0 + 0.001 * k)
                rec.annotate_fault(eid, "broker.deliver", i, "drop")
                rec.complete(eid)
        except Exception as e:  # noqa: BLE001 - surface in the assert
            errors.append(e)

    ts = [threading.Thread(target=writer, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60.0)
    assert not errors
    stats = rec.stats()
    assert stats["completed"] == threads * evals_per_thread
    assert stats["active"] == 0
    # fixed memory: rings never exceed their preallocated sizes
    for stripe in rec._stripes:
        assert len(stripe.ring) == RING_PER_STRIPE
        assert len(stripe.active) <= ACTIVE_PER_STRIPE
    assert len(rec._tail) == TAIL_KEEP
    # every readable trace is whole: all spans present, none torn
    traces = rec.traces(limit=10_000)
    assert traces
    for tr in traces:
        assert len(tr["spans"]) == spans_per_eval
        for s in tr["spans"]:
            assert s["end_ms"] >= s["start_ms"]
            assert s["name"].startswith("stage.")


def test_span_cap_drops_excess_not_memory():
    rec = FlightRecorder()
    t0 = time.monotonic()
    for i in range(SPAN_CAP + 50):
        rec.record_span("e1", f"s{i}", t0, t0 + 0.001)
    rec.complete("e1")
    tr = rec.trace_for("e1")
    assert len(tr["spans"]) == SPAN_CAP
    assert tr["dropped_spans"] == 50
    assert rec.stats()["dropped_spans"] == 50


def test_active_eviction_is_drop_oldest_not_growth():
    rec = FlightRecorder()
    # All keys on one stripe would need hash control; instead flood all
    # stripes far past the global active cap.
    n = N_STRIPES * ACTIVE_PER_STRIPE * 2
    t0 = time.monotonic()
    for i in range(n):
        rec.record_span(f"e{i}", "x", t0)  # never completed
    total_active = rec.stats()["active"]
    assert total_active <= N_STRIPES * ACTIVE_PER_STRIPE
    assert rec.stats()["evicted_active"] >= n - total_active


def test_tail_keep_catches_past_p99_traces():
    rec = FlightRecorder()
    t0 = time.monotonic()
    # fast herd to establish the rolling e2e distribution
    for i in range(TAIL_MIN_SAMPLES + 20):
        eid = f"fast{i}"
        rec.record_span(eid, "x", t0 - 0.001, t0)
        rec.complete(eid)
    # now a slow outlier: must be tail-kept
    rec.record_span("slow", "x", t0 - 5.0, t0)
    rec.complete("slow")
    tail_ids = [t["eval_id"] for t in rec.tail_traces()]
    assert "slow" in tail_ids
    assert rec.trace_for("slow")["tail_kept"] is True


def test_dead_letter_completes_trace_exactly_once(fresh_recorder):
    """Delivery-limit exhaustion closes the trace as 'dead-letter';
    the failed-queue copy and the reaper's later dequeue+ack must NOT
    open or publish a second trace for the same eval."""
    from nomad_tpu.server.broker import FAILED_QUEUE, EvalBroker

    broker = EvalBroker(nack_timeout=60.0, delivery_limit=1)
    broker.set_enabled(True)
    ev = mock.eval()
    broker.enqueue(ev)
    got, token = broker.dequeue([ev.type], timeout=1.0)
    assert got is not None
    broker.nack(ev.id, token)  # delivery limit 1 -> dead-letters
    rec = fresh_recorder
    tr = rec.trace_for(ev.id)
    assert tr is not None and tr["status"] == "dead-letter"
    # the dead copy sits in the failed queue with NO active trace
    assert broker.failed_evals()
    assert rec.stats()["active"] == 0
    # reaper-style pickup: dequeue from the failed queue and ack
    dead, dtoken = broker.dequeue([FAILED_QUEUE], timeout=1.0)
    assert dead is not None
    broker.ack(dead.id, dtoken)
    # still exactly one completed trace, still the dead-letter one
    assert rec.stats()["completed"] == 1
    assert rec.trace_for(ev.id)["status"] == "dead-letter"


def test_reblock_requeue_starts_fresh_trace_with_broker_wait(
        fresh_recorder):
    """An eval reblocked while outstanding: ack completes the FIRST
    run's trace, and the requeued run re-enters with its own enqueue
    mark so its next dequeue still records broker.wait (completing
    after the re-enqueue used to pop that mark and split the second
    lifecycle)."""
    from nomad_tpu.server.broker import EvalBroker

    broker = EvalBroker(nack_timeout=60.0)
    broker.set_enabled(True)
    ev = mock.eval()
    broker.enqueue(ev)
    got, token = broker.dequeue([ev.type], timeout=1.0)
    assert got is not None
    broker.enqueue(ev, token)  # reblock while outstanding
    broker.ack(ev.id, token)
    rec = fresh_recorder
    assert rec.trace_for(ev.id)["status"] == "acked"
    # the requeued run is live again with a fresh enqueue mark...
    assert rec.stats()["active"] == 1
    got2, token2 = broker.dequeue([ev.type], timeout=1.0)
    assert got2 is not None
    broker.ack(ev.id, token2)
    # ...and its own complete trace carrying broker.wait
    assert rec.stats()["completed"] == 2
    second = rec.trace_for(ev.id)
    assert "broker.wait" in {s["name"] for s in second["spans"]}


def test_record_span_create_false_requires_active_trace():
    """FSM applies on followers/replay must not mint traces: with
    create=False a span lands only on an already-open trace."""
    rec = FlightRecorder()
    rec.record_span("ghost", "fsm.alloc_upsert", time.monotonic(),
                    create=False)
    assert rec.stats()["active"] == 0
    rec.record_span("live", "broker.wait", time.monotonic())
    rec.record_span("live", "fsm.alloc_upsert", time.monotonic(),
                    create=False)
    rec.complete("live")
    assert [s["name"] for s in rec.trace_for("live")["spans"]] == [
        "broker.wait", "fsm.alloc_upsert"]


# ---------------------------------------------------------------------
# e2e: one complete span tree per eval through the real control plane


def make_server(**over):
    from nomad_tpu.server import Server, ServerConfig

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
    from nomad_tpu.server.worker import DEQUEUE_TIMEOUT

    for w in server.workers:
        w.set_pause(True)
    time.sleep(DEQUEUE_TIMEOUT + 0.3)


def seed_nodes(server, n=8):
    for _ in range(n):
        node = mock.node()
        node.compute_class()
        server.node_register(node)


def run_dense_storm(server, n_jobs=6):
    """Register a storm of dense-path jobs while workers are parked,
    release, and wait for completion. Returns the eval ids."""
    quiesce(server)
    jobs = []
    for _ in range(n_jobs):
        job = mock.job()
        job.task_groups[0].count = 5  # >3 engages the dense path
        job.task_groups[0].tasks[0].resources.cpu = 20
        job.task_groups[0].tasks[0].resources.memory_mb = 16
        server.job_register(job)
        jobs.append(job)
    assert wait_until(lambda: server.broker.ready_count() >= n_jobs, 10.0)
    for w in server.workers:
        w.set_pause(False)
    assert wait_until(
        lambda: all(
            len(server.fsm.state.allocs_by_job(j.id)) == 5 for j in jobs),
        timeout=120.0)
    evals = [e for j in jobs for e in server.fsm.state.evals_by_job(j.id)]
    assert wait_until(
        lambda: (lambda s: s["acked"] + s["nacked"] >= n_jobs
                 and s["in_flight"] == 0)(server.dispatch.stats()),
        timeout=10.0)
    return [e.id for e in evals]


def _assert_monotonic_tree(tr):
    prev_start = -1.0
    for s in tr["spans"]:
        assert s["start_ms"] >= 0.0
        assert s["end_ms"] >= s["start_ms"]
        assert s["start_ms"] >= prev_start  # sorted by start
        prev_start = s["start_ms"]
        assert s["end_ms"] <= tr["duration_ms"] + 1.0


def test_e2e_span_tree_per_eval_dense_pipeline(fresh_recorder):
    """Every eval through the dispatch pipeline yields ONE complete
    span tree: broker wait, pipeline accumulate/launch, scheduler
    invoke, matrix build, device dispatch, plan submit/evaluate/commit,
    alloc upsert — with monotonic timestamps."""
    server = make_server()
    try:
        seed_nodes(server, 8)
        eval_ids = run_dense_storm(server, n_jobs=6)
        rec = fresh_recorder
        complete = []
        for eid in eval_ids:
            tr = rec.trace_for(eid)
            if tr is None:
                continue
            names = {s["name"] for s in tr["spans"]}
            if set(LIFECYCLE_CORE_STAGES) <= names:
                complete.append(tr)
        assert complete, "no complete span tree found"
        dense = [
            tr for tr in complete
            if {STAGE_DISPATCH_ACCUMULATE, STAGE_DISPATCH_LAUNCH,
                STAGE_MATRIX_BUILD,
                STAGE_DEVICE_DISPATCH} <= {s["name"] for s in tr["spans"]}
        ]
        assert dense, "no trace covered the dense pipeline stages"
        for tr in complete:
            assert tr["status"] == "acked"
            _assert_monotonic_tree(tr)
        # stage table covers the whole lifecycle
        stages = rec.stage_stats()
        for stage in LIFECYCLE_CORE_STAGES + ("e2e",):
            assert stage in stages, f"missing stage {stage}"
            assert stages[stage]["p99_ms"] >= stages[stage]["p50_ms"] >= 0
        # the table also rides server.stats()
        assert "trace" in server.stats()
        assert server.stats()["trace"].keys() == stages.keys()
    finally:
        server.shutdown()


def test_base_delta_span_once_per_derived_delta(fresh_recorder):
    """`base.delta` is the host half of a base prefetch (or of an inline
    replan's matrix.update) that really derived a delta: one sample a
    derived delta, with its four annotations, inside a `device.transfer`
    or `matrix.update` of kind "delta", and none on a hit, a rekey or a
    full build. `device_state.positions_patched_jobs` counts the jobs
    whose index entry the deltas rewrote: with pure creations, at most
    the allocations they added."""
    from nomad_tpu.models.resident import get_tracker
    from nomad_tpu.trace import STAGE_BASE_DELTA

    server = make_server()
    try:
        seed_nodes(server, 8)
        before = get_tracker().stats()
        eval_ids = run_dense_storm(server, n_jobs=4)   # the full build
        eval_ids += run_dense_storm(server, n_jobs=4)  # a delta over it
        rec = fresh_recorder
        assert wait_until(lambda: all(
            rec.trace_for(eid) is not None for eid in eval_ids), 10.0)
        after = server.stats()["device_state"]
        derived = after["delta_updates"] - before["delta_updates"]
        assert derived >= 1
        assert rec.stage_stats()[STAGE_BASE_DELTA]["count"] == derived
        spans = 0
        adds = patched = 0
        for eid in eval_ids:
            tr = rec.trace_for(eid)
            for s in tr["spans"]:
                if s["name"] != STAGE_BASE_DELTA:
                    continue
                spans += 1
                ann = s["annotations"]
                assert set(ann) == {"rows", "adds", "refills",
                                    "patched_jobs"}
                assert 1 <= ann["rows"] <= ann["adds"] + ann["refills"]
                assert ann["patched_jobs"] <= ann["adds"] + ann["refills"]
                adds += ann["adds"]
                patched += ann["patched_jobs"]
                around = [o for o in tr["spans"]
                          if o["name"] in ("device.transfer",
                                           "matrix.update")
                          and o["annotations"]["kind"] == "delta"
                          and o["start_ms"] <= s["start_ms"]
                          and s["end_ms"] <= o["end_ms"] + 0.01]
                assert around, tr["spans"]
        assert spans == derived
        assert after["positions_patched_jobs"] \
            - before["positions_patched_jobs"] == patched
        assert 1 <= patched <= adds
    finally:
        server.shutdown()


_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _bench_entry(name):
    """(BENCHMARK.json's per-layer entry of that name, its metric file,
    every cell's name): the entry is found BY NAME, wherever later PRs
    appended theirs."""
    bench = json.load(open(os.path.join(_ROOT, "BENCHMARK.json")))
    spec = json.load(open(os.path.join(
        _ROOT, "benchmark", "metrics", f"{name}.json")))
    entry = next(e for e in bench["per_layer"] if e["name"] == name)
    return entry, spec, [w["name"] for w in bench["workloads"]]


def _bench_reader(monkeypatch, name):
    """The benchmark's reader of that name, loaded as the harness loads
    it (beside its `stats` module)."""
    def load(module_name, path):
        module_spec = importlib.util.spec_from_file_location(
            module_name, path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        return module

    monkeypatch.setitem(sys.modules, "stats", load(
        "stats", os.path.join(_ROOT, "benchmark", "stats.py")))
    return load(f"benchmark_reader_{name}", os.path.join(
        _ROOT, "benchmark", "readers", f"{name}.py"))


def _span_snapshot(rec):
    """benchmark/run.py span_snapshot's reading."""
    return {stage: rec.stage_buckets(stage) for stage in rec.stage_stats()}


def test_base_delta_metric_file_reads_the_span(fresh_recorder, monkeypatch):
    """`benchmark/metrics/base_delta_p50_ms.json` is an entry
    BENCHMARK.json lists for every cell, names a stage of the program,
    and its reader takes a median from the recorder's histogram over a
    window, and nothing from a program that never recorded the stage
    (the parent's: the line then leaves the metric out)."""
    from nomad_tpu.trace import ALL_STAGES, STAGE_BASE_DELTA

    entry, spec, cells = _bench_entry("base_delta_p50_ms")
    assert entry == {key: spec[key] for key in (
        "name", "unit", "better", "source", "layer", "moves")} | {
        "workloads": cells}
    assert spec["args"] == {"stage": STAGE_BASE_DELTA, "q": 0.5}
    assert STAGE_BASE_DELTA in ALL_STAGES
    reader = _bench_reader(monkeypatch, spec["reader"])
    rec = fresh_recorder
    before = _span_snapshot(rec)
    assert reader.read(spec["args"], {
        "spans_before": before, "spans_after": before}) is None
    now = time.monotonic()
    for ms in (1.0, 2.0, 30.0):
        rec.record_span("ev-1", STAGE_BASE_DELTA, now, now + ms / 1000.0,
                        ann={"rows": 4, "adds": 8, "refills": 0,
                             "patched_jobs": 1})
    value = reader.read(spec["args"], {
        "spans_before": before, "spans_after": _span_snapshot(rec)})
    assert 1.5 < value < 2.7


# ---------------------------------------------------------------------
# the client's path (PR 40): rows of the stage table, no eval's tree


class _CountingLock:
    def __init__(self, inner):
        self.inner, self.holds = inner, 0

    def __enter__(self):
        self.inner.acquire()
        self.holds += 1

    def __exit__(self, *exc):
        self.inner.release()


def test_observe_stages_one_stripe_one_lock_hold():
    """All of a request's rows land in ONE stripe, the calling thread's,
    under one hold of its lock; another thread's may land elsewhere, and
    the read side merges the stripes. No trace is opened and `e2e` is
    not touched."""
    from nomad_tpu.trace import HTTP_STAGES

    rec = FlightRecorder()
    for stripe in rec._stripes:
        stripe.lock = _CountingLock(stripe.lock)
    rows = [(stage, 1.0 + i)
            for i, stage in enumerate(HTTP_STAGES["register"])]
    rec.observe_stages(rows)
    assert sum(stripe.lock.holds for stripe in rec._stripes) == 1
    filled = [stripe for stripe in rec._stripes if stripe.hists]
    assert len(filled) == 1 and filled[0].lock.holds == 1
    assert set(filled[0].hists) == set(HTTP_STAGES["register"])

    threads = [threading.Thread(target=rec.observe_stages, args=(rows,))
               for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # 17 calls, 17 holds; the reads below take every stripe's lock
    assert sum(stripe.lock.holds for stripe in rec._stripes) == 17
    for i, stage in enumerate(HTTP_STAGES["register"]):
        count, buckets = rec.stage_buckets(stage)
        assert count == 17 and sum(buckets) == 17
        assert rec.stage_stats()[stage]["max_ms"] == 1.0 + i
    assert rec.stage_buckets("e2e") is None
    assert rec.stats()["active"] == 0 and rec.traces() == []

    rec.set_enabled(False)
    rec.observe_stages(rows)
    assert rec.stage_buckets(rows[0][0])[0] == 17


def test_client_path_stages_fit_the_stage_table():
    """Every eval stage with a `.self` twin, the derived rows, the
    device's idle rows and the client's path fit one stripe's table (a
    thread's rows may all land in one)."""
    from nomad_tpu.trace import (
        ALL_STAGES,
        CLIENT_PATH_STAGES,
        DEVICE_IDLE_STAGES,
        HTTP_STAGES,
    )
    from nomad_tpu.trace.recorder import MAX_STAGES

    # PR 40's fifteen and the collector's two (PR 43)
    assert len(set(CLIENT_PATH_STAGES)) == len(CLIENT_PATH_STAGES) == 17
    assert not set(CLIENT_PATH_STAGES) & set(ALL_STAGES)
    for names in HTTP_STAGES.values():
        assert set(names) <= set(CLIENT_PATH_STAGES)
    assert (2 * len(ALL_STAGES) + 1 + len(DEVICE_IDLE_STAGES)
            + len(CLIENT_PATH_STAGES)) <= MAX_STAGES


# metric -> (reader, what its window below must read)
CLIENT_PATH_METRICS = {
    "gil_wait_p50_ms": ("span", (1.6, 2.4)),
    "gil_wait_p95_ms": ("span", (6.5, 9.6)),
    "register_server_p50_ms": ("span", (1.6, 2.4)),
    "register_front_p50_ms": ("span", (1.6, 2.4)),
    "register_reply_p50_ms": ("span", (1.6, 2.4)),
    "register_cpu_share": ("span_share", (0.42, 0.58)),
    "eval_read_server_p50_ms": ("span", (1.6, 2.4)),
    "eval_reads_per_eval": ("span_count", (2.0, 2.0)),
    "read_deliver_p50_ms": ("span", (1.6, 2.4)),
    "read_deliver_p95_ms": ("span", (6.5, 9.6)),
    "read_notify_lag_p50_ms": ("span", (1.6, 2.4)),
    "read_serve_wait_p50_ms": ("span", (1.6, 2.4)),
    "read_serve_p50_ms": ("span", (1.6, 2.4)),
    "read_serve_cpu_share": ("span_share", (0.42, 0.58)),
    "read_wakes_per_eval": ("span_count", (2.0, 2.0)),
    # the collector's passes (PR 43, profile/collector.py); the two of
    # the FULL passes only where a window is sure to hold some (SOME_CELLS)
    "gc_pause_p95_ms": ("span", (6.5, 9.6)),
    "gc_passes_per_eval": ("span_count", (2.0, 2.0)),
    "gc_full_pause_p50_ms": ("span", (1.6, 2.4)),
    "gc_full_passes_per_eval": ("span_count", (2.0, 2.0)),
}


# Frozen survivors leave the oldest generation nothing to outgrow: a
# full pass needs ten middle passes with no freeze between them, which
# only the two cells at capacity see in every window (12 and 19 in the
# builder's traced runs, PR 43; 0 in three of the open loops).
SOME_CELLS = {
    "gc_full_pause_p50_ms": ["northstar-10k.storm", "c1m-5k.ramp"],
    "gc_full_passes_per_eval": ["northstar-10k.storm", "c1m-5k.ramp"],
}


@pytest.mark.parametrize("name", sorted(CLIENT_PATH_METRICS))
def test_client_path_metric_file(name, fresh_recorder, monkeypatch):
    """Each of PR 40's and PR 43's metric files is an entry BENCHMARK.json
    lists for every cell (SOME_CELLS: for the cells named), names rows that `span.py` lists, and its reader (one of
    those that were there) takes the WINDOW's difference from a recorder
    that has the rows and nothing from one that has not (the parent's:
    the line then leaves the metric out)."""
    from nomad_tpu.trace import CLIENT_PATH_STAGES

    reader_name, (low, high) = CLIENT_PATH_METRICS[name]
    entry, spec, cells = _bench_entry(name)
    assert entry == {key: spec[key] for key in (
        "name", "unit", "better", "source", "layer", "moves")} | {
        "workloads": SOME_CELLS.get(name, cells)}
    assert set(entry["workloads"]) <= set(cells)
    assert spec["source"] == "program_span"
    assert spec["moves"] == "placed_allocs_per_s"
    assert spec["reader"] == reader_name
    args = spec["args"]
    num = args.get("num", [])
    stages = ([args["stage"]] if "stage" in args    # span, span_count
              else num + args["den"])                # span_share
    assert stages and set(stages) <= set(CLIENT_PATH_STAGES)

    reader = _bench_reader(monkeypatch, reader_name)
    rec = fresh_recorder
    empty = _span_snapshot(rec)
    assert reader.read(args, {"spans_before": empty, "spans_after": empty,
                              "evals_completed": 2}) is None
    # before the window: samples a life-long reading would be moved by
    rec.observe_stages([(stage, 100.0) for stage in stages] * 3)
    before = _span_snapshot(rec)
    # the window: four samples a stage, a share's numerator half as long
    rec.observe_stages([(stage, ms / 2 if stage in num else ms)
                        for stage in stages for ms in (1.0, 2.0, 4.0, 8.0)])
    value = reader.read(args, {
        "spans_before": before, "spans_after": _span_snapshot(rec),
        "evals_completed": 2})
    assert low <= value <= high


def test_chaos_fault_annotation_lands_on_covering_span(fresh_recorder):
    """An armed chaos fault that fires inside a stage must show up as a
    (site, ordinal) annotation ON the span covering that stage."""
    from nomad_tpu.chaos import FaultSpec, chaos

    server = make_server()
    try:
        seed_nodes(server, 8)
        schedule = [FaultSpec("dispatch.submit", "delay", delay=0.05,
                              count=2)]
        with chaos.armed(7, schedule):
            eval_ids = run_dense_storm(server, n_jobs=6)
            assert chaos.unfired() == []
        rec = fresh_recorder
        annotated = []
        for eid in eval_ids:
            tr = rec.trace_for(eid)
            if tr is None:
                continue
            for s in tr["spans"]:
                for f in s.get("faults", ()):
                    annotated.append((s["name"], f))
        assert annotated, "no fault annotation landed on any span"
        for span_name, fault in annotated:
            assert fault["site"] == "dispatch.submit"
            assert fault["kind"] == "delay"
            assert isinstance(fault["ordinal"], int)
            # the fault fired inside the plan-submit stage
            assert span_name == STAGE_PLAN_SUBMIT
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# HTTP surfaces


# One exposition line: comment, or name{labels} value — labels are
# optional, values include the +Inf/-Inf/NaN exposition spellings (the
# combined body now carries the contention observatory's site-labelled
# histograms too; tests/test_metrics.py has the full semantic parser).
PROM_LINE = re.compile(
    r'^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})? '
    r'([-+0-9.eE]+|\+Inf|-Inf|NaN))$')


def test_http_trace_and_metrics_endpoints(fresh_recorder):
    from nomad_tpu.api import Client, HTTPServer

    server = make_server(num_schedulers=1)
    http = HTTPServer(server)
    http.start()
    client = Client(http.addr, timeout=10.0)
    try:
        seed_nodes(server, 4)
        job = mock.job()
        ev_id, _ = server.job_register(job)
        assert wait_until(
            lambda: (lambda e: e is not None and e.status
                     == consts.EVAL_STATUS_COMPLETE)(
                server.fsm.state.eval_by_id(ev_id)), 30.0)
        assert wait_until(
            lambda: fresh_recorder.trace_for(ev_id) is not None, 10.0)

        out, _idx = client.get("/v1/agent/trace")
        assert out["recent"], "no recent traces over HTTP"
        assert out["recorder"]["completed"] >= 1
        assert "stages" in out and "e2e" in out["stages"]
        one, _ = client.get(f"/v1/agent/trace?eval={ev_id}")
        assert one["trace"]["eval_id"] == ev_id
        names = {s["name"] for s in one["trace"]["spans"]}
        assert set(LIFECYCLE_CORE_STAGES) <= names

        # Prometheus text exposition: every line must parse
        text = client.get_raw("/v1/metrics").decode()
        assert text.strip(), "empty exposition"
        for line in text.strip().splitlines():
            assert PROM_LINE.match(line), f"invalid exposition line: {line!r}"
        # histograms carry cumulative buckets + sum + count
        assert "_bucket{le=" in text
        assert '_bucket{le="+Inf"}' in text
        # the per-route http request histogram replaced the old
        # undifferentiated one (the fix this PR ships)
        assert re.search(r"nomad_tpu_http_request_GET_\w+_count", text)
        assert "\nnomad_tpu_http_request_count" not in text
    finally:
        http.stop()
        server.shutdown()


def test_churn_stages_registered_and_documented():
    """migrate.place + preempt.select are first-class lifecycle stages
    (churn PR): present in ALL_STAGES and in both stage tables (README
    + trace/README.md) — doc drift guard."""
    import os

    from nomad_tpu.trace import (
        ALL_STAGES,
        STAGE_MIGRATE_PLACE,
        STAGE_PREEMPT_SELECT,
    )

    assert STAGE_MIGRATE_PLACE in ALL_STAGES
    assert STAGE_PREEMPT_SELECT in ALL_STAGES
    root = os.path.join(os.path.dirname(__file__), "..")
    readme = open(os.path.join(root, "README.md")).read()
    trace_readme = open(os.path.join(
        root, "nomad_tpu", "trace", "README.md")).read()
    for stage in (STAGE_MIGRATE_PLACE, STAGE_PREEMPT_SELECT):
        assert stage in readme, stage
        assert stage in trace_readme, stage
