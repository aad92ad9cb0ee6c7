"""Contention observatory (nomad_tpu/profile): ProfiledLock parity
with threading primitives, concurrent-writer safety on the profiler
rings, the convoy detector's 64-thread fixture, the GIL sampler, the
Prometheus exposition, and the Chrome trace-event export round-trip.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nomad_tpu import profile
from nomad_tpu.profile import (
    ProfiledCondition,
    ProfiledLock,
    ProfiledRLock,
    get_profiler,
)
from nomad_tpu.profile.export import chrome_trace, validate_chrome_trace
from nomad_tpu.profile.timeline import ConvoyTracker, Timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def prof():
    p = get_profiler()
    p.reset()
    p.set_enabled(True)
    yield p
    p.reset()
    p.set_enabled(True)


# ---------------------------------------------------------------------
# ProfiledLock semantics parity


def test_lock_context_manager_and_locked(prof):
    lock = ProfiledLock("t.basic")
    assert not lock.locked()
    with lock:
        assert lock.locked()
    assert not lock.locked()


def test_lock_nonblocking_and_timeout_acquire(prof):
    lock = ProfiledLock("t.nb")
    assert lock.acquire(blocking=False)
    # Held: a second non-blocking acquire fails without deadlock, a
    # bounded blocking acquire times out False.
    got = [None, None]

    def other():
        got[0] = lock.acquire(blocking=False)
        got[1] = lock.acquire(True, 0.02)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert got == [False, False]
    lock.release()
    assert lock.acquire(True, 0.5)
    lock.release()


def test_lock_releases_on_context_exception(prof):
    lock = ProfiledLock("t.exc")
    with pytest.raises(RuntimeError):
        with lock:
            raise RuntimeError("boom")
    # The with-statement released despite the exception.
    assert lock.acquire(blocking=False)
    lock.release()


def test_rlock_reentrancy(prof):
    lock = ProfiledRLock("t.rlock")
    with lock:
        with lock:
            with lock:
                assert lock._depth == 3
        assert lock._depth == 1
    assert lock._depth == 0
    # Hold recorded ONCE per outermost hold, not per nesting level.
    assert lock.stats.hold.count == 1
    assert lock.stats.acquires == 3


def test_rlock_locked_parity(prof):
    """threading.RLock has no .locked() before 3.14; the drop-in
    wrapper must answer correctly anyway — including for the owner
    (where a naive non-blocking probe would reentrantly succeed and
    report free)."""
    lock = ProfiledRLock("t.rlocked")
    assert not lock.locked()
    with lock:
        assert lock.locked()
        seen = []
        t = threading.Thread(target=lambda: seen.append(lock.locked()))
        t.start()
        t.join()
        assert seen == [True]
    assert not lock.locked()


def test_unpark_balances_after_disable_mid_park(prof):
    """A park counted while enabled must decrement even if recording
    is switched off mid-park (the bench --profile-ab off arm), or the
    width gauge reports a phantom pile-up forever."""
    parked = profile.park("t.flip")
    assert parked is True
    prof.set_enabled(False)
    profile.unpark("t.flip")
    prof.set_enabled(True)
    assert prof.convoy_table()["sites"]["t.flip"]["width"] == 0
    # And a park attempted while disabled reports uncounted, so the
    # caller skips the matching unpark.
    prof.set_enabled(False)
    assert profile.park("t.flip") is False


def test_rlock_cross_thread_exclusion(prof):
    lock = ProfiledRLock("t.rlock2")
    lock.acquire()
    seen = []

    def other():
        seen.append(lock.acquire(blocking=False))

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert seen == [False]
    lock.release()


def test_condition_wait_timeout_returns_false(prof):
    cond = ProfiledCondition(ProfiledLock("t.cond.to"), "t.cond.to")
    t0 = time.monotonic()
    with cond:
        assert cond.wait(0.05) is False
    assert time.monotonic() - t0 >= 0.04
    # The park landed in the cond-wait histogram, and hold accounting
    # resumed (release observed a second, tiny hold).
    assert cond.stats.cond_waits == 1
    assert cond.stats.cond_wait.count == 1


def test_condition_notify_wakes_waiter(prof):
    lock = ProfiledLock("t.cond.n")
    cond = ProfiledCondition(lock, "t.cond.n")
    results = []

    def waiter():
        with cond:
            results.append(cond.wait(5.0))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cond:
        cond.notify_all()
    t.join(timeout=5.0)
    assert results == [True]


def test_condition_over_rlock_with_reentrant_notify(prof):
    """The broker shape: Condition over an RLock, notified from a
    nested (reentrant) critical section."""
    lock = ProfiledRLock("t.cond.r")
    cond = ProfiledCondition(lock, "t.cond.r")
    results = []

    def waiter():
        with cond:
            results.append(cond.wait(5.0))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cond:
        with lock:  # reentrant alias of the same lock
            cond.notify_all()
    t.join(timeout=5.0)
    assert results == [True]
    # Wrapper depth bookkeeping survived the cond.wait save/restore.
    assert lock._depth == 0 and lock._owner is None


def test_condition_wait_for(prof):
    cond = ProfiledCondition(ProfiledLock("t.cond.wf"), "t.cond.wf")
    flag = []

    def setter():
        time.sleep(0.05)
        with cond:
            flag.append(1)
            cond.notify_all()

    t = threading.Thread(target=setter)
    t.start()
    with cond:
        assert cond.wait_for(lambda: flag, timeout=5.0)
    t.join()


def test_condition_requires_profiled_lock(prof):
    with pytest.raises(TypeError):
        ProfiledCondition(threading.Lock(), "t.raw")


def test_contended_wait_and_hold_recorded(prof):
    lock = ProfiledLock("t.contend")

    def holder():
        with lock:
            time.sleep(0.03)

    t = threading.Thread(target=holder)
    t.start()
    time.sleep(0.005)
    with lock:
        pass
    t.join()
    st = lock.stats
    assert st.contended == 1
    assert st.wait.count == 1
    assert st.wait.max >= 10.0  # waited most of the 30ms hold
    assert st.hold.count == 2
    assert st.hold.max >= 25.0
    # The waiting thread's drill-down attributes the wait to the site.
    table = prof.threads_table()
    me = threading.current_thread().name
    assert table[me]["lock_waits"] >= 1
    assert table[me]["hottest_site"] == "t.contend"
    assert prof.thread_wait_ms() > 0.0


def test_disabled_profiler_still_locks_correctly(prof):
    prof.set_enabled(False)
    lock = ProfiledLock("t.disabled")
    with lock:
        assert lock.locked()
    rlock = ProfiledRLock("t.disabled.r")
    with rlock:
        with rlock:
            pass
    cond = ProfiledCondition(ProfiledLock("t.disabled.c"), "t.disabled.c")
    with cond:
        assert cond.wait(0.01) is False
    assert lock.stats.acquires == 0
    assert cond.stats.cond_waits == 0


def test_site_aggregation_across_instances(prof):
    """Stripe shape: N locks sharing one declaration site aggregate in
    the read-side table."""
    locks = [ProfiledLock("t.stripe") for _ in range(4)]
    for lk in locks:
        with lk:
            pass
    table = prof.lock_table()
    assert table["t.stripe"]["instances"] >= 4
    assert table["t.stripe"]["acquires"] >= 4


# ---------------------------------------------------------------------
# Timeline ring: concurrent writers, no torn events, caps respected


def test_timeline_concurrent_writers_no_torn_events():
    tl = Timeline(cap=256)
    n_threads, per_thread = 8, 500

    def writer(tid):
        for i in range(per_thread):
            # Self-consistent payload: b is derived from a, so a torn
            # event (fields from two writers) breaks the checksum.
            tl.push("park", f"w{tid}", a=i, b=i * 31 + tid)

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = tl.stats()
    assert stats["events"] == n_threads * per_thread
    assert stats["stored"] == 256  # cap respected, drop-oldest
    events = tl.events()
    assert len(events) == 256
    for (_t, _wall, kind, thread, a, b) in events:
        assert kind == "park"
        tid = int(thread[1:])
        assert b == a * 31 + tid, "torn event: payload fields mixed"


def test_timeline_events_limit_and_order():
    tl = Timeline(cap=64)
    for i in range(100):
        tl.push("ack", a=i)
    evts = tl.events(limit=10)
    assert [e[4] for e in evts] == list(range(90, 100))  # newest, ordered


# ---------------------------------------------------------------------
# Convoy detector


def test_convoy_tracker_width_and_duration():
    tr = ConvoyTracker(min_width=3, keep=8)
    for _ in range(5):
        tr.park()
    assert tr.stats()["width"] == 5
    time.sleep(0.02)
    for _ in range(5):
        tr.unpark()
    assert tr.stats()["width"] == 0
    assert tr.convoys == 1
    recent = tr.recent()
    assert recent[0]["width"] == 5
    assert recent[0]["duration_ms"] >= 10.0


def test_convoy_below_threshold_not_reported():
    tr = ConvoyTracker(min_width=4, keep=8)
    tr.park()
    tr.park()
    tr.unpark()
    tr.unpark()
    assert tr.convoys == 0
    assert tr.stats()["max_width"] == 2


def test_synthetic_64_thread_convoy(prof):
    """The fixture the issue names: 64 threads pile up at a park site;
    the detector must report a convoy of width >= 48."""
    n = 64
    release = threading.Event()
    started = threading.Barrier(n + 1)

    def worker():
        started.wait(timeout=10.0)
        profile.park("test.convoy")
        try:
            release.wait(timeout=10.0)
        finally:
            profile.unpark("test.convoy")

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    started.wait(timeout=10.0)
    # Wait until the pile-up is visible, then release.
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        width = prof.convoy_table()["sites"].get(
            "test.convoy", {}).get("width", 0)
        if width >= 48:
            break
        time.sleep(0.005)
    assert width >= 48, f"pile-up never reached width 48 (saw {width})"
    release.set()
    for t in threads:
        t.join(timeout=10.0)
    table = prof.convoy_table()
    assert table["max_width"] >= 48
    assert table["convoys"] >= 1
    widest = max(c["width"] for c in table["recent"])
    assert widest >= 48
    # The park/unpark flow landed in the timeline too.
    kinds = {e[2] for e in prof.timeline.events()}
    assert {"park", "unpark"} <= kinds


# ---------------------------------------------------------------------
# GIL sampler + runq


def test_gil_sampler_measures_overshoot(prof):
    prof.gil.interval = 0.002
    prof.gil.start()
    try:
        deadline = time.monotonic() + 5.0
        while prof.gil.hist.count < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        prof.gil.stop()
    stats = prof.gil.stats()
    assert stats["count"] >= 5
    assert stats["p99_ms"] >= 0.0
    assert not prof.gil.running()


@pytest.mark.parametrize("enabled", [True, False])
def test_sampler_feeds_the_recorders_row(prof, enabled):
    """Each overshoot also lands in the flight recorder's stage table
    as `runtime.gil_wait`, where the benchmark reads it; with the
    observatory off (`profile_enabled = false`) the sampler does not
    run and the row has no sample."""
    from nomad_tpu.profile.sampler import SAMPLE_INTERVAL_S
    from nomad_tpu.trace import STAGE_RUNTIME_GIL_WAIT, get_recorder

    rec = get_recorder()
    rec.set_enabled(True)
    prof.configure(enabled=False)       # a sampler an earlier test left
    rec.reset()
    try:
        prof.configure(enabled=enabled, sampler_interval=0.002)
        assert prof.gil.running() is enabled
        deadline = time.monotonic() + 5.0
        while enabled and time.monotonic() < deadline and (
                rec.stage_buckets(STAGE_RUNTIME_GIL_WAIT) or (0,))[0] < 5:
            time.sleep(0.01)
        if not enabled:
            time.sleep(0.05)
    finally:
        prof.gil.stop()
        prof.gil.interval = SAMPLE_INTERVAL_S
    row = rec.stage_buckets(STAGE_RUNTIME_GIL_WAIT)
    if enabled:
        assert row[0] >= 5
        assert abs(row[0] - prof.gil.hist.count) <= 1  # its own stays
    else:
        assert row is None
        assert prof.gil.hist.count == 0


def test_runq_sites_fixed_vocabulary(prof):
    profile.record_runq("batch_park", 1.5)
    profile.record_runq("broker_drain", 2.5)
    profile.record_runq("not_a_site", 9.9)  # ignored, never grows
    table = prof.runq_table()
    assert set(table) == {"batch_park", "broker_drain"}
    assert table["batch_park"]["count"] == 1


def test_profiler_snapshot_shape(prof):
    lock = ProfiledLock("t.snap")
    with lock:
        pass
    snap = prof.snapshot(threads=True)
    assert snap["enabled"] is True
    assert "t.snap" in snap["locks"]
    for key in ("gil", "runq", "convoys", "timeline", "threads"):
        assert key in snap
    json.dumps(snap)  # everything JSON-serializable


# ---------------------------------------------------------------------
# Prometheus exposition of the observatory


def test_profile_prometheus_exposition(prof):
    lock = ProfiledLock("t.prom")

    def holder():
        with lock:
            time.sleep(0.02)

    t = threading.Thread(target=holder)
    t.start()
    time.sleep(0.005)
    with lock:
        pass
    t.join()
    profile.record_runq("batch_park", 3.0)
    text = prof.format_prometheus()
    assert '# TYPE nomad_tpu_profile_lock_wait_ms histogram' in text
    assert 'site="t.prom"' in text
    assert 'le="+Inf"' in text
    assert "nomad_tpu_profile_lock_wait_ms_sum" in text
    assert "nomad_tpu_profile_lock_wait_ms_count" in text
    assert "# TYPE nomad_tpu_profile_convoy_max_width gauge" in text
    assert "# TYPE nomad_tpu_profile_convoys_total counter" in text


# ---------------------------------------------------------------------
# Chrome trace-event export + traceconv round trip


def _sample_traces():
    from nomad_tpu.trace import get_recorder

    rec = get_recorder()
    rec.reset()
    for i in range(3):
        eid = f"chrome-{i}"
        t0 = time.monotonic()
        rec.record_span(eid, "scheduler.process", t0 - 0.05, t0 - 0.01,
                        ann={"path": "test"})
        rec.record_span(eid, "device.dispatch", t0 - 0.04, t0 - 0.02)
        rec.complete(eid)
    traces = rec.traces(10)
    rec.reset()
    return traces


def test_chrome_export_schema_valid(prof):
    traces = _sample_traces()
    profile.event("launch", "dispatcher", a=3)
    profile.park("test.chrome")
    profile.unpark("test.chrome")
    doc = chrome_trace(
        traces,
        timeline=prof.timeline.events(),
        convoys=[{"start_unix": time.time(), "duration_ms": 5.0,
                  "width": 12, "site": "test.chrome"}])
    assert validate_chrome_trace(doc) == []
    events = doc["traceEvents"]
    # Every eval got a track: a thread_name metadata event + X spans.
    meta = [e for e in events if e["ph"] == "M" and e["tid"] >= 10]
    assert len(meta) == 3
    spans = [e for e in events if e["ph"] == "X" and e.get("cat") == "eval"]
    assert len(spans) == 6
    for e in spans:
        assert e["dur"] > 0 and e["ts"] > 1e15  # absolute wall micros
    # Pipeline instants + the convoy interval are present.
    assert any(e["ph"] == "i" and e["name"] == "launch" for e in events)
    assert any(e.get("cat") == "convoy" for e in events)


def test_chrome_export_dedups_tail_first():
    traces = _sample_traces()
    dup = dict(traces[0])
    dup["status"] = "tail-copy"
    doc = chrome_trace([dup] + traces)
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["tid"] >= 10]
    # First occurrence wins; no duplicate track for the same eval.
    assert len(names) == 3
    assert any("tail-copy" in n for n in names)


def test_validate_chrome_trace_catches_violations():
    assert validate_chrome_trace({"nope": 1})
    bad = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 2, "name": "x", "ts": -5, "dur": 1},
        {"ph": "Z", "pid": 1, "tid": 2, "name": "x", "ts": 0},
        {"ph": "X", "pid": 1, "tid": 2, "name": "", "ts": 0, "dur": 1},
    ]}
    errors = validate_chrome_trace(bad)
    assert len(errors) == 3


def test_traceconv_cli_round_trip(tmp_path, prof):
    """File-level round trip: a /v1/agent/trace-shaped dump converts
    to a chrome file the validator (and a JSON reload) accepts."""
    traces = _sample_traces()
    dump = {"recent": traces[1:], "tail": traces[:1],
            "profile_timeline": [
                [time.monotonic(), time.time(), "launch", "d", 3, 0]],
            "convoys": [{"start_unix": time.time(), "duration_ms": 2.0,
                         "width": 8, "site": "s"}]}
    src = tmp_path / "dump.json"
    src.write_text(json.dumps(dump))
    out = tmp_path / "out.chrome.json"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "traceconv.py"),
         str(src), "-o", str(out)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    doc = json.loads(out.read_text())
    assert validate_chrome_trace(doc) == []
    assert any(e.get("cat") == "convoy" for e in doc["traceEvents"])
    assert any(e["ph"] == "i" for e in doc["traceEvents"])
    # And the converter's own validator agrees via --validate.
    res2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "traceconv.py"),
         "--validate", str(out)],
        capture_output=True, text=True, timeout=60)
    assert res2.returncode == 0, res2.stderr
    assert "schema clean" in res2.stdout


def test_traceconv_refuses_garbage(tmp_path):
    src = tmp_path / "garbage.json"
    src.write_text('"just a string"')
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "traceconv.py"),
         str(src), "-o", str(tmp_path / "x.json")],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 2


# ---------------------------------------------------------------------
# HTTP surfaces: /v1/agent/profile, server.stats()["profile"],
# /v1/agent/trace?format=chrome


def _wait_until(fn, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def test_http_profile_and_chrome_endpoints(prof):
    from nomad_tpu import mock
    from nomad_tpu.api import Client, HTTPServer
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs import consts

    server = Server(ServerConfig(
        num_schedulers=2,
        scheduler_factories={"service": "service-tpu"},
        eval_batch_size=8))
    server.start()
    http = HTTPServer(server)
    http.start()
    client = Client(http.addr, timeout=10.0)
    try:
        for _ in range(4):
            node = mock.node()
            node.compute_class()
            server.node_register(node)
        ev_id, _ = server.job_register(mock.job())
        assert _wait_until(
            lambda: (lambda e: e is not None and e.status
                     == consts.EVAL_STATUS_COMPLETE)(
                server.fsm.state.eval_by_id(ev_id)), 30.0)

        # server.stats() carries the observatory...
        stats = server.stats()["profile"]
        assert stats["enabled"] is True
        assert "server.broker" in stats["locks"]
        assert stats["locks"]["server.broker"]["acquires"] > 0

        # ...and so does the HTTP surface, with drill-downs.
        out, _ = client.get("/v1/agent/profile")
        assert out["enabled"] is True
        assert "server.broker" in out["locks"]
        assert "gil" in out and "convoys" in out and "runq" in out
        one, _ = client.get("/v1/agent/profile?lock=server.broker")
        assert one["site"] == "server.broker"
        assert one["stats"]["acquires"] > 0
        threads, _ = client.get("/v1/agent/profile?threads=1")
        assert isinstance(threads.get("threads"), dict)
        try:
            client.get("/v1/agent/profile?lock=no.such.site")
            raise AssertionError("expected 404")
        except Exception as e:
            assert "404" in str(e) or "no profiled lock" in str(e)

        # Chrome trace export over HTTP: schema-valid, with the
        # pipeline timeline track present.
        raw = client.get_raw("/v1/agent/trace?format=chrome")
        doc = json.loads(raw.decode())
        assert validate_chrome_trace(doc) == []
        assert any(e["ph"] == "M" and e["args"]["name"]
                   == "pipeline timeline" for e in doc["traceEvents"])
        assert any(e["ph"] == "X" for e in doc["traceEvents"])

        # /v1/metrics carries the observatory's families.
        text = client.get_raw("/v1/metrics").decode()
        assert "nomad_tpu_profile_lock_hold_ms" in text
        assert "nomad_tpu_profile_convoy_max_width" in text
    finally:
        http.stop()
        server.shutdown()


def test_pressure_reasons_cite_lock_site(prof):
    """With the lock-wait thresholds configured, sustained contention
    drives the pressure level and the reason NAMES the hottest
    site."""
    from nomad_tpu.server import Server, ServerConfig

    server = Server(ServerConfig(
        num_schedulers=1,
        admission_lock_wait_yellow_ms=0.0001,
        admission_lock_wait_red_ms=1e9))
    server.start()
    try:
        lock = ProfiledLock("test.pressure.site")

        def holder():
            with lock:
                time.sleep(0.03)

        t = threading.Thread(target=holder)
        t.start()
        time.sleep(0.005)
        with lock:
            pass
        t.join()
        snap = server.admission.pressure.snapshot(refresh=True)
        assert snap["inputs"]["lock_wait_p99_ms"] > 0
        assert snap["inputs"]["lock_wait_site"] == "test.pressure.site"
        assert snap["level"] in ("yellow", "red")
        assert any("test.pressure.site" in r for r in snap["reasons"])
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# Reset semantics (bench A/B isolation)


def test_dead_locks_retire_into_site_aggregate(prof):
    """Snapshot churn (a ProfiledLock per ClusterBase) must not
    exhaust the registry or accrete dead histograms: a GC'd lock's
    counts fold into the site's retired aggregate, its live slot
    frees, and the site table still reports the full history."""
    import gc

    before = prof._lock_instances
    for _ in range(10):
        lock = ProfiledLock("t.churn")
        with lock:
            pass
        del lock
    gc.collect()
    table = prof.lock_table()  # read side drains the retired queue
    # No net growth from the churned locks (<=, not ==: the drain may
    # also retire other tests' dead locks from earlier in the session,
    # shrinking the count below `before`).
    assert prof._lock_instances <= before
    with prof._reg_lock:
        assert prof._lock_sites.get("t.churn", []) == []  # slots freed
    assert table["t.churn"]["acquires"] == 10  # history retained
    assert table["t.churn"]["instances"] == 1  # one retired aggregate
    # And disabled-arm holds never leave a stale stamp behind: a hold
    # spanning a disable/enable flip records nothing giant.
    lock = ProfiledLock("t.stale")
    lock.acquire()
    prof.set_enabled(False)
    lock.release()
    lock.acquire()
    prof.set_enabled(True)
    time.sleep(0.01)
    lock.release()
    assert lock.stats.hold.max < 1000.0  # no disabled-window hold
    assert lock.stats.hold.count <= 1


def test_reset_clears_stats_but_keeps_registrations(prof):
    lock = ProfiledLock("t.reset")
    with lock:
        pass
    profile.park("t.reset.site")
    profile.unpark("t.reset.site")
    assert prof.lock_table()["t.reset"]["acquires"] == 1
    prof.reset()
    table = prof.lock_table()
    assert "t.reset" in table  # registration survives
    assert table["t.reset"]["acquires"] == 0
    assert prof.timeline.stats()["events"] == 0
    with lock:
        pass
    assert prof.lock_table()["t.reset"]["acquires"] == 1
