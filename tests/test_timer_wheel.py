"""Timer wheel + work pool tests (utils/timer.py, utils/pool.py):
callback dispatch must match Go runtime-timer semantics — a slow
callback runs on its own worker and cannot delay other timers — and
cancellation must be safe before, during, and after firing."""

import threading
import time

import pytest

from nomad_tpu.utils.pool import WorkPool
from nomad_tpu.utils.timer import TimerWheel


def test_timers_fire_in_deadline_order():
    wheel = TimerWheel(name="t-order", dispatch_workers=1)
    fired = []
    lock = threading.Lock()
    done = threading.Event()

    def cb(i):
        with lock:
            fired.append(i)
            if len(fired) == 4:
                done.set()

    # Scheduled out of order; with one dispatch worker, execution order
    # must follow deadlines.
    wheel.schedule(0.20, cb, 3)
    wheel.schedule(0.05, cb, 0)
    wheel.schedule(0.15, cb, 2)
    wheel.schedule(0.10, cb, 1)
    assert done.wait(5.0)
    assert fired == [0, 1, 2, 3]


def test_slow_callback_does_not_delay_others():
    """One blocked callback (a raft apply during leader loss) must not
    make other timers fire late — the round-2 wheel serialized all
    callbacks on the firing thread (an advisor finding of round 2)."""
    wheel = TimerWheel(name="t-slow")
    release = threading.Event()
    fast_fired = threading.Event()

    wheel.schedule(0.01, release.wait, 30.0)  # blocks a worker
    wheel.schedule(0.05, fast_fired.set)
    # The fast timer is due 40ms after the slow one starts blocking;
    # it must still fire promptly on another dispatch worker.
    assert fast_fired.wait(2.0), "fast timer was head-of-line blocked"
    release.set()


def test_cancel_before_fire():
    wheel = TimerWheel(name="t-cancel")
    fired = threading.Event()
    h = wheel.schedule(0.15, fired.set)
    h.cancel()
    assert not fired.wait(0.4)
    assert wheel.pending() == 0


def test_cancel_after_fire_is_noop():
    wheel = TimerWheel(name="t-cancel2")
    fired = threading.Event()
    h = wheel.schedule(0.01, fired.set)
    assert fired.wait(2.0)
    h.cancel()  # must not raise or corrupt the wheel
    ok = threading.Event()
    wheel.schedule(0.01, ok.set)
    assert ok.wait(2.0)


def test_cancel_race_under_concurrent_fire():
    """Hammer schedule+cancel while other timers fire: a handle
    cancelled before its deadline must never run, and the wheel must
    stay functional."""
    wheel = TimerWheel(name="t-race")
    fired = set()
    lock = threading.Lock()

    def cb(i):
        with lock:
            fired.add(i)

    handles = []
    for i in range(200):
        # Evens fire fast (keep the wheel busy); odds get a comfortable
        # deadline so cancelling them below is unambiguously pre-fire.
        delay = 0.001 + (i % 10) * 0.002 if i % 2 == 0 else 0.8
        handles.append(wheel.schedule(delay, cb, i))
    for i in range(1, 200, 2):
        handles[i].cancel()
    time.sleep(1.2)
    with lock:
        assert fired == set(range(0, 200, 2))
    # Wheel still functional afterwards.
    ok = threading.Event()
    wheel.schedule(0.01, ok.set)
    assert ok.wait(2.0)


def test_exception_in_callback_does_not_kill_wheel():
    wheel = TimerWheel(name="t-exc")

    def boom():
        raise RuntimeError("bad timer")

    wheel.schedule(0.01, boom)
    ok = threading.Event()
    wheel.schedule(0.05, ok.set)
    assert ok.wait(2.0)


def test_storm_of_timers_all_fire():
    wheel = TimerWheel(name="t-storm")
    n = 500
    count = [0]
    done = threading.Event()
    lock = threading.Lock()

    def cb():
        with lock:
            count[0] += 1
            if count[0] == n:
                done.set()

    for i in range(n):
        wheel.schedule(0.001 + (i % 20) * 0.001, cb)
    assert done.wait(10.0)
    assert count[0] == n


# ---------------------------------------------------------------- pool


def test_pool_bounded_worker_count():
    pool = WorkPool(3, name="p-bound")
    release = threading.Event()
    started = []
    lock = threading.Lock()

    def task(i):
        with lock:
            started.append(i)
        release.wait(10.0)
        return i

    futs = [pool.submit(task, i) for i in range(10)]
    time.sleep(0.3)
    assert pool.worker_count() <= 3
    with lock:
        assert len(started) <= 3  # only `size` tasks run concurrently
    release.set()
    assert sorted(f.result(10.0) for f in futs) == list(range(10))
    assert pool.worker_count() <= 3


def test_pool_future_delivers_result_and_exception():
    pool = WorkPool(2, name="p-fut")
    assert pool.submit(lambda: 41 + 1).result(5.0) == 42

    def boom():
        raise ValueError("nope")

    fut = pool.submit(boom)
    assert fut.wait(5.0)
    with pytest.raises(ValueError, match="nope"):
        fut.result(0.0)


def test_pool_workers_are_reused():
    pool = WorkPool(2, name="p-reuse")
    for _ in range(20):
        pool.submit(lambda: None).result(5.0)
    assert pool.worker_count() <= 2


def test_pool_submit_survives_thread_spawn_failure(monkeypatch):
    """submit() enqueues BEFORE spawning, so a Thread.start failure
    (OS thread pressure) must not raise to the caller — the item is
    already due to run, and raising would hand call sites an item that
    is both 'failed' and still executing (double accounting in the
    dispatch pipeline's slot tracking). The item drains via live
    workers, or via the retried spawn on the next submit."""
    import threading

    pool = WorkPool(3, name="p-spawnfail")
    # Warm one live worker so the queued item has a drain path.
    pool.submit(lambda: None).wait(5.0)

    real_start = threading.Thread.start
    fails = {"n": 0}

    def flaky_start(self):
        if self.name.startswith("p-spawnfail") and fails["n"] == 0:
            fails["n"] += 1
            raise RuntimeError("can't start new thread")
        return real_start(self)

    monkeypatch.setattr(threading.Thread, "start", flaky_start)
    # Saturate the live worker, then submit while a spawn would fire.
    gate = threading.Event()
    blocked = pool.submit(gate.wait, 10.0)
    fut = pool.submit(lambda: 42)  # spawn fails here — must NOT raise
    assert fails["n"] == 1
    gate.set()
    assert blocked.wait(5.0)
    assert fut.result(5.0) == 42  # the enqueued item still ran
    # A later submit retries the spawn successfully.
    assert pool.submit(lambda: 7).result(5.0) == 7



def test_pool_cold_spawn_failure_leaves_item_queued(monkeypatch):
    """Zero live workers + persistent spawn failure: submit must not
    raise, must not run the task inline (a never-block submitter like
    the dispatch pipeline's dispatcher would block), and must not drop
    it — the item stays honestly queued and the NEXT submit's spawn
    retry drains it."""
    import threading

    pool = WorkPool(2, name="p-coldfail")
    real_start = threading.Thread.start
    fails = {"n": 0}

    def flaky_start(self):
        # Both attempts (initial + immediate retry) of the first
        # submit fail; later spawns succeed.
        if self.name.startswith("p-coldfail") and fails["n"] < 2:
            fails["n"] += 1
            raise RuntimeError("can't start new thread")
        return real_start(self)

    monkeypatch.setattr(threading.Thread, "start", flaky_start)
    order = []
    first = pool.submit(order.append, "first")
    assert fails["n"] == 2
    assert not first.done()  # queued, NOT run inline on this thread
    assert pool.queued() == 1
    # The next submit re-fires the spawn trigger; one worker drains
    # both items in FIFO order.
    second = pool.submit(order.append, "second")
    assert first.wait(5.0) and second.wait(5.0)
    assert order == ["first", "second"]


def test_pool_item_submitted_in_a_workers_idle_gap_is_not_stranded():
    """A worker between its get() and its idle decrement still counts
    as idle: a submit in that gap sees capacity that is spoken for and
    spawns nothing. The worker's own look after its decrement spawns the
    worker the item needs; before PR 42 the item sat behind the first
    task for as long as that blocked (in the dispatch pipeline: an eval
    stranded while its batch-mates waited the batcher's cap out for
    it)."""
    import queue

    in_gap, leave_gap = threading.Event(), threading.Event()

    class GapQueue(queue.SimpleQueue):
        def get(self, *a, **kw):
            item = super().get(*a, **kw)
            if item[1] == ("first",):
                in_gap.set()            # taken, idle not yet decremented
                leave_gap.wait(10.0)
            return item

    pool = WorkPool(4, name="p-gap")
    pool._queue = GapQueue()
    pool.submit(lambda: None).result(5.0)       # one worker, now idle
    deadline = time.monotonic() + 5.0
    while pool._idle != 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    release = threading.Event()
    first = pool.submit(lambda tag: release.wait(10.0), "first")
    assert in_gap.wait(5.0)
    second = pool.submit(lambda tag: tag, "second")
    assert pool.worker_count() == 1             # the gap hid the need
    leave_gap.set()
    assert second.result(5.0) == "second"       # while `first` blocks
    assert not first.done()
    release.set()
    assert first.result(5.0) is True
