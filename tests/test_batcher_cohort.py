"""The batcher closes a dispatch on its own batch's count: requests of
a pipeline batch carry a unit of that batch's cohort (open_cohort), and
their dispatch is released by the cohort's last arrival or settle, by a
full queue, or by the cap on a member that never comes — never by the
timed window. Judged by the
batcher's counters and by which calls have returned, not by the clock:
the window is a quarter of a second throughout, and no dispatch here
may be closed by it unless the test says so."""

import threading
import time

import pytest

from nomad_tpu.scheduler import batcher as batcher_mod
from nomad_tpu.scheduler.batcher import PlacementBatcher
from test_batcher import CONFIG, tiny_inputs

WINDOW = 0.25


class _Call:
    """One place() on a thread of its own."""

    def __init__(self, batcher, unit=None, n=128, seed=0):
        self.done = threading.Event()
        self.result = self.error = None
        self._args = (batcher, unit, n, seed)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        batcher, unit, n, seed = self._args
        state, asks, key = tiny_inputs(n=n, seed=seed)
        try:
            self.result = batcher.place(state, asks, key, CONFIG,
                                        cohort=unit)
        except Exception as e:  # noqa: BLE001 - the test reads it
            self.error = e
        self.done.set()

    def finish(self):
        assert self.done.wait(60.0), "place() never returned"
        assert self.error is None, self.error
        return self.result


def _queued(batcher) -> int:
    with batcher._lock:
        return sum(len(q) for q in batcher._queues.values())


def _wait_queued(batcher, n):
    deadline = time.monotonic() + 30.0
    while _queued(batcher) < n:
        assert time.monotonic() < deadline, (_queued(batcher), n)
        time.sleep(0.002)


def _closed(batcher) -> dict:
    stats = batcher.stats()
    return {k[len("closed_by_"):]: v for k, v in stats.items()
            if k.startswith("closed_by_")}


@pytest.mark.parametrize("n", [1, 2, 5])
def test_a_cohort_of_n_closes_on_its_nth_arrival(n):
    batcher = PlacementBatcher(window=WINDOW)
    units = batcher.open_cohort(n)
    assert len(units) == n
    assert batcher.stats()["open_cohorts"] == 1
    calls = [_Call(batcher, u, seed=i) for i, u in enumerate(units[:-1])]
    _wait_queued(batcher, n - 1)
    # The last member is still out: nobody has been served.
    assert not any(c.done.is_set() for c in calls)
    assert batcher.stats()["dispatches"] == 0
    calls.append(_Call(batcher, units[-1], seed=n))
    for call in calls:
        call.finish()
    stats = batcher.stats()
    assert _closed(batcher) == {
        "cohort": 1, "window": 0, "full": 0, "cap": 0}, stats
    assert stats["dispatches"] == 1 and stats["batched_requests"] == n
    assert stats["open_cohorts"] == 0
    assert all(u.closed_by == "cohort" for u in units)


def test_a_settled_unit_closes_its_cohort():
    batcher = PlacementBatcher(window=WINDOW)
    units = batcher.open_cohort(3)
    calls = [_Call(batcher, u, seed=i) for i, u in enumerate(units[:2])]
    _wait_queued(batcher, 2)
    assert not any(c.done.is_set() for c in calls)
    units[2].settle()  # took a host path: its place() never comes
    units[2].settle()  # idempotent
    for call in calls:
        call.finish()
    stats = batcher.stats()
    assert _closed(batcher) == {
        "cohort": 1, "window": 0, "full": 0, "cap": 0}, stats
    assert stats["dispatches"] == 1 and stats["batched_requests"] == 2
    assert stats["open_cohorts"] == 0
    assert units[2].closed_by is None  # rode no dispatch


def test_the_two_batches_in_flight_go_together():
    """A complete cohort holds while another is open: the pipeline's
    two batches in flight then ride one dispatch where they share a
    queue (so the pre-resolution sees both) and go at the same moment
    where they do not. A batch announced AFTER a dispatch has gone is
    none of its business."""
    batcher = PlacementBatcher(window=WINDOW)
    a = batcher.open_cohort(2)
    b = batcher.open_cohort(2)
    assert batcher.stats()["open_cohorts"] == 2
    a_calls = [_Call(batcher, u, seed=i) for i, u in enumerate(a)]
    _wait_queued(batcher, 2)
    assert not any(c.done.is_set() for c in a_calls)  # A holds for B
    b_same = _Call(batcher, b[0], seed=2)             # A's queue
    _wait_queued(batcher, 3)
    assert not b_same.done.is_set()
    b_other = _Call(batcher, b[1], n=256, seed=3)     # a queue of its own
    for call in a_calls + [b_same, b_other]:
        call.finish()
    stats = batcher.stats()
    assert stats["dispatches"] == 2 and stats["batched_requests"] == 4
    assert stats["open_cohorts"] == 0
    (c,) = batcher.open_cohort(1)  # opened after both have gone
    _Call(batcher, c, seed=4).finish()
    assert _closed(batcher) == {
        "cohort": 3, "window": 0, "full": 0, "cap": 0}
    assert all(u.closed_by == "cohort" for u in a + b + [c])


def test_a_member_that_never_comes_is_released_at_the_cap(monkeypatch):
    """The cap closes that cohort alone: B, opened after it, still
    closes on its own count, and A's late member dispatches on
    arrival."""
    monkeypatch.setattr(batcher_mod, "COHORT_WAIT_MAX", 0.05)
    batcher = PlacementBatcher(window=WINDOW)
    a = batcher.open_cohort(2)
    only = _Call(batcher, a[0])
    only.finish()  # a[1] never came
    stats = batcher.stats()
    assert _closed(batcher) == {
        "cohort": 0, "window": 0, "full": 0, "cap": 1}, stats
    assert a[0].closed_by == "cap"
    assert stats["open_cohorts"] == 0
    monkeypatch.setattr(batcher_mod, "COHORT_WAIT_MAX", 60.0)
    b = batcher.open_cohort(2)
    b_first = _Call(batcher, b[0], seed=1)
    _wait_queued(batcher, 1)
    assert not b_first.done.is_set()
    b_second = _Call(batcher, b[1], seed=2)
    b_first.finish()
    b_second.finish()
    late = _Call(batcher, a[1], seed=3)  # its cohort has gone
    late.finish()
    stats = batcher.stats()
    assert _closed(batcher) == {
        "cohort": 2, "window": 0, "full": 0, "cap": 1}, stats
    assert a[1].closed_by == "cohort"
    assert stats["open_cohorts"] == 0 and stats["batched_requests"] == 4


def test_a_second_place_of_one_session_takes_nobody_elses_unit():
    """An inline replan calls place() again with the unit its first
    call closed: it finds its cohort complete, and a count that is not
    its own is not touched by it."""
    batcher = PlacementBatcher(window=WINDOW)
    (mine,) = batcher.open_cohort(1)
    _Call(batcher, mine).finish()
    _Call(batcher, mine, seed=1).finish()  # the replan: nothing to wait for
    assert _closed(batcher) == {
        "cohort": 2, "window": 0, "full": 0, "cap": 0}
    theirs = batcher.open_cohort(2)
    first = _Call(batcher, theirs[0], seed=2)
    _wait_queued(batcher, 1)
    replan = _Call(batcher, mine, seed=3)  # joins their queue, takes no unit
    _wait_queued(batcher, 2)
    assert not first.done.is_set()  # still waits for its own second
    assert batcher.stats()["open_cohorts"] == 1
    second = _Call(batcher, theirs[1], seed=4)
    for call in (first, replan, second):
        call.finish()
    stats = batcher.stats()
    assert _closed(batcher) == {
        "cohort": 3, "window": 0, "full": 0, "cap": 0}, stats
    assert stats["batched_requests"] == 5 and stats["open_cohorts"] == 0


def test_a_full_queue_goes_before_its_cohort_is_complete():
    batcher = PlacementBatcher(max_batch=2, window=WINDOW)
    units = batcher.open_cohort(3)
    calls = [_Call(batcher, u, seed=i) for i, u in enumerate(units[:2])]
    for call in calls:
        call.finish()
    assert _closed(batcher)["full"] == 1
    assert batcher.stats()["open_cohorts"] == 1
    _Call(batcher, units[2], seed=2).finish()
    assert _closed(batcher) == {
        "cohort": 1, "window": 0, "full": 1, "cap": 0}
    assert batcher.stats()["open_cohorts"] == 0


def test_an_unannounced_request_keeps_the_timed_window():
    batcher = PlacementBatcher(window=0.01)
    assert batcher.open_cohort(0) == []
    _Call(batcher).finish()
    stats = batcher.stats()
    assert _closed(batcher) == {
        "cohort": 0, "window": 1, "full": 0, "cap": 0}, stats
    assert stats["open_cohorts"] == 0


def test_many_cohorts_at_once_lose_no_unit():
    """More threads than cores and a short switch interval: twelve
    cohorts of four over two shape queues, three members placing and
    one settling, all at once. A lost update on a cohort's count would
    leave a dispatch to the cap or a cohort open."""
    import sys

    batcher = PlacementBatcher(window=WINDOW)
    cohorts = [batcher.open_cohort(4) for _ in range(12)]
    go = threading.Event()
    calls, settlers = [], []

    def settle_when_told(unit):
        go.wait(30.0)
        unit.settle()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for c, units in enumerate(cohorts):
            for i, unit in enumerate(units):
                if i == c % 4:
                    t = threading.Thread(target=settle_when_told,
                                         args=(unit,), daemon=True)
                    t.start()
                    settlers.append(t)
                else:
                    calls.append(_Call(batcher, unit, seed=4 * c + i,
                                       n=128 if c % 2 else 256))
        _wait_queued(batcher, 36)
        assert batcher.stats()["open_cohorts"] == 12
        go.set()
        for call in calls:
            call.finish()
        for t in settlers:
            t.join(timeout=30.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    stats = batcher.stats()
    closed = _closed(batcher)
    assert closed["window"] == 0 and closed["cap"] == 0, stats
    assert closed["cohort"] + closed["full"] == stats["dispatches"]
    assert stats["batched_requests"] == 36 and stats["open_cohorts"] == 0
