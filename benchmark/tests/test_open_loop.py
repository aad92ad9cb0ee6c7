"""The open loop: its schedule (drawn from the seed before the window),
how the window and a latency count from when a request was due, and the
pool that sends a request late rather than not at all."""

import math
import random
import threading
import time

import pytest

import plugins
import stats

open_loop = plugins.load("generators", "open")
SECONDS = 51.0


def counts_per_second(times, seconds):
    counts = [0] * int(seconds)
    for t in times:
        counts[int(t)] += 1
    return counts


def dispersion(counts):
    mean = sum(counts) / len(counts)
    return sum((c - mean) ** 2 for c in counts) / (len(counts) - 1) / mean


@pytest.mark.parametrize("arrivals,index", [
    ({"process": "poisson", "rate_evals_per_s": 40}, 1),
    ({"process": "bursts", "rate_evals_per_s": 40, "burst_size": 8}, 8),
])
def test_window_schedule(arrivals, index):
    readings = []
    for seed in range(40):
        times = open_loop.window_schedule(arrivals, SECONDS,
                                          random.Random(seed))
        assert times == sorted(times) and 0 < times[0] and times[-1] < SECONDS
        # the mean rate is the file's: every seed holds the same work
        assert len(times) / SECONDS == pytest.approx(
            arrivals["rate_evals_per_s"], rel=0.02)
        readings.append(dispersion(counts_per_second(times, SECONDS)))
    # variance over mean of the counts a second: 1 for Poisson arrivals,
    # the burst's size for bursts (a little under, the count being fixed)
    assert sum(readings) / len(readings) == pytest.approx(index, rel=0.1)
    again = open_loop.window_schedule(arrivals, SECONDS, random.Random(7))
    assert again == open_loop.window_schedule(arrivals, SECONDS,
                                              random.Random(7))
    other = open_loop.window_schedule(arrivals, SECONDS, random.Random(8))
    assert again != other

    # every seed has the same gaps, in another order: as many requests
    # come close together under one seed as under another
    def gaps(times):
        epochs = times[::index]
        return sorted(round(b - a, 9) for a, b in zip([0.0] + epochs,
                                                      epochs + [SECONDS]))
    assert gaps(again) == gaps(other)
    mean = sum(gaps(again)) / len(gaps(again))
    assert sum(1 for g in gaps(again) if g < mean) / len(gaps(again)) \
        == pytest.approx(1 - math.exp(-1), abs=0.01)    # exponential
    if index > 1:
        assert all(len(set(again[i:i + index])) == 1
                   for i in range(0, len(again), index))


@pytest.mark.parametrize("arrivals,index", [
    ({"process": "poisson", "rate_evals_per_s": 40}, 1),
    ({"process": "bursts", "rate_evals_per_s": 40, "burst_size": 8}, 8),
])
def test_open_ended_stream(arrivals, index):
    """Warm-up's stream is the same process by exponential gaps."""
    stream = open_loop.open_ended(arrivals, random.Random(3))
    times = []
    for t in stream:
        if t >= 2000.0:
            break
        times.append(t)
    assert len(times) / 2000.0 == pytest.approx(40, rel=0.02)
    assert dispersion(counts_per_second(times, 2000)) == pytest.approx(
        index, rel=0.1)


def test_unknown_process_is_refused():
    with pytest.raises(ValueError):
        open_loop.window_schedule(
            {"process": "uniform", "rate_evals_per_s": 1}, 5, random.Random(1))


def test_window_shapes_keep_the_shares():
    jobs = [{"name": "prod", "share": 0.2}, {"name": "batch", "share": 0.5},
            {"name": "free", "share": 0.3}]
    for seed in (1, 2):
        shapes = open_loop.window_shapes(1001, jobs, random.Random(seed))
        assert sorted(shapes.count(i) for i in range(3)) == [200, 300, 501]
    assert open_loop.window_shapes(1001, jobs, random.Random(1)) \
        != open_loop.window_shapes(1001, jobs, random.Random(2))
    assert open_loop.window_shapes(5, jobs[:1], random.Random(1)) == [0] * 5


def test_window_and_latency_count_from_when_a_request_was_due():
    def sample(t_due, t_reg, t_term, status="complete"):
        return {"t_due": t_due, "t_register": t_reg, "t_terminal": t_term,
                "status": status}

    samples = [
        sample(9.9, 10.1, 11.0),     # due before the window, sent inside
        sample(19.9, 20.4, 21.0),    # due inside, sent after its end: late
        sample(12.0, 12.001, 12.5),
        sample(15.0, None, None, "unsent"),
        sample(None, 13.0, 14.0),    # a round of warm-up: no due time
    ]
    win = stats.window_samples(samples, 10.0, 20.0)
    assert [s["t_due"] for s in win["registered"]] == [19.9, 12.0, 15.0, None]
    assert [s["t_terminal"] for s in win["completed"]] == [11.0, 12.5, 14.0]
    # the stall is charged to the request it held up
    assert stats.latency_ms(samples[1]) == pytest.approx(1100.0)
    assert stats.latency_ms(samples[4]) == pytest.approx(1000.0)
    assert stats.due(samples[3]) == 15.0


def test_without_a_due_time_nothing_changes():
    samples = [{"t_register": 10.5, "t_terminal": 12.0, "status": "complete"},
               {"t_register": 9.0, "t_terminal": 10.5, "status": "complete"}]
    win = stats.window_samples(samples, 10.0, 20.0)
    assert win["registered"] == samples[:1] and win["completed"] == samples
    assert stats.latency_ms(samples[0]) == pytest.approx(1500.0)


def test_a_busy_pool_sends_late_and_never_drops():
    started, lock = [], threading.Lock()

    def work(item, state):
        with lock:
            started.append((item, state["number"], time.monotonic()))
        time.sleep(0.05)

    pool = open_loop.Pool(2, work)
    t0 = time.monotonic()
    for i in range(6):
        pool.submit(i)
    pool.wait(lambda: t0 + 5.0)
    pool.close()
    for t in pool.threads:
        t.join(2.0)
    assert sorted(item for item, _, _ in started) == list(range(6))
    assert len(pool.threads) == 2 and pool.outstanding == 0
    # two at a time: the last pair waited for two turns of the others
    assert max(t for _, _, t in started) - t0 >= 0.09
    # an idle pool makes no thread it does not need
    pool = open_loop.Pool(8, work)
    for i in range(3):
        pool.submit(i)
        pool.wait(lambda: time.monotonic() + 5.0)
    pool.close()
    assert len(pool.threads) == 1
