import pytest

import plugins
import stats


def reader(name):
    return plugins.load("readers", name)


def test_percentile_interpolates_like_numpy():
    import numpy as np

    values = [5.0, 1.0, 9.0, 3.0, 7.0, 11.0]
    for q in (0.0, 0.5, 0.95, 1.0):
        assert stats.percentile(values, q) == pytest.approx(
            float(np.quantile(values, q)))
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_ladder_is_the_programs():
    from nomad_tpu.utils import metrics

    assert stats.HIST_MIN_MS == metrics.HIST_MIN_MS
    assert stats.HIST_RATIO == metrics.HIST_RATIO
    assert stats.HIST_BUCKETS == metrics.HIST_BUCKETS
    for i in (0, 1, 2, 57, 199):
        assert stats.bucket_upper_ms(i) == metrics.hist_bucket_upper(i)


def test_bucket_difference_reads_only_the_window():
    from nomad_tpu.utils.metrics import LatencyHist

    h = LatencyHist()
    for _ in range(100):
        h.observe(1000.0)           # before the window: slow
    before = (h.count, list(h.buckets))
    for _ in range(40):
        h.observe(10.0)             # inside the window: fast
    after = (h.count, list(h.buckets))
    ctx = {"spans_before": {"device.solve": before},
           "spans_after": {"device.solve": after}}
    p50 = reader("span").read({"stage": "device.solve", "q": 0.5}, ctx)
    assert 8.4 < p50 < 11.9         # one bucket is 19% wide
    # a stage that first appears inside the window, and one that never does
    ctx = {"spans_before": {}, "spans_after": {"device.solve": after}}
    assert reader("span").read({"stage": "device.solve", "q": 0.5}, ctx) > 500
    assert reader("span").read({"stage": "nothing", "q": 0.5}, ctx) is None
    with pytest.raises(ValueError):
        stats.bucket_delta(after, before)


def test_window_arithmetic():
    def sample(t_reg, t_term, status="complete"):
        return {"t_register": t_reg, "t_terminal": t_term, "status": status}

    samples = [
        sample(1.0, 9.0),            # registered before, done inside
        sample(10.5, 12.0),          # both inside
        sample(19.0, 23.0),          # registered inside, done in the drain
        sample(20.0, 21.0),          # registered at the end: outside
        sample(11.0, 13.0, "failed"),
        sample(12.0, None, "unfinished"),
    ]
    win = stats.window_samples(samples, 10.0, 20.0)
    assert len(win["registered"]) == 4
    assert [s["t_register"] for s in win["completed"]] == [10.5]
    win = stats.window_samples(samples, 5.0, 20.0)
    assert [s["t_register"] for s in win["completed"]] == [1.0, 10.5]
    assert stats.latency_ms(samples[1]) == pytest.approx(1500.0)


def test_ratio_and_counter_readers():
    ctx = {"counters_before": {"batcher.batched_requests": 10,
                               "batcher.dispatches": 2},
           "counters_after": {"batcher.batched_requests": 70,
                              "batcher.dispatches": 5},
           "evals_completed": 50}
    ratio = reader("ratio").read
    assert ratio({"num": "batcher.batched_requests",
                  "den": "batcher.dispatches"}, ctx) == 20.0
    assert ratio({"num": "batcher.batched_requests",
                  "den": "evals_completed"}, ctx) == 1.2
    assert ratio({"num": "batcher.batched_requests", "den": "missing"},
                 ctx) is None
    assert reader("counter").read({"counter": "batcher.dispatches"}, ctx) == 3
    assert reader("client").read({"series": "late_ms", "q": 0.95},
                                 {"client": {"late_ms": []}}) is None
