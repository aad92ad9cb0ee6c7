"""Metric arithmetic of the benchmark: percentiles of client samples,
window accounting, and percentiles over the difference of two log-bucket
histogram readings.

The bucket ladder is a copy of the program's (`nomad_tpu/utils/metrics.py`:
HIST_MIN_MS, HIST_RATIO, HIST_BUCKETS); a test compares the two, so a
change of the program's ladder shows there and not as a wrong number.
"""

from __future__ import annotations

import math

HIST_MIN_MS = 1e-3
HIST_RATIO = 2.0 ** 0.25
HIST_BUCKETS = 200


def percentile(values, q: float) -> float:
    """The q-quantile (0..1) of `values` by linear interpolation between
    closest ranks (numpy's default). Raises on an empty list: a metric
    with no sample is left out by its caller, never reported as 0."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    pos = q * (len(ordered) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def bucket_upper_ms(i: int) -> float:
    """Inclusive upper bound of bucket `i` in milliseconds."""
    if i <= 0:
        return 0.0
    if i == 1:
        return HIST_MIN_MS
    return HIST_MIN_MS * HIST_RATIO ** (i - 1)


def bucket_delta(before, after):
    """(count, buckets) that landed between two `stage_buckets()`
    readings. Either reading may be None (no sample yet)."""
    if after is None:
        return 0, [0] * HIST_BUCKETS
    count_after, buckets_after = after
    if before is None:
        return count_after, list(buckets_after)
    count_before, buckets_before = before
    delta = [a - b for a, b in zip(buckets_after, buckets_before)]
    if min(delta) < 0:
        raise ValueError("histogram went backwards between two readings")
    return count_after - count_before, delta


def bucket_percentile_ms(buckets, count: int, q: float):
    """The q-quantile read off bucket counts, in milliseconds: the
    geometric middle of the bucket in which the cumulative count crosses
    rank ceil(q * count). None when the window holds no sample."""
    if count <= 0:
        return None
    rank = max(1, math.ceil(q * count))
    cum = 0
    for i, c in enumerate(buckets):
        cum += c
        if cum >= rank:
            upper = bucket_upper_ms(i)
            return upper / math.sqrt(HIST_RATIO) if i >= 2 else upper
    return bucket_upper_ms(len(buckets) - 1)


def due(sample) -> float:
    """When the request was due: an open loop's schedule says
    (`t_due`); a closed loop's request is due when its client sends it,
    so a sample without `t_due` counts from `t_register`."""
    t_due = sample.get("t_due")
    return sample["t_register"] if t_due is None else t_due


def window_samples(samples, start: float, end: float) -> dict:
    """Split the generator's samples by the window [start, end).

    `registered`: evals that were due inside the window: whose register
    call started there or, in an open loop, was scheduled there, however
    late it was sent (the latency population and `attempted`).
    `completed`: evals seen complete inside the window, whenever they
    were registered (the throughput population)."""
    registered = [s for s in samples if start <= due(s) < end]
    completed = [s for s in samples
                 if s["status"] == "complete" and s["t_terminal"] is not None
                 and start <= s["t_terminal"] < end]
    return {"registered": registered, "completed": completed}


def latency_ms(sample) -> float:
    """From when the request was due, so that a stall is charged to
    every request it held up (no coordinated omission)."""
    return (sample["t_terminal"] - due(sample)) * 1000.0
