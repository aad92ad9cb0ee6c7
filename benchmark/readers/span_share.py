"""The time of some of the program's host-clock stages as a share of the
time of others, over the samples that landed inside the window. Args:
`num`, `den`: lists of stages (names in `nomad_tpu/trace/span.py`; `e2e`
is the whole eval).

A stage's time in the window is read off the difference of the two
bucket readings, as `span.py` does: each bucket's count times the
bucket's geometric middle. A bucket is 19% wide (`stats.HIST_RATIO`), so
a sample lies within 9% of the middle it is counted at and a sum of many
is closer; shares of stages whose samples crowd one bucket's edge can
still be off by that much, and a set of shares that partitions a whole
sums to 1 only within it. Nothing to read where the denominator's stages
have no sample (a program without them: the parent of the PR that added
the stage)."""

import math

import stats


def bucket_middle_ms(i: int) -> float:
    upper = stats.bucket_upper_ms(i)
    return upper / math.sqrt(stats.HIST_RATIO) if i >= 2 else upper


def mass_ms(stages, ctx: dict) -> float:
    """Summed time in ms of `stages` inside the window."""
    total = 0.0
    for stage in stages:
        _count, buckets = stats.bucket_delta(
            ctx["spans_before"].get(stage), ctx["spans_after"].get(stage))
        total += sum(c * bucket_middle_ms(i)
                     for i, c in enumerate(buckets) if c)
    return total


def read(args: dict, ctx: dict):
    den = mass_ms(args["den"], ctx)
    if not den:
        return None
    return mass_ms(args["num"], ctx) / den
