"""A percentile of one of the program's host-clock spans, over the
samples that landed inside the window: the program's histograms
accumulate over the process's life, so the harness reads the buckets at
the window's start and end and this takes the difference. Args: `stage`
(a name in `nomad_tpu/trace/span.py`), `q`.

Note for `device.solve`: the span closes after the batcher has pulled the
placements back to the host, so it ends in a device sync and covers
transfer, compute and read-back of one dispatch (scheduler/batcher.py
`_run_batch` / `_record_solve`). It is a host-clock time, not a device
time."""

import stats


def read(args: dict, ctx: dict):
    count, buckets = stats.bucket_delta(
        ctx["spans_before"].get(args["stage"]),
        ctx["spans_after"].get(args["stage"]))
    return stats.bucket_percentile_ms(buckets, count, args["q"])
