"""`span_count.py` over one of the program's counters: how many samples
one of the program's spans (or rows of its stage table) took inside the
window, over how far a counter moved in it. Args: `stage` (a name in
`nomad_tpu/trace/span.py`), `den` (a counter as in `counter.py`).
Nothing to read where the program does not declare the stage (the
parent of the PR that added it), or where the counter did not move; 0
where it declares the stage and has not recorded a sample in its
life."""

import stats


def read(args: dict, ctx: dict):
    from nomad_tpu.trace import span as program_spans

    declared = program_spans.ALL_STAGES + getattr(
        program_spans, "BATCHER_ROW_STAGES", ())
    if args["stage"] not in declared:
        return None
    after = ctx["counters_after"].get(args["den"])
    if after is None:
        return None
    den = after - ctx["counters_before"].get(args["den"], 0)
    if not den:
        return None
    count, _buckets = stats.bucket_delta(
        ctx["spans_before"].get(args["stage"]),
        ctx["spans_after"].get(args["stage"]))
    return count / den
