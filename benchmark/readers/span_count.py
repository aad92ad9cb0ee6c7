"""How many samples one of the program's host-clock spans took inside
the window, over a count the harness has: from the same two bucket
readings `span.py` takes a percentile of. Args: `stage` (a name in
`nomad_tpu/trace/span.py`), `den` (`evals_completed`: evaluations the
generator saw complete inside the window). Nothing to read where the
program has no such span (the parent of the PR that added it), or where
the denominator is 0."""

import stats


def read(args: dict, ctx: dict):
    if args["stage"] not in ctx["spans_after"]:
        return None
    den = ctx.get(args["den"])
    if not den:
        return None
    count, _buckets = stats.bucket_delta(
        ctx["spans_before"].get(args["stage"]),
        ctx["spans_after"][args["stage"]])
    return count / den
