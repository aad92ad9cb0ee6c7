"""`span_count.py` for a span that marks something rare: how many
samples one of the program's spans took inside the window, over a count
the harness has, and 0, not nothing, where the program declares the
span (its name is in `nomad_tpu/trace/span.py` `ALL_STAGES`) and has
not recorded one in its life. `span_count.py` reads the stage table,
which has no row before a span's first sample, so a cell in which the
marked thing stopped happening would lose the metric that says so.
Nothing to read where the program does not declare the span (the parent
of the PR that added it), or where the denominator is 0. Args: `stage`,
`den` (`evals_completed`)."""

import stats


def read(args: dict, ctx: dict):
    from nomad_tpu.trace import span as program_spans

    if args["stage"] not in program_spans.ALL_STAGES:
        return None
    den = ctx.get(args["den"])
    if not den:
        return None
    count, _buckets = stats.bucket_delta(
        ctx["spans_before"].get(args["stage"]),
        ctx["spans_after"].get(args["stage"]))
    return count / den
