"""A percentile of a series the load generator timed on its own clock,
over the evaluations registered inside the window. Args: `series`
(`late_ms`: terminal status seen to the next register call's start;
`register_ms`: the register call alone; `place_ms`), `q`."""

import stats


def read(args: dict, ctx: dict):
    values = ctx["client"].get(args["series"]) or []
    if not values:
        return None
    return stats.percentile(values, args["q"])
