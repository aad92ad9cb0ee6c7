"""One window difference over another. Args: `num`, `den`: counters as in
`counter.py`, or `evals_completed` (evaluations the generator saw complete
inside the window). Nothing to read where the denominator did not move."""


def _delta(name: str, ctx: dict):
    if name == "evals_completed":
        return ctx["evals_completed"]
    if name not in ctx["counters_after"]:
        return None
    return ctx["counters_after"][name] - ctx["counters_before"].get(name, 0)


def read(args: dict, ctx: dict):
    num, den = _delta(args["num"], ctx), _delta(args["den"], ctx)
    if num is None or not den:
        return None
    return num / den
