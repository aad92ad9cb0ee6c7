"""A number from the profiler trace of the traced stretch (`--trace 1`
only; `tracereduce.py` makes the reduction). Args: `value`:
`busy_ms_per_eval` is the union of the device plane's event intervals
over the evaluations the generator saw complete in the same stretch."""


def read(args: dict, ctx: dict):
    profile = ctx.get("profile")
    if not profile:
        return None
    if args["value"] == "busy_ms_per_eval":
        if not profile["evals_completed"]:
            return None
        return profile["busy_s"] * 1000.0 / profile["evals_completed"]
    return profile.get(args["value"])
