"""A reading the harness took once during set-up. Args: `key`
(`trivial_rtt_us`: median round trip of a near-empty jitted program)."""


def read(args: dict, ctx: dict):
    return ctx["setup"].get(args["key"])
