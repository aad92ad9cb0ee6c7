"""How far one of the program's counters moved inside the window. Args:
`counter`, as `<group>.<key>` of the harness's counter reading
(`counters.py`)."""


def read(args: dict, ctx: dict):
    name = args["counter"]
    if name not in ctx["counters_after"]:
        return None
    return ctx["counters_after"][name] - ctx["counters_before"].get(name, 0)
