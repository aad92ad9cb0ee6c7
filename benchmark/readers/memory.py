"""Peak device memory after the window, from
`device.memory_stats()["peak_bytes_in_use"]` of the fullest chip. Args:
`scale` (1e-6 for MB)."""


def read(args: dict, ctx: dict):
    if ctx.get("memory_peak_bytes") is None:
        return None
    return ctx["memory_peak_bytes"] * args["scale"]
