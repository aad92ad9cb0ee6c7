"""From a profiler trace (`.xplane.pb`) to the device's busy time and the
operations that took it. Nothing but `jax.profiler.ProfileData` is needed
to read the file.

A device plane is one chip (`/device:TPU:<n>`). Busy is the union of the
intervals in which an operation ran on it: the events of its `XLA Ops`
line where the trace has one, of every line but the derived ones
otherwise. Averaged over the chips used; the idle share is
1 - busy_s / window_s, which the driver works out.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# Lines that restate other lines' events at a coarser grain; counting
# them would not change a union but would double the per-op table.
DERIVED_LINES = ("Steps", "XLA Modules", "Framework Ops",
                 "Framework Name Scope", "Source code")
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_length_ns(intervals) -> float:
    """Total length covered by [(start, end)] intervals."""
    total = 0.0
    cur_start = cur_end = None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def gaps_ns(intervals, limit: int = TOP) -> list:
    """The longest gaps between merged intervals, longest first."""
    gaps = []
    cur_end = None
    for start, end in sorted(intervals):
        if cur_end is not None and start > cur_end:
            gaps.append(start - cur_end)
        cur_end = end if cur_end is None else max(cur_end, end)
    return sorted(gaps, reverse=True)[:limit]


def op_name(event_name: str) -> str:
    """The trace names a device operation by its whole HLO line,
    `%while.3 = (s32[], ...) while(...)`: keep the name before ` = `."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:64]


def plane_ops(plane, line_prefix: str = None) -> list:
    """[(name, start_ns, end_ns)] of the events that are operations.
    `line_prefix` is for a rehearsal on the CPU backend, whose operations
    run on host threads named by it."""
    lines = list(plane.lines)
    if line_prefix is not None:
        chosen = [ln for ln in lines if ln.name.startswith(line_prefix)]
    else:
        chosen = [ln for ln in lines if ln.name == OPS_LINE] or \
            [ln for ln in lines if ln.name not in DERIVED_LINES]
    return [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
            for ln in chosen for ev in ln.events if ev.duration_ns > 0]


def reduce_planes(planes) -> dict:
    """`planes`: {plane name: [(op, start_ns, end_ns)]} of the device
    planes. Busy seconds averaged over them, the operations by total
    time, and the longest idle gaps (of the busiest plane; unattributed:
    the program carries no host annotation to name them by)."""
    if not planes:
        return {"busy_s": 0.0, "planes": 0, "device_ops": [],
                "idle_gaps": []}
    busy = {name: union_length_ns([(s, e) for _, s, e in ops])
            for name, ops in planes.items()}
    by_op: dict = {}
    for ops in planes.values():
        for name, s, e in ops:
            by_op[name] = by_op.get(name, 0.0) + (e - s)
    top = sorted(by_op.items(), key=lambda kv: kv[1], reverse=True)[:TOP]
    busiest = max(busy, key=busy.get)
    gaps = gaps_ns([(s, e) for _, s, e in planes[busiest]])
    return {
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "planes": len(planes),
        "device_ops": [[name, ns / 1e9] for name, ns in top],
        "idle_gaps": [["unattributed", ns / 1e9] for ns in gaps],
    }


def reduce_trace(trace_dir: str, device_prefix: str = DEVICE_PREFIX,
                 line_prefix: str = None) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    planes = {p.name: plane_ops(p, line_prefix) for p in data.planes
              if p.name.startswith(device_prefix)}
    planes = {name: ops for name, ops in planes.items() if ops}
    return reduce_planes(planes)
