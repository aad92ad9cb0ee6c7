"""From a profiler trace (`.xplane.pb`) to the device's time by program
and by phase, and its idle gaps named by what the host was doing.

The program marks its batch-level host regions with
`jax.profiler.TraceAnnotation` under `nomad.*` names (trace/README.md),
and the phases inside the placement programs with `jax.named_scope`
(ops/binpack.py). The profiler puts both on one clock with the device's
own events, so a gap between two device operations can be named by the
host annotation that covers most of it. `tools/traceconv.py --xplane`
is the command line over this module; nothing but
`jax.profiler.ProfileData` is needed to read the file.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SCOPE_LINE = "Framework Name Scope"
ANNOTATION_PREFIX = "nomad."
NO_ANNOTATION = "no host annotation"

Interval = Tuple[float, float]


def find_xplane(path: str) -> str:
    """`path` is the file itself, or a directory as
    `jax.profiler.start_trace` leaves it (the newest run's file)."""
    if os.path.isfile(path):
        return path
    found = sorted(
        glob.glob(os.path.join(path, "plugins", "profile", "*",
                               "*.xplane.pb"))
        + glob.glob(os.path.join(path, "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def merged(intervals: List[Interval]) -> List[Interval]:
    """The union of `intervals` as disjoint intervals in order."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def gaps(busy: List[Interval]) -> List[Interval]:
    """The idle intervals between merged busy intervals."""
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def overlap(interval: Interval, disjoint: List[Interval]) -> float:
    """Length of `interval` covered by `disjoint` (as merged() gives
    them: in order, not touching)."""
    lo, hi = interval
    total = 0.0
    at = bisect.bisect_right(disjoint, (lo, float("inf")))
    for s, e in disjoint[max(at - 1, 0):]:
        if s >= hi:
            break
        total += max(0.0, min(hi, e) - max(lo, s))
    return total


def name_gap(gap: Interval,
             annotations: Dict[str, List[Interval]]) -> List[list]:
    """[[annotation name, ns of the gap it covers]], most first; empty
    where no annotation touches the gap. `annotations` holds merged()
    intervals."""
    cover = [[name, overlap(gap, spans)]
             for name, spans in annotations.items()]
    return sorted((c for c in cover if c[1] > 0),
                  key=lambda c: c[1], reverse=True)


def by_name(events: List[Tuple[str, float, float]]) -> List[list]:
    """[[name, seconds, count]] of `(name, start_ns, end_ns)` events,
    most time first."""
    total: Dict[str, List[float]] = {}
    for name, start, end in events:
        row = total.setdefault(name, [0.0, 0])
        row[0] += end - start
        row[1] += 1
    return sorted(([name, ns / 1e9, n] for name, (ns, n) in total.items()),
                  key=lambda r: r[1], reverse=True)


def reduce_events(device: Dict[str, Dict[str, list]],
                  annotations: Dict[str, List[Interval]],
                  top: int = 10) -> dict:
    """`device`: {plane: {line name: [(event name, start_ns, end_ns)]}}
    of the device planes; `annotations`: {name: [(start_ns, end_ns)]} of
    the host's `nomad.*` regions. The busiest plane's busy time, its
    time by module and by named scope, and its idle gaps, longest first,
    each named by the annotation that covers most of it (`idle_gaps`, in
    the shape of the benchmark's `breakdown.idle_gaps`), with every
    annotation that touches it beside (`idle_gap_cover`), and the whole
    idle time between the first and the last operation by that naming
    (`idle_by_name`)."""
    busy_of = {plane: merged([(s, e) for _, s, e in lines.get(OPS_LINE, [])])
               for plane, lines in device.items()}
    busy_of = {plane: busy for plane, busy in busy_of.items() if busy}
    seen = by_name([(n, s, e) for n, spans in annotations.items()
                    for s, e in spans])
    if not busy_of:
        return {"plane": None, "busy_s": 0.0, "span_s": 0.0, "modules": [],
                "scopes": [], "idle_gaps": [], "idle_gap_cover": [],
                "idle_by_name": [], "annotations": seen}
    plane = max(busy_of, key=lambda p: sum(e - s for s, e in busy_of[p]))
    busy = busy_of[plane]
    disjoint = {name: merged(spans) for name, spans in annotations.items()}
    named = []
    for gap in gaps(busy):
        cover = name_gap(gap, disjoint)
        named.append((gap[1] - gap[0],
                      cover[0][0] if cover else NO_ANNOTATION, cover))
    named.sort(key=lambda g: g[0], reverse=True)
    idle: Dict[str, float] = {}
    for ns, name, _cover in named:
        idle[name] = idle.get(name, 0.0) + ns
    return {
        "plane": plane,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "span_s": (busy[-1][1] - busy[0][0]) / 1e9,
        "modules": by_name(device[plane].get(MODULES_LINE, []))[:top],
        "scopes": by_name(device[plane].get(SCOPE_LINE, []))[:top],
        "idle_gaps": [[name, ns / 1e9] for ns, name, _ in named[:top]],
        "idle_gap_cover": [[[n, c / 1e9] for n, c in cover]
                           for _, _, cover in named[:top]],
        "idle_by_name": sorted(([n, ns / 1e9] for n, ns in idle.items()),
                               key=lambda r: r[1], reverse=True),
        "annotations": seen,
    }


def module_name(event_name: str) -> str:
    """`jit_batched_placement_program_compact(1129...)`: the program's
    name without the fingerprint."""
    return event_name.split("(", 1)[0]


def annotation_name(event_name: str) -> str:
    """A TraceMe's arguments may ride its name as `name#k=v,...#`."""
    return event_name.split("#", 1)[0]


def read_xplane(path: str, device_prefix: str = DEVICE_PREFIX):
    """(device, annotations) of one `.xplane.pb`, as reduce_events takes
    them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    device: Dict[str, Dict[str, list]] = {}
    annotations: Dict[str, List[Interval]] = {}
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                rename = module_name if line.name == MODULES_LINE else str
                lines[line.name] = [
                    (rename(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.duration_ns > 0]
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.setdefault(
                            annotation_name(ev.name), []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return device, annotations


def reduce_xplane(path: str, top: int = 10,
                  device_prefix: str = DEVICE_PREFIX) -> dict:
    device, annotations = read_xplane(path, device_prefix)
    return reduce_events(device, annotations, top)
