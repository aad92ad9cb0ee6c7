"""Placement fit and scoring primitives — the kernel the TPU path
vectorizes.

Reference: nomad/structs/funcs.go:60 (AllocsFit), :123 (ScoreFit,
Google BestFit-v3).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .alloc import Allocation
from .network import NetworkIndex
from .node import Node
from .resources import Resources


def allocs_fit(
    node: Node,
    allocs: List[Allocation],
    net_idx: Optional[NetworkIndex] = None,
) -> Tuple[bool, str, Resources]:
    """Whether the set of allocs (plus the node's reserved resources) fits
    on the node. Returns (fit, exhausted-dimension, utilization)."""
    used = Resources()
    if node.reserved:
        used.add(node.reserved)

    for alloc in allocs:
        if alloc.resources is not None:
            used.add(alloc.resources)
        elif alloc.task_resources:
            # Plan allocs carry the combined resources stripped; sum the
            # shared ask plus each task's resources (funcs.go:77-90).
            used.add(alloc.shared_resources)
            for task_res in alloc.task_resources.values():
                used.add(task_res)
        else:
            raise ValueError(f"allocation {alloc.id!r} has no resources set")

    ok, dimension = node.resources.superset(used)
    if not ok:
        return False, dimension, used

    if net_idx is None:
        net_idx = NetworkIndex()
        if net_idx.set_node(node) or net_idx.add_allocs(allocs):
            return False, "reserved port collision", used

    if net_idx.overcommitted():
        return False, "bandwidth exceeded", used

    return True, "", used


def usage_fits(
    capacity: Resources,
    cpu: int,
    memory_mb: int,
    disk_mb: int,
    iops: int,
    port_collision: bool,
    used_bandwidth: Dict[str, int],
    avail_bandwidth: Dict[str, int],
) -> Tuple[bool, str]:
    """allocs_fit's rule over the sums it would have made: the node's
    reserved included in the four, a port held twice or out of range
    as `port_collision`, bandwidth by device. The same three verdicts
    in the same order, for a caller that carries the sums forward
    instead of adding every allocation up again
    (server/plan_apply.py NodeSummary)."""
    if capacity.cpu < cpu:
        return False, "cpu"
    if capacity.memory_mb < memory_mb:
        return False, "memory"
    if capacity.disk_mb < disk_mb:
        return False, "disk"
    if capacity.iops < iops:
        return False, "iops"
    if port_collision:
        return False, "reserved port collision"
    for device, used in used_bandwidth.items():
        if used > avail_bandwidth.get(device, 0):
            return False, "bandwidth exceeded"
    return True, ""


def score_fit(node: Node, util: Resources) -> float:
    """BestFit-v3: 20 - (10^free_cpu_frac + 10^free_mem_frac), clamped to
    [0, 18]. Packed nodes score high; empty nodes score 0.

    Note: util (from allocs_fit) includes node.reserved while the
    denominator subtracts it — reference parity (funcs.go:123-131 does
    the same), so reserved-heavy nodes score as partially packed."""
    node_cpu = float(node.resources.cpu)
    node_mem = float(node.resources.memory_mb)
    if node.reserved:
        node_cpu -= node.reserved.cpu
        node_mem -= node.reserved.memory_mb
    if node_cpu <= 0 or node_mem <= 0:
        # Fully-reserved node: nothing schedulable, worst score.
        return 0.0

    free_pct_cpu = 1.0 - (util.cpu / node_cpu)
    free_pct_mem = 1.0 - (util.memory_mb / node_mem)
    total = 10.0**free_pct_cpu + 10.0**free_pct_mem
    score = 20.0 - total
    return max(0.0, min(18.0, score))
