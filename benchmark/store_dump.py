"""Copy the final store out of the program as plain arrays.

No arithmetic here beyond the per-allocation total the reference
scheduler itself defines (an allocation's combined `resources`, or where
a plan stripped them its shared plus task resources, funcs.go:77-90):
the sums, the comparisons and the verdicts are `reference.py`'s.
"""

from __future__ import annotations

import numpy as np

READY = "ready"
LIVE_DESIRED = "run"
# The node meta keys that name a topology group (models/topology.py)
TOPOLOGY_KEYS = ("rack", "ici")
DEAD_CLIENT = ("complete", "failed", "lost")


def _alloc_total(alloc):
    if alloc.resources is not None:
        r = alloc.resources
        return r.cpu, r.memory_mb, r.disk_mb, r.iops
    cpu = mem = disk = iops = 0
    parts = list(alloc.task_resources.values())
    if alloc.shared_resources is not None:
        parts.append(alloc.shared_resources)
    for r in parts:
        cpu += r.cpu
        mem += r.memory_mb
        disk += r.disk_mb
        iops += r.iops
    return cpu, mem, disk, iops


def _priority(alloc) -> int:
    """The priority of the allocation's job as the store holds it, -1
    where the allocation carries no job."""
    return alloc.job.priority if alloc.job is not None else -1


def _evals(state) -> dict:
    """Every evaluation's job, status, trigger and predecessor: a check
    can then see that an evicted job got its follow-up evaluation."""
    evals = list(state.evals())
    return {"eval_ids": [ev.id for ev in evals],
            "eval_job": [ev.job_id for ev in evals],
            "eval_status": [ev.status for ev in evals],
            "eval_trigger": [ev.triggered_by for ev in evals],
            "eval_previous": [ev.previous_eval for ev in evals]}


def dump_store(state) -> dict:
    """`state` is the server's StateStore (or a snapshot of it)."""
    nodes = list(state.nodes())
    row = {node.id: i for i, node in enumerate(nodes)}
    n = len(nodes)
    cap = np.zeros((n, 4), np.float64)
    reserved = np.zeros((n, 4), np.float64)
    mbits = np.zeros(n, np.float64)
    reserved_mbits = np.zeros(n, np.float64)
    ready = np.zeros(n, bool)
    drain = np.zeros(n, bool)
    res_port_node, res_port_value = [], []
    for i, node in enumerate(nodes):
        r = node.resources
        cap[i] = (r.cpu, r.memory_mb, r.disk_mb, r.iops)
        mbits[i] = r.networks[0].mbits if r.networks else 0
        if node.reserved is not None:
            v = node.reserved
            reserved[i] = (v.cpu, v.memory_mb, v.disk_mb, v.iops)
            for net in v.networks:
                reserved_mbits[i] += net.mbits
                for p in list(net.reserved_ports) + list(net.dynamic_ports):
                    res_port_node.append(i)
                    res_port_value.append(p.value)
        ready[i] = node.status == READY
        drain[i] = bool(node.drain)

    job_ids, job_row = [], {}
    alloc_ids, alloc_eval = [], []
    alloc_node, alloc_job, usage, alloc_mbits = [], [], [], []
    alloc_priority, alloc_group, alloc_name = [], [], []
    port_alloc, port_value = [], []
    gone = {"ids": [], "node": [], "job": [], "priority": [], "desired": [],
            "group": []}
    for alloc in state.allocs():
        if alloc.desired_status != LIVE_DESIRED:
            # stopped or evicted: kept apart for a deployment's own
            # checks (who was evicted, from where, at what priority)
            gone["ids"].append(alloc.id)
            gone["node"].append(row.get(alloc.node_id, -1))
            gone["job"].append(alloc.job_id)
            gone["priority"].append(_priority(alloc))
            gone["desired"].append(alloc.desired_status)
            gone["group"].append(alloc.task_group)
            continue
        if alloc.client_status in DEAD_CLIENT:
            continue
        a = len(alloc_ids)
        alloc_ids.append(alloc.id)
        alloc_eval.append(alloc.eval_id)
        alloc_node.append(row.get(alloc.node_id, -1))
        j = job_row.get(alloc.job_id)
        if j is None:
            j = job_row[alloc.job_id] = len(job_ids)
            job_ids.append(alloc.job_id)
        alloc_job.append(j)
        usage.append(_alloc_total(alloc))
        alloc_priority.append(_priority(alloc))
        alloc_group.append(alloc.task_group)
        alloc_name.append(alloc.name)
        bw = 0
        # The first network of each task is the one the reference's
        # NetworkIndex counts (network.go AddAllocs).
        for task_res in alloc.task_resources.values():
            if not task_res.networks:
                continue
            net = task_res.networks[0]
            bw += net.mbits
            for p in list(net.reserved_ports) + list(net.dynamic_ports):
                port_alloc.append(a)
                port_value.append(p.value)
        alloc_mbits.append(bw)

    return {
        "node_ids": [node.id for node in nodes],
        "node_cap": cap, "node_reserved": reserved,
        "node_mbits": mbits, "node_reserved_mbits": reserved_mbits,
        "node_ready": ready, "node_drain": drain,
        "reserved_port_node": np.asarray(res_port_node, np.int64),
        "reserved_port_value": np.asarray(res_port_value, np.int64),
        "alloc_ids": alloc_ids, "alloc_eval": alloc_eval,
        "alloc_node": np.asarray(alloc_node, np.int64),
        "alloc_job": np.asarray(alloc_job, np.int64),
        "alloc_usage": np.asarray(usage, np.float64).reshape(-1, 4),
        "alloc_mbits": np.asarray(alloc_mbits, np.float64),
        "port_alloc": np.asarray(port_alloc, np.int64),
        "port_value": np.asarray(port_value, np.int64),
        "job_ids": job_ids,
        "alloc_priority": np.asarray(alloc_priority, np.int64),
        "gone_ids": gone["ids"],
        "gone_node": np.asarray(gone["node"], np.int64),
        "gone_job": gone["job"],
        "gone_priority": np.asarray(gone["priority"], np.int64),
        "gone_desired": gone["desired"],
        **_evals(state),
        # for a deployment's own checks (racks, gangs): plain lists, one
        # string a node and level ("" where the node states none), the
        # task group and the name of each live allocation in the order of
        # `alloc_ids`, the task group of each gone one
        "node_meta": {key: [node.meta.get(key) or "" for node in nodes]
                      for key in TOPOLOGY_KEYS},
        "alloc_group": alloc_group,
        "alloc_name": alloc_name,
        "gone_group": gone["group"],
    }
