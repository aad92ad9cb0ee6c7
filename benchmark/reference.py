"""The plain reference: what a valid placement is, in NumPy at float64.

It imports nothing of `nomad_tpu` and takes nothing the program computed:
its input is the final store as plain arrays (`store_dump.py` copies the
fields out, no arithmetic), its output the numbers `run.py` holds against
their limits. The rules are the reference scheduler's own
(`nomad/structs/funcs.go` AllocsFit and ScoreFit, `network.go`
NetworkIndex): on a node, reserved plus the live allocations' cpu,
memory, disk and iops stay within capacity, their bandwidth within the
device's, no port is held twice, the node is ready and not draining, and
a job under `distinct_hosts` holds at most one allocation a node.

A store is a dict of arrays over N nodes, A live allocations and P held
ports:

    node_cap [N,4] node_reserved [N,4]   (cpu MHz, memory MB, disk MB, iops)
    node_mbits [N] node_reserved_mbits [N] node_ready [N] node_drain [N]
    reserved_port_node [R] reserved_port_value [R]
    alloc_node [A] (row in the node arrays, -1 if the node is unknown)
    alloc_job [A] (index into job_ids)  alloc_usage [A,4]  alloc_mbits [A]
    port_alloc [P] (row in the alloc arrays)  port_value [P]
    job_ids (list of A-indexed job names)
"""

from __future__ import annotations

import numpy as np

DIMENSIONS = ("cpu", "memory", "disk", "iops")


def node_sums(store: dict, port_range) -> dict:
    """Per node: reserved plus live usage [N,4], bandwidth in use [N],
    and ports held inside the dynamic range [N]. Sums of integers in
    float64: exact."""
    n = len(store["node_cap"])
    placed = store["alloc_node"] >= 0
    rows = store["alloc_node"][placed]
    util = np.array(store["node_reserved"], np.float64)
    np.add.at(util, rows, np.asarray(store["alloc_usage"], np.float64)[placed])
    bw = np.array(store["node_reserved_mbits"], np.float64)
    np.add.at(bw, rows, np.asarray(store["alloc_mbits"], np.float64)[placed])
    lo, hi = port_range
    dyn = np.zeros(n, np.float64)
    res_val = store["reserved_port_value"]
    in_range = (res_val >= lo) & (res_val < hi)
    np.add.at(dyn, store["reserved_port_node"][in_range], 1.0)
    port_nodes = store["alloc_node"][store["port_alloc"]]
    held = (port_nodes >= 0) & (store["port_value"] >= lo) \
        & (store["port_value"] < hi)
    np.add.at(dyn, port_nodes[held], 1.0)
    return {"util": util, "bw_used": bw, "dyn_ports_used": dyn}


def fit_scores(store: dict, util: np.ndarray) -> np.ndarray:
    """ScoreFit (funcs.go:123, BestFit v3) of every node at utilisation
    `util`: 20 - (10^free_cpu + 10^free_mem), clamped to [0, 18]. As in
    the reference, `util` includes reserved while the denominator
    subtracts it."""
    cap = np.asarray(store["node_cap"], np.float64)
    res = np.asarray(store["node_reserved"], np.float64)
    avail = cap[:, :2] - res[:, :2]
    ok = (avail > 0).all(axis=1)
    free = 1.0 - util[:, :2] / np.where(avail > 0, avail, 1.0)
    score = 20.0 - (10.0 ** free[:, 0] + 10.0 ** free[:, 1])
    return np.where(ok, np.clip(score, 0.0, 18.0), 0.0)


def _of_jobs(store: dict, jobs) -> np.ndarray:
    """Mask over the allocations: those of the named jobs."""
    job_index = {job: i for i, job in enumerate(store["job_ids"])}
    wanted = np.array(sorted(job_index[j] for j in jobs if j in job_index),
                      np.int64)
    return np.isin(store["alloc_job"], wanted)


def judge(store: dict, window_jobs: dict, port_range) -> dict:
    """Count every broken rule on the nodes the window's jobs touched.

    `window_jobs` maps a job id to {"count", "distinct_hosts"}. Returns
    counts (each with the limit 0) and the sums they were taken from."""
    sums = node_sums(store, port_range)
    in_window = _of_jobs(store, window_jobs)
    unknown_node = int(np.sum(in_window & (store["alloc_node"] < 0)))
    touched = np.unique(store["alloc_node"][in_window
                                            & (store["alloc_node"] >= 0)])

    cap = np.asarray(store["node_cap"], np.float64)
    over = sums["util"][touched] > cap[touched]
    over_bw = sums["bw_used"][touched] > np.asarray(
        store["node_mbits"], np.float64)[touched]
    not_ready = ~np.asarray(store["node_ready"], bool)[touched] \
        | np.asarray(store["node_drain"], bool)[touched]

    # A port held twice on one node, the node's reserved ports included.
    port_nodes = np.concatenate([
        store["reserved_port_node"],
        store["alloc_node"][store["port_alloc"]]]).astype(np.int64)
    port_values = np.concatenate([
        store["reserved_port_value"], store["port_value"]]).astype(np.int64)
    on_touched = np.isin(port_nodes, touched)
    keys = port_nodes[on_touched] * 65536 + port_values[on_touched]
    port_twice = int(len(keys) - len(np.unique(keys)))
    bad_port = int(np.sum((port_values[on_touched] < 0)
                          | (port_values[on_touched] >= 65536)))

    # distinct_hosts: (job, node) pairs that repeat.
    sel = _of_jobs(store, [j for j, spec in window_jobs.items()
                           if spec["distinct_hosts"]]) \
        & (store["alloc_node"] >= 0)
    pairs = store["alloc_job"][sel].astype(np.int64) * (len(cap) + 1) \
        + store["alloc_node"][sel]
    shared_host = int(len(pairs) - len(np.unique(pairs)))

    counts = {
        "allocs_on_unknown_node": unknown_node,
        "nodes_not_ready_or_draining": int(np.sum(not_ready)),
        "nodes_over_bandwidth": int(np.sum(over_bw)),
        "ports_held_twice": port_twice,
        "ports_out_of_range": bad_port,
        "distinct_hosts_shared": shared_host,
    }
    for d, name in enumerate(DIMENSIONS):
        counts[f"nodes_over_{name}"] = int(np.sum(over[:, d]))
    return {"counts": counts, "sums": sums, "touched": touched,
            "window_allocs": int(np.sum(in_window))}


def packing(store: dict, window_jobs: dict, sums: dict, rng) -> dict:
    """Is placement still bin-packing? The mean final fit score over the
    window's allocations, by the node each landed on, beside the same
    mean over as many ready nodes drawn uniformly by `rng` (a
    numpy Generator made from the seed)."""
    sel = _of_jobs(store, window_jobs) & (store["alloc_node"] >= 0)
    rows = store["alloc_node"][sel]
    scores = fit_scores(store, sums["util"])
    ready = np.flatnonzero(np.asarray(store["node_ready"], bool)
                           & ~np.asarray(store["node_drain"], bool))
    if len(rows) == 0 or len(ready) == 0:
        return {"placed_mean": None, "uniform_mean": None, "allocs": 0}
    drawn = rng.choice(ready, size=len(rows), replace=True)
    return {"placed_mean": float(scores[rows].mean()),
            "uniform_mean": float(scores[drawn].mean()),
            "allocs": int(len(rows))}
