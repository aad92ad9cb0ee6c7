"""The guarantees `pai-gpu-1800` states beyond the five comparisons, held
on the final store: a gang is whole or absent, no instance lies on a
machine of another GPU type than its job names, and no machine holds
more GPU shares than it has GPUs. Imports nothing of the program; the
constraint operands are the ones `borg_constraints.py` beside this file
implements (loaded by path), every count is held to 0.

The fleet has no racks (the source has none), so a machine's class is
read from its capacity row: the configuration's classes differ in cores,
memory, GPUs or network (`(cpu, memory_mb, disk_mb)` less the reserved,
and `mbits`, is unique a class; the check counts a machine that matches
none as of a wrong type). What the class says of its machines
(`attributes`, `meta`, `node_class`) is what a constraint's `${attr.*}`,
`${meta.*}` and `${node.class}` resolve to. GPUs ride the capacity axis
the file's `gpu_axis` names, in its units (1/10,000 of a GPU): column 2
of `node_cap` and of `alloc_usage` (disk); the check sums and compares
them and never converts. A gang is one job of the window whose shape carries
a `gang` stanza (`window_jobs[job]["gang"]` is not None); a job's
constraints are its shape's (`window_jobs[job]["template"]`).

- `partial_gangs`: gang jobs of the window with live allocations other
  than 0 or `count`.
- `members_on_a_wrong_gpu_type`: live allocations of the window's jobs
  on a machine that fails one of their job's constraints, or on a
  machine of no class of the configuration (or on no known machine).
- `machines_over_their_gpus`: machines whose live allocations, standing
  jobs and warm-up's included, sum to more GPU units than the machine
  has after its reserved (the check's own sum; `reference.py` takes the
  same axis as `nodes_over_disk`).
- `no_gang_of_the_window_is_whole`: 1 where no gang of the window is
  live with all its members: the cell exists to run gangs beside plain
  jobs.
- `no_constrained_job_placed`: 1 where no job of the window whose shape
  states more than `${attr.kernel.name} = linux` is live with all its
  allocations.
"""

import importlib.util
import os

GPU_AXIS = 2        # disk, in the order cpu, memory, disk, iops


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"checks_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_operands = _sibling("borg_constraints")
meets = _operands.meets


def class_signature(node: dict) -> tuple:
    """(cores, memory, GPU units) a machine of the class offers after
    its reserved, and its network."""
    reserved = node["reserved"]
    return (node["cpu"] - reserved["cpu"],
            node["memory_mb"] - reserved["memory_mb"],
            node["disk_mb"] - reserved["disk_mb"], node["mbits"])


def machines_by_signature(config: dict) -> dict:
    datacenter = config["fleet"]["datacenter"]
    by_signature = {}
    for cls in config["fleet"]["classes"]:
        signature = class_signature(cls["node"])
        if signature in by_signature:
            raise ValueError(f"two classes offer {signature}: the check "
                             f"cannot tell their machines apart")
        by_signature[signature] = dict(cls["node"], datacenter=datacenter)
    return by_signature


def check(store, window_jobs, config):
    by_signature = machines_by_signature(config)
    shapes = {spec["name"]: spec["constraints"] for spec in config["jobs"]}
    cap, reserved = store["node_cap"], store["node_reserved"]
    n = len(store["node_ids"])

    machines: dict = {}         # node row -> the class's node, or None

    def machine_of(node: int):
        if not 0 <= node < n:
            return None
        if node not in machines:
            machines[node] = by_signature.get((*(
                int(cap[node][d]) - int(reserved[node][d])
                for d in range(3)), int(store["node_mbits"][node])))
        return machines[node]

    verdicts: dict = {}         # (shape, class) -> bool
    held = [0.0] * n            # GPU units of every live allocation
    live: dict = {}             # job id of the window -> live allocations
    wrong = 0
    for job_row, node, usage in zip(store["alloc_job"], store["alloc_node"],
                                    store["alloc_usage"]):
        node = int(node)
        if 0 <= node < n:
            held[node] += float(usage[GPU_AXIS])
        job_id = store["job_ids"][int(job_row)]
        spec = window_jobs.get(job_id)
        if spec is None:
            continue
        live[job_id] = live.get(job_id, 0) + 1
        machine = machine_of(node)
        if machine is None:
            wrong += 1
            continue
        key = (spec["template"], machine["node_class"])
        if key not in verdicts:
            verdicts[key] = meets(shapes[spec["template"]], machine)
        wrong += not verdicts[key]

    partial = whole_gangs = pinned_whole = 0
    for job_id, count in live.items():
        spec = window_jobs[job_id]
        whole = count == spec["count"]
        if spec.get("gang") is not None:
            partial += not whole
            whole_gangs += whole
        if whole and _operands._is_pinned(shapes[spec["template"]]):
            pinned_whole += 1
    over = sum(1 for row in range(n)
               if held[row] > float(cap[row][GPU_AXIS])
               - float(reserved[row][GPU_AXIS]))
    return {"partial_gangs": partial,
            "members_on_a_wrong_gpu_type": wrong,
            "machines_over_their_gpus": over,
            "no_gang_of_the_window_is_whole": int(whole_gangs == 0),
            "no_constrained_job_placed": int(pinned_whole == 0)}
