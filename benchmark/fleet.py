"""One generic builder for every configuration file: the fleet (classes of
node shape with counts and filler rules), loaded in bulk through the
raft log, and the job templates the load generator registers.

Everything comes from the configuration's JSON and `--seed`; a new
deployment is a new file, not new code here.

Two keys a deployment with racks and gangs may add, both optional (a
file without them loads and registers exactly what it did before):

- A class's `topology`: `{"rack": {"nodes_per_group": 24, "prefix":
  "a"}}`, and `ici` likewise (the levels `models/topology.py` reads from
  a node's `meta`). The class's i-th node, counted from 0 in load order,
  gets `meta.rack = "<prefix><i // nodes_per_group>"`; `prefix` defaults
  to the level's name, and the class's last group is short where its
  count is no multiple. `ici` alone is numbered the same way. With both,
  the rack's `nodes_per_group` has to be a multiple of the `ici` one and
  `meta.ici = "<the node's rack>-<ici prefix><(i % rack size) // ici
  size>"`: an `ici` group lies inside one rack and its name says which.
  The node's `meta` is the class's `meta` plus these keys, and its
  computed class is taken anew (the rack is part of it). The rule draws
  nothing from the seed. Every class counts from 0, so two classes that
  state one prefix share group names: rack `a0` then holds the first
  `nodes_per_group` nodes of each of them, a mixed rack.
- A job shape's `gang`: `{"slice": "rack"}` (or `affinity`, `spread`;
  `rack` or `ici`; `{}` for no topology policy) becomes the task group's
  gang stanza: its `count` members are placed all or none.
"""

from __future__ import annotations

import random
import uuid

# Allocations to a raft entry when the fillers are loaded: few large
# entries, so the store's per-apply work (index bump, watch stamps,
# notify) is paid some tens of times and not tens of thousands.
FILLER_ENTRY = 5000


def seeded_uuid(rng: random.Random) -> str:
    return str(uuid.UUID(int=rng.getrandbits(128), version=4))


def scaled(config: dict, rehearse: bool) -> dict:
    """The configuration as run. A rehearsal shrinks the fleet and the
    job by the factors the file itself states; a real run changes
    nothing."""
    if not rehearse:
        return config
    small = dict(config)
    spec = config["rehearsal"]
    fleet = dict(config["fleet"])
    fleet["classes"] = [
        dict(c, count=max(8, int(c["count"] * spec["fleet_scale"])))
        for c in fleet["classes"]]
    small["fleet"] = fleet
    counts = spec["job_count"]      # a number (all) or a map by name
    small_jobs = [dict(job, count=counts[job["name"]]
                       if isinstance(counts, dict) else counts)
                  for job in job_specs(config)]
    if isinstance(config.get("jobs"), list):
        small["jobs"] = small_jobs
    else:
        small["job"] = small_jobs[0]
    return small


def job_specs(config: dict) -> list:
    """The configuration's job shapes: `jobs`, a list of templates each
    with a `name` and a `share` of the arrivals, or `job` alone, which is
    a list of one. (`jobs` as a line of prose, as `c1m-5k.json` has it,
    is a note and not a list.)"""
    if isinstance(config.get("jobs"), list):
        return config["jobs"]
    return [dict(config["job"], name=config["job"].get("name", "job"),
                 share=1.0)]


def _node_template(shape: dict, datacenter: str):
    from nomad_tpu.structs import NetworkResource, Node, Port, Resources

    res = shape["reserved"]
    return Node(
        datacenter=datacenter, name="bench",
        attributes=dict(shape["attributes"]), meta=dict(shape["meta"]),
        node_class=shape["node_class"], status="ready",
        resources=Resources(
            cpu=shape["cpu"], memory_mb=shape["memory_mb"],
            disk_mb=shape["disk_mb"], iops=shape["iops"],
            networks=[NetworkResource(
                device=shape["device"], cidr=shape["cidr"], ip=shape["ip"],
                mbits=shape["mbits"])]),
        reserved=Resources(
            cpu=res["cpu"], memory_mb=res["memory_mb"],
            disk_mb=res["disk_mb"], iops=res["iops"],
            networks=[NetworkResource(
                device=shape["device"], ip=shape["ip"], mbits=res["mbits"],
                reserved_ports=[Port(f"r{p}", p) for p in res["ports"]])]))


def _topology_meta(topology: dict, i: int) -> dict:
    """The meta keys a class's `topology` rule gives its i-th node (the
    module's docstring says how)."""
    unknown = set(topology) - {"rack", "ici"}
    if unknown:
        raise ValueError(f"no topology level {sorted(unknown)}")
    meta = {}
    for level, rule in topology.items():
        meta[level] = (f"{rule.get('prefix', level)}"
                       f"{i // rule['nodes_per_group']}")
    if len(topology) == 2:
        rack, ici = (topology[level]["nodes_per_group"]
                     for level in ("rack", "ici"))
        if rack % ici:
            raise ValueError(f"a rack of {rack} nodes does not hold whole "
                             f"ici groups of {ici}")
        meta["ici"] = (f"{meta['rack']}-{topology['ici'].get('prefix', 'ici')}"
                       f"{i % rack // ici}")
    return meta


def _filler_job(datacenter: str, rule: dict):
    """A rule that states a priority gets a filler job of its own
    (`filler-p<priority>`), so that a fleet can hold several tiers."""
    from nomad_tpu.structs import (EphemeralDisk, Job, Resources, Task,
                                   TaskGroup)

    priority = rule.get("priority", 50)
    job_id = f"filler-p{priority}" if "priority" in rule else "filler"
    job = Job(id=job_id, name=job_id, type=rule.get("type", "service"),
              priority=priority, datacenters=[datacenter],
              task_groups=[TaskGroup(
                  name="web", count=1, ephemeral_disk=EphemeralDisk(),
                  tasks=[Task(name="web", driver="exec",
                              resources=Resources(cpu=100, memory_mb=64))])])
    job.canonicalize()
    return job


def load_fleet(server, config: dict, seed: int) -> dict:
    """Nodes one raft entry each (the program has no bulk node entry),
    fillers FILLER_ENTRY to an entry; every object a copy of one
    template. Goes through `server.log.apply`, so the FSM's tables and
    indexes are the program's own."""
    from nomad_tpu.structs import Allocation, Resources

    rng = random.Random(seed)
    fleet = config["fleet"]
    dc = fleet["datacenter"]
    filler_jobs: dict = {}
    n_nodes = n_allocs = 0
    pending = []
    for cls in fleet["classes"]:
        template = _node_template(cls["node"], dc)
        template.compute_class()
        # one rule, or a list of them: one tier of fillers each
        rules = cls["filler"]
        rules = rules if isinstance(rules, list) else [rules]
        tiers = []
        for rule in rules:
            job = _filler_job(dc, rule)
            tiers.append((rule, filler_jobs.setdefault(job.id, job)))
        topology = cls.get("topology")
        for i in range(cls["count"]):
            node = template.copy()
            node.id = seeded_uuid(rng)
            node.secret_id = seeded_uuid(rng)
            if topology:
                node.meta.update(_topology_meta(topology, i))
                node.compute_class()
            server.log.apply("node_register", {"node": node})
            n_nodes += 1
            for rule, filler_job in tiers:
                for k in range(rule.get("per_node", 0)):
                    pending.append(Allocation(
                        id=seeded_uuid(rng), eval_id="filler",
                        node_id=node.id, name=f"{filler_job.id}.web[{k}]",
                        job_id=filler_job.id, job=filler_job,
                        task_group="web",
                        shared_resources=Resources(disk_mb=rule["disk_mb"]),
                        task_resources={"web": Resources(
                            cpu=rng.choice(rule["cpu"]),
                            memory_mb=rng.choice(rule["memory_mb"]))},
                        desired_status="run", client_status="running"))
            if len(pending) >= FILLER_ENTRY:
                server.log.apply("alloc_update", {"allocs": pending})
                n_allocs += len(pending)
                pending = []
    if pending:
        server.log.apply("alloc_update", {"allocs": pending})
        n_allocs += len(pending)
    return {"nodes": n_nodes, "filler_allocs": n_allocs}


def job_template(spec: dict) -> dict:
    """One job shape (an entry of `job_specs`) as the JSON body's `job`;
    the generator fills in `id` and `name`."""
    from nomad_tpu.structs import (Constraint, EphemeralDisk, Gang, Job,
                                   NetworkResource, Port, Resources,
                                   RestartPolicy, Task, TaskGroup)
    from nomad_tpu.utils.codec import to_dict

    task = spec["task"]
    gang = spec.get("gang")     # {} is a gang with no topology policy
    networks = []
    if task["mbits"] or task["dynamic_ports"]:
        networks = [NetworkResource(
            mbits=task["mbits"],
            dynamic_ports=[Port(label, 0) for label in task["dynamic_ports"]])]
    group_constraints = []
    if spec["distinct_hosts"]:
        group_constraints.append(Constraint(operand="distinct_hosts"))
    if spec["type"] == "batch":
        restart = RestartPolicy(attempts=0, interval=0.0, delay=0.0,
                                mode="fail")
    else:
        restart = RestartPolicy(attempts=3, interval=600.0, delay=60.0,
                                mode="delay")
    job = Job(
        region="global", id="template", name="template", type=spec["type"],
        priority=spec["priority"], datacenters=list(spec["datacenters"]),
        constraints=[Constraint(**c) for c in spec["constraints"]],
        task_groups=[TaskGroup(
            name=spec["group"], count=spec["count"],
            constraints=group_constraints, restart_policy=restart,
            ephemeral_disk=EphemeralDisk(size_mb=spec["ephemeral_disk_mb"]),
            gang=Gang(**gang) if gang is not None else None,
            tasks=[Task(
                name=task["name"], driver=task["driver"],
                config={"command": "/bin/date"},
                resources=Resources(cpu=task["cpu"],
                                    memory_mb=task["memory_mb"],
                                    networks=networks))])])
    job.canonicalize()
    errors = job.validate()
    if errors:
        raise ValueError(
            f"job template {spec['name']!r} does not validate: {errors}")
    return to_dict(job)
