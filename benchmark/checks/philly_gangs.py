"""The guarantees `philly-552` states beyond the five comparisons, held
on the final store: a gang is placed whole or not at all, all its
members lie in one rack, and a server never holds more members and
standing jobs than it has GPUs. Imports nothing of the program and
nothing at all; every count is held to 0.

A gang is one job of the window whose shape carries a `gang` stanza
(`window_jobs[job]["gang"]`); its members are the job's live
allocations, and a member's rack is its node's `node_meta[<level>]`, the
level being the stanza's `slice`. One GPU is one slot of the
configuration's `gpu_slot`: a server's GPUs are its cpu after its
reserved over the slot's cpu, and every live allocation on it, a
standing job's or a member's, warm-up's too, holds one.

- `gangs_split_across_racks`: gangs whose live members lie in more than
  one rack.
- `gangs_partial`: gangs with more than 0 and fewer than `count` live
  members.
- `members_without_rack`: live members on a node that states no rack (or
  on no known node).
- `no_gang_placed`: 1 where no gang of the window is live with all its
  members: the cell exists to run the gang pass.
- `servers_over_their_gpus`: servers that hold more live allocations
  than they have GPUs.
"""


def check(store, window_jobs, config):
    job_of_row = store["job_ids"]
    members: dict = {}          # job id -> [node row of each live member]
    held = [0] * len(store["node_ids"])
    for job_row, node in zip(store["alloc_job"], store["alloc_node"]):
        node = int(node)
        if node >= 0:
            held[node] += 1
        job_id = job_of_row[int(job_row)]
        spec = window_jobs.get(job_id)
        if spec is not None and spec.get("gang") is not None:
            members.setdefault(job_id, []).append(node)

    split = partial = bare = whole = 0
    for job_id, nodes in members.items():
        spec = window_jobs[job_id]
        if len(nodes) < spec["count"]:
            partial += 1
        else:
            whole += 1
        level = spec["gang"].get("slice")
        if not level:
            continue
        column = store["node_meta"][level]
        racks = [column[node] if node >= 0 else "" for node in nodes]
        bare += sum(1 for rack in racks if not rack)
        if len(set(racks)) > 1:
            split += 1

    slot_cpu = config["gpu_slot"]["cpu"]
    over = 0
    for row, count in enumerate(held):
        gpus = (int(store["node_cap"][row][0])
                - int(store["node_reserved"][row][0])) // slot_cpu
        over += count > gpus
    return {"gangs_split_across_racks": split, "gangs_partial": partial,
            "members_without_rack": bare, "no_gang_placed": int(whole == 0),
            "servers_over_their_gpus": over}
