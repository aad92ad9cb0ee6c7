"""The guarantees a deployment of gangs on racks states beyond the five
comparisons, held on the final store: a gang is placed whole or not at
all, and a `slice` gang lies inside one topology group. Imports nothing
of the program and nothing at all; every count is held to 0.

A gang here is one job of the window whose shape carries a `gang`
stanza (`window_jobs[job]["gang"]`); its members are the job's live
allocations (`alloc_job`, `alloc_group`), and a member's group is its
node's `node_meta[<level>]`, the level being the stanza's `slice`.

- `gangs_split_across_groups`: `slice` gangs whose live members lie in
  more than one group of their level.
- `gangs_partial`: gangs with more than 0 and fewer than `count` live
  members (every kind of gang, sliced or not).
- `members_without_group`: live members of a `slice` gang on a node that
  states no group of that level (or on no known node).
- `no_gang_placed`: 1 where no gang of the window is live with all its
  members: the fixture exists to run the gang pass, and a run that placed
  none measured something else.
"""


def check(store, window_jobs, config):
    job_of_row = store["job_ids"]
    members: dict = {}          # job id -> [node row of each live member]
    for job_row, node in zip(store["alloc_job"], store["alloc_node"]):
        job_id = job_of_row[int(job_row)]
        spec = window_jobs.get(job_id)
        if spec is not None and spec.get("gang") is not None:
            members.setdefault(job_id, []).append(int(node))

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
        groups = [column[node] if node >= 0 else "" for node in nodes]
        bare += sum(1 for group in groups if not group)
        if len(set(groups)) > 1:
            split += 1
    return {"gangs_split_across_groups": split, "gangs_partial": partial,
            "members_without_group": bare, "no_gang_placed": int(whole == 0)}
