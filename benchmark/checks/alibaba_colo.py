"""The guarantees `alibaba-colo-4k` states beyond the five comparisons,
held on the final store: a task has all `count` instances, the
containers of one `app-8` lie on different machines, no machine is over
its cores or its memory, and nothing of the window lies on a node that
is not ready. Imports nothing of the program; every count is held to 0.

A task is one job of the window (`window_jobs[job]["count"]`); an
application is a job of the window whose shape says `distinct_hosts`.
The sums are the check's own, in float64 over the store's arrays:
reserved plus every live allocation's cpu and memory (columns 0 and 1 of
`alloc_usage`), standing containers and warm-up's tasks included.

- `tasks_short_of_their_count`: jobs of the window with fewer live
  allocations than `count` (one with none at all included).
- `tasks_past_their_count`: jobs of the window with more.
- `app_containers_sharing_a_machine`: live allocations of an
  application on a machine that already holds one of the same job.
- `machines_over_cpu_or_memory`: machines whose sum passes the
  capacity on either axis.
- `window_allocs_on_unready_nodes`: live allocations of the window's
  jobs on a node that is not ready, is draining, or is not in the store.
"""

CPU, MEMORY = 0, 1


def check(store, window_jobs, config):
    n = len(store["node_ids"])
    cap = store["node_cap"]
    held = [[float(row[CPU]), float(row[MEMORY])]
            for row in store["node_reserved"]]
    live: dict = {}             # job id of the window -> live allocations
    seen: set = set()           # (job id, node) of the applications
    sharing = unready = 0
    for job_row, node, usage in zip(store["alloc_job"], store["alloc_node"],
                                    store["alloc_usage"]):
        node = int(node)
        known = 0 <= node < n
        if known:
            held[node][CPU] += float(usage[CPU])
            held[node][MEMORY] += float(usage[MEMORY])
        job_id = store["job_ids"][int(job_row)]
        spec = window_jobs.get(job_id)
        if spec is None:
            continue
        live[job_id] = live.get(job_id, 0) + 1
        if not (known and bool(store["node_ready"][node])
                and not bool(store["node_drain"][node])):
            unready += 1
        if spec.get("distinct_hosts"):
            sharing += (job_id, node) in seen
            seen.add((job_id, node))
    short = sum(1 for job_id, spec in window_jobs.items()
                if live.get(job_id, 0) < spec["count"])
    past = sum(1 for job_id, spec in window_jobs.items()
               if live.get(job_id, 0) > spec["count"])
    over = sum(1 for row in range(n)
               if held[row][CPU] > float(cap[row][CPU])
               or held[row][MEMORY] > float(cap[row][MEMORY]))
    return {"tasks_short_of_their_count": short,
            "tasks_past_their_count": past,
            "app_containers_sharing_a_machine": sharing,
            "machines_over_cpu_or_memory": over,
            "window_allocs_on_unready_nodes": unready}
