"""The guarantees `borg-push-12k` states beyond the five comparisons,
held on the final store: after an update every live allocation of the
job is of the registered version, exactly `count` of them, and an
in-place update stops nothing and moves nothing. Imports nothing of the
program; every count is held to 0.

A job of the window is of the kind its sample's `template` names
(`window_jobs[job]["template"]`): `push` (a new version that restarts
every task: the task's memory changes), `scale` (more instances of an
unchanged task), `touch` (the same body again) or `new` (an arrival).
The three first are UPDATES of a standing service, which ran the
standing shape's count at the standing shape's memory (the
configuration's `updates.standing`). A job is updated at most once in a
run, by one evaluation, so after it every live allocation of the job
carries that evaluation's id (`alloc_eval`): those placed by it, and
those it rewrote in place.

- `pushed_allocs_at_the_old_memory`: live allocations of a `push` job
  whose memory (column 1 of `alloc_usage`) is not the `push` shape's.
- `updated_jobs_off_their_count`: jobs of the window, arrivals
  included, whose live allocations are not `count`.
- `updated_jobs_with_allocs_of_two_evals`: `push`, `scale` or `touch`
  jobs whose live allocations carry more than one evaluation's id (one
  the update left behind at the old version).
- `in_place_jobs_with_a_stopped_alloc`: `scale` or `touch` jobs with an
  allocation that is stopped or evicted.
- `pushed_jobs_not_stopping_their_old_count`: `push` jobs whose stopped
  allocations are not the standing shape's count.
- `window_allocs_on_unready_nodes`: live allocations of the window's
  jobs on a node that is not ready, is draining, or is not in the store.
- `no_push_completed`: 1 where no `push` of the window ended with
  `count` live allocations, all of the new memory.
"""

MEMORY = 1
UPDATES = ("push", "scale", "touch")
IN_PLACE = ("scale", "touch")


def check(store, window_jobs, config):
    shapes = {job["name"]: job for job in config["jobs"]}
    pushed_memory = float(shapes["push"]["task"]["memory_mb"])
    standing_count = shapes[config["updates"]["standing"]]["count"]
    n = len(store["node_ids"])
    live: dict = {}         # job id of the window -> live allocations
    evals: dict = {}        # ... -> the eval ids they carry
    old_memory: dict = {}   # push job -> live allocations not of its memory
    unready = 0
    for job_row, node, usage, eval_id in zip(
            store["alloc_job"], store["alloc_node"], store["alloc_usage"],
            store["alloc_eval"]):
        job_id = store["job_ids"][int(job_row)]
        spec = window_jobs.get(job_id)
        if spec is None:
            continue
        live[job_id] = live.get(job_id, 0) + 1
        evals.setdefault(job_id, set()).add(eval_id)
        if spec["template"] == "push" \
                and float(usage[MEMORY]) != pushed_memory:
            old_memory[job_id] = old_memory.get(job_id, 0) + 1
        node = int(node)
        if not (0 <= node < n and bool(store["node_ready"][node])
                and not bool(store["node_drain"][node])):
            unready += 1
    stopped: dict = {}      # job id of the window -> stopped allocations
    for job_id, desired in zip(store["gone_job"], store["gone_desired"]):
        if job_id in window_jobs and desired in ("stop", "evict"):
            stopped[job_id] = stopped.get(job_id, 0) + 1
    kind = {job_id: spec["template"] for job_id, spec in window_jobs.items()}
    pushes_whole = sum(
        1 for job_id, spec in window_jobs.items()
        if kind[job_id] == "push" and live.get(job_id, 0) == spec["count"]
        and job_id not in old_memory)
    return {
        "pushed_allocs_at_the_old_memory": sum(old_memory.values()),
        "updated_jobs_off_their_count": sum(
            1 for job_id, spec in window_jobs.items()
            if live.get(job_id, 0) != spec["count"]),
        "updated_jobs_with_allocs_of_two_evals": sum(
            1 for job_id in window_jobs
            if kind[job_id] in UPDATES and len(evals.get(job_id, ())) > 1),
        "in_place_jobs_with_a_stopped_alloc": sum(
            1 for job_id in window_jobs
            if kind[job_id] in IN_PLACE and stopped.get(job_id, 0)),
        "pushed_jobs_not_stopping_their_old_count": sum(
            1 for job_id in window_jobs
            if kind[job_id] == "push"
            and stopped.get(job_id, 0) != standing_count),
        "window_allocs_on_unready_nodes": unready,
        "no_push_completed": int(pushes_whole == 0),
    }
