"""The guarantees `borg-12k` states beyond the five comparisons, held on
the final store: production work is placed by evicting the bands below
it, the lowest first, and nothing else is disturbed. NumPy only; imports
nothing of the program. Every count is held to 0.

Standing work is the filler jobs (`filler-p<priority>`, loaded before
the window); everything else in the store arrived over HTTP, in warm-up
or in the window. An evicted allocation is one the store holds with
`desired_status` `evict`.

- `victims_not_below_preemptor`: evicted allocations whose priority is
  not strictly below that of the lowest arrival shape that may preempt
  (priority above the server's `preempt_priority_threshold`). The store
  does not name a victim's preemptor; every arrival shape of this
  deployment has one priority, so the lowest is each one's.
- `victims_above_a_survivor`: evicted allocations whose priority is
  above that of a standing filler still live on the same node: on a
  node the lowest band goes first.
- `evictions_without_placement`: nodes with an eviction and no live
  allocation of an arrival: an eviction commits only with the placement
  it made room for (warm-up's arrivals count too).
- `arrivals_evicted`: evicted allocations of a job that is not a
  filler: production is not evicted for production.
- `no_eviction_in_window`: 1 where nothing at all was evicted: the cell
  exists to run the mechanism, and a run that never did measured
  something else.
- `allocs_without_priority`: live allocations that carry no job.
"""

import numpy as np

EVICT = "evict"
FILLER_PREFIX = "filler"


def check(store, window_jobs, config):
    threshold = config["server"].get("preempt_priority_threshold", 50)
    preemptors = [job["priority"] for job in config["jobs"]
                  if job["priority"] > threshold]
    floor = min(preemptors) if preemptors else threshold + 1

    n_nodes = len(store["node_ids"])
    evicted = np.asarray([d == EVICT for d in store["gone_desired"]], bool)
    gone_filler = np.asarray([j.startswith(FILLER_PREFIX)
                              for j in store["gone_job"]], bool)
    ev_node = store["gone_node"][evicted]
    ev_prio = store["gone_priority"][evicted]

    job_is_filler = np.asarray([j.startswith(FILLER_PREFIX)
                                for j in store["job_ids"]], bool)
    live_filler = job_is_filler[store["alloc_job"]]
    live_node = store["alloc_node"]
    on_node = live_node >= 0

    # the lowest priority among the standing fillers still live, by node
    lowest_left = np.full(n_nodes, np.iinfo(np.int64).max, np.int64)
    keep = live_filler & on_node
    np.minimum.at(lowest_left, live_node[keep], store["alloc_priority"][keep])
    # live allocations of arrivals, by node
    arrivals_here = np.zeros(n_nodes, np.int64)
    keep = ~live_filler & on_node
    np.add.at(arrivals_here, live_node[keep], 1)

    known = ev_node >= 0
    evicted_nodes = np.unique(ev_node[known])
    return {
        "victims_not_below_preemptor": int(np.sum(ev_prio >= floor)),
        "victims_above_a_survivor": int(np.sum(
            ev_prio[known] > lowest_left[ev_node[known]])),
        "evictions_without_placement": int(np.sum(
            arrivals_here[evicted_nodes] == 0)) + int(np.sum(~known)),
        "arrivals_evicted": int(np.sum(evicted & ~gone_filler)),
        "no_eviction_in_window": int(not evicted.any()),
        "allocs_without_priority": int(np.sum(store["alloc_priority"] < 0)),
    }
