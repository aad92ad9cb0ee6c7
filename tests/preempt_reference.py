"""A plain reference of the dense preemption pass (ops/preempt.py), for
tests/test_preempt_reference.py: NumPy float64, sequential, ask by ask
and node by node, importing nothing of `nomad_tpu/ops`.

For each ask, in order:

- a node FITS as it is when the ask's resources, bandwidth and ports
  go in beside what the node holds (capacity in every dimension), the
  node is feasible and ready for the ask's task group, and
  distinct_hosts allows it; its score is BestFit (20 - 10^free_cpu -
  10^free_mem, clipped to 0..18, after the placement) less the job
  anti-affinity penalty, plus the tie-break noise the caller hands in;
- only when NO node fits, a node may be evicted into: walking its
  victim candidates lowest priority first (the order they are given
  in), skipping the consumed ones, stopping at the first that does not
  lie strictly below the preemptor, the SMALLEST prefix whose freed
  resources, bandwidth and ports make the ask fit; its score is
  BestFit after the eviction less the anti-affinity penalty less
  VICTIM_PENALTY a victim, plus the noise;
- the best score wins (the lowest row on a tie, as argmax breaks it);
  the ask's claim and what its victims freed are carried to the next
  ask, and the victims taken are consumed.

Returns per ask the node chosen (-1: none), its score without the
noise, and the number of victims taken.
"""

import numpy as np

VICTIM_PENALTY = 2.0


def _best_fit(util_after, sched_capacity):
    free = 1.0 - util_after / np.maximum(sched_capacity, 1.0)
    return float(np.clip(20.0 - (10.0 ** free[0] + 10.0 ** free[1]),
                         0.0, 18.0))


def preempt_reference(node, victims, asks, eval_priority, penalty, noise):
    """`node`: capacity, sched_capacity, util [N,4]; bw_avail, bw_used,
    ports_free, job_count [N]; tg_count [N,G]; feasible [N,G]; node_ok
    [N]. `victims`: res [N,V,4]; bw, ports, prio, ok [N,V]. `asks`:
    resources [K,4]; bw, ports, tg_index, active [K]; job_dh; tg_dh [G].
    `noise` [K,N]."""
    f64 = lambda x: np.array(x, np.float64)  # noqa: E731 - a copy each
    capacity, sched = f64(node["capacity"]), f64(node["sched_capacity"])
    util, bw_avail = f64(node["util"]), f64(node["bw_avail"])
    bw_used, ports_free = f64(node["bw_used"]), f64(node["ports_free"])
    job_count = np.array(node["job_count"], np.int64)
    tg_count = np.array(node["tg_count"], np.int64)
    feasible = np.asarray(node["feasible"], bool)
    node_ok = np.asarray(node["node_ok"], bool)
    v_res, v_bw = f64(victims["res"]), f64(victims["bw"])
    v_ports, v_prio = f64(victims["ports"]), f64(victims["prio"])
    v_ok = np.array(victims["ok"], bool)
    n, v = v_ok.shape

    choices, scores, counts = [], [], []
    for j in range(len(asks["active"])):
        res = f64(asks["resources"][j])
        bw, ports = float(asks["bw"][j]), float(asks["ports"][j])
        gi = int(asks["tg_index"][j])
        tg_dh = bool(asks["tg_dh"][gi])

        def eligible(i):
            if not (feasible[i, gi] and node_ok[i]):
                return False
            if asks["job_dh"] and job_count[i] != 0:
                return False
            return not (tg_dh and tg_count[i, gi] != 0)

        def fits(i, freed, freed_bw, freed_ports):
            return (np.all(util[i] + res - freed <= capacity[i])
                    and bw_used[i] + bw - freed_bw <= bw_avail[i]
                    and ports_free[i] + freed_ports >= ports)

        zero = np.zeros(4)
        best = None  # (score with noise, row, victims taken, freed...)
        normal = [i for i in range(n)
                  if eligible(i) and fits(i, zero, 0.0, 0.0)]
        for i in normal:
            # a node with nothing schedulable scores worst
            fit = (_best_fit(util[i] + res, sched[i])
                   if sched[i, 0] > 0 and sched[i, 1] > 0 else 0.0)
            total = fit - penalty * job_count[i] + noise[j, i]
            if best is None or total > best[0]:
                best = (total, i, [], zero, 0.0, 0.0)
        if not normal:
            for i in range(n):
                if not eligible(i):
                    continue
                freed, freed_bw, freed_ports, taken = zero.copy(), 0.0, 0.0, []
                found = False
                for s in range(v):
                    if not v_ok[i, s]:
                        continue
                    if not v_prio[i, s] < eval_priority:
                        break
                    freed = freed + v_res[i, s]
                    freed_bw += v_bw[i, s]
                    freed_ports += v_ports[i, s]
                    taken.append(s)
                    if fits(i, freed, freed_bw, freed_ports):
                        found = True
                        break
                if not found:
                    continue
                total = (_best_fit(util[i] + res - freed, sched[i])
                         - penalty * job_count[i]
                         - VICTIM_PENALTY * len(taken) + noise[j, i])
                if best is None or total > best[0]:
                    best = (total, i, taken, freed, freed_bw, freed_ports)
        if best is None or not asks["active"][j]:
            choices.append(-1)
            scores.append(0.0)
            counts.append(0)
            continue
        total, i, taken, freed, freed_bw, freed_ports = best
        util[i] += res - freed
        bw_used[i] += bw - freed_bw
        ports_free[i] += freed_ports - ports
        job_count[i] += 1
        tg_count[i, gi] += 1
        v_ok[i, taken] = False
        choices.append(i)
        scores.append(total - noise[j, i])
        counts.append(len(taken))
    return choices, scores, counts
