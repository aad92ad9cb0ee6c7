"""The plain reference of a shared-base dispatch: its lanes placed one
at a time by the unbatched program, each starting from the utilisation,
bandwidth and free ports the lanes before it left, the serialization
the plan applier would impose. What the batched programs' scan over the
eval axis must equal."""

import numpy as np

from nomad_tpu.ops.binpack import NodeState, placement_program_jit


def serial_placement(base, lanes, config):
    """`base` carries the shared node fields (capacity, sched_capacity,
    util, bw_avail, bw_used, ports_free, node_ok); `lanes` is the
    dispatch's lanes in order, each (job_count, tg_count, feasible,
    asks, key). Returns (choices, scores), one row a lane."""
    util, bw, pf = base.util, base.bw_used, base.ports_free
    choices, scores = [], []
    for job_count, tg_count, feasible, asks, key in lanes:
        state = NodeState(
            capacity=base.capacity, sched_capacity=base.sched_capacity,
            util=util, bw_avail=base.bw_avail, bw_used=bw, ports_free=pf,
            job_count=job_count, tg_count=tg_count, feasible=feasible,
            node_ok=base.node_ok)
        c, s, final = placement_program_jit(state, asks, key, config)
        util = np.asarray(final.util)
        bw = np.asarray(final.bw_used)
        pf = np.asarray(final.ports_free)
        choices.append(np.asarray(c))
        scores.append(np.asarray(s))
    return np.stack(choices), np.stack(scores)


def no_patches(b: int, n: int):
    """The `patches` argument of the shared-base programs for `b` lanes
    over `n` nodes none of whose plans touches a row (ops/binpack.py
    _patched: a row of n is out of range and dropped)."""
    return (np.full((b, 1), n, np.int32), np.zeros((b, 1, 6), np.float32))
