"""Gang scheduling: all-or-nothing multi-node placement on the
node-topology tensor.

A task group carrying a ``gang`` stanza (structs/job.py ``Gang``)
places its ``count`` members ATOMICALLY — all K or none:

- the dense leg (ops/gang.py) runs the all-K feasibility pass over
  the device-resident cluster base: per-node member capacity ->
  topology-group cumulative capacity -> contiguous-slice selection ->
  K-step member assignment, with all-K enforcement on device. A gang
  is dispatched through the placement batcher (scheduler/batcher.py
  place_gang) like any dense ask: it joins its pipeline batch's
  cohort, reads the resident base and its deltas, and the gangs of one
  dispatch are solved in one program that carries each gang's claims
  to the next;
- the host leg (gang/host.py) mirrors the semantics through the
  sequential iterator stack — parity target, oracle for the
  differential rig (kernels/differential.py ``judge_gang_plan``), and
  the breaker/device-fault fallback;
- atomic commit: members stage through ``Plan.append_gang_alloc``
  into the ``gang_groups`` leg, and the plan applier rejects the WHOLE
  gang when any member's node fails verification
  (server/plan_apply.py) — nothing partial ever commits;
- whole-gang replacement: losing one member invalidates the gang
  (a multi-node DL job cannot run at K-1), so the scheduler stops the
  survivors and re-places all K as a unit
  (scheduler/generic.py ``promote_gang_replacements``).

This module holds the shared spec/routing helpers both scheduler
paths, the applier, and the rig import — it never touches the state
store (gang terminals only ever stamp through the raft funnel).
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

from ..structs import Job, TaskGroup, consts

from ..structs.job import Gang  # noqa: F401 (re-export)

__all__ = [
    "Gang",
    "gang_spec",
    "gang_task_groups",
    "is_gang_job",
    "gang_key",
    "gang_mode",
    "build_gang_config",
    "build_gang_request",
    "build_gang_state",
    "gang_distinct_hosts",
    "note_gang_dispatch",
    "note_gang_rejected_whole",
    "note_gang_result",
    "note_mixed_batch",
    "gang_stats",
    "reset_gang_stats",
    "spread_cap",
]


def gang_spec(tg: TaskGroup) -> Optional[Gang]:
    """The task group's gang stanza, or None. getattr-shielded so jobs
    decoded from pre-gang wire payloads (no field) behave as plain
    groups."""
    return getattr(tg, "gang", None)


def gang_task_groups(job: Optional[Job]) -> List[TaskGroup]:
    if job is None:
        return []
    return [tg for tg in job.task_groups if gang_spec(tg) is not None]


def is_gang_job(job: Optional[Job]) -> bool:
    return bool(gang_task_groups(job))


def gang_key(job_id: str, tg_name: str) -> str:
    """The Plan.gang_groups key for one gang: a (job, task group)
    pair — a gang is a TG-scoped unit."""
    return f"{job_id}/{tg_name}"


def gang_mode(gang: Gang) -> Tuple[str, str]:
    """(mode, topology level) for the dense/host programs. ``free``
    keeps atomicity with no topology policy; its level defaults to
    "rack" only so a column exists to thread (the program ignores
    it)."""
    from ..ops.gang import (
        GANG_MODE_AFFINITY,
        GANG_MODE_FREE,
        GANG_MODE_SLICE,
        GANG_MODE_SPREAD,
    )

    if gang.slice:
        return GANG_MODE_SLICE, gang.slice
    if gang.spread:
        return GANG_MODE_SPREAD, gang.spread
    if gang.affinity:
        return GANG_MODE_AFFINITY, gang.affinity
    return GANG_MODE_FREE, "rack"


def gang_distinct_hosts(job: Job, tg: TaskGroup) -> bool:
    dh = any(c.operand == consts.CONSTRAINT_DISTINCT_HOSTS
             for c in job.constraints)
    return dh or any(c.operand == consts.CONSTRAINT_DISTINCT_HOSTS
                     for c in tg.constraints)


def build_gang_config(job: Job, tg: TaskGroup, topo_groups: int):
    """The static GangConfig for one (job, gang TG) against a topology
    column with ``topo_groups`` groups. Every field is hashable and
    bucketed, so each (mode, dh, g_pad, penalty) pair is exactly one
    compiled program per shape bucket."""
    from ..models.topology import topo_group_pad
    from ..ops.gang import GangConfig
    from ..scheduler.stack import (
        BATCH_JOB_ANTI_AFFINITY_PENALTY,
        SERVICE_JOB_ANTI_AFFINITY_PENALTY,
    )

    mode, _level = gang_mode(gang_spec(tg))
    return GangConfig(
        anti_affinity_penalty=(
            BATCH_JOB_ANTI_AFFINITY_PENALTY
            if job.type == consts.JOB_TYPE_BATCH
            else SERVICE_JOB_ANTI_AFFINITY_PENALTY),
        mode=mode,
        distinct_hosts=gang_distinct_hosts(job, tg),
        g_pad=topo_group_pad(topo_groups),
    )


class GangRequest(NamedTuple):
    """One gang's dispatch against a ClusterMatrix: its own lane
    (ops/gang.py GangLane), the topology column it reads with the key
    the device's copy is kept under, and the static config."""

    lane: object  # ops.gang.GangLane
    topo_ids: object  # [N] int32 host column
    topo_key: tuple
    config: object  # ops.gang.GangConfig


def build_gang_request(matrix, job: Job, tg: TaskGroup) -> GangRequest:
    """The request one gang hands the batcher. Reuses the matrix's
    memoized feasibility mask and overlay counts — the gang pass adds
    no per-eval host recomputation beyond slicing them."""
    import numpy as np

    from ..models.matrix import ASK_BUCKETS, bucket_size
    from ..ops.gang import GANG_MODE_SLICE, make_gang_lane

    gi = next(i for i, g in enumerate(job.task_groups)
              if g.name == tg.name)
    k = tg.count
    k_pad = bucket_size(max(k, 1), ASK_BUCKETS)
    active = np.zeros(k_pad, bool)
    active[:k] = True

    # Uniform member ask from the matrix's shared group-size builder
    # (one row; gang members are identical by construction).
    resources, bw, ports, _tgi, _act, _jdh, _tdh = \
        matrix.build_asks([gi])

    mode, level = gang_mode(gang_spec(tg))
    topo = matrix.topology
    singleton = mode != GANG_MODE_SLICE
    if singleton:
        topo_ids, topo_groups = topo.singleton_column(level)
    else:
        topo_ids = topo.column(level)
        topo_groups = topo.counts[level]

    dh = gang_distinct_hosts(job, tg)
    job_dh = any(c.operand == consts.CONSTRAINT_DISTINCT_HOSTS
                 for c in job.constraints)
    if dh:
        dh_presence = (matrix.job_count if job_dh
                       else matrix.tg_count[:, gi])
    else:
        dh_presence = np.zeros(matrix.n, np.int32)

    return GangRequest(
        lane=make_gang_lane(matrix.feasible[:, gi], matrix.job_count,
                            dh_presence, resources[0], bw[0], ports[0],
                            active),
        topo_ids=topo_ids,
        topo_key=topo.device_key(level, singleton),
        config=build_gang_config(job, tg, topo_groups))


def build_gang_state(matrix, job: Job, tg: TaskGroup):
    """(GangState, active [K_pad], ask (res, bw, ports), config) for
    the single-gang program (ops/gang.py gang_placement_program, the
    plain reference of one lane) against a ClusterMatrix: the same
    request, with the node arrays from the matrix's host side."""
    from ..ops.gang import make_gang_state

    req = build_gang_request(matrix, job, tg)
    lane = req.lane
    state = make_gang_state(
        matrix.capacity, matrix.sched_capacity, matrix.util,
        matrix.bw_avail, matrix.bw_used, matrix.ports_free,
        lane.feas_row & matrix.node_ok, lane.job_count,
        lane.dh_presence, req.topo_ids)
    return (state, lane.active,
            (lane.ask_res, lane.ask_bw, lane.ask_ports), req.config)


# ---------------------------------------------------------------- stats

_stats_lock = threading.Lock()
_stats: Dict[str, int] = {}


def note_gang_result(placed: bool, members: int, path: str) -> None:
    """Count one gang attempt's outcome (leaf lock, constant work).
    ``path`` is "device" | "host"."""
    with _stats_lock:
        _stats["gangs_placed" if placed else "gangs_rejected"] = (
            _stats.get("gangs_placed" if placed else "gangs_rejected", 0)
            + 1)
        if placed:
            _stats["members_placed"] = (
                _stats.get("members_placed", 0) + members)
        key = f"path_{path}"
        _stats[key] = _stats.get(key, 0) + 1


def note_gang_dispatch(gangs: int, moved: int) -> None:
    """Count one device dispatch of the gang program (the batcher's):
    how many gangs rode it, and how many of them an earlier lane's
    claims moved off the rack they would have taken alone."""
    with _stats_lock:
        _stats["dispatches"] = _stats.get("dispatches", 0) + 1
        _stats["dispatched_gangs"] = (
            _stats.get("dispatched_gangs", 0) + gangs)
        _stats["moved_by_claims"] = _stats.get("moved_by_claims", 0) + moved


def note_mixed_batch() -> None:
    """Count one hand-over of a mixed pipeline batch: a plain dispatch
    that started from the claims of its batch's gang dispatch
    (scheduler/batcher.py _take_claims)."""
    with _stats_lock:
        _stats["mixed_batches"] = _stats.get("mixed_batches", 0) + 1


def note_gang_rejected_whole(gangs: int) -> None:
    """Count gangs the plan applier removed whole because a member's
    node failed verification (server/plan_apply.py; one `gang.rejected`
    span each)."""
    with _stats_lock:
        _stats["rejected_whole"] = _stats.get("rejected_whole", 0) + gangs


def gang_stats() -> Dict[str, object]:
    """Counters, and `gangs_per_dispatch` once a dispatch has gone."""
    with _stats_lock:
        out: Dict[str, object] = dict(_stats)
    if out.get("dispatches"):
        out["gangs_per_dispatch"] = round(
            out["dispatched_gangs"] / out["dispatches"], 3)
    return out


def reset_gang_stats() -> None:
    with _stats_lock:
        _stats.clear()


def spread_cap(k: int, eligible_groups: int) -> int:
    """The spread mode's per-group member cap (shared by the host leg
    and the rig's judge so they can never disagree with the device
    formula)."""
    return int(math.ceil(k / max(eligible_groups, 1)))
