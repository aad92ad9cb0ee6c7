"""Dense all-K gang placement over the node-topology tensor.

A gang (structs/job.py ``Gang``) is a task group of count K that
places ATOMICALLY: all K members or none. The reference scheduler has
no such mode — DL-shaped workloads (Tesserae, PAPERS.md) get it here
as one compiled program over the cluster:

- **per-node fit mask -> member capacity**: how many gang members each
  node could hold (min over resource dims of floor(free/ask), bounded
  by bandwidth/ports/feasibility; clamped to 1 under distinct-hosts);
- **topology-group cumulative capacity**: member capacities scatter-add
  by the node-topology id column (models/topology.py) into per-group
  totals — the dense form of "does any rack fit the whole gang?";
- **slice selection**: among groups whose capacity covers all K, pick
  the TIGHTEST sufficient slice (smallest covering capacity, noise
  tie-broken) — a gang should consume the fragment that fits it, not
  crack open the emptiest rack (the BestFit ethos at rack granularity);
- **member assignment**: a K-step masked-argmax scan restricted to the
  chosen slice (or spread/affinity-masked for those modes), carrying
  claimed capacity and per-group member counts;
- **all-K enforcement ON DEVICE**: if any member came back unplaced,
  every choice is rewritten to -1 — a partial gang never leaves the
  device.

Modes (static, from the gang stanza): ``slice`` (hard contiguity),
``spread`` (≤ ceil(K / eligible groups) members per group),
``affinity`` (soft co-location bonus), ``free`` (atomicity only).

Shapes are static — N and K ride the caller's buckets and the
topology-group axis rides TOPO_GROUP_BUCKETS (models/topology.py) —
so the gang leg compiles once per (bucket, config) and steady-state
``jit_recompiles`` stays 0 (it joins the placement path's jit
accounting in ops/binpack.py).

A DISPATCH is B gangs (scheduler/batcher.py queues gang requests like
plain ones and stacks them): ``batched_gang_placement_program`` scans
the lanes in the batch's order and carries utilisation, bandwidth and
ports from one gang to the next, so the gangs of a burst, which all
plan on one snapshot and would all pick the same tightest rack, see
each other's claims on device instead of colliding at the plan applier
(as the plain lanes of the shared-base programs do). Inside a lane the pass is
``gang_placement_program``, which stays the plain reference of one
lane. The batch axis rides the batcher's BATCH_BUCKETS. The scan's final
carry is an output, and its start may be another dispatch's carry: where
the gangs' pipeline batch holds plain asks too, the plain lanes go first
and the gangs start from what they claimed (scheduler/batcher.py).

The host twin lives in nomad_tpu/gang/host.py; the plan applier's
per-node verification plus the ``Plan.gang_groups`` atomicity leg
(server/plan_apply.py) make any device approximation cost a replan,
never a partial commit.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as _np

from .binpack import NEG_INF

GANG_MODE_SLICE = "slice"
GANG_MODE_SPREAD = "spread"
GANG_MODE_AFFINITY = "affinity"
GANG_MODE_FREE = "free"

# Soft co-location bonus per already-placed gang member in the node's
# topology group (affinity mode). Half the service anti-affinity
# penalty: co-location should steer ties, not overpower fit quality.
GANG_AFFINITY_BONUS = 5.0


class GangConfig(NamedTuple):
    """Static (compile-time) gang-program knobs. ``g_pad`` is the
    bucketed topology-group axis size (TOPO_GROUP_BUCKETS) — part of
    the compiled shape like the node bucket."""

    anti_affinity_penalty: float
    mode: str = GANG_MODE_FREE
    distinct_hosts: bool = False
    g_pad: int = 16
    noise_scale: float = 2.0

    @property
    def kernel(self) -> str:
        """What a dispatch of this program is called where a plain one
        names its placement kernel (the device.solve span)."""
        return "gang"


class GangState(NamedTuple):
    """Dense per-node inputs for one gang dispatch. All [N] unless
    noted. HOST-side numpy by convention (binpack.make_node_state):
    device residency happens once, inside the jitted call."""

    capacity: jnp.ndarray  # [N, 4]
    sched_capacity: jnp.ndarray  # [N, 4]
    util: jnp.ndarray  # [N, 4]
    bw_avail: jnp.ndarray  # [N]
    bw_used: jnp.ndarray  # [N]
    ports_free: jnp.ndarray  # [N]
    feas_row: jnp.ndarray  # [N] bool: gang TG feasibility & node_ok
    job_count: jnp.ndarray  # [N] this job's allocs (anti-affinity)
    dh_presence: jnp.ndarray  # [N] existing same-host conflicts under
    #                            distinct-hosts (zeros when dh off)
    topo_ids: jnp.ndarray  # [N] topology group id (-1 = excluded)


def make_gang_state(capacity, sched_capacity, util, bw_avail, bw_used,
                    ports_free, feas_row, job_count, dh_presence,
                    topo_ids) -> GangState:
    f32 = functools.partial(_np.asarray, dtype=_np.float32)
    return GangState(
        capacity=f32(capacity), sched_capacity=f32(sched_capacity),
        util=f32(util), bw_avail=f32(bw_avail), bw_used=f32(bw_used),
        ports_free=f32(ports_free),
        feas_row=_np.asarray(feas_row, bool),
        job_count=_np.asarray(job_count, _np.int32),
        dh_presence=_np.asarray(dh_presence, _np.int32),
        topo_ids=_np.asarray(topo_ids, _np.int32),
    )


def _member_units(state: GangState, ask_res, ask_bw, ask_ports,
                  config: GangConfig):
    """[N] f32: how many gang members each node can hold from its
    current free capacity. 0 on infeasible/excluded nodes."""
    big = 1e9
    free = state.capacity - state.util  # [N, 4]
    per_dim = jnp.where(ask_res[None, :] > 0,
                        jnp.floor(free / jnp.maximum(ask_res[None, :],
                                                     1e-9)),
                        big)
    units = jnp.min(per_dim, axis=1)
    units = jnp.minimum(units, jnp.where(
        ask_bw > 0,
        jnp.floor((state.bw_avail - state.bw_used)
                  / jnp.maximum(ask_bw, 1e-9)),
        big))
    units = jnp.minimum(units, jnp.where(
        ask_ports > 0,
        jnp.floor(state.ports_free / jnp.maximum(ask_ports, 1e-9)),
        big))
    units = jnp.maximum(units, 0.0)
    units = jnp.where(state.feas_row, units, 0.0)
    if config.distinct_hosts:
        units = jnp.minimum(units, 1.0)
        units = jnp.where(state.dh_presence > 0, 0.0, units)
    if config.mode == GANG_MODE_SLICE:
        # Nodes without a topology id can never prove contiguity.
        units = jnp.where(state.topo_ids >= 0, units, 0.0)
    return units


def _group_capacity(units, topo_ids, g_pad):
    """[g_pad] f32 member capacity per topology group; ids < 0 scatter
    out of range and drop. Under shard_map the inputs are one node-axis
    SHARD of the fleet and the result is the shard's PARTIAL group
    capacity — parallel/shard.py sharded_group_capacity psums the
    partials (a gang slice can span shards)."""
    safe_ids = jnp.where(topo_ids >= 0, topo_ids, g_pad)
    return jnp.zeros(g_pad, jnp.float32).at[safe_ids].add(
        units, mode="drop")


def _group_noise(key, g_pad: int):
    """One uniform draw per topology group: the slice choice's
    tie-break, from the caller's key."""
    return jax.random.uniform(
        jax.random.fold_in(key, 1), (g_pad,), minval=0.0, maxval=1.0)


def _select_slice(group_cap, topo_ids, k_actual, group_noise,
                  config: GangConfig):
    """Slice selection: the tightest topology group whose capacity
    covers all K, noise tie-broken. Returns (chosen group id [] int32,
    -1 when the mode has no slice or no group covers the gang; [N] bool
    mask of the nodes a member may take)."""
    n = topo_ids.shape[0]
    if config.mode != GANG_MODE_SLICE:
        return jnp.int32(-1), jnp.ones(n, bool)
    with jax.named_scope("gang_slice_select"):
        covers = group_cap >= k_actual
        # Smaller sufficient capacity scores higher; noise < 1 breaks
        # exact-capacity ties without reordering distinct capacities.
        gscore = jnp.where(covers, -group_cap + group_noise, NEG_INF)
        best = jnp.argmax(gscore)
        any_group = gscore[best] > NEG_INF / 2
        chosen_group = jnp.where(any_group, best, -1).astype(jnp.int32)
        # A -1 sentinel must match NOTHING: compare against g_pad + 1
        # (no real id) when no group covers the gang.
        match = jnp.where(any_group, best, config.g_pad + 1)
        return chosen_group, topo_ids == match


def gang_placement_program(state: GangState, ask_res, ask_bw, ask_ports,
                           active, key, config: GangConfig):
    """Place one gang of K uniform members. ``active`` is the [K]
    padded member mask (binpack Asks convention). Returns
    (choices [K] int32, scores [K] f32, slice_group [] int32):
    choices are ALL >= 0 (a full gang) or ALL -1 (whole-gang reject);
    slice_group is the chosen topology group id (-1 when the mode has
    no slice or nothing placed)."""
    n = state.util.shape[0]
    k = active.shape[0]
    g_pad = config.g_pad
    k_actual = jnp.sum(active.astype(jnp.float32))

    # One uniform draw per (member, node) + one per group, all from the
    # caller's host key (binpack.host_prng_key layout).
    noise = jax.random.uniform(
        key, (k, n), minval=0.0, maxval=config.noise_scale)
    group_noise = _group_noise(key, g_pad)

    units = _member_units(state, ask_res, ask_bw, ask_ports, config)
    group_cap = _group_capacity(units, state.topo_ids, g_pad)

    chosen_group, slice_mask = _select_slice(
        group_cap, state.topo_ids, k_actual, group_noise, config)

    # ---- spread cap: at most ceil(K / eligible groups) per group.
    spread_cap = jnp.float32(k)
    if config.mode == GANG_MODE_SPREAD:
        eligible = jnp.maximum(jnp.sum((group_cap >= 1.0)
                                       .astype(jnp.float32)), 1.0)
        spread_cap = jnp.ceil(k_actual / eligible)

    safe_ids = jnp.where(state.topo_ids >= 0, state.topo_ids, g_pad)

    def body(carry, xs):
        util, bw_used, ports_free, placed, group_members = carry
        member_active, noise_row = xs

        new_util = util + ask_res[None, :]
        fits = jnp.all(new_util <= state.capacity, axis=1)
        fits &= bw_used + ask_bw <= state.bw_avail
        fits &= ports_free >= ask_ports
        fits &= state.feas_row
        fits &= slice_mask
        if config.distinct_hosts:
            fits &= (placed == 0) & (state.dh_presence == 0)
        if config.mode == GANG_MODE_SPREAD:
            gcount = group_members[jnp.clip(safe_ids, 0, g_pad - 1)]
            fits &= jnp.where(state.topo_ids >= 0,
                              gcount < spread_cap, True)

        denom = jnp.maximum(state.sched_capacity, 1.0)
        free_frac = 1.0 - new_util / denom
        fitness = 20.0 - (jnp.power(10.0, free_frac[:, 0])
                          + jnp.power(10.0, free_frac[:, 1]))
        fitness = jnp.clip(fitness, 0.0, 18.0)
        fitness = jnp.where(
            (state.sched_capacity[:, 0] <= 0)
            | (state.sched_capacity[:, 1] <= 0), 0.0, fitness)
        score = fitness - config.anti_affinity_penalty * (
            state.job_count + placed).astype(jnp.float32)
        if config.mode == GANG_MODE_AFFINITY:
            gcount = group_members[jnp.clip(safe_ids, 0, g_pad - 1)]
            score = score + GANG_AFFINITY_BONUS * jnp.where(
                state.topo_ids >= 0, gcount, 0.0)
        score = score + noise_row
        score = jnp.where(fits, score, NEG_INF)

        choice = jnp.argmax(score)
        valid = (score[choice] > NEG_INF / 2) & member_active
        clean = score[choice] - noise_row[choice]
        safe = jnp.where(valid, choice, n)
        gid = safe_ids[jnp.clip(choice, 0, n - 1)]
        gsafe = jnp.where(valid & (gid < g_pad), gid, g_pad)
        carry = (
            util.at[safe].add(ask_res, mode="drop"),
            bw_used.at[safe].add(ask_bw, mode="drop"),
            ports_free.at[safe].add(-ask_ports, mode="drop"),
            placed.at[safe].add(1, mode="drop"),
            group_members.at[gsafe].add(1.0, mode="drop"),
        )
        out_choice = jnp.where(valid, choice, -1).astype(jnp.int32)
        out_score = jnp.where(valid, clean, 0.0)
        return carry, (out_choice, out_score)

    carry0 = (state.util, state.bw_used, state.ports_free,
              jnp.zeros(n, jnp.int32), jnp.zeros(g_pad, jnp.float32))
    with jax.named_scope("gang_scan"):
        _, (choices, scores) = jax.lax.scan(
            body, carry0, (active, noise))

    # ---- all-K enforcement: a partial gang never leaves the device.
    all_placed = jnp.all(jnp.where(active, choices >= 0, True))
    choices = jnp.where(all_placed, choices, -1).astype(jnp.int32)
    scores = jnp.where(all_placed, scores, 0.0)
    slice_group = jnp.where(
        all_placed, chosen_group, -1).astype(jnp.int32)
    return choices, scores, slice_group


@functools.partial(jax.jit, static_argnames=("config",))
def gang_placement_program_jit(state: GangState, ask_res, ask_bw,
                               ask_ports, active, key,
                               config: GangConfig):
    return gang_placement_program(state, ask_res, ask_bw, ask_ports,
                                  active, key, config)


class GangBase(NamedTuple):
    """What the lanes of one dispatch share: the cluster base's node
    arrays (device-resident per base token, scheduler/batcher.py) and
    the topology id column resident beside it."""

    capacity: jnp.ndarray  # [N, 4]
    sched_capacity: jnp.ndarray  # [N, 4]
    util: jnp.ndarray  # [N, 4]
    bw_avail: jnp.ndarray  # [N]
    bw_used: jnp.ndarray  # [N]
    ports_free: jnp.ndarray  # [N]
    node_ok: jnp.ndarray  # [N] bool
    topo_ids: jnp.ndarray  # [N] topology group id (-1 = excluded)


class GangLane(NamedTuple):
    """One gang's own inputs; a dispatch stacks B of them along a
    leading axis. HOST-side numpy, like GangState."""

    feas_row: jnp.ndarray  # [N] bool: the gang TG's feasibility
    job_count: jnp.ndarray  # [N] int32
    dh_presence: jnp.ndarray  # [N] int32
    ask_res: jnp.ndarray  # [4]
    ask_bw: jnp.ndarray  # []
    ask_ports: jnp.ndarray  # []
    active: jnp.ndarray  # [K] bool member mask; all False = padding


def make_gang_lane(feas_row, job_count, dh_presence, ask_res, ask_bw,
                   ask_ports, active) -> GangLane:
    return GangLane(
        feas_row=_np.asarray(feas_row, bool),
        job_count=_np.asarray(job_count, _np.int32),
        dh_presence=_np.asarray(dh_presence, _np.int32),
        ask_res=_np.asarray(ask_res, _np.float32),
        ask_bw=_np.asarray(ask_bw, _np.float32),
        ask_ports=_np.asarray(ask_ports, _np.float32),
        active=_np.asarray(active, bool),
    )


def batched_gang_placement_program(base: GangBase, lanes: GangLane,
                                   keys, config: GangConfig):
    """Place the B gangs of one dispatch, in the batch's order, each
    seeing the claims of those before it: an outer scan over the lanes
    carries utilisation, bandwidth and free ports; inside a lane the
    pass is gang_placement_program on the carried state. A lane whose
    gang was rejected whole claims nothing, nor does a padding lane.

    Returns (choices [B, K] int32, scores [B, K] f32, info [B, 2]
    int32, util [N, 4], bw_used [N], ports_free [N]): info[:, 0] is the
    lane's slice group, info[:, 1] is 1 where an earlier lane's claims
    moved the gang off the group it would have taken on the unclaimed
    base (slice mode only); the last three are the scan's final carry,
    the base's columns after every lane's claims. They stay on the
    device: the batcher starts the later dispatches on the same base
    token from them (scheduler/batcher.py _publish_claims)."""
    n = base.util.shape[0]

    def lane_state(util, bw_used, ports_free, lane: GangLane):
        return GangState(
            capacity=base.capacity, sched_capacity=base.sched_capacity,
            util=util, bw_avail=base.bw_avail, bw_used=bw_used,
            ports_free=ports_free,
            feas_row=lane.feas_row & base.node_ok,
            job_count=lane.job_count, dh_presence=lane.dh_presence,
            topo_ids=base.topo_ids)

    def body(carry, xs):
        util, bw_used, ports_free = carry
        lane, key = xs
        state = lane_state(util, bw_used, ports_free, lane)
        choices, scores, group = gang_placement_program(
            state, lane.ask_res, lane.ask_bw, lane.ask_ports,
            lane.active, key, config)
        moved = jnp.int32(0)
        if config.mode == GANG_MODE_SLICE:
            # The group this gang would have taken had it been solved
            # alone on the snapshot, under the same tie-break noise.
            alone = lane_state(base.util, base.bw_used, base.ports_free,
                               lane)
            units = _member_units(alone, lane.ask_res, lane.ask_bw,
                                  lane.ask_ports, config)
            alone_group, _mask = _select_slice(
                _group_capacity(units, base.topo_ids, config.g_pad),
                base.topo_ids,
                jnp.sum(lane.active.astype(jnp.float32)),
                _group_noise(key, config.g_pad), config)
            moved = ((group >= 0) & (alone_group != group)).astype(
                jnp.int32)
        safe = jnp.where(choices >= 0, choices, n)
        carry = (
            util.at[safe].add(lane.ask_res[None, :], mode="drop"),
            bw_used.at[safe].add(lane.ask_bw, mode="drop"),
            ports_free.at[safe].add(-lane.ask_ports, mode="drop"),
        )
        return carry, (choices, scores, jnp.stack([group, moved]))

    carry, (choices, scores, info) = jax.lax.scan(
        body, (base.util, base.bw_used, base.ports_free), (lanes, keys))
    return (choices, scores, info, *carry)


@functools.partial(jax.jit, static_argnames=("config",))
def batched_gang_placement_program_jit(base: GangBase, lanes: GangLane,
                                       keys, config: GangConfig):
    return batched_gang_placement_program(base, lanes, keys, config)
