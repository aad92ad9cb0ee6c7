"""Dense priority preemption: victim selection and placement in one
masked pass (ROADMAP item 3; SURVEY.md build-plan stage 7).

A cluster whose MACHINES are full has no headroom for a
high-priority eval, whatever the control plane's pressure reads: the
normal dense pass leaves its asks unplaced and the eval would block.
An eval above the priority threshold then runs this pass over the
unplaced asks (migrate.preemption_eligible; a cluster with headroom
never gets here). The reference handles this with per-node iterator
walks over candidate allocs; here the whole decision runs as ONE
compiled program over the cluster:

- the host hands over a ``VictimState``: per node, the V
  lowest-priority live allocations sorted priority-ascending, with
  their resource/bandwidth/port footprints (models/matrix.py: a table
  kept beside the cached cluster base and carried along its delta
  chain, ``ClusterMatrix.build_victims`` patches the few rows this
  eval's own job or plan touches);
- for each ask the kernel computes, per node, the cumulative capacity
  freed by evicting the first k victims (a prefix cumsum over the
  sorted axis) and the smallest k that makes the ask fit — *victim
  choice on device*, and lowest-priority-first by construction: a
  prefix of a priority-ascending sort can never evict an alloc while
  sparing a lower-priority one on the same node;
- nodes that fit WITHOUT eviction always win (preemption scores carry
  a per-victim penalty on top of the post-eviction BestFit score), so
  the pass degenerates to the normal argmax whenever capacity exists;
- the scan carries both the claimed capacity AND the consumed-victim
  mask, so later asks in the same eval neither double-count a
  victim's capacity nor evict it twice.

The kernel returns (choice, score, n_victims) per ask; the host maps
``n_victims`` back to concrete allocations (the next n unconsumed
entries of the node's sorted candidate list — identical order by
construction) and stages them on the plan's ``node_preemptions`` leg,
which the plan applier re-verifies victim-by-victim against the
snapshot before committing eviction + placement in one raft apply
(server/plan_apply.py). A victim lost between selection and
verification (chaos site ``preempt.victim_lost``) costs a replan,
never a double-evict.

Shapes are static: N and K ride the caller's buckets, V is the fixed
``PREEMPT_MAX_VICTIMS`` — the preemption leg compiles once per bucket
and steady-state ``jit_recompiles`` stays 0 (it joins the placement
path's jit accounting in ops/binpack.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as _np

from .binpack import NEG_INF, _score_and_mask

# Per-node victim candidate ceiling. An ask that needs more than this
# many evictions on one node is pathological (it wants the node, not
# room on it) — the pass simply finds no fit there.
PREEMPT_MAX_VICTIMS = 8

# Score penalty per evicted victim: preemption must prefer the node
# that disrupts least, and any node that fits WITHOUT eviction beats
# any that needs one (normal fits never pay this penalty).
PREEMPT_VICTIM_PENALTY = 2.0


class VictimState(NamedTuple):
    """Per-node preemption candidates, priority-ascending along V.
    Padding slots: ok=False, prio=+inf, zero footprint."""

    res: jnp.ndarray  # [N, V, 4] victim resource footprints
    bw: jnp.ndarray  # [N, V]
    ports: jnp.ndarray  # [N, V] dynamic-port counts held
    prio: jnp.ndarray  # [N, V] job priority (f32; padding = +inf)
    ok: jnp.ndarray  # [N, V] live candidate (not padding/consumed)


def make_victim_state(res, bw, ports, prio, ok) -> VictimState:
    """HOST-side (numpy) victim state — device residency happens once,
    inside the jitted call (see binpack.make_node_state)."""
    f32 = functools.partial(_np.asarray, dtype=_np.float32)
    return VictimState(
        res=f32(res), bw=f32(bw), ports=f32(ports), prio=f32(prio),
        ok=_np.asarray(ok, bool),
    )


def _preempt_step(state, vok, victims: VictimState, ask, eval_priority,
                  config, noise):
    """One ask's combined place-or-preempt decision."""
    (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, active,
     job_dh, tg_dh) = ask
    n = state.util.shape[0]
    v = victims.prio.shape[1]

    score = _score_and_mask(
        state, ask_res, ask_bw, ask_ports, feas_row, tg_onehot, job_dh,
        tg_dh, config, noise)
    normal_fit = score > NEG_INF / 2

    # Non-capacity eligibility, mirrored from _score_and_mask: a node
    # we would evict into must still satisfy constraints/readiness and
    # distinct-hosts for this ask.
    tg_cnt = jnp.sum(state.tg_count * tg_onehot[None, :], axis=1)
    elig_node = feas_row
    elig_node &= jnp.where(job_dh, state.job_count == 0, True)
    elig_node &= jnp.where(tg_dh, tg_cnt == 0, True)

    # Prefix frees over the live candidates (consumed/padding slots
    # contribute nothing and do not break the prefix).
    okf = vok.astype(jnp.float32)
    freed = jnp.cumsum(victims.res * okf[:, :, None], axis=1)  # [N,V,4]
    freed_bw = jnp.cumsum(victims.bw * okf, axis=1)  # [N,V]
    freed_ports = jnp.cumsum(victims.ports * okf, axis=1)  # [N,V]
    elig_prefix = jnp.cumprod(
        (~vok) | (victims.prio < eval_priority), axis=1).astype(bool)

    new_util = state.util + ask_res[None, :]  # [N,4]
    fits_k = jnp.all(new_util[:, None, :] - freed
                     <= state.capacity[:, None, :], axis=2)
    fits_k &= (state.bw_used + ask_bw)[:, None] - freed_bw \
        <= state.bw_avail[:, None]
    fits_k &= state.ports_free[:, None] + freed_ports >= ask_ports
    # Slot k itself must be a live, outrankable victim: a prefix ending
    # on a dead slot frees nothing the shorter prefix didn't.
    fits_k &= elig_prefix & vok

    k_star = jnp.argmax(fits_k, axis=1)  # first fitting prefix
    can_preempt = fits_k.any(axis=1) & elig_node & ~normal_fit

    take = functools.partial(jnp.take_along_axis, indices=k_star[:, None],
                             axis=1)
    freed_star = jnp.take_along_axis(
        freed, k_star[:, None, None], axis=1)[:, 0, :]  # [N,4]
    freed_bw_star = take(freed_bw)[:, 0]
    freed_ports_star = take(freed_ports)[:, 0]
    nv = take(jnp.cumsum(okf, axis=1))[:, 0]  # live victims in prefix

    # Post-eviction BestFit score with the per-victim disruption
    # penalty (binpack.py ScoreFit shape).
    util_after = new_util - freed_star
    denom = jnp.maximum(state.sched_capacity, 1.0)
    free_frac = 1.0 - util_after / denom
    fitness = 20.0 - (jnp.power(10.0, free_frac[:, 0])
                      + jnp.power(10.0, free_frac[:, 1]))
    fitness = jnp.clip(fitness, 0.0, 18.0)
    pscore = (fitness
              - config.anti_affinity_penalty
              * state.job_count.astype(jnp.float32)
              - PREEMPT_VICTIM_PENALTY * nv
              + noise)
    # Preemption is strictly last-resort PER ASK: while any node fits
    # without eviction, the eviction branch is masked out entirely —
    # BestFit's packing preference must never out-score zero
    # disruption (an empty node scores LOW on fitness by design).
    any_fit = normal_fit.any()
    total = jnp.where(normal_fit, score,
                      jnp.where(can_preempt & ~any_fit, pscore, NEG_INF))

    choice = jnp.argmax(total)
    valid = (total[choice] > NEG_INF / 2) & active
    preempted = valid & ~normal_fit[choice]
    clean_score = total[choice] - noise[choice]

    safe = jnp.where(valid, choice, n)
    d_util = ask_res - jnp.where(preempted, freed_star[choice], 0.0)
    d_bw = ask_bw - jnp.where(preempted, freed_bw_star[choice], 0.0)
    d_ports = jnp.where(preempted, freed_ports_star[choice], 0.0) - ask_ports
    new_state = state._replace(
        util=state.util.at[safe].add(d_util, mode="drop"),
        bw_used=state.bw_used.at[safe].add(d_bw, mode="drop"),
        ports_free=state.ports_free.at[safe].add(d_ports, mode="drop"),
        job_count=state.job_count.at[safe].add(1, mode="drop"),
        tg_count=state.tg_count.at[safe].add(
            tg_onehot.astype(jnp.int32), mode="drop"),
    )
    # Consume the chosen prefix's live victims.
    row = vok[jnp.clip(choice, 0, n - 1)]
    consume = preempted & (jnp.arange(v) <= k_star[jnp.clip(choice, 0, n - 1)])
    new_vok = vok.at[safe].set(row & ~consume, mode="drop")

    out_choice = jnp.where(valid, choice, -1).astype(jnp.int32)
    out_score = jnp.where(valid, clean_score, 0.0)
    out_nv = jnp.where(preempted,
                       nv[jnp.clip(choice, 0, n - 1)], 0.0).astype(jnp.int32)
    return new_state, new_vok, (out_choice, out_score, out_nv)


def preempt_placement_program(state, victims: VictimState, asks, key,
                              eval_priority, config):
    """K sequential place-or-preempt decisions as one compiled program.
    Same NodeState/Asks contract as binpack.placement_program, plus the
    victim tensor; returns (choices [K], scores [K], n_victims [K]).
    ``eval_priority`` is traced (a plain f32 scalar), so every priority
    shares one compiled program per shape bucket."""
    k_count = asks.resources.shape[0]
    n = state.util.shape[0]
    g = state.feasible.shape[1]
    noise = jax.random.uniform(
        key, (k_count, n), minval=0.0, maxval=config.noise_scale)
    tg_onehots = (jnp.arange(g)[None, :] == asks.tg_index[:, None])
    feas_rows = (jnp.take(state.feasible, asks.tg_index, axis=1).T
                 & state.node_ok[None, :])
    tg_dhs = jnp.take(asks.tg_distinct_hosts, asks.tg_index)

    def body(carry, xs):
        st, vok = carry
        (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, tg_dh, active,
         noise_row) = xs
        new_st, new_vok, out = _preempt_step(
            st, vok, victims,
            (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, active,
             asks.job_distinct_hosts, tg_dh),
            eval_priority, config, noise_row)
        return (new_st, new_vok), out

    with jax.named_scope("preempt_scan"):
        (_, _), (choices, scores, n_victims) = jax.lax.scan(
            body, (state, victims.ok),
            (asks.resources, asks.bw, asks.ports, feas_rows, tg_onehots,
             tg_dhs, asks.active, noise))
    return choices, scores, n_victims


@functools.partial(jax.jit, static_argnames=("config",))
def preempt_placement_program_jit(state, victims, asks, key,
                                  eval_priority, config):
    return preempt_placement_program(state, victims, asks, key,
                                     eval_priority, config)
