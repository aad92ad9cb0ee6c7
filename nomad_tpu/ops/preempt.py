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
- per node, the cumulative capacity freed by evicting the first k
  victims (a prefix sum over the sorted axis) and, for each ask, the
  smallest k that makes the ask fit — *victim choice on device*, and
  lowest-priority-first by construction: a prefix of a
  priority-ascending sort can never evict an alloc while sparing a
  lower-priority one on the same node;
- nodes that fit WITHOUT eviction always win (preemption scores carry
  a per-victim penalty on top of the post-eviction BestFit score), so
  the pass degenerates to the normal argmax whenever capacity exists;
- the scan carries both the claimed capacity AND the consumed-victim
  mask, so later asks in the same eval neither double-count a
  victim's capacity nor evict it twice.

Layout: everything of victim granularity has the NODE axis last
(``VictimState``: ``[4, V, N]`` and ``[V, N]``; the prefix tables and
the utilisation the victim arithmetic reads likewise), so that the
chip's (8, 128) tiles hold V = 8 slots of 128 nodes with no padding.
The prefix tables depend only on the victims and the live mask, and an
ask changes the mask on ONE node: they are computed once before the
scan and carried, and an ask that evicts takes its prefix out of the
chosen node's lane (``_consume``: one masked select over the tables;
a dynamic slice along the lanes made the compiler copy four tables
node-major inside the loop, at 2.5 times the device time: PERF.md,
PR 35). What an ask does over the whole cell is one comparison of the
carried tables against the carried utilisation, the first fitting
prefix as a one-hot over the V slots, the score, one argmax and that
select. The scoring rule itself is binpack's ``_score_and_mask`` on
its ``[N, 4]`` ``NodeState``, which reads the carried utilisation as a
transposed view.

The kernel returns (choice, score, n_victims) per ask, stacked in one
array and read back in one transfer (``unpack_result``); the host maps
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

from .binpack import NEG_INF, NodeState, _score_and_mask

# Per-node victim candidate ceiling. An ask that needs more than this
# many evictions on one node is pathological (it wants the node, not
# room on it) — the pass simply finds no fit there.
PREEMPT_MAX_VICTIMS = 8

# Score penalty per evicted victim: preemption must prefer the node
# that disrupts least, and any node that fits WITHOUT eviction beats
# any that needs one (normal fits never pay this penalty).
PREEMPT_VICTIM_PENALTY = 2.0


class VictimState(NamedTuple):
    """Per-node preemption candidates, priority-ascending along V, the
    NODE axis last: one (8, 128) tile of the chip holds the V = 8 slots
    of 128 nodes and nothing is padded (with N leading, an (8, 4) slab
    a node was a whole tile for 128 bytes of data: 32 times the size).
    Padding slots: ok=False, prio=+inf, zero footprint."""

    res: jnp.ndarray  # [4, V, N] victim resource footprints
    bw: jnp.ndarray  # [V, N]
    ports: jnp.ndarray  # [V, N] dynamic-port counts held
    prio: jnp.ndarray  # [V, N] job priority (f32; padding = +inf)
    ok: jnp.ndarray  # [V, N] live candidate (not padding/consumed)


def make_victim_state(res, bw, ports, prio, ok) -> VictimState:
    """HOST-side (numpy) victim state, node axis last as the victim
    table keeps it (models/matrix.py _VictimTable) — device residency
    happens once, inside the jitted call (see binpack.make_node_state).
    """
    f32 = functools.partial(_np.asarray, dtype=_np.float32)
    return VictimState(
        res=f32(res), bw=f32(bw), ports=f32(ports), prio=f32(prio),
        ok=_np.asarray(ok, bool),
    )


class _Prefix(NamedTuple):
    """What evicting a node's live candidates up to and including slot
    k frees, for every k: a function of the victims and the live mask
    alone, so the scan carries it."""

    freed: jnp.ndarray  # [4, V, N]
    freed_bw: jnp.ndarray  # [V, N]
    freed_ports: jnp.ndarray  # [V, N]
    live: jnp.ndarray  # [V, N] live candidates among slots 0..k (f32)
    # the four sums first: _consume and the step take them as [:4]
    elig: jnp.ndarray  # [V, N] slot k live, it and all before outranked


def _running(x, op):
    """Running `op` along the victim axis (second to last), slot by
    slot: V - 1 elementwise steps over [..., N] (a jnp.cumsum is a
    reduce-window on the chip, a quarter of the old program's time)."""
    out = [x[..., 0, :]]
    for k in range(1, x.shape[-2]):
        out.append(op(out[-1], x[..., k, :]))
    return jnp.stack(out, axis=-2)


def _prefix_tables(victims: VictimState, vok, eval_priority) -> _Prefix:
    """Prefix frees over the live candidates (consumed/padding slots
    contribute nothing and do not break the prefix). A prefix ending on
    a dead slot frees nothing the shorter prefix didn't, so only a
    live, outrankable slot k may end one."""
    okf = vok.astype(jnp.float32)
    outranked = _running(
        (~vok) | (victims.prio < eval_priority), jnp.logical_and)
    return _Prefix(
        freed=_running(victims.res * okf, jnp.add),
        freed_bw=_running(victims.bw * okf, jnp.add),
        freed_ports=_running(victims.ports * okf, jnp.add),
        live=_running(okf, jnp.add),
        elig=outranked & vok,
    )


def _consume(prefix: _Prefix, vok, hit, k_star, star):
    """The tables and the live mask after the node `hit` marks (one
    lane, or none) evicted its prefix ending on slot k_star: every live
    slot up to k_star is gone, so there a prefix frees nothing and may
    not end; a longer one frees what it did less the evicted prefix
    (`star`: the four sums' values at k_star), and is as eligible as
    it was, because the evicted slots were outranked and are now dead.
    Whole numbers under 2^24 (MHz, MB, Mbit, ports): the difference is
    exactly the fresh sum over the slots left."""
    slot = jnp.arange(vok.shape[0])[:, None]
    cut = hit[None, :] & (slot <= k_star[None, :])  # [V,N]
    sums = (jnp.where(cut, 0.0,
                      jnp.where(hit, table - at_star[..., None, :], table))
            for table, at_star in zip(prefix[:4], star))
    return _Prefix(*sums, elig=prefix.elig & ~cut), vok & ~cut


class _Carry(NamedTuple):
    """What the scan carries, node axis last but for tg_count, which
    is NodeState's. The NodeState the scoring rule reads is the static
    one with these put in (util as a transposed view)."""

    util: jnp.ndarray  # [4, N]
    bw_used: jnp.ndarray  # [N]
    ports_free: jnp.ndarray  # [N]
    job_count: jnp.ndarray  # [N]
    tg_count: jnp.ndarray  # [N, G]
    vok: jnp.ndarray  # [V, N]
    prefix: _Prefix


def _preempt_step(carry: _Carry, state: NodeState, ask, config, noise):
    """One ask's combined place-or-preempt decision. `state` holds the
    static columns (capacity, feasibility, readiness); everything an
    ask changes is in `carry`."""
    (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, active,
     job_dh, tg_dh) = ask
    prefix = carry.prefix
    v, n = carry.vok.shape

    score = _score_and_mask(
        state._replace(
            util=carry.util.T, bw_used=carry.bw_used,
            ports_free=carry.ports_free, job_count=carry.job_count,
            tg_count=carry.tg_count),
        ask_res, ask_bw, ask_ports, feas_row, tg_onehot, job_dh,
        tg_dh, config, noise)
    normal_fit = score > NEG_INF / 2

    # Non-capacity eligibility, mirrored from _score_and_mask: a node
    # we would evict into must still satisfy constraints/readiness and
    # distinct-hosts for this ask.
    tg_cnt = jnp.sum(carry.tg_count * tg_onehot[None, :], axis=1)
    elig_node = feas_row
    elig_node &= jnp.where(job_dh, carry.job_count == 0, True)
    elig_node &= jnp.where(tg_dh, tg_cnt == 0, True)

    new_util = carry.util + ask_res[:, None]  # [4,N]
    fits_k = jnp.all(new_util[:, None, :] - prefix.freed
                     <= state.capacity.T[:, None, :], axis=0)  # [V,N]
    fits_k &= (carry.bw_used + ask_bw)[None, :] - prefix.freed_bw \
        <= state.bw_avail[None, :]
    fits_k &= carry.ports_free[None, :] + prefix.freed_ports >= ask_ports
    fits_k &= prefix.elig

    # The first fitting prefix, as a one-hot over the V slots (all
    # false on a node where none fits: k_star is v there), and the
    # tables' values at it.
    slot = jnp.arange(v)[:, None]
    k_star = jnp.min(jnp.where(fits_k, slot, v), axis=0)  # [N]
    first = slot == k_star[None, :]
    can_preempt = (k_star < v) & elig_node & ~normal_fit
    star = tuple(jnp.sum(jnp.where(first, table, 0.0), axis=-2)
                 for table in prefix[:4])
    freed_star, freed_bw_star, freed_ports_star, nv = star

    # Post-eviction BestFit score with the per-victim disruption
    # penalty (binpack.py ScoreFit shape).
    denom = jnp.maximum(state.sched_capacity.T, 1.0)
    free_frac = 1.0 - (new_util - freed_star) / denom
    fitness = 20.0 - (jnp.power(10.0, free_frac[0])
                      + jnp.power(10.0, free_frac[1]))
    fitness = jnp.clip(fitness, 0.0, 18.0)
    pscore = (fitness
              - config.anti_affinity_penalty
              * carry.job_count.astype(jnp.float32)
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

    # Node n is out of range: mode="drop" makes the invalid case a no-op.
    safe = jnp.where(valid, choice, n)
    d_util = ask_res - jnp.where(preempted, freed_star[:, choice], 0.0)
    d_bw = ask_bw - jnp.where(preempted, freed_bw_star[choice], 0.0)
    d_ports = jnp.where(preempted, freed_ports_star[choice], 0.0) - ask_ports
    # The chosen node's lane where the ask evicts, no lane where it
    # does not: then nothing is consumed and the tables stay as they
    # are.
    hit = (jnp.arange(n) == choice) & preempted
    new_prefix, new_vok = _consume(prefix, carry.vok, hit, k_star, star)
    new_carry = _Carry(
        util=carry.util.at[:, safe].add(d_util, mode="drop"),
        bw_used=carry.bw_used.at[safe].add(d_bw, mode="drop"),
        ports_free=carry.ports_free.at[safe].add(d_ports, mode="drop"),
        job_count=carry.job_count.at[safe].add(1, mode="drop"),
        tg_count=carry.tg_count.at[safe].add(
            tg_onehot.astype(jnp.int32), mode="drop"),
        vok=new_vok, prefix=new_prefix,
    )
    return new_carry, jnp.stack([
        jnp.where(valid, choice, -1).astype(jnp.float32),
        jnp.where(valid, clean_score, 0.0),
        jnp.where(preempted, nv[choice], 0.0),
    ])


def _scan_parts(state, victims: VictimState, asks, key, eval_priority,
                config):
    """(body, init, xs) of the program's scan over the asks."""
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
        (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, tg_dh, active,
         noise_row) = xs
        return _preempt_step(
            carry, state,
            (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, active,
             asks.job_distinct_hosts, tg_dh),
            config, noise_row)

    init = _Carry(
        util=state.util.T, bw_used=state.bw_used,
        ports_free=state.ports_free, job_count=state.job_count,
        tg_count=state.tg_count, vok=victims.ok,
        prefix=_prefix_tables(victims, victims.ok, eval_priority))
    xs = (asks.resources, asks.bw, asks.ports, feas_rows, tg_onehots,
          tg_dhs, asks.active, noise)
    return body, init, xs


def preempt_placement_program(state, victims: VictimState, asks, key,
                              eval_priority, config):
    """K sequential place-or-preempt decisions as one compiled program.
    Same NodeState/Asks contract as binpack.placement_program, plus the
    victim tensor; returns ONE float32 array [3, K], read on the host
    with ``unpack_result``: the node chosen (-1: none), its score
    without the noise, the victims taken (node rows and counts are
    whole numbers far under 2^24: exact). ``eval_priority`` is traced
    (a plain f32 scalar), so every priority shares one compiled program
    per shape bucket."""
    body, init, xs = _scan_parts(state, victims, asks, key, eval_priority,
                                 config)
    with jax.named_scope("preempt_scan"):
        _, out = jax.lax.scan(body, init, xs)
    return out.T


def unpack_result(out):
    """The program's [3, K] result on the host, in one transfer:
    (choices int32, scores float32, n_victims int32)."""
    choices, scores, n_victims = _np.asarray(out)
    return (choices.astype(_np.int32), scores,
            n_victims.astype(_np.int32))


@functools.partial(jax.jit, static_argnames=("config",))
def preempt_placement_program_jit(state, victims, asks, key,
                                  eval_priority, config):
    return preempt_placement_program(state, victims, asks, key,
                                     eval_priority, config)
