"""Convex-relaxation bin-packing placement kernel (CvxCluster-style).

The greedy kernel (ops/binpack.py) places the K asks of an eval
SEQUENTIALLY: each step argmaxes the masked BestFit score against the
carried state. That is the reference's semantics, but it is myopic —
step j cannot see asks j+1..K, and the tie-break noise that
decorrelates concurrent evals also scatters placements across
near-equal nodes, stranding capacity in fragments (the Tesserae
fragmentation axis the quality scoreboard measures).

This kernel solves the JOINT problem first, then rounds:

1. **Relax** the node-per-ask assignment to a simplex-constrained
   program: x[k, :] is a distribution over the N nodes for ask k.
   The objective trades three terms —
   the per-(ask, node) BestFit affinity computed once at the initial
   state; a quadratic penalty on EXPECTED over-capacity (cpu/mem/disk/
   iops + bandwidth + ports under the relaxed loads), which is what
   makes the K asks repel each other away from jointly-overcommitted
   nodes; and a concentration reward on expected per-node load that
   pulls asks onto already-utilized (and shared) nodes — the
   anti-fragmentation pressure a sequential argmax cannot express.

2. **Solve** with a fixed-iteration mirror-descent loop: gradient
   ascent on logits with x = softmax(logits) is exactly entropic
   projection onto the simplex, the projection structure CvxCluster
   exploits (PAPERS.md: first-order relaxations run 100-1000x faster
   than exact solvers and vectorize natively). `lax.scan` over
   SOLVE_ITERS keeps the loop inside one XLA program; shapes are the
   caller's buckets, so steady-state recompiles stay 0.

3. **Round** with the greedy repair scan, score-biased by the relaxed
   solution: each step's feasibility mask (`_score_and_mask` — the
   SAME mask the greedy kernel and the CPU oracle enforce) guarantees
   capacity/bandwidth/ports/distinct-hosts/constraint validity at the
   carried state, and ROUND_BIAS * x[k] steers the argmax toward the
   relaxation's choice. An ask whose relaxed node no longer fits
   falls through to the next-best FEASIBLE node — the repair pass.
   Validity is therefore structurally identical to greedy: the
   relaxation can only change WHICH feasible node wins, never whether
   an infeasible one does (kernels/differential.py asserts this
   against the CPU oracle).

Pure and transform-safe: vmap-able over the batch axis, scan-able
over the lanes' carry, exactly like the greedy program — the batcher's
overlay/compact/fused-delta paths ride unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.binpack import (
    NEG_INF,
    NUM_RESOURCES,
    R_CPU,
    R_MEM,
    Asks,
    NodeState,
    PlacementConfig,
    _score_and_mask,
)

# Fixed solver iteration count: compile-time constant so the mirror-
# descent loop is ONE lax.scan inside the cached program. 12 steps
# converges the storm shapes (K <= 64) well past rounding precision —
# the gradient is CLOSED-FORM (below), so each step is a couple of
# [K,N]/[N,R] contractions, not an autodiff replay.
SOLVE_ITERS = 12
# Mirror-descent step on the logits. The affinity term is in fitness
# units (0..18); 0.35 crosses that range in a handful of steps without
# oscillating against the quadratic penalty.
SOLVE_STEP = 0.35
# Weight of the quadratic expected-overcapacity penalty, per
# normalized resource dimension. Large enough that one fully
# overcommitted dimension (load ratio 1 over capacity) dominates the
# whole affinity range.
OVER_PENALTY = 60.0
# Concentration (anti-fragmentation) reward on expected per-node load:
# pulls asks toward already-utilized nodes and toward sharing nodes
# with each other, up against the overcapacity penalty.
PACK_REWARD = 6.0
# Rounding bias: how many fitness points the relaxation's preference
# is worth in the repair scan's argmax. Bounded, so a NEG_INF
# (infeasible) mask can never be overridden; comparable to the
# BestFit dynamic range, so the relaxation decides ties and near-ties
# while a grossly worse node still loses.
ROUND_BIAS = 8.0
# Strand-awareness in the repair scan: fitness-point cost of leaving a
# node with free capacity that no longer fits this ask (normalized
# waste fraction x this weight). BestFit is blind to the ask quantum —
# it prefers the TIGHTEST feasible node even when the remainder
# strands (headroom 1.6x ask beats 2.0x ask, wasting 0.6 of an ask) —
# and the tie-break noise randomizes choices within ~2 fitness points
# besides. This term is what turns the rounding into a
# fragmentation-aware repair pass; it biases WITHIN the feasible set
# only, so validity is untouched.
STRAND_BIAS = 12.0


def mirror_descent(logits, lin, mask, res_active, bw_active, ports_active,
                   base_frac, base_bw_frac, denom_nr, bw_denom, ports_denom,
                   active, iters: int):
    """The entropic mirror-descent loop on the relaxed joint-assignment
    objective, factored out so the hot-path kernel (`_relaxed_assignment`)
    and the off-path defrag solver (nomad_tpu/defrag/solver.py) run the
    SAME program — the defrag loop warm-starts it from the previous
    round's logits, which is where the CvxCluster-style re-solve
    speedup comes from. `iters` must be a compile-time constant (the
    loop is UNROLLED: at these shapes a lax.scan's per-iteration
    dispatch overhead on CPU backends outweighs the whole body, and
    the flat graph fuses). Returns the final logits (the iterate the
    warm start carries)."""
    for _ in range(iters):
        x = jax.nn.softmax(logits + mask, axis=1) * active
        exp_load = base_frac + jnp.einsum("kn,kr->nr", x,
                                          res_active) / denom_nr
        over = jnp.maximum(exp_load - 1.0, 0.0)
        over_bw = jnp.maximum(
            base_bw_frac + (x.T @ bw_active) / bw_denom - 1.0, 0.0)
        over_ports = jnp.maximum(
            (x.T @ ports_active) / ports_denom - 1.0, 0.0)
        tot = jnp.sum(exp_load, axis=1) / NUM_RESOURCES
        node_term = (PACK_REWARD / NUM_RESOURCES) * tot[:, None] \
            - 2.0 * OVER_PENALTY * over  # [N, R]: d obj / d exp_load
        g = (lin
             + jnp.einsum("nr,kr->kn", node_term / denom_nr, res_active)
             - 2.0 * OVER_PENALTY
             * (jnp.outer(bw_active, over_bw / bw_denom)
                + jnp.outer(ports_active, over_ports / ports_denom)))
        logits = logits + SOLVE_STEP * g
    return logits


def _relaxed_assignment(state: NodeState, asks: Asks,
                        config: PlacementConfig):
    """Solve the simplex-relaxed joint assignment; returns x [K, N]
    (rows of inactive asks are meaningless and ignored downstream)."""
    g = state.feasible.shape[1]

    # -------- static per-(ask, node) structure, computed once --------
    tg_onehots = (jnp.arange(g)[None, :]
                  == asks.tg_index[:, None])  # [K, G]
    feas = (jnp.take(state.feasible, asks.tg_index, axis=1).T
            & state.node_ok[None, :])  # [K, N]
    # Initial-state resource fit, one [K, N] plane per dimension (the
    # [K, N, R] broadcast would be ~0.5GB at the top buckets).
    headroom = state.capacity - state.util  # [N, R]
    for r in range(NUM_RESOURCES):
        feas &= asks.resources[:, r][:, None] <= headroom[None, :, r]
    feas &= (asks.bw[:, None]
             <= (state.bw_avail - state.bw_used)[None, :])
    feas &= asks.ports[:, None] <= state.ports_free[None, :]
    tg_cnt = jnp.einsum("ng,kg->kn", state.tg_count,
                        tg_onehots.astype(state.tg_count.dtype))
    tg_dhs = jnp.take(asks.tg_distinct_hosts, asks.tg_index)  # [K]
    feas &= jnp.where(asks.job_distinct_hosts,
                      state.job_count[None, :] == 0, True)
    feas &= jnp.where(tg_dhs[:, None], tg_cnt == 0, True)

    # BestFit affinity at the initial state (ScoreFit on the post-
    # placement free fractions, anti-affinity included) — the linear
    # term of the objective.
    denom_nr = jnp.maximum(state.sched_capacity, 1.0)  # [N, R]
    free_cpu = 1.0 - (state.util[None, :, R_CPU]
                      + asks.resources[:, None, R_CPU]) / denom_nr[None, :, R_CPU]
    free_mem = 1.0 - (state.util[None, :, R_MEM]
                      + asks.resources[:, None, R_MEM]) / denom_nr[None, :, R_MEM]
    fitness = 20.0 - (jnp.power(10.0, free_cpu)
                      + jnp.power(10.0, free_mem))
    fitness = jnp.clip(fitness, 0.0, 18.0)
    fitness = jnp.where(
        (state.sched_capacity[None, :, R_CPU] <= 0)
        | (state.sched_capacity[None, :, R_MEM] <= 0),
        0.0, fitness)
    affinity = fitness - (config.anti_affinity_penalty
                          * state.job_count.astype(jnp.float32)[None, :])

    active = asks.active.astype(jnp.float32)[:, None]  # [K, 1]
    mask = jnp.where(feas, 0.0, NEG_INF)  # [K, N]

    # Expectation terms stay [K,R] x [K,N] -> [N,R] contractions — the
    # [K,N,R] broadcast they replace is ~0.5GB at the top buckets.
    # Normalizing by schedulable capacity puts every dimension (and
    # every node size) on one scale so OVER_PENALTY means the same
    # thing at 1 core as at 64.
    res_active = asks.resources * active  # [K, R]
    bw_active = asks.bw * active[:, 0]  # [K]
    ports_active = asks.ports * active[:, 0]  # [K]
    base_frac = state.util / denom_nr
    bw_denom = jnp.maximum(state.bw_avail, 1.0)
    base_bw_frac = state.bw_used / bw_denom
    ports_denom = jnp.maximum(state.ports_free, 1.0)
    lin = jnp.where(feas, affinity, 0.0)

    # Entropic mirror descent (exponentiated gradient) with the
    # CLOSED-FORM gradient:
    #
    #   obj(x) = <x, lin>
    #            - OVER_PENALTY * (|over|^2 + |over_bw|^2 + |over_p|^2)
    #            + PACK_REWARD/2 * |tot|^2
    #
    # with exp_load = base_frac + (x^T res)/denom (per node/dim),
    # over = relu(exp_load - 1), tot = mean_r exp_load, so
    #
    #   d obj/d x[k,n] = lin[k,n]
    #     + sum_r (PACK_REWARD/R * tot[n] - 2*OVER_PENALTY*over[n,r])
    #             * res[k,r]/denom[n,r]
    #     - 2*OVER_PENALTY * (over_bw[n]*bw[k]/bw_denom[n] + ports...)
    #
    # The MD step on the simplex is x <- x*exp(step*g) renormalized =
    # logits += step*g under softmax — NOT the Euclidean chain rule
    # x*(g - <x,g>), which stalls exactly when x is still diffuse.
    # The shared loop lives in mirror_descent() (the defrag solver
    # warm-starts the same program across rounds).
    logits = mirror_descent(
        lin, lin, mask, res_active, bw_active, ports_active,
        base_frac, base_bw_frac, denom_nr, bw_denom, ports_denom,
        active, SOLVE_ITERS)  # init at the objective's own linear term
    return jax.nn.softmax(logits + mask, axis=1)


def convex_placement_program(state: NodeState, asks: Asks, key,
                             config: PlacementConfig):
    """Drop-in for ops/binpack.placement_program (PlacementConfig.
    kernel == "convex"): relaxed joint solve, then the feasibility-
    mask-respecting rounding scan. Returns (choices [K] int32,
    scores [K] f32, final_state)."""
    x = _relaxed_assignment(state, asks, config)

    k_count = asks.resources.shape[0]
    n = state.util.shape[0]
    g = state.feasible.shape[1]
    noise = jax.random.uniform(
        key, (k_count, n), minval=0.0, maxval=config.noise_scale)
    tg_onehots = (jnp.arange(g)[None, :]
                  == asks.tg_index[:, None])  # [K, G]
    feas_rows = (jnp.take(state.feasible, asks.tg_index, axis=1).T
                 & state.node_ok[None, :])  # [K, N]
    tg_dhs = jnp.take(asks.tg_distinct_hosts, asks.tg_index)  # [K]

    # Rounding preference, max-normalized (raw softmax mass spreads
    # over N nodes — the RELATIVE ordering is the signal). Two parts:
    # the ask's own row, and the relaxation's AGGREGATE node mass
    # y[n] = sum_k x[k,n] — the node SET the joint solve decided to
    # fill. The aggregate is what breaks the identical-asks
    # degeneracy: symmetric asks get symmetric rows (the LP cannot
    # order them), but their SUM marks how much total load the solve
    # wants on each node, and the sequential repair scan then packs
    # that set in order, falling to the next-preferred node exactly
    # when the carried state stops fitting.
    y = jnp.sum(x, axis=0)
    pref = (x / (jnp.max(x, axis=1, keepdims=True) + 1e-9)
            + y[None, :] / (jnp.max(y) + 1e-9)) * 0.5

    def body(carry, xs):
        (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, tg_dh, active,
         noise_row, pref_row) = xs
        # The SAME mask/score the greedy kernel and the oracle enforce,
        # evaluated at the CARRIED state — feasibility here is exact.
        score = _score_and_mask(
            carry, ask_res, ask_bw, ask_ports, feas_row, tg_onehot,
            asks.job_distinct_hosts, tg_dh, config, noise_row)
        # Strand lookahead (see STRAND_BIAS): what this placement
        # leaves behind on each node, in ask-quanta. Nodes whose
        # post-placement headroom still fits another such ask (or is
        # ~zero) cost nothing; a remainder in (0, ask) is waste,
        # weighted by its normalized size over the dimensions the ask
        # actually uses.
        head = carry.capacity - carry.util - ask_res[None, :]  # [N, R]
        fits_another = jnp.all(head >= ask_res[None, :], axis=1)
        used_dim = (ask_res > 0).astype(jnp.float32)  # [R]
        waste = (jnp.maximum(head, 0.0)
                 / jnp.maximum(carry.sched_capacity, 1.0)) @ used_dim \
            / jnp.maximum(jnp.sum(used_dim), 1.0)
        strand_pen = jnp.where(fits_another, 0.0, waste)
        bias = ROUND_BIAS * pref_row - STRAND_BIAS * strand_pen
        biased = score + bias
        choice = jnp.argmax(biased)
        valid = (biased[choice] > NEG_INF / 2) & active
        # Reported score excludes the tie-break noise AND the
        # relaxation bias: AllocMetric carries the node's actual
        # BestFit fitness, comparable across kernels.
        clean_score = score[choice] - noise_row[choice]

        safe = jnp.where(valid, choice, n)  # row n: OOB-drop no-op
        new_state = carry._replace(
            util=carry.util.at[safe].add(ask_res, mode="drop"),
            bw_used=carry.bw_used.at[safe].add(ask_bw, mode="drop"),
            ports_free=carry.ports_free.at[safe].add(
                -ask_ports, mode="drop"),
            job_count=carry.job_count.at[safe].add(1, mode="drop"),
            tg_count=carry.tg_count.at[safe].add(
                tg_onehot.astype(jnp.int32), mode="drop"),
        )
        out_choice = jnp.where(valid, choice, -1).astype(jnp.int32)
        out_score = jnp.where(valid, clean_score, 0.0)
        return new_state, (out_choice, out_score)

    final_state, (choices, scores) = jax.lax.scan(
        body,
        state,
        (asks.resources, asks.bw, asks.ports, feas_rows, tg_onehots,
         tg_dhs, asks.active, noise, pref),
    )
    return choices, scores, final_state
