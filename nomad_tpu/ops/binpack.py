"""Vectorized bin-packing placement: the TPU reformulation of the
reference's per-node iterator chain.

The reference scores candidates one node at a time through
BinPackIterator (scheduler/rank.go:161) bounded by LimitIterator
(scheduler/select.go:5). Here one evaluation's K placements run as a
`lax.scan` whose body performs the whole cluster's feasibility mask,
BestFit-v3 score, anti-affinity penalty, and masked argmax as dense
[N]-wide vector ops — one pass on the VPU instead of K x limit Python
iterations. The scan carries the proposed-usage state so placements
within an eval see each other (the reference's ProposedAllocs
semantics, scheduler/context.go:108).

Shapes are static: node count N and placement count K are bucketed by
the caller (models/matrix.py) so XLA compiles once per bucket. The
program is pure and vmap-able over a leading batch axis (independent
evals against the same snapshot = optimistic concurrency) and
shard_map-able over the node axis (parallel/mesh.py).

Port/network fidelity: dynamic-port *counts* and bandwidth are tracked
densely; exact port numbers are assigned host-side after the kernel
picks nodes, and the plan applier re-verifies every node exactly
(reference plan_apply.go:318), so a dense approximation costs at most a
retry, never correctness.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..utils.jaxcache import enable_compilation_cache

enable_compilation_cache()

# Resource dims in the dense matrices.
R_CPU, R_MEM, R_DISK, R_IOPS = 0, 1, 2, 3
NUM_RESOURCES = 4

NEG_INF = -1e30


class PlacementConfig(NamedTuple):
    """Static (compile-time) knobs."""

    anti_affinity_penalty: float  # 10 service / 5 batch (stack.go:14-18)
    # Per-eval tie-break noise, in FITNESS units. This is the dense
    # analog of the reference's shuffled power-of-two-choices
    # (stack.go:120-132 LimitIterator): concurrent evals planning
    # against ONE snapshot must spread across near-equally-good nodes,
    # or every eval argmaxes the same winners (BestFit gravitates to
    # the most-packed nodes) and the plan applier rejects all but the
    # first (measured: 1e-4 noise made a 60-eval 10k-node storm retry
    # 2.3x per eval on bandwidth conflicts). The reference takes the
    # best of ~log2(N) nodes drawn from a SHUFFLED feasible stream —
    # a random sample whose fitness spread on real clusters spans a
    # couple of points; 2.0 reproduces that quality band while
    # decorrelating concurrent evals.
    noise_scale: float = 2.0
    # Uniform distinct-hosts fast path: when EVERY active ask of an
    # eval is identical (one task group scaled to count=K, the storm
    # shape) AND distinct-hosts applies to it, the K sequential
    # argmax steps collapse to ONE scoring pass + top_k — placing on a
    # node never changes any OTHER node's score, and distinct-hosts
    # excludes the chosen node from the remaining asks, so the K-step
    # scan provably selects the K best-scoring feasible nodes: ~8x
    # fewer [N]-wide passes per eval. uniform_dh_flag() decides
    # eligibility host-side; the flag is compile-time like the rest of
    # the config, so each case is its own cached program.
    uniform_dh: bool = False
    # Placement kernel (nomad_tpu/kernels): which per-batch solve
    # placement_program runs. "greedy" is the native sequential
    # masked-argmax scan below; any other name resolves through the
    # kernel registry at trace time. Static (a hashable str), so each
    # kernel is its own cached XLA program and joins the batcher's
    # shape key — kernels never share a dispatch.
    kernel: str = "greedy"


class NodeState(NamedTuple):
    """Dense per-node cluster state. All arrays share leading dim N.

    util is the running utilization *including node reserved* and the
    capacity denominator subtracts reserved — exactly the reference's
    AllocsFit/ScoreFit accounting (structs/funcs.go:60,123).
    """

    capacity: jnp.ndarray  # [N, 4] total node resources
    sched_capacity: jnp.ndarray  # [N, 4] capacity - reserved (score denom)
    util: jnp.ndarray  # [N, 4] reserved + existing usage (scan-carried)
    bw_avail: jnp.ndarray  # [N] primary-device bandwidth
    bw_used: jnp.ndarray  # [N] (scan-carried)
    ports_free: jnp.ndarray  # [N] free dynamic-port count (scan-carried)
    job_count: jnp.ndarray  # [N] this job's allocs per node (scan-carried)
    tg_count: jnp.ndarray  # [N, G] per-task-group counts (scan-carried)
    feasible: jnp.ndarray  # [N, G] constraint feasibility (static mask)
    node_ok: jnp.ndarray  # [N] ready & real (not padding)


class Asks(NamedTuple):
    """The K placements to make, in order. Leading dim K."""

    resources: jnp.ndarray  # [K, 4]
    bw: jnp.ndarray  # [K]
    ports: jnp.ndarray  # [K] dynamic-port count
    tg_index: jnp.ndarray  # [K] int32 index into the G axis
    active: jnp.ndarray  # [K] bool (padding rows are inactive)
    job_distinct_hosts: jnp.ndarray  # [] bool
    tg_distinct_hosts: jnp.ndarray  # [G] bool


import numpy as _np


def make_node_state(
    capacity, sched_capacity, util, bw_avail, bw_used, ports_free,
    job_count, tg_count, feasible, node_ok,
) -> NodeState:
    """HOST-side (numpy) state. Deliberately NOT jnp: device residency
    happens once, inside the single jitted dispatch — eager jnp.asarray
    here would cost one host->device transfer PER FIELD PER EVAL,
    and the batcher must be able to np.stack request fields without
    pulling them back."""
    f32 = functools.partial(_np.asarray, dtype=_np.float32)
    return NodeState(
        capacity=f32(capacity),
        sched_capacity=f32(sched_capacity),
        util=f32(util),
        bw_avail=f32(bw_avail),
        bw_used=f32(bw_used),
        ports_free=f32(ports_free),
        job_count=_np.asarray(job_count, _np.int32),
        tg_count=_np.asarray(tg_count, _np.int32),
        feasible=_np.asarray(feasible, bool),
        node_ok=_np.asarray(node_ok, bool),
    )


def make_asks(
    resources, bw, ports, tg_index, active, job_distinct_hosts, tg_distinct_hosts
) -> Asks:
    """HOST-side (numpy) asks — see make_node_state on why."""
    return Asks(
        resources=_np.asarray(resources, _np.float32),
        bw=_np.asarray(bw, _np.float32),
        ports=_np.asarray(ports, _np.float32),
        tg_index=_np.asarray(tg_index, _np.int32),
        active=_np.asarray(active, bool),
        job_distinct_hosts=_np.asarray(job_distinct_hosts, bool),
        tg_distinct_hosts=_np.asarray(tg_distinct_hosts, bool),
    )


def check_device_chaos() -> None:
    """Host-side fault gate for device execution, called by the
    placement batcher immediately before it issues device programs.
    Armed with a ``binpack.device`` 'error' spec it raises
    ChaosInjectedError exactly as a real device/runtime fault would
    surface from the jitted call — the dense schedulers' recovery
    contract (fall back to the host iterator path, identical placement
    semantics) is exercised without needing a chip that actually
    fails. A no-op two-attribute check in production."""
    from ..chaos import chaos

    if chaos.enabled:
        chaos.fire("binpack.device")


def host_prng_key(seed: int) -> "_np.ndarray":
    """A threefry key as a HOST uint32[2] (what jax.random.PRNGKey
    yields, without the eager device transfer); jax.random accepts the
    raw layout inside jit."""
    return _np.array([0, _np.uint32(seed & 0xFFFFFFFF)], _np.uint32)


@jax.jit
def apply_base_delta(util, bw_used, ports_free, node_ok, rows,
                     util_rows, bw_rows, ports_rows, ok_rows):
    """Scatter-update the mutable arrays of a device-resident cluster
    base with recomputed node rows. Plan applies touch a handful of
    nodes; shipping those rows (a few hundred bytes) and updating on
    device beats re-uploading the full [N,4] base per snapshot — the
    device-side half of models/matrix.py's incremental delta path.
    Padding duplicates the first changed row (same value, so the
    duplicate-index scatter is benign); capacity/bandwidth-avail never
    change with allocs and keep the parent's device arrays by
    reference. node_ok rows ride the same scatter: a node-down/drain
    transition is a delta too (models/resident.py) — the row stays in
    the matrix, masked, instead of forcing a full rebuild of the node
    axis."""
    return (
        util.at[rows].set(util_rows),
        bw_used.at[rows].set(bw_rows),
        ports_free.at[rows].set(ports_rows),
        node_ok.at[rows].set(ok_rows),
    )


# The phases below carry jax.named_scope names (score_and_mask, topk,
# claim, claim_scan, expand_overlay): metadata on the HLO ops, so a
# profiler trace's `Framework Name Scope` line separates the time of one
# program by phase (tools/traceconv.py --xplane). The compiled code does
# not change; the jitted entry points already differ by name.
@jax.named_scope("score_and_mask")
def _score_and_mask(state: NodeState, ask_res, ask_bw, ask_ports, feas_row,
                    tg_onehot, job_dh, tg_dh, config: PlacementConfig,
                    noise):
    """One placement's dense pass: feasibility mask + score over all N
    nodes. feas_row is the [N] constraint-feasibility column for this
    ask's task group (gathered ONCE per eval outside the scan — the
    [N, G] one-hot contraction per step was pure wasted traffic);
    tg_onehot is the [G] one-hot still used for the carried tg_count
    contraction, tg_dh the scalar distinct-hosts flag for this ask's
    group. Returns masked_score [N]."""
    new_util = state.util + ask_res[None, :]

    # AllocsFit: full capacity superset on every dimension.
    fits = jnp.all(new_util <= state.capacity, axis=1)
    # Bandwidth and dynamic-port count.
    fits &= state.bw_used + ask_bw <= state.bw_avail
    fits &= state.ports_free >= ask_ports
    # Constraint feasibility for this TG (precomputed per class) and
    # node readiness, pre-ANDed into feas_row by the caller.
    fits &= feas_row
    # distinct_hosts: job-level blocks any co-placement of the job;
    # TG-level blocks only same-TG co-placement (feasible.go:211-238).
    tg_cnt = jnp.sum(state.tg_count * tg_onehot[None, :], axis=1)
    fits &= jnp.where(job_dh, state.job_count == 0, True)
    fits &= jnp.where(tg_dh, tg_cnt == 0, True)

    # ScoreFit (BestFit-v3): packed nodes score high.
    denom = jnp.maximum(state.sched_capacity, 1.0)
    free_frac = 1.0 - new_util / denom
    fitness = 20.0 - (
        jnp.power(10.0, free_frac[:, R_CPU]) + jnp.power(10.0, free_frac[:, R_MEM])
    )
    fitness = jnp.clip(fitness, 0.0, 18.0)
    # Zero schedulable capacity scores worst (fully-reserved node).
    fitness = jnp.where(
        (state.sched_capacity[:, R_CPU] <= 0) | (state.sched_capacity[:, R_MEM] <= 0),
        0.0,
        fitness,
    )

    # Job anti-affinity (rank.go:287-299).
    score = fitness - config.anti_affinity_penalty * state.job_count.astype(jnp.float32)

    # Random tie-break: preserves the reference's shuffled-source
    # de-correlation between concurrent workers.
    score = score + noise
    return jnp.where(fits, score, NEG_INF)


def placement_step(state: NodeState, ask, config: PlacementConfig, noise):
    """Place one ask: pick the argmax-score node and update the carried
    state. Returns (new_state, (choice, score)); choice is -1 when no
    node fits or the ask row is padding.

    The state update is a single-row scatter (`.at[choice]`, OOB-drop
    for the no-fit case) instead of the old [N]-wide one-hot
    multiply-adds: the update side read+wrote every carried array per
    step, roughly half the scan body's memory traffic for work that
    touches exactly one row."""
    (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, active,
     job_dh, tg_dh) = ask
    n = state.util.shape[0]

    score = _score_and_mask(
        state, ask_res, ask_bw, ask_ports, feas_row, tg_onehot, job_dh,
        tg_dh, config, noise
    )
    choice = jnp.argmax(score)
    valid = (score[choice] > NEG_INF / 2) & active
    # Reported score excludes the tie-break noise: AllocMetric must
    # carry the node's actual fitness, not the per-eval PRNG draw.
    clean_score = score[choice] - noise[choice]

    # Row n is out of range: mode="drop" makes the invalid case a no-op.
    safe = jnp.where(valid, choice, n)
    with jax.named_scope("claim"):
        new_state = state._replace(
            util=state.util.at[safe].add(ask_res, mode="drop"),
            bw_used=state.bw_used.at[safe].add(ask_bw, mode="drop"),
            ports_free=state.ports_free.at[safe].add(
                -ask_ports, mode="drop"),
            job_count=state.job_count.at[safe].add(1, mode="drop"),
            tg_count=state.tg_count.at[safe].add(
                tg_onehot.astype(jnp.int32), mode="drop"),
        )
    out_choice = jnp.where(valid, choice, -1).astype(jnp.int32)
    out_score = jnp.where(valid, clean_score, 0.0)
    return new_state, (out_choice, out_score)


def _uniform_topk_program(state: NodeState, asks: Asks, key,
                          config: PlacementConfig):
    """The uniform distinct-hosts placement: ONE scoring pass + top_k
    instead of K sequential argmax steps (see PlacementConfig.
    uniform_dh for the equivalence argument). The caller guarantees
    every active ask row is identical (uniform_dh_flag); ask row 0 is
    the representative (active rows are a prefix, padding rows are
    masked by `active` exactly like the sequential path)."""
    n = state.util.shape[0]
    g = state.feasible.shape[1]
    k_count = asks.resources.shape[0]
    ask_res = asks.resources[0]
    ask_bw = asks.bw[0]
    ask_ports = asks.ports[0]
    tg_onehot = jnp.arange(g) == asks.tg_index[0]
    feas_row = jnp.any(state.feasible & tg_onehot[None, :],
                       axis=1) & state.node_ok
    tg_dh = jnp.any(asks.tg_distinct_hosts & tg_onehot)
    noise = jax.random.uniform(key, (n,), minval=0.0,
                               maxval=config.noise_scale)
    score = _score_and_mask(
        state, ask_res, ask_bw, ask_ports, feas_row, tg_onehot,
        asks.job_distinct_hosts, tg_dh, config, noise)
    # top_k requires k <= n, and the ask bucket (k_count) can pad past
    # the node bucket (n) when count > cluster size. Surplus asks can
    # never place under distinct-hosts anyway, so clamp and pad them
    # back as unplaceable — the same choice=-1 the sequential scan
    # yields once every node carries the job.
    k_eff = min(k_count, n)
    with jax.named_scope("topk"):
        top_scores, top_idx = jax.lax.top_k(score, k_eff)
    if k_eff < k_count:
        pad = k_count - k_eff
        top_scores = jnp.concatenate(
            [top_scores, jnp.full((pad,), NEG_INF, top_scores.dtype)])
        top_idx = jnp.concatenate(
            [top_idx, jnp.zeros((pad,), top_idx.dtype)])
    valid = (top_scores > NEG_INF / 2) & asks.active
    choices = jnp.where(valid, top_idx, -1).astype(jnp.int32)
    scores_out = jnp.where(valid, top_scores - noise[top_idx], 0.0)
    # Each chosen node receives exactly one ask (distinct by top_k);
    # invalid rows scatter to row n and drop.
    safe = jnp.where(valid, top_idx, n)
    vi = valid.astype(jnp.int32)
    with jax.named_scope("claim"):
        new_state = state._replace(
            util=state.util.at[safe].add(
                jnp.where(valid[:, None], ask_res[None, :], 0.0),
                mode="drop"),
            bw_used=state.bw_used.at[safe].add(
                jnp.where(valid, ask_bw, 0.0), mode="drop"),
            ports_free=state.ports_free.at[safe].add(
                jnp.where(valid, -ask_ports, 0.0), mode="drop"),
            job_count=state.job_count.at[safe].add(vi, mode="drop"),
            tg_count=state.tg_count.at[safe].add(
                vi[:, None] * tg_onehot[None, :].astype(jnp.int32),
                mode="drop"),
        )
    return choices, scores_out, new_state


def placement_program(
    state: NodeState, asks: Asks, key, config: PlacementConfig
):
    """Run K sequential placements over the cluster as one compiled
    program. Returns (choices [K] int32, scores [K] f32, final_state).

    config.kernel selects the solve: the default runs the sequential
    masked-argmax scan below; anything else resolves through the
    kernel registry (nomad_tpu/kernels) and runs in this program's
    place — same signature, same validity mask, different solve. The
    branch is on a STATIC config field, so it happens at trace time
    and every batcher path (overlay/compact/fused-delta)
    carries any kernel unchanged."""
    if config.kernel != "greedy":
        from ..kernels import kernel_program

        return kernel_program(config.kernel)(state, asks, key, config)
    if config.uniform_dh:
        return _uniform_topk_program(state, asks, key, config)

    k_count = asks.resources.shape[0]
    n = state.util.shape[0]
    g = state.feasible.shape[1]
    # All tie-break noise drawn in one op; the scan consumes rows.
    noise = jax.random.uniform(
        key, (k_count, n), minval=0.0, maxval=config.noise_scale
    )
    tg_onehots = (
        jnp.arange(g)[None, :] == asks.tg_index[:, None]
    )  # [K, G]
    # Per-ask feasibility rows, gathered ONCE: the constraint mask is
    # static through the eval (only capacity/counters are carried), so
    # the per-step [N, G] contraction was pure overhead.
    feas_rows = (jnp.take(state.feasible, asks.tg_index, axis=1).T
                 & state.node_ok[None, :])  # [K, N]
    tg_dhs = jnp.take(asks.tg_distinct_hosts, asks.tg_index)  # [K]

    def body(carry, xs):
        (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, tg_dh, active,
         noise_row) = xs
        new_state, out = placement_step(
            carry,
            (ask_res, ask_bw, ask_ports, feas_row, tg_onehot, active,
             asks.job_distinct_hosts, tg_dh),
            config,
            noise_row,
        )
        return new_state, out

    with jax.named_scope("claim_scan"):
        final_state, (choices, scores) = jax.lax.scan(
            body,
            state,
            (asks.resources, asks.bw, asks.ports, feas_rows, tg_onehots,
             tg_dhs, asks.active, noise),
        )
    return choices, scores, final_state


@functools.partial(jax.jit, static_argnames=("config",))
def placement_program_jit(state: NodeState, asks: Asks, key, config: PlacementConfig):
    return placement_program(state, asks, key, config)


@functools.partial(jax.jit, static_argnames=("config",))
def batched_placement_program(states: NodeState, asks: Asks, keys, config: PlacementConfig):
    """vmap over a leading batch axis: B independent evals planned
    against the same snapshot (optimistic concurrency — conflicts are
    caught by the plan applier, SURVEY.md section 2.4)."""
    return jax.vmap(
        lambda s, a, k: placement_program(s, a, k, config)
    )(states, asks, keys)


def _patched(carry, patch, sign: float):
    """The carry (util, bw_used, ports_free) with one lane's plan patch
    put in (`sign` 1) or taken out again (-1). `patch` is (rows [P],
    values [P, 6]: cpu, memory, disk, iops, bandwidth, dynamic ports;
    models/matrix.py ClusterMatrix.plan_patch): what the lane's plan
    stops and has placed, as the difference it makes on the rows it
    touches. Padding rows are N, out of range, and dropped. The sums are
    integers under 2^24, exact in float32: in and out again is the
    carry it was, plus the lane's claims."""
    util, bw_used, ports_free = carry
    rows, vals = patch
    vals = vals * sign
    return (util.at[rows].add(vals[:, :NUM_RESOURCES], mode="drop"),
            bw_used.at[rows].add(vals[:, 4], mode="drop"),
            ports_free.at[rows].add(-vals[:, 5], mode="drop"))


@functools.partial(jax.jit, static_argnames=("config",))
def batched_placement_program_overlay(
    state: NodeState, asks: Asks, keys, config: PlacementConfig, patches
):
    """Batched evals of DIFFERENT jobs against one shared snapshot: the
    heavy [N,4] base matrices are unbatched (uploaded once per
    snapshot, cached on device by the batcher), while job_count [B,N],
    tg_count/feasible [B,N,G], asks, and keys carry the batch axis.
    This is what makes live broker-drain batches cheap: per dispatch
    only the small per-job overlays move host->device.

    The eval axis is a lax.scan whose carry is the shared mutable
    cluster state (util, bw_used, ports_free), so eval i+1 plans
    against what evals 0..i claimed — the in-batch analog of the plan
    applier's serialization (plan_apply.go:194). B evals planning
    independently against one snapshot argmax toward the same headroom
    and the applier rejects the collisions, each rejection a full
    dispatch round-trip to replan. The per-job overlay fields
    (job_count/tg_count/feasible) stay per-eval: they describe the
    eval's OWN job. Batch-padding rows scan AFTER the real rows, so
    their phantom claims never affect a real output. Returns the
    lanes' final carry after the choices and scores.

    `patches` (rows [B, P], values [B, P, 6]) are the lanes' plan
    patches (_patched): a lane plans on the carry with its own patch
    put in, and the patch is taken out again before the carry goes on,
    so capacity that a lane's stops would free is that lane's alone to
    place on, and the carry handed on holds placements only."""

    def body(carry, xs):
        (job_count, tg_count, feasible), a, k, patch = xs
        util, bw_used, ports_free = _patched(carry, patch, 1.0)
        s = state._replace(
            util=util, bw_used=bw_used, ports_free=ports_free,
            job_count=job_count, tg_count=tg_count, feasible=feasible,
        )
        choices, scores, final = placement_program(s, a, k, config)
        return (_patched((final.util, final.bw_used, final.ports_free),
                         patch, -1.0), (choices, scores))

    carry0 = (state.util, state.bw_used, state.ports_free)
    xs = ((state.job_count, state.tg_count, state.feasible), asks, keys,
          patches)
    carry, (choices, scores) = jax.lax.scan(body, carry0, xs)
    return choices, scores, carry


class CompactOverlay(NamedTuple):
    """Per-eval overlay in its pre-expansion form: what actually needs
    to cross host->device per request. The dense [N]/[N,G] overlays are
    rebuilt ON DEVICE from these — at 10k nodes the dense overlay is
    ~100KB x G per request, while this is a few KB:

    - feasibility = class verdicts [C, G] expanded through the base's
      device-resident class_ids [N], plus a sparse patch for rows the
      class verdict can't represent (classless nodes, escaped
      constraints);
    - job/tg counts = scatter-adds of this job's alloc row positions.

    Padding convention: row arrays pad with N (out of range) and the
    scatters drop OOB indices."""

    verdicts: jnp.ndarray  # [C, G] bool per-class feasibility
    patch_rows: jnp.ndarray  # [P] int32 node rows (pad = N)
    patch_vals: jnp.ndarray  # [P, G] bool row feasibility
    job_rows: jnp.ndarray  # [J] int32 rows of this job's allocs (pad = N)
    job_tgs: jnp.ndarray  # [J] int32 their task-group indices


@jax.named_scope("expand_overlay")
def _expand_overlay(class_ids, ov: CompactOverlay, n: int, g: int):
    """Device-side overlay reconstruction (one eval)."""
    classed = class_ids >= 0
    feasible = jnp.where(
        classed[:, None],
        ov.verdicts[jnp.clip(class_ids, 0), :],
        False,
    )
    feasible = feasible.at[ov.patch_rows].set(ov.patch_vals, mode="drop")
    job_count = jnp.zeros(n, jnp.int32).at[ov.job_rows].add(1, mode="drop")
    tg_count = jnp.zeros((n, g), jnp.int32).at[ov.job_rows, ov.job_tgs].add(
        1, mode="drop")
    return feasible, job_count, tg_count


def _compact_batch(capacity, sched_capacity, util, bw_avail, bw_used,
                   ports_free, node_ok, class_ids, overlays, patches, asks,
                   keys, config):
    """The eval axis of a compact batch: the scan of
    batched_placement_program_overlay, each lane's overlay expanded on
    the device inside its step and its plan patch put in for that step
    alone."""
    n = util.shape[0]
    g = overlays.verdicts.shape[-1]

    def body(carry, xs):
        ov, patch, a, k = xs
        u, bw, pf = _patched(carry, patch, 1.0)
        feasible, job_count, tg_count = _expand_overlay(
            class_ids, ov, n, g)
        s = NodeState(
            capacity=capacity, sched_capacity=sched_capacity, util=u,
            bw_avail=bw_avail, bw_used=bw, ports_free=pf,
            job_count=job_count, tg_count=tg_count, feasible=feasible,
            node_ok=node_ok,
        )
        choices, scores, final = placement_program(s, a, k, config)
        return (_patched((final.util, final.bw_used, final.ports_free),
                         patch, -1.0), (choices, scores))

    carry, (choices, scores) = jax.lax.scan(
        body, (util, bw_used, ports_free), (overlays, patches, asks, keys))
    return choices, scores, carry


@functools.partial(jax.jit, static_argnames=("config",))
def batched_placement_program_compact(
    capacity, sched_capacity, util, bw_avail, bw_used, ports_free,
    node_ok, class_ids, overlays: CompactOverlay, patches, asks: Asks,
    keys, config: PlacementConfig
):
    """The overlay path with device-side overlay expansion: the seven
    base arrays and class_ids are the device-cached cluster base
    (unbatched); `overlays` and `patches` carry the batch axis on every
    field and the dense per-eval masks/counts are rebuilt on device."""
    return _compact_batch(capacity, sched_capacity, util, bw_avail,
                          bw_used, ports_free, node_ok, class_ids,
                          overlays, patches, asks, keys, config)


@functools.partial(jax.jit, static_argnames=("config",))
def batched_placement_program_compact_delta(
    capacity, sched_capacity, util, bw_avail, bw_used, ports_free,
    node_ok, class_ids, rows, util_rows, bw_rows, ports_rows, ok_rows,
    overlays: CompactOverlay, patches, asks: Asks, keys,
    config: PlacementConfig
):
    """Compact dispatch FUSED with a base delta-update: the mutable
    base arrays come from the (device-cached) PARENT snapshot and the
    changed rows ride this very call's arguments — deriving the child
    base costs zero extra round-trips. Returns the batch results plus
    the updated (util, bw_used, ports_free, node_ok) for the batcher to
    cache under the child's token and, after them, the lanes' final
    carry (util, bw_used, ports_free after every lane's claims) for the
    dispatches that follow on that token.
    Padding rows duplicate a real row (same value, so the
    duplicate-index scatter is benign)."""
    util2 = util.at[rows].set(util_rows)
    bw2 = bw_used.at[rows].set(bw_rows)
    ports2 = ports_free.at[rows].set(ports_rows)
    ok2 = node_ok.at[rows].set(ok_rows)
    choices, scores, final = _compact_batch(
        capacity, sched_capacity, util2, bw_avail, bw2, ports2,
        ok2, class_ids, overlays, patches, asks, keys, config)
    return (choices, scores, util2, bw2, ports2, ok2) + tuple(final)


@jax.jit
def device_resident(*arrays):
    """Identity program: makes host arrays device-resident in ONE call.
    jax.device_put issues one transfer per array while jitted-call
    arguments all ride the call itself. What that is worth on an
    attached chip has not been measured."""
    return arrays


def uniform_dh_flag(placements, job_dh, tg_dh) -> bool:
    """Host-side eligibility check for PlacementConfig.uniform_dh:
    True when every placement asks for the SAME task group (identical
    resources by construction — asks are per-TG) and distinct-hosts
    applies to it (job-level, or TG-level for that group). The flag is
    static, so mixed batches never share a program with uniform ones
    (it joins the batcher's shape key via the config)."""
    if not placements:
        return False
    gi = placements[0]
    if any(p != gi for p in placements):
        return False
    return bool(job_dh) or bool(_np.asarray(tg_dh).reshape(-1)[gi])


# ------------------------------------------------------- jit accounting
#
# Every jitted entry point of the placement path, so the compile-cache
# size (programs compiled this process) is one number: steady state is
# FLAT — a growing count under load is a recompile storm (a shape
# bucket leak, an unhashable static arg, a drifting ladder) silently
# eating multi-second trace+compile stalls. Exposed via
# server.stats()["device_state"] and /v1/metrics (the benchmark's
# window_compiles reads it and chip_smoke.py holds it flat across a
# steady wave).

# The static mirror of _jit_entry_points() + the parallel/shard.py
# factory caches, enforced two ways: ntalint's `unregistered-jit` rule
# flags any jit/lru_cache site in ops//kernels//models//parallel/
# missing from this manifest, and tests/test_compile_surface.py diffs
# it against both the AST scan and the runtime tuple below — the
# static rule and jit_cache_size() accounting can never disagree.
NTA_JIT_ACCOUNTED = (
    "placement_program_jit",
    "batched_placement_program",
    "batched_placement_program_overlay",
    "batched_placement_program_compact",
    "batched_placement_program_compact_delta",
    "apply_base_delta",
    "device_resident",
    "preempt_placement_program_jit",
    "gang_placement_program_jit",
    "batched_gang_placement_program_jit",
    # parallel/shard.py program factories, accounted via
    # shard_cache_size() (one compile per (mesh, pad) build key).
    "sharded_base_delta",
    "sharded_group_capacity",
)

_JIT_ENTRY_POINTS = ()


def _jit_entry_points():
    global _JIT_ENTRY_POINTS
    if not _JIT_ENTRY_POINTS:
        # The preemption leg (ops/preempt.py) and the gang leg
        # (ops/gang.py) are part of the placement path's compile
        # budget: jit_cache_size() must see their caches too, or a
        # preemption/gang shape leak would hide.
        from .gang import (
            batched_gang_placement_program_jit,
            gang_placement_program_jit,
        )
        from .preempt import preempt_placement_program_jit

        _JIT_ENTRY_POINTS = (
            placement_program_jit,
            batched_placement_program,
            batched_placement_program_overlay,
            batched_placement_program_compact,
            batched_placement_program_compact_delta,
            apply_base_delta,
            device_resident,
            preempt_placement_program_jit,
            gang_placement_program_jit,
            batched_gang_placement_program_jit,
        )
    return _JIT_ENTRY_POINTS


def jit_cache_size() -> int:
    """Total compiled-program count across the placement entry points
    (jax's per-function in-process jit cache). The defrag loop's
    global-relaxation solve (nomad_tpu/defrag/solver.py) joins the
    count: it is off the latency path, but a shape leak there would
    eat the same multi-second compile stalls — steady state is exactly
    cold+warm per live (K bucket, N) shape and then FLAT.

    `_cache_size` is jax's private per-function counter; a JAX without
    it raises AttributeError here — a recompile gate that reads 0 of
    nothing would be true of nothing."""
    from ..defrag.solver import solve_cache_size
    from ..parallel.shard import shard_cache_size

    total = solve_cache_size() + shard_cache_size()
    for fn in _jit_entry_points():
        total += fn._cache_size()
    return total
