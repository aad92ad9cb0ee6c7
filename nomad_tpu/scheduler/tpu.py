"""TPU/dense scheduler factories ("service-tpu", "batch-tpu",
"system-tpu").

The north-star design (BASELINE.json): identical control flow to the
host schedulers — same reconciliation, same blocked-eval/rolling
semantics, same plan shape — but computePlacements runs as one dense
program instead of per-node iterators. In-place updates and
sticky-disk preferences stay host-side (SURVEY.md section 7 hard
parts); exact port numbers are assigned host-side on the chosen nodes;
the plan applier re-verifies every node so kernel approximations cost
retries, not correctness.

The generic path searches (masked argmax on the TPU); the system path
(system_sched.go) pins every placement to its node, so its dense
reformulation is pure vectorized feasibility+fit over the pinned rows
— no search, one ClusterMatrix build instead of a per-node iterator
stack per placement.
"""

from __future__ import annotations

import logging
import random
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import trace
from ..structs import (
    Allocation,
    AllocMetric,
    NetworkIndex,
    NetworkResource,
    Resources,
    consts,
)
from ..utils.ids import generate_uuid
from .generic import GenericScheduler
from .system import SystemScheduler
from .util import AllocTuple

# A preemption-eligible eval whose task groups ask for at most this
# many allocations in all pads its asks to that count on its FIRST
# attempt already (see BatchedTPUScheduler._compute_placements): the top
# of the ask ladder's small steps (models/matrix.py ASK_BUCKETS
# 8/16/32/64). A replan pads to its first attempt's count whatever the
# size.
REPLAN_PAD_MAX_ASKS = 64


def _offer_networks(rng, missing: AllocTuple, node, net_indexes, matrix):
    """Exact per-task network offers on a dense-path-chosen node.
    Returns {task: Resources} or None if a port can't be assigned."""
    idx = net_indexes.get(node.id)
    if idx is None:
        idx = NetworkIndex()
        idx.set_node(node)
        idx.add_allocs(matrix._proposed_allocs(node.id))
        net_indexes[node.id] = idx

    task_resources: Dict[str, Resources] = {}
    for task in missing.task_group.tasks:
        resources = task.resources.copy()
        if resources.networks:
            ask = resources.networks[0]
            offer, err = idx.assign_network(ask, rng)
            if offer is None:
                # Drop the partially-updated index; it is rebuilt
                # from the plan on next use.
                net_indexes.pop(node.id, None)
                return None
            idx.add_reserved(offer)
            resources.networks = [offer]
        task_resources[task.name] = resources
    return task_resources


def build_placement_config(batch: bool, kernel, placements, ask_arrays):
    """The PlacementConfig BatchedTPUScheduler hands the batcher. One
    factory for the STATIC fields that key compiled device programs
    (penalty, uniform_dh, kernel): a second way to build
    them would mint a second program per shape bucket (a recompile
    storm; analysis/compile_surface.py sanctions this factory)."""
    from ..kernels import active_kernel
    from ..ops.binpack import PlacementConfig, uniform_dh_flag
    from .stack import (
        BATCH_JOB_ANTI_AFFINITY_PENALTY,
        SERVICE_JOB_ANTI_AFFINITY_PENALTY,
    )

    kernel = kernel or active_kernel()
    return PlacementConfig(
        anti_affinity_penalty=(
            BATCH_JOB_ANTI_AFFINITY_PENALTY if batch
            else SERVICE_JOB_ANTI_AFFINITY_PENALTY),
        # Uniform distinct-hosts fast path: one TG scaled to count=K
        # under distinct-hosts (the storm shape) collapses the K-step
        # scan to one scoring pass + top_k (ops/binpack.py). Static, so
        # mixed batches never share a program with uniform ones.
        # Greedy-only: non-default kernels run their own joint solve.
        uniform_dh=(kernel == "greedy" and uniform_dh_flag(
            placements, ask_arrays[5], ask_arrays[6])),
        kernel=kernel,
    )


def _build_allocation(sched, missing: AllocTuple, node, task_resources,
                      metrics) -> Allocation:
    """The Allocation literal both dense schedulers append to the plan
    (shared so the field set can't drift between them)."""
    alloc = Allocation(
        id=generate_uuid(),
        eval_id=sched.eval.id,
        name=missing.name,
        job_id=sched.job.id,
        task_group=missing.task_group.name,
        metrics=metrics,
        node_id=node.id,
        task_resources=task_resources,
        desired_status=consts.ALLOC_DESIRED_RUN,
        client_status=consts.ALLOC_CLIENT_PENDING,
        shared_resources=Resources(
            disk_mb=missing.task_group.ephemeral_disk.size_mb
        ),
    )
    if missing.alloc is not None and missing.alloc.id:
        alloc.previous_allocation = missing.alloc.id
    return alloc


class BatchedTPUScheduler(GenericScheduler):
    """GenericScheduler whose bulk placement loop runs on the TPU.

    `kernel` pins the placement kernel (nomad_tpu/kernels) for this
    scheduler instance — the `service-<kernel>-tpu` factory variants
    set it; None defers to the process-global active kernel
    (kernels.configure, fed by ServerConfig.placement_kernel)."""

    def __init__(self, logger, state, planner, batch=False, rng=None,
                 kernel: Optional[str] = None):
        super().__init__(logger, state, planner, batch=batch, rng=rng)
        self.kernel = kernel
        # The dense asks of this scheduler's first attempt (None before
        # it): an inline replan after a partly rejected plan pads to it.
        self._first_asks: Optional[int] = None

    def _inplace_update(self, updates):
        """Batched host-side in-place routing (scheduler/util.py
        inplace_update_batched): compatible tweaks rewrite allocs with
        zero evictions and zero device dispatches; only destructive
        updates flow on to the dense placement path."""
        from .util import inplace_update_batched

        return inplace_update_batched(
            self.ctx, self.eval, self.job, self.stack, updates)

    def _compute_placements(self, place: List[AllocTuple]) -> None:
        from ..models.matrix import ClusterMatrix
        from ..ops.binpack import host_prng_key, make_asks
        from .batcher import get_batcher

        # Gang task groups (nomad_tpu/gang) take the dense all-K pass:
        # one gang = one lane of ops/gang.py's batched program, through
        # the batcher like the plain asks below, atomically staged on
        # the plan's gang leg.
        gang_sets, place = self._split_gang_placements(place)
        for tg, tuples in gang_sets:
            self._place_gang_dense(tg, tuples)
        if not place:
            if gang_sets:
                self._settle_cohort()
            return
        # Sticky-disk placements keep the host path (they pin to one node).
        sticky: List[AllocTuple] = []
        bulk: List[AllocTuple] = []
        for missing in place:
            if self._find_preferred_node(missing) is not None:
                sticky.append(missing)
            else:
                bulk.append(missing)
        if sticky:
            super()._compute_placements(sticky)
        # A TG that already failed (e.g. in the sticky host path) only
        # coalesces from here on — same invariant as the host loop
        # (generic_sched.go:444-447).
        remaining: List[AllocTuple] = []
        for missing in bulk:
            if self.failed_tg_allocs and missing.task_group.name in self.failed_tg_allocs:
                self.failed_tg_allocs[missing.task_group.name].coalesced_failures += 1
            else:
                remaining.append(missing)
        bulk = remaining
        if not bulk:
            self._settle_cohort()
            return
        from ..migrate import preemption_eligible
        from ..utils import metrics

        may_preempt = preemption_eligible(self.eval.priority)
        # This eval's unit of its pipeline batch's cohort (a Planner
        # outside the pipeline has none): place() marks it arrived, and
        # its dispatch closes on the cohort, not on the timed window.
        unit = getattr(self.planner, "cohort", None)
        rides_batch = (unit is not None and unit.batch_mates() > 0
                       and not getattr(self.planner, "requeued", False))
        if len(bulk) <= 3 and not may_preempt and not rides_batch:
            # One to three asks and no batch to ride: an eval that is
            # alone (no cohort, or a cohort of one), or the retry after
            # a partially-rejected plan (inline: its unit has ridden its
            # dispatch; requeued by the pipeline into a later batch: its
            # session says so; 1-3 conflicted allocs replanned on a
            # FRESH snapshot, what the rule was written for). The host
            # iterators place a handful in low-ms with identical
            # semantics. A first run of one to three asks whose batch
            # holds other evals rides that batch's plain dispatch as a
            # lane like any other: the dispatch goes anyway, and on the device it
            # plans against its batch-mates' claims, the gangs' too
            # (scheduler/batcher.py), where the host walk would plan on
            # a snapshot none of them sees. A preemption-eligible eval
            # stays dense at ANY size: the host iterators cannot evict,
            # and the retry after a partially-committed preemption plan
            # is exactly a 1-3 ask replan that still needs the eviction
            # leg.
            # Counted: every route off the device is visible from
            # outside (/v1/metrics), like the fault and breaker routes.
            # In allocations and in evals: a rate per eval needs the
            # second.
            metrics.incr_counter(
                ("scheduler", "small_route_host"), len(bulk))
            metrics.incr_counter(("scheduler", "small_route_host_evals"))
            self._settle_cohort()
            super()._compute_placements(bulk)
            return

        # Device-path circuit breaker (nomad_tpu/admission): the
        # consuming gate, checked BEFORE the matrix build — an open
        # breaker means the device path is sick, so paying the
        # ClusterMatrix + build_asks cost only to discard them would
        # burn leader CPU per eval for nothing. An open breaker (or a
        # busy half-open probe slot) routes this eval to the host
        # iterators WITHOUT paying the failure latency the per-eval
        # fallback below would — the whole point of the breaker is
        # that N consecutive failures become one routing decision,
        # not N timeouts.
        from ..admission import get_breaker
        from ..chaos import chaos

        breaker = get_breaker()
        if not breaker.acquire():
            self._settle_cohort()
            metrics.incr_counter(
                ("scheduler", "breaker_rejected"), len(bulk))
            trace.record_span(
                self.eval.id, trace.STAGE_DEVICE_DISPATCH,
                time.monotonic(),
                ann={"breaker": breaker.state()},
                trace_id=self.eval.trace_id)
            super()._compute_placements(bulk)
            return

        # The compiled programs an eval runs are keyed by its ask rung
        # and its job's positions bucket (models/matrix.py), and a
        # REPLAN after a partly rejected plan comes with fewer asks and
        # more positions of its own: with task groups of 1 to 2,000 in
        # one window, rung x positions bucket is a set no warm-up
        # closes by meeting each job shape in turn, and a compile is
        # seconds of the eval's latency. So the set is closed here:
        # - positions are padded as for the job's whole count on every
        #   attempt, the first included (a few KB at the most): their
        #   bucket is the job's, never the attempt's;
        # - a replan (inline: this scheduler has planned before;
        #   requeued: its session says so and the first attempt's count
        #   is taken to be the task groups') pads its asks to the first
        #   attempt's, and runs the program that attempt ran. A task
        #   group scaling up by a few is a first attempt and scans its
        #   few asks;
        # - a preemption-eligible eval of small task groups pads its
        #   first attempt too, as if none of its allocations were
        #   placed: it is replanned by the preemption pass, which has
        #   programs of its own to keep few (small task groups only:
        #   that is where a replan changes bucket).
        counts = {m.task_group.name: m.task_group.count for m in bulk}
        job_asks = sum(counts.values())
        ask_floor = 0
        if may_preempt and job_asks <= REPLAN_PAD_MAX_ASKS:
            ask_floor = job_asks
        inline_replan = self._first_asks is not None
        if inline_replan:
            ask_floor = max(ask_floor, self._first_asks)
        elif getattr(self.planner, "requeued", False):
            ask_floor = max(ask_floor, job_asks)
        else:
            self._first_asks = len(bulk)
        _t0 = time.monotonic()
        matrix = ClusterMatrix(self.state, self.job, self.plan,
                               ask_floor=ask_floor, rows_floor=job_asks)
        _t_base = time.monotonic()
        tg_indices = {tg.name: i for i, tg in enumerate(self.job.task_groups)}
        placements = [tg_indices[m.task_group.name] for m in bulk]

        ask_arrays = matrix.build_asks(placements)
        asks = make_asks(*ask_arrays)
        trace.record_span(self.eval.id, trace.STAGE_MATRIX_BUILD, _t0,
                          ann={"placements": len(bulk)},
                          trace_id=self.eval.trace_id)
        self._record_matrix_update(matrix, _t0, _t_base)
        # Compression-plane attribution (models/classes.py): how far
        # the fleet interned — C classes over N nodes. Zero-duration
        # marker span (the interning rides the base build above); its
        # value is the annotation in the flight recorder.
        cidx = getattr(matrix, "class_index", None)
        if cidx is not None:
            trace.record_span(
                self.eval.id, trace.STAGE_MATRIX_COMPRESS, _t_base,
                _t_base, ann=cidx.stats(),
                trace_id=self.eval.trace_id)
        # Placement kernel (nomad_tpu/kernels): instance pin from the
        # factory variant, else the process-global active kernel inside
        # build_placement_config. The name is a static PlacementConfig
        # field — it joins the batcher's shape key, so kernels never
        # share a dispatch.
        config = build_placement_config(
            self.batch, self.kernel, placements, ask_arrays)
        kernel = config.kernel
        # Host-side key: a device PRNGKey here would cost a device
        # round-trip per eval and force the batcher to pull keys back
        # for stacking.
        key = host_prng_key(self.rng.getrandbits(31))

        # The drain-to-batch shim (BASELINE north star): concurrent
        # workers' same-shaped placement programs coalesce into one
        # device dispatch instead of N serial calls, and evals
        # sharing a cluster base ride one cached device upload.
        _t0 = time.monotonic()
        try:
            if chaos.enabled:
                # 'error' = an injected device fault AT the breaker's
                # gate: lands in the except below, so a seeded schedule
                # can trip the breaker deterministically (the overload
                # soak drives trip -> half-open -> reclose through
                # this site).
                chaos.fire("device.breaker_trip", eval_id=self.eval.id)
            if may_preempt or inline_replan:
                # The other half of the padding above: an inline replan
                # plans on a snapshot no launch prologue prefetched.
                # Its base is made resident first (a no-op wherever it
                # already is), so the dispatch takes the plain program
                # and not the one that fuses the base's delta in: one
                # program a shape for such an eval.
                get_batcher().prefetch_base(matrix)
            choices, scores = get_batcher().place(
                matrix, asks, key, config,
                span=(self.eval.id, self.eval.trace_id), cohort=unit)
        except Exception:
            # Device dispatch failed (runtime fault, OOM on device,
            # chaos binpack.device / device.breaker_trip): the host
            # iterators have IDENTICAL placement semantics
            # (parity-tested), so falling back costs milliseconds of
            # CPU instead of failing the eval into a nack/redelivery
            # round — the eval still completes this delivery. The whole
            # bulk set takes the host path; the plan applier
            # re-verifies either way. The breaker counts the failure:
            # K consecutive ones trip the dense path out of the way.
            # A fault BEFORE place() left the unit open: batch-mates
            # must not wait out the host placement below.
            self._settle_cohort()
            breaker.record_failure()
            self.logger.warning(
                "device placement dispatch failed; falling back to the "
                "host path for %d placements", len(bulk), exc_info=True)
            metrics.incr_counter(("scheduler", "host_fallback"), len(bulk))
            trace.record_span(
                self.eval.id, trace.STAGE_DEVICE_DISPATCH, _t0,
                ann={"host_fallback": True}, trace_id=self.eval.trace_id)
            super()._compute_placements(bulk)
            return
        breaker.record_success((time.monotonic() - _t0) * 1000.0)
        choices = np.asarray(choices)
        scores = np.asarray(scores)
        ann = {"rung": int(np.shape(asks.active)[0])}
        if unit is not None:
            ann["closed_by"] = unit.closed_by
        trace.record_span(
            self.eval.id, trace.STAGE_DEVICE_DISPATCH, _t0, ann=ann,
            trace_id=self.eval.trace_id)

        # Host-side exact port assignment per chosen node, incremental.
        net_indexes: Dict[str, NetworkIndex] = {}
        # Placements actually APPENDED to the plan, as (ask row j,
        # node row) — the quality board must score committed claims
        # only (coalesced failures and port-collision host re-places
        # never commit through this loop).
        committed: List[Tuple[int, int]] = []
        # Asks the kernel could not place: candidates for the dense
        # preemption pass (an outranking eval only) before they become
        # recorded failures.
        unplaced: List[AllocTuple] = []

        for j, missing in enumerate(bulk):
            # Coalesce once the TG has failed, even if the kernel found a
            # node for a later ask of that TG (host-loop invariant).
            if self.failed_tg_allocs and missing.task_group.name in self.failed_tg_allocs:
                self.failed_tg_allocs[missing.task_group.name].coalesced_failures += 1
                continue

            choice = int(choices[j])
            node = matrix.nodes[choice] if 0 <= choice < matrix.n_real else None

            metrics = AllocMetric()
            metrics.nodes_evaluated = matrix.n_real
            metrics.nodes_available = matrix.nodes_by_dc

            if node is None:
                if may_preempt:
                    unplaced.append(missing)
                else:
                    self._record_placement_failure(
                        missing, matrix, metrics, tg_indices
                    )
                continue

            metrics.score_node(node, "binpack", float(scores[j]))
            task_resources = _offer_networks(
                self.rng, missing, node, net_indexes, matrix
            )
            if task_resources is None:
                # Dense port-count approximation missed a real collision:
                # fall back to the exact host path for this placement.
                super()._compute_placements([missing])
                continue

            self.plan.append_alloc(_build_allocation(
                self, missing, node, task_resources, metrics))
            committed.append((j, int(choices[j])))

        # Quality scoreboard (kernels/quality.py): score the cluster
        # state this plan commits to — base utilization plus the
        # claims this loop actually appended — on the fragmentation/
        # bin-pack axes, labeled by kernel so stats() can compare
        # kernels. Cheap ([N,4] copy + vector ops) next to the
        # dispatch it follows.
        self._note_quality(kernel, matrix, ask_arrays[0], committed)

        if unplaced:
            _t0 = time.monotonic()
            candidates = self._preempt_placements(unplaced, tg_indices,
                                                  ask_floor)
            trace.record_span(
                self.eval.id, trace.STAGE_PREEMPT_SELECT, _t0,
                ann={"asks": len(unplaced), "candidates": candidates},
                trace_id=self.eval.trace_id)

    def _place_gang_dense(self, tg, tuples: List[AllocTuple]) -> None:
        """One gang's all-K pass (ops/gang.py): per-node fit mask ->
        topology-group cumulative capacity -> contiguous-slice selection
        -> K-step member assignment. The gang goes to the device as
        place() sends plain asks: a request built from the snapshot's
        cached cluster base and handed to the batcher with this eval's
        cohort unit (scheduler/batcher.py place_gang), so the gangs of
        one pipeline batch ride one dispatch over the resident base,
        each seeing the claims of those before it. Members stage
        through the plan's gang leg (Plan.append_gang_alloc) — the
        applier verifies per node and rejects the WHOLE gang on any
        member's under-fit. Device faults and an open breaker fall back
        to the host gang stack with identical atomicity semantics."""
        from ..admission import get_breaker
        from ..chaos import chaos
        from ..gang import build_gang_request
        from ..models.matrix import ClusterMatrix
        from ..ops.binpack import host_prng_key
        from ..utils import metrics as _metrics
        from .batcher import get_batcher

        name = tg.name
        if self.failed_tg_allocs and name in self.failed_tg_allocs:
            self.failed_tg_allocs[name].coalesced_failures += len(tuples)
            return

        breaker = get_breaker()
        if not breaker.acquire():
            self._settle_cohort()
            _metrics.incr_counter(
                ("scheduler", "gang_breaker_rejected"), len(tuples))
            self._place_gang_host(tg, tuples)
            return

        _t0 = time.monotonic()
        # The cached base of the snapshot; where this plan already
        # stages something (gang replacement stops free their capacity)
        # the rows it touches are written into a dense state of this
        # matrix's own — the all-K pass must see the room the
        # survivors' stops open up. Such a matrix carries no base token
        # and its gang dispatches alone (the plain lanes' patch is not
        # taken by the gang program: ROADMAP.md D13).
        matrix = ClusterMatrix(self.state, self.job, self.plan,
                               plan_overlay=True)
        _t_base = time.monotonic()
        gang = build_gang_request(matrix, self.job, tg)
        config = gang.config
        key = host_prng_key(self.rng.getrandbits(31))
        trace.record_span(self.eval.id, trace.STAGE_MATRIX_BUILD, _t0,
                          ann={"placements": len(tuples), "gang": True},
                          trace_id=self.eval.trace_id)
        self._record_matrix_update(matrix, _t0, _t_base)
        trace.record_span(self.eval.id, trace.STAGE_GANG_BUILD, _t0,
                          trace_id=self.eval.trace_id)
        _t_solve = time.monotonic()
        unit = getattr(self.planner, "cohort", None)
        try:
            if chaos.enabled:
                chaos.fire("device.breaker_trip", eval_id=self.eval.id)
            choices, scores, slice_gid, moved = get_batcher().place_gang(
                matrix, gang, key,
                span=(self.eval.id, self.eval.trace_id), cohort=unit)
        except Exception:
            # A fault before place_gang() left the unit open.
            self._settle_cohort()
            breaker.record_failure()
            self.logger.warning(
                "gang device dispatch failed; falling back to the host "
                "gang stack for %d members", len(tuples), exc_info=True)
            _metrics.incr_counter(
                ("scheduler", "gang_host_fallback"), len(tuples))
            trace.record_span(
                self.eval.id, trace.STAGE_GANG_SELECT, _t0,
                ann={"members": len(tuples), "mode": config.mode,
                     "host_fallback": True},
                trace_id=self.eval.trace_id)
            self._place_gang_host(tg, tuples)
            return
        breaker.record_success((time.monotonic() - _t_solve) * 1000.0)
        ann = {"lanes": len(tuples), "rung": len(gang.lane.active)}
        if unit is not None:
            ann["closed_by"] = unit.closed_by
        trace.record_span(
            self.eval.id, trace.STAGE_DEVICE_DISPATCH, _t_solve, ann=ann,
            trace_id=self.eval.trace_id)
        try:
            self._stage_gang(tg, tuples, matrix, gang, choices, scores)
        finally:
            trace.record_span(
                self.eval.id, trace.STAGE_GANG_SELECT, _t0,
                ann={"members": len(tuples), "mode": config.mode,
                     "slice_group": slice_gid, "moved": moved},
                trace_id=self.eval.trace_id)

    def _stage_gang(self, tg, tuples: List[AllocTuple], matrix, gang,
                    choices, scores) -> None:
        """What the device chose for one gang onto the plan: all K
        members on the gang leg, or the one failure of a gang rejected
        whole."""
        from ..gang import gang_key, note_gang_result
        from ..utils import metrics as _metrics

        name = tg.name
        ask_res = gang.lane.ask_res
        if int(choices[0]) < 0:
            # Whole-gang reject on device (no slice fits all K, or a
            # member found no node): ONE failure for the TG, with
            # class eligibility from the feasibility mask so the
            # blocked eval re-runs when capacity returns.
            note_gang_result(False, len(tuples), "device")
            m = AllocMetric()
            m.nodes_evaluated = matrix.n_real
            m.nodes_available = matrix.nodes_by_dc
            tg_indices = {g.name: i
                          for i, g in enumerate(self.job.task_groups)}
            self._record_placement_failure(tuples[0], matrix, m,
                                           tg_indices)
            if len(tuples) > 1:
                self.failed_tg_allocs[name].coalesced_failures += (
                    len(tuples) - 1)
            return

        # Materialize: exact host-side port offers per member, staged
        # on the gang leg. ANY member failing port assignment unwinds
        # the whole gang to the host stack (exact ports there) — a
        # partial gang never survives this loop.
        gkey = gang_key(self.job.id, name)
        net_indexes: Dict[str, NetworkIndex] = {}
        committed: List[Tuple[int, int]] = []
        for j, missing in enumerate(tuples):
            choice = int(choices[j])
            node = (matrix.nodes[choice]
                    if 0 <= choice < matrix.n_real else None)
            m = AllocMetric()
            m.nodes_evaluated = matrix.n_real
            m.nodes_available = matrix.nodes_by_dc
            task_resources = None
            if node is not None:
                m.score_node(node, "gang", float(scores[j]))
                task_resources = _offer_networks(
                    self.rng, missing, node, net_indexes, matrix)
            if task_resources is None:
                self.plan.pop_gang(gkey)
                _metrics.incr_counter(
                    ("scheduler", "gang_port_fallback"), len(tuples))
                self._place_gang_host(tg, tuples)
                return
            self.plan.append_gang_alloc(gkey, _build_allocation(
                self, missing, node, task_resources, m))
            committed.append((j, choice))
        note_gang_result(True, len(tuples), "device")
        from ..kernels import active_kernel

        self._note_quality(
            self.kernel or active_kernel(), matrix,
            np.tile(np.asarray(ask_res)[None, :], (len(tuples), 1)),
            committed)

    def _preempt_placements(self, pending: List[AllocTuple],
                            tg_indices: Dict[str, int],
                            ask_floor: int) -> int:
        """The dense preemption pass (ops/preempt.py): place the asks
        the normal kernel could not, by selecting lowest-priority
        victims and the placement in the same masked program. Runs
        only when migrate.preemption_eligible said yes (preemption on,
        eval outranks the threshold) and the normal pass left these
        asks unplaced: the machines are full. Victim evictions
        are staged on the plan's node_preemptions leg and re-verified
        per victim by the plan applier before committing with the
        placements in one raft apply — chaos site preempt.victim_lost
        drops a staged victim here to prove that verification.
        `ask_floor` is the normal pass's (see _compute_placements).
        Returns the number of victim candidates the pass had to choose
        from."""
        from ..chaos import chaos
        from ..migrate import note_preemption, note_preemption_failure
        from ..models.matrix import ClusterMatrix
        from ..ops.binpack import (
            PlacementConfig,
            host_prng_key,
            make_asks,
            make_node_state,
        )
        from ..ops.preempt import (
            make_victim_state,
            preempt_placement_program_jit,
            unpack_result,
        )
        from .stack import (
            BATCH_JOB_ANTI_AFFINITY_PENALTY,
            SERVICE_JOB_ANTI_AFFINITY_PENALTY,
        )
        from .util import ALLOC_PREEMPTED

        def fail_all(rows: List[AllocTuple], pm) -> None:
            for missing in rows:
                name = missing.task_group.name
                if self.failed_tg_allocs and name in self.failed_tg_allocs:
                    self.failed_tg_allocs[name].coalesced_failures += 1
                    continue
                metrics = AllocMetric()
                metrics.nodes_evaluated = pm.n_real
                metrics.nodes_available = pm.nodes_by_dc
                self._record_placement_failure(missing, pm, metrics,
                                               tg_indices)

        # The node state is the snapshot's CACHED base (the one the
        # normal pass just used) with the rows this very plan touches
        # written into a copy: the preemption pass must not claim
        # headroom an earlier ask of this same eval just took, and its
        # victim lists must exclude allocs the plan already stops.
        pm = ClusterMatrix(self.state, self.job, self.plan,
                           plan_overlay=True, ask_floor=ask_floor)
        _t_victims = time.monotonic()
        varrays, victims_of, n_candidates = pm.build_victims(
            self.eval.priority)
        trace.record_span(
            self.eval.id, trace.STAGE_PREEMPT_VICTIMS, _t_victims,
            ann={"candidates": n_candidates}, trace_id=self.eval.trace_id)
        if n_candidates == 0:
            fail_all(pending, pm)
            return 0
        placements = [tg_indices[m.task_group.name] for m in pending]
        ask_arrays = pm.build_asks(placements)
        asks = make_asks(*ask_arrays)
        state = make_node_state(
            pm.capacity, pm.sched_capacity, pm.util, pm.bw_avail,
            pm.bw_used, pm.ports_free, pm.job_count, pm.tg_count,
            pm.feasible, pm.node_ok)
        victims = make_victim_state(*varrays)
        penalty = (BATCH_JOB_ANTI_AFFINITY_PENALTY if self.batch
                   else SERVICE_JOB_ANTI_AFFINITY_PENALTY)
        # Plain greedy config: the preemption program is its own
        # compiled entry point — kernel variants do not apply here.
        config = PlacementConfig(anti_affinity_penalty=penalty)
        key = host_prng_key(self.rng.getrandbits(31))
        # The preemption dispatch shares the device-path breaker: a
        # persistently failing preempt program (e.g. device OOM from
        # the extra victim tensors) must become one routing decision,
        # not a fresh dispatch-failure latency per preempting eval.
        from ..admission import get_breaker

        breaker = get_breaker()
        if not breaker.acquire():
            note_preemption_failure(breaker_rejected=len(pending))
            fail_all(pending, pm)
            return n_candidates
        _t_solve = time.monotonic()
        try:
            with trace.annotation("nomad.preempt", asks=len(pending)):
                choices, scores, counts = unpack_result(
                    preempt_placement_program_jit(
                        state, victims, asks, key,
                        np.float32(self.eval.priority), config))
        except Exception:  # noqa: BLE001 - degrade to plain failure
            # The device path is sick: these asks simply stay failed/blocked — the
            # no-preemption outcome, never a half-staged eviction. The
            # breaker counts the failure like any dense dispatch.
            breaker.record_failure()
            self.logger.warning(
                "preemption dispatch failed; %d placements stay "
                "unplaced", len(pending), exc_info=True)
            note_preemption_failure(dispatch_failed=len(pending))
            fail_all(pending, pm)
            return n_candidates
        breaker.record_success((time.monotonic() - _t_solve) * 1000.0)
        trace.record_span(
            self.eval.id, trace.STAGE_PREEMPT_SOLVE, _t_solve,
            trace_id=self.eval.trace_id)

        net_indexes: Dict[str, NetworkIndex] = {}
        consumed: Dict[int, int] = {}
        staged_total = 0
        placed_total = 0
        for j, missing in enumerate(pending):
            name = missing.task_group.name
            if self.failed_tg_allocs and name in self.failed_tg_allocs:
                self.failed_tg_allocs[name].coalesced_failures += 1
                continue
            choice = int(choices[j])
            node = pm.nodes[choice] if 0 <= choice < pm.n_real else None
            metrics = AllocMetric()
            metrics.nodes_evaluated = pm.n_real
            metrics.nodes_available = pm.nodes_by_dc
            if node is None:
                self._record_placement_failure(missing, pm, metrics,
                                               tg_indices)
                continue
            cnt = int(counts[j])
            taken = []
            if cnt > 0:
                lst = victims_of(choice)
                start = consumed.get(choice, 0)
                taken = lst[start:start + cnt]
                consumed[choice] = start + len(taken)
            staged = 0
            for victim in taken:
                if chaos.enabled and chaos.fire(
                        "preempt.victim_lost", eval_id=self.eval.id,
                        alloc=victim.id) == "drop":
                    # The victim vanished between selection and commit:
                    # its freed capacity was already counted on device,
                    # so the plan under-frees — the applier's exact
                    # verification rejects the node and forces a replan.
                    continue
                self.plan.append_preemption(
                    victim, consts.ALLOC_DESIRED_EVICT, ALLOC_PREEMPTED)
                staged += 1
            metrics.score_node(node, "preempt", float(scores[j]))
            task_resources = _offer_networks(
                self.rng, missing, node, net_indexes, pm)
            if task_resources is None:
                # Port collision on the chosen node: back the victims
                # out — an eviction must never commit without the
                # placement it was freeing room for.
                self.plan.pop_preemptions(node.id, staged)
                self._record_placement_failure(missing, pm, metrics,
                                               tg_indices)
                continue
            self.plan.append_alloc(_build_allocation(
                self, missing, node, task_resources, metrics))
            staged_total += staged
            placed_total += 1
        note_preemption(staged_total, placed_total)
        return n_candidates

    def _record_matrix_update(self, matrix, t0: float, t1: float) -> None:
        """Attribution for the device-resident path: how this eval's
        base came to be (cache hit / incremental delta / full rebuild)
        and how many node rows the delta touched — the resident
        design's win IS this span staying "hit"/"delta" with small row
        counts under steady load (models/resident.py)."""
        kind = getattr(matrix, "build_kind", None)
        if kind is not None:
            trace.record_span(
                self.eval.id, trace.STAGE_MATRIX_UPDATE, t0, t1,
                ann={"kind": kind, "rows": matrix.delta_rows},
                trace_id=self.eval.trace_id)
        _record_built_spans(self.eval, matrix)

    def _note_quality(self, kernel, matrix, ask_res, committed) -> None:
        note_quality(self.logger, self.job, kernel, matrix, ask_res,
                     committed)

    def _settle_cohort(self) -> None:
        """This eval takes a host path: the dispatch pipeline announced
        its place() to the batcher (a unit of its batch's cohort), and
        its batch-mates' dispatch must not wait out a host placement
        for a request that never arrives. A Planner outside the
        pipeline announced nothing."""
        settle = getattr(self.planner, "settle_cohort", None)
        if settle is not None:
            settle()

    # ------------------------------------------------------------------

    def _record_placement_failure(
        self, missing: AllocTuple, matrix, metrics, tg_indices: Dict[str, int]
    ) -> None:
        name = missing.task_group.name
        gi = tg_indices[name]
        infeasible = int(matrix.n_real - matrix.feasible[: matrix.n_real, gi].sum())
        metrics.nodes_filtered = infeasible
        metrics.nodes_exhausted = matrix.n_real - infeasible
        if self.failed_tg_allocs is None:
            self.failed_tg_allocs = {}
        self.failed_tg_allocs[name] = metrics
        # Feed the blocked-eval machinery per-class eligibility from the mask.
        elig = self.ctx.eligibility
        for i, node in enumerate(matrix.nodes):
            if node.computed_class:
                elig.set_task_group_eligibility(
                    bool(matrix.feasible[i, gi]), name, node.computed_class
                )

def _record_built_spans(ev, matrix) -> None:
    """The spans of what this eval's matrix really built itself:
    `feasibility.build`, the constraint mask (a miss of the mask memo,
    models/matrix.py _build_feasibility: its samples are the memo's
    misses), and `base.delta`, the cluster base derived inline from its
    parent (a replan on a snapshot no prologue prefetched), and
    `matrix.plan_patch`, what a plan that is not a no-op changes on the
    rows it touches (_build_plan_patch)."""
    for stage, built in (
            (trace.STAGE_FEASIBILITY_BUILD,
             getattr(matrix, "feas_build", None)),
            (trace.STAGE_BASE_DELTA,
             getattr(matrix, "base_delta_span", None)),
            (trace.STAGE_MATRIX_PLAN_PATCH,
             getattr(matrix, "plan_patch_span", None))):
        if built is not None:
            trace.record_span(ev.id, stage, built[0], built[1],
                              ann=built[2], trace_id=ev.trace_id)


def note_quality(logger, job, kernel, matrix, ask_res, committed) -> None:
    """Quality scoreboard entry (kernels/quality.py) for one dense
    plan's committed claims. Scoring must never fail an eval."""
    from ..kernels.quality import (
        get_board,
        quality_from_arrays,
        reference_ask,
    )

    try:
        if not get_board().should_sample(kernel):
            return
        util = matrix.proposed_columns()[0]
        if committed:
            js = np.asarray([j for j, _r in committed])
            rows = np.asarray([r for _j, r in committed])
            np.add.at(util, rows, np.asarray(ask_res)[js])
        q = quality_from_arrays(util, matrix.capacity,
                                matrix.node_ok,
                                reference_ask(job))
        get_board().note_plan(kernel, q["fragmentation"],
                              q["binpack_score"])
    except Exception:  # noqa: BLE001 - scoring must never fail an eval
        logger.warning("placement-quality scoring failed",
                       exc_info=True)


def dense_diff_system_allocs(state, job, nodes, tainted, allocs,
                             terminal_allocs):
    """diff_system_allocs (scheduler/util.go:62) with the place set
    feasibility-gated up front: the host version materializes one
    AllocTuple (and a stub Allocation) per required slot on EVERY ready
    node, then the placement loop filters the infeasible ones one
    python iteration at a time — at 10k nodes with rack-scoped system
    jobs that is ~9k tuples built and discarded per eval. Here the
    class-vectorized constraint mask picks the candidate rows first and
    only those materialize; the infeasible remainder is returned as
    per-task-group counts for the caller's metric/queued bookkeeping.

    Returns (DiffResult, prefiltered) where prefiltered maps
    tg name -> [count, first_infeasible_node]."""
    from ..models.matrix import node_feasibility, ready_class_index
    from .util import DiffResult, diff_allocs, materialize_task_groups

    groups = job.task_groups
    class_ids, class_reps = ready_class_index(state, nodes, job.datacenters)
    feasible = node_feasibility(state, job, groups, nodes,
                                class_ids, class_reps)
    gi_by_name = {tg.name: gi for gi, tg in enumerate(groups)}
    required = materialize_task_groups(job)
    result = DiffResult()
    prefiltered: Dict[str, list] = {}

    def gate_place(tuples, row):
        """Feasibility-gate one node's place tuples."""
        kept = []
        for tup in tuples:
            if feasible[row, gi_by_name[tup.task_group.name]]:
                kept.append(tup)
            else:
                ent = prefiltered.get(tup.task_group.name)
                if ent is None:
                    prefiltered[tup.task_group.name] = [1, nodes[row]]
                else:
                    ent[0] += 1
        return kept

    node_row = {n.id: i for i, n in enumerate(nodes)}
    # Nodes holding this job's allocs: the faithful per-node diff
    # (stop/lost/update/ignore need the alloc-level comparisons).
    node_allocs: Dict[str, List[Allocation]] = {}
    for alloc in allocs:
        node_allocs.setdefault(alloc.node_id, []).append(alloc)
    for node_id, nallocs in node_allocs.items():
        diff = diff_allocs(job, tainted, required, nallocs, terminal_allocs)
        if node_id in tainted:
            diff.place = []
        else:
            row = node_row.get(node_id)
            for tup in diff.place:
                if tup.alloc is None or tup.alloc.node_id != node_id:
                    tup.alloc = Allocation(node_id=node_id)
            diff.place = (gate_place(diff.place, row)
                          if row is not None else diff.place)
        # A tainted node invalidates the job there: migrations -> stops.
        diff.stop.extend(diff.migrate)
        diff.migrate = []
        result.append(diff)

    # Nodes WITHOUT allocs place every required slot; candidates and
    # the infeasible tally come from array ops, python only touches
    # the (usually few) feasible rows.
    has_alloc = np.zeros(len(nodes), bool)
    for node_id in node_allocs:
        row = node_row.get(node_id)
        if row is not None:
            has_alloc[row] = True
    candidates = ~has_alloc
    if tainted:
        for node_id in tainted:
            row = node_row.get(node_id)
            if row is not None:
                candidates[row] = False
    cand_feasible = candidates[:, None] & feasible
    any_rows = np.flatnonzero(cand_feasible.any(axis=1))
    for i in any_rows:
        node_id = nodes[i].id
        for name, tg in required.items():
            if not cand_feasible[i, gi_by_name[tg.name]]:
                continue
            talloc = terminal_allocs.get(name)
            if talloc is None or talloc.node_id != node_id:
                talloc = Allocation(node_id=node_id)
            result.place.append(AllocTuple(name, tg, talloc))
    # Infeasible tallies + first offender per TG, without materializing.
    slots_per_tg = {tg.name: 0 for tg in groups}
    for _name, tg in required.items():
        slots_per_tg[tg.name] += 1
    for tg in groups:
        gi = gi_by_name[tg.name]
        bad = candidates & ~feasible[:, gi]
        n_bad = int(bad.sum()) * slots_per_tg[tg.name]
        if not n_bad:
            continue
        ent = prefiltered.get(tg.name)
        if ent is None:
            prefiltered[tg.name] = [n_bad, nodes[int(np.argmax(bad))]]
        else:
            ent[0] += n_bad
    return result, prefiltered


class DenseSystemScheduler(SystemScheduler):
    """SystemScheduler whose diff and placement loops are vectorized
    passes.

    The host loop (system_sched.go:255) builds a one-node iterator
    stack per pinned placement; here the whole placement set is checked
    against a single ClusterMatrix: constraint feasibility comes from
    the [N, G] mask, resource fit is a vectorized AllocsFit over the
    pinned rows, and in-eval utilization accumulates per task group so
    multi-TG system jobs see their own earlier placements. The diff is
    feasibility-gated up front (dense_diff_system_allocs), so the
    pinned matrix and the plan only ever see candidate nodes."""

    def _diff_system(self, tainted, allocs, terminal_allocs):
        """Feasibility-gated diff (see dense_diff_system_allocs). A
        deregistered job (job=None: every alloc diffs into stop) takes
        the host diff — there is nothing to gate without constraints."""
        if self.job is None:
            return super()._diff_system(tainted, allocs, terminal_allocs)
        return dense_diff_system_allocs(
            self.state, self.job, self.nodes, tainted, allocs,
            terminal_allocs)

    def _compute_placements(self, place: List[AllocTuple]) -> None:
        from ..models.matrix import ClusterMatrix

        # Matrix only the PINNED nodes: system placements are fixed to
        # their node up front (diffSystemAllocs), so feasibility/fit for
        # the other N-P nodes would be wasted work — at 10k nodes with
        # rack-scoped jobs that's a 200x smaller matrix per eval.
        pinned_ids = []
        seen = set()
        for missing in place:
            nid = missing.alloc.node_id
            if nid not in seen:
                seen.add(nid)
                pinned_ids.append(nid)
        by_id = {n.id: n for n in self.nodes}
        pinned_nodes = [by_id[nid] for nid in pinned_ids if nid in by_id]
        _t0 = time.monotonic()
        matrix = ClusterMatrix(self.state, self.job, self.plan,
                               nodes=pinned_nodes)
        matrix.nodes_by_dc = self.nodes_by_dc
        node_index = {n.id: i for i, n in enumerate(matrix.nodes)}
        tg_by_name = {tg.name: i for i, tg in enumerate(self.job.task_groups)}

        placements = [tg_by_name[m.task_group.name] for m in place]
        resources, bw, ports, _tg_index, _active, _jdh, _tdh = \
            matrix.build_asks(placements)
        trace.record_span(self.eval.id, trace.STAGE_MATRIX_BUILD, _t0,
                          ann={"placements": len(place), "pinned": True},
                          trace_id=self.eval.trace_id)
        _record_built_spans(self.eval, matrix)

        util = matrix.util.copy()
        bw_used = matrix.bw_used.copy()
        ports_free = matrix.ports_free.copy()

        rows = np.empty(len(place), np.int64)
        for j, missing in enumerate(place):
            row = node_index.get(missing.alloc.node_id)
            if row is None:
                raise RuntimeError(
                    f"could not find node {missing.alloc.node_id!r}")
            rows[j] = row

        gis = np.asarray(placements)
        feasible = matrix.feasible[rows, gis]
        # Vectorized AllocsFit per task group so same-node placements of
        # different groups accumulate (G passes, each all-numpy). The
        # ask arrays from build_asks are per-placement rows; every row
        # of one group carries that group's ask.
        fits = np.zeros(len(place), bool)
        for gi in sorted(set(placements)):
            sel = gis == gi
            j0 = int(np.flatnonzero(sel)[0])
            ask_res, ask_bw, ask_ports = resources[j0], bw[j0], ports[j0]
            r = rows[sel]
            ok = (
                feasible[sel]
                & np.all(util[r] + ask_res <= matrix.capacity[r], axis=1)
                & (bw_used[r] + ask_bw <= matrix.bw_avail[r])
                & (ports_free[r] >= ask_ports)
            )
            fits[sel] = ok
            acc = r[ok]
            np.add.at(util, acc, ask_res)
            np.add.at(bw_used, acc, ask_bw)
            np.add.at(ports_free, acc, -ask_ports)

        net_indexes: Dict[str, NetworkIndex] = {}
        # Successful pinned placements all carry the identical metric
        # record (one node evaluated, same availability): share ONE
        # object across the plan — the store's upsert copies it per
        # alloc, so sharing here is invisible downstream, and a system
        # storm materializes ~N of these per eval.
        success_metrics: Optional[AllocMetric] = None

        for j, missing in enumerate(place):
            name = missing.task_group.name
            node = matrix.nodes[rows[j]]

            if not fits[j]:
                # Failure paths mutate their metric record, so those
                # stay per-placement, like the host path where every
                # stack.select starts fresh (stack.go Select → ctx
                # reset); the pinned node is the one node evaluated.
                metrics = AllocMetric()
                metrics.nodes_available = self.nodes_by_dc
                metrics.evaluate_node()
                if not feasible[j]:
                    # Constraint mismatch: the alloc was never really
                    # "queued" on this node (host path's nodes_filtered
                    # branch, system_sched.go undo accounting).
                    metrics.filter_node(node, "constraint")
                    self.queued_allocs[name] -= 1
                    if (
                        self.eval.annotate_plan
                        and self.plan.annotations is not None
                        and name in self.plan.annotations.desired_tg_updates
                    ):
                        self.plan.annotations.desired_tg_updates[name].place -= 1
                else:
                    metrics.exhausted_node(node, "resources")
                # Record the first failure per TG, coalesce the rest —
                # for filtered AND exhausted alike (system_sched.go:261).
                if self.failed_tg_allocs and name in self.failed_tg_allocs:
                    self.failed_tg_allocs[name].coalesced_failures += 1
                else:
                    if self.failed_tg_allocs is None:
                        self.failed_tg_allocs = {}
                    self.failed_tg_allocs[name] = metrics
                continue

            task_resources = self._offer_networks_on(
                missing, node, net_indexes, matrix)
            if task_resources is None:
                # Dense port-count approximation missed a collision:
                # fall back to the exact host path for this placement.
                super()._compute_placements([missing])
                continue

            if success_metrics is None:
                success_metrics = AllocMetric()
                success_metrics.nodes_available = self.nodes_by_dc
                success_metrics.evaluate_node()
            self.plan.append_alloc(_build_allocation(
                self, missing, node, task_resources, success_metrics))

    def _offer_networks_on(self, missing: AllocTuple, node, net_indexes,
                           matrix):
        """Exact per-task network offers on the pinned node (same logic
        as the generic dense path)."""
        has_networks = any(
            t.resources is not None and t.resources.networks
            for t in missing.task_group.tasks
        )
        if not has_networks:
            return {
                t.name: t.resources.copy()
                for t in missing.task_group.tasks
            }
        return _offer_networks(self.rng, missing, node, net_indexes, matrix)
