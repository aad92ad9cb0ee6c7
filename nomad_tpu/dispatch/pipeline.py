"""The central placement pipeline for dense-path evaluations.

Three stages, pipelined the way the plan applier pipelines verify and
commit (reference nomad/plan_apply.go:19-39), applied one layer up to
device dispatch:

- **central drain** — every worker that dequeues a dense-factory eval
  hands it here instead of draining its own slice of the broker; the
  dispatcher tops the accumulating batch up with ONE
  broker.dequeue_many across everything ready, so a storm packs toward
  MAX_BATCH lanes instead of fragmenting into per-worker groups
  (measured r05: 9.4 of 64 lanes per dispatch).
- **pipelined launch** — a closed batch is fanned out to the stage
  pool and the dispatcher immediately resumes accumulating; up to
  `dispatch_max_inflight` batches run concurrently, so the next
  batch's evals build matrices and upload overlays WHILE the previous
  batch's device sync and plan submits are still in flight. Plan
  submission + ack runs on the stage/result threads, never on the
  dispatcher. The second slot is earned: a batch beside one in flight
  plans without that one's uncommitted plans, and where that costs
  plan conflicts the next batches go one at a time
  (DispatchPipeline.__init__ has the rule).
- **conflict requeue** — a plan the applier partially rejects
  (RefreshIndex) does not replan alone on a fresh snapshot (a 1-3
  alloc retry that pays a full round-trip, r05's retry tax); the eval
  is folded back into the ACCUMULATING batch and replans with the next
  full dispatch. In-batch collisions are already resolved on device
  (a dispatch's lanes plan in order on one carry, ops/binpack.py), so
  requeues are the cross-batch residue only.

The pipeline preserves the worker path's contracts: per-job broker
serialization (a drained batch is always over distinct jobs), eval
ack/nack with the original broker token, and the nack-clock pause
while a plan waits in the plan queue. A batch of any size, one
included, runs on the dense factories; only an open device-path
breaker sends a batch to the host factories.
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
from typing import List, Optional, Tuple

from .. import profile, trace
from ..chaos import chaos
from ..profile import ProfiledCondition, ProfiledLock
from ..scheduler import new_scheduler
from ..server.worker import EvalSession
from ..structs import Evaluation, Plan, PlanResult, consts
from ..utils import metrics
from ..utils.backoff import poll_until

DEQUEUE_TOPUP_SLICE = 0.002  # cond-wait granularity while accumulating
SLOT_WAIT_SLICE = 0.02  # cond-wait granularity while all slots busy
WAIT_INDEX_TIMEOUT = 5.0
# Plan-conflict requeues one eval may take before it retries inline on
# a fresh snapshot (PipelineSession.submit_plan).
MAX_REQUEUES = 3
# Most batches one plan conflict can send through the pipeline one at a
# time (DispatchPipeline._note_conflict doubles up to here).
ALONE_MAX = 64

# ntalint lock-discipline manifest: functions reachable from these
# entrypoints run on the dispatcher thread and must never block (the
# accumulator IS the pipeline's clock — a blocked dispatcher stops
# batches from closing for every worker at once). Bounded cond-waits on
# the pipeline's own lock are the sanctioned scheduling primitive;
# everything slow (FSM catch-up, snapshotting, plan submit, device
# sync) belongs on the stage threads.
NTA_DISPATCHER_ENTRYPOINTS = ("DispatchPipeline._run",)


class _RequeueConflict(Exception):
    """Raised out of PipelineSession.submit_plan to abort the eval's
    current scheduling attempt: the plan was (partially) rejected and
    the eval should replan as part of the pipeline's accumulating
    batch instead of alone on a fresh snapshot."""


class _Pending:
    __slots__ = ("eval", "token", "requeues", "enqueued_at", "min_index")

    def __init__(self, ev: Evaluation, token: str, requeues: int = 0):
        self.eval = ev
        self.token = token
        self.requeues = requeues
        self.enqueued_at = time.monotonic()
        # Lowest FSM index this entry may replan against: a conflict
        # requeue records its plan's RefreshIndex here, so the relaunch
        # snapshot provably includes the eval's OWN partial commit (a
        # follower's FSM can lag the leader commit; replanning before
        # it replicates would double-place the committed allocs).
        self.min_index = 0


class PipelineSession(EvalSession):
    """Per-eval Planner for pipeline-processed evals. Inherits the
    whole Planner contract (pause-nack framing, eval updates, reblock)
    from server/worker.py EvalSession — one implementation to keep in
    sync — and overrides only the plan-conflict handling: refreshes
    raise _RequeueConflict (bounded, side-effect-guarded) so the retry
    rides the ACCUMULATING batch instead of replanning alone."""

    def __init__(self, pipeline: "DispatchPipeline", entry: _Pending,
                 cohort=None):
        # EvalSession only needs `.server` and `._wait_for_index` from
        # its worker — the pipeline provides both.
        super().__init__(pipeline, entry.eval, entry.token)
        self.pipeline = pipeline
        self.entry = entry
        # This eval's unit of its batch's cohort (scheduler/batcher.py
        # CohortUnit, opened by the launch prologue), None when the
        # batch goes to the host or the eval never meets the batcher.
        # The dense scheduler hands it to place(), which marks it
        # arrived; every other way the eval can end goes through
        # settle_cohort.
        self.cohort = cohort
        # This run replans a plan the applier partly rejected (the
        # conflict requeue put the eval into this batch): the dense
        # scheduler sends what is left of one to three asks to the host
        # iterators, as it does an inline retry's, batch or no batch.
        self.requeued = entry.requeues > 0
        # Evals created this attempt (blocked / rolling follow-ups):
        # once any exist, aborting the attempt would re-create them on
        # the requeued run — fall back to the inline retry instead.
        self.created_evals = 0

    def settle_cohort(self) -> None:
        """This eval's place() is not coming (a host route inside the
        dense scheduler, no placements, a failure) or has come: its
        batch-mates' dispatch must not wait for it. Idempotent; the
        dense scheduler calls it as early as it knows, _process_entry
        when the scheduler returns, whichever way."""
        if self.cohort is not None:
            self.cohort.settle()

    def submit_plan(self, plan: Plan) -> Tuple[PlanResult, Optional[object]]:
        start = time.monotonic()
        plan.eval_token = self.token
        if chaos.enabled:
            # 'error' = the submit RPC fails (leader flap mid-batch);
            # the eval nacks and redelivers. 'delay' = a slow plan
            # queue.
            chaos.fire("dispatch.submit", eval_id=self.eval.id)
        try:
            self.server.eval_pause_nack(self.eval.id, self.token)
        except ValueError:
            pass
        try:
            result = self.server.plan_submit(plan)
        finally:
            try:
                self.server.eval_resume_nack(self.eval.id, self.token)
            except ValueError:
                pass
        self.pipeline._note_submit(start)
        trace.record_span(self.eval.id, trace.STAGE_PLAN_SUBMIT, start,
                          trace_id=self.eval.trace_id)
        if result.refresh_index:
            self.pipeline._note_conflict()
            if (self.created_evals == 0
                    and self.entry.requeues < MAX_REQUEUES):
                # Replan as part of the next packed batch — which must
                # snapshot at or past this plan's partial commit.
                self.entry.min_index = max(self.entry.min_index,
                                           result.refresh_index)
                raise _RequeueConflict()
            # Bounded out (or side effects exist): classic inline
            # retry — catch local state up, hand back a fresh snapshot.
            self.pipeline._note_inline_retry()
            self.pipeline._wait_for_index(
                result.refresh_index, WAIT_INDEX_TIMEOUT)
            return result, self.server.fsm.state.snapshot()
        return result, None

    def create_eval(self, ev: Evaluation) -> None:
        self.created_evals += 1
        super().create_eval(ev)


class DispatchPipeline:
    def __init__(self, server):
        self.server = server
        cfg = server.config
        self.logger = logging.getLogger("nomad_tpu.dispatch")
        self.max_batch = max(1, cfg.eval_batch_size)
        self.max_inflight = max(1, cfg.dispatch_max_inflight)
        self.window = cfg.dispatch_window
        self.idle_grace = cfg.dispatch_idle_grace
        # The eval types whose factories are dense — what the central
        # drain pulls from the broker.
        from ..server.worker import is_dense_factory

        self.types: List[str] = [
            t for t in cfg.enabled_schedulers
            if is_dense_factory(cfg.factory_for(t))
        ]
        # eval_batch_size <= 1 is the operator turning batching off:
        # the worker then runs each dense eval itself.
        self.enabled = bool(self.types and cfg.eval_batch_size > 1)

        # Profiled (nomad_tpu/profile): the accumulator lock every
        # worker handoff and batch cut crosses.
        self._lock = ProfiledLock("dispatch.pipeline")
        self._cond = ProfiledCondition(self._lock, "dispatch.pipeline")
        self._pending: List[_Pending] = []  # guarded-by: _lock
        self._inflight = 0  # guarded-by: _lock
        # Run-queue delay measurement (work announced -> dispatcher
        # actually running): _admit stamps _notified_at ONLY while the
        # dispatcher is parked on the seed wait (_drain_waiting) — a
        # notify that lands mid-top-up wakes nothing, and timing it
        # would read the whole accumulation window as scheduling delay.
        self._notified_at = 0.0  # guarded-by: _lock
        self._drain_waiting = False  # guarded-by: _lock
        # The forming batch's placeholder at the batcher
        # (_announce_forming); the dispatcher thread's alone.
        self._forming = None
        # The second slot is EARNED (PR 41). A batch that launches
        # beside one in flight plans on a snapshot without that one's
        # uncommitted plans; where the two want the same nodes (a storm
        # of like jobs on a bin-packed fleet) the applier rejects the
        # later plans and a third of every eval is done again, where
        # they do not (the ramp's thousand-allocation jobs) the overlap
        # is pure gain. The pipeline cannot tell the two apart before
        # the fact, so it reads the fact: a plan conflict in a stretch
        # of shared slots (from the first batch cut beside another
        # until the pipeline is empty again) sends the next
        # `_alone_next` batches through ONE at a time (each snapshots
        # after its predecessor's last commit), the one after them may
        # take the second slot again, and every stretch that conflicts
        # doubles the count (up to ALONE_MAX) until one ends without a
        # conflict (_release_slot), which resets it. A conflict among
        # one batch's own plans (nothing shared) costs nothing here.
        self._alone = 0  # guarded-by: _lock (batches still to go alone)
        self._alone_next = 1  # guarded-by: _lock (the next conflict's cost)
        # plan_conflicts at the first batch cut beside another since the
        # pipeline was last empty; None while no slots were shared.
        self._shared_mark: Optional[int] = None  # guarded-by: _lock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.drained = 0  # guarded-by: _lock (evals requeued by drain())
        self.finish_dropped = 0  # guarded-by: _lock (chaos dispatch.finish)
        self.expired_dropped = 0  # guarded-by: _lock (deadline at launch)
        self.breaker_routed = 0  # guarded-by: _lock (host via open breaker)

        # ---- stats ----
        self.evals_in = 0  # guarded-by: _lock (handed off / requeued)
        self.batches = 0  # guarded-by: _lock (batches launched)
        self.alone_batches = 0  # guarded-by: _lock (launched under _alone)
        self.dispatched_evals = 0  # guarded-by: _lock (sum batch sizes)
        self.largest_batch = 0  # guarded-by: _lock
        # Batches cut with nothing in flight; of them, those cut on the
        # first look because no register was on the way, and those that
        # waited for one up to the cap (idle_grace). The rest waited
        # until the last register had returned.
        self.idle_closes = 0  # guarded-by: _lock
        self.idle_closes_at_once = 0  # guarded-by: _lock
        self.idle_closes_at_cap = 0  # guarded-by: _lock
        self.routed_host = 0  # guarded-by: _lock (sent to host factory)
        self.acked = 0  # guarded-by: _lock
        self.nacked = 0  # guarded-by: _lock
        self.plan_conflicts = 0  # guarded-by: _lock (RefreshIndex'd)
        self.requeues = 0  # guarded-by: _lock (retries via accumulator)
        self.requeues_batched = 0  # guarded-by: _lock (joined a batch)
        self.inline_retries = 0  # guarded-by: _lock (classic retries)
        self.prefetches = 0  # guarded-by: _lock (base prefetch calls)
        self.prefetch_bytes = 0  # guarded-by: _lock (host->device bytes)
        self.prefetch_failures = 0  # guarded-by: _lock (upload raised)

    # ------------------------------------------------------- lifecycle

    def start(self) -> None:
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="dispatch-pipeline", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        # Accumulated evals must not die with the pipeline: hand their
        # leases back so another server's workers redeliver them.
        self.drain()

    def drain(self) -> int:
        """Leadership loss (or shutdown): requeue every accumulated
        eval into the broker by nacking its outstanding token, so
        nothing a batch had in hand is lost and nothing double-places.

        Extends the PR 1 requeue path one failure class further: a
        conflict requeue re-enters the ACCUMULATING batch with its
        token still outstanding; a drain gives the token BACK — on the
        (old) leader the nack re-readies the eval immediately, and when
        the broker is already disabled/flushed (a real flap) the nack
        fails cleanly and the new leader re-seeds the eval from raft
        state (_restore_evals), since an undelivered eval is still
        status=pending there. In-flight-but-unacked batch members need
        no sweep: their stage threads' acks fail against the flushed
        broker and the same restore covers them, while the plan-queue
        token guard (plan_submit checks the OUTSTANDING token) keeps a
        stale batch from committing a double placement."""
        with self._cond:
            pending, self._pending = self._pending, []
            self._cond.notify_all()
        for entry in pending:
            self._finish(entry, acked=False)
        if pending:
            with self._lock:
                self.drained += len(pending)
            self.logger.info(
                "drained %d accumulated evals back to the broker",
                len(pending))
        return len(pending)

    # ------------------------------------------------------ admission

    def submit(self, ev: Evaluation, token: str) -> None:
        """Hand a dequeued dense-path eval to the pipeline (worker
        handoff, and the conflict-requeue re-entry)."""
        self._admit(_Pending(ev, token))

    def _admit(self, entry: _Pending) -> None:
        entry.enqueued_at = time.monotonic()
        with self._cond:
            self._pending.append(entry)
            self.evals_in += 1
            if self._drain_waiting and not self._notified_at:
                # Stamped HERE, lock held, right before the notify —
                # not entry.enqueued_at: the admitter's own wait for
                # this lock is already measured by the lock's wait
                # histogram, and folding it in would double-count
                # admit-side contention as dispatcher wake latency.
                self._notified_at = time.monotonic()
            self._cond.notify_all()

    def arrivals_settled(self) -> None:
        """The last register on the way has returned
        (Server._registering): its eval is on the broker or was never
        made, so an idle accumulator that held its batch for it takes
        one more pass and cuts, now and not a slice later."""
        with self._cond:
            self._cond.notify_all()

    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    def saturated(self) -> bool:
        """Intake-backpressure signal for the worker handoff
        (server/worker.py): True while the accumulator already holds
        two full batches' worth of evals. A saturated pipeline must not
        keep draining the broker — evals held here are invisible to the
        bounded ready queues (nomad_tpu/admission), so an unbounded
        drain would reopen exactly the intake the depth caps close."""
        with self._lock:
            return len(self._pending) >= 2 * self.max_batch

    # ------------------------------------------------------ dispatcher

    def _run(self) -> None:
        while not self._stop.is_set():
            batch = self._accumulate()
            forming, self._forming = self._forming, None
            if not batch and forming is not None:
                forming.settle()
            if batch:
                # The launch prologue BLOCKS — _wait_for_index
                # sleep-polls the FSM for up to WAIT_INDEX_TIMEOUT and
                # snapshotting walks every table — so it runs on a
                # stage thread. The dispatcher goes straight back to
                # accumulating: the next batch keeps filling while this
                # one catches up to its snapshot index (previously a
                # follower lagging the leader commit froze ALL lanes
                # for the duration, not just this batch's).
                # _accumulate already took the in-flight slot, so the
                # pipelining bound still holds while the launch is in
                # hand-off.
                self.server.eval_pool.submit(self._launch, batch, forming)

    def _accumulate(self) -> List[_Pending]:
        """Pack the next batch: wait for a seed eval, then top up with
        one central broker drain per pass. Close rules: a FULL batch
        closes immediately; an idle pipeline (nothing in flight) closes
        after the pass's drain as soon as no register is on the way
        (Server.registers_on_the_way: nobody can join, and a lone
        interactive eval must not marinate), and while one is it waits
        for the last of them to return (arrivals_settled wakes it) or
        for `idle_grace`, the cap, whichever is first; while batches
        are in flight the accumulator keeps filling for `window` — the
        in-flight round-trip is exactly the budget this wait amortizes
        — and when every slot is busy it simply keeps accumulating
        until one frees."""
        with self._cond:
            self._drain_waiting = True
            try:
                while not self._pending and not self._stop.is_set():
                    self._cond.wait(0.25)
            finally:
                self._drain_waiting = False
            if not self._pending:
                self._notified_at = 0.0
                return []
            # Run-queue delay at the broker-drain point: notify-while-
            # parked -> this thread actually running — the dispatcher's
            # wake latency under GIL pressure, nothing else (the top-up
            # window and slot waits are deliberate batching time: the
            # dispatch.accumulate span, not this).
            if self._notified_at:
                profile.record_runq(
                    "broker_drain",
                    (time.monotonic() - self._notified_at) * 1000.0)
                self._notified_at = 0.0
            profile.event("accumulate_open", "dispatcher",
                          a=len(self._pending))
        start = time.monotonic()
        held = False  # an idle pass waited for a register on the way
        while not self._stop.is_set():
            with self._lock:
                room = self.max_batch - len(self._pending)
            if room > 0:
                # The central drain: everything ready across the
                # broker, not one worker's slice.
                got = self.server.eval_dequeue_many(self.types, room)
                if got:
                    now = time.monotonic()
                    with self._cond:
                        for ev, token in got:
                            entry = _Pending(ev, token)
                            entry.enqueued_at = now
                            self._pending.append(entry)
                            self.evals_in += 1
            with self._cond:
                elapsed = time.monotonic() - start
                if len(self._pending) >= self.max_batch:
                    break
                slots = 1 if self._alone else self.max_inflight
                if self._inflight == 0:
                    on_the_way = self.server.registers_on_the_way()
                    if not on_the_way or elapsed >= self.idle_grace:
                        self.idle_closes += 1
                        if on_the_way:
                            self.idle_closes_at_cap += 1
                        elif not held:
                            self.idle_closes_at_once += 1
                        break
                    held = True
                elif self._inflight < slots and elapsed >= self.window:
                    break
                announce = (self._forming is None
                            and 0 < self._inflight < slots)
                if not announce:
                    self._cond.wait(DEQUEUE_TOPUP_SLICE)
            if announce:
                self._announce_forming()
        # Wait for an in-flight slot; late arrivals keep joining the
        # pending list while we wait (that IS the adaptive window).
        with self._cond:
            while (self._inflight >= (1 if self._alone
                                      else self.max_inflight)
                   and not self._stop.is_set()):
                self._cond.wait(SLOT_WAIT_SLICE)
            batch = self._pending[: self.max_batch]
            del self._pending[: len(batch)]
            if not batch:
                return []
            self._inflight += 1
            others = self._inflight > 1
            if self._alone:
                self._alone -= 1
                self.alone_batches += 1
            elif others and self._shared_mark is None:
                self._shared_mark = self.plan_conflicts
            self.batches += 1
            self.dispatched_evals += len(batch)
            self.largest_batch = max(self.largest_batch, len(batch))
            if len(batch) > 1:
                self.requeues_batched += sum(
                    1 for entry in batch if entry.requeues)
            profile.event("accumulate_close", "dispatcher",
                          a=len(batch), b=self.batches)
        if others and self._forming is None:
            self._announce_forming()
        metrics.add_sample(("dispatch", "batch_size"), len(batch))
        return batch

    def _announce_forming(self) -> None:
        """Tell the batcher that a batch is forming beside one in
        flight and WILL launch within the window (a slot is free, or
        it has just taken one): a cohort of one placeholder unit, which
        _launch settles when the batch's prologue is through (its own
        cohort is open by then) or has failed, and the prologue itself
        before it waits for a lagging FSM. A dispatch that holds the
        in-flight batch's requests waits for every open cohort
        (scheduler/batcher.py _accumulate), so the two batches go
        together whatever the prologue's length: it waits for at most
        this batch's window and its base prefetch. While a prologue
        took 40-80 ms they mostly met by themselves; since PR 39 it
        takes 5-25, every batch went alone, overlapped both neighbours'
        uncommitted plans, and the storm's conflicts rose from 0.16 an
        eval to 0.45 (PERF.md section 6, PRs 31 and 39). Bounded by the
        cohort's cap like any member that does not come."""
        from ..scheduler.batcher import get_batcher

        (self._forming,) = get_batcher().open_cohort(1)

    def _launch(self, batch: List[_Pending], forming=None) -> None:
        try:
            self._launch_batch(batch, forming)
        finally:
            if forming is not None:
                forming.settle()

    def _launch_batch(self, batch: List[_Pending], forming) -> None:
        # Trace: the accumulate stage closes when the batch is cut.
        # Recorded HERE (stage thread) rather than in _accumulate so
        # the dispatcher thread carries zero extra work per batch.
        t_launch = time.monotonic()
        # Deadline enforcement BEFORE any matrix build or cohort
        # announcement: an expired eval must not burn a device lane on
        # a plan its submitter already gave up on (nomad_tpu/admission
        # deadline semantics; the broker enforces the same bound at
        # dequeue, this covers time spent accumulating).
        batch = self._drop_expired(batch, t_launch)
        if not batch:
            with self._cond:
                self._inflight -= 1
                self._cond.notify_all()
            return
        for entry in batch:
            trace.record_span(
                entry.eval.id, trace.STAGE_DISPATCH_ACCUMULATE,
                entry.enqueued_at, t_launch,
                ann={"batch": len(batch), "requeues": entry.requeues},
                trace_id=entry.eval.trace_id)
        # The whole prologue is guarded: it runs on a pool thread now,
        # where an escaped exception dies into an unread PoolFuture —
        # and the slot _accumulate took would leak, wedging the
        # accumulator once max_inflight failed launches pile up.
        try:
            with trace.annotation("nomad.launch_prologue",
                                  evals=len(batch)):
                prologue = self._launch_prologue(batch, forming)
        except Exception:
            self.logger.exception(
                "batch launch failed; nacking %d evals", len(batch))
            prologue = None
        # One instant ends every member's dispatch.launch and starts its
        # dispatch.pool_wait: the fan-out below hands the entries to the
        # pool one after another, and an entry's wait for its turn in
        # that loop is part of its wait for a stage thread.
        t_fan = time.monotonic()
        for entry in batch:
            trace.record_span(
                entry.eval.id, trace.STAGE_DISPATCH_LAUNCH, t_launch,
                t_fan,
                ann=({"failed": True} if prologue is None else None),
                trace_id=entry.eval.trace_id)
        # Single abort call site: an abort raising INSIDE the try must
        # never be re-entered by the except path (double slot release).
        if prologue is None:
            self._abort_batch(batch)
            return
        # Fan-out needs no guard: WorkPool.submit enqueues then NEVER
        # raises (a failed worker spawn is swallowed and retried on the
        # next submit — utils/pool.py), so every entry is handed off
        # exactly once and releases the slot via `remaining`. A
        # partial-fan-out cleanup here would double-finish entries the
        # pool still runs.
        snapshot, route_host, units = prologue
        profile.event("launch", "stage", a=len(batch), b=int(route_host))
        remaining = [len(batch)]
        for entry, unit in zip(batch, units):
            self.server.eval_pool.submit(
                self._process_entry, entry, snapshot, route_host,
                remaining, t_fan, unit)

    def _drop_expired(self, batch: List[_Pending],
                      t_launch: float) -> List[_Pending]:
        """Split out entries whose deadline passed while accumulating,
        terminalize them (status=failed with a structured reason +
        ack), and return the live remainder. Runs on a stage thread."""
        now = time.time()
        live: List[_Pending] = []
        expired: List[_Pending] = []
        for entry in batch:
            if entry.eval.expired(now):
                expired.append(entry)
            else:
                live.append(entry)
        if not expired:
            return batch
        with self._lock:
            self.expired_dropped += len(expired)
        metrics.incr_counter(("dispatch", "expired_dropped"),
                             len(expired))
        for entry in expired:
            trace.record_span(
                entry.eval.id, trace.STAGE_DISPATCH_ACCUMULATE,
                entry.enqueued_at, t_launch,
                ann={"expired": True, "deadline": entry.eval.deadline},
                trace_id=entry.eval.trace_id)
            self._finish_expired(entry)
        return live

    def _finish_expired(self, entry: _Pending) -> None:
        """Persist the structured terminal outcome for one expired
        entry, then release its broker lease. On a leader flap either
        write can fail — the nack timer redelivers and the broker's
        dequeue-side deadline check parks it structured there instead,
        so the eval still reaches exactly one terminal outcome."""
        upd = entry.eval.copy()
        upd.status = consts.EVAL_STATUS_FAILED
        upd.status_description = (
            f"deadline expired before dispatch: deadline "
            f"{entry.eval.deadline:.3f} passed while accumulating "
            f"(originally triggered by {entry.eval.triggered_by!r})")
        try:
            self.server.eval_update([upd])
        except Exception:
            self.logger.warning(
                "expired-eval terminal write for %s failed; broker "
                "deadline check will re-park it", entry.eval.id,
                exc_info=True)
            self._finish(entry, acked=False)
            return
        self._finish(entry, acked=True)

    def _abort_batch(self, batch: List[_Pending]) -> None:
        """Nack every entry and release the in-flight slot
        _accumulate took for this batch. The release is in a finally:
        aborts run exactly when the leader is unreachable, so the
        nacks themselves may fail — a slot leak here would wedge the
        accumulator after max_inflight failed aborts."""
        try:
            for entry in batch:
                self._finish(entry, acked=False)
        finally:
            with self._cond:
                self._inflight -= 1
                self._cond.notify_all()

    def _launch_prologue(self, batch: List[_Pending], forming=None):
        """(snapshot, route_host, units) for a launchable batch, None
        when the FSM never caught up to the batch's snapshot index.
        `units` holds, entry for entry, each eval's unit of the batch's
        cohort at the batcher (None for an eval that never meets it).
        Returned, not stored: concurrent launches each carry their
        own."""
        if chaos.enabled:
            # 'error' = the launch prologue dies (snapshot/catch-up
            # failure): _launch aborts the batch, every eval nacks and
            # redelivers. 'delay' = a follower lagging the leader.
            chaos.fire("dispatch.launch", batch=len(batch))
        # Device-path circuit breaker (admission/breaker.py), the one
        # route to the host factories (identical placement semantics,
        # parity-tested): an OPEN breaker inside its cool-down routes
        # the whole batch there up front — no matrix build against a
        # sick device path, no cohort to open.
        # This is the NON-consuming hint: once the cool-down elapses it
        # goes quiet and the dense path's acquire() gate
        # (scheduler/tpu.py) sends exactly one half-open probe.
        from ..admission import get_breaker

        route_host = get_breaker().should_route_host()
        if route_host:
            with self._lock:
                self.breaker_routed += len(batch)
                self.routed_host += len(batch)
            metrics.incr_counter(
                ("dispatch", "breaker_route_host"), len(batch))
            metrics.incr_counter(("dispatch", "route_host"), len(batch))
        # One MVCC snapshot for the whole batch: every member plans
        # against the same cluster state so their ClusterMatrix bases
        # share one token, one device upload, and one serialized
        # claim scan. Optimistic concurrency keeps it safe: the applier
        # re-verifies every node.
        max_index = max(max(e.eval.modify_index, e.min_index)
                        for e in batch)
        if (forming is not None
                and self.server.fsm.state.latest_index() < max_index):
            # The FSM lags (a follower behind its leader): nobody waits
            # that out on this batch's placeholder; the batch in flight
            # goes alone, as it did before the placeholder.
            forming.settle()
        if not self._wait_for_index(max_index, WAIT_INDEX_TIMEOUT):
            return None
        snapshot = self.server.fsm.state.snapshot()
        units = [None] * len(batch)
        if not route_host:
            self._prefetch_bases(batch, snapshot)
            # Announce the fan-out to the batcher: this batch is cut and
            # counted, so a dispatch that holds its place() calls (their
            # matrix builds stagger under the GIL) closes when the last
            # of the batch has arrived or been settled, not on a timed
            # window. Opened last, after everything that can raise and
            # after the prefetch: a prologue that fails opens nothing.
            # (The other batch in flight does wait this prefetch out
            # since PR 39, on the placeholder _announce_forming opened
            # and _launch settles once this cohort is open; never an
            # index wait.) System-dense evals
            # are excluded — DenseSystemScheduler's vectorized pass
            # never touches the batcher. A generic dense eval that
            # takes a host path, places nothing or fails settles its
            # unit (PipelineSession.settle_cohort).
            from ..scheduler.batcher import get_batcher

            members = [i for i, e in enumerate(batch)
                       if e.eval.type != consts.JOB_TYPE_SYSTEM]
            for i, unit in zip(
                    members, get_batcher().open_cohort(len(members))):
                units[i] = unit
        return snapshot, route_host, units

    def _prefetch_bases(self, batch: List[_Pending], snapshot) -> None:
        """Async double-buffering, host side: make this batch's cluster
        base(s) device-resident NOW — on this stage thread, while the
        PREVIOUS batch's device compute and plan submits are still in
        flight (`dispatch_max_inflight` overlaps them) — so the batch's
        evals find their base token already cached at place() time and
        the (tiny) delta transfer hides under compute instead of
        serializing in front of its own dispatch. The base is
        job-independent; distinct datacenter sets across the batch's
        jobs each resolve one base. Failures are non-fatal: place()
        falls back to uploading synchronously, exactly as before."""
        from ..models.matrix import prefetch_cluster_base
        from ..scheduler.batcher import get_batcher

        dc_sets = {}
        for entry in batch:
            if entry.eval.type == consts.JOB_TYPE_SYSTEM:
                # DenseSystemScheduler builds its matrix over explicit
                # pinned nodes (a different cache family) and never
                # touches the batcher — same exclusion as the cohort's.
                continue
            job = snapshot.job_by_id(entry.eval.job_id)
            if job is None:
                continue
            dc_sets.setdefault(
                tuple(sorted(job.datacenters or [])), []).append(entry)
        batcher = get_batcher()
        for dcs, entries in dc_sets.items():
            t0 = time.monotonic()
            try:
                view, kind = prefetch_cluster_base(snapshot, list(dcs))
                t_host = time.monotonic()
                nbytes = batcher.prefetch_base(view) if view else 0
            except Exception:
                self.logger.warning(
                    "base prefetch failed; place() will upload "
                    "synchronously", exc_info=True)
                with self._lock:
                    self.prefetch_failures += 1
                continue
            with self._lock:
                self.prefetches += 1
                self.prefetch_bytes += nbytes
            metrics.incr_counter(("dispatch", "prefetch_bytes"), nbytes)
            profile.event("prefetch", "stage", a=int(nbytes))
            if kind == "delta":
                # The host half alone, once a derived delta (on the
                # batch's first eval): what the delta held and how many
                # jobs' positions it rewrote.
                trace.record_span(
                    entries[0].eval.id, trace.STAGE_BASE_DELTA, t0, t_host,
                    ann=view.delta_stats,
                    trace_id=entries[0].eval.trace_id)
            # One span per eval riding this base: stage attribution for
            # the new path (the bytes shipped are the batch's WHOLE
            # host->device traffic when the delta path holds).
            for entry in entries:
                trace.record_span(
                    entry.eval.id, trace.STAGE_DEVICE_TRANSFER, t0,
                    ann={"bytes": nbytes, "kind": kind},
                    trace_id=entry.eval.trace_id)

    # ---------------------------------------------------------- stages

    def _process_entry(self, entry: _Pending, snapshot, route_host: bool,
                       remaining: List[int], t_fan: float,
                       unit=None) -> None:
        ev, token = entry.eval, entry.token
        start = time.monotonic()
        trace.record_span(ev.id, trace.STAGE_DISPATCH_POOL_WAIT, t_fan,
                          start, trace_id=ev.trace_id)
        # Lock-wait attribution for this stage: the profiler keeps a
        # per-thread contended-wait total; the delta across the
        # scheduler invoke lands on the span so a slow scheduler.process
        # can be read as "blocked on locks" vs "actually computing".
        wait0 = profile.thread_wait_ms()
        session = PipelineSession(self, entry, cohort=unit)
        try:
            try:
                self._schedule(session, snapshot, route_host)
            finally:
                # The net under every way out of the scheduler (no
                # placements, a raise, a requeue, or a path that
                # settled already): no unit of the cohort stays open
                # past its eval.
                session.settle_cohort()
        except _RequeueConflict:
            with self._lock:
                self.requeues += 1
            trace.record_span(ev.id, trace.STAGE_SCHED_PROCESS, start,
                              ann={"path": "pipeline", "requeued": True},
                              trace_id=ev.trace_id)
            metrics.incr_counter(("dispatch", "requeue"))
            # Back into the ACCUMULATING batch; the broker token stays
            # outstanding, so per-job serialization still holds.
            entry.requeues += 1
            self._release_slot(remaining)
            self._admit(entry)
            return
        except Exception:
            self.logger.exception("pipeline eval %s failed", ev.id)
            trace.record_span(ev.id, trace.STAGE_SCHED_PROCESS, start,
                              ann={"path": "pipeline", "failed": True},
                              trace_id=ev.trace_id)
            self._finish(entry, acked=False)
            self._release_slot(remaining)
            return
        trace.record_span(
            ev.id, trace.STAGE_SCHED_PROCESS, start,
            ann={"path": "pipeline", "route_host": route_host,
                 "lock_wait_ms": round(
                     profile.thread_wait_ms() - wait0, 3)},
            trace_id=ev.trace_id)
        self._finish(entry, acked=True)
        self._release_slot(remaining)

    def _schedule(self, session: PipelineSession, snapshot,
                  route_host: bool) -> None:
        """Run one eval's scheduler against the batch's snapshot."""
        ev = session.eval
        if chaos.enabled:
            # 'delay' = a stalled stage consumer (a wedged
            # scheduler thread): the eval sits in process, the e2e
            # p99 inflates, and the pressure monitor must see it —
            # the overload soak forces consumer stalls through this
            # site. 'error' = the consumer dies; the eval nacks and
            # redelivers via _process_entry's except path.
            chaos.fire("admission.slow_consumer", eval_id=ev.id)
        factory = self.server.config.factory_for(ev.type)
        if route_host:
            from ..server.worker import host_factory

            factory = host_factory(factory)
        # Independent PRNG per eval (see worker.py: correlated
        # tie-break streams spike plan conflicts).
        rng = random.Random(int.from_bytes(os.urandom(8), "little"))
        sched = new_scheduler(
            factory, self.logger, snapshot, session, rng=rng)
        sched.process_eval(ev)

    def _finish(self, entry: _Pending, acked: bool) -> None:
        if chaos.enabled and chaos.fire(
                "dispatch.finish", eval_id=entry.eval.id) == "drop":
            # Injected worker crash holding an unacked eval: neither
            # ack nor nack goes out — the broker's nack timer is the
            # recovery path and MUST reclaim it (asserted by the soak).
            with self._lock:
                self.finish_dropped += 1
            return
        try:
            if acked:
                self.server.eval_ack(entry.eval.id, entry.token)
            else:
                self.server.eval_nack(entry.eval.id, entry.token)
        except ValueError:
            pass  # nack timer fired concurrently
        except Exception:
            # On a follower the ack/nack is an RPC to the leader and
            # fails exactly when aborts happen (leader flap). The
            # broker's nack timer reclaims the eval either way; raising
            # out of a stage thread would leak slot accounting instead.
            self.logger.warning(
                "eval %s %s failed; nack timer will reclaim",
                entry.eval.id, "ack" if acked else "nack",
                exc_info=True)
        with self._lock:
            if acked:
                self.acked += 1
            else:
                self.nacked += 1
        profile.event("ack", a=int(acked))

    def _release_slot(self, remaining: List[int]) -> None:
        with self._cond:
            remaining[0] -= 1
            if remaining[0] == 0:
                self._inflight -= 1
                if self._inflight == 0 and self._shared_mark is not None:
                    # A stretch of shared slots is over. If no plan
                    # conflicted in it, sharing works here: the next
                    # conflict costs one batch alone again.
                    if self.plan_conflicts == self._shared_mark:
                        self._alone_next = 1
                    self._shared_mark = None
                self._cond.notify_all()

    # ------------------------------------------------------- plumbing

    def _wait_for_index(self, index: int, timeout: float) -> bool:
        # Runs on stage threads only (never the dispatcher); the shared
        # jittered-backoff poll replaces the ad-hoc doubling loop.
        return poll_until(
            lambda: self.server.fsm.state.latest_index() >= index,
            timeout, stop=self._stop, base=0.001, max_delay=0.1)

    def _note_submit(self, start: float) -> None:
        metrics.measure_since(("dispatch", "submit_plan"), start)
        profile.event(
            "submit", a=round((time.monotonic() - start) * 1000.0, 3))

    def _note_conflict(self) -> None:
        with self._lock:
            self.plan_conflicts += 1
            if self._shared_mark is not None and not self._alone:
                # Slots are shared: the batches in flight finish, then
                # `_alone_next` batches go one at a time (__init__ has
                # the rule).
                self._alone = self._alone_next
                self._alone_next = min(2 * self._alone_next, ALONE_MAX)
        metrics.incr_counter(("dispatch", "plan_conflict"))

    def _note_inline_retry(self) -> None:
        with self._lock:
            self.inline_retries += 1

    # ----------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._lock:
            batches = self.batches
            dispatched = self.dispatched_evals
            done = self.acked + self.nacked
            retries = self.requeues + self.inline_retries
            return {
                "enabled": self.enabled,
                "max_batch": self.max_batch,
                "batches": batches,
                "dispatched_evals": dispatched,
                # Lanes filled per launched batch (the r05 headline
                # bottleneck: 9.4/64).
                "occupancy": round(dispatched / batches, 2) if batches else 0.0,
                "occupancy_frac": round(
                    dispatched / (batches * self.max_batch), 4
                ) if batches else 0.0,
                "largest_batch": self.largest_batch,
                "in_flight": self._inflight,
                "registers_on_the_way": self.server.registers_on_the_way(),
                "idle_closes": self.idle_closes,
                "idle_closes_at_once": self.idle_closes_at_once,
                "idle_closes_at_cap": self.idle_closes_at_cap,
                "slots": 1 if self._alone else self.max_inflight,
                "alone": self._alone,
                "alone_next": self._alone_next,
                "alone_batches": self.alone_batches,
                "pending": len(self._pending),
                "evals_in": self.evals_in,
                "acked": self.acked,
                "nacked": self.nacked,
                "routed_host": self.routed_host,
                "plan_conflicts": self.plan_conflicts,
                "requeues": self.requeues,
                "requeues_batched": self.requeues_batched,
                "inline_retries": self.inline_retries,
                "drained": self.drained,
                "finish_dropped": self.finish_dropped,
                "expired_dropped": self.expired_dropped,
                "breaker_routed": self.breaker_routed,
                "prefetches": self.prefetches,
                "prefetch_bytes": self.prefetch_bytes,
                "prefetch_failures": self.prefetch_failures,
                "retries_per_eval": round(retries / done, 4) if done else 0.0,
            }
