"""Span model for eval-lifecycle tracing.

A *span* is one named stage of one evaluation's life, with monotonic
start/end timestamps and optional annotations. The full set of stage
names an eval can produce is enumerated here so the e2e completeness
test (and the README table) have one source of truth.

Spans are stored as plain immutable tuples — ``(name, t0, t1, ann)`` —
so a reader racing the flight recorder can never observe a torn span:
the tuple is fully constructed before it is published into a ring slot.
"""

from __future__ import annotations

from typing import Optional, Tuple

# ------------------------------------------------------- stage names
#
# Ordered roughly by lifecycle position. Not every eval produces every
# stage: host-path evals skip the dense stages, placement-less evals
# (job stop) skip fsm.alloc_upsert, and dispatch.* only appear when the
# central pipeline handles the eval.

STAGE_API_REGISTER = "api.register"        # HTTP register handler entered
#   -> response ready (api/http.py; recorded at the handler's end onto
#   the trace the broker's mark opened inside the raft apply, so e2e
#   starts at the request)
STAGE_BROKER_WAIT = "broker.wait"          # enqueue -> dequeue
STAGE_DISPATCH_ACCUMULATE = "dispatch.accumulate"  # pipeline admit -> batch cut
STAGE_DISPATCH_LAUNCH = "dispatch.launch"  # launch prologue (catch-up + snapshot)
STAGE_DISPATCH_POOL_WAIT = "dispatch.pool_wait"  # launch fan-out ->
#   the eval's stage thread running (the pipeline's hand-off to
#   its stage pool)
STAGE_SCHED_PROCESS = "scheduler.process"  # scheduler invoke, end to end
STAGE_SCHED_RECONCILE = "scheduler.reconcile"  # inside scheduler.process:
#   the job's allocations against its registered version, diff_allocs
#   through evict_and_place (scheduler/generic.py _compute_job_allocs;
#   ann: stop, inplace, place, ignore): what an update of a running job
#   costs before anything is placed
STAGE_MATRIX_BUILD = "matrix.build"        # ClusterMatrix + ask construction
STAGE_MATRIX_UPDATE = "matrix.update"      # incremental delta vs full rebuild
STAGE_MATRIX_COMPRESS = "matrix.compress"  # signature-class interning
#   (models/classes.py; ann: classes C, nodes N, escaped, ratio N/C)
STAGE_FEASIBILITY_BUILD = "feasibility.build"  # inside matrix.build:
#   one job's constraint mask really BUILT over the node axis
#   (models/matrix.py _build_feasibility: a checker pass per computed
#   class, the expansion over N, the compact form; ann: classes,
#   groups, constraints, escaped). Recorded only on a miss of the
#   mask memo, so its sample count is the memo's misses
STAGE_MATRIX_PLAN_PATCH = "matrix.plan_patch"  # inside matrix.build:
#   what a plan that already stops or has placed something changes on
#   the rows it touches, stated against the cached base (models/
#   matrix.py _build_plan_patch; ann: rows, bucket: the padded rows a
#   lane ships, 0 where they went into a dense state of the matrix's
#   own). One sample a matrix whose plan touches a row; none for an
#   arrival
STAGE_DEVICE_TRANSFER = "device.transfer"  # base prefetch host->device
STAGE_BASE_DELTA = "base.delta"            # the HOST half of that
#   prefetch, or of an inline replan's matrix.update: a cluster base
#   really derived from its parent by delta (models/matrix.py
#   delta_update; ann: rows, adds, refills, patched_jobs). One sample a
#   derived delta (on the batch's first eval, or the replanning one):
#   never on a hit, a rekey or a full build
STAGE_DEVICE_DISPATCH = "device.dispatch"  # batcher.place round-trip
STAGE_DEVICE_SOLVE = "device.solve"        # the jitted placement-kernel
#   solve inside the dispatch (issue + device sync, kernel-annotated) —
#   device.dispatch minus batch-wait and host stacking
STAGE_MIGRATE_PLACE = "migrate.place"      # drain-displaced allocs staged
#   for re-placement under the migration budget (ann: migrations
#   claimed this wave, deferred to the follow-up eval)
STAGE_PREEMPT_SELECT = "preempt.select"    # dense victim-selection +
#   placement pass, whole (ops/preempt.py; ann: asks, candidate
#   victims); what its two children leave is `preempt.select.self`:
#   the node state from the cached base, the asks, staging the plan
STAGE_PREEMPT_VICTIMS = "preempt.victims"  # inside preempt.select: the
#   victim candidates looked up in the base's table (or built)
STAGE_PREEMPT_SOLVE = "preempt.solve"      # inside preempt.select: the
#   preemption program, issue to results on the host (ends in a sync)
STAGE_GANG_SELECT = "gang.select"          # one gang's all-K pass,
#   whole: request, dispatch through the batcher, plan staged
#   (nomad_tpu/gang, ops/gang.py; ann: members, mode, slice group,
#   moved, host_fallback). Its children are gang.build and the gang's
#   device.dispatch; what they leave is `gang.select.self`: port
#   offers and staging the members on the plan's gang leg
STAGE_GANG_BUILD = "gang.build"            # inside gang.select: the
#   request's host side (the matrix from the cached base, matrix.build
#   inside it, and the gang's lane: gang/__init__.py build_gang_request)
STAGE_GANG_SOLVE = "gang.solve"            # inside the gang's
#   device.dispatch: the batched gang program, issue to every result
#   on the host (ends in a sync; ann: gangs in the dispatch). Wraps
#   the dispatch's device.solve, which ends when the placements are
#   back; the slice and claim readings follow
STAGE_GANG_REJECTED = "gang.rejected"      # inside plan.evaluate: a
#   zero-length marker, one a gang the applier removed WHOLE because a
#   member's node failed verification (server/plan_apply.py; ann: width
#   = the gang's members, node = the first of its nodes that failed).
#   Its sample count is the gangs rejected whole
STAGE_BATCH_CLAIMS = "batch.claims"        # inside the device.dispatch
#   of an eval whose pipeline batch held gangs and plain asks: from the
#   first program's issue (the plain lanes') to the second's (the
#   gangs'), while the first's claims wait on the device to be the
#   second's starting state (scheduler/batcher.py
#   _record_claims_carry; ann: gang_lanes, plain_lanes). One sample a
#   hand-over, on the taking dispatch's first traced request; the
#   interval was device.dispatch.self before
STAGE_BATCH_HANDOVER = "batch.handover"    # inside the device.dispatch
#   of an eval whose pipeline batch's plain asks went to more than one
#   dispatch on their base token (several ask rungs, a service job
#   beside batch ones, a queue past its dispatch's lane cap): from the
#   earlier plain program's issue to this one's, which starts from its
#   claims (scheduler/batcher.py _record_claims_carry; ann: kind,
#   from_rung, rung, from_lanes, lanes). One sample a plain dispatch
#   that started from a plain dispatch's carry, on its first traced
#   request; batch.claims (which also carries kind and the rungs) keeps
#   meaning a plain and a gang dispatch crossing
STAGE_DEFRAG_SOLVE = "defrag.solve"        # one defrag-loop round's
#   warm-started global relaxation solve + move extraction
#   (nomad_tpu/defrag; ann: movable, moves, gain, warm, solve_ms) —
#   recorded on its own per-round trace, not an eval's
STAGE_PLAN_SUBMIT = "plan.submit"          # plan queue wait + commit (worker view)
STAGE_PLAN_QUEUE_WAIT = "plan.queue_wait"  # PlanQueue.enqueue -> the
#   applier taking the plan
STAGE_PLAN_EVALUATE = "plan.evaluate"      # applier per-node verification
STAGE_PLAN_COMMIT = "plan.commit"          # raft apply of the accepted plan
STAGE_ALLOC_UPSERT = "fsm.alloc_upsert"    # state-store alloc write
STAGE_EVAL_UPDATE = "eval.update"          # the eval's terminal status
#   write through raft (inside scheduler.process)

# Derived at complete() from the finished tree, never recorded by a call
# site (recorder.py _account): `<stage>.self` is a span's duration minus
# the union of its children's intervals, fed for every stage that has
# had children; `eval.uncovered` is the part of e2e no span covers.
SELF_SUFFIX = ".self"
STAGE_EVAL_UNCOVERED = "eval.uncovered"

# The device's idle time on the host clock, split by cause per dispatch
# (scheduler/batcher.py): fed through observe_stage, no eval's tree.
STAGE_IDLE_NO_WORK = "device.idle.no_work"        # last results ->
#   this batch's first request arrives (nothing was waiting for the chip)
STAGE_IDLE_BATCH_WAIT = "device.idle.batch_wait"  # first arrival ->
#   batch close (window and cohort wait)
STAGE_IDLE_STACK = "device.idle.stack"            # batch close -> issue
#   (host stacking and base upload)
DEVICE_IDLE_STAGES = (
    STAGE_IDLE_NO_WORK,
    STAGE_IDLE_BATCH_WAIT,
    STAGE_IDLE_STACK,
)
# The shape queues a pipeline batch's requests went to
# (scheduler/batcher.py _count_queues): a row of the stage table fed
# through observe_stages when the batch's last unit has arrived, one
# sample a queue, each the time the batch took to gather. Its sample
# count over the pipeline's `batches` is how many dispatches a batch
# becomes at the least.
STAGE_BATCH_QUEUES = "batch.queues"
BATCHER_ROW_STAGES = (STAGE_BATCH_QUEUES,)

# The client's path (PR 40): what the client is timed on and no eval's
# tree holds. Rows of the stage table fed through observe_stages /
# observe_stage, never spans of a trace, so `e2e` keeps its start and its
# end. README.md, "The client's path", has each stamp's exact place.
#
# The request on the server's thread (api/http.py `_dispatch`), for the
# route families `register` (PUT/POST /v1/jobs, /v1/job/<id>) and `eval`
# (GET /v1/evaluation/<id>): `front` is the request line in the handler
# thread's hands -> the route's handler entered; `reply` the handler
# returned -> the response written (none for a request that parks);
# `request` the first stamp to the last (for a parked one: to the
# hand-over to the mux); `cpu` the thread's CPU time over `request`'s
# interval, so that wall minus CPU is what the thread waited for: the
# GIL, a lock, the socket.
HTTP_STAGES = {
    family: tuple(f"http.{family}.{part}"
                  for part in ("front", "reply", "request", "cpu"))
    for family in ("register", "eval")
}
# The terminal status from its commit to the client's socket
# (readplane/mux.py).
STAGE_READ_PARK = "read.park"              # park -> hand-off to the
#   serve pool, by a wake or a timeout (the shutdown flush serves
#   inline): mostly the eval's own run time, not a lag
STAGE_READ_SERVE = "read.serve"            # the serve pool's re-run of
#   the query, the write and the connection's hand-back
STAGE_READ_SERVE_CPU = "read.serve.cpu"    # thread CPU time over it
STAGE_READ_NOTIFY_LAG = "read.notify_lag"  # a commit's notify (FSM
#   thread) -> the wake loop taking that batch; one sample a batch
STAGE_READ_SERVE_WAIT = "read.serve_wait"  # hand-off -> a pool worker
#   starting on it; woken queries only
STAGE_READ_DELIVER = "read.deliver"        # the commit's notify (or
#   the park, where park()'s own recheck found it satisfied) -> sendall
#   returned; woken queries only, none for a timeout or a shutdown flush
# The interpreter's scheduling delay (profile/sampler.py): how late a
# thread that asked for a 5 ms sleep woke, 200 samples a second.
STAGE_RUNTIME_GIL_WAIT = "runtime.gil_wait"
# The collector's passes (profile/collector.py), every thread stopped for
# their length: the hook queues them, the sampler's thread feeds them.
STAGE_RUNTIME_GC_PAUSE = "runtime.gc_pause"            # every pass
STAGE_RUNTIME_GC_FULL_PAUSE = "runtime.gc_full_pause"  # generation 2
CLIENT_PATH_STAGES = tuple(
    stage for names in HTTP_STAGES.values() for stage in names
) + (
    STAGE_READ_PARK,
    STAGE_READ_SERVE,
    STAGE_READ_SERVE_CPU,
    STAGE_READ_NOTIFY_LAG,
    STAGE_READ_SERVE_WAIT,
    STAGE_READ_DELIVER,
    STAGE_RUNTIME_GIL_WAIT,
    STAGE_RUNTIME_GC_PAUSE,
    STAGE_RUNTIME_GC_FULL_PAUSE,
)

ALL_STAGES = (
    STAGE_API_REGISTER,
    STAGE_BROKER_WAIT,
    STAGE_DISPATCH_ACCUMULATE,
    STAGE_DISPATCH_LAUNCH,
    STAGE_DISPATCH_POOL_WAIT,
    STAGE_SCHED_PROCESS,
    STAGE_SCHED_RECONCILE,
    STAGE_MATRIX_BUILD,
    STAGE_MATRIX_UPDATE,
    STAGE_MATRIX_COMPRESS,
    STAGE_FEASIBILITY_BUILD,
    STAGE_MATRIX_PLAN_PATCH,
    STAGE_DEVICE_TRANSFER,
    STAGE_BASE_DELTA,
    STAGE_DEVICE_DISPATCH,
    STAGE_DEVICE_SOLVE,
    STAGE_MIGRATE_PLACE,
    STAGE_PREEMPT_SELECT,
    STAGE_PREEMPT_VICTIMS,
    STAGE_PREEMPT_SOLVE,
    STAGE_GANG_SELECT,
    STAGE_GANG_BUILD,
    STAGE_GANG_SOLVE,
    STAGE_BATCH_CLAIMS,
    STAGE_BATCH_HANDOVER,
    STAGE_DEFRAG_SOLVE,
    STAGE_PLAN_SUBMIT,
    STAGE_PLAN_QUEUE_WAIT,
    STAGE_PLAN_EVALUATE,
    STAGE_GANG_REJECTED,
    STAGE_PLAN_COMMIT,
    STAGE_ALLOC_UPSERT,
    STAGE_EVAL_UPDATE,
)

# The stages every PLACING eval must produce regardless of path (the
# e2e completeness contract; dense/dispatch stages are path-dependent).
LIFECYCLE_CORE_STAGES = (
    STAGE_BROKER_WAIT,
    STAGE_SCHED_PROCESS,
    STAGE_PLAN_SUBMIT,
    STAGE_PLAN_EVALUATE,
    STAGE_PLAN_COMMIT,
    STAGE_ALLOC_UPSERT,
)

# Span tuple layout: (stage_name, t0_monotonic, t1_monotonic, ann)
# where ann is None or a small read-only dict built by the caller.
Span = Tuple[str, float, float, Optional[dict]]


def make_span(name: str, t0: float, t1: float,
              ann: Optional[dict] = None) -> Span:
    if t1 < t0:  # clock users pass (start, now); never invert
        t1 = t0
    return (name, t0, t1, ann)


def span_to_dict(span: Span, origin: float, faults=()) -> dict:
    """JSON shape for one span. `origin` is the trace's monotonic start
    so exported offsets are relative (monotonic absolutes are
    process-meaningless). `faults` are the chaos (site, ordinal, kind)
    triples whose firing time fell inside this span."""
    name, t0, t1, ann = span
    out = {
        "name": name,
        "start_ms": round((t0 - origin) * 1000.0, 3),
        "end_ms": round((t1 - origin) * 1000.0, 3),
        "duration_ms": round((t1 - t0) * 1000.0, 3),
    }
    if ann:
        out["annotations"] = dict(ann)
    if faults:
        out["faults"] = [
            {"site": site, "ordinal": seq, "kind": kind}
            for (_t, site, seq, kind) in faults
        ]
    return out
