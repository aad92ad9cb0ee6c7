"""Placement batcher: coalesce concurrent evaluations into one TPU
dispatch.

The north star (BASELINE.json, SURVEY.md §5): evals drained from the
broker batch into a single device program — N workers' placement
requests with the same bucketed shapes ride one dispatch
instead of N serial dispatches. Per-dispatch overhead (Python→XLA
call, transfer, device RTT) is paid once per batch.

Requests are grouped by shape key (node bucket, ask bucket, group
count, penalty): only same-shaped programs can share a dispatch (no
recompiles). Within a batch there are two device paths:

- every request shares one *cluster base* (the job-independent [N,4]
  matrices, models/matrix.py _ClusterBase, identified by its token):
  the base is uploaded once and LRU-cached on device; the dispatch
  moves only the small per-job overlays (alloc counts + feasibility),
  asks, and PRNG keys (ops/binpack.py
  batched_placement_program_overlay), and the lanes plan in order on
  one carry of the base's claimed columns. This is the live
  broker-drain fast path — many evals of different jobs against one
  snapshot.
- mixed bases: the full states stack along the batch axis and plan
  independently (batched_placement_program): nothing shared to carry.

A gang (nomad_tpu/gang) is a request in the same queues: place_gang
keys it by its static GangConfig as place() keys a plain one by its
PlacementConfig, it carries its pipeline batch's cohort, is counted in
`batched_requests` and `dispatches`, reads the same resident base and
its deltas (the topology id column resident beside it, _device_topology)
and the gangs of one dispatch are one program (ops/gang.py
batched_gang_placement_program) that carries each gang's claims to the
next.

A pipeline batch whose requests fall into more than one queue on one
base token (plain lanes of several ask rungs, a `service` job beside
`batch` ones, gangs beside plain asks, or one queue's lanes past its
dispatch's cap) is several dispatches on that token, and they go ONE
AFTER ANOTHER, each from the claims of those before it. The order is
total and read off what the requests carry (_rank): plain dispatches by
the padded length of their ask axis, the ask rung, SHORTEST FIRST, then
the gang dispatches; ties go by the order of the pops. A program is as
long as its lanes' scans, and whoever goes later waits for the earlier
on the device: with the claims carried no order loses a plan to the
applier, so the order is the one that costs the batch's evals least (a
burst's one-ask lanes do not wait out a 2,048-step scan; gangs first was
built and measured first: PERF.md section 6, PRs 42 and 45). A
program's final carry (utilisation, bandwidth, free ports after every
lane's claims) stays on the device under the token from
the moment the program is issued (_publish_claims); a dispatch on that
token waits for those ahead of it that are queued, or popped and not
yet issued (_await_turn, bounded by CLAIMS_WAIT_MAX), takes the newest
carry as its starting state in place of the base's three columns
(_take_claims), and publishes its own: no second copy of the base, no
round trip of the claims through the host, and no wait for an earlier
program's results either (the device runs them in order). Between its
turn and its issue a dispatch holds the token, so no two dispatches
ever start from one carry, and no two ever wait for each other. A batch
of ONE queue on a token nobody else touches is served as before: one
dispatch on the base, the program that fuses the base's delta in where
it ran. The programs stay the programs they were.

Past the ask ladder's first rung a queue's lanes go BATCH_BUCKETS[0] to
a dispatch (_lane_cap): there a lane is a scan of 16 to 2,048 steps, a
padding lane costs what a real one does, and every batch bucket would
be one more program for every rung; the chunks are chained by the carry
like any two dispatches of a token. ON the first rung a dispatch that
takes part in a hand-over pads its batch axis to BATCH_BUCKETS[1] at the
least, as a gang dispatch does (_batch_bucket): how many of a burst's
short lanes fall into one pipeline batch is the arrivals' luck, and the
step of the batch ladder they take must not be. And a first-rung
dispatch whose own step has never run takes the next one up that has,
where there is one: a compiled program is worth more than twelve idle
lanes of eight steps. So a token's short lanes run one program from the
first batch that shares its token on.

An eval whose plan already stops or has placed something (an update of
a running job: stops and placements in one plan) is a lane like any
other. Its matrix keeps the snapshot's base token and states the plan as
a PATCH (models/matrix.py ClusterMatrix.plan_patch): the rows the plan
touches and what it changes on them. The shared-base programs put a
lane's patch into that lane's view of the carry and take it out again
before the carry goes on, so what the lane's stops would free is its
alone to place on (another eval's plan could reach the applier first),
and the carry a token hands on holds placements only. An arrival carries
the empty patch of the ladder's first rung; the patch's bucket is part
of the queue key as the compact overlay's are, and stands nowhere in the
order of a token's dispatches (_rank): two queues that differ only in it
go by the order of their pops.

A dispatch closes on what its requests carry. Requests of a pipeline
batch carry that batch's cohort (open_cohort: the launch prologue has
already counted them), and their dispatch is released the moment every
cohort in the queue, and any other that is open, is complete, never by
the clock. Requests without
one (a worker's lone dense eval, direct callers) have nobody upstream
to count them: a first request on an idle batcher waits a short window
for concurrent workers to pile on, and while a device dispatch is in
flight new requests simply accumulate for the follow-up dispatch.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import profile, trace
from ..profile import ProfiledCondition, ProfiledLock

MAX_BATCH = 64
# Node count at which the device-cached base shards across a multi-chip
# mesh (node axis over ICI, parallel/mesh.py): below this, per-chip
# matrices are too small to beat the collective the sharded argmax
# inserts. Single-device runs never shard.
SHARD_MIN_NODES = 2048
# Idle-batcher accumulation window, for requests that carry NO cohort:
# a worker's one-eval dense run (eval_batch_size <= 1) and direct
# callers, whose concurrent place() calls nobody upstream has counted.
# Their calls arrive staggered by the GIL-serialized host phases
# (~2-4ms each), so a too-small window ships a near-empty first
# dispatch. ADAPTIVE: when the measured dispatch round-trip is large,
# waiting a fraction of it fills batches further. No served path of the
# dispatch pipeline reaches it: every request of a pipeline batch
# carries its cohort and closes on that (_accumulate). WINDOW_S and
# WINDOW_MAX_S have not been re-measured on an attached chip.
WINDOW_S = 0.02
WINDOW_MAX_S = 0.12
RESPAWN_WINDOW_S = 0.005  # post-dispatch window, same requests: GIL stragglers
# Cluster bases kept on device. Sized for the live storm's token churn:
# ~4 workers' wave snapshots plus the delta parents they derive from —
# evicting a parent forces the next delta into a full re-upload.
DEVICE_BASE_CACHE = 8
# Topology id columns kept on device (models/topology.py device_key):
# one per (tensor, level, singleton form) a gang dispatch has read. A
# tensor is rebuilt only when the node set changes, so a handful covers
# the live family and the one before it.
DEVICE_TOPO_CACHE = 8
# In-flight dispatches allowed per shape: overlapping device calls
# hides the per-dispatch round-trip behind the next batch's
# accumulation. XLA serializes the programs on-device; overlap buys
# transfer/queueing concurrency. Value not re-measured on an attached
# chip.
MAX_INFLIGHT = 3
# Requester park slice while its batch is in flight: long enough that
# re-checks are noise (the window + device call usually complete in
# one slice), short enough that a dead dispatcher is noticed fast.
REQUEST_WAIT_SLICE_S = 0.1
# The one bound on a cohort member that never comes, per cohort, from
# the cohort's first arrival. Every exit of an announced eval settles
# its unit (PipelineSession.settle_cohort, with a finally as the net),
# so a dispatch reaches this only through a fault in that hand-over or
# a member stuck for a second before its place(); it must still not
# wedge its batch-mates. On expiry that cohort alone is closed: its
# dispatch goes (counted closed_by_cap) and late members dispatch on
# arrival.
COHORT_WAIT_MAX = 1.0
# The bound on a dispatch's wait for the dispatches ahead of it on its
# token (_await_turn): as long as a dispatcher waits for another's
# upload of one base (_device_base), since the first dispatch of a shape
# compiles inside its issue. On expiry the dispatch goes on what has
# been published, blind to whoever it waited for as every dispatch was
# before PR 42, and is counted (claims_wait_expired).
CLAIMS_WAIT_MAX = 30.0


# ntalint residency manifest (analysis/residency.py): the ONE function
# allowed to ship a full cluster base host->device, and the one that
# ships a topology id column beside it (once a rebuild of the node
# set's tensor, models/topology.py). Everything else on
# the dispatch/scheduler steady state must ride the delta/cached paths
# — a full-matrix device_put creeping back into a hot path is exactly
# the per-batch re-ship the device-resident design removed, and it
# regresses silently (the code still works, just 10-100x the bytes).
NTA_REBUILD_ENTRYPOINTS = ("PlacementBatcher._build_device_base",
                           "PlacementBatcher._device_topology")


class _Cohort:
    """One pipeline batch's announcement (open_cohort): how many of its
    units have neither arrived in place() nor been settled. Guarded by
    the lock of the batcher that opened it."""

    __slots__ = ("size", "pending", "deadline", "arrived", "capped",
                 "queues", "opened")

    def __init__(self, n: int):
        self.size = n  # units announced: the evals of the batch
        self.pending = n
        # The shape queues its requests went to (counted token_queues
        # when the last unit has arrived or been settled).
        self.queues: set = set()
        self.opened = time.monotonic()
        # COHORT_WAIT_MAX from when it was opened, then from its first
        # arrival.
        self.deadline = time.monotonic() + COHORT_WAIT_MAX
        self.arrived = False
        self.capped = False  # closed by the cap, a member still out


class CohortUnit:
    """One eval's place in its batch's cohort. Handed to place() as
    `cohort=`, which marks it arrived; an eval that will not place
    calls settle(). Either happens once: whatever comes after finds
    the unit closed and takes nobody else's."""

    __slots__ = ("batcher", "cohort", "open", "closed_by")

    def __init__(self, batcher: "PlacementBatcher", cohort: _Cohort):
        self.batcher = batcher
        self.cohort = cohort
        self.open = True  # guarded by the batcher's lock
        # What released the dispatch this unit's request rode:
        # "cohort", "full" or "cap" (the device.dispatch span's
        # annotation); None until it has ridden one.
        self.closed_by: Optional[str] = None

    def take(self) -> bool:
        """Close this unit (the batcher's lock is held). True when
        that completed its cohort."""
        if not self.open:
            return False
        self.open = False
        if self.cohort.pending <= 0:
            return False  # the cap closed the cohort before
        self.cohort.pending -= 1
        return self.cohort.pending == 0

    def settle(self) -> None:
        """This eval's place() is not coming: its batch-mates'
        dispatch must not wait for it. Idempotent."""
        self.batcher.settle(self)

    def batch_mates(self) -> int:
        """The other evals of this unit's batch, while the unit has not
        ridden a dispatch nor been settled; 0 after (an inline replan),
        and for a batch of one."""
        return self.batcher.batch_mates(self)


class _Carry:
    """What the dispatches on one base token have claimed so far: the
    newest program's final carry, on the device. Guarded by the lock of
    the batcher that keeps it."""

    __slots__ = ("util", "bw_used", "ports_free", "kind", "lanes", "rung",
                 "issued_at", "taken")

    def __init__(self, carry, kind: str, lanes: int, rung: int):
        self.util, self.bw_used, self.ports_free = carry
        self.kind = kind  # of the program: "plain" or "gang"
        self.lanes = lanes
        self.rung = rung  # the program's ask (or member) axis
        self.issued_at = time.monotonic()
        # True once a dispatch of the OTHER kind has started from it
        # (one batch.claims sample a hand-over).
        self.taken = False


class _Request:
    __slots__ = ("token", "base", "overlay", "compact", "patch", "asks",
                 "key", "delta", "event", "choices", "scores", "error",
                 "span", "ready_at", "arrived_at", "unit", "topo", "info",
                 "hand_over", "order")

    def __init__(self, token, base, overlay, asks, key, delta=None,
                 compact=None, patch=None, span=None, unit=None,
                 topo=None):
        self.token = token  # cluster-base identity, None = unshared
        self.base = base  # (capacity, sched_capacity, util, bw_avail,
        #                    bw_used, ports_free, node_ok, class_ids)
        self.overlay = overlay  # (job_count, tg_count, feasible)
        # Pre-expansion overlay (ops/binpack.py CompactOverlay): when
        # every request in a shared-base batch carries one, only a few
        # KB cross host->device per eval and the dense overlays are
        # rebuilt on device.
        self.compact = compact
        # The lane's plan patch (models/matrix.py
        # ClusterMatrix.plan_patch): what its plan stops and has placed,
        # put into this lane's view of the shared base alone. Empty for
        # an arrival; None on a request without a token.
        self.patch = patch
        self.asks = asks  # ops/binpack.py Asks; a gang's GangLane
        self.key = key
        # A gang request's topology column, (device key, host [N]
        # int32); None on a plain request.
        self.topo = topo
        # A gang's (slice group, moved by an earlier lane's claims).
        self.info = None
        # Set at the pop on the first request of a batch that takes
        # part in a hand-over of claims (_dispatch): every gang batch
        # with a token, and a plain batch whose token another queue,
        # or another dispatch of its own, touches. It starts from its
        # token's carry and publishes its own, in the order of `order`
        # (_rank and the pop's ordinal).
        self.hand_over = False
        self.order: Optional[tuple] = None
        self.delta = delta  # (parent_token, changed_rows) or None
        self.span = span  # (eval_id, trace_id) for the device.solve span
        self.unit: Optional[CohortUnit] = unit
        # When the request reached the batcher: the earliest of a
        # batch's is where the device's idle gap stops being "no work"
        # and starts being "batch wait" (_idle_parts).
        self.arrived_at = time.monotonic()
        self.event = threading.Event()
        self.choices = None
        self.scores = None
        self.error: Optional[BaseException] = None
        # Stamped by the dispatcher right before event.set(): the
        # requester's wake latency from this instant is its RUN-QUEUE
        # delay (profile record_runq "batch_park") — how long a ready
        # result waited for the GIL to hand the parked worker a slot.
        self.ready_at = 0.0

    def full_state(self):
        from ..ops.binpack import make_node_state

        b, o = self.base, self.overlay
        return make_node_state(
            b[0], b[1], b[2], b[3], b[4], b[5], o[0], o[1], o[2], b[6]
        )


# Shape-bucket ladders. Every distinct padded size is a distinct XLA
# program (a trace + compile, or a compile-cache load), so COARSE
# ladders beat tight padding — the wasted lanes are device compute,
# the extra shapes are host stalls (pow2 row buckets made every storm
# dispatch a fresh shape). The ladders have not been re-measured on an
# attached chip.
ROW_BUCKETS = (256, 4096)
BATCH_BUCKETS = (4, 16, 64)

# Registered sizers for ntalint's `unbucketed-shape` rule: these two
# ARE this module's bucket functions (hand-rolled ladders over the
# tuples above, with a deliberate pow2 overflow fallback), so shapes
# they produce are sanctioned the same as matrix.py bucket_size.
NTA_BUCKET_FNS = ("_pad_rows", "_pad_batch", "_pad_gang_batch",
                  "_pad_gang_members", "_batch_bucket")


def _pad_rows(rows) -> np.ndarray:
    """Pad a changed-row index list up to a ladder bucket; padding
    repeats the FIRST changed row, and a duplicate-index scatter
    writing the identical value is benign."""
    n = len(rows)
    for b in ROW_BUCKETS:
        if n <= b:
            k = b
            break
    else:
        k = 1 << (n - 1).bit_length()
    rows_p = np.full(k, rows[0], np.int32)
    rows_p[:n] = rows
    return rows_p


def _pad_batch(n: int, max_batch: int) -> int:
    for b in BATCH_BUCKETS:
        if n <= b <= max_batch:
            return b
    return max_batch


def _node_base(state) -> tuple:
    """The job-independent node arrays of `state` in the order the
    device keeps a base (_build_device_base). Plain NodeState callers
    (tests) have no class index: the compact path is off for them
    anyway."""
    class_ids = getattr(state, "class_ids", None)
    if class_ids is None:
        class_ids = np.full(np.shape(state.node_ok), -1, np.int32)
    return (state.capacity, state.sched_capacity, state.util,
            state.bw_avail, state.bw_used, state.ports_free,
            state.node_ok, class_ids)


def _pad_gang_batch(n: int, max_batch: int) -> int:
    """The batch bucket of a gang dispatch: the ladder without its
    smallest step. A gang dispatch's member axis is its largest gang's
    bucket (_pad_gang_members), so every batch bucket is a program for
    EACH member bucket the traffic holds, and a lane with no member
    active costs the device next to nothing: a straggler's dispatch of
    one or two lanes runs the program its burst compiled."""
    return _pad_batch(max(n, BATCH_BUCKETS[1]), max_batch)


# The member axis of a gang dispatch (_pad_gang_members).
GANG_MEMBER_BUCKETS = [8, 32, 128, 512, 1024]


def _pad_gang_members(k: int) -> int:
    """The member axis of a gang dispatch: its largest gang's size on
    every other step of the ask ladder up to 1,024 (8, 32, 128, 512,
    1,024). Which gang is a batch's largest changes from batch to batch,
    and each step is a program for every batch bucket: half the steps is
    half the programs a warm-up has to meet, for a few member steps
    more."""
    from ..models.matrix import bucket_size

    return bucket_size(k, GANG_MEMBER_BUCKETS)


def _rank(req: "_Request") -> Tuple[bool, int]:
    """Where a request's dispatch stands among the dispatches of its
    base token: plain before gang, and plain by the padded length of
    the ask axis (the ask rung: the length of each lane's scan),
    shortest first."""
    if req.topo is not None:
        return (True, 0)
    return (False, int(np.shape(req.asks.active)[0]))


def _lane_cap(req: "_Request", max_batch: int) -> int:
    """The most lanes one dispatch of `req`'s queue takes. On the ask
    ladder's first rung, and for gangs, the batcher's max_batch: an
    eval is a handful of scan steps there, and the batch ladder up to
    sixty-four lanes is three short programs. Past it the ladder's
    first step: a lane is a scan of its own rung's length, which a
    padding lane runs too, and every batch bucket would be a program
    for every rung (nine rungs to 2,048); the rest of the queue rides
    the next dispatches, each from the claims of the one before."""
    from ..models.matrix import ASK_BUCKETS

    gang, rung = _rank(req)
    if gang or rung <= ASK_BUCKETS[0]:
        return max_batch
    return min(max_batch, BATCH_BUCKETS[0])


def _idle_parts(last_end: float, first_arrival: float, closed: float,
                issue: float) -> Tuple[float, float, float]:
    """The device's idle gap before one dispatch, [last_end, issue] on
    time.monotonic(), split by cause into seconds of (no_work,
    batch_wait, stack): up to the arrival of the batch's first request
    nothing was waiting for the chip; from there to the batch's close
    it was the window and the cohort wait; from close to issue, host
    stacking and base upload. Each boundary is clipped into the gap, so
    the three sum to it whatever the order of the instants (a request
    that arrived while the last program was still in flight has no
    no_work part)."""
    if issue <= last_end:
        return 0.0, 0.0, 0.0
    arrived = min(max(first_arrival, last_end), issue)
    closed = min(max(closed, arrived), issue)
    return arrived - last_end, closed - arrived, issue - closed


class PlacementBatcher:
    """Coalesces placement_program calls across scheduler threads."""

    def __init__(self, max_batch: int = MAX_BATCH, window: float = WINDOW_S):
        self.max_batch = max_batch
        self.window = window
        self.logger = logging.getLogger("nomad_tpu.batcher")
        # Profiled (nomad_tpu/profile): THE hot lock of the dense path
        # — per-site acquire-wait/hold histograms feed the contention
        # observatory's attribution of the device.dispatch tail.
        self._lock = ProfiledLock("scheduler.batcher")
        # Signaled by place() when a shape's queue reaches max_batch so
        # an accumulating dispatcher wakes immediately instead of
        # polling out its window.
        self._full = ProfiledCondition(self._lock, "scheduler.batcher")
        self._queues: Dict[Tuple, List[_Request]] = {}  # guarded-by: _lock
        self._dispatchers: Dict[Tuple, int] = {}  # guarded-by: _lock
        self._device_bases: "OrderedDict[object, tuple]" = OrderedDict()  # guarded-by: _lock
        # token -> Event while an upload/derivation is in progress:
        # overlapped dispatchers on one token must not each pay the
        # transfer this cache exists to avoid.
        self._base_pending: Dict[object, threading.Event] = {}  # guarded-by: _lock
        self._device_topos: "OrderedDict[tuple, object]" = OrderedDict()  # guarded-by: _lock
        self.topo_uploads = 0  # guarded-by: _lock (host->device columns)
        self._mesh = None  # guarded-by: _lock (lazy; False = 1 device)
        # Bases made device-resident SHARDED across the mesh — full
        # uploads and delta-derivations from a sharded parent alike.
        self.sharded_bases = 0  # guarded-by: _lock
        # Bases big enough to shard on a multi-device backend that
        # stayed on one device because the row count did not divide.
        self.unsharded_fallbacks = 0  # guarded-by: _lock
        self.dispatches = 0  # guarded-by: _lock (device calls issued)
        self.batched_requests = 0  # guarded-by: _lock (requests served)
        self.base_uploads = 0  # guarded-by: _lock (host->device bases)
        self.base_delta_updates = 0  # guarded-by: _lock (derived bases)
        self.overlay_dispatches = 0  # guarded-by: _lock (shared-base)
        self.compact_dispatches = 0  # guarded-by: _lock (device expand)
        # Per-dispatch cost breakdown (seconds/bytes, cumulative):
        # host->device payload size, dispatch issue, and the device
        # round-trip (sync = transport + compute). Host stacking and
        # base upload are the device.idle.stack histogram and the
        # nomad.stack / nomad.base_upload annotations.
        self.t_issue = 0.0  # guarded-by: _lock (jitted-call issue)
        self.t_sync = 0.0  # guarded-by: _lock (result fetch RTT)
        self.bytes_overlay = 0.0  # guarded-by: _lock (dispatch payload)
        self.bytes_upload = 0.0  # guarded-by: _lock (upload payload)
        # EMA of the dispatch round-trip, drives the adaptive window.
        self._sync_ema = 0.0  # guarded-by: _lock
        # Cohorts opened (open_cohort) with a unit still out: empty
        # whenever no pipeline batch is between its launch prologue and
        # its last member's place() or settle() (the gauge
        # open_cohorts).
        self._cohorts: set = set()  # guarded-by: _lock
        # Dispatches by what released them (_accumulate).
        self._closed_by = {"cohort": 0, "window": 0,  # guarded-by: _lock
                           "full": 0, "cap": 0}
        # The device's idle time by cause (_issue): programs between
        # issue and results on the host, over every shape key, and the
        # end of the last such interval (0.0: none yet). A gap is only
        # counted by the dispatch that ends it, and only when nothing
        # else was in flight, so concurrent dispatchers never count one
        # stretch twice.
        self._in_flight = 0  # guarded-by: _lock
        self._busy_until = 0.0  # guarded-by: _lock
        self._issued = 0  # guarded-by: _lock (nomad.dispatch ordinal)
        # token -> what the dispatches on a base token that more than
        # one dispatch touches have claimed so far. As many as bases are
        # kept.
        self._claims: "OrderedDict[object, _Carry]" = OrderedDict()  # guarded-by: _lock
        # token -> the dispatches of a hand-over popped and not yet
        # issued, {id of the batch's first request: its order}: a
        # dispatch waits for those of a lower order, and a dispatch
        # popped while any is here takes part.
        self._unissued: Dict[object, Dict[int, tuple]] = {}  # guarded-by: _lock
        # token -> the one dispatch (id of its first request) between
        # its turn and its issue: it has read the token's carry and not
        # yet published its own.
        self._going: Dict[object, int] = {}  # guarded-by: _lock
        self._popped = 0  # guarded-by: _lock (ordinal of hand-over pops)
        # program shape (a queue's key less its token) -> the steps of
        # the batch ladder its first-rung dispatches have run
        # (_batch_bucket).
        self._buckets_run: Dict[tuple, set] = {}  # guarded-by: _lock
        # Dispatches that started from the OTHER kind's carry (a plain
        # and a gang dispatch crossing), plain dispatches that started
        # from another plain dispatch's carry, the shape queues that
        # pipeline batches' requests went to, and dispatches that
        # waited CLAIMS_WAIT_MAX out.
        self.mixed_batches = 0  # guarded-by: _lock
        self.plain_handovers = 0  # guarded-by: _lock
        self.token_queues = 0  # guarded-by: _lock
        self.claims_wait_expired = 0  # guarded-by: _lock

    def open_cohort(self, n: int) -> List[CohortUnit]:
        """Announce a batch of `n` place() calls (the dispatch pipeline's
        launch prologue, which has cut the batch and so has counted it):
        the `n` units of one new cohort, one for each eval to hand to
        its place(). A dispatch that holds any of them is released when
        all `n` have arrived or been settled (and so have those of
        every other cohort open then: _accumulate), not by the clock —
        bounded by COHORT_WAIT_MAX."""
        if n <= 0:
            return []
        cohort = _Cohort(n)
        with self._lock:
            self._cohorts.add(cohort)
        return [CohortUnit(self, cohort) for _ in range(n)]

    def settle(self, unit: CohortUnit) -> None:
        """CohortUnit.settle: a unit whose place() will not come."""
        done = None
        with self._full:
            if unit.take():
                self._cohorts.discard(unit.cohort)
                self._full.notify_all()
                done = unit.cohort
        if done is not None:
            self._count_queues(done)

    def _count_queues(self, cohort: _Cohort) -> None:
        """A pipeline batch's last unit has arrived or been settled: the
        shape queues its requests went to, counted (token_queues, beside
        the pipeline's `batches`) and as that many samples of the row
        batch.queues, each the time the batch took to gather. Called
        without the lock."""
        n = len(cohort.queues)
        if not n:
            return
        from ..utils import metrics

        with self._lock:
            self.token_queues += n
        metrics.incr_counter(("placement_batcher", "token_queues"), n)
        ms = (time.monotonic() - cohort.opened) * 1000.0
        trace.get_recorder().observe_stages(
            [(trace.STAGE_BATCH_QUEUES, ms)] * n)

    def batch_mates(self, unit: CohortUnit) -> int:
        """CohortUnit.batch_mates."""
        with self._lock:
            return unit.cohort.size - 1 if unit.open else 0

    def place(self, state, asks, rng_key, config, span=None, cohort=None):
        """Submit one eval's placement; blocks until its batch's device
        dispatch returns. Returns (choices, scores) for THIS request.

        `cohort` is this eval's CohortUnit when a pipeline batch
        announced it (open_cohort): the call marks it arrived, and the
        request's dispatch closes on its cohort instead of the timed
        window. A second place() with the same unit (an inline replan)
        finds it closed, and its cohort complete.

        `state` is anything exposing the NodeState field names
        (ops/binpack.NodeState itself, or models/matrix.ClusterMatrix —
        the latter also carries base_token, enabling the shared-base
        device cache). `span` is an optional (eval_id, trace_id) pair:
        when set, the dispatcher records a `device.solve` span on that
        eval covering the jitted solve itself (issue + device sync,
        kernel-annotated) — the part of `device.dispatch` that is the
        kernel, separated from batch-wait and stacking."""
        base = _node_base(state)
        overlay = (state.job_count, state.tg_count, state.feasible)
        compact = getattr(state, "compact_overlay", None)
        token = getattr(state, "base_token", None)
        # Token is part of the grouping key: same-token requests share
        # one dispatch through the device-cached base (only the small
        # per-job overlays cross host->device). Mixing tokens in one
        # batch would force the stacked full-state path — at 5k+ nodes
        # that is ~10x the bytes per dispatch. Requests with
        # different tokens form separate queues whose dispatches
        # overlap (MAX_INFLIGHT is per key).
        # Compact padding sizes join the key: stacking requires every
        # request in a batch to share them (and a compact/dense mix in
        # one batch could not dispatch as one program).
        compact_key = None if compact is None else (
            np.shape(compact.verdicts)[0],
            np.shape(compact.patch_rows)[0],
            np.shape(compact.job_rows)[0],
        )
        # The lane's plan patch, and its bucket in the key for the same
        # reason: an update eval of a handful of stops carries the first
        # rung, as every arrival does (the empty patch), and rides their
        # dispatch.
        patch = None
        if token is not None:
            patch = getattr(state, "plan_patch", None)
            if patch is None:
                from ..models.matrix import empty_plan_patch

                patch = empty_plan_patch(np.shape(state.node_ok)[0])
        # The token first: what follows it is the program's shape
        # (_batch_bucket).
        shape_key = (
            token, np.shape(state.capacity), np.shape(asks.resources),
            np.shape(state.feasible)[-1], config, compact_key,
            None if patch is None else np.shape(patch[0])[0],
        )
        req = _Request(token, base, overlay, asks, rng_key,
                       delta=getattr(state, "base_delta", None),
                       compact=compact, patch=patch, span=span,
                       unit=cohort)
        self._submit(req, shape_key, config)
        return req.choices, req.scores

    def place_gang(self, state, gang, rng_key, span=None, cohort=None):
        """Submit one gang (nomad_tpu/gang build_gang_request against
        `state`, a ClusterMatrix); blocks until its batch's device
        dispatch returns. Returns (choices [K], scores [K], slice group,
        moved) for THIS gang: choices all >= 0 or all -1; `moved` says
        an earlier gang of the dispatch claimed the rack this one would
        have taken alone.

        The request queues, counts, closes on its cohort and reads the
        resident base exactly as place()'s do; gangs that share a base
        token, a topology column and a GangConfig share a dispatch,
        whatever their sizes, in which each sees the claims of those
        before it. A state without
        a base token (a plan that already stops or places something)
        has nothing resident to share and dispatches alone."""
        base = _node_base(state)
        token = getattr(state, "base_token", None)
        # No token, nothing to share: a key of its own.
        share = token if token is not None else object()
        # The gang's size is no part of the key: gangs of every size
        # share a dispatch (so each sees every other's claims), whose
        # member axis is its largest gang's bucket (_run_gang_batch).
        shape_key = (
            "gang", np.shape(state.capacity), gang.config, share,
            gang.topo_key,
        )
        req = _Request(token, base, None, gang.lane, rng_key,
                       delta=getattr(state, "base_delta", None),
                       span=span, unit=cohort,
                       topo=(gang.topo_key, gang.topo_ids))
        self._submit(req, shape_key, gang.config)
        group, moved = req.info
        return req.choices, req.scores, group, moved

    def _submit(self, req: _Request, shape_key, config) -> None:
        """Queue `req` under its shape key, dispatch or park until its
        batch's dispatch has set its results, and raise what the
        dispatch raised."""
        run_dispatch = False
        done = None
        with self._lock:
            q = self._queues.setdefault(shape_key, [])
            q.append(req)
            unit = req.unit
            if unit is not None:
                unit.cohort.queues.add(shape_key)
                if not unit.cohort.arrived:
                    unit.cohort.arrived = True
                    unit.cohort.deadline = req.arrived_at + COHORT_WAIT_MAX
                if unit.take():
                    self._cohorts.discard(unit.cohort)
                    self._full.notify_all()
                    done = unit.cohort
            if len(q) >= self.max_batch:
                self._full.notify_all()
            if self._dispatchers.get(shape_key, 0) == 0:
                # First in: this thread becomes the batch's dispatcher.
                # (Only idle shapes start here — while dispatchers are
                # in flight, arrivals accumulate for their respawns.)
                self._dispatchers[shape_key] = 1
                run_dispatch = True
        if done is not None:
            self._count_queues(done)
        if run_dispatch:
            self._dispatch(shape_key, config, wait_window=True)
        # Bounded park (ntalint unbounded-wait): slices with an
        # ownership re-check instead of a bare event.wait() — a
        # dispatcher that could not spawn (Thread.start under OS
        # thread pressure) or died in a way the _dispatch finally
        # could not cover must not wedge this worker forever.
        # Ownership has a legal gap (between a dispatcher's queue pop
        # and its finally running), so act only on the SECOND
        # consecutive ownerless observation.
        #
        # This wait region is the BATCH BOUNDARY: every worker whose
        # eval joined an in-flight dispatch parks here. The profiler's
        # convoy tracker measures the pile-up width/duration (ROADMAP
        # open item 1's named pathology), and ready_at -> wake latency
        # is the worker's run-queue delay under GIL pressure.
        suspect = False
        if not req.event.is_set():
            parked = profile.park("batcher.place")
            try:
                while not req.event.wait(REQUEST_WAIT_SLICE_S):
                    claim = orphaned = False
                    with self._lock:
                        live = self._dispatchers.get(shape_key, 0)
                        queued = any(
                            r is req
                            for r in self._queues.get(shape_key, ()))
                        if live > 0:
                            suspect = False
                        elif suspect and queued:
                            # Self-rescue: still queued with no
                            # dispatcher (a respawn's Thread.start
                            # failed) — become the dispatcher, exactly
                            # like the first-in path above.
                            self._dispatchers[shape_key] = 1
                            claim = True
                        elif suspect:
                            orphaned = True
                        else:
                            suspect = True
                    if claim:
                        self._dispatch(shape_key, config,
                                       wait_window=False)
                    elif orphaned and not req.event.is_set():
                        raise RuntimeError(
                            "placement request orphaned: no live "
                            "dispatcher for its shape key and the "
                            "request left the queue without a result "
                            "(dispatcher thread died between queue pop "
                            "and completion)")
            finally:
                if parked:
                    profile.unpark("batcher.place")
        if req.ready_at:
            profile.record_runq(
                "batch_park", (time.monotonic() - req.ready_at) * 1000.0)
        if req.error is not None:
            raise req.error

    # ------------------------------------------------------------------

    def _device_base(self, token, base, delta=None):
        """One host->device upload per cluster base, LRU-cached. When
        the base was delta-derived from a parent that is still on
        device, only the changed rows cross host->device and a scatter
        program derives the new base there (ops/binpack.py
        apply_base_delta) — a few hundred bytes instead of the full
        [N,4]x7 matrices."""
        while True:
            with self._lock:
                cached = self._device_bases.get(token)
                if cached is not None:
                    # True LRU: a hit refreshes recency, so alternating
                    # hot snapshots don't thrash the eviction order.
                    self._device_bases.move_to_end(token)
                    return cached, 0
                pending = self._base_pending.get(token)
                if pending is None:
                    # We are the builder.
                    done = threading.Event()
                    self._base_pending[token] = done
                    break
            # Another dispatcher is building this base: wait for its
            # cache insert instead of paying a duplicate transfer.
            pending.wait(30.0)
        try:
            with trace.annotation("nomad.base_upload",
                                  delta=delta is not None):
                dev, nbytes = self._build_device_base(token, base, delta)
        finally:
            with self._lock:
                self._base_pending.pop(token, None)
            done.set()
        return dev, nbytes

    def prefetch_base(self, state) -> int:
        """Double-buffering entry point (dispatch/pipeline.py): make
        `state`'s cluster base device-resident NOW, on the caller's
        (stage) thread — batch k+1's base upload/delta derivation runs
        under batch k's in-flight device compute instead of serializing
        in front of its own dispatch. `state` is a ClusterMatrix (or
        anything place() accepts); un-tokened states have nothing
        cacheable and return 0. Returns the bytes that crossed
        host->device (0 on a cache hit)."""
        token = getattr(state, "base_token", None)
        if token is None:
            return 0
        with self._lock:
            if token in self._device_bases:
                return 0
        base = _node_base(state)
        # Bytes come back from THIS call's build (0 on a lost
        # build race): a global counter-diff here would attribute
        # concurrent uploads of other tokens to this prefetch.
        _dev, nbytes = self._device_base(
            token, base, getattr(state, "base_delta", None))
        return int(nbytes)

    def _base_mesh(self, n: int):
        """nodes-axis mesh for big clusters on multi-device backends
        (one mesh per process; None on a single chip or small N).
        Built OUTSIDE the lock (device enumeration can stall on backend
        init) and published with a compare-and-set: concurrent builders
        waste one redundant make_mesh, never hold the batcher lock
        through it."""
        if n < SHARD_MIN_NODES:
            return None
        with self._lock:
            mesh = self._mesh
        if mesh is None:
            import jax

            if jax.device_count() > 1:
                from ..parallel.mesh import make_mesh

                built = make_mesh(dp=1)
            else:
                built = False
            with self._lock:
                if self._mesh is None:
                    self._mesh = built
                mesh = self._mesh
        mesh = mesh or None
        if mesh is not None and n % mesh.shape["nodes"]:
            # Bucketing (models/matrix.py, multiples of 128) should
            # prevent this. The base then lives on ONE device of a
            # multi-device backend — correct, but not the layout the
            # operator's hardware implies, so it is counted and said.
            with self._lock:
                self.unsharded_fallbacks += 1
            self.logger.warning(
                "cluster base of %d rows does not divide over %d "
                "devices; keeping it on one device", n,
                mesh.shape["nodes"])
            return None
        return mesh

    def _build_device_base(self, token, base, delta):
        import jax

        nbytes = 0
        dev = None
        if delta is not None:
            parent_token, rows = delta
            with self._lock:
                parent = self._device_bases.get(parent_token)
            if parent is not None and rows:
                from ..ops.binpack import apply_base_delta

                rows_p = _pad_rows(rows)
                nbytes = rows_p.nbytes + len(rows_p) * (4 * 4 + 4 + 4 + 1)
                payload = (rows_p,
                           np.asarray(base[2])[rows_p],
                           np.asarray(base[4])[rows_p],
                           np.asarray(base[5])[rows_p],
                           np.asarray(base[6])[rows_p])
                psh = getattr(parent[2], "sharding", None)
                if (psh is not None and getattr(psh, "mesh", None)
                        is not None and len(psh.device_set) > 1):
                    # Sharded resident parent: place the (replicated)
                    # delta payload on the SAME mesh up front so the
                    # scatter keeps the node axis sharded instead of
                    # gathering it to one device (parallel/mesh.py
                    # pins the payload specs next to base_specs),
                    # then run the explicit shard_map scatter
                    # (parallel/shard.py) — each shard keeps only the
                    # rows landing in its slice, zero collectives.
                    from jax.sharding import NamedSharding

                    from ..parallel.mesh import delta_row_specs
                    from ..parallel.shard import sharded_base_delta

                    payload = jax.device_put(
                        payload,
                        tuple(NamedSharding(psh.mesh, s)
                              for s in delta_row_specs()))
                    util2, bw2, ports2, ok2 = sharded_base_delta(
                        psh.mesh)(parent[2], parent[4], parent[5],
                                  parent[6], *payload)
                else:
                    util2, bw2, ports2, ok2 = apply_base_delta(
                        parent[2], parent[4], parent[5], parent[6],
                        *payload)
                # capacity/sched_capacity/bw_avail/class_ids never
                # change with allocs: share the parent's device arrays.
                # node_ok rides the scatter (node-down deltas mask rows
                # in place, models/resident.py).
                dev = (parent[0], parent[1], util2, parent[3],
                       bw2, ports2, ok2, parent[7])
        delta_derived = dev is not None
        # Delta children of a sharded parent are themselves sharded.
        sharded = delta_derived and len(dev[0].sharding.device_set) > 1
        if dev is None:
            mesh = self._base_mesh(np.shape(base[0])[0])
            if mesh is not None:
                # Big cluster on a multi-chip mesh: the base lives
                # sharded over the node axis (ICI); GSPMD propagates the
                # sharding through the dispatch, lowering the masked
                # argmax to a cross-chip reduction. Specs come from
                # parallel/mesh.py so the cached base's layout can't
                # drift from what the sharded dispatch expects.
                from jax.sharding import NamedSharding

                from ..parallel.mesh import base_specs

                dev = tuple(jax.device_put(
                    tuple(np.asarray(x) for x in base),
                    tuple(NamedSharding(mesh, s) for s in base_specs()),
                ))
                sharded = True
            else:
                # Jitted identity, not device_put: call arguments all
                # ride ONE dispatch, device_put issues one transfer per
                # array.
                from ..ops.binpack import device_resident

                dev = tuple(device_resident(
                    *(np.asarray(x) for x in base)))
        if not delta_derived:
            nbytes = sum(np.asarray(x).nbytes for x in base)
        with self._lock:
            self.bytes_upload += nbytes
            # Counters under the lock: builders of DIFFERENT tokens run
            # concurrently (the pending guard is per token) and += is
            # not atomic across a GIL switch.
            if delta_derived:
                self.base_delta_updates += 1
            else:
                self.base_uploads += 1
            if sharded:
                self.sharded_bases += 1
            while len(self._device_bases) >= DEVICE_BASE_CACHE:
                self._device_bases.popitem(last=False)
            self._device_bases[token] = dev
        return dev, nbytes

    def _claim_fused_delta(self, token, delta):
        """Claim the right to derive `token`'s base INSIDE the compact
        dispatch itself (batched_placement_program_compact_delta): when
        the delta's parent snapshot is still device-cached, the changed
        rows can ride the dispatch's own arguments and the derived base
        comes back with the results — zero extra round-trips.

        Returns (parent_device_base, changed_rows, done_event) on a
        successful claim, else None (caller falls back to
        _device_base). A claim registers `done_event` in
        self._base_pending[token]; the CALLER must cache the derived
        base, clear the pending slot, and set the event — concurrent
        dispatchers on this token wait on it instead of paying a
        duplicate derivation."""
        if delta is None:
            return None
        parent_token, rows = delta
        if not rows:
            return None
        with self._lock:
            if token in self._device_bases or token in self._base_pending:
                # Already resident (or being built): the plain cached
                # path is strictly cheaper than re-deriving.
                return None
            parent = self._device_bases.get(parent_token)
            if parent is None:
                return None
            if len(parent[0].sharding.device_set) > 1:
                # Sharded parents go through _build_device_base, whose
                # apply_base_delta call preserves the mesh layout; the
                # fused program is compiled for the single-chip case.
                return None
            self._device_bases.move_to_end(parent_token)
            done = threading.Event()
            self._base_pending[token] = done
        return parent, rows, done

    def _await_turn(self, first: _Request) -> None:
        """The order of a token's dispatches: the one `first` heads goes
        after every dispatch on its token that stands before it (_rank,
        then the order of the pops) and is queued (the cohort that
        released this dispatch released those too) or popped and not
        yet issued, so that their claims are its starting state; and
        after whichever dispatch holds the token between its own turn
        and its issue. Then it holds the token itself until it has
        published (_publish_claims) or failed (_release). The order is
        total, so no two dispatches wait for each other. Bounded by
        CLAIMS_WAIT_MAX: on expiry the dispatch goes on what has been
        published."""
        token, mine, me = first.token, first.order, id(first)
        deadline = time.monotonic() + CLAIMS_WAIT_MAX
        with self._full:
            while (token in self._going
                   or any(order < mine for other, order
                          in self._unissued.get(token, {}).items()
                          if other != me)
                   or any(q and q[0].token == token
                          and _rank(q[0]) < mine[:2]
                          for q in self._queues.values())):
                left = deadline - time.monotonic()
                if left <= 0:
                    self.claims_wait_expired += 1
                    break
                self._full.wait(left)
            self._going.setdefault(token, me)

    def _publish_claims(self, first: _Request, carry, kind: str,
                        lanes: int, rung: int) -> None:
        """The final carry of the program just ISSUED for the batch that
        `first` heads, kept on the device under the batch's token for
        the dispatches that follow on it. A dispatch that waits behind
        this one goes on at once: what it starts from is this program's
        output on the device, and the device runs the two in order;
        nobody waits for these lanes' results to reach the host."""
        with self._full:
            self._claims.pop(first.token, None)
            while len(self._claims) >= DEVICE_BASE_CACHE:
                self._claims.popitem(last=False)
            self._claims[first.token] = _Carry(carry, kind, lanes, rung)
        self._release(first)

    def _release(self, first: _Request) -> None:
        """The dispatch that `first` heads holds nobody back any more:
        issued, or failed before. Idempotent."""
        with self._full:
            ahead = self._unissued.get(first.token)
            if ahead is not None:
                ahead.pop(id(first), None)
                if not ahead:
                    del self._unissued[first.token]
            if self._going.get(first.token) == id(first):
                del self._going[first.token]
            self._full.notify_all()

    def _take_claims(self, token, kind: str):
        """(what the dispatches before this one on `token` have claimed,
        crossing, handed) or None. `crossing`: the newest carry is of
        the other kind and no dispatch of this kind has started from it
        yet (the hand-over of one mixed batch, counted once).
        `handed`: a plain dispatch starts from a plain dispatch's
        carry."""
        with self._lock:
            claims = self._claims.get(token)
            if claims is None:
                return None
            crossing = claims.kind != kind and not claims.taken
            if crossing:
                claims.taken = True
                self.mixed_batches += 1
            handed = claims.kind == kind == "plain"
            if handed:
                self.plain_handovers += 1
            return claims, crossing, handed

    def _batch_bucket(self, shape_key, first: _Request, n: int,
                      hand_over: bool) -> int:
        """The padded length of the batch axis for `n` plain lanes that
        `first` heads, of the queue `shape_key` (place()). Past the ask
        ladder's first rung, the ladder's step for `n` (_pad_batch;
        _lane_cap holds `n` to the first).
        On the first rung, two rules that keep the step from depending
        on how a burst's lanes fell into pipeline batches:

        - a dispatch of a hand-over (one of several on its token) pads
          to BATCH_BUCKETS[1] at the least, as a gang dispatch does
          (_pad_gang_batch): its padding lanes claim nothing and cost
          eight scan steps each;
        - a dispatch whose step this program shape has never run takes
          the next step up to BATCH_BUCKETS[1] that it has run, if any:
          the tighter program would be compiled for this one batch."""
        from ..models.matrix import ASK_BUCKETS

        step = _pad_batch(n, self.max_batch)
        if _rank(first)[1] > ASK_BUCKETS[0]:
            return step
        if hand_over:
            step = _pad_batch(max(n, BATCH_BUCKETS[1]), self.max_batch)
        with self._lock:
            run = self._buckets_run.setdefault(shape_key[1:], set())
            if (step not in run and step < BATCH_BUCKETS[1]
                    and BATCH_BUCKETS[1] in run):
                step = BATCH_BUCKETS[1]
            run.add(step)
        return step

    def _run_batch(self, batch: List[_Request], config, shape_key) -> None:
        import jax

        from ..chaos import chaos
        from ..ops.binpack import (
            NodeState,
            batched_placement_program,
            batched_placement_program_compact,
            batched_placement_program_overlay,
            check_device_chaos,
            placement_program_jit,
        )

        # The batch is closed: from here to the issue is host stacking
        # and base upload (device.idle.stack).
        closed = time.monotonic()
        if chaos.enabled:
            # 'delay' = a slow device for this dispatch; the adaptive
            # window sees the inflated RTT.
            chaos.fire("batcher.dispatch", batch=len(batch))
        # Device-fault gate (binpack.device): an injected error
        # propagates to every request in the batch via req.error —
        # exactly the blast shape of a real device failure — and the
        # dense schedulers fall back to the host path per eval.
        check_device_chaos()

        if batch[0].topo is not None:
            self._run_gang_batch(batch, config, closed)
            return

        if len(batch) == 1 and batch[0].token is None:
            # Unshared lone request: nothing cacheable, dispatch as-is.
            # Token-carrying lone requests fall through to the overlay
            # path below (B=1): the trickle regime — one eval at a time
            # against a stable snapshot — is exactly where re-uploading
            # the full [N,4] base every dispatch hurt most.
            req = batch[0]
            req.choices, req.scores, _ = self._issue(
                batch, config, closed, placement_program_jit,
                req.full_state(), req.asks, req.key, config)
            return

        # Pad the batch axis up a ladder bucket (see BATCH_BUCKETS):
        # live drains produce ragged sizes — unbucketed, each one would
        # pay a full compile. Padding rows replicate the last request;
        # their outputs are discarded.
        n_live = len(batch)
        token = batch[0].token
        # Shared-base fast path: base cached on device, only the
        # per-eval payloads cross host->device this dispatch.
        shared = token is not None and all(r.token == token for r in batch)
        # A token that several dispatches touch: these lanes start from
        # what the dispatches before them on it claimed, and what they
        # claim is where those after them start (_dispatch set the mark
        # at the pop).
        hand_over = shared and batch[0].hand_over
        pad_to = self._batch_bucket(shape_key, batch[0], n_live, hand_over)
        padded = batch + [batch[-1]] * (pad_to - n_live)

        def handed_on(carry_of):
            """on_issued for a shared-base program, whose lanes' final
            carry is `carry_of(outputs)`."""
            def on_issued(out) -> None:
                self._publish_claims(
                    batch[0], carry_of(out), "plain", n_live,
                    _rank(batch[0])[1])
            return on_issued if hand_over else None
        # Compact overlays: class verdicts + sparse patches + job
        # positions, expanded to the dense [B,N,G] masks ON DEVICE — a
        # few KB per eval instead of ~100KB x G.
        compact = shared and batch[0].compact is not None

        def stacked(*xs):
            return np.stack(xs)

        with trace.annotation("nomad.stack", lanes=n_live):
            keys = np.stack([r.key for r in padded])
            lane_asks = [r.asks for r in padded]
            if hand_over:
                # The carry is handed on: a padding lane must claim
                # nothing (a replica of the last request would place its
                # asks again, after the real lanes, and the gangs would
                # start from a fleet that much fuller than it is).
                idle = batch[-1].asks._replace(
                    active=np.zeros_like(batch[-1].asks.active))
                lane_asks[n_live:] = [idle] * (pad_to - n_live)
            asks = jax.tree.map(stacked, *lane_asks)
            if shared:
                patches = jax.tree.map(
                    stacked, *[r.patch for r in padded])
            if compact:
                per_eval = jax.tree.map(
                    stacked, *[r.compact for r in padded])
            elif shared:
                per_eval = tuple(np.stack([r.overlay[i] for r in padded])
                                 for i in range(3))
            else:
                per_eval = jax.tree.map(
                    stacked, *[r.full_state() for r in padded])
        payload = (sum(x.nbytes for x in asks) + keys.nbytes
                   + sum(x.nbytes for x in per_eval))
        if shared:
            payload += sum(x.nbytes for x in patches)
        # Stacked: now the dispatch waits for its turn on the token and
        # reads what those before it claimed.
        claims = None
        if hand_over:
            self._await_turn(batch[0])
            claims = self._take_claims(token, "plain")
        if compact:
            # The program that fuses the base's delta in derives the
            # base: it can only be the first dispatch on its token, and
            # hands its lanes' carry on after the derived columns.
            fused = (None if claims is not None
                     else self._claim_fused_delta(token, batch[0].delta))
            if fused is not None:
                # Base delta FUSED into this dispatch: the changed rows
                # ride the call, the derived base comes back as device
                # residents — zero extra round-trips.
                from ..ops.binpack import (
                    batched_placement_program_compact_delta,
                )

                parent, rows, done = fused

                def publish() -> None:
                    with self._lock:
                        self._base_pending.pop(token, None)
                    done.set()

                hand_on = handed_on(lambda out: out[6:9])

                def cache_derived(out) -> None:
                    # As soon as the program is issued, before its
                    # results are pulled: dispatchers waiting on this
                    # token need the derived base, not the placements.
                    dev = (parent[0], parent[1], out[2], parent[3],
                           out[3], out[4], out[5], parent[7])
                    with self._lock:
                        self.base_delta_updates += 1
                        while len(self._device_bases) >= DEVICE_BASE_CACHE:
                            self._device_bases.popitem(last=False)
                        self._device_bases[token] = dev
                    publish()
                    if hand_on is not None:
                        hand_on(out)

                try:
                    rows_p = _pad_rows(rows)
                    hb = batch[0].base
                    row_payload = tuple(np.asarray(hb[i])[rows_p]
                                        for i in (2, 4, 5, 6))
                    payload += rows_p.nbytes + sum(
                        x.nbytes for x in row_payload)
                    choices, scores, times = self._issue(
                        batch, config, closed,
                        batched_placement_program_compact_delta,
                        *parent[:8], rows_p, *row_payload, per_eval,
                        patches, asks, keys, config,
                        on_issued=cache_derived)
                finally:
                    publish()
            else:
                dev = self._claimed_base(batch, claims)
                choices, scores, times = self._issue(
                    batch, config, closed,
                    batched_placement_program_compact, *dev[:8],
                    per_eval, patches, asks, keys, config,
                    on_issued=handed_on(lambda out: out[2]))
        elif shared:
            dev = self._claimed_base(batch, claims)
            state = NodeState(
                capacity=dev[0], sched_capacity=dev[1], util=dev[2],
                bw_avail=dev[3], bw_used=dev[4], ports_free=dev[5],
                job_count=per_eval[0], tg_count=per_eval[1],
                feasible=per_eval[2], node_ok=dev[6],
            )
            choices, scores, times = self._issue(
                batch, config, closed, batched_placement_program_overlay,
                state, asks, keys, config, patches,
                on_issued=handed_on(lambda out: out[2]))
        else:
            choices, scores, times = self._issue(
                batch, config, closed, batched_placement_program,
                per_eval, asks, keys, config)
        self._count_dispatch(times, payload, compact, shared)
        if claims is not None:
            self._record_claims_carry(batch, claims, "plain", times[0],
                                      _rank(batch[0])[1])
        for i, req in enumerate(batch):
            req.choices = choices[i]
            req.scores = scores[i]

    def _claimed_base(self, batch: List[_Request], claims) -> tuple:
        """The resident base of the batch's token, its utilisation,
        bandwidth and free-port columns replaced by what the dispatches
        before this one on that token have claimed (_take_claims), if
        any."""
        first = batch[0]
        dev, _ = self._device_base(first.token, first.base, first.delta)
        if claims is None:
            return dev
        carry = claims[0]
        return (dev[0], dev[1], carry.util, dev[3], carry.bw_used,
                carry.ports_free, *dev[6:])

    def _record_claims_carry(self, batch: List[_Request], taken,
                             kind: str, issued: float, rung: int) -> None:
        """One sample a hand-over (`taken` is _take_claims'), on the
        first traced request of the dispatch that took the claims: from
        the earlier program's issue (or that request's arrival, if
        later: the span lies inside its device.dispatch) to this
        dispatch's issue. The span batch.claims where a plain and a
        gang dispatch cross, batch.handover where a plain dispatch
        started from a plain one's carry; both say which kinds met and
        on which rungs (`rung`: this dispatch's ask axis, a gang
        dispatch's member axis)."""
        claims, crossing, handed = taken
        if crossing:
            from ..gang import note_mixed_batch

            note_mixed_batch()
            stage = trace.STAGE_BATCH_CLAIMS
            ann = {f"{claims.kind}_lanes": claims.lanes,
                   f"{kind}_lanes": len(batch)}
        elif handed:
            from ..utils import metrics

            metrics.incr_counter(("placement_batcher", "plain_handovers"))
            stage = trace.STAGE_BATCH_HANDOVER
            ann = {"from_lanes": claims.lanes, "lanes": len(batch)}
        else:
            return
        req = next((r for r in batch if r.span), None)
        if req is not None:
            ann.update(kind=f"{claims.kind}>{kind}", from_rung=claims.rung,
                       rung=rung)
            trace.record_span(
                req.span[0], stage,
                min(max(claims.issued_at, req.arrived_at), issued), issued,
                ann=ann, trace_id=req.span[1])

    def _count_dispatch(self, times, payload: int, compact: bool,
                        shared: bool) -> None:
        """One dispatch's cost into the cumulative breakdown."""
        t1, t2, t3 = times
        with self._lock:
            self.t_issue += t2 - t1
            self.t_sync += t3 - t2
            self.bytes_overlay += payload
            # Path counters under the lock: dispatchers of different
            # shape keys run concurrently and += is not atomic across a
            # GIL switch.
            self.compact_dispatches += compact
            self.overlay_dispatches += shared
            sync = t3 - t2
            self._sync_ema = (sync if self._sync_ema == 0.0
                              else 0.7 * self._sync_ema + 0.3 * sync)

    def _device_topology(self, key, column):
        """The device's copy of one topology id column
        (models/topology.py device_key), LRU-cached: it crosses
        host->device once a rebuild of the tensor, not once a base
        token. Two dispatchers that miss at once both upload ([N]
        int32); the second insert wins."""
        with self._lock:
            dev = self._device_topos.get(key)
            if dev is not None:
                self._device_topos.move_to_end(key)
                return dev
        import jax

        column = np.asarray(column, np.int32)
        dev = jax.device_put(column)
        with self._lock:
            self.topo_uploads += 1
            self.bytes_upload += column.nbytes
            while len(self._device_topos) >= DEVICE_TOPO_CACHE:
                self._device_topos.popitem(last=False)
            self._device_topos[key] = dev
        return dev

    def _run_gang_batch(self, batch: List[_Request], config,
                        closed: float) -> None:
        """One dispatch of the gangs in `batch`, in the queue's order
        (ops/gang.py batched_gang_placement_program): the lanes stack
        along the batch axis, each member mask padded to the largest
        gang's bucket (_pad_gang_members), the batch padded up a bucket
        (_pad_gang_batch) with lanes that have no member active (they
        place and claim nothing); the
        node arrays are the resident base of the batch's token with the
        topology column beside it, so only the lanes cross host->device.
        A tokenless request came alone (place_gang) and its node arrays
        ride the call."""
        import jax

        from ..gang import note_gang_dispatch
        from ..ops.gang import GangBase, batched_gang_placement_program_jit

        n_live = len(batch)
        pad_to = _pad_gang_batch(n_live, self.max_batch)
        first = batch[0]
        k_pad = _pad_gang_members(max(len(r.asks.active) for r in batch))

        def stacked(*xs):
            return np.stack(xs)

        def members(active):
            out = np.zeros(k_pad, bool)
            out[:len(active)] = active
            return out

        with trace.annotation("nomad.stack", lanes=n_live):
            idle = first.asks._replace(active=np.zeros(k_pad, bool))
            lanes = jax.tree.map(
                stacked, *([r.asks._replace(active=members(r.asks.active))
                            for r in batch] + [idle] * (pad_to - n_live)))
            keys = np.stack([r.key for r in batch]
                            + [first.key] * (pad_to - n_live))
        payload = sum(x.nbytes for x in lanes) + keys.nbytes
        topo_key, topo_ids = first.topo
        claims = None
        if first.token is None:
            f32 = np.float32
            node = tuple(np.asarray(x, f32) for x in first.base[:6]) \
                + (np.asarray(first.base[6], bool),)
            topo = np.asarray(topo_ids, np.int32)
            payload += sum(x.nbytes for x in node) + topo.nbytes
        else:
            # The plain lanes of this token go first, and what the
            # dispatches before this one claimed is where these lanes
            # start.
            self._await_turn(first)
            claims = self._take_claims(first.token, "gang")
            dev = self._claimed_base(batch, claims)
            node = dev[:7]
            # Beside a base sharded over a mesh the column rides the
            # call uncommitted: jit lays it out with the base.
            topo = (np.asarray(topo_ids, np.int32)
                    if len(dev[0].sharding.device_set) > 1
                    else self._device_topology(topo_key, topo_ids))
        issued = []

        def on_issued(out) -> None:
            issued.append(out)
            if first.token is not None:
                self._publish_claims(first, out[3:6], "gang", n_live, k_pad)

        with trace.annotation("nomad.gang", gangs=n_live,
                              mode=config.mode):
            choices, scores, times = self._issue(
                batch, config, closed, batched_gang_placement_program_jit,
                GangBase(*node, topo), lanes, keys, config,
                on_issued=on_issued)
            info = np.asarray(issued[0][2])
        t_info = time.monotonic()
        self._count_dispatch(times, payload, False, first.token is not None)
        if claims is not None:
            self._record_claims_carry(batch, claims, "gang", times[0],
                                      k_pad)
        note_gang_dispatch(n_live, int(info[:n_live, 1].sum()))
        for i, req in enumerate(batch):
            req.choices = choices[i]
            req.scores = scores[i]
            req.info = (int(info[i, 0]), bool(info[i, 1]))
            if req.span:
                # Issue to the gang's slice and claim readings on the
                # host: device.solve, then the pull of `info`.
                trace.record_span(
                    req.span[0], trace.STAGE_GANG_SOLVE, times[0], t_info,
                    ann={"gangs": n_live}, trace_id=req.span[1])

    def _issue(self, batch: List[_Request], config, closed: float, program,
               *args, on_issued=None):
        """Issue one placement program and pull its placements back to
        the host: (choices, scores, (t_issue, t_issued, t_results)), all
        on time.monotonic(). `on_issued(outputs)` runs between the issue
        and the pull.

        The interval from issue to results is when the device has this
        batch's work; everything the measurement knows of the device
        from the host's side hangs on it: the `device.solve` span, the
        `nomad.dispatch` annotation, and the three `device.idle.*`
        parts of the gap since the last such interval ended
        (_idle_parts; nothing while another program is in flight)."""
        first_arrival = min(r.arrived_at for r in batch)
        with self._lock:
            t1 = time.monotonic()
            self._issued += 1
            ordinal = self._issued
            idle = None
            if self._in_flight == 0 and self._busy_until:
                idle = _idle_parts(self._busy_until, first_arrival,
                                   closed, t1)
            self._in_flight += 1
        try:
            with trace.annotation(
                    "nomad.dispatch", ordinal=ordinal, lanes=len(batch),
                    program=getattr(program, "__name__", "program")):
                out = program(*args)
                t2 = time.monotonic()
                if on_issued is not None:
                    on_issued(out)
                choices = np.asarray(out[0])
                scores = np.asarray(out[1])
        finally:
            with self._lock:
                t3 = time.monotonic()
                self._in_flight -= 1
                self._busy_until = t3
        if idle is not None:
            recorder = trace.get_recorder()
            for stage, seconds in zip(trace.DEVICE_IDLE_STAGES, idle):
                recorder.observe_stage(stage, seconds * 1000.0)
        self._record_solve(batch, config, t1, t3)
        return choices, scores, (t1, t2, t3)

    def _record_solve(self, batch, config, t1: float, t3: float) -> None:
        """device.solve spans for the requests that carry a trace
        identity: the jitted solve's issue + device sync window,
        kernel-annotated — the slice of device.dispatch that IS the
        placement kernel (batch-wait and host stacking excluded). Both
        ends were taken on time.monotonic(), the recorder's clock, where
        the issue happened and where the results arrived: the same
        instants the device.idle.* parts and nomad.dispatch use."""
        if not any(r.span for r in batch):
            return
        ann = {"kernel": getattr(config, "kernel", "greedy"),
               "batch": len(batch)}
        for req in batch:
            if req.span:
                trace.record_span(
                    req.span[0], trace.STAGE_DEVICE_SOLVE, t1, t3,
                    ann=ann, trace_id=req.span[1])

    def _accumulate(self, shape_key, window: float) -> str:
        """Hold the shape's queue open until it may dispatch, and say
        what released it. Sleeps on the condition that place() and
        settle() signal — no lock-polling on the scheduler hot path.

        - "full": max_batch requests are queued, nothing more can join.
        - "cohort": the queue holds requests of pipeline batches and
          every cohort among them is complete (all units arrived or
          settled), and so is every other cohort open at that moment.
          Never the clock: the pipeline has counted these requests, and
          shipping a fragment while the rest is provably coming wastes
          a round-trip, as waiting on after the last has come wastes
          the wait. A batch spread over several shape queues is waited
          for as a whole by each: a member's shape is not known before
          it arrives. The OTHER batch in flight is waited for because
          the in-batch pre-resolution sees only its own dispatch: the
          pipeline keeps two batches in flight, and two that go
          together either share a snapshot and ride one dispatch, or
          at least commit together, so that the next two plan on both.
          Going as soon as the own batch was complete was measured
          (PERF.md section 6, PR 31): at saturation the batches fall
          out of step, every batch then overlaps its predecessor's
          uncommitted plans AND its successor's, and plan conflicts
          double. An open cohort is a batch whose prologue is done,
          or the placeholder of one that forms beside a batch in
          flight and is sure to launch within the pipeline's window
          (dispatch/pipeline.py _announce_forming; PR 39): without it
          the two met only while a prologue took as long as that
          window.
        - "cap": as "cohort", but a request among them waited
          COHORT_WAIT_MAX out for a member that did not come, and its
          cohort was closed without it.
        - "window": no request in the queue carries a cohort, and
          `window` seconds have passed."""
        deadline = time.monotonic() + window
        expired = False  # this wait ran into a cohort's cap
        with self._full:
            while True:
                q = self._queues.get(shape_key, ())
                if len(q) >= self.max_batch:
                    return "full"
                now = time.monotonic()
                cohorts = {r.unit.cohort for r in q if r.unit is not None}
                if cohorts:
                    cohorts |= self._cohorts
                waiting = [c for c in cohorts if c.pending > 0]
                if waiting:
                    until = min(c.deadline for c in waiting)
                    if now >= until:
                        expired = True
                        for c in waiting:
                            if now >= c.deadline:
                                c.pending = 0
                                c.capped = True
                                self._cohorts.discard(c)
                        # Its other shape queues wait on it too.
                        self._full.notify_all()
                        continue
                elif cohorts:
                    # "cap" for a request that waited a capped cohort
                    # out, in whichever of its queues; a member that
                    # comes after the cap finds its cohort complete.
                    waited_out = expired or any(
                        r.unit.cohort.capped
                        and r.arrived_at < r.unit.cohort.deadline
                        for r in q if r.unit is not None)
                    return "cap" if waited_out else "cohort"
                elif now >= deadline:
                    return "window"
                else:
                    until = deadline
                self._full.wait(until - now)

    def _spawn_dispatcher(self, shape_key, config) -> None:
        t = threading.Thread(
            target=self._dispatch, args=(shape_key, config, False),
            daemon=True, name="placement-batch")
        try:
            t.start()
        except (RuntimeError, OSError):
            # OS thread pressure. Un-claim the dispatcher slot the
            # caller counted for us; the parked requesters' bounded
            # wait in place() observes the ownerless queue and one of
            # them claims dispatchership inline (self-rescue) — the
            # work is late, never lost.
            with self._lock:
                remaining = self._dispatchers.get(shape_key, 1) - 1
                if remaining > 0:
                    self._dispatchers[shape_key] = remaining
                else:
                    self._dispatchers.pop(shape_key, None)
            self.logger.warning(
                "placement dispatcher thread failed to spawn; parked "
                "requesters will self-rescue", exc_info=True)

    def _dispatch(self, shape_key, config, wait_window: bool) -> None:
        """Everything — including imports and the queue pop — runs
        under the error handler: a dispatcher that dies without setting
        its requests' events (e.g. a TPU runtime init failure) would
        wedge every worker on that shape forever.

        The caller has already counted us in self._dispatchers; the
        finally block counts us out and respawns if work remains."""
        batch: List[_Request] = []
        popped = False
        handing = None  # the first request of a batch in a hand-over
        try:
            with self._lock:
                sync_ema = self._sync_ema
            # The timed window, for a queue whose requests carry no
            # cohort (_accumulate; one that does closes on its cohorts
            # and never reads it). Idle batcher: give concurrent
            # workers a moment to pile on; the window grows with the
            # measured round-trip (see WINDOW_S note). Post-dispatch
            # respawns use a shorter one — most of their batch
            # accumulated during the in-flight device call, the short
            # wait only catches stragglers mid-host-phase — but not a
            # fixed one: when the round-trip is long (sync_ema ~100ms+)
            # a 5ms straggler window ships near-empty follow-up
            # dispatches, and each ragged size is its own XLA program.
            if not wait_window:
                window = max(RESPAWN_WINDOW_S,
                             min(WINDOW_MAX_S, sync_ema * 0.5))
            elif self.window > 0:
                window = min(WINDOW_MAX_S, max(self.window, sync_ema * 0.5))
            else:
                window = 0.0
            closed_by = self._accumulate(shape_key, window)
            with self._lock:
                waiting = self._queues.pop(shape_key, [])
                cap = (_lane_cap(waiting[0], self.max_batch)
                       if waiting else self.max_batch)
                batch = waiting[:cap]
                leftover = waiting[cap:]
                if leftover:
                    # Overflow rides the next dispatch; dropping it
                    # would wedge those workers in event.wait().
                    self._queues[shape_key] = leftover
                popped = True
                if batch and batch[0].token is not None:
                    first = batch[0]
                    token = first.token
                    if (first.topo is not None or token in self._claims
                            or token in self._unissued
                            or any(q and q[0].token == token
                                   for q in self._queues.values())):
                        # A gang dispatch, or a plain one that is one
                        # of several on its token: an earlier one has
                        # published its claims, one is popped and not
                        # yet issued, or requests on the token are
                        # queued (another queue of the batch, whose
                        # requests are all queued by now, or this
                        # queue's own lanes past the cap). It takes its
                        # place among them, and those popped from now
                        # to its issue take part.
                        handing = first
                        first.hand_over = True
                        self._popped += 1
                        first.order = _rank(first) + (self._popped,)
                        self._unissued.setdefault(
                            first.token, {})[id(first)] = first.order
                if batch:
                    self._closed_by[closed_by] += 1
                    for req in batch:
                        if req.unit is not None:
                            req.unit.closed_by = closed_by
                # Overlap: if work is already waiting, start the next
                # dispatcher NOW so its accumulation + transfer hides
                # behind our device round-trip.
                overlap = (
                    bool(self._queues.get(shape_key))
                    and self._dispatchers.get(shape_key, 0) < MAX_INFLIGHT
                )
                if overlap:
                    self._dispatchers[shape_key] += 1
            if overlap:
                self._spawn_dispatcher(shape_key, config)
            if not batch:
                return
            self._run_batch(batch, config, shape_key)
            with self._lock:
                # Under the lock: dispatchers of different shape keys
                # race these (+= is not atomic across a GIL switch).
                self.dispatches += 1
                self.batched_requests += len(batch)
        except BaseException as e:  # noqa: BLE001 - propagate per request
            with self._lock:
                # Died before the pop: the queued requests were OUR
                # responsibility (no overlap dispatcher was spawned for
                # them) — fail them too rather than leave them wedged.
                if not popped:
                    batch = self._queues.pop(shape_key, [])
            for req in batch:
                req.error = e
        finally:
            ready = time.monotonic()
            for req in batch:
                req.ready_at = ready
                req.event.set()
            if handing is not None:
                # Had it failed before its issue, the dispatches behind
                # it would still be waiting.
                self._release(handing)
            # Count ourselves out; anything still queued with no live
            # dispatcher gets a fresh one. Zero-count keys are removed —
            # every new cluster-base token mints a new shape key, so a
            # long-running server would otherwise accrete dead entries.
            with self._full:
                remaining = self._dispatchers.get(shape_key, 1) - 1
                spawn = bool(self._queues.get(shape_key)) and remaining == 0
                if spawn:
                    remaining = 1
                if remaining > 0:
                    self._dispatchers[shape_key] = remaining
                else:
                    self._dispatchers.pop(shape_key, None)
            if spawn:
                self._spawn_dispatcher(shape_key, config)

    def shard_occupancy(self) -> list:
        """Per-shard [{device, rows, bytes}] of the newest resident
        base (parallel/shard.py per_shard_occupancy). Snapshot under
        the lock, read layouts outside it (pure metadata)."""
        with self._lock:
            dev = next(reversed(self._device_bases.values()), None) \
                if self._device_bases else None
        if dev is None:
            return []
        from ..parallel.shard import per_shard_occupancy

        return per_shard_occupancy(dev)

    def stats(self) -> dict:
        from ..ops.binpack import jit_cache_size

        # Read OUTSIDE the lock: jax's cache introspection is not ours
        # to serialize, and it never tears (a single int).
        jit_programs = jit_cache_size()
        with self._lock:
            # Under the lock: a reader racing a dispatcher's update
            # would otherwise tear the breakdown (e.g. dispatches
            # bumped but t_sync not yet) — the per-dispatch divisions
            # downstream want a consistent cut.
            return {
                "dispatches": self.dispatches,
                "batched_requests": self.batched_requests,
                "base_uploads": self.base_uploads,
                "base_delta_updates": self.base_delta_updates,
                "overlay_dispatches": self.overlay_dispatches,
                "compact_dispatches": self.compact_dispatches,
                "sharded_bases": self.sharded_bases,
                "unsharded_fallbacks": self.unsharded_fallbacks,
                # Cost breakdown (cumulative; divide by `dispatches`
                # for per-dispatch): microseconds so the config-6
                # delta print stays integral.
                "issue_us": int(self.t_issue * 1e6),
                "sync_us": int(self.t_sync * 1e6),
                "payload_bytes": int(self.bytes_overlay),
                "upload_bytes": int(self.bytes_upload),
                # Topology id columns made resident beside a base
                # (_device_topology): rises only when the node set
                # changed under a gang dispatch.
                "topo_uploads": self.topo_uploads,
                # Compiled XLA programs this process holds (all the
                # placement entry points): steady state is FLAT — a
                # climb under load is a recompile storm (the benchmark's
                # window_compiles reads it).
                "jit_cache_size": jit_programs,
                # How each dispatch was released (_accumulate), and the
                # cohorts with a unit still out: a served request always
                # has a cohort, so closed_by_window counts only lone
                # dense evals and direct callers, and closed_by_cap a
                # hand-over that lost a unit.
                **{f"closed_by_{k}": v
                   for k, v in self._closed_by.items()},
                "open_cohorts": len(self._cohorts),
                # Hand-overs on a token: dispatches that started from
                # the claims of the other kind's dispatch, plain
                # dispatches that started from a plain one's, the shape
                # queues pipeline batches' requests went to (beside the
                # pipeline's `batches`), and dispatches that waited
                # CLAIMS_WAIT_MAX out and went without.
                "mixed_batches": self.mixed_batches,
                "plain_handovers": self.plain_handovers,
                "token_queues": self.token_queues,
                "claims_wait_expired": self.claims_wait_expired,
            }


_global: Optional[PlacementBatcher] = None
_global_lock = threading.Lock()


def get_batcher() -> PlacementBatcher:
    global _global
    with _global_lock:
        if _global is None:
            _global = PlacementBatcher()
        return _global
