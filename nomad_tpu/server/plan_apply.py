"""Plan applier: the leader's serialization point for optimistic
concurrency.

Reference: nomad/plan_apply.go:41 — a long-lived leader loop that
dequeues plans by priority, verifies each node's placements against the
latest state, partially commits what fits, and hands workers a
RefreshIndex when their snapshot went stale. Pipelining: the next plans
are evaluated against an optimistic snapshot while a commit is in
flight (plan_apply.go:19-39).

The unit of work is the GROUP of plans that are in the queue when the
loop looks (PlanQueue.dequeue_group): verified plan by plan, in queue
order, on this thread against one overlay, and committed in one raft
entry. The reference fans the per-node checks out over a pool
(plan_apply_pool.go:18); under one GIL nothing of that runs in
parallel and every hand-off waits for the lock, so the checks run
inline.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

from .. import trace
from ..structs import (
    Allocation,
    NetworkResource,
    Node,
    Plan,
    PlanResult,
    allocs_fit,
    consts,
    usage_fits,
)
from ..utils import metrics
from .fsm import ALLOC_UPDATE
from .plan_queue import PendingPlan, PlanQueue


def evaluate_node_preemptions(snapshot, plan: Plan, node_id: str) -> bool:
    """Per-victim verification of a preemption leg: every victim must
    still exist, be non-terminal, and be STRICTLY lower-priority than
    the plan. A victim that completed, died, or was replaced underneath
    the scheduler (chaos site preempt.victim_lost models the same
    shape from the other side: a victim whose freed capacity was
    counted but whose eviction never got staged) rejects the node —
    the freed-capacity discount the placement relied on is void, so
    the whole node replans on fresh state."""
    victims = plan.node_preemptions.get(node_id)
    if not victims:
        return True
    from ..migrate import victim_priority

    # The node's LIVE allocs through whichever view we were handed —
    # the optimistic overlay already hides in-flight evictions, so a
    # victim another pipelined plan is stopping verifies as lost here.
    live = {a.id: a
            for a in snapshot.allocs_by_node_terminal(node_id, False)}
    for victim in victims:
        stored = live.get(victim.id)
        if stored is None or stored.terminal_status():
            return False
        if victim_priority(stored) >= plan.priority:
            return False
    return True


def evaluate_node_plan(snapshot, plan: Plan, node_id: str) -> bool:
    """Whether the plan's changes to one node can be applied against the
    given state (plan_apply.go:318 evaluateNodePlan), by the full list:
    every allocation the view holds on the node is read and added up.
    The applier itself verifies from a NodeSummary
    (PlanApplier._verify_node); this is the rule that is held equal to,
    plan for plan (tests/test_plan_apply_summary.py), what a node the
    store no longer holds falls back on, and what kernels/differential.py
    judges placements by."""
    if not evaluate_node_preemptions(snapshot, plan, node_id):
        return False
    if not plan.node_allocation.get(node_id):
        return True  # evictions only: always safe

    node = snapshot.node_by_id(node_id)
    if node is None:
        return False
    if node.status != consts.NODE_STATUS_READY or node.drain:
        return False

    from ..scheduler.util import proposed_allocs_for_node

    proposed = proposed_allocs_for_node(snapshot, plan, node_id)
    fit, _, _ = allocs_fit(node, proposed)
    return fit


# What one allocation adds to its node's sums: cpu, memory, disk, iops
# and the networks NetworkIndex.add_allocs would reserve for it.
Share = Tuple[int, int, int, int, Tuple[NetworkResource, ...]]


def _share(alloc: Allocation) -> Share:
    """The accounting of allocs_fit and NetworkIndex.add_allocs for one
    allocation: its combined resources where it carries them, else the
    shared ask plus each task's; of each task the first network."""
    tasks = alloc.task_resources
    res = alloc.resources
    nets: Tuple[NetworkResource, ...] = ()
    if res is not None:
        cpu, mem, disk, iops = res.cpu, res.memory_mb, res.disk_mb, res.iops
        for res in tasks.values():
            if res.networks:
                nets += (res.networks[0],)
    elif tasks:
        res = alloc.shared_resources
        if res is not None:
            cpu, mem, disk, iops = (res.cpu, res.memory_mb, res.disk_mb,
                                    res.iops)
        else:
            cpu = mem = disk = iops = 0
        for res in tasks.values():
            cpu += res.cpu
            mem += res.memory_mb
            disk += res.disk_mb
            iops += res.iops
            if res.networks:
                nets += (res.networks[0],)
    else:
        raise ValueError(f"allocation {alloc.id!r} has no resources set")
    return cpu, mem, disk, iops, nets


class _Over:
    """Writes over a dict that must stay as it is: a plan's ports are
    tried on a node's counts without touching them."""

    __slots__ = ("base", "own")

    def __init__(self, base: dict):
        self.base = base
        self.own: dict = {}

    def get(self, key, default):
        value = self.own.get(key)
        return self.base.get(key, default) if value is None else value

    def __setitem__(self, key, value) -> None:
        self.own[key] = value

    def __delitem__(self, key) -> None:
        self.own[key] = 0


def _reserve(ports, bandwidth, net: NetworkResource, sign: int) -> int:
    """NetworkIndex.add_reserved as counts, so that it can be taken
    back: `ports` counts the holders of each (ip, port), `bandwidth`
    sums by device. Returns the change in the node's collisions, which
    are every holder of a port beyond its first and every port out of
    range; at such a port the walk ends with the bandwidth not added,
    as add_reserved's does."""
    collisions = 0
    ip = net.ip
    for port in (*net.reserved_ports, *net.dynamic_ports):
        value = port.value
        if value < 0 or value >= consts.MAX_VALID_PORT:
            return collisions + sign
        key = (ip, value)
        held = ports.get(key, 0)
        if sign > 0:
            if held:
                collisions += 1
            ports[key] = held + 1
        elif held > 1:
            collisions -= 1
            ports[key] = held - 1
        elif held:
            del ports[key]
    bandwidth[net.device] = bandwidth.get(net.device, 0) + sign * net.mbits
    return collisions


class NodeSummary:
    """What allocs_fit derives from the allocations standing on one
    node, kept so that a plan is verified against it and not against
    the allocations themselves: the four sums (the node's reserved
    included), bandwidth used by device, the holders of every port by
    IP, and each covered allocation's share by id, so that a stop, a
    preemption or an in-place update takes exactly that share out.
    Integers throughout: a restatement of the list, not an estimate.
    Good for one node ROW (the store replaces a node's row on every
    write to it) and for the allocations NodeSummaries vouches for."""

    __slots__ = ("node", "shares", "cpu", "memory_mb", "disk_mb", "iops",
                 "avail", "bandwidth", "ports", "collisions")

    def __init__(self, node: Node, standing: List[Allocation]):
        self.node = node
        self.shares: Dict[str, Share] = {}
        self.cpu = self.memory_mb = self.disk_mb = self.iops = 0
        self.avail = {n.device: n.mbits for n in node.resources.networks
                      if n.device}
        self.bandwidth: Dict[str, int] = {}
        self.ports: Dict[Tuple[str, int], int] = {}
        self.collisions = 0
        reserved = node.reserved
        if reserved:
            self.cpu = reserved.cpu
            self.memory_mb = reserved.memory_mb
            self.disk_mb = reserved.disk_mb
            self.iops = reserved.iops
            for net in reserved.networks:
                self.collisions += _reserve(self.ports, self.bandwidth,
                                            net, 1)
        # The view lists an allocation once: no share to give back.
        shares = self.shares
        cpu = mem = disk = iops = 0
        for alloc in standing:
            share = shares[alloc.id] = _share(alloc)
            cpu += share[0]
            mem += share[1]
            disk += share[2]
            iops += share[3]
            for net in share[4]:
                self.collisions += _reserve(self.ports, self.bandwidth,
                                            net, 1)
        self.cpu += cpu
        self.memory_mb += mem
        self.disk_mb += disk
        self.iops += iops

    def take(self, alloc: Allocation) -> None:
        """Count one allocation in; one the summary covers under the
        same id (an in-place update) gives its share back first."""
        share = _share(alloc)
        if alloc.id in self.shares:
            self.drop(alloc.id)
        self.shares[alloc.id] = share
        cpu, mem, disk, iops, nets = share
        self.cpu += cpu
        self.memory_mb += mem
        self.disk_mb += disk
        self.iops += iops
        for net in nets:
            self.collisions += _reserve(self.ports, self.bandwidth, net, 1)

    def drop(self, alloc_id: str) -> None:
        share = self.shares.pop(alloc_id, None)
        if share is None:
            return
        cpu, mem, disk, iops, nets = share
        self.cpu -= cpu
        self.memory_mb -= mem
        self.disk_mb -= disk
        self.iops -= iops
        for net in nets:
            self.collisions += _reserve(self.ports, self.bandwidth, net, -1)

    def fits(self, removed: List[Allocation],
             placed: List[Allocation]) -> Tuple[bool, str]:
        """allocs_fit over what stands, less `removed` (the plan's
        stops and victims here), with `placed` over it by id: the
        verdict and the dimension exhausted. Changes nothing."""
        shares = self.shares
        leaving = {a.id: shares[a.id] for a in removed if a.id in shares}
        if len(placed) > 1:
            placed = list({a.id: a for a in placed}.values())
        for alloc in placed:
            if alloc.id in shares:
                leaving[alloc.id] = shares[alloc.id]
        cpu, mem, disk, iops = (self.cpu, self.memory_mb, self.disk_mb,
                                self.iops)
        moves: List[Tuple[NetworkResource, int]] = []
        for c, m, d, i, nets in leaving.values():
            cpu -= c
            mem -= m
            disk -= d
            iops -= i
            moves.extend((net, -1) for net in nets)
        for alloc in placed:
            c, m, d, i, nets = _share(alloc)
            cpu += c
            mem += m
            disk += d
            iops += i
            moves.extend((net, 1) for net in nets)
        collisions, bandwidth = self.collisions, self.bandwidth
        if moves:
            ports, bandwidth = _Over(self.ports), dict(bandwidth)
            for net, sign in moves:
                collisions += _reserve(ports, bandwidth, net, sign)
        return usage_fits(self.node.resources, cpu, mem, disk, iops,
                          collisions > 0, bandwidth, self.avail)


# The most nodes the applier keeps a summary of; the one used longest
# ago goes first. Above every cell's fleet (12,583 machines the
# largest), so there the bound never acts; a node without a summary is
# read from the store as every node was before. A constant: what it
# bounds is memory (an entry an allocation standing), nobody's tuning.
MAX_SUMMARIES = 1 << 14


class NodeSummaries:
    """The applier's node summaries, carried from plan to plan and from
    group to group: node id -> NodeSummary, each the equal of what the
    applier's view (OptimisticSnapshot: the base plus what was accepted
    and has not landed) lists on that node.

    Three things keep that true. What the applier accepts goes in and
    out as it is accepted (`accept`). What anybody ELSE wrote is found
    when the view moves to a new base (`rebase`), in the store's
    journal of allocation writes (state/store.py allocs_changed_since,
    the one models/matrix.py delta_update reads): the summary of every
    node named by a changed allocation that no commit of the applier
    wrote last is dropped; a journal that does not reach back to the
    last base, another store, or a table whose size the journal does
    not explain (a collected allocation leaves no entry) drops them
    all; a placement of the applier's own that the new base lacks
    (created and collected between two bases) drops its node. And a
    node's own row is compared when its summary is asked for (`of`). A
    failed commit drops them all as well: they held what never landed.
    Touched on the applier's thread alone."""

    def __init__(self):
        self._by_node: "OrderedDict[str, NodeSummary]" = OrderedDict()
        # The base they are good for: whose store, its allocs-table
        # index and the table's size there.
        self._store_id = ""
        self._index = 0
        self._count = 0
        # Raft indexes of the applier's own commits since that base.
        self._own: set = set()
        # Nodes whose summary the next base outdates: see `accept`.
        self._outdated: set = set()
        # Placements handed to a commit since that base, id -> node:
        # the next base must hold every one of them (`rebase`).
        self._committing: Dict[str, str] = {}
        self.hits = 0  # verifications served from a summary carried
        self.builds = 0  # summaries built from the store's index
        self.dropped = 0  # summaries thrown away, whatever the cause
        self.standing = 0  # allocations read to build them

    def __len__(self) -> int:
        return len(self._by_node)

    def clear(self) -> None:
        self.dropped += len(self._by_node)
        self._by_node.clear()
        self._outdated.clear()
        self._committing.clear()

    def _drop(self, node_id: str) -> None:
        if self._by_node.pop(node_id, None) is not None:
            self.dropped += 1

    def seal(self, results: List[PlanResult]) -> None:
        """These accepted results go into a raft entry now."""
        for result in results:
            for node_id, allocs in result.node_allocation.items():
                for alloc in allocs:
                    self._committing[alloc.id] = node_id

    def note_commit(self, index: int) -> None:
        self._own.add(index)

    def rebase(self, base) -> None:
        """Move to a new base: keep what the journal vouches for."""
        index, count = base.index("allocs"), base.alloc_count()
        if self._by_node:
            changed = (base.allocs_changed_since(self._index)
                       if base.store_id == self._store_id else None)
            if changed is None or count != self._count + sum(
                    1 for a in changed if a.create_index > self._index):
                self.clear()
            else:
                for node_id in self._outdated:
                    self._drop(node_id)
                # The table's size cannot show an allocation created
                # AND collected since the last base, and the journal
                # skips it; the summaries can hold one only if the
                # applier placed it, and every commit sealed since has
                # landed by now.
                for alloc_id, node_id in self._committing.items():
                    if base.alloc_by_id(alloc_id) is None:
                        self._drop(node_id)
                for alloc in changed:
                    summary = self._by_node.get(alloc.node_id)
                    if summary is None:
                        continue
                    # The applier's own write was counted when it was
                    # accepted, and the store kept its resources. What
                    # the store may have kept besides is the client's
                    # status (upsert_allocs): an allocation the client
                    # had finished stays finished under an in-place
                    # update, so the row must be live where the summary
                    # covers it and nowhere else.
                    if (alloc.modify_index in self._own
                            and (alloc.id in summary.shares)
                            == (not alloc.terminal_status())):
                        continue
                    self._drop(alloc.node_id)
        self._store_id, self._index, self._count = (base.store_id, index,
                                                    count)
        self._own.clear()
        self._outdated.clear()
        self._committing.clear()

    def of(self, view: "OptimisticSnapshot", node: Node) -> NodeSummary:
        """The node's summary: the one carried if it is of this row of
        the node, else built from everything the view lists there."""
        summary = self._by_node.get(node.id)
        if summary is not None:
            if summary.node is node:
                self.hits += 1
                self._by_node.move_to_end(node.id)
                return summary
            self._drop(node.id)
        standing = view.allocs_by_node_terminal(node.id, False)
        summary = self._by_node[node.id] = NodeSummary(node, standing)
        self.builds += 1
        self.standing += len(standing)
        if len(self._by_node) > MAX_SUMMARIES:
            self._by_node.popitem(last=False)
            self.dropped += 1
        return summary

    def accept(self, result: PlanResult,
               extra_by_node: Dict[str, Dict[str, Allocation]]) -> None:
        """An accepted result's stops and victims go out of its nodes'
        summaries and its placements in: O(what the result holds).
        `extra_by_node` is the view's unlanded placements BEFORE this
        result's: the view keeps listing one of those after a later
        plan stops it, until the next base (where the stop hides it),
        so its share stays and the node's summary ends with this
        base."""
        for stops in (result.node_update, result.node_preemptions):
            for node_id, allocs in stops.items():
                summary = self._by_node.get(node_id)
                extra = extra_by_node.get(node_id) or ()
                for alloc in allocs:
                    if alloc.id in extra:
                        # Also where the node has no summary yet: one
                        # built on this base would count it too.
                        self._outdated.add(node_id)
                    elif summary is not None:
                        summary.drop(alloc.id)
        for node_id, allocs in result.node_allocation.items():
            summary = self._by_node.get(node_id)
            if summary is not None:
                for alloc in allocs:
                    summary.take(alloc)


class OptimisticSnapshot:
    """Base snapshot + accepted allocations of plans that have not
    landed — the read view for verifying a plan behind its group-mates
    and behind the group whose commit is still in flight
    (plan_apply.go:155-161 optimistic snap.UpsertAllocs). Exposes
    exactly what evaluate_node_plan reads, and the node summaries that
    restate it (`summaries`: the applier's, carried across views; a
    view made without them keeps its own)."""

    def __init__(self, base, summaries: Optional[NodeSummaries] = None):
        self.base = base
        self.summaries = summaries if summaries is not None else NodeSummaries()
        self._extra_by_node = {}  # node_id -> {alloc_id: alloc}
        self._evicted = set()  # alloc ids stopped by in-flight plans
        # Raft entries the view runs ahead of its base by: those sealed
        # and handed to the commit thread, and the one the group being
        # verified will make once it holds an accepted result.
        self._sealed = 0
        self._open = False

    def add_result(self, result: PlanResult, summarised: bool = False) -> None:
        """Take an accepted result into the view, and into the
        summaries unless they hold it already (`summarised`: a result
        carried over to a new base)."""
        if result.is_no_op():
            return
        if not summarised:
            self.summaries.accept(result, self._extra_by_node)
        for node_id, allocs in result.node_allocation.items():
            d = self._extra_by_node.setdefault(node_id, {})
            for alloc in allocs:
                d[alloc.id] = alloc
        for allocs in result.node_update.values():
            for alloc in allocs:
                self._evicted.add(alloc.id)
        # In-flight preemption evictions hide from the next plan's
        # verification exactly like staged stops do.
        for allocs in result.node_preemptions.values():
            for alloc in allocs:
                self._evicted.add(alloc.id)
        self._open = True

    def seal_entry(self) -> None:
        """The results added so far go into one raft entry, now."""
        if self._open:
            self._sealed += 1
            self._open = False

    def node_by_id(self, node_id):
        return self.base.node_by_id(node_id)

    def latest_index(self) -> int:
        # A plan rejected off this view must refresh PAST every entry
        # the view holds — the in-flight group's and its own group's —
        # otherwise the worker's "refresh" is a no-op against
        # pre-commit state and it spins resubmitting the same plan (the
        # reference advances its optimistic snapshot's index the same
        # way).
        return self.base.latest_index() + self._sealed + self._open

    def allocs_by_node_terminal(self, node_id, terminal):
        live = {
            a.id: a
            for a in self.base.allocs_by_node_terminal(node_id, terminal)
            if a.id not in self._evicted
        }
        if not terminal:
            live.update(self._extra_by_node.get(node_id, {}))
        return list(live.values())

    def live_alloc(self, node_id: str, alloc_id: str) -> Optional[Allocation]:
        """One allocation the view lists on the node, as that list
        would hold it: the unlanded placement before the stored row."""
        extra = self._extra_by_node.get(node_id)
        if extra and alloc_id in extra:
            return extra[alloc_id]
        return self.base.alloc_by_id(alloc_id)


# The most allocations one group carries into its one raft entry, and
# so into one upsert_allocs under the store's lock. Grouping pays where
# a plan's fixed costs (a snapshot, a copy of each table on the first
# write after it, two thread hand-offs) are of the size of its own
# work: plans of 8 allocations, of which 1,024 is every plan of the
# pipeline's two in-flight dispatches (2 x 64 lanes x 8). A plan of a
# thousand is its own work five hundred times over, and in a group it
# only waits for its mates' verification: at 2,048 and 4,096 the C1M
# cell's plan.commit and plan.submit.self medians rose two- and
# many-fold for no gain, so such a plan goes alone and the store's lock
# is held no longer than one of them already held it (PERF.md, PR 26).
# A constant, not a setting: no two deployments need different values.
MAX_GROUP_ALLOCS = 1024

# One verified plan of a group: the waiter and what it will be told.
Verified = Tuple[PendingPlan, PlanResult]


class PlanApplier:
    """Consumes the plan queue; runs as a leader-only thread.

    Pipelined like the reference (plan_apply.go:41-118): one raft
    commit is in flight at a time while the NEXT group is verified
    against an optimistic snapshot that includes the in-flight group's
    accepted allocations. A failed commit fails every plan of its
    group and forces the following group to re-verify on a fresh
    snapshot."""

    def __init__(self, plan_queue: PlanQueue, fsm, log,
                 logger: Optional[logging.Logger] = None):
        self.plan_queue = plan_queue
        self.fsm = fsm
        self.log = log
        self.logger = logger or logging.getLogger("nomad_tpu.plan_apply")
        # Dedicated single-thread executor: commits stay ordered.
        self._commit_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="plan-commit"
        )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # The outgoing generation's thread, kept so start() can wait
        # out its final in-flight commit before spawning a successor.
        self._draining: Optional[threading.Thread] = None
        self._lifecycle = threading.Lock()  # start/stop can race on
        # leadership flaps (raft elections)
        # Conflict observability (feeds the dispatch pipeline's
        # retries-per-eval accounting and the benchmark's conflict
        # metrics):
        # counters only ever touched on the applier thread.
        self.plans_evaluated = 0
        self.plans_rejected = 0  # plans that lost >= 1 node (refresh)
        self.nodes_rejected = 0  # node verifications that failed
        # Gang atomicity (nomad_tpu/gang): whole gangs removed because
        # a member's node failed verification — every one of these is a
        # proven nothing-partial-committed event.
        self.gangs_rejected = 0
        # That groups form: raft entries applied, the plans they held,
        # and the most plans one of them held. Touched on the commit
        # thread only.
        self.commits = 0
        self.plans_committed = 0
        self.largest_group = 0
        # Per-node standing usage, carried between plans and groups.
        # The applier thread's alone (NodeSummaries).
        self._summaries = NodeSummaries()

    def start(self) -> None:
        with self._lifecycle:
            # Idempotent: a re-confirmed leadership (start without an
            # intervening stop) must not spawn a second loop — with
            # per-generation stop events the first one would become
            # permanently unstoppable.
            if self._thread is not None and self._thread.is_alive():
                return
            draining, self._draining = self._draining, None
        if draining is not None and draining.is_alive():
            # Wait out the predecessor's final in-flight commit OUTSIDE
            # the lock: two live loops would verify plans against
            # snapshots that miss each other's commits — the serial
            # verification invariant the single applier exists for.
            draining.join(timeout=5.0)
            if draining.is_alive():
                # Still wedged past the bound: REFUSE to spawn a
                # concurrent successor. One missing applier stalls the
                # plan queue visibly; two live ones double-place
                # silently. The next leadership confirmation retries.
                with self._lifecycle:
                    self._draining = draining
                self.logger.error(
                    "plan applier predecessor still draining after "
                    "5s; refusing to start a concurrent loop")
                return
        with self._lifecycle:
            if self._thread is not None and self._thread.is_alive():
                return  # lost a start/start race while joining
            # Fresh Event PER thread generation: clearing a shared
            # event here could race a stop()'s set before the outgoing
            # thread observed it (stop joins OUTSIDE the lock), leaving
            # two _run loops alive after a leadership flap.
            stop = threading.Event()
            self._stop = stop
            thread = threading.Thread(
                target=self._run, args=(stop,), name="plan-applier",
                daemon=True
            )
            thread.start()
            self._thread = thread

    def stop(self) -> None:
        # Detach under the lock, join outside it: holding _lifecycle
        # across the join would block a concurrent start() for the
        # whole drain instead of serializing just the handoff. The
        # detached thread is remembered in _draining so a prompt
        # restart waits for its final commit.
        with self._lifecycle:
            self._stop.set()
            thread, self._thread = self._thread, None
            if thread is not None:
                self._draining = thread
        if thread is not None:
            thread.join(timeout=5.0)

    def _run(self, stop: Optional[threading.Event] = None) -> None:
        stop = stop if stop is not None else self._stop
        inflight = None  # future of the in-flight group commit
        overlay: Optional[OptimisticSnapshot] = None
        # A generation of the loop starts from nothing: what a
        # predecessor accepted last may never have landed.
        self._summaries.clear()
        while not stop.is_set():
            group = self.plan_queue.dequeue_group(
                MAX_GROUP_ALLOCS, timeout=0.02 if inflight else 0.25)
            inflight, overlay = self._turn(group, inflight, overlay)
        if inflight is not None:
            self._wait_commit(inflight)

    def _turn(self, group: List[PendingPlan], inflight,
              overlay: Optional[OptimisticSnapshot]):
        """One turn of the loop: the group the queue gave (none when it
        stood empty) behind the commit in flight and the view that
        holds it; returns the commit in flight and the view after."""
        if not group:
            if inflight is not None:
                self._wait_commit(inflight)
            return None, None  # queue drained: next gets fresh state
        if inflight is None:
            # Nothing outstanding: the group verifies against fresh
            # state. The overlay only ever spans ONE in-flight
            # commit and the group being verified — a rejected or
            # no-op group must not pin the next one to a stale base.
            overlay = self._fresh_overlay()
        # Verified against the optimistic view WHILE the previous
        # group's raft commit is still in flight — the reference's
        # verify-(N+1)-during-commit-(N) overlap.
        verified = self._verify_group(overlay, group, queued=True)
        if inflight is not None:
            ok = self._wait_commit(inflight)
            inflight = None
            # Rebase on committed state either way: staleness is
            # bounded to one commit's duration, and node
            # drains/client updates applied meanwhile are seen.
            overlay = self._fresh_overlay()
            if ok:
                # The summaries took these in when they were
                # verified; the new view has yet to.
                for _pending, result in verified:
                    overlay.add_result(result, summarised=True)
            else:
                # The old view contained allocs that never landed:
                # this group's verification must be redone.
                verified = self._verify_group(
                    overlay, [pending for pending, _ in verified])
        accepted = []
        for pending, result in verified:
            if result.is_no_op():
                pending.respond(result, None)
            else:
                accepted.append((pending, result))
        if accepted:
            overlay.seal_entry()
            self._summaries.seal([result for _pending, result in accepted])
            # The waiters are answered by the commit thread the
            # INSTANT the entry has applied, not when this loop
            # next wakes.
            inflight = self._commit_pool.submit(self._commit, accepted)
        return inflight, overlay

    def _fresh_overlay(self) -> OptimisticSnapshot:
        """A view of the store as it stands, and the summaries moved
        to it: those a write of anybody else's touched since the last
        base are gone before the first plan is verified on this one."""
        base = self.fsm.state.snapshot()
        self._summaries.rebase(base)
        return OptimisticSnapshot(base, self._summaries)

    def _verify_group(self, overlay: "OptimisticSnapshot",
                      group: List[PendingPlan],
                      queued: bool = False) -> List[Verified]:
        """Each plan in queue order against the overlay, its accepted
        part added before the next plan is checked: a plan sees
        committed state plus everything accepted ahead of it that has
        not landed. A plan whose verification raises is answered with
        the error and leaves nothing in the overlay."""
        verified: List[Verified] = []
        with trace.annotation("nomad.plan_apply", phase="evaluate",
                              plans=len(group)):
            for pending in group:
                if queued:
                    # Ends where the plan's OWN verification starts:
                    # waiting for the group-mates ahead is queue wait.
                    # create=False, as the applier's other spans: a
                    # remote (follower-worker) plan's trace lives in
                    # its own process.
                    trace.record_span(pending.plan.eval_id,
                                      trace.STAGE_PLAN_QUEUE_WAIT,
                                      pending.enqueue_time, create=False)
                start = time.monotonic()
                try:
                    result = self._evaluate_plan(overlay, pending.plan)
                except Exception as e:  # noqa: BLE001 - fail the one plan
                    self.logger.exception("plan evaluate failed")
                    pending.respond(None, e)
                    continue
                metrics.measure_since(("plan", "evaluate"), start)
                overlay.add_result(result)
                verified.append((pending, result))
        return verified

    def _wait_commit(self, inflight) -> bool:
        """Wait out an in-flight raft commit; False when it failed
        (asyncPlanWait, plan_apply.go:166). The waiters were already
        answered on the commit thread. No extra timeout here:
        log.apply has its own bounded timeouts, and abandoning a
        still-running commit would let it land after the pipeline moved
        on (double-commit on retry)."""
        try:
            # Its index names the applier's own allocations in the next
            # base's journal: the summaries hold those already.
            self._summaries.note_commit(inflight.result())
            return True
        except Exception:  # noqa: BLE001 - logged; waiters already told
            self.logger.exception("plan commit failed")
            # The summaries hold what the failed group wrote, and what
            # was verified on top of it: none of it is in the store.
            self._summaries.clear()
            return False

    def _note_stale_state(self) -> None:
        """A node verification failed in a way ordinary optimistic
        concurrency cannot explain: the matrix claimed a fit that its
        OWN snapshot refutes. Mark the resident delta chain suspect so
        the next cacheable matrix build pays one full rebuild instead
        of trusting it (models/resident.py; the carve-over of the
        reference's plan_apply.go:318 exactness)."""
        from ..models.resident import note_rejection

        note_rejection()

    @staticmethod
    def _ordinary_conflict(snapshot, plan: Plan, node_id: str) -> bool:
        """Whether this node's rejection is explained by state the
        scheduler's matrix could not have seen: an in-flight pipelined
        plan's accepted allocs, or node/alloc changes committed after
        the plan's matrix watermark. True means a routine optimistic-
        concurrency loss (the replan refreshes past it) — purging the
        whole device-resident base cache for it would degenerate a
        conflict-heavy storm back into rebuild-per-snapshot. False (or
        no watermark) means the resident chain itself is suspect."""
        if plan.matrix_index < 0:
            return False
        extra = getattr(snapshot, "_extra_by_node", None)
        if extra and extra.get(node_id):
            return True
        base = getattr(snapshot, "base", snapshot)
        node = base.node_by_id(node_id)
        if node is not None and node.modify_index > plan.matrix_index:
            return True
        return any(a.modify_index > plan.matrix_index
                   for a in base.allocs_by_node(node_id))

    @staticmethod
    def _verify_node(view: OptimisticSnapshot, plan: Plan,
                     node_id: str) -> bool:
        """evaluate_node_plan from the node's summary: the same legs in
        the same order, in proportion to what the plan writes there and
        not to what stands there."""
        victims = plan.node_preemptions.get(node_id)
        placed = plan.node_allocation.get(node_id)
        if not victims and not placed:
            return True  # evictions only: always safe
        node = view.node_by_id(node_id)
        if node is None:
            # Nothing to summarise for: the rule over the full list.
            return evaluate_node_plan(view, plan, node_id)
        summary = None
        if victims:
            from ..migrate import victim_priority

            # As evaluate_node_preemptions: every victim live in the
            # view (the summary covers exactly those) and strictly
            # below the plan.
            summary = view.summaries.of(view, node)
            for victim in victims:
                if victim.id not in summary.shares:
                    return False
                stored = view.live_alloc(node_id, victim.id)
                if stored is None or victim_priority(stored) >= plan.priority:
                    return False
            if not placed:
                return True
        if node.status != consts.NODE_STATUS_READY or node.drain:
            return False
        if summary is None:
            summary = view.summaries.of(view, node)
        removed = plan.node_update.get(node_id) or ()
        if victims:
            removed = [*removed, *victims]
        return summary.fits(removed, placed)[0]

    def _evaluate_plan(self, snapshot, plan: Plan) -> PlanResult:
        """Per-node verification with partial commit
        (plan_apply.go:194 evaluatePlan)."""
        _t0 = time.monotonic()
        if not isinstance(snapshot, OptimisticSnapshot):
            # A bare snapshot: a view over it with summaries of its
            # own, so nothing is carried and every node is read.
            snapshot = OptimisticSnapshot(snapshot)
        summaries = snapshot.summaries
        built0, standing0 = summaries.builds, summaries.standing

        def cost() -> dict:
            # nodes: those the plan touches; built: the summaries made
            # from the store for it; standing: the allocations read to
            # make them (0 and 0 where every node's was carried).
            return {"nodes": len(node_ids),
                    "built": summaries.builds - built0,
                    "standing": summaries.standing - standing0}

        result = PlanResult(
            node_update=dict(plan.node_update),
            node_allocation=dict(plan.node_allocation),
            node_preemptions=dict(plan.node_preemptions),
        )

        node_ids = (set(plan.node_update) | set(plan.node_allocation)
                    | set(plan.node_preemptions))
        self.plans_evaluated += 1
        rejected = 0
        suspect = False
        rejected_nodes = set()
        for node_id in node_ids:
            if self._verify_node(snapshot, plan, node_id):
                continue
            # This node's changes don't fit anymore.
            rejected += 1
            metrics.incr_counter(("plan", "node_rejected"))
            if not self._ordinary_conflict(snapshot, plan, node_id):
                suspect = True
            if plan.all_at_once:
                # Whole-plan gang commit: reject everything, force a
                # refresh.
                result.node_update = {}
                result.node_allocation = {}
                result.node_preemptions = {}
                result.refresh_index = snapshot.latest_index()
                self.plans_rejected += 1
                self.nodes_rejected += rejected
                if suspect:
                    self._note_stale_state()
                trace.record_span(
                    plan.eval_id, trace.STAGE_PLAN_EVALUATE, _t0,
                    ann={**cost(), "nodes_rejected": rejected, "gang": True},
                    create=False)
                return result
            rejected_nodes.add(node_id)
        # Gang atomicity leg (nomad_tpu/gang): which nodes host which
        # gang's members — decided from the PLAN (gang_groups stages
        # alloc ids), applied to the RESULT below. The chaos site
        # models an applier-side under-fit on exactly one member node;
        # the invariant under test is that the whole gang rejects.
        gang_nodes: Dict[str, set] = {}
        if plan.gang_groups:
            id_to_gang = {aid: gk
                          for gk, ids in plan.gang_groups.items()
                          for aid in ids}
            for node_id, placed in plan.node_allocation.items():
                for alloc in placed:
                    gk = id_to_gang.get(alloc.id)
                    if gk is not None:
                        gang_nodes.setdefault(gk, set()).add(node_id)
            from ..chaos import chaos

            if chaos.enabled and chaos.fire(
                    "gang.partial_commit",
                    eval_id=plan.eval_id) == "drop":
                for gk in sorted(gang_nodes):
                    nodes = sorted(gang_nodes[gk] - rejected_nodes)
                    if nodes:
                        rejected += 1
                        rejected_nodes.add(nodes[0])
                        break
        for node_id in rejected_nodes:
            result.node_update.pop(node_id, None)
            result.node_allocation.pop(node_id, None)
            result.node_preemptions.pop(node_id, None)
            result.refresh_index = snapshot.latest_index()
        # All-K-or-nothing: a gang with ANY member on a rejected node
        # loses EVERY member — filtered off accepted nodes too.
        # Removing allocs only frees capacity, so the surviving
        # placements that verified alongside them still fit.
        doomed = sorted(gk for gk, nodes in gang_nodes.items()
                        if nodes & rejected_nodes)
        for gk in doomed:
            ids = set(plan.gang_groups.get(gk, ()))
            for node_id in sorted(gang_nodes[gk] - rejected_nodes):
                placed = result.node_allocation.get(node_id)
                if not placed:
                    continue
                kept = [a for a in placed if a.id not in ids]
                if kept:
                    result.node_allocation[node_id] = kept
                else:
                    del result.node_allocation[node_id]
            result.refresh_index = snapshot.latest_index()
        if doomed:
            self.gangs_rejected += len(doomed)
            metrics.incr_counter(("plan", "gang_rejected"), len(doomed))
            from ..gang import note_gang_rejected_whole

            note_gang_rejected_whole(len(doomed))
            now = time.monotonic()
            for gk in doomed:
                # One zero-length marker a gang rejected whole: how wide
                # it was, and the first of its nodes that failed.
                trace.record_span(
                    plan.eval_id, trace.STAGE_GANG_REJECTED, now, now,
                    ann={"width": len(plan.gang_groups.get(gk, ())),
                         "node": min(gang_nodes[gk] & rejected_nodes)},
                    create=False)
        if rejected:
            self.plans_rejected += 1
            self.nodes_rejected += rejected
            if suspect:
                self._note_stale_state()
        # create=False: the applier serves remote (follower-worker)
        # plans too — their lifecycle trace lives in the follower's
        # process, not this one.
        ann = cost()
        if rejected or doomed:
            ann["nodes_rejected"] = rejected
            if doomed:
                ann["gangs_rejected"] = len(doomed)
        trace.record_span(
            plan.eval_id, trace.STAGE_PLAN_EVALUATE, _t0,
            ann=ann, create=False)
        return result

    def stats(self) -> dict:
        """Conflict counters: how often optimistic plans lost node
        verifications (each rejection is a replan round-trip somewhere
        upstream — the dispatch pipeline's A/B measures these); and
        whether groups form: plans_committed over commits is the plans
        a raft entry carries; and how the node summaries serve:
        verifications from one carried (`summary_hits`), summaries
        built from the store (`summary_builds`) and thrown away
        (`summary_dropped`)."""
        summaries = self._summaries
        return {
            "plans_evaluated": self.plans_evaluated,
            "plans_rejected": self.plans_rejected,
            "nodes_rejected": self.nodes_rejected,
            "gangs_rejected": self.gangs_rejected,
            "commits": self.commits,
            "plans_committed": self.plans_committed,
            "largest_group": self.largest_group,
            "summary_hits": summaries.hits,
            "summary_builds": summaries.builds,
            "summary_dropped": summaries.dropped,
        }

    def _commit(self, accepted: List[Verified]) -> int:
        """One raft entry for the group's accepted results; every plan
        is answered with its own result once that entry has applied,
        or with the error if it did not."""
        start = time.monotonic()
        parts = []
        n_allocs = n_preempted = 0
        for pending, result in accepted:
            allocs: List[Allocation] = []
            for update_list in result.node_update.values():
                allocs.extend(update_list)
            for victim_list in result.node_preemptions.values():
                # Victims ride the SAME raft apply as the placements
                # they make room for: one log entry, one terminal stamp
                # — the exactly-once contract the preemption soak
                # asserts.
                allocs.extend(victim_list)
                n_preempted += len(victim_list)
            for alloc_list in result.node_allocation.values():
                allocs.extend(alloc_list)
            n_allocs += len(allocs)
            # One part per plan: the handler re-attaches each plan's
            # job to that plan's allocations alone.
            parts.append({"allocs": allocs, "job": pending.plan.job})
        try:
            with trace.annotation("nomad.plan_apply", phase="commit",
                                  allocs=n_allocs, plans=len(accepted)):
                index = self.log.apply(ALLOC_UPDATE, {"plans": parts})
        except Exception as e:  # noqa: BLE001 - fail the whole group
            for pending, _result in accepted:
                pending.respond(None, e)
            raise
        applied = time.monotonic()
        self.commits += 1
        self.plans_committed += len(accepted)
        self.largest_group = max(self.largest_group, len(accepted))
        if n_preempted:
            from ..migrate import note_preemption_committed

            note_preemption_committed(n_preempted)
        # Stamp indexes onto the result's alloc objects the way the Go
        # store mutates shared pointers — workers count fresh
        # placements by create_index == alloc_index (scheduler/util.py).
        # One view of the store for the group: each read through the
        # store itself would take a snapshot of its own.
        snapshot = self.fsm.state.snapshot()
        for (pending, result), part in zip(accepted, parts):
            trace.record_span(
                pending.plan.eval_id, trace.STAGE_PLAN_COMMIT, start, applied,
                ann={"allocs": len(part["allocs"]), "plans": len(accepted)},
                create=False)
            for alloc_list in result.node_allocation.values():
                for alloc in alloc_list:
                    stored = snapshot.alloc_by_id(alloc.id)
                    if stored is not None:
                        alloc.create_index = stored.create_index
                        alloc.modify_index = stored.modify_index
            result.alloc_index = index
            pending.respond(result, None)
        metrics.measure_since(("plan", "submit"), start)
        return index
