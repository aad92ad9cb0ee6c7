"""Dense cluster-matrix construction for the TPU placement kernel.

Bridges the object model (structs/state) to the array program
(ops/binpack.py):

- nodes -> [N, 4] capacity/utilization matrices (+ bandwidth, free
  dynamic-port counts);
- constraints -> a [N, G] feasibility mask computed per *computed node
  class* host-side (C << N constraint evaluations, the dense analog of
  the reference's FeasibilityWrapper memo, scheduler/feasible.go:457),
  with `unique.`-escaped constraints evaluated per node;
- shapes bucketed (N and K padded to fixed sizes) so XLA compiles one
  program per bucket instead of per cluster size.
"""

from __future__ import annotations

import bisect
import time
from collections import Counter
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..scheduler.context import EvalContext
from ..scheduler.feasible import ConstraintChecker, DriverChecker
from ..structs import (
    Allocation,
    Job,
    Node,
    Plan,
    consts,
    escaped_constraints,
    remove_allocs,
)
from ..structs.resources import Resources

# Node-count buckets: VPU-lane-friendly multiples of 128. Denser steps
# above 8k: pure powers of two made a 10k-node cluster pad to 16384
# (+63% on every transfer and scan row).
BUCKETS = [128, 256, 512, 1024, 2048, 4096, 6144, 8192, 10240, 12288,
           16384, 20480, 24576, 32768]
# The ask ladder: the padded length of an eval's ask axis, one compiled
# program a rung (and a batch bucket). 2,048 is a NAMED rung: past the
# ladder's top bucket_size rounds up to the next multiple of the top,
# each multiple a compile of its own, so a count the traffic holds
# belongs on the ladder (a task of 2,000 instances: alibaba-colo-4k).
ASK_BUCKETS = [8, 16, 32, 64, 128, 256, 512, 1024, 2048]
# Compact-overlay padding buckets (each distinct size is one compile):
# class count, feasibility-patch rows, job alloc positions. Overlays
# larger than the top bucket fall back to the dense [N,G] overlay. A
# rack is part of a node's computed class wherever it states one, so the
# class ladder has to hold a fleet's rack count: 2,048 racks of 40 are
# 80k nodes, and a lane's verdicts are then 2 KB x G.
CLASS_BUCKETS = [8, 32, 128, 512, 2048]
PATCH_BUCKETS = [16, 64, 256]
JOBPOS_BUCKETS = [16, 64, 256, 1024, 2048]
# The rows of a lane's plan patch (ClusterMatrix.plan_patch): a ladder
# of its own, because every lane of every dispatch carries a patch and
# an arrival's is empty: on the positions ladder, whose floor is the
# job's whole count, a task of 2,000 instances would ship 57 KB of
# padding a lane. A plan that touches more rows than the top holds
# takes a dense state of its own (_build_plan_patch).
PLAN_BUCKETS = [16, 64, 256, 1024]

# Job-independent cluster base, cached across evaluations: rebuilding
# the [N,4] utilization matrices is O(N x allocs) host work per eval,
# and the base only changes when the nodes or allocs tables do (the
# incremental-update-keyed-on-raft-index plan from SURVEY.md §7).
# _BASE_FAMILY tracks the newest base per (store, nodes-index, dc-set)
# so a snapshot that only advanced the allocs table DELTA-updates the
# previous base (recompute touched node rows only) instead of paying
# the O(N x allocs) full rebuild — the live pipeline bumps the allocs
# index on every plan apply, so full rebuilds would dominate at 10k+
# nodes / 50k+ allocs.
_BASE_CACHE: Dict[Tuple, "_ClusterBase"] = {}
_BASE_FAMILY: Dict[Tuple, "_ClusterBase"] = {}
# key -> Event while a build is in flight (single-flight guard).
_BASE_PENDING: Dict[Tuple, object] = {}
# Bumped (under _BASE_CACHE_LOCK) by every stale-purge: a builder that
# delta'd from a pre-purge parent sees the epoch moved at store time
# and must discard its chain instead of re-seeding the purged cache.
_BASE_EPOCH = 0
_BASE_CACHE_MAX = 8
_BASE_CACHE_LOCK = __import__("threading").Lock()
_BASE_TOKENS = __import__("itertools").count(1)


def base_epoch() -> int:
    """The stale-purge epoch (bumped by every plan-apply-rejection
    purge in resolve_cluster_base). The defrag loop snapshots it before
    a solve and discards the solved wave if it moved — a wave derived
    from a chain the applier just convicted must commit nothing
    (nomad_tpu/defrag, chaos site `defrag.solve_stale`)."""
    with _BASE_CACHE_LOCK:
        return _BASE_EPOCH


class _ClusterBase:
    __slots__ = ("n_real", "n", "capacity", "sched_capacity",
                 "util", "bw_avail", "bw_used", "ports_free", "node_ok",
                 "alloc_groups", "token", "nodes_token", "allocs_index",
                 "table_len",
                 "nodes_index", "delta_parent", "delta_stats", "class_ids",
                 "class_reps",
                 "class_index", "topology", "_positions",
                 "_positions_lock", "_victims", "_victims_lock",
                 "_row_of")

    def __init__(self, nodes, proposed_fn, allocs_index: int = -1,
                 table_len: int = -1, nodes_index: int = -1):
        # Identity token: evals whose matrices share one base can share
        # a single device upload (scheduler/batcher.py groups by it).
        self.token = next(_BASE_TOKENS)
        # Identity of the NODE axis: a full build's own token, which its
        # delta clones inherit with the class index, the topology tensor
        # and the row order they share by reference. What depends on the
        # nodes alone (the feasibility masks) is memoized under it and
        # so outlives every commit; a rebuild of the node axis (a
        # register, a class split) mints a new one.
        self.nodes_token = self.token
        self.allocs_index = allocs_index  # -1 = not delta-updatable
        # Allocs-table size at build time: deletions (GC) are invisible
        # to the modify_index scan, so a shrinking table forces a full
        # rebuild (see delta_update).
        self.table_len = table_len
        # Nodes-table watermark: node up/down/drain transitions bump it
        # and delta as node_ok row flips (models/resident.py) — the
        # node stays in the matrix, masked, instead of rebuilding the
        # node axis. -1 = node-axis deltas off for this base.
        self.nodes_index = nodes_index
        # (parent_token, changed_rows) when this base was produced by
        # delta_update: the batcher uses it to scatter-update the
        # parent's device-cached arrays instead of re-uploading
        # (ops/binpack.py apply_base_delta).
        self.delta_parent = None
        # What that delta held, the annotations of the span
        # `base.delta`: rows written, allocations added to additive
        # rows, rows refilled, jobs whose positions entry was rewritten.
        self.delta_stats = None
        self.n_real = len(nodes)
        self.n = bucket_size(self.n_real)
        n = self.n
        self.capacity = np.zeros((n, 4), np.float32)
        self.sched_capacity = np.zeros((n, 4), np.float32)
        self.util = np.zeros((n, 4), np.float32)
        self.bw_avail = np.zeros(n, np.float32)
        self.bw_used = np.zeros(n, np.float32)
        self.ports_free = np.zeros(n, np.float32)
        self.node_ok = np.zeros(n, bool)
        # per node: [(job_id, task_group), ...] of live allocs, for the
        # cheap per-job overlay counts
        self.alloc_groups: List[List[Tuple[str, str]]] = []
        self._init_class_index(nodes)
        # The job-positions index, {job_id: _JobRows}: built lazily by
        # a full build's first reader, carried on by delta clones
        from ..profile import ProfiledLock

        self._positions = None  # guarded-by: _positions_lock
        self._positions_lock = ProfiledLock("models.matrix.positions")
        # Preemption candidates by node (_VictimTable), built lazily by
        # the first preempting eval and carried along the delta chain.
        self._victims = None  # guarded-by: _victims_lock
        self._victims_lock = ProfiledLock("models.matrix.victims")
        self._row_of = None  # node id -> row, lazy; shared by delta clones
        self._fill_all(nodes, proposed_fn)

    def _init_class_index(self, nodes) -> None:
        """Node -> computed-class index, so feasibility evaluates once
        per CLASS on a representative node and numpy-expands to all N
        (the dense analog of FeasibilityWrapper's memo,
        scheduler/feasible.go:457). Node-level, alloc-independent:
        delta clones share it by reference."""
        ids, self.class_reps = compute_class_index(nodes)
        self.class_ids = np.full(self.n, -1, np.int32)
        self.class_ids[: len(nodes)] = ids
        # Signature-class interning (models/classes.py): REFINES the
        # computed class with the static row state, so class-granular
        # dense programs (the defrag solve's x[K, C]) can expand back
        # to bit-identical node rows. Escaped nodes get singleton
        # classes there, so aggregation always covers the whole fleet.
        from .classes import ClassIndex

        self.class_index = ClassIndex(nodes, self.n)
        # Node-topology tensor (models/topology.py): rack/ICI id
        # columns for the gang program. Node-level and alloc-
        # independent like the class index — delta clones share it by
        # reference; register/deregister breaks the family and this
        # rebuild re-derives it.
        from .topology import TopologyIndex

        self.topology = TopologyIndex(nodes, self.n)

    def job_positions(self, job_id: str) -> Dict[str, np.ndarray]:
        """{task_group: node-row indices (with repeats)} for one job's
        live allocs; the order of a task group's rows is no part of the
        contract. The index over alloc_groups builds lazily ONCE per
        chain of bases (O(total allocs), on a full build's first
        reader); a delta clone carries it on at O(what the delta wrote)
        (_patch_positions), and a reader pays O(its own job's allocs):
        the lookup, and once a base the fold of the rows that came to
        and went from THIS job since it was last read (_JobRows). No
        other job's rows are looked at, here or in a delta."""
        with self._positions_lock:
            if self._positions is None:
                positions: Dict[str, Dict[str, List[int]]] = {}
                for i, groups in enumerate(self.alloc_groups):
                    for jid, tg in groups:
                        positions.setdefault(jid, {}).setdefault(
                            tg, []).append(i)
                self._positions = {
                    jid: _JobRows({tg: np.asarray(rows, np.int64)
                                   for tg, rows in per.items()})
                    for jid, per in positions.items()
                }
            entry = self._positions.get(job_id)
        return entry.rows() if entry is not None else {}

    def row_of(self, nodes) -> Dict[str, int]:
        """{node id: row} of this base's node set. Built once per
        family: delta clones keep the node set and share the dict."""
        rows = self._row_of
        if rows is None:
            rows = self._row_of = {
                node.id: i for i, node in enumerate(nodes)}
        return rows

    def victim_table(self, nodes, state) -> "_VictimTable":
        """The preemption candidates of every node of this base's
        snapshot `state` (ops/preempt.py), built once per base: the
        first preempting eval pays the walk over the store's live
        allocations, its batch-mates wait for it here, and newer bases
        re-derive only the rows their delta touched (_patch_victims)."""
        with self._victims_lock:
            if self._victims is None:
                self._victims = _VictimTable.build(self.n, nodes, state)
            return self._victims

    def _patch_victims(self, parent: "_ClusterBase", rows, nodes,
                       state) -> None:
        """Carry the parent's victim table forward (see
        _patch_positions): only the changed rows are re-derived. A
        parent that never built one leaves this base lazy too, so a
        server that never preempts never pays for the table."""
        with parent._victims_lock:
            table = parent._victims
        if table is None:
            return
        patched = table.with_rows({
            i: _victim_candidates(
                state.allocs_by_node_terminal(nodes[i].id, False))
            for i in rows})
        with self._victims_lock:
            self._victims = patched

    def _fill_static(self, i, node) -> Tuple[float, float, int]:
        """Node-only (alloc-independent) fields of one row. Returns
        (reserved bw, reserved dynamic-port count) for the caller to
        combine with alloc usage."""
        r = node.resources
        self.capacity[i] = (r.cpu, r.memory_mb, r.disk_mb, r.iops)
        res = node.reserved
        res_cpu = res.cpu if res else 0
        res_mem = res.memory_mb if res else 0
        res_disk = res.disk_mb if res else 0
        res_iops = res.iops if res else 0
        self.sched_capacity[i] = (
            r.cpu - res_cpu, r.memory_mb - res_mem,
            r.disk_mb - res_disk, r.iops - res_iops,
        )
        self.util[i] = (res_cpu, res_mem, res_disk, res_iops)
        self.bw_avail[i] = r.networks[0].mbits if r.networks else 0.0
        res_bw = 0.0
        ports_used = 0
        if res:
            for net in res.networks:
                res_bw += net.mbits
                for p in list(net.reserved_ports) + list(net.dynamic_ports):
                    if consts.MIN_DYNAMIC_PORT <= p.value < consts.MAX_DYNAMIC_PORT:
                        ports_used += 1
        self.bw_used[i] = res_bw
        return res_bw, ports_used

    def _fill_row(self, i, node, allocs) -> None:
        """(Re)compute one node's row from its object + live allocs
        (the delta-update path; full builds go through _fill_all)."""
        _res_bw, ports_used = self._fill_static(i, node)
        # Accumulate in python floats: one numpy scalar op per ALLOC
        # (util[i] += tuple) was the dominant cost of row fills.
        cpu = mem = disk = iops = bw = 0.0
        groups: List[Tuple[str, str]] = []
        for alloc in allocs:
            c, m, d, io, mbits, aports = _alloc_usage(alloc)
            cpu += c
            mem += m
            disk += d
            iops += io
            bw += mbits
            ports_used += aports
            groups.append((alloc.job_id, alloc.task_group))
        if allocs:
            self.util[i] += (cpu, mem, disk, iops)
            self.bw_used[i] += bw
        self.alloc_groups[i] = groups
        self.ports_free[i] = (
            consts.MAX_DYNAMIC_PORT - consts.MIN_DYNAMIC_PORT - ports_used)
        # Readiness is ROW state, not matrix membership: the resident
        # universe keeps down/draining nodes in the matrix with node_ok
        # masked, so their transitions are deltas (models/resident.py).
        self.node_ok[i] = node.ready()

    def _fill_all(self, nodes, proposed_fn) -> None:
        """Full build, vectorized over allocs: statics per node (a
        python loop over N cheap attribute reads), then ONE bulk
        scatter-add of every alloc's memoized usage — the per-row
        python/numpy churn here dominated the per-eval matrix cost in
        system storms (BASELINE config 5/7)."""
        n_real = self.n_real
        rows: List[int] = []
        usages: List[Tuple] = []
        static_ports = np.zeros(n_real, np.float32)
        for i, node in enumerate(nodes):
            _res_bw, ports_used = self._fill_static(i, node)
            static_ports[i] = ports_used
            groups: List[Tuple[str, str]] = []
            for alloc in proposed_fn(node.id):
                rows.append(i)
                usages.append(_alloc_usage(alloc))
                groups.append((alloc.job_id, alloc.task_group))
            self.alloc_groups.append(groups)
        alloc_ports = np.zeros(n_real, np.float32)
        if rows:
            ridx = np.asarray(rows, np.intp)
            ua = np.asarray(usages, np.float32)
            np.add.at(self.util[:n_real], ridx, ua[:, :4])
            np.add.at(self.bw_used[:n_real], ridx, ua[:, 4])
            np.add.at(alloc_ports, ridx, ua[:, 5])
        self.ports_free[:n_real] = (
            consts.MAX_DYNAMIC_PORT - consts.MIN_DYNAMIC_PORT
            - static_ports - alloc_ports)
        self.node_ok[:n_real] = [node.ready() for node in nodes]

    def delta_update(self, nodes, state, new_allocs_index: int,
                     new_nodes_index: int = -1) -> Optional["_ClusterBase"]:
        """A newer base for the same node set, at a cost that follows
        what was written since our allocs_index and never what lives
        on the rows it touched: an allocation created since is
        scatter-added to its row and appended to its own job's
        positions, a row with a changed pre-existing allocation is
        refilled from its own entries, and no other job is looked at
        (_patch_positions). Only rows whose allocs
        changed since our allocs_index are recomputed — and, when the
        NODES table advanced too, rows whose node object changed
        (up/down/drain flips) are refilled with node_ok re-derived, so
        a node transition is a delta record like a plan commit instead
        of a node-axis rebuild. What changed is what the store's journal
        of allocation writes says (`state.allocs_changed_since`), never
        a walk over the table. Returns None when a full rebuild is the
        better deal (too many touched rows) or required for correctness
        (the journal does not reach back to this base; allocs were
        DELETED — GC removals leave no trace in it, so their usage
        would stay baked in; or a changed node's
        capacity/class moved, which the device-shared immutable arrays
        cannot express), or self unchanged-but-rekeyed when no relevant
        alloc moved (same token -> the device-cached upload is reused
        as-is)."""
        # Snapshot the watermark set ONCE: this base may be shared
        # across worker threads, and a concurrent rekey mid-scan would
        # make us compare a mixed-era (table_len, allocs_index) pair.
        with _BASE_CACHE_LOCK:
            base_allocs_index = self.allocs_index
            base_table_len = self.table_len
            base_nodes_index = self.nodes_index
        if base_allocs_index < 0 or base_table_len < 0:
            return None
        if new_nodes_index != base_nodes_index and base_nodes_index < 0:
            # The nodes table moved but this base can't attribute node
            # changes (no watermark): rebuild.
            return None
        node_rows: List[int] = []
        if 0 <= base_nodes_index < new_nodes_index:
            for i, node in enumerate(nodes):
                if node.modify_index <= base_nodes_index:
                    continue
                # The device keeps capacity/sched_capacity/bw_avail
                # and the class index of a delta child BY REFERENCE to
                # the parent (scheduler/batcher.py): a node whose
                # computed class moved (or that IS its class's
                # representative — the memoized verdicts were computed
                # on its old attributes) can't ride a row delta.
                ci = int(self.class_ids[i]) if i < self.n_real else -1
                if ci >= 0:
                    rep = self.class_reps[ci]
                    if rep == i or (nodes[rep].computed_class
                                    != node.computed_class):
                        return None
                elif node.computed_class:
                    return None
                # Class-split path (models/classes.py): the signature
                # covers capacity/reserved/link state beyond the
                # computed class — a node whose signature moved cannot
                # keep riding the shared interning; rebuild re-interns.
                # Readiness/drain flips are row state, outside the
                # signature, and stay deltas.
                from .classes import node_signature

                if (i < self.n_real
                        and self.class_index.signature_of(i)
                        != node_signature(node)):
                    return None
                node_rows.append(i)
        # What changed comes from the store's journal of allocation
        # writes (state/store.py allocs_changed_since): O(a commit's
        # few), where walking the table is O(all allocations) under the
        # GIL on the stage thread every eval of the batch waits for.
        # A state without the journal, or one whose journal does not
        # reach back to this base (a restored store), gets a full build.
        from .resident import get_tracker

        changed_since = getattr(state, "allocs_changed_since", None)
        changed = (changed_since(base_allocs_index)
                   if changed_since is not None else None)
        get_tracker().count_journal(changed)
        if changed is None:
            return None
        table_len = state.alloc_count()
        # An alloc created after the base was written after it, so the
        # created are all among the changed.
        created = sum(1 for a in changed
                      if a.create_index > base_allocs_index)
        if table_len != base_table_len + created:
            return None  # deletions happened; they are untraceable
        # Split the changes: an alloc CREATED after our watermark was
        # never in this base, so its usage can be scatter-ADDED to its
        # row directly — no re-scan of the node's other allocs. Only
        # rows with modified pre-existing allocs (in-place updates,
        # terminal transitions whose usage must come OUT) need the full
        # refill. A placement storm is pure creations — without this
        # split every committed plan degraded the next eval's delta to
        # a full O(N x allocs) rebuild (the refill cap below), making
        # the storm quadratic in total allocs (VERDICT r4 ask #8).
        refill_nids = set()
        adds = []
        for a in changed:
            if a.create_index > base_allocs_index:
                if not a.terminal_status():
                    adds.append(a)
                # created-then-terminal since the base: never counted,
                # consumes nothing now — nothing to do.
            else:
                refill_nids.add(a.node_id)
        row_of = self.row_of(nodes)
        adds = [a for a in adds
                if a.node_id not in refill_nids and a.node_id in row_of]
        node_row_set = set(node_rows)
        refill_rows = sorted(
            {row_of[nid] for nid in refill_nids if nid in row_of}
            | node_row_set)
        adds = [a for a in adds if row_of[a.node_id] not in node_row_set]
        rows = sorted({row_of[a.node_id] for a in adds}
                      | set(refill_rows))
        if not rows:
            # Nothing in OUR node set changed: rekey in place. table_len
            # must advance too — allocs may have been created on nodes
            # outside this family (other DCs, non-pinned nodes), and a
            # stale length would trip the deletion check on the next
            # delta, degrading every future update to a full rebuild.
            # Compare-and-advance under the lock: a concurrent delta from
            # a NEWER snapshot must never have its watermark regressed.
            with _BASE_CACHE_LOCK:
                if new_allocs_index > self.allocs_index:
                    self.allocs_index = new_allocs_index
                    self.table_len = table_len
                if 0 <= self.nodes_index < new_nodes_index:
                    self.nodes_index = new_nodes_index
            return self
        if len(refill_rows) > get_tracker().max_refill_rows(self.n_real):
            return None  # full rebuild is cheaper (refills only: the
            #              additive rows cost O(1) per new alloc)
        from ..chaos import chaos

        if chaos.enabled and chaos.fire(
                "matrix.stale_delta", rows=len(rows)) == "drop":
            # Injected staleness: one delta record is LOST — the row
            # keeps its previous values on host AND device (the scatter
            # below ships the un-recomputed row, so mirror and resident
            # tensor agree with each other and disagree with the
            # store). The plan applier's exact verification is the
            # safety net that must catch the resulting bad placement
            # and force a rebuild (models/resident.py note_rejection).
            lost = rows[0]
            refill_rows = [r for r in refill_rows if r != lost]
            adds = [a for a in adds if row_of[a.node_id] != lost]
            node_rows = [r for r in node_rows if r != lost]
        new = _ClusterBase.__new__(_ClusterBase)
        new.token = next(_BASE_TOKENS)
        new.nodes_token = self.nodes_token
        new.allocs_index = new_allocs_index
        new.table_len = table_len
        new.nodes_index = max(base_nodes_index, new_nodes_index)
        new.delta_parent = (self.token, tuple(rows))
        new.n_real, new.n = self.n_real, self.n
        # Node-level class index is alloc-independent: share it. The
        # topology tensor rides the same contract (a meta edit that
        # moved a group also moved the computed class, and the class
        # checks above already refused the row delta for that).
        new.class_ids, new.class_reps = self.class_ids, self.class_reps
        new.class_index = self.class_index
        new.topology = self.topology
        # Same profiled declaration site as __init__: delta clones ARE
        # the live pipeline's dominant base-build path, and an
        # unprofiled lock here would make the observatory's
        # 'models.matrix.positions' row cover only the rare full
        # rebuilds. Dead clones' stats retire on GC (profile
        # _register_lock), so snapshot churn never exhausts the
        # registry.
        from ..profile import ProfiledLock

        new._positions_lock = ProfiledLock("models.matrix.positions")
        new._positions = None  # patched below when the parent built one
        new._victims_lock = ProfiledLock("models.matrix.victims")
        new._victims = None  # likewise
        new._row_of = self._row_of
        new.capacity = self.capacity.copy()
        new.sched_capacity = self.sched_capacity.copy()
        new.util = self.util.copy()
        new.bw_avail = self.bw_avail.copy()
        new.bw_used = self.bw_used.copy()
        new.ports_free = self.ports_free.copy()
        new.node_ok = self.node_ok.copy()
        new.alloc_groups = list(self.alloc_groups)
        for i in refill_rows:
            new._fill_row(
                i, nodes[i],
                state.allocs_by_node_terminal(nodes[i].id, False))
        if node_rows:
            # The device delta scatters only the MUTABLE arrays
            # (util/bw_used/ports_free/node_ok); a node change that
            # moved capacity, reserved headroom, or link bandwidth
            # cannot be expressed as a row delta against the parent's
            # shared immutable arrays — rebuild instead. Readiness and
            # drain flips (the common transitions) leave these
            # untouched.
            nr = np.asarray(node_rows, np.intp)
            if (not np.array_equal(new.capacity[nr], self.capacity[nr])
                    or not np.array_equal(new.sched_capacity[nr],
                                          self.sched_capacity[nr])
                    or not np.array_equal(new.bw_avail[nr],
                                          self.bw_avail[nr])):
                return None
        add_rows = [row_of[a.node_id] for a in adds]
        if adds:
            # Additive rows: one bulk scatter-add of the new allocs'
            # memoized usage — O(new allocs), not O(rows x allocs).
            ridx = np.asarray(add_rows, np.intp)
            ua = np.asarray([_alloc_usage(a) for a in adds], np.float32)
            np.add.at(new.util, ridx, ua[:, :4])
            np.add.at(new.bw_used, ridx, ua[:, 4])
            np.subtract.at(new.ports_free, ridx, ua[:, 5])
            for a, i in zip(adds, add_rows):
                # Copy-on-write: the parent's row list stays untouched.
                if new.alloc_groups[i] is self.alloc_groups[i]:
                    new.alloc_groups[i] = list(self.alloc_groups[i])
                new.alloc_groups[i].append((a.job_id, a.task_group))
        patched = new._patch_positions(self, adds, add_rows, refill_rows)
        new._patch_victims(self, rows, nodes, state)
        # Counted after the chaos drop above: what was really written.
        new.delta_stats = {"rows": len(set(add_rows) | set(refill_rows)),
                           "adds": len(adds),
                           "refills": len(refill_rows),
                           "patched_jobs": patched}
        get_tracker().count_delta(len(rows) - len(node_rows),
                                  len(node_rows), patched)
        return new

    def _patch_positions(self, parent: "_ClusterBase", adds, add_rows,
                         refill_rows) -> int:
        """Carry the parent's job-positions index forward at a cost of
        O(entries this delta wrote): the allocations it adds plus the
        entries of the rows it refills. Never O(jobs resident on the
        touched rows), never O(touched rows x allocations a row) per
        job, and no scan of the positions of a job that neither gains
        nor loses an entry.

        - An added allocation (`adds`, on the additive rows `add_rows`)
          changes its own (job, task group) alone, by one appended row;
          no other job on that row is looked at.
        - A refilled row (`refill_rows`: a pre-existing allocation
          changed, an eviction, a node flip) is read once, its old
          entries against its new: what leaves and what comes, counted
          per (job, task group), and only a pair whose count really
          differs is written.
        - What is written is an edit on the job's entry (_JobRows), not
          a new array: a standing job that loses one row of 59,000 is
          not scanned for it. Entries of jobs the delta did not write
          are the parent's objects; the dict that holds them is copied
          (26 us for 6,200 jobs: PERF.md section 6, PR 39).

        Returns the number of jobs whose entry was rewritten (the
        span's `patched_jobs`); 0 where the parent never built an index
        (this base stays lazy too)."""
        with parent._positions_lock:
            base_positions = parent._positions
        if base_positions is None:
            return 0
        come: Dict[str, Dict[str, List[int]]] = {}  # job -> tg -> rows
        gone: Dict[str, Dict[str, List[int]]] = {}
        for a, i in zip(adds, add_rows):
            come.setdefault(a.job_id, {}).setdefault(
                a.task_group, []).append(i)
        for i in refill_rows:
            moved = Counter(self.alloc_groups[i])
            moved.subtract(parent.alloc_groups[i])
            for (jid, tg), k in moved.items():
                if k:
                    (come if k > 0 else gone).setdefault(
                        jid, {}).setdefault(tg, []).extend([i] * abs(k))
        patched = dict(base_positions)
        written = come.keys() | gone.keys()
        for jid in written:
            patched[jid] = base_positions.get(jid, _NO_ROWS).edited(
                come.get(jid, {}), gone.get(jid, {}))
        # Publish under the lock: `self` is freshly built and unshared
        # in the current delta path, but the guarded-by contract on
        # _positions is unconditional — a future caller patching a
        # LIVE base would otherwise race job_positions' lazy build.
        with self._positions_lock:
            self._positions = patched
        return len(written)


# A positions entry folds its pending edits in by itself once they are
# more than this many plus a quarter of its arrays' length: the chain
# of edits of a standing job nobody reads stays bounded, at an
# amortised O(1) a written entry.
_FOLD_AFTER = 64


class _JobRows:
    """One job's node rows by task group (with repeats), as the arrays
    some ancestor base held and the edits of the deltas since. A delta
    writes down the rows that came and went, O(what it wrote), and
    never reads the arrays; the first reader folds them in (`rows`),
    and a delta after that starts from the folded arrays. Immutable
    but for that memo, so bases share entries freely."""

    __slots__ = ("arrays", "edits", "pending", "_folded")

    def __init__(self, arrays: Dict[str, np.ndarray], edits=None,
                 pending: int = 0):
        self.arrays = arrays    # shared with the ancestor, never written
        self.edits = edits      # (come, gone, older edits) or None
        self.pending = pending  # rows the edits hold
        self._folded = arrays if edits is None else None

    def edited(self, come: Dict[str, List[int]],
               gone: Dict[str, List[int]]) -> "_JobRows":
        """This job one delta on: `come` and `gone` are {tg: rows}."""
        n = sum(map(len, come.values())) + sum(map(len, gone.values()))
        folded = self._folded
        if folded is not None:
            new = _JobRows(folded, (come, gone, None), n)
        else:
            new = _JobRows(self.arrays, (come, gone, self.edits),
                           self.pending + n)
        if new.pending > _FOLD_AFTER + sum(
                map(len, new.arrays.values())) // 4:
            new.rows()
        return new

    def rows(self) -> Dict[str, np.ndarray]:
        folded = self._folded
        if folded is None:
            # Two readers may both fold: they get equal arrays, and the
            # memo is one store.
            folded = self._folded = _fold(self.arrays, self.edits)
        return folded


_NO_ROWS = _JobRows({})


def _fold(arrays: Dict[str, np.ndarray], edits) -> Dict[str, np.ndarray]:
    """`arrays` with a chain of edits applied, as multisets of rows: a
    task group left with no row is dropped."""
    come: Dict[str, List[int]] = {}
    gone: Dict[str, List[int]] = {}
    while edits is not None:
        plus, minus, edits = edits
        for tg, rows in plus.items():
            come.setdefault(tg, []).extend(rows)
        for tg, rows in minus.items():
            gone.setdefault(tg, []).extend(rows)
    out = {}
    for tg in list(arrays) + [tg for tg in come if tg not in arrays]:
        rows = arrays.get(tg)
        if tg in come:
            plus = np.asarray(come[tg], np.int64)
            rows = plus if rows is None else np.concatenate([rows, plus])
        if tg in gone:
            # Every row that went was there: the multiset difference.
            vals, counts = np.unique(rows, return_counts=True)
            gone_vals, gone_counts = np.unique(
                np.asarray(gone[tg], np.int64), return_counts=True)
            counts[np.searchsorted(vals, gone_vals)] -= gone_counts
            rows = np.repeat(vals, counts)
        if rows.size:
            out[tg] = rows
    return out


def _victim_candidates(allocs, job_id=None, max_priority=None):
    """A node's preemption candidates: its live allocations, lowest
    priority first (nomad_tpu/migrate victim_sort_key: the host list
    and the device tensor MUST agree on the order, because the kernel
    returns only a victim COUNT per placement), at most
    PREEMPT_MAX_VICTIMS of them. With a `job_id` that job's own are
    left out, with a `max_priority` those at or above it."""
    from ..migrate import victim_priority, victim_sort_key
    from ..ops.preempt import PREEMPT_MAX_VICTIMS

    cands = [a for a in allocs
             if not a.terminal_status()
             and (job_id is None or a.job_id != job_id)
             and (max_priority is None
                  or victim_priority(a) < max_priority)]
    cands.sort(key=victim_sort_key)
    return cands[:PREEMPT_MAX_VICTIMS]


class _VictimTable:
    """Per node, the V lowest-priority live allocations in victim
    order with their footprints: what ops/preempt.py's VictimState
    holds, for every job and every priority at once, and in its
    layout: the NODE axis last, so that a pass hands the arrays to the
    device as they are. Which of a node's entries a given eval may
    evict is a prefix of them (priorities ascend), so an eval masks
    `ok` by its own priority and patches only the nodes its own job or
    plan touches (ClusterMatrix.build_victims).
    Immutable once built: evals of one batch share it."""

    __slots__ = ("res", "bw", "ports", "prio", "ok", "lists")

    def __init__(self, res, bw, ports, prio, ok, lists: List):
        self.res, self.bw, self.ports = res, bw, ports  # [4,V,N], [V,N] x2
        self.prio, self.ok = prio, ok  # [V,N]; padding: +inf, False
        self.lists = lists  # row -> ordered Allocations, or None

    @classmethod
    def build(cls, n: int, nodes, state) -> "_VictimTable":
        from ..ops.preempt import PREEMPT_MAX_VICTIMS as V

        table = cls(np.zeros((4, V, n), np.float32),
                    np.zeros((V, n), np.float32),
                    np.zeros((V, n), np.float32),
                    np.full((V, n), np.inf, np.float32),
                    np.zeros((V, n), bool), [None] * len(nodes))
        table._set_rows({
            i: _victim_candidates(
                state.allocs_by_node_terminal(node.id, False))
            for i, node in enumerate(nodes)})
        return table

    def with_rows(self, cands_by_row: Dict[int, List[Allocation]],
                  ok: Optional[np.ndarray] = None) -> "_VictimTable":
        """A copy in which the given rows hold the given candidates
        (and `ok` is the caller's own mask, which it may write to)."""
        new = _VictimTable(
            self.res.copy(), self.bw.copy(), self.ports.copy(),
            self.prio.copy(), self.ok.copy() if ok is None else ok,
            list(self.lists))
        new._set_rows(cands_by_row)
        return new

    def _set_rows(self, cands_by_row: Dict[int, List[Allocation]]) -> None:
        """Write the given rows: one bulk assignment of every
        candidate's memoized usage."""
        from ..migrate import victim_priority

        rows: List[int] = []
        slots: List[int] = []
        flat: List[Allocation] = []
        for i, cands in cands_by_row.items():
            self.lists[i] = cands or None
            rows.extend([i] * len(cands))
            slots.extend(range(len(cands)))
            flat.extend(cands)
        touched = np.fromiter(cands_by_row, np.intp, len(cands_by_row))
        self.res[..., touched] = 0.0
        self.bw[:, touched] = 0.0
        self.ports[:, touched] = 0.0
        self.prio[:, touched] = np.inf
        self.ok[:, touched] = False
        if not flat:
            return
        r, v = np.asarray(rows, np.intp), np.asarray(slots, np.intp)
        usage = np.asarray([_alloc_usage(a) for a in flat], np.float32)
        self.res[:, v, r] = usage[:, :4].T
        self.bw[v, r] = usage[:, 4]
        self.ports[v, r] = usage[:, 5]
        self.prio[v, r] = [victim_priority(a) for a in flat]
        self.ok[v, r] = True


def compute_class_index(nodes) -> Tuple[np.ndarray, List[int]]:
    """Node -> computed-class index: ids[i] is the class number of
    nodes[i] (-1 = classless), class_reps[c] a representative row."""
    ids = np.full(len(nodes), -1, np.int32)
    reps: List[int] = []
    index: Dict[str, int] = {}
    for i, node in enumerate(nodes):
        cls = node.computed_class
        if not cls:
            continue
        ci = index.get(cls)
        if ci is None:
            ci = len(reps)
            index[cls] = ci
            reps.append(i)
        ids[i] = ci
    return ids, reps


# Ready-node class index cached per snapshot node set: every system
# eval of a storm sees the same ready nodes, and the O(N) class walk
# per eval would otherwise dominate the vectorized diff.
_CLASS_INDEX_CACHE: Dict[Tuple, Tuple[np.ndarray, List[int]]] = {}
_CLASS_INDEX_MAX = 4

# Feasibility memo per (node axis, job constraint signature): the
# [N, G] mask depends only on the nodes (their computed classes and,
# for escaped constraints and classless nodes, their own attributes)
# and the job's constraint/driver STRUCTURE: not on its id, and not on
# a single allocation. So it is keyed on the base's `nodes_token`, which
# a delta clone inherits: a commit mints a new base token and leaves the
# masks where they are, and the next eval of each signature finds its
# mask instead of paying one ConstraintChecker pass per class per
# constraint under the GIL and the expansion over N again. A rebuild of
# the node axis (a register, a deregister, a meta edit that moves a
# computed class) mints a new nodes_token and the masks are built anew.
# A mask that reads per-node attributes (`per_node`) also pins the
# nodes-table index it was built at: a node's unique attributes can
# change under a row delta that keeps its class.
# Sized for the signatures a deployment has in flight on one or two
# node axes, not for one storm's single signature.
_FEAS_CACHE: Dict[Tuple, "_Mask"] = {}
_FEAS_MAX = 64


class _Mask:
    """One memoized feasibility mask: the padded [N, G] mask, and its
    compact form for the device-side expansion (ops/binpack.py
    CompactOverlay): the per-class verdicts padded to a class bucket
    and the sparse patch for rows the class verdict cannot represent
    (classless nodes, escaped constraints); `compact` is None where
    there are no classes or a part overflows its top bucket. Read-only
    once built: the evals of a batch share it."""

    __slots__ = ("feasible", "compact", "nodes_index", "idle")

    def __init__(self, feasible, compact, nodes_index):
        self.feasible = feasible
        self.compact = compact  # (verdicts, patch_rows, patch_vals)
        self.nodes_index = nodes_index  # None: class verdicts alone
        # The whole overlay of a job with NO live allocs (every job of
        # a placement storm, until its own plan commits), by job-rows
        # floor: (zero job_count, zero tg_count, compact overlay). It is
        # then a pure function of the mask, and its padded arrays are
        # identical across the batch: per-eval numpy materialization
        # was the residual cohort-window stagger after the mask memo.
        # Two evals that miss at once store equal values.
        self.idle: Dict[int, Tuple] = {}


def _constraint_sig(cons) -> Tuple:
    return tuple((c.ltarget, c.operand, c.rtarget) for c in cons)


def _constraint_scopes(job, groups):
    """The constraint lists node_feasibility reads: the job's, then
    each task group's and its tasks'."""
    yield job.constraints
    for tg in groups:
        yield tg.constraints
        for task in tg.tasks:
            yield task.constraints


def feasibility_signature(job) -> Tuple:
    """Hashable signature of everything node_feasibility reads from the
    job: job/TG/task constraints (order-sensitive, like the checkers)
    and the TG driver sets. Two jobs with equal signatures get
    identical masks on the same base."""
    tg_sigs = []
    for tg in job.task_groups:
        tg_sigs.append((
            _constraint_sig(tg.constraints),
            tuple(_constraint_sig(t.constraints) for t in tg.tasks),
            tuple(sorted({t.driver for t in tg.tasks})),
        ))
    return (_constraint_sig(job.constraints), tuple(tg_sigs))


# Full node UNIVERSE per (snapshot nodes-index, dc set): every node of
# the dc set regardless of readiness, plus the ready-only per-dc counts
# (metric parity with the host path) and an identity signature over the
# ordered node-id tuple. The resident dense path builds its matrix over
# THIS list — readiness is a node_ok row bit, so up/down/drain flips
# are delta records against the device-resident base instead of a
# rebuild of the node axis (models/resident.py). The signature keys the
# base FAMILY: it changes exactly when the node set (or its row order)
# changes, which is when a delta chain must break.
_UNIVERSE_CACHE: Dict[Tuple, Tuple[List[Node], Dict[str, int], int]] = {}
_UNIVERSE_MAX = 4


def universe_nodes_cached(state, datacenters):
    """(nodes, ready_by_dc, ids_sig) over the full dc node universe;
    memoized per snapshot nodes-index: the dispatch pipeline fans a
    whole batch out against one snapshot, and each eval's ClusterMatrix
    would otherwise re-walk all N node objects under the GIL."""
    key = None
    if hasattr(state, "index") and getattr(state, "store_id", ""):
        key = (state.store_id, state.index("nodes"),
               tuple(sorted(datacenters or [])))
        with _BASE_CACHE_LOCK:
            hit = _UNIVERSE_CACHE.get(key)
        if hit is not None:
            return hit
    dc_map = {dc: 0 for dc in (datacenters or [])}
    nodes: List[Node] = []
    for node in state.nodes():
        if node.datacenter not in dc_map:
            continue
        nodes.append(node)
        if node.ready():
            dc_map[node.datacenter] += 1
    out = (nodes, dc_map, hash(tuple(n.id for n in nodes)))
    if key is not None:
        with _BASE_CACHE_LOCK:
            while len(_UNIVERSE_CACHE) >= _UNIVERSE_MAX:
                _UNIVERSE_CACHE.pop(next(iter(_UNIVERSE_CACHE)))
            _UNIVERSE_CACHE[key] = out
    return out


def ready_class_index(state, nodes, dcs) -> Tuple[np.ndarray, List[int]]:
    key = None
    if hasattr(state, "index") and getattr(state, "store_id", ""):
        key = (state.store_id, state.index("nodes"),
               tuple(sorted(dcs or [])), len(nodes))
        with _BASE_CACHE_LOCK:
            cached = _CLASS_INDEX_CACHE.get(key)
        if cached is not None:
            return cached
    out = compute_class_index(nodes)
    if key is not None:
        with _BASE_CACHE_LOCK:
            while len(_CLASS_INDEX_CACHE) >= _CLASS_INDEX_MAX:
                _CLASS_INDEX_CACHE.pop(next(iter(_CLASS_INDEX_CACHE)))
            _CLASS_INDEX_CACHE[key] = out
    return out


def node_feasibility(state, job, groups, nodes, class_ids, class_reps,
                     return_verdicts: bool = False):
    """[len(nodes), G] constraint mask. Non-escaped job/TG constraints
    are evaluated ONCE PER COMPUTED CLASS on a representative node and
    numpy-expanded; escaped constraints and classless nodes fall back
    to per-node checks (node_class.go:70).

    With return_verdicts, returns (feasible, verdicts [C, G] or None):
    the per-class verdicts are the compact form the device-side overlay
    expansion consumes (ops/binpack.py CompactOverlay)."""
    n_real = len(nodes)
    g = len(groups)
    feasible = np.zeros((n_real, g), bool)
    ctx = EvalContext(state, Plan())

    job_cons = job.constraints
    job_escaped = escaped_constraints(job_cons)
    job_static = [c for c in job_cons if c not in job_escaped]

    per_group = []
    any_esc = bool(job_escaped)
    for tg in groups:
        cons = list(tg.constraints)
        drivers = set()
        for task in tg.tasks:
            cons.extend(task.constraints)
            drivers.add(task.driver)
        esc = escaped_constraints(cons)
        static = [c for c in cons if c not in esc]
        any_esc = any_esc or bool(esc)
        per_group.append((static, esc, drivers))

    job_checker = ConstraintChecker(ctx, job_static)
    cons_checker = ConstraintChecker(ctx)
    driver_checker = DriverChecker(ctx)
    esc_checker = ConstraintChecker(ctx)

    def static_row(node) -> np.ndarray:
        row = np.zeros(g, bool)
        if not job_checker.feasible(node):
            return row
        for gi, (static, _esc, drivers) in enumerate(per_group):
            driver_checker.set_drivers(drivers)
            cons_checker.set_constraints(static)
            row[gi] = (driver_checker.feasible(node)
                       and cons_checker.feasible(node))
        return row

    # One evaluation per class, expanded by numpy take.
    verdicts = None
    if class_reps:
        verdicts = np.stack([static_row(nodes[rep]) for rep in class_reps])
        ids = class_ids[:n_real]
        classed = ids >= 0
        feasible[classed] = verdicts[ids[classed]]
    # Classless nodes: individual evaluation (flatnonzero — a python
    # scan over 10k rows that are all classed would cost more than
    # the class pass saved).
    for i in np.flatnonzero(class_ids[:n_real] < 0):
        feasible[i] = static_row(nodes[i])
    # Escaped constraints reference unique per-node attrs: they can
    # never ride the class verdict (node_class.go:70) — walk only
    # the still-candidate rows.
    if any_esc:
        for i in np.flatnonzero(feasible.any(axis=1)):
            node = nodes[i]
            if job_escaped:
                esc_checker.set_constraints(job_escaped)
                if not esc_checker.feasible(node):
                    feasible[i] = False
                    continue
            for gi, (_static, esc, _drivers) in enumerate(per_group):
                if esc and feasible[i, gi]:
                    esc_checker.set_constraints(esc)
                    feasible[i, gi] = esc_checker.feasible(node)
    if return_verdicts:
        return feasible, verdicts
    return feasible


def bucket_size(n: int, buckets: List[int] = BUCKETS) -> int:
    i = bisect.bisect_left(buckets, max(n, 1))
    if i == len(buckets):
        # Beyond the largest bucket: round up to a multiple of the top.
        top = buckets[-1]
        return ((n + top - 1) // top) * top
    return buckets[i]


def empty_plan_patch(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """The plan patch of a lane whose plan touches nothing, over a node
    axis of `n`: the ladder's first rung, every row out of range."""
    return (np.full(PLAN_BUCKETS[0], n, np.int32),
            np.zeros((PLAN_BUCKETS[0], 6), np.float32))


def _alloc_usage(alloc: Allocation) -> Tuple[float, float, float, float, float, int]:
    """(cpu, mem, disk, iops, mbits, dyn_ports_in_range) consumed by one
    alloc — same accounting as AllocsFit (structs/funcs.go:72-94).

    Memoized on the alloc object: an alloc's usage never changes after
    creation (store writes replace the object), and every base rebuild
    across a storm re-reads the same allocs — the attribute-walk here
    was the top cost of the per-eval matrix build. Allocation.copy()
    drops the memo (a copy's resources may be rewritten, e.g. in-place
    updates)."""
    cached = alloc.__dict__.get("_dense_usage")
    if cached is not None:
        return cached
    cpu = mem = disk = iops = 0.0
    mbits = 0.0
    ports = 0
    resources: List[Resources] = []
    if alloc.resources is not None:
        resources.append(alloc.resources)
    else:
        if alloc.shared_resources is not None:
            resources.append(alloc.shared_resources)
        resources.extend(alloc.task_resources.values())
    for r in resources:
        cpu += r.cpu
        mem += r.memory_mb
        disk += r.disk_mb
        iops += r.iops
    # Network usage mirrors NetworkIndex.AddAllocs: first network of each
    # task's resources (structs/network.go:94-107).
    for tr in alloc.task_resources.values():
        if tr.networks:
            n0 = tr.networks[0]
            mbits += n0.mbits
            for p in list(n0.reserved_ports) + list(n0.dynamic_ports):
                if consts.MIN_DYNAMIC_PORT <= p.value < consts.MAX_DYNAMIC_PORT:
                    ports += 1
    usage = (cpu, mem, disk, iops, mbits, ports)
    alloc._dense_usage = usage
    return usage


def resolve_cluster_base(state, datacenters, nodes=None, explicit=False):
    """Resolve the job-independent cluster base for one (snapshot, dc
    set): exact-key cache hit, family delta-update, or full rebuild —
    single-flighted, since a drained batch's evals all build matrices
    CONCURRENTLY against one fresh snapshot (without the pending gate
    every thread misses at once and builds its own base with its own
    token, fragmenting the batcher's token-keyed queues AND paying one
    ~full base upload per thread; observed: 24 uploads of one identical
    10k-node base).

    Module-level (job-free) on purpose: the dispatch pipeline prefetches
    batch k+1's base under batch k's in-flight compute with no job in
    hand (dispatch/pipeline.py), and ClusterMatrix delegates here for
    its own build. With `nodes=None` the node list is the resident
    universe; `explicit` marks a caller's own list (the system path's
    pinned subsets), which keeps a family of its own.

    Returns (base, kind) with kind in "hit" | "rekey" | "delta" |
    "full". Family keying is the residency core: the universe's family
    keys on the node-SET identity instead of the nodes-table index, so
    node up/down/drain transitions (which bump the index but keep the
    set) delta against the previous base instead of starting a new
    family — the delta chain only breaks when nodes register/deregister
    (the universe signature moves)."""
    from .resident import get_tracker

    tracker = get_tracker()
    if nodes is None:
        nodes, _by_dc, _sig = universe_nodes_cached(state, datacenters)
    from ..scheduler.util import proposed_allocs_for_node

    def proposed_fn(node_id, _state=state):
        return proposed_allocs_for_node(_state, None, node_id)

    key = family = prev = done = None
    allocs_idx = nodes_idx = -1
    if hasattr(state, "index") and getattr(state, "store_id", ""):
        dcs = tuple(sorted(datacenters or []))
        # Caller-provided node lists (the system path's pinned
        # subsets) need their identity in the key: two different
        # subsets of equal size on one snapshot must not collide.
        # The universe is determined by (nodes index, dcs), so a
        # constant marker suffices there.
        nodes_sig = (hash(tuple(n.id for n in nodes)) if explicit else 0)
        nodes_idx = state.index("nodes")
        allocs_idx = state.index("allocs")
        key = (state.store_id, nodes_idx, allocs_idx, dcs,
               len(nodes), nodes_sig)
        if explicit:
            family = (state.store_id, nodes_idx, dcs,
                      len(nodes), nodes_sig)
        else:
            _unodes, _by_dc, usig = universe_nodes_cached(
                state, datacenters)
            family = (state.store_id, "resident", dcs, usig)
        if tracker.consume_stale():
            # A plan-apply rejection marked the resident chain suspect:
            # whatever matrix the scheduler planned against disagreed
            # with the store. The rejection doesn't say WHOSE state was
            # wrong, so purge every cached base (the exact-key entries
            # included — a rejected plan commits nothing, so the next
            # build may land on the SAME snapshot index and would
            # otherwise be served the poisoned entry) and pay one full
            # rebuild to re-anchor (models/resident.py counts it in
            # stale_rebuilds).
            with _BASE_CACHE_LOCK:
                global _BASE_EPOCH
                _BASE_EPOCH += 1
                _BASE_CACHE.clear()
                _BASE_FAMILY.clear()
        while True:
            with _BASE_CACHE_LOCK:
                cached = _BASE_CACHE.get(key)
                if cached is not None:
                    return cached, "hit"
                pending = _BASE_PENDING.get(key)
                if pending is None:
                    done = __import__("threading").Event()
                    _BASE_PENDING[key] = done
                    prev = _BASE_FAMILY.get(family)
                    epoch = _BASE_EPOCH
                    break
            pending.wait(60.0)
    base = None
    kind = "full"
    try:
        while True:
            if prev is not None and 0 <= prev.allocs_index <= allocs_idx:
                base = prev.delta_update(
                    nodes, state, allocs_idx,
                    new_nodes_index=-1 if explicit else nodes_idx)
                if base is prev:
                    kind = "rekey"
                elif base is not None:
                    kind = "delta"
            if base is None:
                table_len = (state.alloc_count()
                             if key is not None
                             and hasattr(state, "alloc_count") else -1)
                base = _ClusterBase(
                    nodes, proposed_fn,
                    allocs_index=allocs_idx if key is not None else -1,
                    table_len=table_len,
                    nodes_index=-1 if (key is None or explicit)
                    else nodes_idx)
                kind = "full"
                if key is not None:
                    tracker.count_full()
                    if not explicit and prev is None:
                        # No family base to delta from: first build, or
                        # the node SET itself changed (register/
                        # deregister) — the one transition that must
                        # re-anchor.
                        tracker.count_universe()
            if key is None:
                return base, kind
            with _BASE_CACHE_LOCK:
                # A full build derives from the snapshot alone, so it
                # is clean regardless of purges; a delta/rekey result
                # extends a pre-registration parent and is suspect if
                # a stale-purge landed since — checking the epoch
                # atomically with the store means an in-flight delta
                # can never re-seed a purged cache.
                if kind == "full" or epoch == _BASE_EPOCH:
                    while len(_BASE_CACHE) >= _BASE_CACHE_MAX:
                        _BASE_CACHE.pop(next(iter(_BASE_CACHE)))
                    _BASE_CACHE[key] = base
                    _BASE_FAMILY[family] = base
                    while len(_BASE_FAMILY) > _BASE_CACHE_MAX:
                        _BASE_FAMILY.pop(next(iter(_BASE_FAMILY)))
                    return base, kind
                epoch = _BASE_EPOCH
            prev = None
            base = None
    finally:
        if key is not None:
            with _BASE_CACHE_LOCK:
                _BASE_PENDING.pop(key, None)
            done.set()


def compress_stats() -> Optional[dict]:
    """How far the newest cluster base's fleet interned: the
    `matrix.compress` annotation (models/classes.py ClassIndex.stats)
    and `computed_classes`, the count the compact overlay's class
    bucket has to hold. None before any cacheable base was built.
    `/v1/agent/self` carries it as `matrix_compress`."""
    with _BASE_CACHE_LOCK:
        base = max(_BASE_FAMILY.values(), key=lambda b: b.token,
                   default=None)
    if base is None:
        return None
    return dict(base.class_index.stats(),
                computed_classes=len(base.class_reps))


class _BaseView:
    """A _ClusterBase under the attribute names the batcher's
    device-residency entry points expect (ClusterMatrix's surface) —
    what prefetch_cluster_base hands to PlacementBatcher.prefetch_base."""

    __slots__ = ("base_token", "base_delta", "delta_stats", "capacity",
                 "sched_capacity", "util", "bw_avail", "bw_used",
                 "ports_free", "node_ok", "class_ids")

    def __init__(self, base: "_ClusterBase"):
        self.base_token = base.token
        self.base_delta = base.delta_parent
        self.delta_stats = base.delta_stats
        self.capacity = base.capacity
        self.sched_capacity = base.sched_capacity
        self.util = base.util
        self.bw_avail = base.bw_avail
        self.bw_used = base.bw_used
        self.ports_free = base.ports_free
        self.node_ok = base.node_ok
        self.class_ids = base.class_ids


def prefetch_cluster_base(state, datacenters):
    """Resolve the cacheable cluster base for (snapshot, dc set) and
    return (view-or-None, kind) — the dispatch pipeline's double-buffer
    prefetch entry. The base is job-independent, so no job is needed;
    un-cacheable snapshots (no store identity) return None."""
    base, kind = resolve_cluster_base(state, datacenters)
    if base.allocs_index < 0:
        return None, kind
    return _BaseView(base), kind


class ClusterMatrix:
    """Dense view of the schedulable cluster for one job's placements."""

    def __init__(self, state, job: Job, plan: Optional[Plan] = None,
                 nodes: Optional[List[Node]] = None,
                 plan_overlay: bool = False, ask_floor: int = 0,
                 rows_floor: int = 0):
        """`plan`: where it already stops, evicts or has placed
        something, the node state is still the CACHED base of the
        snapshot, under its token, and what the plan changes is the
        matrix's `plan_patch`: the rows the plan touches and, a row,
        the proposed allocations' usage less the live ones' (scheduler/
        util.py proposed_allocs_for_node) on the six usage columns. The
        shared-base programs apply it to this lane's view alone
        (ops/binpack.py); the job's positions and counts are the
        proposed state's. No plan walks every node.

        `plan_overlay`: write that patch into copies of the host arrays
        instead, for a program that takes a dense state of its own (the
        preemption and gang passes; a caller's own node list, which the
        system path reads on the host, is written the same way). Such a
        matrix carries no base token: what it holds is no cached base's
        content.

        `ask_floor`: pad the asks and the job's alloc positions as for
        that many asks at least. An eval that retries on the dense path
        gives its first attempt's count, so that the retry (fewer asks,
        more positions) runs the programs the first attempt compiled.

        `rows_floor`: pad the job's alloc positions alone as for that
        many: the job's whole count, on the first attempt too, so that
        the positions bucket is the job's and not the attempt's."""
        self.state = state
        self.job = job
        self.plan = plan
        self._plan_overlay = plan_overlay
        self._ask_floor = ask_floor
        self._job_rows_floor = bucket_size(
            min(max(ask_floor, rows_floor), JOBPOS_BUCKETS[-1]),
            JOBPOS_BUCKETS)
        self._explicit_nodes = nodes is not None
        if nodes is None:
            # Resident universe: ALL dc nodes, readiness as the
            # node_ok row bit — up/down/drain flips become deltas
            # against the device-resident base instead of changing
            # the matrix shape (models/resident.py).
            nodes, by_dc, _sig = universe_nodes_cached(
                state, job.datacenters)
            self.nodes_by_dc = by_dc
        else:
            self.nodes_by_dc = {}
        self.nodes: List[Node] = nodes
        self.n_real = len(nodes)
        self.n = bucket_size(self.n_real)
        self.groups = job.task_groups
        self.g = len(self.groups)
        self._build()

    # ------------------------------------------------------------------

    def _proposed_allocs(self, node_id: str) -> List[Allocation]:
        from ..scheduler.util import proposed_allocs_for_node

        return proposed_allocs_for_node(self.state, self.plan, node_id)

    def _cached_base(self) -> "_ClusterBase":
        t0 = time.monotonic()
        base, self.build_kind = resolve_cluster_base(
            self.state, self.job.datacenters, nodes=self.nodes,
            explicit=self._explicit_nodes)
        # Set where this very build derived the delta (a replan on a
        # snapshot no prologue prefetched): (t0, t1, annotations) of the
        # span `base.delta`.
        self.base_delta_span = (
            (t0, time.monotonic(), base.delta_stats)
            if self.build_kind == "delta" else None)
        self.delta_rows = (len(base.delta_parent[1])
                           if self.build_kind == "delta"
                           and base.delta_parent else 0)
        return base

    def _build(self) -> None:
        n, g = self.n, self.g
        base = self._base = self._cached_base()
        if self.plan is not None and hasattr(self.state, "index"):
            # Any nodes/allocs change the matrix could have seen has
            # modify_index <= this watermark; anything later is an
            # optimistic race the applier must not blame on the
            # resident chain. max() keeps the strictest watermark when
            # several builds feed one plan — over-purging is safe,
            # under-purging is not.
            wm = max(self.state.index("allocs"), self.state.index("nodes"))
            if wm > self.plan.matrix_index:
                self.plan.matrix_index = wm
        # Share the immutable base arrays; the kernel never mutates its
        # inputs (functional scan carries copies).
        self.base_token = base.token
        self.base_delta = base.delta_parent
        self.capacity = base.capacity
        self.sched_capacity = base.sched_capacity
        self.util = base.util
        self.bw_avail = base.bw_avail
        self.bw_used = base.bw_used
        self.ports_free = base.ports_free
        self.node_ok = base.node_ok
        # Padded [N] class index: rides the device base upload so the
        # compact overlay's verdict expansion happens on device.
        self.class_ids = base.class_ids
        # Signature-class interning (models/classes.py): the defrag
        # solver's class-compressed solve reads this off the resolved
        # matrix.
        self.class_index = base.class_index
        # Node-topology tensor (models/topology.py) for the gang
        # program's slice/spread/affinity group ops.
        self.topology = base.topology

        # Job-specific overlay: this job's per-node alloc counts, from
        # the base's lazy positions index (O(this job's allocs)), moved
        # to the proposed state where the plan touches them.
        positions = base.job_positions(self.job.id)
        positions = self._build_plan_patch(positions)
        # Set by _build_feasibility where this eval really built a mask
        # (a memo miss): (t0, t1, annotations) of the span
        # `feasibility.build`.
        self.feas_build = None
        mask = self._build_feasibility(base)
        self.feasible = mask.feasible
        if (not positions and base.allocs_index >= 0
                and self.base_token is not None):
            # No live allocs (the storm shape): the whole overlay is
            # the mask's, shared across the batch and across commits.
            hit = mask.idle.get(self._job_rows_floor)
            if hit is None:
                self.job_count = np.zeros(n, np.int32)
                self.tg_count = np.zeros((n, g), np.int32)
                self._build_compact_overlay(mask, {})
                hit = mask.idle[self._job_rows_floor] = (
                    self.job_count, self.tg_count, self.compact_overlay)
            self.job_count, self.tg_count, self.compact_overlay = hit
            return
        job_count = np.zeros(n, np.int32)
        tg_count = np.zeros((n, g), np.int32)
        gi_by_name = {tg.name: gi for gi, tg in enumerate(self.groups)}
        for task_group, rows in positions.items():
            np.add.at(job_count, rows, 1)
            gi = gi_by_name.get(task_group)
            if gi is not None:
                np.add.at(tg_count[:, gi], rows, 1)
        self.job_count = job_count
        self.tg_count = tg_count
        self._build_compact_overlay(mask, positions)

    def _plan_rows(self) -> Dict[int, str]:
        """{row: node id} of the nodes this matrix's plan stops,
        evicts or places something on."""
        plan = self.plan
        if plan is None or plan.is_no_op():
            return {}
        row_of = self._base.row_of(self.nodes)
        return {row_of[nid]: nid
                for nid in (set(plan.node_update) | set(plan.node_allocation)
                            | set(plan.node_preemptions))
                if nid in row_of}

    def _build_plan_patch(self, positions):
        """What this matrix's plan changes, stated against the cached
        base: each row the plan touches moves by the proposed
        allocations' usage less the live ones' (live allocations less
        the plan's stops and victims plus its placements, scheduler/
        util.py proposed_allocs_for_node), as a base's delta adds a new
        allocation's usage; every other row IS the cached base's.
        Sets `plan_patch`, (rows [P] int32 padded with n, values [P, 6]
        float32: cpu, memory, disk, iops, bandwidth, dynamic ports), the
        empty one for a plan that touches nothing; `plan_patch_span` is
        (t0, t1, annotations) of the span `matrix.plan_patch` where it
        touches something. Returns the job's positions in the proposed
        state: a stopped allocation no longer counts on its node."""
        self.plan_patch_span = None
        rows = self._plan_rows()
        if not rows:
            self.plan_patch = empty_plan_patch(self.n)
            return positions
        t0 = time.monotonic()

        def total(allocs) -> np.ndarray:
            return np.asarray([_alloc_usage(a) for a in allocs],
                              np.float64).reshape(-1, 6).sum(axis=0)

        idx = np.fromiter(rows, np.int64, len(rows))
        moved = np.zeros((len(rows), 6), np.float32)
        mine: Dict[str, List[int]] = {}
        for k, (i, nid) in enumerate(rows.items()):
            proposed = self._proposed_allocs(nid)
            moved[k] = total(proposed) - total(
                self.state.allocs_by_node_terminal(nid, False))
            for a in proposed:
                if a.job_id == self.job.id:
                    mine.setdefault(a.task_group, []).append(i)
        proposed_positions = {}
        for tg in set(positions) | set(mine):
            kept = positions.get(tg, np.zeros(0, np.int64))
            kept = np.concatenate([
                kept[~np.isin(kept, idx)],
                np.asarray(mine.get(tg, ()), np.int64)])
            if len(kept):
                proposed_positions[tg] = kept
        bucket = bucket_size(len(idx), PLAN_BUCKETS)
        # Pad with self.n: out of range, dropped by the device scatter.
        p_rows = np.full(bucket, self.n, np.int32)
        p_rows[: len(idx)] = idx
        p_vals = np.zeros((bucket, 6), np.float32)
        p_vals[: len(idx)] = moved
        self.plan_patch = (p_rows, p_vals)
        if (self._plan_overlay or self._explicit_nodes
                or len(idx) > PLAN_BUCKETS[-1]):
            # A dense state of this matrix's own: the patch goes into
            # copies of the columns (the base's are shared).
            self.util, self.bw_used, self.ports_free = \
                self.proposed_columns()
            self.base_token = self.base_delta = self.plan_patch = None
            bucket = 0
        self.plan_patch_span = (t0, time.monotonic(),
                                {"rows": len(idx), "bucket": bucket})
        return proposed_positions

    def proposed_columns(self) -> Tuple[np.ndarray, ...]:
        """(util [N, 4], bw_used [N], ports_free [N]) as this matrix's
        plan leaves them: copies of the base's columns with the patch
        put in, as the shared-base programs put it into a lane's view
        (ops/binpack.py _patched)."""
        util = np.array(self.util)
        bw_used = np.array(self.bw_used)
        ports_free = np.array(self.ports_free)
        if self.plan_patch is not None:
            rows, vals = self.plan_patch
            live = rows < self.n
            util[rows[live]] += vals[live, :4]
            bw_used[rows[live]] += vals[live, 4]
            ports_free[rows[live]] -= vals[live, 5]
        return util, bw_used, ports_free

    def _build_compact_overlay(self, mask: "_Mask", positions) -> None:
        """The pre-expansion overlay (ops/binpack.py CompactOverlay):
        the mask's compact form (per-class verdicts + a sparse patch,
        memoized with the mask) and this job's alloc row positions — a
        few KB per eval instead of the ~100KB x G dense overlay at 10k
        nodes. None (dense fallback) when the base isn't
        device-cacheable or any component overflows its top padding
        bucket."""
        self.compact_overlay = None
        if self.base_token is None or mask.compact is None:
            return
        # This job's alloc positions, flattened with their TG indices.
        gi_by_name = {tg.name: gi for gi, tg in enumerate(self.groups)}
        rows_parts: List[np.ndarray] = []
        tg_parts: List[np.ndarray] = []
        n_pos = 0
        for task_group, rows in positions.items():
            gi = gi_by_name.get(task_group)
            if gi is None:
                continue
            rows_parts.append(rows)
            tg_parts.append(np.full(len(rows), gi, np.int64))
            n_pos += len(rows)
        if n_pos > JOBPOS_BUCKETS[-1]:
            return
        j_pad = max(bucket_size(n_pos, JOBPOS_BUCKETS),
                    self._job_rows_floor)
        # Pad with self.n: out of range, dropped by the device scatter.
        j_rows = np.full(j_pad, self.n, np.int32)
        j_tgs = np.zeros(j_pad, np.int32)
        if n_pos:
            j_rows[:n_pos] = np.concatenate(rows_parts)
            j_tgs[:n_pos] = np.concatenate(tg_parts)
        from ..ops.binpack import CompactOverlay

        verd, p_rows, p_vals = mask.compact
        self.compact_overlay = CompactOverlay(
            verdicts=verd, patch_rows=p_rows, patch_vals=p_vals,
            job_rows=j_rows, job_tgs=j_tgs)

    def _compact_mask(self, base, feasible, verdicts):
        """The compact form of one mask, or None: the class verdicts
        padded to a class bucket, and as patch rows wherever the real
        mask differs from their expansion (classless nodes, escaped
        constraints)."""
        if verdicts is None or len(base.class_reps) > CLASS_BUCKETS[-1]:
            return None
        n_real, g = self.n_real, self.g
        ids = base.class_ids[:n_real]
        expected = np.zeros((n_real, g), bool)
        classed = ids >= 0
        expected[classed] = verdicts[ids[classed]]
        feas_real = feasible[:n_real]
        patch_rows = np.flatnonzero((feas_real != expected).any(axis=1))
        if len(patch_rows) > PATCH_BUCKETS[-1]:
            return None
        c_pad = bucket_size(max(len(base.class_reps), 1), CLASS_BUCKETS)
        p_pad = bucket_size(len(patch_rows), PATCH_BUCKETS) \
            if len(patch_rows) else PATCH_BUCKETS[0]
        verd = np.zeros((c_pad, g), bool)
        verd[: len(verdicts)] = verdicts
        # Pad with self.n: out of range, dropped by the device scatter.
        p_rows = np.full(p_pad, self.n, np.int32)
        p_rows[: len(patch_rows)] = patch_rows
        p_vals = np.zeros((p_pad, g), bool)
        p_vals[: len(patch_rows)] = feas_real[patch_rows]
        return verd, p_rows, p_vals

    def _build_feasibility(self, base) -> "_Mask":
        """The job's mask over this base's nodes; see node_feasibility.
        Memoized per (node axis, job constraint signature): a storm's
        structurally identical jobs share one mask computation per
        rebuild of the node axis, whatever commits in between (the
        memo'd arrays are treated as immutable by every consumer)."""
        key = nodes_index = None
        if base.allocs_index >= 0:  # cacheable bases only
            key = (base.nodes_token, feasibility_signature(self.job))
            nodes_index = self.state.index("nodes")
            with _BASE_CACHE_LOCK:
                hit = _FEAS_CACHE.get(key)
            if hit is not None and hit.nodes_index in (None, nodes_index):
                return hit
        t0 = time.monotonic()
        feasible = np.zeros((self.n, self.g), bool)
        ids = base.class_ids[: self.n_real]
        real, verdicts = node_feasibility(
            self.state, self.job, self.groups, self.nodes, ids,
            base.class_reps, return_verdicts=True)
        feasible[: self.n_real] = real
        cons = [c for scope in _constraint_scopes(self.job, self.groups)
                for c in scope]
        escaped = len(escaped_constraints(cons))
        per_node = bool(escaped) or bool((ids < 0).any())
        mask = _Mask(feasible, self._compact_mask(base, feasible, verdicts),
                     nodes_index if per_node else None)
        self.feas_build = (t0, time.monotonic(), {
            "classes": len(base.class_reps), "groups": self.g,
            "constraints": len(cons), "escaped": escaped})
        if key is not None:
            with _BASE_CACHE_LOCK:
                _FEAS_CACHE.pop(key, None)
                while len(_FEAS_CACHE) >= _FEAS_MAX:
                    _FEAS_CACHE.pop(next(iter(_FEAS_CACHE)))
                _FEAS_CACHE[key] = mask
        return mask

    # ------------------------------------------------------------------

    def build_asks(self, placements) -> Tuple[np.ndarray, ...]:
        """Convert an ordered list of (tg_index) placements into padded
        ask arrays. placements: list of task-group indices."""
        k_real = len(placements)
        k = bucket_size(max(k_real, self._ask_floor), ASK_BUCKETS)
        resources = np.zeros((k, 4), np.float32)
        bw = np.zeros(k, np.float32)
        ports = np.zeros(k, np.float32)
        tg_index = np.zeros(k, np.int32)
        active = np.zeros(k, bool)

        group_sizes = []
        for tg in self.groups:
            cpu = mem = iops = 0.0
            disk = tg.ephemeral_disk.size_mb if tg.ephemeral_disk else 0
            mbits = 0.0
            nports = 0
            for task in tg.tasks:
                r = task.resources
                cpu += r.cpu
                mem += r.memory_mb
                disk += r.disk_mb
                iops += r.iops
                if r.networks:
                    mbits += r.networks[0].mbits
                    nports += len(r.networks[0].dynamic_ports) + len(
                        r.networks[0].reserved_ports
                    )
            group_sizes.append((cpu, mem, disk, iops, mbits, nports))

        for j, gi in enumerate(placements):
            cpu, mem, disk, iops, mbits, nports = group_sizes[gi]
            resources[j] = (cpu, mem, disk, iops)
            bw[j] = mbits
            ports[j] = nports
            tg_index[j] = gi
            active[j] = True

        job_dh = any(
            c.operand == consts.CONSTRAINT_DISTINCT_HOSTS for c in self.job.constraints
        )
        tg_dh = np.array(
            [
                any(c.operand == consts.CONSTRAINT_DISTINCT_HOSTS for c in tg.constraints)
                for tg in self.groups
            ],
            bool,
        )
        return resources, bw, ports, tg_index, active, job_dh, tg_dh

    def build_victims(self, max_priority: int):
        """Per-node preemption candidates for ops/preempt.py: the V
        lowest-priority live allocations on each real node, sorted
        priority-ascending, strictly below ``max_priority`` (the
        preempting eval's) and never this job's own.

        Looked up in the table the snapshot's cached base keeps
        (_VictimTable: every job's and priority's candidates, built
        once per base): this eval masks it by its priority, and only
        the rows where its own job runs or its plan stops, evicts or
        places something are derived again from the store, exactly.

        Returns (victim_arrays, victims_of, total): victim_arrays feed
        make_victim_state (node axis last: [4,V,N] and [V,N]),
        victims_of(row) is the row's ordered Allocation list (the
        commit loop maps the kernel's victim COUNT back to its next
        unconsumed entries), total the number of candidates; nodes
        beyond n_real are padding."""
        base = self._base
        table = base.victim_table(self.nodes, self.state)
        res, bw, ports, prio = table.res, table.bw, table.ports, table.prio
        ok = table.ok & (prio < max_priority)
        lists = table.lists

        patch = {int(i) for rows in base.job_positions(self.job.id).values()
                 for i in rows} | set(self._plan_rows())
        if patch:
            own = table.with_rows({
                i: _victim_candidates(
                    self._proposed_allocs(self.nodes[i].id),
                    self.job.id, max_priority)
                for i in patch}, ok)
            res, bw, ports, prio = own.res, own.bw, own.ports, own.prio
            lists = own.lists

        def victims_of(row: int) -> List[Allocation]:
            return lists[row] or []

        return (res, bw, ports, prio, ok), victims_of, int(ok.sum())
