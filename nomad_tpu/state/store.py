"""MVCC in-memory state store.

Reference: nomad/state/state_store.go:35 (StateStore over go-memdb's
immutable radix trees) and nomad/state/schema.go:18-40 (tables: nodes,
jobs, job_summary, periodic_launch, evals, allocs, index).

Design: a table (and a secondary index) is two levels, a top of
`_FANOUT` buckets chosen by the key's hash over plain dicts, treated
as immutable-after-snapshot. `snapshot()` marks every table shared and
returns views that hold the tops by reference, in O(1); the first
write after it copies the top (a thousand references) and the bucket
the key lives in, and every later write to a bucket copied since goes
in place: copy-on-write at the granularity of what is written, so a
write transaction copies O(keys written x bucket), never O(table)
(go-memdb copies a path of its radix tree; two levels are what a
Python dict makes cheap). Records are never mutated in place once
inserted (writers insert fresh copies), so snapshots are stable
without locking, which is what lets N scheduling workers read while
the FSM writes (the reference's lock-free MVCC property, SURVEY.md
section 2.3).

Iteration order: a bucketed table lists its rows bucket by bucket,
which is no order a caller may rely on (the index sets never had one).
The `nodes` table alone is ONE bucket and so keeps insertion order:
`nodes()` fixes the row order of the cluster base (models/matrix.py
`universe_nodes_cached`). A write to it after a snapshot copies it
whole, as every table's did; nodes are written by registrations and
status changes, not by plans.

What a write transaction copied and wrote is counted (`_WriteStats`:
`StateStore.write_stats()`, the `state_store` block of
`server.stats()`; `StateStore.last_write`, the `copied` / `written`
annotations of the span `fsm.alloc_upsert`).
"""

from __future__ import annotations

import bisect
import contextlib
import copy as _copy
import threading
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..structs import (
    Allocation,
    Evaluation,
    Job,
    JobSummary,
    Node,
    TaskGroupSummary,
    consts,
)
from . import watch


@dataclass
class PeriodicLaunch:
    id: str = ""
    launch: float = 0.0  # unix time of last launch
    create_index: int = 0
    modify_index: int = 0


# Buckets a table or an index spreads its keys over (a power of two:
# the bucket is the hash's low bits). 213,000 allocations are some 200
# a bucket; a write after a snapshot copies the top once and one
# bucket a key.
_FANOUT = 1024

# Every bucket nobody has written: shared by all tops and never
# written (a writer copies a bucket it does not own first).
_EMPTY: Dict[str, object] = {}


class _WriteStats:
    """What the store's write transactions copied and wrote, counted
    by the tables themselves (plain ints under the store's lock)."""

    __slots__ = ("write_txns", "entries_copied", "buckets_copied",
                 "entries_written", "largest_txn_copy")

    def __init__(self):
        self.write_txns = 0
        # Entries copied before a write could go in place: a top's
        # references, a bucket's entries, an index set's members.
        self.entries_copied = 0
        self.buckets_copied = 0
        self.entries_written = 0
        self.largest_txn_copy = 0


class _Buckets:
    """The read side of a table or an index, and what a snapshot holds
    of one: the top by reference and the count at that moment."""

    __slots__ = ("top", "mask", "count")

    def __init__(self, top: List[Dict[str, object]], count: int):
        self.top = top
        self.mask = len(top) - 1
        self.count = count

    def get(self, key: str, default=None):
        return self.top[hash(key) & self.mask].get(key, default)

    def __contains__(self, key: str) -> bool:
        return key in self.top[hash(key) & self.mask]

    def __len__(self) -> int:
        return self.count

    def rows(self, keys: Iterable[str]) -> List[object]:
        """The rows under `keys`, each of which the table holds."""
        top, mask = self.top, self.mask
        return [top[hash(k) & mask][k] for k in keys]

    def values(self) -> List[object]:
        out: List[object] = []
        if not self.count:
            return out  # node registrations list an empty jobs table
        for bucket in self.top:
            if bucket:
                out.extend(bucket.values())
        return out


class _Table(_Buckets):
    """A table the store writes. `_own` names the buckets created or
    copied since the last `share()`: no snapshot can hold those, so
    they are written in place."""

    __slots__ = ("shared", "_own", "_stats")

    def __init__(self, stats: _WriteStats, fanout: int):
        super().__init__([_EMPTY] * fanout, 0)
        self.shared = False
        self._own: Set[int] = set()
        self._stats = stats

    def _bucket(self, key: str) -> Dict[str, object]:
        """The bucket `key` lives in, the writer's own: after a
        `share()` the top is copied once, and a bucket on its first
        write since."""
        n = hash(key) & self.mask
        stats = self._stats
        if self.shared:
            # The new top is published whole: a lock-free point read
            # sees the old top or the new, both complete.
            self.top = list(self.top)
            self._own = set()
            self.shared = False
            stats.entries_copied += len(self.top)
        elif n in self._own:
            return self.top[n]
        held = self.top[n]
        bucket = self.top[n] = dict(held)
        self._own.add(n)
        if held:
            stats.entries_copied += len(held)
            stats.buckets_copied += 1
        return bucket

    def put(self, key: str, value) -> None:
        bucket = self._bucket(key)
        if key not in bucket:
            self.count += 1
        bucket[key] = value
        self._stats.entries_written += 1

    def pop(self, key: str):
        """Remove and return the row under `key`, None where there is
        none (and then nothing is copied)."""
        if key not in self:
            return None
        self.count -= 1
        self._stats.entries_written += 1
        return self._bucket(key).pop(key)

    def share(self) -> _Buckets:
        self.shared = True
        return _Buckets(self.top, self.count)


class _Index(_Table):
    """Secondary index: key -> frozenset-ish of ids, copy-on-write.

    Below the bucket the granularity is per-SET: `_fresh` names the
    keys whose set was created or copied since the last `share()` (no
    snapshot can hold those, so they mutate in place). Without this,
    every `add` under one hot key (500k allocs of one job) copies the
    whole growing set and a bulk load goes quadratic.
    """

    __slots__ = ("_fresh",)

    def __init__(self, stats: _WriteStats):
        super().__init__(stats, _FANOUT)
        self._fresh: Set[str] = set()

    def add(self, key: str, id_: str) -> None:
        bucket = self._bucket(key)
        self._stats.entries_written += 1
        cur = bucket.get(key)
        if cur is None:
            bucket[key] = {id_}
            self.count += 1
        elif key in self._fresh:
            cur.add(id_)  # private since last share(): mutate in place
            return
        else:
            bucket[key] = cur | {id_}  # copy: snapshots may hold cur
            self._stats.entries_copied += len(cur)
        self._fresh.add(key)

    def remove(self, key: str, id_: str) -> None:
        cur = self.get(key)
        if not cur or id_ not in cur:
            return
        bucket = self._bucket(key)
        self._stats.entries_written += 1
        if key in self._fresh:
            cur.discard(id_)
        else:
            self._stats.entries_copied += len(cur)
            cur = bucket[key] = cur - {id_}  # copy: snapshots may hold cur
            self._fresh.add(key)
        if not cur:
            del bucket[key]
            self.count -= 1
            self._fresh.discard(key)

    def share(self) -> _Buckets:
        self._fresh.clear()
        return super().share()


# Allocation writes the journal keeps before it drops its older half.
# The cluster base's delta asks only for what was written since the
# newest base, a commit or a few; the cap is what the list may cost
# (two words an entry), not a tuning knob.
_ALLOC_JOURNAL_CAP = 1 << 18


class _AllocJournal:
    """Append-only record of allocation writes, `(index, alloc id)` in
    index order, so that a reader of a snapshot finds what changed
    since an earlier index without walking the table
    (StateSnapshot.allocs_changed_since; models/matrix.py delta_update).
    Derived state: no part of the raft snapshot, nothing replicated.

    Snapshots take `view()`, the lists by reference with their length
    at that moment, so they never see a later entry; a trim REPLACES
    the lists rather than mutating what a snapshot holds. `floor` is
    the highest index below which entries may be missing."""

    __slots__ = ("indexes", "ids", "floor")

    def __init__(self, floor: int = 0):
        self.indexes: List[int] = []
        self.ids: List[str] = []
        self.floor = floor

    def record(self, index: int, ids: List[str]) -> None:
        if self.indexes and index < self.indexes[-1]:
            # Out of index order (raft never does this): the bisect
            # below would lie, so forget what came before.
            self.floor = self.indexes[-1]
            self.indexes, self.ids = [], []
        self.indexes.extend([index] * len(ids))
        self.ids.extend(ids)
        if len(self.ids) > _ALLOC_JOURNAL_CAP:
            # Drop the older half: O(cap) once in cap/2 appends.
            cut = len(self.ids) // 2
            self.floor = self.indexes[cut - 1]
            self.indexes = self.indexes[cut:]
            self.ids = self.ids[cut:]

    def view(self) -> Tuple[List[int], List[str], int, int]:
        return self.indexes, self.ids, len(self.ids), self.floor


TABLES = (
    "nodes",
    "jobs",
    "job_summary",
    "periodic_launch",
    "evals",
    "allocs",
    "vault_accessors",
)


def _jobs_table(items) -> tuple:
    """("jobs",) where a txn's watch items say that `_set_job_status`
    rewrote a job, for the txn's one `_bump`."""
    return ("jobs",) if watch.table("jobs") in items else ()


class StateSnapshot:
    """Immutable point-in-time view with the scheduler's read interface
    (scheduler.State, reference scheduler/scheduler.go:55)."""

    def __init__(self, tables, indexes, table_indexes, latest,
                 alloc_journal, store_id: str = ""):
        self._t = tables
        self._i = indexes
        self._table_indexes = table_indexes
        self._latest = latest
        self._alloc_journal = alloc_journal  # _AllocJournal.view()
        # Identity of the owning store: table indexes alone are not
        # unique across stores in one process (tests, multi-server),
        # so caches keyed on indexes must include this.
        self.store_id = store_id

    # -- index queries --
    def latest_index(self) -> int:
        return self._latest

    def index(self, table: str) -> int:
        return self._table_indexes.get(table, 0)

    # -- nodes --
    def node_by_id(self, node_id: str) -> Optional[Node]:
        return self._t["nodes"].get(node_id)

    def nodes(self) -> List[Node]:
        return self._t["nodes"].values()

    # -- jobs --
    def job_by_id(self, job_id: str) -> Optional[Job]:
        return self._t["jobs"].get(job_id)

    def jobs(self) -> List[Job]:
        return self._t["jobs"].values()

    def jobs_by_scheduler(self, scheduler_type: str) -> List[Job]:
        return [j for j in self._t["jobs"].values() if j.type == scheduler_type]

    def jobs_by_periodic(self, periodic: bool = True) -> List[Job]:
        return [j for j in self._t["jobs"].values() if j.is_periodic() == periodic]

    def job_summary_by_id(self, job_id: str) -> Optional[JobSummary]:
        return self._t["job_summary"].get(job_id)

    # -- periodic launches --
    def periodic_launch_by_id(self, job_id: str) -> Optional[PeriodicLaunch]:
        return self._t["periodic_launch"].get(job_id)

    def periodic_launches(self) -> List[PeriodicLaunch]:
        return self._t["periodic_launch"].values()

    # -- evals --
    def eval_by_id(self, eval_id: str) -> Optional[Evaluation]:
        return self._t["evals"].get(eval_id)

    def evals(self) -> List[Evaluation]:
        return self._t["evals"].values()

    def evals_by_job(self, job_id: str) -> List[Evaluation]:
        ids = self._i["evals_by_job"].get(job_id, ())
        return self._t["evals"].rows(ids)

    # -- allocs --
    def alloc_by_id(self, alloc_id: str) -> Optional[Allocation]:
        return self._t["allocs"].get(alloc_id)

    def allocs(self) -> List[Allocation]:
        return self._t["allocs"].values()

    def alloc_count(self) -> int:
        """O(1) allocs-table size (delta caches detect GC deletions by
        comparing it; listing 50k allocs to count them would defeat the
        point)."""
        return len(self._t["allocs"])

    def allocs_changed_since(self, index: int) -> Optional[List[Allocation]]:
        """The distinct allocations, as this snapshot holds them, whose
        modify_index is above `index`, in the order they were first
        written since; None where the journal does not reach back to
        `index` (trimmed, or a restored store). One that was written
        and then collected is skipped. A bisect plus O(entries since
        `index`), where allocs() is O(table)."""
        indexes, ids, length, floor = self._alloc_journal
        if index < floor:
            return None
        table = self._t["allocs"]
        start = bisect.bisect_right(indexes, index, 0, length)
        changed = (table.get(i) for i in dict.fromkeys(ids[start:length]))
        return [a for a in changed if a is not None]

    def allocs_by_job(self, job_id: str) -> List[Allocation]:
        ids = self._i["allocs_by_job"].get(job_id, ())
        return self._t["allocs"].rows(ids)

    def allocs_by_node(self, node_id: str) -> List[Allocation]:
        ids = self._i["allocs_by_node"].get(node_id, ())
        return self._t["allocs"].rows(ids)

    def allocs_by_node_terminal(self, node_id: str, terminal: bool) -> List[Allocation]:
        return [
            a for a in self.allocs_by_node(node_id) if a.terminal_status() == terminal
        ]

    def vault_accessors(self) -> List[object]:
        return self._t["vault_accessors"].values()

    def vault_accessors_by_alloc(self, alloc_id: str) -> List[object]:
        return [
            a for a in self._t["vault_accessors"].values()
            if a.alloc_id == alloc_id
        ]

    def allocs_by_eval(self, eval_id: str) -> List[Allocation]:
        ids = self._i["allocs_by_eval"].get(eval_id, ())
        return self._t["allocs"].rows(ids)


class StateStore:
    """The authoritative replicated state. All writes come from the FSM
    applying log entries; every write bumps the per-table and global
    index and fires scoped watches.

    Who takes `_lock`: every write txn, `snapshot()` (and so every
    read that spans rows: it goes through `__getattr__` to a fresh
    snapshot), `persist` and `restore`. Who takes NO lock and no
    snapshot: `latest_index()`, `index(table)`, `scope_index(items)`
    and the four point reads `node_by_id`, `job_by_id`, `eval_by_id`,
    `alloc_by_id`. Each of those is one read of an int or one
    `dict.get` with str (or tuple-of-str) keys (a point read: of the
    bucket the table's top holds for the key; a writer publishes a
    copied top whole and a copied bucket whole before it writes
    either), which is atomic only because the interpreter has a GIL:
    on a free-threaded build
    (`sys._is_gil_enabled()` false) the writers' in-place dict writes
    race with these reads and the lock has to come back. With the GIL,
    the read mux's one wake loop, the HTTP handlers and the catch-up
    polls never queue behind the FSM. Scheduler and dispatch
    code is no party to this: it plans against `snapshot()` only
    (analysis/snapshot.py's rule; `latest_index` is its one probe).

    The orders the lock-free readers rely on, all inside one writer's
    hold of `_lock` (writers are serialised, and raft applies indexes
    in rising order):

    1. A row is complete before it is inserted (copy, set fields,
       then `table[id] = row`) and never mutated after, so a point
       read sees a whole row, old or new.
    2. A txn makes ALL its table writes before it moves ANY index:
       `_bump` (table indexes, then the global one) and then `_stamp`
       (scope indexes) come last, once a txn (`_set_job_status` moves
       none: the txn's one bump names the jobs table, `_jobs_table`).
       So a reader that reads an index FIRST and the data SECOND, by
       id or through a snapshot, finds the rows of every txn up to
       that index: for that order of reads, new index with old data
       is not possible. (`park()`'s recheck and the wake loop read so:
       index, then the serve re-runs the query.) The other order of
       reads is not covered by this and never was by the lock:
       `_blocking` and the mux's `serve` read the data first and
       compute `X-Nomad-Index` after it, so a commit that lands
       between the two goes out as the OLDER row under the NEWER
       index, and that client sits its `wait` out before it sees the
       row. Under the lock the gap was one lock hand-off wide, now it
       is two dict reads; closing it means reading the index before
       the data in api/http.py (PERF.md section 7). What a point read
       CAN newly see is a row whose stamp has not landed; the reply
       then carries new data under the older index and the client's
       next poll returns at once, which loses nothing.
    3. `notify` fires after the lock is released, so a watcher that
       read the old index and parked is told afterwards (the mux's
       park() rechecks after registering, for the commit in between).
    4. `_stamp`'s pruning raises `_scope_floor` BEFORE it drops the
       entries, and `scope_index` reads the entry before the floor:
       no scope's index ever reads lower than it did.

    What this does not give: two point reads are two moments (they
    always were: each took a snapshot of its own), and whoever needs
    rows that belong together takes `snapshot()`."""

    def __init__(self):
        self._lock = threading.RLock()
        self._writes = _WriteStats()
        # (copied, written) of the newest write txn, for its caller:
        # the FSM, the one writer, annotates `fsm.alloc_upsert` with it.
        self.last_write: Tuple[int, int] = (0, 0)
        # `nodes` is one bucket: it keeps insertion order, the row
        # order of the cluster base (module docstring).
        self._tables: Dict[str, _Table] = {
            name: _Table(self._writes, 1 if name == "nodes" else _FANOUT)
            for name in TABLES
        }
        self._indexes = {
            name: _Index(self._writes)
            for name in ("evals_by_job", "allocs_by_job", "allocs_by_node",
                         "allocs_by_eval")
        }
        self._table_indexes: Dict[str, int] = {}
        self._latest_index = 0
        # Per-watch-scope modify indexes (the reference's state_store.go
        # index-table device, at watch.Item granularity): one entry per
        # (kind, key) actually touched by a commit. Blocking queries
        # wake — and stamp X-Nomad-Index — off THEIR scope's index, not
        # the global one, so a write to job A never re-runs a watcher
        # of job B. Bounded by _SCOPE_CAP: pruning raises _scope_floor
        # so evicted scopes degrade to conservative (global-ish) wakes
        # instead of missed ones.
        self._scope_indexes: Dict[watch.Item, int] = {}
        self._scope_floor = 0
        self._alloc_journal = _AllocJournal()
        self.notify = watch.NotifyGroup()
        from ..utils.ids import generate_uuid

        self.store_id = generate_uuid()

    # ------------------------------------------------------------------
    # snapshots & watches
    # ------------------------------------------------------------------

    def snapshot(self) -> StateSnapshot:
        with self._lock:
            tables = {name: t.share() for name, t in self._tables.items()}
            indexes = {name: i.share() for name, i in self._indexes.items()}
            return StateSnapshot(
                tables, indexes, dict(self._table_indexes),
                self._latest_index, self._alloc_journal.view(),
                store_id=self.store_id,
            )

    def latest_index(self) -> int:
        return self._latest_index  # lock-free: class docstring

    def index(self, table: str) -> int:
        return self._table_indexes.get(table, 0)  # lock-free

    def watch(self, items) -> "threading.Event":
        return self.notify.watch(items)

    def stop_watch(self, items, ev) -> None:
        self.notify.stop_watch(items, ev)

    def scope_index(self, items) -> int:
        """Max modify index across the given watch scopes — the index a
        blocking query on `items` should compare against ?index=N and
        report as X-Nomad-Index. Never-stamped scopes fall back to the
        scope floor (0 on a fresh store; the restored latest index when
        the snapshot predates scope persistence, so correctness degrades
        to the old conservative global behavior, never to missed
        wakes). Lock-free (class docstring): the entry is read BEFORE
        the floor, and `_stamp` raises the floor before it drops an
        entry, so no scope's index ever reads lower than it did."""
        scopes = self._scope_indexes
        best = 0
        for item in items:
            idx = scopes.get(item)
            if idx is None:
                kind, key = item
                if kind == "table":
                    idx = self._table_indexes.get(key, 0)
                else:
                    idx = self._scope_floor
            if idx > best:
                best = idx
        return best

    # Point reads: ONE bucket read of the live table (its top as it
    # stands, then the key's bucket), no lock and no snapshot, so no
    # share() and nothing for the next write txn to copy (class
    # docstring).
    def node_by_id(self, node_id: str) -> Optional[Node]:
        return self._tables["nodes"].get(node_id)

    def job_by_id(self, job_id: str) -> Optional[Job]:
        return self._tables["jobs"].get(job_id)

    def eval_by_id(self, eval_id: str) -> Optional[Evaluation]:
        return self._tables["evals"].get(eval_id)

    def alloc_by_id(self, alloc_id: str) -> Optional[Allocation]:
        return self._tables["allocs"].get(alloc_id)

    # Every read that spans rows goes through a fresh snapshot, so it
    # is one consistent view (and takes the lock to get it).
    def __getattr__(self, name):
        snap_methods = (
            "nodes",
            "jobs",
            "jobs_by_scheduler",
            "jobs_by_periodic",
            "job_summary_by_id",
            "periodic_launch_by_id",
            "periodic_launches",
            "evals",
            "evals_by_job",
            "allocs",
            "alloc_count",
            "allocs_changed_since",
            "allocs_by_job",
            "allocs_by_node",
            "allocs_by_node_terminal",
            "allocs_by_eval",
            "vault_accessors",
            "vault_accessors_by_alloc",
        )
        if name in snap_methods:
            return getattr(self.snapshot(), name)
        raise AttributeError(name)

    # ------------------------------------------------------------------
    # write transactions (FSM-only)
    # ------------------------------------------------------------------

    # Scope entries ever stamped before pruning engages; prune drops
    # the oldest half and raises the floor to the highest dropped
    # index (conservative, not lossy).
    _SCOPE_CAP = 262144

    @contextlib.contextmanager
    def _write_txn(self):
        """One write transaction: the lock, and the account of what
        its tables copied and wrote (`last_write`, `write_stats`)."""
        with self._lock:
            stats = self._writes
            copied, written = stats.entries_copied, stats.entries_written
            try:
                yield
            finally:
                copied = stats.entries_copied - copied
                stats.write_txns += 1
                if copied > stats.largest_txn_copy:
                    stats.largest_txn_copy = copied
                self.last_write = (copied, stats.entries_written - written)

    def write_stats(self) -> Dict[str, int]:
        """What the write txns copied before they could write, tops,
        buckets and index sets counted by entry (the `state_store`
        block of `server.stats()`). Lock-free: plain ints."""
        stats = self._writes
        return {name: getattr(stats, name) for name in stats.__slots__}

    def _bump(self, index: int, *tables: str) -> None:
        for t in tables:
            self._table_indexes[t] = index
        self._latest_index = max(self._latest_index, index)

    def _stamp(self, index: int, items) -> None:
        """Record `index` as the modify index of every touched scope.
        Runs under self._lock, after ALL of the txn's table writes, so
        a reader that sees the stamp finds the txn's rows (the readers
        of `scope_index` take no lock: class docstring)."""
        scopes = self._scope_indexes
        for item in items:
            scopes[item] = index
        if len(scopes) > self._SCOPE_CAP:
            by_age = sorted(scopes.items(), key=lambda kv: kv[1])
            cut = len(by_age) // 2
            if cut:
                # The floor rises BEFORE the entries go: a lock-free
                # reader that misses an entry then reads a floor at or
                # above what the entry held, never below it.
                self._scope_floor = max(self._scope_floor,
                                        by_age[cut - 1][1])
            for item, _idx in by_age[:cut]:
                del scopes[item]

    def upsert_node(self, index: int, node: Node) -> None:
        items = [watch.table("nodes"), watch.node(node.id)]
        with self._write_txn():
            table = self._tables["nodes"]
            existing = table.get(node.id)
            node = node.copy()
            if existing is not None:
                node.create_index = existing.create_index
            else:
                node.create_index = index
            node.modify_index = index
            # Always recompute: a re-registering node may carry a stale
            # class alongside changed attributes.
            node.compute_class()
            table.put(node.id, node)
            self._bump(index, "nodes")
            self._stamp(index, items)
        self.notify.notify(items)

    def delete_node(self, index: int, node_id: str) -> None:
        items = [watch.table("nodes"), watch.node(node_id)]
        with self._write_txn():
            table = self._tables["nodes"]
            if node_id not in table:
                return
            table.pop(node_id)
            self._bump(index, "nodes")
            self._stamp(index, items)
        self.notify.notify(items)

    def update_node_status(self, index: int, node_id: str, status: str) -> None:
        items = [watch.table("nodes"), watch.node(node_id)]
        with self._write_txn():
            table = self._tables["nodes"]
            existing = table.get(node_id)
            if existing is None:
                raise KeyError(f"node {node_id} not found")
            node = existing.copy()
            node.status = status
            node.modify_index = index
            import time as _time

            node.status_updated_at = _time.time()
            table.put(node_id, node)
            self._bump(index, "nodes")
            self._stamp(index, items)
        self.notify.notify(items)

    def update_node_drain(self, index: int, node_id: str, drain: bool) -> None:
        items = [watch.table("nodes"), watch.node(node_id)]
        with self._write_txn():
            table = self._tables["nodes"]
            existing = table.get(node_id)
            if existing is None:
                raise KeyError(f"node {node_id} not found")
            node = existing.copy()
            node.drain = drain
            node.modify_index = index
            table.put(node_id, node)
            self._bump(index, "nodes")
            self._stamp(index, items)
        self.notify.notify(items)

    def upsert_job(self, index: int, job: Job) -> None:
        items = [watch.table("jobs"), watch.job(job.id), watch.job_summary(job.id)]
        with self._write_txn():
            table = self._tables["jobs"]
            existing = table.get(job.id)
            job = job.copy()
            if existing is not None:
                job.create_index = existing.create_index
                job.job_modify_index = index
            else:
                job.create_index = index
                job.job_modify_index = index
            job.modify_index = index
            table.put(job.id, job)
            self._ensure_job_summary(index, job)
            items.extend(self._set_job_status(index, job))
            self._bump(index, "jobs", "job_summary")
            self._stamp(index, items)
        self.notify.notify(items)

    def delete_job(self, index: int, job_id: str) -> None:
        items = [watch.table("jobs"), watch.job(job_id), watch.job_summary(job_id)]
        with self._write_txn():
            table = self._tables["jobs"]
            if job_id not in table:
                return
            table.pop(job_id)
            summary = self._tables["job_summary"]
            summary.pop(job_id)
            launches = self._tables["periodic_launch"]
            launches.pop(job_id)
            self._bump(index, "jobs", "job_summary", "periodic_launch")
            self._stamp(index, items)
        self.notify.notify(items)

    def upsert_periodic_launch(self, index: int, launch: PeriodicLaunch) -> None:
        items = [watch.table("periodic_launch")]
        with self._write_txn():
            table = self._tables["periodic_launch"]
            existing = table.get(launch.id)
            rec = PeriodicLaunch(
                id=launch.id,
                launch=launch.launch,
                create_index=existing.create_index if existing else index,
                modify_index=index,
            )
            table.put(launch.id, rec)
            self._bump(index, "periodic_launch")
            self._stamp(index, items)
        self.notify.notify(items)

    def delete_periodic_launch(self, index: int, job_id: str) -> None:
        items = [watch.table("periodic_launch")]
        with self._write_txn():
            table = self._tables["periodic_launch"]
            table.pop(job_id)
            self._bump(index, "periodic_launch")
            self._stamp(index, items)
        self.notify.notify(items)

    def upsert_vault_accessors(self, index: int, accessors) -> None:
        """Track derived vault tokens (state_store.go vault_accessors
        table; schema.go:18-40)."""
        items = [watch.table("vault_accessors")]
        with self._write_txn():
            table = self._tables["vault_accessors"]
            for acc in accessors:
                acc.create_index = index
                table.put(acc.accessor, acc)
            self._bump(index, "vault_accessors")
            self._stamp(index, items)
        self.notify.notify(items)

    def delete_vault_accessors(self, index: int, accessors: List[str]) -> None:
        items = [watch.table("vault_accessors")]
        with self._write_txn():
            table = self._tables["vault_accessors"]
            for acc in accessors:
                table.pop(acc)
            self._bump(index, "vault_accessors")
            self._stamp(index, items)
        self.notify.notify(items)

    def upsert_evals(self, index: int, evals: List[Evaluation]) -> None:
        items = [watch.table("evals")]
        with self._write_txn():
            table = self._tables["evals"]
            for ev in evals:
                items.append(watch.eval_item(ev.id))
                existing = table.get(ev.id)
                ev = ev.copy()
                if existing is not None:
                    ev.create_index = existing.create_index
                else:
                    ev.create_index = index
                    self._indexes["evals_by_job"].add(ev.job_id, ev.id)
                ev.modify_index = index
                table.put(ev.id, ev)
                # Propagate queued-alloc counts into the job summary
                # (state_store.go UpsertEvals -> updateSummaryWithEval).
                if ev.queued_allocations:
                    self._update_summary_queued(index, ev)
                job = self._tables["jobs"].get(ev.job_id)
                if job is not None:
                    items.extend(self._set_job_status(index, job))
                    items.append(watch.job_summary(ev.job_id))
            self._bump(index, "evals", "job_summary", *_jobs_table(items))
            self._stamp(index, items)
        self.notify.notify(items)

    def delete_evals(self, index: int, eval_ids: List[str], alloc_ids: List[str]) -> None:
        items = [watch.table("evals"), watch.table("allocs")]
        touched_jobs: Set[str] = set()
        with self._write_txn():
            evals = self._tables["evals"]
            for eid in eval_ids:
                ev = evals.pop(eid)
                if ev is not None:
                    self._indexes["evals_by_job"].remove(ev.job_id, eid)
                    items.append(watch.eval_item(eid))
                    touched_jobs.add(ev.job_id)
            allocs = self._tables["allocs"]
            for aid in alloc_ids:
                alloc = allocs.pop(aid)
                if alloc is not None:
                    self._indexes["allocs_by_job"].remove(alloc.job_id, aid)
                    self._indexes["allocs_by_node"].remove(alloc.node_id, aid)
                    self._indexes["allocs_by_eval"].remove(alloc.eval_id, aid)
                    touched_jobs.add(alloc.job_id)
                    items.extend(
                        [
                            watch.alloc(aid),
                            watch.alloc_job(alloc.job_id),
                            watch.alloc_node(alloc.node_id),
                            watch.alloc_eval(alloc.eval_id),
                        ]
                    )
            for job_id in touched_jobs:
                job = self._tables["jobs"].get(job_id)
                if job is not None:
                    items.extend(self._set_job_status(
                        index, job, eval_delete=True))
            self._bump(index, "evals", "allocs", *_jobs_table(items))
            self._stamp(index, items)
        self.notify.notify(items)

    def upsert_allocs(self, index: int, allocs: List[Allocation]) -> None:
        """Scheduler/plan-apply driven alloc writes (state_store.go:922).
        Client-reported status on existing allocs is preserved."""
        items = [watch.table("allocs")]
        with self._write_txn():
            table = self._tables["allocs"]
            for alloc in allocs:
                existing = table.get(alloc.id)
                alloc = alloc.copy()
                if existing is not None:
                    alloc.create_index = existing.create_index
                    alloc.task_states = existing.task_states
                    # The client owns client_status — EXCEPT lost: the
                    # scheduler marks an alloc lost exactly because its
                    # node went down and the client can never report
                    # again (state_store.go:922 carves out the same
                    # case). Without this the node-down -> alloc-lost
                    # chain silently reverted to the stale 'running'.
                    if alloc.client_status != consts.ALLOC_CLIENT_LOST:
                        alloc.client_status = existing.client_status
                        alloc.client_description = existing.client_description
                    # The eval index is by the field's value (memdb): an
                    # allocation updated in place comes back under the
                    # eval that updated it (scheduler/util.py
                    # inplace_update) and is listed there, no longer
                    # under the eval that placed it.
                    if existing.eval_id != alloc.eval_id:
                        by_eval = self._indexes["allocs_by_eval"]
                        by_eval.remove(existing.eval_id, alloc.id)
                        by_eval.add(alloc.eval_id, alloc.id)
                        items.append(watch.alloc_eval(existing.eval_id))
                else:
                    alloc.create_index = index
                    if not alloc.client_status:
                        alloc.client_status = consts.ALLOC_CLIENT_PENDING
                    self._indexes["allocs_by_job"].add(alloc.job_id, alloc.id)
                    self._indexes["allocs_by_node"].add(alloc.node_id, alloc.id)
                    self._indexes["allocs_by_eval"].add(alloc.eval_id, alloc.id)
                alloc.modify_index = index
                alloc.alloc_modify_index = index
                table.put(alloc.id, alloc)
                self._update_summary_with_alloc(index, alloc, existing)
                items.extend(
                    [
                        watch.alloc(alloc.id),
                        watch.alloc_job(alloc.job_id),
                        watch.alloc_node(alloc.node_id),
                        watch.alloc_eval(alloc.eval_id),
                        watch.job_summary(alloc.job_id),
                    ]
                )
            self._alloc_journal.record(index, [a.id for a in allocs])
            # Derived job status recomputes once per touched job, not
            # once per alloc (a system job upserts one alloc per node).
            for job_id in {a.job_id for a in allocs}:
                job = self._tables["jobs"].get(job_id)
                if job is not None:
                    items.extend(self._set_job_status(index, job))
            self._bump(index, "allocs", "job_summary", *_jobs_table(items))
            self._stamp(index, items)
        self.notify.notify(items)

    def update_allocs_from_client(self, index: int, allocs: List[Allocation]) -> None:
        """Client status sync (state_store.go:843): only client-owned
        fields change; alloc_modify_index is NOT bumped so the client's
        long-poll diff (keyed on it) ignores its own writes."""
        items = [watch.table("allocs")]
        with self._write_txn():
            table = self._tables["allocs"]
            written: List[str] = []
            for update in allocs:
                existing = table.get(update.id)
                if existing is None:
                    continue
                written.append(update.id)
                alloc = existing.copy()
                alloc.client_status = update.client_status
                alloc.client_description = update.client_description
                # Deep-copy: the caller keeps mutating its TaskState objects
                # and stored records must stay immutable for snapshots.
                alloc.task_states = {
                    k: _copy.deepcopy(v) for k, v in update.task_states.items()
                }
                alloc.modify_index = index
                table.put(alloc.id, alloc)
                self._update_summary_with_alloc(index, alloc, existing)
                job = self._tables["jobs"].get(alloc.job_id)
                if job is not None:
                    items.extend(self._set_job_status(index, job))
                items.extend(
                    [
                        watch.alloc(alloc.id),
                        watch.alloc_job(alloc.job_id),
                        watch.alloc_node(alloc.node_id),
                        watch.alloc_eval(alloc.eval_id),
                        watch.job_summary(alloc.job_id),
                    ]
                )
            self._alloc_journal.record(index, written)
            self._bump(index, "allocs", "job_summary", *_jobs_table(items))
            self._stamp(index, items)
        self.notify.notify(items)

    # ------------------------------------------------------------------
    # derived state (job status + summaries)
    # ------------------------------------------------------------------

    def _ensure_job_summary(self, index: int, job: Job) -> None:
        summaries = self._tables["job_summary"]
        existing = summaries.get(job.id)
        summary = existing.copy() if existing else JobSummary(job_id=job.id, create_index=index)
        for tg in job.task_groups:
            summary.summary.setdefault(tg.name, TaskGroupSummary())
        summary.modify_index = index
        summaries.put(job.id, summary)

    def _update_summary_queued(self, index: int, ev: Evaluation) -> None:
        summaries = self._tables["job_summary"]
        existing = summaries.get(ev.job_id)
        if existing is None:
            return
        summary = existing.copy()
        for tg, queued in ev.queued_allocations.items():
            tgs = summary.summary.setdefault(tg, TaskGroupSummary())
            tgs.queued = queued
        summary.modify_index = index
        summaries.put(ev.job_id, summary)

    def _update_summary_with_alloc(
        self, index: int, alloc: Allocation, existing: Optional[Allocation]
    ) -> None:
        """Maintain per-task-group client-status counts
        (state_store.go:1552 updateSummaryWithAlloc)."""
        summaries = self._tables["job_summary"]
        cur = summaries.get(alloc.job_id)
        if cur is None:
            cur = JobSummary(job_id=alloc.job_id, create_index=index)
        summary = cur.copy()
        tgs = summary.summary.setdefault(alloc.task_group, TaskGroupSummary())

        def bucket(status: str) -> Optional[str]:
            return {
                consts.ALLOC_CLIENT_PENDING: "starting",
                consts.ALLOC_CLIENT_RUNNING: "running",
                consts.ALLOC_CLIENT_COMPLETE: "complete",
                consts.ALLOC_CLIENT_FAILED: "failed",
                consts.ALLOC_CLIENT_LOST: "lost",
            }.get(status)

        if existing is not None:
            old = bucket(existing.client_status)
            if old and getattr(tgs, old) > 0:
                setattr(tgs, old, getattr(tgs, old) - 1)
        new = bucket(alloc.client_status)
        if new:
            setattr(tgs, new, getattr(tgs, new) + 1)
        summary.modify_index = index
        summaries.put(alloc.job_id, summary)

    def _get_job_status(self, job: Job, eval_delete: bool) -> str:
        """Derive job status (state_store.go:1457 getJobStatus): running if
        any non-terminal alloc; pending if any non-terminal eval; dead when
        everything outstanding is terminal (or evals were GC'd); a brand-new
        job with nothing outstanding is pending (running if periodic)."""
        has_alloc = False
        for aid in self._indexes["allocs_by_job"].get(job.id, ()):
            alloc = self._tables["allocs"].get(aid)
            if alloc is None:
                continue
            has_alloc = True
            if not alloc.terminal_status():
                return consts.JOB_STATUS_RUNNING
        has_eval = False
        for eid in self._indexes["evals_by_job"].get(job.id, ()):
            ev = self._tables["evals"].get(eid)
            if ev is None:
                continue
            has_eval = True
            if not ev.terminal_status():
                return consts.JOB_STATUS_PENDING
        if eval_delete or has_eval or has_alloc:
            return consts.JOB_STATUS_DEAD
        # A periodic parent never gets allocs/evals of its own.
        if job.is_periodic():
            return consts.JOB_STATUS_RUNNING
        return consts.JOB_STATUS_PENDING

    def _set_job_status(self, index: int, job: Job, eval_delete: bool = False) -> list:
        """Recompute and store the derived job status (state_store.go:1417
        setJobStatus). Returns the watch items to notify (empty when the
        status is unchanged). It moves NO index: the caller's txn bumps
        the jobs table with its own, when its table writes are through
        (`_jobs_table`; class docstring, order 2)."""
        status = self._get_job_status(job, eval_delete)
        stored = self._tables["jobs"].get(job.id)
        if stored is None or stored.status == status:
            return []  # unchanged: no write, so no bucket copied
        jobs = self._tables["jobs"]
        updated = stored.copy()
        updated.status = status
        updated.modify_index = index
        jobs.put(job.id, updated)
        return [watch.table("jobs"), watch.job(job.id)]

    # ------------------------------------------------------------------
    # persistence (FSM snapshot install/restore)
    # ------------------------------------------------------------------

    def persist(self) -> dict:
        from ..utils.codec import to_dict

        with self._lock:
            return {
                "nodes": [to_dict(n) for n in self._tables["nodes"].values()],
                "jobs": [to_dict(j) for j in self._tables["jobs"].values()],
                "job_summary": [
                    to_dict(s) for s in self._tables["job_summary"].values()
                ],
                "periodic_launch": [
                    to_dict(p) for p in self._tables["periodic_launch"].values()
                ],
                "evals": [to_dict(e) for e in self._tables["evals"].values()],
                "allocs": [to_dict(a) for a in self._tables["allocs"].values()],
                "vault_accessors": [
                    to_dict(v)
                    for v in self._tables["vault_accessors"].values()
                ],
                "table_indexes": dict(self._table_indexes),
                "latest_index": self._latest_index,
                "scope_indexes": [
                    [kind, key, idx]
                    for (kind, key), idx in self._scope_indexes.items()
                ],
                "scope_floor": self._scope_floor,
            }

    @classmethod
    def restore(cls, data: dict) -> "StateStore":
        from ..utils.codec import from_dict

        store = cls()
        with store._lock:
            for raw in data.get("nodes", []):
                n = from_dict(Node, raw)
                store._tables["nodes"].put(n.id, n)
            for raw in data.get("jobs", []):
                j = from_dict(Job, raw)
                store._tables["jobs"].put(j.id, j)
            for raw in data.get("job_summary", []):
                s = from_dict(JobSummary, raw)
                store._tables["job_summary"].put(s.job_id, s)
            for raw in data.get("periodic_launch", []):
                p = from_dict(PeriodicLaunch, raw)
                store._tables["periodic_launch"].put(p.id, p)
            for raw in data.get("evals", []):
                e = from_dict(Evaluation, raw)
                store._tables["evals"].put(e.id, e)
                store._indexes["evals_by_job"].add(e.job_id, e.id)
            for raw in data.get("allocs", []):
                a = from_dict(Allocation, raw)
                store._tables["allocs"].put(a.id, a)
                store._indexes["allocs_by_job"].add(a.job_id, a.id)
                store._indexes["allocs_by_node"].add(a.node_id, a.id)
                store._indexes["allocs_by_eval"].add(a.eval_id, a.id)
            from ..structs.alloc import VaultAccessor

            for raw in data.get("vault_accessors", []):
                v = from_dict(VaultAccessor, raw)
                store._tables["vault_accessors"].put(v.accessor, v)
            store._table_indexes = dict(data.get("table_indexes", {}))
            store._latest_index = data.get("latest_index", 0)
            # The journal is derived state and was not persisted: what
            # was written up to the restored allocs index is unknown.
            store._alloc_journal = _AllocJournal(
                floor=store._table_indexes.get("allocs", 0))
            scopes = data.get("scope_indexes")
            if scopes is None:
                # Snapshot predates scope persistence: every scope's
                # history is unknown, so the floor is the whole
                # restored history (conservative global-index wakes for
                # pre-restore scopes, exact tracking from here on).
                store._scope_floor = store._latest_index
            else:
                store._scope_indexes = {
                    (kind, key): idx for kind, key, idx in scopes
                }
                store._scope_floor = data.get("scope_floor", 0)
        return store
