"""Device-resident cluster-state tracking.

The dense path's authoritative ``[N, R]`` node matrix lives ON DEVICE
(scheduler/batcher.py's base cache); ``models/matrix.py``'s cached
``_ClusterBase`` is its host-side mirror. This module is the control
plane for that residency:

- **generation accounting** — every base is keyed by the raft
  watermarks it was built from (``nodes`` index, ``allocs`` index); a
  newer snapshot derives the next generation by a DELTA (recompute the
  touched rows, scatter them on device) instead of a full rebuild +
  re-upload. Plan commits advance the allocs axis
  (``_ClusterBase.delta_update``); node up/down/drain transitions
  advance the nodes axis and ride the SAME row scatter — the node
  stays in the matrix with ``node_ok`` masked instead of forcing a
  rebuild of the node axis (the matrix is built over the full
  datacenter *universe*, not the ready subset, exactly so readiness
  is row state rather than matrix shape).
- **the topology tensor** — the rack and ici id columns
  (models/topology.py) are node-level like the node axis itself: every
  delta clone of a base shares its parent's tensor, and the batcher
  keeps each column a gang dispatch has read resident beside the base
  under the tensor's own token (scheduler/batcher.py
  ``_device_topology``), so a column is uploaded once for every rebuild
  of the node set (``topo_uploads``), not once a base token.
- **rebuild policy** — when a delta stops being worth it (too many
  touched rows: ``max_refill_rows``) or stops being *possible* (alloc
  deletions, node registrations, capacity edits), with counters that
  tell the two cases apart.
- **staleness safety net** — the plan applier re-verifies every node
  exactly (server/plan_apply.py); a rejected plan means *some* state
  the scheduler planned against was wrong, so ``note_rejection()``
  marks the resident state suspect and the next build pays one full
  rebuild (``stale_rebuilds``) instead of trusting a possibly-bad
  delta chain. A wrong placement therefore costs one retry, never a
  committed double-book — the carve-over of the reference's
  plan_apply.go:318 exactness.

Chaos site ``matrix.stale_delta`` (kind='drop') deterministically
corrupts one delta application — a changed row is left un-recomputed —
so tests can prove the verification-rejection-rebuild loop end to end
without waiting for a real race.

Everything here is process-global (like the batcher's device cache it
fronts) and lock-guarded; counters are exposed via
``server.stats()["device_state"]`` and ``/v1/metrics``.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional


class ResidentStateTracker:
    """Counters + policy for the device-resident node matrix."""

    def __init__(self):
        self._lock = threading.Lock()
        # Build-mode counters. full_rebuilds counts every from-scratch
        # _ClusterBase on the cacheable path; the *_reason counters
        # attribute why the delta path was skipped.
        self.full_rebuilds = 0  # guarded-by: _lock
        self.delta_updates = 0  # guarded-by: _lock (alloc-axis rows)
        self.node_delta_updates = 0  # guarded-by: _lock (node-axis rows)
        # Cumulative recomputed-row counts per axis: delta SIZE, not
        # count — a climbing rows/update ratio says deltas are drifting
        # toward the rebuild threshold.
        self.alloc_delta_rows = 0  # guarded-by: _lock
        self.node_delta_rows = 0  # guarded-by: _lock
        # How the delta learnt what changed (state/store.py's journal
        # of allocation writes): deltas it served, deltas it could not
        # (trimmed past the base, a restored store, a state without
        # one: a full build follows), and the changed allocations it
        # handed over. allocs / deltas is the allocations a commit.
        self.journal_deltas = 0  # guarded-by: _lock
        self.journal_misses = 0  # guarded-by: _lock
        self.journal_allocs = 0  # guarded-by: _lock
        # Jobs whose entry of the positions index the deltas rewrote
        # (models/matrix.py _patch_positions). Over delta_updates, the
        # jobs a delta patched: the jobs of what it wrote, never the
        # jobs that merely live on the rows it touched.
        self.positions_patched_jobs = 0  # guarded-by: _lock
        self.stale_rebuilds = 0  # guarded-by: _lock (post-rejection)
        self.universe_rebuilds = 0  # guarded-by: _lock (node set changed)
        # Plan-apply rejection marked the resident chain suspect; the
        # next cacheable build consumes this and rebuilds from scratch.
        self._stale = False  # guarded-by: _lock

    # ------------------------------------------------------------ policy

    @staticmethod
    def max_refill_rows(n_real: int) -> int:
        """Most refilled rows a delta may carry before a full rebuild
        is the better deal."""
        return max(64, n_real // 4)

    # --------------------------------------------------------- staleness

    def note_rejection(self) -> None:
        """The plan applier rejected a plan: whatever matrix the
        scheduler planned against disagreed with the store. Mark the
        resident chain suspect — one full rebuild re-anchors it. Cheap
        and idempotent; called from the applier's rejection path."""
        with self._lock:
            self._stale = True

    def consume_stale(self) -> bool:
        """True exactly once per note_rejection burst: the caller must
        full-rebuild (and gets counted in stale_rebuilds)."""
        with self._lock:
            if not self._stale:
                return False
            self._stale = False
            self.stale_rebuilds += 1
            return True

    # ---------------------------------------------------------- counters

    def count_full(self) -> None:
        with self._lock:
            self.full_rebuilds += 1

    def count_universe(self) -> None:
        with self._lock:
            self.universe_rebuilds += 1

    def count_delta(self, alloc_rows: int, node_rows: int,
                    patched_jobs: int) -> None:
        with self._lock:
            self.delta_updates += 1
            self.alloc_delta_rows += alloc_rows
            self.positions_patched_jobs += patched_jobs
            if node_rows:
                self.node_delta_updates += 1
                self.node_delta_rows += node_rows

    def count_journal(self, changed: Optional[list]) -> None:
        """One delta asked the store's journal: `changed` is its answer
        (None = it could not serve)."""
        with self._lock:
            if changed is None:
                self.journal_misses += 1
            else:
                self.journal_deltas += 1
                self.journal_allocs += len(changed)

    def stats(self) -> Dict[str, object]:
        with self._lock:
            return {
                "full_rebuilds": self.full_rebuilds,
                "delta_updates": self.delta_updates,
                "node_delta_updates": self.node_delta_updates,
                "alloc_delta_rows": self.alloc_delta_rows,
                "node_delta_rows": self.node_delta_rows,
                "journal_deltas": self.journal_deltas,
                "journal_misses": self.journal_misses,
                "journal_allocs": self.journal_allocs,
                "positions_patched_jobs": self.positions_patched_jobs,
                "stale_rebuilds": self.stale_rebuilds,
                "universe_rebuilds": self.universe_rebuilds,
            }


_tracker = ResidentStateTracker()


def get_tracker() -> ResidentStateTracker:
    return _tracker


def note_rejection() -> None:
    _tracker.note_rejection()


def device_state_stats() -> Dict[str, object]:
    """The ``server.stats()["device_state"]`` payload: resident-chain
    counters plus the batcher's upload/delta tallies and the jit
    compile-cache size (a CLIMBING cache under steady load is a
    recompile storm — the benchmark's window_compiles reads it)."""
    from ..scheduler.batcher import get_batcher

    out = _tracker.stats()
    b = get_batcher().stats()
    out["jit_cache_size"] = b["jit_cache_size"]
    out["base_uploads"] = b["base_uploads"]
    out["base_delta_updates"] = b["base_delta_updates"]
    out["upload_bytes"] = b["upload_bytes"]
    out["topo_uploads"] = b["topo_uploads"]
    return out
