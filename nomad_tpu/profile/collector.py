"""The program's policy for the interpreter's cyclic collector: one rule.

The heap of a server IS its fleet (nodes, allocations, the store's
tables): some 0.8 to 2 million tracked objects that no pass of the
collector will ever free, because the store replaces a record by
reference count and nothing in them is a cycle. Left alone, the collector
walks all of it again whenever a quarter as much has been allocated, with
every thread stopped: 0.24 s for 0.78 M objects on an idle CPU, 0.5 to
1 s under load (PERF.md section 6, PR 40's step 0).

**The rule.** A pass that held the interpreter for long and freed next to
nothing was pure loss, and the next one like it will be too: so its
survivors go to the permanent generation (`gc.freeze()`), and the next
pass walks only what was allocated since. In what a `gc.callbacks` hook
can observe: after a pass of ANY generation that took `FLOOR_MS` or more
and freed fewer than `BARREN_PER_MS` objects for each millisecond it
held the interpreter, freeze. It adapts by what it observes: a small heap
never has a pass over the floor and is left alone, a heap whose passes do
free a good share is left alone by the yield test, a heap that grows
(the ramp cell places 1,500 allocations a second on empty hosts) is
frozen in steps, each time its passes come up long and barren again.

**Who does what.** The hook (`_on_gc`) may run on any thread at any
allocation, also on one that holds a recorder stripe
(`trace.recorder.stripe` is not re-entrant): it takes NO lock, writes
plain ints and appends one tuple to a bounded deque, and never calls
`gc.freeze()` itself. The sampler's thread (`sampler.py`, 200 wakes a
second, running while `profile_enabled`) drains the passes into the
recorder's stage table (`drain()`; `runtime.gc_pause`, every pass;
`runtime.gc_full_pause`, generation 2 only) and performs the freeze the
hook asked for (`freeze_if_asked()`). The policy does not depend on `profile_enabled`: with the
observatory off nobody drains (the deque is bounded and the rows stay
empty), and each server's telemetry thread performs the freeze at its
next tick (every `telemetry_interval`, 10 s: a few more fleet-length
passes are paid first).

**Lifetime.** Process-wide like the collector it steers: `install()` by
every `Server` that starts, `uninstall()` when it shuts down, counted;
the last one out removes the hook and calls `gc.unfreeze()`, so that a
process that starts and stops hundreds of servers (the tests) keeps
collecting dead servers' cycles.

**What a freeze costs, and its bound.** A frozen object still dies by
reference count; only a CYCLE that dies among frozen objects is missed,
and stays until the next `settle()`: `gc.unfreeze(); gc.collect();
gc.freeze()`, one fleet-length pause. The core GC scheduler calls it on
its eval-GC tick and on `/v1/system/gc` (`server/core_gc.py`), so a dead
frozen cycle lives at most `eval_gc_interval` (300 s) and the process
pays one fleet-length pause per that interval where it paid one every
six seconds. `FSM.restore` ends with the same call: a restored snapshot
is the same heap by another door.
"""

from __future__ import annotations

import collections
import gc
import threading
import time

# A pass shorter than this is not worth a freeze: a runnable thread of
# the storm cell waits 15 ms for the interpreter at its p95 anyway
# (`gil_wait_p95_ms`, ledger, PR 42). The young and middle passes of a
# busy window (3,900 and 330 in the storm's 51 s, 0.66 and 6.1 ms at the
# mean) mostly stay below it; the ones that a large young container or
# the host stretches past it (10 to 145 ms, about one a second there;
# PERF.md section 6, PR 43) trip the rule too, and freed nothing either.
FLOOR_MS = 10.0
# `gc.collect(2)` takes 0.24 s for the 778,654 objects of the
# northstar-10k fleet on an idle CPU (ISSUE 43's sizing): 3,244 objects
# walked a millisecond. A pass that frees under a twentieth of what a
# pass of its length walks pays twenty objects' walk for each it frees:
# 162 a millisecond. A loaded host walks fewer a millisecond, which only
# makes the test keener to freeze.
BARREN_PER_MS = 778_654 / 240.0 / 20.0
# Passes the hook may queue before the sampler drains them: a 10 s stall
# of the sampler at the storm's 60 passes a second.
PENDING_MAX = 1024


class Collector:
    def __init__(self):
        self._lock = threading.Lock()   # install / uninstall / freeze /
        #   settle; never the hook
        self._servers = 0               # written under _lock
        # Written by the hook alone (one pass runs at a time); read
        # torn-free as plain ints and floats.
        self._t0 = 0.0                  # 0.0: no pass of ours is open
        self._pending: collections.deque = collections.deque(
            maxlen=PENDING_MAX)         # (generation, ms)
        self._freeze_asked = False
        self.passes = 0
        self.full_passes = 0
        self.pause_ms = 0.0
        self.max_pause_ms = 0.0
        self.freezes = 0

    # ------------------------------------------------------ lifetime

    def install(self) -> None:
        with self._lock:
            self._servers += 1
            if self._servers == 1:
                gc.callbacks.append(self._on_gc)

    def uninstall(self) -> None:
        with self._lock:
            if self._servers == 0:
                return
            self._servers -= 1
            if self._servers:
                return
            gc.callbacks.remove(self._on_gc)
            self._freeze_asked = False
            self._pending.clear()
            gc.unfreeze()

    # ------------------------------------------------------ the hook

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        t0, self._t0 = self._t0, 0.0
        if not t0:
            return  # installed between this pass's two calls
        ms = (time.perf_counter() - t0) * 1000.0
        generation = info["generation"]
        self.passes += 1
        if generation == 2:
            self.full_passes += 1
        self.pause_ms += ms
        if ms > self.max_pause_ms:
            self.max_pause_ms = ms
        self._pending.append((generation, ms))
        if (ms >= FLOOR_MS and info["collected"] + info["uncollectable"]
                < ms * BARREN_PER_MS):
            self._freeze_asked = True

    # ------------------------------------- the sampler's thread's half

    def drain(self):
        """The queued passes, oldest first, as `(generation, ms)`."""
        pending = self._pending
        while pending:
            try:
                yield pending.popleft()
            except IndexError:  # uninstall() cleared it under us
                return

    def freeze_if_asked(self) -> None:
        if not self._freeze_asked:
            return
        with self._lock:
            if not self._freeze_asked:
                return
            self._freeze_asked = False
            if self._servers:
                gc.freeze()
                self.freezes += 1

    # -------------------------------------------- the bound, and restore

    def settle(self) -> None:
        """One full pass over everything, the frozen included, and the
        survivors frozen again: what bounds a dead frozen cycle's life,
        and how a restored snapshot's heap starts out. Nothing where no
        server of this process runs (nothing is frozen then, and nobody
        would thaw it)."""
        with self._lock:
            if not self._servers:
                return
            gc.unfreeze()
            gc.collect()
            gc.freeze()
            self.freezes += 1
            self._freeze_asked = False

    def stats(self) -> dict:
        return {
            "installed": self._servers > 0,
            "passes": self.passes,
            "full_passes": self.full_passes,
            "pause_ms": round(self.pause_ms, 3),
            "max_pause_ms": round(self.max_pause_ms, 3),
            "freezes": self.freezes,
            "frozen_objects": gc.get_freeze_count(),
        }


# Process-wide, as the interpreter's collector is.
_collector = Collector()


def get_collector() -> Collector:
    return _collector
