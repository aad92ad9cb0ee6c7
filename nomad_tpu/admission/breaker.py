"""Device-path circuit breaker.

The dense placement path has exactly one expensive shared dependency:
the batched device dispatch (scheduler/batcher.py -> ops/binpack.py).
PR 3 gave it a *per-eval* recovery — a failed ``place()`` falls back to
the host iterators for that eval — but a persistently sick device path
(runtime wedged, device OOM-looping) then pays the
failure latency on EVERY eval before falling back: the cluster limps at
fault-detection speed instead of host speed. The breaker turns N
consecutive per-eval failures into one routing decision.

States::

    closed ──(K consecutive failures OR M consecutive slow batches)──▶ open
    open ──(cool-down elapses; next acquire())──▶ half-open
    half-open ──(fast probe success)──▶ closed
    half-open ──(probe failure or slow probe)──▶ open   (cool-down re-arms)

- ``acquire()`` is the consuming gate at the device-dispatch call site
  (scheduler/tpu.py): CLOSED always grants; OPEN grants nothing until
  the cool-down elapses, then transitions to HALF_OPEN and grants ONE
  probe; HALF_OPEN grants only while no probe is in flight. Every
  grant must be followed by exactly one ``record_success`` /
  ``record_failure``.
- ``should_route_host()`` is the non-consuming *routing hint* for the
  dispatch pipeline's launch prologue: True only while OPEN inside the
  cool-down, so whole batches skip matrix build + cohort announcement
  without burning the half-open probe budget.
- a *slow batch* (``record_success`` with ``duration_ms >= slow_ms``)
  counts toward its own consecutive-trip threshold: a device that
  still answers but at 10x latency is an overload signal, not a
  success. A slow HALF_OPEN probe re-opens.

The instance is process-global (``get_breaker()``) for the same reason
the placement batcher is: it guards the one shared device path, and
every scheduler thread must see the same verdict.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

from ..utils import metrics

BREAKER_CLOSED = "closed"
BREAKER_OPEN = "open"
BREAKER_HALF_OPEN = "half-open"

_LEVELS = {BREAKER_CLOSED: 0, BREAKER_HALF_OPEN: 1, BREAKER_OPEN: 2}
_TRANSITION_CAP = 16  # bounded transition ring (drop-oldest)


class CircuitBreaker:
    def __init__(self, failure_threshold: int = 5, slow_ms: float = 0.0,
                 slow_batches: int = 8, cooldown: float = 5.0,
                 enabled: bool = True):
        # RLock: helper methods re-acquire so every guarded access is
        # lexically under the lock (ntalint guarded-by discipline).
        self._lock = threading.RLock()
        # Thresholds are written only by configure() (operator/boot
        # path) and read on the hot path; plain attributes like
        # chaos.enabled — a racing read sees old or new, either fine.
        self.enabled = enabled
        self.failure_threshold = max(1, failure_threshold)
        self.slow_ms = slow_ms  # 0 disables slow-batch trips
        self.slow_batches = max(1, slow_batches)
        self.cooldown = cooldown

        self._state = BREAKER_CLOSED  # guarded-by: _lock
        self._opened_at = 0.0  # guarded-by: _lock
        self._probe_inflight = False  # guarded-by: _lock
        self._consec_failures = 0  # guarded-by: _lock
        self._consec_slow = 0  # guarded-by: _lock
        self.trips = 0  # guarded-by: _lock
        self.half_opens = 0  # guarded-by: _lock
        self.recloses = 0  # guarded-by: _lock
        self.rejected = 0  # guarded-by: _lock (acquire() denials)
        self.successes = 0  # guarded-by: _lock
        self.failures = 0  # guarded-by: _lock
        self.slow = 0  # guarded-by: _lock
        # Bounded transition log (slot writes, drop-oldest): the soak
        # asserts the open -> half-open -> closed sequence from here.
        self._transitions: List[Optional[tuple]] = (
            [None] * _TRANSITION_CAP)  # guarded-by: _lock
        self._transition_idx = 0  # guarded-by: _lock

    # ----------------------------------------------------- transitions

    def _set_state_locked(self, new: str) -> None:
        """Record a state change; callers hold _lock (RLock re-entry
        keeps the guarded accesses lexically locked)."""
        with self._lock:
            old = self._state
            if old == new:
                return
            self._state = new
            self._transitions[self._transition_idx % _TRANSITION_CAP] = (
                time.time(), old, new)
            self._transition_idx += 1
        metrics.set_gauge(("admission", "breaker_state"), _LEVELS[new])

    def _trip_locked(self, reason: str) -> None:
        with self._lock:
            self.trips += 1
            self._opened_at = time.monotonic()
            self._probe_inflight = False
            self._consec_failures = 0
            self._consec_slow = 0
            self._set_state_locked(BREAKER_OPEN)
        metrics.incr_counter(("admission", "breaker_trip"))

    # ------------------------------------------------------------ gate

    def acquire(self) -> bool:
        """Consuming gate at the device-dispatch call site. A True
        return MUST be matched by exactly one record_success /
        record_failure (the half-open probe slot is held until then)."""
        if not self.enabled:
            return True
        with self._lock:
            if self._state == BREAKER_CLOSED:
                return True
            if self._state == BREAKER_OPEN:
                if time.monotonic() - self._opened_at < self.cooldown:
                    self.rejected += 1
                    return False
                # Cool-down over: half-open, this caller is the probe.
                self.half_opens += 1
                self._probe_inflight = True
                self._set_state_locked(BREAKER_HALF_OPEN)
                return True
            # HALF_OPEN: one probe at a time.
            if self._probe_inflight:
                self.rejected += 1
                return False
            self._probe_inflight = True
            return True

    def should_route_host(self) -> bool:
        """Non-consuming routing hint for the dispatch pipeline: True
        only while OPEN inside the cool-down. Once the cool-down
        elapses this returns False so dense-path traffic reaches the
        acquire() gate and one eval probes."""
        if not self.enabled:
            return False
        with self._lock:
            return (self._state == BREAKER_OPEN
                    and time.monotonic() - self._opened_at < self.cooldown)

    # --------------------------------------------------------- results

    def record_success(self, duration_ms: float = 0.0) -> None:
        if not self.enabled:
            return
        slow = bool(self.slow_ms and duration_ms >= self.slow_ms)
        with self._lock:
            self.successes += 1
            if self._state == BREAKER_HALF_OPEN:
                self._probe_inflight = False
                if slow:
                    # The device answered the probe but at overload
                    # latency: that is not recovery — re-open.
                    self.slow += 1
                    self._trip_locked("slow probe")
                    return
                self.recloses += 1
                self._consec_failures = 0
                self._consec_slow = 0
                self._set_state_locked(BREAKER_CLOSED)
                return
            self._consec_failures = 0
            if slow:
                self.slow += 1
                self._consec_slow += 1
                if self._consec_slow >= self.slow_batches:
                    self._trip_locked("consecutive slow batches")
            else:
                self._consec_slow = 0

    def record_failure(self) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.failures += 1
            if self._state == BREAKER_HALF_OPEN:
                # The probe failed: straight back to open.
                self._probe_inflight = False
                self._trip_locked("probe failure")
                return
            self._consec_failures += 1
            if (self._state == BREAKER_CLOSED
                    and self._consec_failures >= self.failure_threshold):
                self._trip_locked("consecutive failures")

    # ----------------------------------------------------- observation

    def state(self) -> str:
        with self._lock:
            return self._state

    def transitions(self) -> List[tuple]:
        """(wall time, from, to) transitions, oldest first (bounded)."""
        with self._lock:
            n = min(self._transition_idx, _TRANSITION_CAP)
            start = self._transition_idx - n
            return [self._transitions[(start + k) % _TRANSITION_CAP]
                    for k in range(n)]

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "state": self._state,
                "failure_threshold": self.failure_threshold,
                "slow_ms": self.slow_ms,
                "slow_batches": self.slow_batches,
                "cooldown": self.cooldown,
                "consecutive_failures": self._consec_failures,
                "consecutive_slow": self._consec_slow,
                "probe_inflight": self._probe_inflight,
                "trips": self.trips,
                "half_opens": self.half_opens,
                "recloses": self.recloses,
                "rejected": self.rejected,
                "successes": self.successes,
                "failures": self.failures,
                "slow": self.slow,
                "transitions": [
                    {"at": round(t, 3), "from": a, "to": b}
                    for (t, a, b) in (
                        tr for tr in self._transitions if tr is not None)
                ],
            }

    # --------------------------------------------------------- control

    def configure(self, failure_threshold: Optional[int] = None,
                  slow_ms: Optional[float] = None,
                  slow_batches: Optional[int] = None,
                  cooldown: Optional[float] = None,
                  enabled: Optional[bool] = None) -> None:
        """Update thresholds in place (server boot / operator retune).
        Keeps current state and counters — reconfiguring a live breaker
        must not silently un-trip it; use reset() for that."""
        if failure_threshold is not None:
            self.failure_threshold = max(1, failure_threshold)
        if slow_ms is not None:
            self.slow_ms = slow_ms
        if slow_batches is not None:
            self.slow_batches = max(1, slow_batches)
        if cooldown is not None:
            self.cooldown = cooldown
        if enabled is not None:
            self.enabled = enabled

    def configure_defaults(self) -> None:
        """Restore the constructor-default thresholds (test-isolation
        helper for the process-global singleton: fixtures restoring
        the breaker must not hand-copy the defaults — a drifted copy
        silently reconfigures every later test)."""
        d = CircuitBreaker()
        self.configure(failure_threshold=d.failure_threshold,
                       slow_ms=d.slow_ms, slow_batches=d.slow_batches,
                       cooldown=d.cooldown, enabled=d.enabled)

    def reset(self) -> None:
        """Back to closed with zeroed counters (tests; operator
        override after a confirmed repair)."""
        with self._lock:
            self._state = BREAKER_CLOSED
            self._opened_at = 0.0
            self._probe_inflight = False
            self._consec_failures = 0
            self._consec_slow = 0
            self.trips = 0
            self.half_opens = 0
            self.recloses = 0
            self.rejected = 0
            self.successes = 0
            self.failures = 0
            self.slow = 0
            self._transitions = [None] * _TRANSITION_CAP
            self._transition_idx = 0


# Process-global instance: the breaker guards the ONE shared device
# path, so every scheduler/pipeline thread must see the same verdict
# (the placement batcher is global for the same reason).
_global = CircuitBreaker()


def get_breaker() -> CircuitBreaker:
    return _global
