"""PlanQueue: leader-only priority queue of pending plans.

Reference: nomad/plan_queue.go:29 — plans are futures: the worker blocks
on the result while the single plan applier serializes commits.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from typing import List, Optional, Tuple

from ..structs import Plan, PlanResult


class PendingPlan:
    """A queued plan and its response future."""

    __slots__ = ("plan", "n_allocs", "enqueue_time", "_event", "_result",
                 "_error")

    def __init__(self, plan: Plan):
        self.plan = plan
        # What the plan would put into a raft entry if all of it were
        # accepted: the measure the applier's group bound is in.
        self.n_allocs = sum(
            len(allocs)
            for leg in (plan.node_update, plan.node_allocation,
                        plan.node_preemptions)
            for allocs in leg.values())
        self.enqueue_time = time.monotonic()
        self._event = threading.Event()
        self._result: Optional[PlanResult] = None
        self._error: Optional[Exception] = None

    def respond(self, result: Optional[PlanResult], error: Optional[Exception]) -> None:
        self._result = result
        self._error = error
        self._event.set()

    def wait(self, timeout: Optional[float] = None) -> PlanResult:
        if not self._event.wait(timeout):
            raise TimeoutError("plan apply timed out")
        if self._error is not None:
            raise self._error
        return self._result


class PlanQueue:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._enabled = False
        self._heap: List[Tuple[int, int, PendingPlan]] = []
        self._counter = itertools.count()

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            self._enabled = enabled
            if not enabled:
                for _, _, pending in self._heap:
                    pending.respond(None, RuntimeError("plan queue disabled"))
                self._heap = []
            self._cond.notify_all()

    def enabled(self) -> bool:
        with self._lock:
            return self._enabled

    def enqueue(self, plan: Plan) -> PendingPlan:
        pending = PendingPlan(plan)
        with self._lock:
            if not self._enabled:
                raise RuntimeError("plan queue is disabled")
            heapq.heappush(
                self._heap, (-plan.priority, next(self._counter), pending)
            )
            self._cond.notify()
            return pending

    def dequeue_group(self, max_allocs: int,
                      timeout: Optional[float] = None) -> List[PendingPlan]:
        """Every pending plan, in heap order (priority, then arrival),
        while their summed allocations stay within `max_allocs`. Waits
        up to `timeout` for the first plan and never for a second: one
        plan queued is a group of one. The first plan is always taken,
        so a plan larger than the bound is a group of its own. Empty
        on timeout or when disabled."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while True:
                if not self._enabled:
                    return []
                if self._heap:
                    break
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return []
                self._cond.wait(remaining if remaining is not None else 1.0)
            group = [heapq.heappop(self._heap)[2]]
            total = group[0].n_allocs
            while (self._heap
                   and total + self._heap[0][2].n_allocs <= max_allocs):
                pending = heapq.heappop(self._heap)[2]
                total += pending.n_allocs
                group.append(pending)
            return group

    def depth(self) -> int:
        with self._lock:
            return len(self._heap)
