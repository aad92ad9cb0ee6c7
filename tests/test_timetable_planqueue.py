"""Direct unit tests for two previously indirectly-covered leader
components: TimeTable (nomad/timetable.go:30 — index<->time ring used
by GC thresholds) and PlanQueue (nomad/plan_queue.go:29 — priority heap
of pending-plan futures, leader-only). Plus the uuid fork-safety hook
and the chunked-streaming HTTP reply path at size."""

import os
import threading
import time

import pytest

from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.server.timetable import TimeTable
from nomad_tpu.structs import Plan, PlanResult


class TestTimeTable:
    def test_witness_and_nearest_index(self):
        tt = TimeTable(granularity=0.0)
        t0 = 1000.0
        for i, dt in ((10, 0.0), (20, 10.0), (30, 20.0)):
            tt.witness(i, t0 + dt)
        assert tt.nearest_index(t0 + 25.0) == 30
        assert tt.nearest_index(t0 + 15.0) == 20
        assert tt.nearest_index(t0 + 5.0) == 10
        assert tt.nearest_index(t0 - 1.0) == 0

    def test_nearest_time(self):
        tt = TimeTable(granularity=0.0)
        tt.witness(10, 1000.0)
        tt.witness(20, 1010.0)
        assert tt.nearest_time(25) == 1010.0
        assert tt.nearest_time(15) == 1000.0
        assert tt.nearest_time(5) == 0.0

    def test_granularity_coalesces(self):
        tt = TimeTable(granularity=5.0)
        tt.witness(1, 1000.0)
        tt.witness(2, 1001.0)  # within granularity: dropped
        tt.witness(3, 1006.0)
        assert tt.nearest_index(1001.0) == 1
        assert tt.nearest_index(1007.0) == 3

    def test_history_limit_trims(self):
        tt = TimeTable(granularity=0.0, limit=10)
        tt.witness(1, 1000.0)
        tt.witness(2, 1020.0)  # 1000.0 is now past the 10s window
        assert tt.nearest_index(1001.0) == 0


class TestPlanQueue:
    def make_plan(self, priority=50):
        plan = Plan()
        plan.priority = priority
        return plan

    def test_disabled_rejects_enqueue(self):
        q = PlanQueue()
        with pytest.raises(Exception):
            q.enqueue(self.make_plan())

    def test_priority_order(self):
        q = PlanQueue()
        q.set_enabled(True)
        lo = q.enqueue(self.make_plan(10))
        hi = q.enqueue(self.make_plan(90))
        assert q.depth() == 2
        first, second = q.dequeue_group(8, timeout=1.0)
        assert first is hi and first.plan.priority == 90
        assert second is lo and q.depth() == 0

    def test_future_resolves_waiter(self):
        q = PlanQueue()
        q.set_enabled(True)
        pending = q.enqueue(self.make_plan())
        got = {}

        def waiter():
            got["result"] = pending.wait(timeout=5.0)

        t = threading.Thread(target=waiter)
        t.start()
        (applier_side,) = q.dequeue_group(8, timeout=1.0)
        result = PlanResult()
        applier_side.respond(result, None)
        t.join(timeout=5.0)
        assert got["result"] is result

    def test_future_propagates_error(self):
        q = PlanQueue()
        q.set_enabled(True)
        pending = q.enqueue(self.make_plan())
        q.dequeue_group(8, timeout=1.0)[0].respond(None, RuntimeError("boom"))
        with pytest.raises(RuntimeError, match="boom"):
            pending.wait(timeout=5.0)

    def test_disable_flushes(self):
        q = PlanQueue()
        q.set_enabled(True)
        pending = q.enqueue(self.make_plan())
        q.set_enabled(False)
        # The parked plan fails rather than hanging its worker forever.
        with pytest.raises(Exception):
            pending.wait(timeout=5.0)
        assert q.depth() == 0

    def test_dequeue_timeout_returns_nothing(self):
        q = PlanQueue()
        q.set_enabled(True)
        assert q.dequeue_group(8, timeout=0.05) == []

    def test_group_stops_at_the_allocation_bound(self):
        """Plans are taken while their summed allocations fit the
        bound; the rest stay queued, in order, for the next group."""
        q = PlanQueue()
        q.set_enabled(True)
        pendings = []
        for n_allocs in (2, 2, 2):
            plan = self.make_plan()
            plan.node_allocation = {"n": [object()] * n_allocs}
            pendings.append(q.enqueue(plan))
        assert [p.n_allocs for p in pendings] == [2, 2, 2]
        assert q.dequeue_group(5, timeout=1.0) == pendings[:2]
        assert q.dequeue_group(1, timeout=1.0) == pendings[2:]


def test_generate_uuid_fork_safe():
    """A forked child must not replay the parent's buffered entropy
    (utils/ids.py register_at_fork hook)."""
    from nomad_tpu.utils.ids import generate_uuid

    generate_uuid()  # warm the parent's batch buffer
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:  # child
        os.close(r)
        ids = ",".join(generate_uuid() for _ in range(8))
        os.write(w, ids.encode())
        os.close(w)
        os._exit(0)
    os.close(w)
    child_ids = os.read(r, 65536).decode().split(",")
    os.close(r)
    os.waitpid(pid, 0)
    parent_ids = [generate_uuid() for _ in range(8)]
    assert not (set(child_ids) & set(parent_ids)), "fork replayed entropy"


def test_chunked_stream_reply_large_payload():
    """A multi-megabyte streamed RawResponse survives HTTP chunked
    framing intact (the sticky-disk snapshot path at size)."""
    import urllib.request

    from nomad_tpu.api import HTTPServer
    from nomad_tpu.api.http import RawResponse
    from nomad_tpu.server import Server, ServerConfig

    blob = os.urandom(3 * 1024 * 1024)

    srv = Server(ServerConfig(num_schedulers=0))
    srv.start()
    http = HTTPServer(srv)

    def fake_route(method, query, body):
        def stream(w):
            for off in range(0, len(blob), 65536):
                w.write(blob[off:off + 65536])
        return RawResponse(stream=stream, content_type="application/x-tar")

    orig_handle = http.handle

    def handle(req):
        if req.path == "/stream-test":
            return fake_route(None, None, None)
        return orig_handle(req)

    http.handle = handle
    http.start()
    try:
        with urllib.request.urlopen(http.addr + "/stream-test",
                                    timeout=30) as resp:
            data = resp.read()
        assert data == blob
    finally:
        http.stop()
        srv.shutdown()
