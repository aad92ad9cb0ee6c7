"""The benchmark's counter reader against a live server.

`benchmark/counters.py` indexes `/v1/agent/self` by key and holds 13
failure routes to 0 in every run of every cell. Tier-1 does not run
`benchmark/tests/`, so this keeps the program's stats surface and that
one reader together: a block it indexes that went missing would fail
every cell with a KeyError, not a number."""

import importlib.util
import os
import time

from nomad_tpu import mock
from nomad_tpu.admission import get_breaker
from nomad_tpu.api.http import HTTPServer
from nomad_tpu.scheduler.batcher import get_batcher
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.worker import DEQUEUE_TIMEOUT

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    """A module of benchmark/ under a name of its own (the directory is
    not a package and its module names are common ones)."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name}", os.path.join(REPO, "benchmark", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def wait_until(fn, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def test_read_counters_names_every_failure_route_at_zero():
    counters, httpc = _load("counters"), _load("httpc")
    get_breaker().reset()
    server = Server(ServerConfig(
        num_schedulers=2,
        scheduler_factories={"service": "service-tpu", "batch": "batch-tpu",
                             "system": "system-tpu"}))
    server.start()
    http = HTTPServer(server, host="127.0.0.1", port=0)
    http.start()
    get_batcher()  # so that /v1/agent/self carries its block
    conn = httpc.Conn(http.addr)
    try:
        for _ in range(8):
            node = mock.node()
            node.compute_class()
            server.node_register(node)
        before = counters.read_counters(conn)
        # a storm of four: one batch, so the device path runs
        for w in server.workers:
            w.set_pause(True)
        time.sleep(DEQUEUE_TIMEOUT + 0.3)
        jobs = []
        for _ in range(4):
            job = mock.job()
            job.task_groups[0].count = 5
            server.job_register(job)
            jobs.append(job)
        assert wait_until(lambda: server.broker.ready_count() >= 4, 10.0)
        for w in server.workers:
            w.set_pause(False)
        assert wait_until(lambda: all(
            len(server.fsm.state.allocs_by_job(j.id)) == 5 for j in jobs))
        after = counters.read_counters(conn)
        # The scheduler's counters, the batcher and the breaker belong
        # to the process, and tier-1 runs other files in it first: what
        # is held to 0 here is this server's own stretch.
        for name in counters.FAILURE_ROUTES:
            if name in after:
                after[name] -= before.get(name, 0)

        rows = counters.device_did_the_work(before, after)
        by_name = {name: (value, ok) for name, value, _limit, ok in rows}
        assert by_name["device_requests_in_window"][0] >= 4, by_name
        assert len(counters.FAILURE_ROUTES) == 13
        for name in counters.FAILURE_ROUTES:
            assert by_name[name] == (0, True), (name, by_name[name])
        assert by_name["breaker.state"] == ("closed", True)
        # the one reader of the constant stub (server/server.py stats())
        assert "executive.host_fallbacks" in counters.FAILURE_ROUTES
        assert not [k for k in after if k.startswith("executive.")]
    finally:
        conn.close()
        http.stop()
        server.shutdown()
