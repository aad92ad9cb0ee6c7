"""HTTP API + SDK tests (mirror command/agent/*_endpoint_test.go and
api/ black-box tests)."""

import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.api import Client, HTTPServer
from nomad_tpu.api.client import APIError
from nomad_tpu.client import MockClient
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import consts


def wait_until(fn, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


@pytest.fixture
def api():
    server = Server(ServerConfig(num_schedulers=1, eval_nack_timeout=5.0))
    server.start()
    http = HTTPServer(server)
    http.start()
    client = Client(http.addr, timeout=10.0)
    mc = MockClient(server)
    mc.start()
    yield client, server
    mc.stop()
    http.stop()
    server.shutdown()


def test_job_lifecycle_over_http(api):
    client, server = api
    job = mock.job()
    job.task_groups[0].count = 2

    eval_id = client.jobs.register(job)
    assert eval_id

    # eval completes and allocs appear
    assert wait_until(
        lambda: client.evaluations.info(eval_id)[0].status
        == consts.EVAL_STATUS_COMPLETE
    )
    allocs, idx = client.jobs.allocations(job.id)
    assert len(allocs) == 2
    assert idx > 0

    out, _ = client.jobs.info(job.id)
    assert out.id == job.id

    jobs, _ = client.jobs.list()
    assert any(j["id"] == job.id for j in jobs)

    summary, _ = client.jobs.summary(job.id)
    assert "web" in summary["summary"]

    evals, _ = client.jobs.evaluations(job.id)
    assert any(e.id == eval_id for e in evals)

    # deregister
    client.jobs.deregister(job.id)
    with pytest.raises(APIError) as excinfo:
        wait_until(lambda: client.jobs.info(job.id) and False, timeout=2.0)
    assert excinfo.value.status == 404


def test_blocking_query_fires_on_change(api):
    client, server = api
    job = mock.job()
    job.task_groups[0].count = 1
    client.jobs.register(job)
    assert wait_until(lambda: len(client.jobs.allocations(job.id)[0]) == 1)

    _, idx = client.jobs.allocations(job.id)
    results = {}

    def blocker():
        # long-poll: returns when a new alloc change lands
        t0 = time.monotonic()
        out, new_idx = client.jobs.allocations(job.id, index=idx, wait=5.0)
        results["elapsed"] = time.monotonic() - t0
        results["index"] = new_idx

    t = threading.Thread(target=blocker)
    t.start()
    time.sleep(0.3)
    client.jobs.evaluate(job.id)  # may or may not change allocs
    server.job_deregister(job.id)  # definitely stops the alloc
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert results["index"] > idx
    assert results["elapsed"] < 5.0  # returned before the full wait


def test_nodes_over_http(api):
    client, server = api
    nodes, _ = client.nodes.list()
    assert len(nodes) == 1
    node, _ = client.nodes.info(nodes[0]["id"])
    assert node.status == consts.NODE_STATUS_READY

    client.nodes.drain(node.id, True)
    assert wait_until(
        lambda: client.nodes.info(node.id)[0].drain is True
    )
    client.nodes.drain(node.id, False)

    # secret-gated alloc listing (node_endpoint.go:585 GetClientAllocs)
    with pytest.raises(APIError) as excinfo:
        client.nodes.allocations(node.id, secret="wrong")
    assert excinfo.value.status == 403


def test_plan_over_http(api):
    client, server = api
    job = mock.job()
    job.task_groups[0].count = 3
    out = client.jobs.plan(job)
    assert out["annotations"]["desired_tg_updates"]["web"]["place"] == 3
    with pytest.raises(APIError):
        client.jobs.info(job.id)  # dry run committed nothing


def test_agent_and_system_endpoints(api):
    client, server = api
    info = client.agent.self()
    assert info["stats"]["leader"] is True
    assert client.agent.leader() != ""
    client.system.garbage_collect()  # should not raise


def test_unknown_route_404(api):
    client, server = api
    with pytest.raises(APIError) as excinfo:
        client.get("/v1/bogus")
    assert excinfo.value.status == 404


def test_blocking_query_times_out_with_current_state(api):
    """An unchanged watch returns at the wait deadline with the current
    index (rpc.go:334 blockingRPC timeout path), not an error."""
    client, server = api
    job = mock.job()
    job.task_groups[0].count = 1
    client.jobs.register(job)
    # Settle fully (alloc placed, eval complete) so no async write
    # fires the watch after we capture the index. (This fixture runs no
    # client agent, so alloc status never changes after placement.)
    assert wait_until(lambda: len(client.jobs.allocations(job.id)[0]) == 1)
    assert wait_until(
        lambda: all(a.get("client_status") == "running"
                    for a in client.jobs.allocations(job.id)[0]))
    assert wait_until(
        lambda: (evs := client.jobs.evaluations(job.id)[0])
        and all(e.status == "complete" for e in evs))
    _, idx = client.jobs.allocations(job.id)

    t0 = time.monotonic()
    out, new_idx = client.jobs.allocations(job.id, index=idx, wait=0.5)
    elapsed = time.monotonic() - t0
    assert 0.4 <= elapsed < 3.0  # waited the window, then answered
    assert len(out) == 1
    assert new_idx >= idx


def test_blocking_query_stale_index_returns_immediately(api):
    """index below the current state answers without waiting."""
    client, server = api
    job = mock.job()
    job.task_groups[0].count = 1
    client.jobs.register(job)
    assert wait_until(lambda: len(client.jobs.allocations(job.id)[0]) == 1)

    _, cur = client.jobs.allocations(job.id)
    t0 = time.monotonic()
    # a POSITIVE index below current drives the stale-index comparison
    # (index=0 would take the non-blocking fast path instead)
    out, new_idx = client.jobs.allocations(job.id, index=max(cur - 1, 1),
                                           wait=5.0)
    assert time.monotonic() - t0 < 1.0
    assert len(out) == 1 and new_idx >= cur


# ---------------------------------------------------------------------
# overload admission (nomad_tpu/admission): 429/503 + Retry-After,
# effective long-poll timeout echo


def _raw_request(addr, path, method="GET", body=None):
    """Raw urllib call returning (status, headers, json_body) — the SDK
    client hides headers, and Retry-After is the point here."""
    import json as _json
    import urllib.error
    import urllib.request

    data = _json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(addr + path, data=data, method=method)
    req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=10.0) as resp:
            return resp.status, dict(resp.headers), _json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), _json.loads(e.read())


def test_internal_dequeue_echoes_effective_timeout(api, monkeypatch):
    client, server = api
    addr = client.address.rstrip("/")
    # An over-limit ask is clamped AND the clamp is reported. The cap
    # is shrunk so the clamped long-poll returns within the test
    # budget instead of parking for the real 300s.
    from nomad_tpu.api import http as http_mod

    monkeypatch.setattr(http_mod, "MAX_BLOCKING_WAIT", 0.2)
    status, _h, out = _raw_request(
        addr, "/v1/internal/eval/dequeue", method="POST",
        body={"schedulers": [], "timeout": 99999.0})
    assert status == 200
    assert out["timeout"] == 0.2  # the effective (clamped) budget
    assert out["eval"] is None
    # An in-budget ask echoes itself.
    status, _h, out = _raw_request(
        addr, "/v1/internal/eval/dequeue", method="POST",
        body={"schedulers": [], "timeout": 0.05})
    assert status == 200
    assert out["timeout"] == 0.05


def test_admission_red_sheds_writes_with_retry_after(api):
    client, server = api
    addr = client.address.rstrip("/")
    server.admission.force_level("red")
    try:
        job = mock.job()
        from nomad_tpu.utils.codec import to_dict

        status, headers, out = _raw_request(
            addr, "/v1/jobs", method="PUT", body={"job": to_dict(job)})
        assert status == 503
        assert float(headers["Retry-After"]) > 0
        assert "retry_after" in out
        # Observability stays reachable while shedding.
        status, _h, _out = _raw_request(addr, "/v1/metrics?format=json")
        assert status == 200
        # Internal leader-forward routes stay reachable.
        status, _h, out = _raw_request(
            addr, "/v1/internal/eval/dequeue", method="POST",
            body={"schedulers": [], "timeout": 0.01})
        assert status == 200
    finally:
        server.admission.force_level(None)
    # Back to green: writes flow again.
    eval_id = client.jobs.register(mock.job())
    assert eval_id


def test_admission_yellow_rate_limits_writes_429(api):
    client, server = api
    addr = client.address.rstrip("/")
    # Drain the write bucket to a deterministic empty.
    server.admission._write.rate = 0.0
    server.admission._write.burst = 0.0
    with server.admission._write._lock:
        server.admission._write._tokens = 0.0
    server.admission.force_level("yellow")
    try:
        from nomad_tpu.utils.codec import to_dict

        status, headers, _out = _raw_request(
            addr, "/v1/jobs", method="PUT",
            body={"job": to_dict(mock.job())})
        assert status == 429
        assert float(headers["Retry-After"]) > 0
        # Reads pass under yellow.
        status, _h, _out = _raw_request(addr, "/v1/jobs")
        assert status == 200
    finally:
        server.admission.force_level(None)
        server.admission._write.rate = 50.0
        server.admission._write.burst = 100.0


# ------------------------------------------- the client's path (PR 40)


def _http_rows(rec):
    return {stage: row for stage, row in rec.stage_stats().items()
            if stage.startswith("http.")}


@pytest.mark.parametrize(
    "case", ["register", "eval_inline", "eval_parked", "other"])
def test_client_path_rows_of_a_request(api, case):
    """The two route families on every client's path feed the
    recorder's `http.<family>.*` rows, all of a request's in one call
    after its reply: front + reply lie inside request, the thread's CPU
    time cannot pass the wall time, a request that parks has no reply
    row, and another route (or another method on the same route) feeds
    nothing."""
    from nomad_tpu.trace import HTTP_STAGES, get_recorder

    client, server = api
    rec = get_recorder()
    rec.set_enabled(True)
    job = mock.job()
    job.task_groups[0].count = 1
    if case != "register":
        eval_id = client.jobs.register(job)
        assert wait_until(
            lambda: server.fsm.state.eval_by_id(eval_id).status
            == consts.EVAL_STATUS_COMPLETE)
        _ev, index = client.evaluations.info(eval_id)
        # the rows are fed after the reply is on the wire
        assert wait_until(
            lambda: HTTP_STAGES["eval"][2] in rec.stage_stats())
    rec.reset()

    if case == "other":
        client.jobs.info(job.id)      # the register route, by GET
        client.jobs.list()
        client.nodes.list()
        client.evaluations.list()
        time.sleep(0.1)
        assert _http_rows(rec) == {}
        return

    family = "register" if case == "register" else "eval"
    front, reply, request, cpu = HTTP_STAGES[family]
    if case == "register":
        client.jobs.register(job)
    elif case == "eval_inline":
        client.evaluations.info(eval_id)
    else:
        # Nothing writes the finished eval again: the read parks in the
        # mux and its wait runs out.
        ev, _ = client.evaluations.info(eval_id, index=index, wait=0.2)
        assert ev.status == consts.EVAL_STATUS_COMPLETE
    assert wait_until(lambda: request in rec.stage_stats())
    rows = _http_rows(rec)
    expected = {front, request, cpu} | (
        set() if case == "eval_parked" else {reply})
    assert set(rows) == expected
    assert all(row["count"] == 1 for row in rows.values())
    # stage_stats rounds to a microsecond
    in_request = rows[front]["max_ms"] + (
        rows[reply]["max_ms"] if reply in rows else 0.0)
    assert in_request <= rows[request]["max_ms"] + 0.01
    # ... and a thread clock may tick in jiffies
    assert rows[cpu]["max_ms"] <= rows[request]["max_ms"] + 10.0
    if case == "eval_parked":
        assert wait_until(lambda: "read.serve" in rec.stage_stats())
        assert "read.deliver" not in rec.stage_stats()
