"""Black-box dense-path test: a SPAWNED `agent -dev -tpu` binary must
place a concurrent storm through the device batcher (testutil/server.go
discipline — exec the real binary, poll its HTTP API). This is the
harness that would have caught the round-4 break, where the live TPU
dispatch path raised AttributeError while every in-process test stayed
green."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HTTP_PORT = 14886
SERF_PORT = 14888


def get(path, timeout=10.0):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{HTTP_PORT}{path}", timeout=timeout) as r:
        return json.loads(r.read())


def put(path, obj, timeout=60.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{HTTP_PORT}{path}",
        data=json.dumps(obj).encode(), method="PUT")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.fixture
def tpu_agent(tmp_path):
    env = {**os.environ,
           "PYTHONPATH": os.pathsep.join(
               p for p in [REPO, os.environ.get("PYTHONPATH", "")] if p),
           # The dense factories are backend-agnostic; this black-box
           # test runs them on the CPU backend.
           "JAX_PLATFORMS": "cpu"}
    log = open(tmp_path / "agent.log", "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "nomad_tpu.cli", "agent", "-dev", "-tpu",
         "-port", str(HTTP_PORT), "-serf-port", str(SERF_PORT)],
        stdout=log, stderr=subprocess.STDOUT, env=env)
    try:
        # Generous: under full-suite load the spawned interpreter's jax
        # import alone can take tens of seconds.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                nodes = get("/v1/nodes", timeout=2.0)
                if nodes and nodes[0]["status"] == "ready":
                    break
            except Exception:
                pass
            time.sleep(0.3)
        else:
            raise AssertionError(
                "agent never became ready: "
                + (tmp_path / "agent.log").read_text()[-2000:])
        yield proc
    finally:
        proc.terminate()
        proc.wait(timeout=15)
        log.close()


def test_spawned_tpu_agent_places_storm_through_batcher(tpu_agent, tmp_path):
    def reg(i):
        job = {"id": f"bb-{i}", "name": f"bb-{i}", "type": "batch",
               "priority": 50, "datacenters": ["dc1"],
               "task_groups": [{"name": "g", "count": 5,
                   "tasks": [{"name": "t", "driver": "mock_driver",
                              "config": {"run_for": 3.0},
                              "resources": {"cpu": 20,
                                            "memory_mb": 16}}]}]}
        put(f"/v1/job/bb-{i}", {"job": job})

    threads = [threading.Thread(target=reg, args=(i,)) for i in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    deadline = time.monotonic() + 120
    placed = 0
    pb = None
    while time.monotonic() < deadline:
        allocs = [a for a in get("/v1/allocations")
                  if a["job_id"].startswith("bb-")]
        placed = len(allocs)
        pb = get("/v1/agent/self").get("placement_batcher")
        if placed >= 50 and pb and pb.get("dispatches", 0) > 0:
            break
        time.sleep(1.0)
    assert placed >= 50, (
        f"storm placed {placed}/50: "
        + (tmp_path / "agent.log").read_text()[-2000:])
    assert pb and pb.get("dispatches", 0) > 0, (
        f"dense path never engaged: {pb}")
    assert pb.get("batched_requests", 0) > pb.get("dispatches", 0), (
        f"dispatches never coalesced: {pb}")
    # The agent knows its device and says so at boot.
    assert "Placement device: cpu" in (tmp_path / "agent.log").read_text()
