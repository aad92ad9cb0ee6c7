"""The benchmark's `alibaba-colo-4k` deployment against a live dev
server: its committed configuration at rehearsal scale loaded by
`benchmark/fleet.py`, two bursts of its eleven shapes registered over
HTTP as the generator registers them (tasks of 1 to 130 instances in
five ask rungs and an application scale-out, in one pipeline batch), and
the store's dump judged by the deployment's own check,
`benchmark/checks/alibaba_colo.py`. Tier-1 does not run
`benchmark/tests/` (its `test_alibaba.py` holds the files' sums and
whole rehearsals), so this keeps the program's hand-over between plain
dispatches, the traffic file and the check's reading of the dump
together: sound placements read 0 on every count; a doctored dump reads
each count in turn."""

import importlib.util
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from nomad_tpu.api.http import HTTPServer
from nomad_tpu.models.matrix import ASK_BUCKETS, bucket_size
from nomad_tpu.scheduler.batcher import get_batcher
from nomad_tpu.server import Server, ServerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTS = ("tasks_short_of_their_count", "tasks_past_their_count",
          "app_containers_sharing_a_machine", "machines_over_cpu_or_memory",
          "window_allocs_on_unready_nodes")


def _load(name):
    """A module of benchmark/ under a name of its own (the directory is
    not a package and its module names are common ones)."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name.replace('/', '_')}",
        os.path.join(REPO, "benchmark", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def committed():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "alibaba-colo-4k")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "alibaba-colo-4k.stages")
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    return bench, entry, cell, config, traffic


def test_the_committed_files_load_and_validate():
    bench, entry, cell, config, traffic = committed()
    fleet = _load("fleet")
    assert entry["reduced"] == config["reduced"] == [
        "instance_num_tail", "arrival_rate"]
    assert entry["source"] == config["source"] and cell["chips"] == 1
    assert config["checks"] == ["alibaba_colo"]
    specs = fleet.job_specs(config)
    assert len(specs) == 11
    assert abs(sum(s["share"] for s in specs) - 1.0) < 1e-9
    # every rung of the ask ladder is met, and the one past 1,024
    assert {bucket_size(s["count"], ASK_BUCKETS) for s in specs} == \
        set(ASK_BUCKETS)
    for spec in specs:
        body = fleet.job_template(spec)     # validates, or raises
        assert body["task_groups"][0]["count"] == spec["count"]
    arrivals = traffic["arrivals"]
    assert (traffic["kind"], arrivals["process"], arrivals["burst_size"],
            traffic["max_in_flight"]) == ("open", "bursts", 8, 512)
    # warm-up's rounds meet each of the eleven shapes at least twice
    assert sum(r // len(specs) for r in traffic["warmup"]["rounds"]) >= 2
    small = fleet.scaled(config, True)
    assert {s["name"] for s in fleet.job_specs(small)} == \
        set(config["rehearsal"]["job_count"])


@pytest.fixture(scope="module")
def placed():
    """(store dump, window jobs, configuration, counters) after two
    bursts of one job a shape on the committed fleet at rehearsal
    scale."""
    fleet, httpc, store_dump = (_load(name) for name in (
        "fleet", "httpc", "store_dump"))
    config = fleet.scaled(committed()[3], True)
    server = Server(ServerConfig(**config["server"]))
    server.start()
    http = HTTPServer(server, host="127.0.0.1", port=0)
    http.start()
    conn = httpc.Conn(http.addr)
    try:
        loaded = fleet.load_fleet(server, config, 2**31 + 4501)
        assert loaded["nodes"] == sum(
            c["count"] for c in config["fleet"]["classes"])
        window_jobs = {}
        before = get_batcher().stats()

        def register(body):
            c = httpc.Conn(http.addr)
            try:
                return c.request("PUT", "/v1/jobs", body)[0]["eval_id"]
            finally:
                c.close()

        for burst in range(2):
            bodies = []
            for spec in fleet.job_specs(config):
                job_id = f"stages-b{burst}-{spec['name']}"
                bodies.append(json.dumps({"job": dict(
                    fleet.job_template(spec), id=job_id,
                    name=job_id)}).encode())
                window_jobs[job_id] = {
                    "count": spec["count"], "template": spec["name"],
                    "distinct_hosts": spec["distinct_hosts"]}
            # At once, as a burst of the cell comes.
            with ThreadPoolExecutor(len(bodies)) as pool:
                evals = list(pool.map(register, bodies))
            deadline = time.monotonic() + 120.0
            for eval_id in evals:
                while time.monotonic() < deadline:
                    ev, _ = conn.request("GET", f"/v1/evaluation/{eval_id}")
                    if ev["status"] in ("complete", "failed", "cancelled"):
                        break
                    time.sleep(0.05)
                assert ev["status"] == "complete", ev
        after = get_batcher().stats()
        store = store_dump.dump_store(server.fsm.state.snapshot())
        counters = {key: after[key] - before[key] for key in (
            "dispatches", "batched_requests", "plain_handovers",
            "token_queues", "claims_wait_expired")}
        yield store, window_jobs, config, counters
    finally:
        conn.close()
        http.stop()
        server.shutdown()


def test_the_committed_deployment_places_every_task_whole(placed):
    store, window_jobs, config, counters = placed
    check = _load("checks/alibaba_colo").check
    assert check(store, window_jobs, config) == dict.fromkeys(COUNTS, 0)
    # the bursts were batches of several queues on one snapshot, served
    # by the device one dispatch after another. (An HTTP burst can fall
    # into two pipeline batches; a straggler alone takes the host
    # route: routing, not a fault.)
    assert counters["batched_requests"] >= 12
    assert counters["token_queues"] >= 5
    assert counters["plain_handovers"] >= 3
    assert counters["claims_wait_expired"] == 0


def _doctored(store, window_jobs, case):
    """The dump with one thing wrong, and the count that has to see it."""
    job_of = {job: i for i, job in enumerate(store["job_ids"])}
    drop = None
    store = dict(store)
    if case == "tasks_short_of_their_count":
        t = job_of["stages-b0-t120"]
        drop = int(np.flatnonzero(store["alloc_job"] == t)[0])
    elif case == "tasks_past_their_count":
        window_jobs = dict(window_jobs)
        window_jobs["stages-b1-t30"] = dict(
            window_jobs["stages-b1-t30"],
            count=window_jobs["stages-b1-t30"]["count"] - 1)
    elif case == "app_containers_sharing_a_machine":
        rows = np.flatnonzero(store["alloc_job"] == job_of["stages-b0-app-8"])
        nodes = store["alloc_node"].copy()
        nodes[rows[1]] = nodes[rows[0]]
        store["alloc_node"] = nodes
    elif case == "machines_over_cpu_or_memory":
        cap = store["node_cap"].copy()
        row = int(store["alloc_node"][0])
        cap[row][0] = store["node_reserved"][row][0] + 100
        store["node_cap"] = cap
    elif case == "window_allocs_on_unready_nodes":
        t = job_of["stages-b1-t1"]
        row = int(store["alloc_node"][np.flatnonzero(
            store["alloc_job"] == t)[0]])
        ready = store["node_ready"].copy()
        ready[row] = False
        store["node_ready"] = ready
    if drop is not None:
        for key in ("alloc_job", "alloc_node", "alloc_usage"):
            store[key] = np.delete(store[key], drop, axis=0)
    return store, window_jobs


@pytest.mark.parametrize("case", COUNTS)
def test_each_count_of_the_check_fires_on_a_doctored_store(placed, case):
    store, window_jobs, config, _counters = placed
    check = _load("checks/alibaba_colo").check
    counts = check(*_doctored(store, window_jobs, case), config)
    assert counts[case] >= 1
    # and nothing else does, but for what the doctoring itself implies:
    # a container moved onto its sibling's machine may fill it past its
    # cores (BestFit had packed it)
    implied = {"app_containers_sharing_a_machine":
               {"machines_over_cpu_or_memory"}}.get(case, set())
    others = {k: v for k, v in counts.items()
              if k != case and v and k not in implied}
    assert not others, others
