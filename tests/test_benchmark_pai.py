"""The benchmark's `pai-gpu-1800` deployment against a live dev server:
its committed configuration at rehearsal scale loaded by
`benchmark/fleet.py`, two bursts of its seven shapes registered over HTTP
as the generator registers them (one-instance jobs on GPU fractions and
free gangs, some pinned to a GPU type, in one pipeline batch), and the
store's dump judged by the deployment's own check,
`benchmark/checks/pai_shared.py`. Tier-1 does not run
`benchmark/tests/` (its `test_pai.py` holds the same cases around whole
rehearsals), so this keeps the program's mixed batch, the capacity rows
the harness dumps and the check's reading of them together: sound
placements read 0 on every count and no gang is rejected whole; a dump
with one member dropped or its machines' rows rotated does not."""

import importlib.util
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from nomad_tpu.api.http import HTTPServer
from nomad_tpu.scheduler.batcher import get_batcher
from nomad_tpu.server import Server, ServerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTS = {"partial_gangs", "members_on_a_wrong_gpu_type",
          "machines_over_their_gpus", "no_gang_of_the_window_is_whole",
          "no_constrained_job_placed"}


def _load(name):
    """A module of benchmark/ under a name of its own (the directory is
    not a package and its module names are common ones)."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name.replace('/', '_')}",
        os.path.join(REPO, "benchmark", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def committed_config():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "pai-gpu-1800")
    with open(os.path.join(REPO, entry["file"])) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def placed():
    """(store dump, window jobs, configuration, counters) after two
    bursts of one job a shape on the committed fleet at rehearsal
    scale."""
    fleet, httpc, store_dump = (_load(name) for name in (
        "fleet", "httpc", "store_dump"))
    config = fleet.scaled(committed_config(), True)
    server = Server(ServerConfig(**config["server"]))
    server.start()
    http = HTTPServer(server, host="127.0.0.1", port=0)
    http.start()
    conn = httpc.Conn(http.addr)
    try:
        loaded = fleet.load_fleet(server, config, 2**31 + 4211)
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
                job_id = f"shared-b{burst}-{spec['name']}"
                bodies.append(json.dumps({"job": dict(
                    fleet.job_template(spec), id=job_id,
                    name=job_id)}).encode())
                window_jobs[job_id] = {
                    "count": spec["count"], "template": spec["name"],
                    "gang": spec.get("gang")}
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
        counters = {
            "applier": server.plan_applier.stats(),
            "gang": server.stats()["gang"],
            "dispatches": after["dispatches"] - before["dispatches"],
            "mixed_batches": after["mixed_batches"] - before["mixed_batches"],
            "served": after["batched_requests"] - before["batched_requests"]}
        yield store, window_jobs, config, counters
    finally:
        conn.close()
        http.stop()
        server.shutdown()


def test_the_committed_deployment_places_gangs_whole_on_their_gpu_types(
        placed):
    store, window_jobs, config, counters = placed
    check = _load("checks/pai_shared")
    live = {}
    for row in store["alloc_job"]:
        job_id = store["job_ids"][int(row)]
        live[job_id] = live.get(job_id, 0) + 1
    for job_id, spec in window_jobs.items():
        assert live.get(job_id) == spec["count"], job_id
    assert check.check(store, window_jobs, config) == dict.fromkeys(COUNTS, 0)
    # the bursts were mixed batches: gangs and one-instance jobs on the
    # device, the gangs from the plain lanes' claims. (An HTTP burst can
    # fall into two pipeline batches on different snapshots, which no
    # hand-over inside a batch reconciles: on 93 machines one gang in a
    # few runs is rejected whole and replans; tests/test_mixed_batch.py
    # holds the count to 0 where the batch is one.)
    assert counters["applier"]["gangs_rejected"] <= 1
    assert counters["mixed_batches"] >= 1
    assert counters["gang"]["mixed_batches"] >= 1
    # (a straggler of an HTTP burst may fall into a batch of its own and
    # take the host stack alone: routing, not a fault)
    assert counters["gang"]["path_device"] >= 5
    assert counters["served"] >= 9
    # every machine reads as a class of the configuration
    signatures = check.machines_by_signature(config)
    rows = {(*(int(c - r) for c, r in zip(cap[:3], res[:3])), int(mbits))
            for cap, res, mbits in zip(store["node_cap"],
                                       store["node_reserved"],
                                       store["node_mbits"])}
    assert rows == set(signatures)


def test_a_dump_with_a_member_dropped_or_its_machines_rotated_is_caught(
        placed):
    store, window_jobs, config, _counters = placed
    check = _load("checks/pai_shared").check
    wide = next(j for j, job_id in enumerate(store["job_ids"])
                if job_id.endswith("wide-128"))
    drop = int(np.flatnonzero(store["alloc_job"] == wide)[0])
    less = dict(store, **{key: np.delete(store[key], drop, axis=0)
                          for key in ("alloc_job", "alloc_node",
                                      "alloc_usage")})
    counts = check(less, window_jobs, config)
    assert counts["partial_gangs"] == 1
    assert counts["members_on_a_wrong_gpu_type"] == 0
    # by the smallest class of the rehearsal's fleet: every row reads as
    # its neighbour's machine
    turned = dict(store, **{key: np.roll(store[key], 8, axis=0)
                            for key in ("node_cap", "node_reserved",
                                        "node_mbits")})
    counts = check(turned, window_jobs, config)
    assert counts["members_on_a_wrong_gpu_type"] >= 1
    assert counts["partial_gangs"] == 0


def test_the_gpu_axis_is_the_asks_third_axis():
    """What the configuration's `gpu_axis` says of the program: a
    task's canonical disk is 300, so a shape's ephemeral disk is its GPU
    share in units less that, and the dense ask carries the share."""
    from nomad_tpu.models.matrix import ClusterMatrix
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import Resources
    from nomad_tpu.utils.codec import from_dict
    from nomad_tpu.structs import Job

    fleet = _load("fleet")
    config = committed_config()
    axis = config["gpu_axis"]
    assert axis["task_default_disk_mb"] == Resources.DEFAULT_DISK_MB
    shares = {"infer-t4": 0.25, "frac": 0.5, "one": 1, "ps-8": 0.5,
              "v100-8": 1, "train-32": 1, "wide-128": 0.5}
    state = StateStore()
    for spec in fleet.job_specs(config):
        job = from_dict(Job, fleet.job_template(spec))
        job.canonicalize()
        resources = ClusterMatrix(state, job, nodes=[]).build_asks([0])[0]
        assert resources[0][2] == shares[spec["name"]] * axis["units_per_gpu"]
