"""The benchmark's `borg-attrs-12k` deployment against a live dev
server: its committed configuration at rehearsal scale loaded by
`benchmark/fleet.py`, one job of each of its seven shapes registered
over HTTP as the generator registers it, and the store's dump judged by
the deployment's own check, `benchmark/checks/borg_constraints.py`.
Tier-1 does not run `benchmark/tests/` (its `test_borg_attrs.py` holds
the same cases around whole rehearsals), so this keeps the program's
feasibility mask, the node meta the harness dumps and the check's
reading of them together: sound placements read 0 on every count, a
mask forced all-true from outside reads allocations on machines their
job may not use, and the check's own operands agree with
`scheduler/feasible.py` on a table of cases."""

import importlib.util
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from nomad_tpu.api.http import HTTPServer
from nomad_tpu.models import matrix
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.feasible import check_constraint
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import Plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# [operand, left, right, holds]: the reference's checkConstraint (the
# table `benchmark/tests/test_borg_attrs.py` reads too)
with open(os.path.join(REPO, "benchmark", "tests", "data", "borg-attrs-12k",
                       "operands.json")) as _f:
    OPERANDS = [tuple(row) for row in json.load(_f)]


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
    entry = next(c for c in bench["configs"] if c["name"] == "borg-attrs-12k")
    with open(os.path.join(REPO, entry["file"])) as f:
        return json.load(f)


def place_one_of_each_shape(seed: int) -> dict:
    """The check's counts after one job of each shape was placed on the
    committed fleet at rehearsal scale."""
    fleet, httpc, store_dump, check = (_load(name) for name in (
        "fleet", "httpc", "store_dump", "checks/borg_constraints"))
    config = fleet.scaled(committed_config(), True)
    server = Server(ServerConfig(**config["server"]))
    server.start()
    http = HTTPServer(server, host="127.0.0.1", port=0)
    http.start()
    conn = httpc.Conn(http.addr)
    try:
        loaded = fleet.load_fleet(server, config, seed)
        assert loaded["nodes"] == sum(
            c["count"] for c in config["fleet"]["classes"])
        # At once, as a burst of the cell comes.
        window_jobs, bodies = {}, []
        for spec in fleet.job_specs(config):
            job_id = f"pinned-{spec['name']}"
            bodies.append(json.dumps({"job": dict(
                fleet.job_template(spec), id=job_id, name=job_id)}).encode())
            window_jobs[job_id] = {"count": spec["count"],
                                   "template": spec["name"]}

        def register(body):
            c = httpc.Conn(http.addr)
            try:
                return c.request("PUT", "/v1/jobs", body)[0]["eval_id"]
            finally:
                c.close()

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
        store = store_dump.dump_store(server.fsm.state.snapshot())
        placed = sum(1 for row in store["alloc_job"]
                     if store["job_ids"][int(row)] in window_jobs)
        assert placed == sum(j["count"] for j in window_jobs.values())
        # every machine's rack names its attribute class
        assert all(rack for rack in store["node_meta"]["rack"])
        info, _ = conn.request("GET", "/v1/agent/self")
        classes = info["matrix_compress"]["computed_classes"]
        assert classes == len(set(store["node_meta"]["rack"]))
        return check.check(store, window_jobs, config)
    finally:
        conn.close()
        http.stop()
        server.shutdown()


@pytest.fixture
def fresh_memos():
    matrix._FEAS_CACHE.clear()
    yield
    matrix._FEAS_CACHE.clear()


def test_the_committed_deployment_places_only_where_constraints_allow(
        fresh_memos):
    assert place_one_of_each_shape(2**31 + 3811) == {
        "allocs_on_infeasible_machines": 0, "machines_without_a_class": 0,
        "no_constrained_job_placed": 0}


def test_a_mask_forced_all_true_lands_on_infeasible_machines(
        fresh_memos, monkeypatch):
    """The check's control: the applier verifies capacity, not
    constraints, so with every node feasible for every job the plans
    commit and only the deployment's own check sees where they lie."""
    real = matrix.node_feasibility

    def all_true(*args, **kwargs):
        out = real(*args, **kwargs)
        if isinstance(out, tuple):
            feasible, verdicts = out
            return (np.ones_like(feasible),
                    None if verdicts is None else np.ones_like(verdicts))
        return np.ones_like(out)

    monkeypatch.setattr(matrix, "node_feasibility", all_true)
    counts = place_one_of_each_shape(2**31 + 3812)
    assert counts["allocs_on_infeasible_machines"] > 0
    assert counts["machines_without_a_class"] == 0


@pytest.mark.parametrize("operand,left,right,holds", OPERANDS)
def test_the_checks_operands_agree_with_the_programs(operand, left, right,
                                                     holds):
    check = _load("checks/borg_constraints")
    assert check.operand_holds(operand, left, right) is holds
    assert check_constraint(EvalContext(None, Plan()), operand, left,
                            right) is holds


def test_the_committed_configuration_holds_past_twice_the_old_class_ladder():
    config = committed_config()
    classes = config["fleet"]["classes"]
    racks = sum(-(-c["count"] // c["topology"]["rack"]["nodes_per_group"])
                for c in classes)
    assert sum(c["count"] for c in classes) == 12583
    assert config["computed_classes"] == racks
    assert 256 < racks <= matrix.CLASS_BUCKETS[-1]
    assert matrix.bucket_size(racks, matrix.CLASS_BUCKETS) == 512
    check = _load("checks/borg_constraints")
    narrow = next(j for j in config["jobs"] if j["name"] == "narrow")
    machines = sum(
        c["count"] for c in classes
        if check.meets(narrow["constraints"], dict(c["node"], datacenter="dc1")))
    assert 200 <= machines <= 0.03 * 12583
