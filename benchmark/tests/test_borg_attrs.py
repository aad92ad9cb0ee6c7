"""The cell PR 38 added, `borg-attrs-12k.pinned`: files and entries only
(every file and entry the parent's benchmark had is as it was), correct
at rehearsal size with its own check's counts at 0 and its metrics read;
the check's control: with the feasibility mask forced all-true from
outside, constrained jobs land on machines they may not use and the run
is not correct; the check's own operands agree with the program's on a
table of cases."""

import hashlib
import json
import os

import numpy as np
import pytest

import plugins
import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data", "borg-attrs-12k")
CELL = "borg-attrs-12k.pinned"
NEW_METRICS = {"feas_share_of_eval", "feas_builds_per_eval",
               "compact_dispatch_share"}
NEW_FILES = {"configs/borg-attrs-12k.json", "traffic/pinned.json",
             "checks/borg_constraints.py", "tests/test_borg_attrs.py",
             "tests/data/borg-attrs-12k/parent_digests.json",
             "tests/data/borg-attrs-12k/parent_benchmark.json",
             "tests/data/borg-attrs-12k/operands.json",
             *(f"metrics/{name}.json" for name in NEW_METRICS)}
COUNTS = {"allocs_on_infeasible_machines", "machines_without_a_class",
          "no_constrained_job_placed"}
# [operand, left, right, holds]: the reference's checkConstraint; the
# tier-1 copy of these cases (tests/test_benchmark_borg_attrs.py) reads
# the same table
with open(os.path.join(DATA, "operands.json")) as _f:
    OPERANDS = [tuple(row) for row in json.load(_f)]


def rehearse(capsys, seed, trace=0, seconds=6):
    code = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace), "--rehearse"])
    assert code == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert all(line.startswith("REHEARSAL ") for line in lines)
    return (json.loads(lines[-1][len("REHEARSAL "):]),
            [line for line in lines if line.endswith("FAIL")], lines)


def test_the_cell_is_files_and_entries_only():
    parent = json.load(open(os.path.join(DATA, "parent_digests.json")))
    now = {}
    for base, _dirs, files in os.walk(BENCH):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                now[os.path.relpath(path, BENCH)] = hashlib.sha256(
                    f.read()).hexdigest()
    assert {k: v for k, v in now.items() if k in parent} == parent
    assert set(now) - set(parent) == NEW_FILES

    was = json.load(open(os.path.join(DATA, "parent_benchmark.json")))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for key in ("command", "paths", "run_seconds"):
        assert bench[key] == was[key]
    assert bench["configs"][:-1] == was["configs"]
    assert bench["configs"][-1]["name"] == "borg-attrs-12k"
    assert bench["configs"][-1]["reduced"] == []
    assert bench["workloads"][:-1] == was["workloads"]
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name=CELL, config="borg-attrs-12k",
        traffic="pinned", chips=1)
    listed = {"place_due_p50_ms", "place_due_p95_ms", "place_due_p99_ms"}
    for kind in ("end_to_end", "per_layer"):
        for old, new in zip(was[kind], bench[kind]):
            if old["name"] in listed:
                assert new == dict(old, workloads=old["workloads"] + [CELL])
            else:
                assert new == old
    assert len(bench["end_to_end"]) == len(was["end_to_end"])
    added = bench["per_layer"][len(was["per_layer"]):]
    assert {m["name"] for m in added} == NEW_METRICS
    for m in added:
        assert m["workloads"] == [CELL] and m["layer"] == "feasibility"
        assert m["moves"] == "place_due_p50_ms"


def test_the_configuration_states_what_the_issue_asks():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = json.load(open(os.path.join(ROOT, bench["configs"][-1]["file"])))
    classes = config["fleet"]["classes"]
    assert sum(c["count"] for c in classes) == 12583
    assert len(classes) >= 30 and config["attribute_classes"] == len(classes)
    racks = sum(-(-c["count"] // c["topology"]["rack"]["nodes_per_group"])
                for c in classes)
    assert config["computed_classes"] == racks > 256
    prefixes = [c["topology"]["rack"]["prefix"] for c in classes]
    assert len(set(prefixes)) == len(classes)
    # a prefix ends in a letter, so the check can take the digits off
    assert all(p[-1].isalpha() for p in prefixes)
    assert config["reduced"] == [] and config["checks"] == ["borg_constraints"]
    assert "preemption_enabled" not in config["server"]
    assert "constraints" in config["guarantees"] and config["assumed"]
    for c in classes:
        node, rule = c["node"], c["filler"]
        low = rule["per_node"] * min(rule["memory_mb"])
        high = rule["per_node"] * max(rule["memory_mb"])
        mem = node["memory_mb"]
        assert "priority" not in rule
        assert 0.5 < (low + node["reserved"]["memory_mb"]) / mem
        assert (high + node["reserved"]["memory_mb"]) / mem < 0.72
        assert len(node["meta"]["disks"]) == 1
    jobs = {job["name"]: job for job in config["jobs"]}
    assert list(jobs) == ["free", "platform", "kernel", "family", "notclass",
                          "fastnet", "narrow"]
    assert abs(sum(job["share"] for job in jobs.values()) - 1.0) < 1e-9
    assert abs(sum(job["share"] for name, job in jobs.items()
                   if name != "free") - 0.6) < 1e-9
    assert all(job["count"] == 8 and job["type"] == "service"
               and job["distinct_hosts"] for job in jobs.values())
    operands = {c["operand"] for job in jobs.values()
                for c in job["constraints"]}
    assert operands == {"=", "!=", ">=", "version", "regexp"}
    # how many machines each shape may use, by the check's own operands
    check = plugins.load("checks", "borg_constraints")
    dc = config["fleet"]["datacenter"]
    for name, job in jobs.items():
        n = sum(c["count"] for c in classes
                if check.meets(job["constraints"],
                               dict(c["node"], datacenter=dc)))
        assert config["feasible_machines"][name].startswith(f"{n} of 12583")
        if name == "narrow":
            assert 200 <= n <= 0.03 * 12583 and len(job["constraints"]) == 3


def test_pinned_rehearsal_is_correct_and_reads_its_metrics(capsys):
    result, failed, _lines = rehearse(capsys, 2**31 + 3801, trace=1)
    assert result["correct"] is True and not failed, failed
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert {name.split(".", 1)[1] for name in compared
            if name.startswith("borg_constraints.")} == COUNTS
    for name in COUNTS:
        assert compared[f"borg_constraints.{name}"]["value"] == 0
    assert NEW_METRICS <= set(result["metrics"])
    assert result["metrics"]["compact_dispatch_share"]["value"] == 1.0
    assert result["metrics"]["feas_builds_per_eval"]["value"] < 0.1
    assert result["metrics"]["place_due_p95_ms"]["value"] > 0
    assert compared["device_requests_in_window"]["value"] > 0


def test_a_mask_forced_all_true_is_not_correct(capsys, monkeypatch):
    """The control of the deployment's check: the timed path broken
    underneath. Every node reads feasible for every job, the applier
    verifies capacity and not constraints, so the run commits and only
    the deployment's own check sees where the allocations lie."""
    from nomad_tpu.models import matrix

    real = matrix.node_feasibility

    def all_true(*args, **kwargs):
        out = real(*args, **kwargs)
        if isinstance(out, tuple):
            feasible, verdicts = out
            return (np.ones_like(feasible),
                    None if verdicts is None else np.ones_like(verdicts))
        return np.ones_like(out)

    monkeypatch.setattr(matrix, "node_feasibility", all_true)
    matrix._FEAS_CACHE.clear()
    try:
        result, failed, _lines = rehearse(capsys, 2**31 + 3802)
    finally:
        matrix._FEAS_CACHE.clear()
    assert result["correct"] is False
    assert result["compared"][
        "borg_constraints.allocs_on_infeasible_machines"]["value"] > 0
    assert [line.split()[2].rstrip(":") for line in failed] == [
        "borg_constraints.allocs_on_infeasible_machines"]


@pytest.mark.parametrize("operand,left,right,holds", OPERANDS)
def test_the_checks_operands_agree_with_the_programs(operand, left, right,
                                                     holds):
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.feasible import check_constraint
    from nomad_tpu.structs import Plan

    check = plugins.load("checks", "borg_constraints")
    assert check.operand_holds(operand, left, right) is holds
    assert check_constraint(EvalContext(None, Plan()), operand, left,
                            right) is holds


def test_the_check_on_a_doctored_store():
    check = plugins.load("checks", "borg_constraints").check
    node = {"node_class": "big", "attributes": {"platform": "B"},
            "meta": {"disks": "4"}}
    config = {"fleet": {"datacenter": "dc1", "classes": [
        {"node": node, "topology": {"rack": {"prefix": "a01-r"}}},
        {"node": dict(node, attributes={"platform": "C"}),
         "topology": {"rack": {"prefix": "a02-r"}}}]},
        "jobs": [{"name": "free", "constraints": [
            {"ltarget": "${attr.kernel.name}", "operand": "=",
             "rtarget": "linux"}]},
            {"name": "pinned", "constraints": [
                {"ltarget": "${attr.platform}", "operand": "=",
                 "rtarget": "B"},
                {"ltarget": "${meta.disks}", "operand": ">=",
                 "rtarget": "4"}]}]}
    for cls in config["fleet"]["classes"]:
        cls["node"]["attributes"]["kernel.name"] = "linux"
    store = {"job_ids": ["filler", "j1", "j2"],
             "node_ids": ["n0", "n1", "n2"],
             "node_meta": {"rack": ["a01-r0", "a01-r12", "a02-r0"]},
             "alloc_job": [0, 1, 1, 2, 2], "alloc_node": [2, 0, 1, 1, 2]}
    jobs = {"j1": {"count": 2, "template": "pinned"},
            "j2": {"count": 2, "template": "free"}}
    assert check(store, jobs, config) == {
        "allocs_on_infeasible_machines": 0, "machines_without_a_class": 0,
        "no_constrained_job_placed": 0}
    # the pinned job on a machine of platform C; a rack of no class
    moved = dict(store, alloc_node=[2, 0, 2, 1, 2])
    assert check(moved, jobs, config) == {
        "allocs_on_infeasible_machines": 1, "machines_without_a_class": 0,
        "no_constrained_job_placed": 0}
    bare = dict(store, node_meta={"rack": ["a01-r0", "", "a02-r0"]})
    assert check(bare, jobs, config)["machines_without_a_class"] == 2
    # no pinned job whole
    part = check(store, dict(jobs, j1={"count": 3, "template": "pinned"}),
                 config)
    assert part["no_constrained_job_placed"] == 1
    # an attribute the class does not state fails the constraint
    config["jobs"][1]["constraints"][0]["ltarget"] = "${attr.unique.x}"
    assert check(store, jobs, config)["allocs_on_infeasible_machines"] == 2
