"""The cell PR 33 added, `philly-552.sweeps`: files and entries only
(every file and entry the parent's benchmark had is as it was), correct
at rehearsal size with its own check's counts at 0 and its six metrics
read, and the check's control: handed a `node_meta` rotated by one node
it reads gangs split across racks and the run is not correct."""

import hashlib
import json
import os

import plugins
import run
import store_dump

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data", "philly-552")
CELL = "philly-552.sweeps"
NEW_METRICS = {"gang_select_p50_ms", "gang_select_p95_ms",
               "gang_build_p50_ms", "gang_solve_p50_ms",
               "gang_share_of_eval", "gang_passes_per_eval"}
NEW_FILES = {"configs/philly-552.json", "traffic/sweeps.json",
             "checks/philly_gangs.py", "rooflines/gang.py",
             "tests/test_philly.py",
             "tests/data/philly-552/parent_digests.json",
             "tests/data/philly-552/parent_benchmark.json",
             *(f"metrics/{name}.json" for name in NEW_METRICS)}
COUNTS = {"gangs_split_across_racks", "gangs_partial",
          "members_without_rack", "no_gang_placed",
          "servers_over_their_gpus"}


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
    # one configuration and one cell, at the end
    assert bench["configs"][:-1] == was["configs"]
    assert bench["configs"][-1]["name"] == "philly-552"
    assert bench["configs"][-1]["reduced"] == []
    assert bench["workloads"][:-1] == was["workloads"]
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name=CELL, config="philly-552",
        traffic="sweeps", chips=1)
    # the open loops' latency and its two tails list the cell; every
    # other accepted metric is as it was
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
        assert m["workloads"] == [CELL] and m["layer"] == "gang pass"
        assert m["moves"] == "place_due_p50_ms"

    config = json.load(open(os.path.join(ROOT, bench["configs"][-1]["file"])))
    classes = config["fleet"]["classes"]
    assert sum(c["count"] for c in classes) == 552
    assert sum(c["count"] * int(c["node"]["meta"]["gpus"])
               for c in classes) == 2490
    slot = config["gpu_slot"]
    for c in classes:
        node, gpus = c["node"], int(c["node"]["meta"]["gpus"])
        # exactly g slots after the reserved, in both dimensions; no
        # server full at load
        assert node["cpu"] - node["reserved"]["cpu"] == gpus * slot["cpu"]
        assert node["memory_mb"] - node["reserved"]["memory_mb"] \
            == gpus * slot["memory_mb"]
        assert c["filler"]["per_node"] < gpus
    assert config["reduced"] == [] and config["checks"] == ["philly_gangs"]
    assert all(job["gang"] == {"slice": "rack"} and not job["distinct_hosts"]
               for job in config["jobs"])
    assert abs(sum(job["share"] for job in config["jobs"]) - 1.0) < 1e-9


def test_sweeps_rehearsal_is_correct_and_reads_its_metrics(capsys):
    result, failed, _lines = rehearse(capsys, 2**31 + 3301, trace=1)
    assert result["correct"] is True and not failed, failed
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert {name.split(".", 1)[1] for name in compared
            if name.startswith("philly_gangs.")} == COUNTS
    for name in COUNTS:
        assert compared[f"philly_gangs.{name}"]["value"] == 0
    assert NEW_METRICS <= set(result["metrics"])
    for name in NEW_METRICS | {"place_due_p95_ms", "place_due_p99_ms"}:
        assert result["metrics"][name]["value"] > 0, name
    # the device's gang pass did the work, not the host stack
    assert result["metrics"]["gang_passes_per_eval"]["value"] >= 0.8
    assert compared["device_requests_in_window"]["value"] > 0
    assert result["metrics"]["eval_uncovered_share"]["value"] < 0.01


def test_racks_rotated_by_one_node_are_not_correct(capsys, monkeypatch):
    """The control of the deployment's check: the run is sound, and the
    check is handed racks that are not the fleet's (every node's rack is
    its neighbour's). A gang that lies at a rack's edge then reads as
    split, and the run as not correct."""
    dump = store_dump.dump_store

    def rotated(snapshot):
        store = dump(snapshot)
        racks = store["node_meta"]["rack"]
        store["node_meta"] = dict(store["node_meta"],
                                  rack=racks[1:] + racks[:1])
        return store

    monkeypatch.setattr(run.store_dump, "dump_store", rotated)
    result, failed, _lines = rehearse(capsys, 2**31 + 3302)
    assert result["correct"] is False
    assert result["compared"][
        "philly_gangs.gangs_split_across_racks"]["value"] > 0
    assert [line.split()[2].rstrip(":") for line in failed] == [
        "philly_gangs.gangs_split_across_racks"]


def test_the_check_on_a_doctored_store():
    check = plugins.load("checks", "philly_gangs").check
    config = {"gpu_slot": {"cpu": 4000, "memory_mb": 16384}}
    store = {
        "job_ids": ["filler", "g1", "g2"],
        "node_ids": ["a", "b", "c"],
        "node_cap": [[8200, 0, 0, 0]] * 3,
        "node_reserved": [[200, 0, 0, 0]] * 3,
        "node_meta": {"rack": ["r0", "r0", "r1"], "ici": [""] * 3},
        "alloc_job": [0, 1, 1, 2, 2],
        "alloc_node": [0, 0, 1, 1, 2],
    }
    jobs = {"g1": {"count": 2, "gang": {"slice": "rack"}},
            "g2": {"count": 2, "gang": {"slice": "rack"}}}
    assert check(store, jobs, config) == {
        "gangs_split_across_racks": 1, "gangs_partial": 0,
        "members_without_rack": 0, "no_gang_placed": 0,
        "servers_over_their_gpus": 0}
    # a third allocation on a server of two GPUs
    over = dict(store, alloc_job=[0, 0, 1, 1, 2, 2],
                alloc_node=[0, 0, 0, 1, 1, 1])
    out = check(over, jobs, config)
    assert out["servers_over_their_gpus"] == 2
    assert out["gangs_split_across_racks"] == 0
    # a partial gang, and none whole
    part = check(store, dict(jobs, g1={"count": 3, "gang": {"slice": "rack"}},
                             g2={"count": 3, "gang": {"slice": "rack"}}),
                 config)
    assert part["gangs_partial"] == 2 and part["no_gang_placed"] == 1
