"""The cell PR 45 added, `alibaba-colo-4k.stages`: it is files and
entries only (against the PARENT's digests); the committed files state
what the deployment is; the shares sum to 1 and the window's demand, by
largest remainders, is the same on every seed; the capacity sum of the
file's `deployment` text holds at the mean and on 200 seeds of the
schedule; the check's five counts on a doctored store; the cell is
correct at rehearsal size and reads its metrics. No test here pins the
set of files under `benchmark/` for later PRs (`ROADMAP.md` M1 (k))."""

import hashlib
import json
import os
import random

import numpy as np
import pytest

import fleet
import plugins
import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data", "alibaba-colo-4k")
CONFIG = "alibaba-colo-4k"
CELL = "alibaba-colo-4k.stages"
NEW_METRICS = {"dispatches_per_batch", "plain_handovers_per_eval"}
NEW_FILES = {"configs/alibaba-colo-4k.json", "traffic/stages.json",
             "checks/alibaba_colo.py", "readers/span_count_over.py",
             "tests/test_alibaba.py",
             "tests/data/alibaba-colo-4k/parent_digests.json",
             "tests/data/alibaba-colo-4k/parent_benchmark.json",
             "tests/data/alibaba-colo-4k/capacity.json",
             *(f"metrics/{name}.json" for name in NEW_METRICS)}
COUNTS = {"tasks_short_of_their_count", "tasks_past_their_count",
          "app_containers_sharing_a_machine", "machines_over_cpu_or_memory",
          "window_allocs_on_unready_nodes"}
with open(os.path.join(DATA, "capacity.json")) as _f:
    CAPACITY = json.load(_f)


def committed():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "stages.json")))
    return bench, entry, config, traffic


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
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][:-1] == was["workloads"]
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name=CELL, config=CONFIG, traffic="stages",
        chips=1)
    assert len(bench["workloads"]) == 9
    accepted = [w["name"] for w in was["workloads"]]
    due = {"place_due_p50_ms", "place_due_p95_ms", "place_due_p99_ms"}
    for kind in ("end_to_end", "per_layer"):
        for old, new in zip(was[kind], bench[kind]):
            # the lists that name every accepted cell, and the open
            # loop's latencies, gain the cell; nothing else moves
            if old["name"] in due or set(old.get("workloads", ())) \
                    >= set(accepted):
                assert new == dict(old, workloads=old["workloads"] + [CELL])
            else:
                assert new == old
    assert len(bench["end_to_end"]) == len(was["end_to_end"])
    added = bench["per_layer"][len(was["per_layer"]):]
    assert {m["name"] for m in added} == NEW_METRICS
    for m in added:
        assert m["workloads"] == [CELL] and m["layer"] == "mixed batch"
        assert m["moves"] == "place_due_p50_ms"
        spec = json.load(open(os.path.join(
            BENCH, "metrics", f"{m['name']}.json")))
        assert os.path.exists(os.path.join(
            BENCH, "readers", f"{spec['reader']}.py"))


def test_the_committed_files_state_the_deployment():
    bench, entry, config, traffic = committed()
    assert entry["reduced"] == config["reduced"] == [
        "instance_num_tail", "arrival_rate"]
    assert len(entry["source"]) <= 200 and entry["source"] == config["source"]
    for table in ("machine_meta", "container_meta", "batch_task",
                  "batch_instance"):
        assert table in entry["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(cell["why"]) <= 200
    assert str(traffic["arrivals"]["rate_evals_per_s"]) + " evals/s" \
        in cell["why"]

    classes = config["fleet"]["classes"]
    assert sum(c["count"] for c in classes) == 4034
    nodes = [c["node"] for c in classes]
    assert all(n == nodes[0] for n in nodes)       # one class of machine
    node = nodes[0]
    assert node["cpu"] - node["reserved"]["cpu"] == 9600
    assert "topology" not in json.dumps(classes)
    assert config["fleet"]["datacenter"] == "dc1"
    # standing work: the online containers, 17 or 18 a machine by rule,
    # of 400 and 800 cpu, no machine full on any axis
    containers = sum(c["count"] * sum(r["per_node"] for r in c["filler"])
                     for c in classes)
    assert containers == 71476
    assert {sum(r["per_node"] for r in c["filler"]) for c in classes} \
        == {17, 18}
    for c in classes:
        assert all(r["type"] == "service" for r in c["filler"])
        assert {v for r in c["filler"] for v in r["cpu"]} == {400, 800}
        for key, total in (("cpu", 9600), ("memory_mb", node["memory_mb"]
                                           - node["reserved"]["memory_mb"])):
            worst = sum(r["per_node"] * max(r[key]) for r in c["filler"])
            assert worst < 0.85 * total, key

    table = [("t1", 0.26, 1), ("t3", 0.18, 3), ("t12", 0.15, 12),
             ("t30", 0.12, 30), ("t60", 0.09, 60), ("t120", 0.06, 120),
             ("t250", 0.04, 250), ("t500", 0.03, 500), ("t1000", 0.02, 1000),
             ("t2000", 0.01, 2000), ("app-8", 0.04, 8)]
    jobs = config["jobs"]
    assert [(j["name"], j["share"], j["count"]) for j in jobs] == table
    assert abs(sum(j["share"] for j in jobs) - 1.0) < 1e-9
    assert abs(sum(j["share"] * j["count"] for j in jobs) - 84.12) < 1e-9
    assert abs(sum(j["share"] for j in jobs if j["count"] <= 3) - 0.44) < 1e-9
    for job in jobs[:-1]:
        assert (job["type"], job["distinct_hosts"], job["task"]["cpu"],
                job["task"]["mbits"], job["task"]["dynamic_ports"]) == (
            "batch", False, 100, 0, [])
        assert "gang" not in job
        # plan_mem: a few tenths of a percent of a machine
        assert 0.003 < job["task"]["memory_mb"] / node["memory_mb"] < 0.005
    app = jobs[-1]
    assert (app["type"], app["distinct_hosts"], app["task"]["cpu"]) == (
        "service", True, 400)
    for job in jobs:
        body = fleet.job_template(job)      # validates, or raises
        assert body["task_groups"][0]["count"] == job["count"]
        assert [c["rtarget"] for c in job["constraints"]] == ["linux"]
    assert config["checks"] == ["alibaba_colo"] and config["assumed"]
    assert any("94.5" in line for line in config["assumed"])
    assert config["architecture"] is None
    assert {"tasks", "app_du", "capacity"} <= set(config["guarantees"])
    assert config["server"] == json.load(open(os.path.join(
        BENCH, "configs", "pai-gpu-1800.json")))["server"]
    arrivals = traffic["arrivals"]
    assert arrivals["process"] == "bursts" and arrivals["burst_size"] == 8
    assert traffic["max_in_flight"] == 512
    # warm-up's rounds meet each of the eleven shapes at least twice
    assert sum(r // len(jobs) for r in traffic["warmup"]["rounds"]) >= 2


# ---------------------------------------------------------------------
# the window's demand and the capacity sum


def cores(job):
    return job["task"]["cpu"] * job["count"] / 100.0


def taken_by(config, traffic, seed, seconds=51.0):
    """Cores one run takes, drawn as the generator draws them: warm-up's
    rounds, its arrivals for as long as warm-up may last (`max_s`), the
    window's schedule of `seed`. Nothing departs. Every machine is of
    one class and a task instance is one core, so what fits is the sum
    (BestFit fills a machine to its last core before it opens the next;
    an `app-8` needs 8 machines with 4 free cores, and the machines not
    yet opened have 20 and more)."""
    gen = plugins.load("generators", "open")
    jobs = config["jobs"]
    rng = random.Random(seed)
    offsets = gen.window_schedule(traffic["arrivals"], seconds, rng)
    window = gen.window_shapes(len(offsets), jobs, rng)
    order = [i % len(jobs) for r in traffic["warmup"]["rounds"]
             for i in range(r)]
    share = [job["share"] for job in jobs]
    for t in gen.open_ended(traffic["arrivals"], rng):
        if t >= traffic["warmup"]["max_s"]:
            break
        order.append(rng.choices(range(len(jobs)), share)[0])
    return sum(cores(jobs[s]) for s in order), \
        sum(cores(jobs[s]) for s in window)


def test_the_windows_demand_is_the_same_on_every_seed():
    _bench, _entry, config, traffic = committed()
    gen = plugins.load("generators", "open")
    jobs = config["jobs"]
    demands = set()
    for seed in (1, 2**31 + 4500, 2**32 - 1):
        rng = random.Random(seed)
        offsets = gen.window_schedule(traffic["arrivals"], 51.0, rng)
        shapes = gen.window_shapes(len(offsets), jobs, rng)
        demands.add(tuple(shapes.count(i) for i in range(len(jobs))))
    (by_shape,) = demands
    assert dict(zip((j["name"] for j in jobs), by_shape)) \
        == CAPACITY["sums"]["window_evals_by_shape"]


def test_the_capacity_sum_holds_at_the_mean_and_on_200_seeds():
    _bench, _entry, config, traffic = committed()
    gen = plugins.load("generators", "open")
    jobs = config["jobs"]
    free = sum(
        c["count"] * (c["node"]["cpu"] - c["node"]["reserved"]["cpu"]
                      - sum(r["per_node"] * sum(r["cpu"]) / len(r["cpu"])
                            for r in c["filler"])) / 100.0
        for c in config["fleet"]["classes"])
    offsets = gen.window_schedule(traffic["arrivals"], 51.0,
                                  random.Random(1))
    shapes = gen.window_shapes(len(offsets), jobs, random.Random(1))
    rounds = [i % len(jobs) for r in traffic["warmup"]["rounds"]
              for i in range(r)]
    sums = {
        "cores_in_the_fleet": sum(
            c["count"] * (c["node"]["cpu"] - c["node"]["reserved"]["cpu"])
            for c in config["fleet"]["classes"]) // 100,
        "cores_free_at_load_at_the_mean": round(free, 1),
        "window_evals": len(offsets),
        "window_evals_by_shape": {
            job["name"]: shapes.count(i) for i, job in enumerate(jobs)},
        "window_allocations": sum(jobs[s]["count"] for s in shapes),
        "window_cores": sum(cores(jobs[s]) for s in shapes),
        "warmup_rounds_cores": sum(cores(jobs[s]) for s in rounds),
        "warmup_arrivals_cores_per_s": round(
            traffic["arrivals"]["rate_evals_per_s"]
            * sum(j["share"] * cores(j) for j in jobs), 4),
        "warmup_max_s": traffic["warmup"]["max_s"]}
    assert sums == CAPACITY["sums"]
    for number in CAPACITY["in_the_deployment_text"]:
        assert number in config["deployment"], number
    # at the mean, warm-up at its bound and the window leave a third of
    # the cores free at load
    mean = (sums["warmup_rounds_cores"] + sums["window_cores"]
            + sums["warmup_arrivals_cores_per_s"] * sums["warmup_max_s"])
    assert 1.0 - mean / free >= 1.0 / 3.0
    # on every one of 200 seeds the fleet holds the run; the worst
    # leaves the share the file states (a t2000 is 2,000 cores: the
    # draw of warm-up's arrivals moves the sum by thousands)
    worst = 1.0
    for seed in range(2**31 + 4500, 2**31 + 4700):
        before, window = taken_by(config, traffic, seed)
        assert window == sums["window_cores"]
        worst = min(worst, 1.0 - (before + window) / free)
    assert worst > 0.05
    assert round(worst, 3) == CAPACITY["worst_free_share_of_200_seeds"]
    # memory binds nowhere first: a machine's free cores, each taken by
    # an instance, ask for less memory than the fullest machine has left
    node = config["fleet"]["classes"][0]["node"]
    for c in config["fleet"]["classes"]:
        least_mem = (node["memory_mb"] - node["reserved"]["memory_mb"]
                     - sum(r["per_node"] * max(r["memory_mb"])
                           for r in c["filler"]))
        most_cores = (9600 - sum(r["per_node"] * min(r["cpu"])
                                 for r in c["filler"])) // 100
        assert most_cores * jobs[0]["task"]["memory_mb"] < least_mem


# ---------------------------------------------------------------------
# the check


def doctored():
    """A store of four machines of 8 cores, a standing container each,
    a task of three instances, an application of two containers on two
    machines and a task of one; what the check reads of it is all 0."""
    store = {
        "node_ids": [f"n{i}" for i in range(4)],
        "node_cap": np.asarray([[800, 1000, 0, 0]] * 4, np.float64),
        "node_reserved": np.zeros((4, 4)),
        "node_ready": np.ones(4, bool), "node_drain": np.zeros(4, bool),
        "job_ids": ["filler", "t3", "app", "t1"],
        "alloc_job": np.asarray([0] * 4 + [1] * 3 + [2] * 2 + [3]),
        "alloc_node": np.asarray([0, 1, 2, 3, 0, 0, 1, 2, 3, 1]),
        "alloc_usage": np.asarray(
            [[400, 100, 0, 0]] * 4 + [[100, 10, 0, 0]] * 3
            + [[200, 50, 0, 0]] * 2 + [[100, 10, 0, 0]], np.float64)}
    window = {"t3": {"count": 3, "distinct_hosts": False},
              "app": {"count": 2, "distinct_hosts": True},
              "t1": {"count": 1, "distinct_hosts": False}}
    return store, window


def test_the_check_on_a_doctored_store():
    check = plugins.load("checks", "alibaba_colo").check
    store, window = doctored()
    zero = dict.fromkeys(COUNTS, 0)
    assert check(store, window, {}) == zero
    # an instance dropped; a task that was never placed at all
    less = dict(store, **{k: np.delete(store[k], 4, axis=0)
                          for k in ("alloc_job", "alloc_node", "alloc_usage")})
    assert check(less, window, {}) == dict(zero, tasks_short_of_their_count=1)
    assert check(store, dict(window, t9={"count": 9, "distinct_hosts": False}),
                 {}) == dict(zero, tasks_short_of_their_count=1)
    # one instance too many
    assert check(store, dict(window, t3=dict(window["t3"], count=2)), {}) \
        == dict(zero, tasks_past_their_count=1)
    # the application's two containers on one machine
    nodes = store["alloc_node"].copy()
    nodes[8] = 2
    assert check(dict(store, alloc_node=nodes), window, {}) == dict(
        zero, app_containers_sharing_a_machine=1)
    # a machine over its cores, and one over its memory
    heavy = store["alloc_usage"].copy()
    heavy[9, 0] = 301
    assert check(dict(store, alloc_usage=heavy), window, {}) == dict(
        zero, machines_over_cpu_or_memory=1)
    heavy = store["alloc_usage"].copy()
    heavy[7, 1] = 901
    assert check(dict(store, alloc_usage=heavy), window, {}) == dict(
        zero, machines_over_cpu_or_memory=1)
    # the window's allocations on a node that is not ready, draining,
    # or unknown
    down = store["node_ready"].copy()
    down[0] = False
    assert check(dict(store, node_ready=down), window, {}) == dict(
        zero, window_allocs_on_unready_nodes=2)
    drain = store["node_drain"].copy()
    drain[3] = True
    assert check(dict(store, node_drain=drain), window, {}) == dict(
        zero, window_allocs_on_unready_nodes=1)
    nodes = store["alloc_node"].copy()
    nodes[9] = -1
    assert check(dict(store, alloc_node=nodes), window, {}) == dict(
        zero, window_allocs_on_unready_nodes=1)


# ---------------------------------------------------------------------
# the cell at rehearsal size


def rehearse(capsys, seed, trace=0, seconds=6):
    code = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace), "--rehearse"])
    assert code == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert all(line.startswith("REHEARSAL ") for line in lines)
    return (json.loads(lines[-1][len("REHEARSAL "):]),
            [line.split()[2].rstrip(":") for line in lines
             if line.endswith("FAIL")])


def test_stages_rehearsal_is_correct_and_reads_its_metrics(capsys):
    result, failed = rehearse(capsys, 2**31 + 4501, trace=1)
    assert result["correct"] is True and not failed, failed
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert {name.split(".", 1)[1] for name in compared
            if name.startswith("alibaba_colo.")} == COUNTS
    for name in COUNTS:
        assert compared[f"alibaba_colo.{name}"]["value"] == 0
    metrics = result["metrics"]
    assert NEW_METRICS | {"place_due_p95_ms", "base_delta_p50_ms",
                          "gc_passes_per_eval"} <= set(metrics)
    assert "mixed_batches_per_eval" not in metrics
    assert metrics["plain_handovers_per_eval"]["value"] > 0
    assert metrics["dispatches_per_batch"]["value"] > 1
    assert compared["device_requests_in_window"]["value"] > 0


def test_a_doctored_dump_is_not_correct(capsys, monkeypatch):
    """The check around a whole rehearsal: an instance of a task of the
    window dropped from the store's dump on its way to the judge (the
    run itself is sound)."""
    real = run.store_dump.dump_store

    def doctor(snapshot):
        store = real(snapshot)
        tasks = [j for j, job_id in enumerate(store["job_ids"])
                 if "-c0" in job_id and np.sum(store["alloc_job"] == j) > 1]
        drop = int(np.flatnonzero(store["alloc_job"] == tasks[0])[0])
        for key in ("alloc_ids", "alloc_eval", "alloc_group", "alloc_name"):
            del store[key][drop]
        for key in ("alloc_node", "alloc_job", "alloc_usage", "alloc_mbits",
                    "alloc_priority"):
            store[key] = np.delete(store[key], drop, axis=0)
        store["port_alloc"] = store["port_alloc"] - (store["port_alloc"] > drop)
        return store

    monkeypatch.setattr(run.store_dump, "dump_store", doctor)
    result, failed = rehearse(capsys, 2**31 + 4502)
    assert result["correct"] is False
    name = "alibaba_colo.tasks_short_of_their_count"
    assert name in failed and result["compared"][name]["value"] >= 1
