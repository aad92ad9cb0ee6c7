"""The cell PR 42 added, `pai-gpu-1800.shared`: the committed files load
and state what the deployment is; the capacity sum of the file's
`deployment` text holds on 200 seeds of the schedule; the check's
controls (one member of a gang dropped from the store: `partial_gangs`;
the machines' capacity rows rotated: `members_on_a_wrong_gpu_type`),
on a doctored store and around whole rehearsals; the cell is correct at
rehearsal size and reads its metrics. No test here pins the set of files
under `benchmark/` (`ROADMAP.md` M1 (k))."""

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
DATA = os.path.join(HERE, "data", "pai-gpu-1800")
CELL = "pai-gpu-1800.shared"
MIXED = {"gang_rejects_per_eval", "claims_carry_p50_ms",
         "mixed_batches_per_eval"}
LISTED = {"place_due_p50_ms", "place_due_p95_ms", "place_due_p99_ms",
          "gang_select_p50_ms", "gang_select_p95_ms", "gang_build_p50_ms",
          "gang_solve_p50_ms", "gang_share_of_eval", "gang_passes_per_eval",
          "feas_share_of_eval", "feas_builds_per_eval",
          "compact_dispatch_share"} | MIXED
COUNTS = {"partial_gangs", "members_on_a_wrong_gpu_type",
          "machines_over_their_gpus", "no_gang_of_the_window_is_whole",
          "no_constrained_job_placed"}
with open(os.path.join(DATA, "capacity.json")) as _f:
    CAPACITY = json.load(_f)


def committed():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "pai-gpu-1800")
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "shared.json")))
    return bench, entry, config, traffic


def test_the_committed_files_state_the_deployment():
    bench, entry, config, traffic = committed()
    assert entry["reduced"] == [] and config["reduced"] == []
    assert len(entry["source"]) <= 200 and "Table 1" in entry["source"]
    assert entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="pai-gpu-1800", traffic="shared",
                        chips=1) and len(cell["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if metric["name"] in LISTED:
                assert CELL in metric["workloads"], metric["name"]
    for name in MIXED:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        assert metric["layer"] == "mixed batch"
        assert metric["moves"] == "place_due_p50_ms"
        spec = json.load(open(os.path.join(BENCH, "metrics", f"{name}.json")))
        assert os.path.exists(os.path.join(
            BENCH, "readers", f"{spec['reader']}.py"))

    classes = config["fleet"]["classes"]
    assert [(c["node"]["node_class"], c["count"],
             int(c["node"]["meta"]["gpus"])) for c in classes] == [
        ("t4", 497, 2), ("p100", 798, 2), ("misc", 280, 8),
        ("v100m32", 135, 8), ("v100", 104, 8)]
    assert sum(c["count"] for c in classes) == 1814
    assert sum(c["count"] * int(c["node"]["meta"]["gpus"])
               for c in classes) == 6742
    axis = config["gpu_axis"]
    units = axis["units_per_gpu"]
    assert axis["axis"] == "disk_mb" and "topology" not in json.dumps(classes)
    check = plugins.load("checks", "pai_shared")
    assert len(check.machines_by_signature(config)) == len(classes)
    for c in classes:
        node, rule = c["node"], c["filler"]
        gpus = int(node["meta"]["gpus"])
        assert node["disk_mb"] - node["reserved"]["disk_mb"] == units * gpus
        assert node["meta"]["gpu_type"] == node["node_class"].upper()
        # standing work: one even rule, about half of every axis
        assert rule["per_node"] * rule["disk_mb"] == units * gpus // 2
        for key, total in (("cpu", node["cpu"] - node["reserved"]["cpu"]),
                           ("memory_mb", node["memory_mb"]
                            - node["reserved"]["memory_mb"])):
            low = rule["per_node"] * min(rule[key]) / total
            high = rule["per_node"] * max(rule[key]) / total
            assert 0.4 < low <= 0.5 <= high < 0.6, (node["node_class"], key)

    table = [  # name, share, count, gang, GPU share, cores, memory GiB
        ("infer-t4", 0.25, 1, False, 0.25, 4, 8),
        ("frac", 0.30, 1, False, 0.5, 6, 16),
        ("one", 0.18, 1, False, 1, 6, 29),
        ("ps-8", 0.12, 8, True, 0.5, 6, 16),
        ("v100-8", 0.07, 8, True, 1, 6, 29),
        ("train-32", 0.05, 32, True, 1, 6, 29),
        ("wide-128", 0.03, 128, True, 0.5, 6, 16)]
    jobs = config["jobs"]
    assert [(j["name"], j["share"], j["count"], j.get("gang") == {},
             (j["ephemeral_disk_mb"] + axis["task_default_disk_mb"]) / units,
             j["task"]["cpu"] // 1000, j["task"]["memory_mb"] // 1024)
            for j in jobs] == table
    assert all("gang" in j or j["count"] == 1 for j in jobs)
    assert all(j["type"] == "batch" and not j["distinct_hosts"]
               and j["task"]["mbits"] == 0 and not j["task"]["dynamic_ports"]
               for j in jobs)
    assert abs(sum(j["share"] for j in jobs) - 1.0) < 1e-9
    assert abs(sum(j["share"] for j in jobs if "gang" in j) - 0.27) < 1e-9
    assert abs(sum(j["share"] * j["count"] for j in jobs) - 7.69) < 1e-9
    dc = config["fleet"]["datacenter"]
    for job in jobs:
        # the program takes the shape as the generator will send it, and
        # asks for the share on the GPU axis that the table states
        body = fleet.job_template(job)
        group = body["task_groups"][0]
        on_axis = (group["ephemeral_disk"]["size_mb"]
                   + group["tasks"][0]["resources"]["disk_mb"])
        assert on_axis == round(units * dict(
            (row[0], row[4]) for row in table)[job["name"]])
        n = sum(c["count"] for c in classes
                if check.meets(job["constraints"], dict(c["node"],
                                                        datacenter=dc)))
        assert config["feasible_machines"][job["name"]].startswith(
            f"{n} of 1814")
    assert [config["feasible_machines"][n].split()[0]
            for n in ("infer-t4", "v100-8", "train-32")] == [
                "497", "239", "1317"]
    assert config["checks"] == ["pai_shared"] and config["assumed"]
    assert config["architecture"] is None
    assert {"gangs", "gpu_type", "capacity"} <= set(config["guarantees"])
    assert config["server"] == json.load(open(os.path.join(
        BENCH, "configs", "philly-552.json")))["server"]
    arrivals = traffic["arrivals"]
    assert arrivals["process"] == "bursts" and arrivals["burst_size"] == 8
    assert traffic["max_in_flight"] == 512
    # a round meets every shape: both queues, member steps 8, 32, 128
    assert all(r >= len(jobs) for r in traffic["warmup"]["rounds"])


# ---------------------------------------------------------------------
# the capacity sum


def load_state(config, rng):
    """(free [n, 3], capacity [n, 3], class [n]) at load, in cores MHz,
    memory MB and units of the GPU axis, every standing job drawn from
    its class's rule."""
    free, cap, cls = [], [], []
    for ci, c in enumerate(config["fleet"]["classes"]):
        node, rule = c["node"], c["filler"]
        full = [node[k] - node["reserved"][k]
                for k in ("cpu", "memory_mb", "disk_mb")]
        for _ in range(c["count"]):
            row = list(full)
            for _k in range(rule["per_node"]):
                row[0] -= rng.choice(rule["cpu"])
                row[1] -= rng.choice(rule["memory_mb"])
                row[2] -= rule["disk_mb"]
            free.append(row)
            cap.append(full)
            cls.append(ci)
    return (np.asarray(free, np.float64), np.asarray(cap, np.float64),
            np.asarray(cls))


def simulate(config, traffic, seed, seconds=51.0):
    """One run's demand placed on the fleet in the order the generator
    sends it: warm-up's rounds, its arrivals for as long as warm-up may
    last (`max_s`), the window's schedule of `seed`. Placement is the
    program's rule in plain NumPy: among the machines a shape's
    constraints allow and its ask fits (cores, memory, GPUs), the best
    BestFit score on cores and memory less 5 a member of the same job
    already there, plus noise of 2; a gang is all or none. Returns
    (shapes that did not fit, free GPUs by class at the end,
    allocations placed)."""
    gen = plugins.load("generators", "open")
    check = plugins.load("checks", "pai_shared")
    rng = random.Random(seed)
    nprng = np.random.default_rng(seed)
    jobs = config["jobs"]
    offsets = gen.window_schedule(traffic["arrivals"], seconds, rng)
    window = gen.window_shapes(len(offsets), jobs, rng)
    order = [i % len(jobs) for r in traffic["warmup"]["rounds"]
             for i in range(r)]
    share = [job["share"] for job in jobs]
    for t in gen.open_ended(traffic["arrivals"], rng):
        if t >= traffic["warmup"]["max_s"]:
            break
        order.append(rng.choices(range(len(jobs)), share)[0])
    order += window

    free, cap, cls = load_state(config, rng)
    dc = config["fleet"]["datacenter"]
    allowed = [np.asarray([check.meets(job["constraints"],
                                       dict(c["node"], datacenter=dc))
                           for c in config["fleet"]["classes"]])[cls]
               for job in jobs]
    axis = config["gpu_axis"]
    asks = [np.asarray([job["task"]["cpu"], job["task"]["memory_mb"],
                        job["ephemeral_disk_mb"]
                        + axis["task_default_disk_mb"]], np.float64)
            for job in jobs]
    unfit, placed = [], 0
    for shape in order:
        ask, count = asks[shape], jobs[shape]["count"]
        mine = np.zeros(len(free))
        chosen = []
        for _member in range(count):
            fits = allowed[shape] & (free >= ask).all(axis=1)
            if not fits.any():
                break
            frac = (free[:, :2] - ask[:2]) / cap[:, :2]
            score = np.clip(20.0 - (10.0 ** frac).sum(axis=1), 0.0, 18.0)
            score = score - 5.0 * mine + nprng.uniform(0, 2, len(free))
            node = int(np.argmax(np.where(fits, score, -np.inf)))
            free[node] -= ask
            mine[node] += 1
            chosen.append(node)
        if len(chosen) < count:
            for node in chosen:     # all or none
                free[node] += ask
            unfit.append(jobs[shape]["name"])
        else:
            placed += count
    units = config["gpu_axis"]["units_per_gpu"]
    free_gpus = [float(free[cls == ci, 2].sum()) / units
                 for ci in range(len(config["fleet"]["classes"]))]
    return unfit, free_gpus, placed


def test_the_capacity_sum_holds_on_200_seeds_of_the_schedule():
    _bench, _entry, config, traffic = committed()
    units = config["gpu_axis"]["units_per_gpu"]
    gen = plugins.load("generators", "open")
    jobs = config["jobs"]

    def gpus(shape):
        return (jobs[shape]["ephemeral_disk_mb"]
                + config["gpu_axis"]["task_default_disk_mb"]) \
            * jobs[shape]["count"] / units

    # the sums the file's `deployment` text states
    classes = config["fleet"]["classes"]
    free_at_load = {c["node"]["node_class"]: c["count"] * (
        int(c["node"]["meta"]["gpus"]) - c["filler"]["per_node"]
        * c["filler"]["disk_mb"] // units) for c in classes}
    offsets = gen.window_schedule(traffic["arrivals"], 51.0,
                                  random.Random(1))
    shapes = gen.window_shapes(len(offsets), jobs, random.Random(1))
    by_shape = {job["name"]: shapes.count(i) for i, job in enumerate(jobs)}
    rounds = [i % len(jobs) for r in traffic["warmup"]["rounds"]
              for i in range(r)]
    sums = {
        "free_gpus_at_load": free_at_load,
        "window_evals": len(offsets), "window_evals_by_shape": by_shape,
        "window_allocations": sum(jobs[s]["count"] for s in shapes),
        "window_gpus": sum(gpus(s) for s in shapes),
        "warmup_rounds_gpus": sum(gpus(s) for s in rounds),
        "warmup_arrivals_gpus_per_s": round(
            traffic["arrivals"]["rate_evals_per_s"]
            * sum(j["share"] * gpus(i) for i, j in enumerate(jobs)), 4),
        "warmup_max_s": traffic["warmup"]["max_s"]}
    assert sums == CAPACITY["sums"]
    for number in CAPACITY["in_the_deployment_text"]:
        assert number in config["deployment"], number

    have = {c["node"]["node_class"]: c["count"] * int(c["node"]["meta"]["gpus"])
            for c in classes}
    groups = {"fleet": list(have), "t4": ["t4"], "misc": ["misc"],
              "v100": ["v100"], "v100 classes": ["v100m32", "v100"],
              "p100": ["p100"], "v100m32": ["v100m32"]}
    worst = dict.fromkeys(groups, 1.0)
    for seed in range(2**31 + 4200, 2**31 + 4400):
        unfit, free_gpus, placed = simulate(config, traffic, seed)
        # every shape fits to the window's end, v100-8 on its 239
        # machines and infer-t4 on its 497 included, even where warm-up
        # ran to its bound
        assert unfit == [], (seed, unfit)
        assert placed >= sums["window_allocations"]
        free = dict(zip(have, free_gpus))
        for name, members in groups.items():
            worst[name] = min(worst[name], sum(free[m] for m in members)
                              / sum(have[m] for m in members))
    # a tenth of the fleet's GPUs is free at the window's end, and of
    # the classes a constraint names; BestFit drains the P100 and
    # V100M32 machines first (an ask is a larger share of 64 cores, of
    # 384 GiB), and those two classes end near empty
    for name in ("fleet", "t4", "misc", "v100", "v100 classes"):
        assert worst[name] >= 0.10, worst
    assert worst["p100"] < 0.10 and worst["v100m32"] < 0.10, worst
    assert {k: round(v, 3) for k, v in worst.items()} \
        == CAPACITY["worst_free_share_of_200_seeds"]


# ---------------------------------------------------------------------
# the check


def doctored():
    """A store of six machines of three classes (two T4, two P100, two
    V100), one whole gang of four on the V100 machines, one T4 job and a
    standing job a machine; what the check reads of it is all 0."""
    def node(name, gpu_type, gpus, cores, mbits):
        return {"node_class": name, "attributes": {"kernel.name": "linux"},
                "meta": {"gpu_type": gpu_type, "gpus": str(gpus)},
                "cpu": cores * 1000, "memory_mb": 1000, "disk_mb": gpus * 100,
                "mbits": mbits,
                "reserved": {"cpu": 0, "memory_mb": 0, "disk_mb": 0}}

    linux = {"ltarget": "${attr.kernel.name}", "operand": "=",
             "rtarget": "linux"}

    def pinned(op, value):
        return [linux, {"ltarget": "${meta.gpu_type}", "operand": op,
                        "rtarget": value}]

    config = {"fleet": {"datacenter": "dc1", "classes": [
        {"node": node("t4", "T4", 2, 96, 10)},
        {"node": node("p100", "P100", 2, 64, 10)},
        {"node": node("v100", "V100", 8, 96, 25)}]},
        "jobs": [{"name": "infer", "constraints": pinned("=", "T4")},
                 {"name": "gang", "constraints": pinned("regexp", "^V100")},
                 {"name": "free", "constraints": [linux]}]}
    cap = np.asarray([[96000, 1000, 200, 0]] * 2 + [[64000, 1000, 200, 0]] * 2
                     + [[96000, 1000, 800, 0]] * 2, np.float64)
    store = {
        "node_ids": [f"n{i}" for i in range(6)], "node_cap": cap,
        "node_reserved": np.zeros((6, 4)),
        "node_mbits": np.asarray([10, 10, 10, 10, 25, 25], np.float64),
        "job_ids": ["filler", "g1", "i1", "f1"],
        #            six standing, the gang's four, the T4 job, a free one
        "alloc_job": np.asarray([0] * 6 + [1] * 4 + [2, 3]),
        "alloc_node": np.asarray([0, 1, 2, 3, 4, 5, 4, 4, 5, 5, 0, 3]),
        "alloc_usage": np.asarray(
            [[0, 0, 100, 0]] * 6 + [[0, 0, 100, 0]] * 4
            + [[0, 0, 25, 0], [0, 0, 100, 0]], np.float64)}
    window = {"g1": {"count": 4, "template": "gang", "gang": {}},
              "i1": {"count": 1, "template": "infer", "gang": None},
              "f1": {"count": 1, "template": "free", "gang": None}}
    return store, window, config


def test_the_check_on_a_doctored_store():
    check = plugins.load("checks", "pai_shared").check
    store, window, config = doctored()
    zero = dict.fromkeys(COUNTS, 0)
    assert check(store, window, config) == zero
    # one member of the gang dropped from the store
    less = dict(store, **{k: np.delete(store[k], 6, axis=0)
                          for k in ("alloc_job", "alloc_node", "alloc_usage")})
    assert check(less, window, config) == dict(
        zero, partial_gangs=1, no_gang_of_the_window_is_whole=1)
    # the machines' capacity rows rotated by two: every class reads as
    # another, the gang lies on what reads as T4 and the T4 job on V100
    turned = dict(store, **{k: np.roll(store[k], 2, axis=0)
                            for k in ("node_cap", "node_mbits")})
    counts = check(turned, window, config)
    assert counts["members_on_a_wrong_gpu_type"] == 5
    assert counts["no_constrained_job_placed"] == 0
    # a member on a machine of no class; a machine over its GPUs
    odd = dict(store, node_mbits=np.asarray([10, 10, 10, 10, 25, 40.0]))
    assert check(odd, window, config)["members_on_a_wrong_gpu_type"] == 2
    heavy = store["alloc_usage"].copy()
    heavy[11, 2] = 150
    assert check(dict(store, alloc_usage=heavy), window, config) == dict(
        zero, machines_over_their_gpus=1)
    # no constrained job whole
    gone = dict(window, g1=dict(window["g1"], count=5),
                i1=dict(window["i1"], count=2))
    assert check(store, gone, config) == dict(
        zero, partial_gangs=1, no_gang_of_the_window_is_whole=1,
        no_constrained_job_placed=1)
    # two classes one signature: the check says so and judges nothing
    config["fleet"]["classes"][1]["node"] = dict(
        config["fleet"]["classes"][0]["node"], node_class="twin")
    with pytest.raises(ValueError):
        check(store, window, config)


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


def test_shared_rehearsal_is_correct_and_reads_its_metrics(capsys):
    result, failed = rehearse(capsys, 2**31 + 4201, trace=1)
    assert result["correct"] is True and not failed, failed
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert {name.split(".", 1)[1] for name in compared
            if name.startswith("pai_shared.")} == COUNTS
    for name in COUNTS:
        assert compared[f"pai_shared.{name}"]["value"] == 0
    metrics = result["metrics"]
    assert LISTED - {"place_due_p50_ms"} <= set(metrics)
    assert metrics["gang_rejects_per_eval"]["value"] < 0.05
    assert metrics["mixed_batches_per_eval"]["value"] > 0
    assert metrics["gang_passes_per_eval"]["value"] >= 0.2
    assert metrics["small_route_host_evals_per_eval"]["value"] < 0.2
    assert compared["device_requests_in_window"]["value"] > 0


@pytest.mark.parametrize("control", ["member_dropped", "machines_rotated"])
def test_a_doctored_dump_is_not_correct(capsys, monkeypatch, control):
    """The check's two controls around a whole rehearsal: the store's
    dump doctored on its way to the judge (the run itself is sound)."""
    real = run.store_dump.dump_store

    def doctor(snapshot):
        store = real(snapshot)
        if control == "machines_rotated":
            # by the smallest class of the rehearsal's fleet
            for key in ("node_cap", "node_reserved", "node_mbits"):
                store[key] = np.roll(store[key], 8, axis=0)
            return store
        gangs = [j for j, job_id in enumerate(store["job_ids"])
                 if "-c0" in job_id and np.sum(store["alloc_job"] == j) > 1]
        drop = int(np.flatnonzero(store["alloc_job"] == gangs[0])[0])
        for key in ("alloc_ids", "alloc_eval", "alloc_group", "alloc_name"):
            del store[key][drop]
        for key in ("alloc_node", "alloc_job", "alloc_usage", "alloc_mbits",
                    "alloc_priority"):
            store[key] = np.delete(store[key], drop, axis=0)
        store["port_alloc"] = store["port_alloc"] - (store["port_alloc"] > drop)
        return store

    monkeypatch.setattr(run.store_dump, "dump_store", doctor)
    result, failed = rehearse(capsys, 2**31 + 4202)
    assert result["correct"] is False
    name = ("pai_shared.partial_gangs" if control == "member_dropped"
            else "pai_shared.members_on_a_wrong_gpu_type")
    assert name in failed and result["compared"][name]["value"] >= 1
