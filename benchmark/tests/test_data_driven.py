"""A later PR adds a configuration, a traffic mix and a per-layer metric
as new files and new entries of BENCHMARK.json, and edits no file that
is there. Shown on a temporary copy, in rehearsal."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import gang_racks_fixture

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def digests(top):
    out = {}
    for base, _dirs, files in os.walk(top):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_new_cell_is_files_and_entries_only(tmp_path):
    bench_dir = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "nomad_tpu"), tmp_path / "nomad_tpu")
    before = digests(bench_dir)

    # a third configuration: two classes of node shape, one with fillers
    config = json.load(open(bench_dir / "configs" / "northstar-10k.json"))
    small = json.loads(json.dumps(config["fleet"]["classes"][0]))
    small["count"] = 3000
    small["node"].update(cpu=2000, memory_mb=4096, node_class="linux-small")
    small["filler"] = {"per_node": 0}
    config["name"] = "edge-2class"
    config["source"] = "test fixture"
    config["fleet"]["classes"] = [config["fleet"]["classes"][0], small]
    config["job"]["count"] = 6
    json.dump(config, open(bench_dir / "configs" / "edge-2class.json", "w"))
    # a second traffic mix of the kind that exists
    json.dump({"kind": "closed", "clients": 8, "poll_wait_s": 5,
               "warmup": {"min_s": 3, "still_s": 2, "still_dispatches": 1,
                          "min_requests": 4, "max_s": 60},
               "drain_s": 30, "trace": {"start_s": 1, "seconds": 2},
               "rehearsal": {"clients": 6}},
              open(bench_dir / "traffic" / "trickle.json", "w"))
    # a per-layer metric over a span that exists: data alone
    json.dump({"name": "plan_evaluate_p50_ms", "unit": "ms",
               "better": "lower", "source": "program_span",
               "layer": "plan queue and applier",
               "moves": "placed_allocs_per_s", "reader": "span",
               "args": {"stage": "plan.evaluate", "q": 0.5}},
              open(bench_dir / "metrics" / "plan_evaluate_p50_ms.json", "w"))

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "edge-2class", "source": "test fixture",
        "file": "benchmark/configs/edge-2class.json", "reduced": [],
        "why": "fixture"})
    bench["workloads"].append({
        "name": "edge-2class.trickle", "config": "edge-2class",
        "traffic": "trickle", "chips": 1, "why": "fixture"})
    bench["per_layer"].append({
        "name": "plan_evaluate_p50_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "plan queue and applier",
        "moves": "placed_allocs_per_s",
        "workloads": ["edge-2class.trickle"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    proc = subprocess.run(
        [sys.executable, str(bench_dir / "run.py"), "--workload",
         "edge-2class.trickle", "--seed", "77", "--seconds", "6",
         "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1][len("REHEARSAL "):])
    assert result["correct"] is True, [l for l in lines if "FAIL" in l]
    assert result["metrics"]["plan_evaluate_p50_ms"]["value"] > 0
    assert "gen_late_p95_ms" in result["metrics"]   # metrics of every cell
    assert "place_tail_p95_ms" not in result["metrics"]
    fleet_line = next(l for l in lines if " fleet: " in l)
    assert "'nodes': 332" in fleet_line, fleet_line  # 256 + 76, both classes

    after = digests(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/edge-2class.json", "traffic/trickle.json",
        "metrics/plan_evaluate_p50_ms.json"}


CHECK_FILE = '''"""A deployment's own rule, as a file: nothing evicted sat at or above
the priority of the lowest job of the window (with nothing evicted, 0),
and every live allocation carries its job's priority."""

import numpy as np


def check(store, window_jobs, config):
    floor = min(spec["priority"] for spec in window_jobs.values())
    evicted = np.asarray([d == "evict" for d in store["gone_desired"]], bool)
    return {
        "victims_not_below_preemptor": int(np.sum(
            store["gone_priority"][evicted] >= floor)),
        "allocs_without_priority": int(np.sum(store["alloc_priority"] < 0)),
        "evals_without_job": sum(1 for j in store["eval_job"] if not j),
        "templates_unknown": len(
            {spec["template"] for spec in window_jobs.values()}
            - {job["name"] for job in config["jobs"]}),
    }
'''


def test_a_tiered_deployment_is_files_and_entries_only(tmp_path):
    """What `borg-12k.mixed` will need, at fixture size: two job shapes
    of different count and priority in one queue, fillers of two tiers,
    preemption switched on in `server`, a check of the deployment's own,
    and open arrivals: all of it new files and entries."""
    bench_dir = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "nomad_tpu"), tmp_path / "nomad_tpu")
    before = digests(bench_dir)

    config = json.load(open(bench_dir / "configs" / "northstar-10k.json"))
    config["name"] = "tiers-2shape"
    config["source"] = "test fixture"
    cls = config["fleet"]["classes"][0]
    cls["filler"] = [
        {"per_node": 3, "cpu": [50, 100], "memory_mb": [64, 128],
         "disk_mb": 150, "priority": 20, "type": "batch"},
        {"per_node": 2, "cpu": [100], "memory_mb": [128], "disk_mb": 150,
         "priority": 70}]
    job = config.pop("job")
    config["jobs"] = [
        dict(job, name="prod", share=0.25, priority=80, count=6),
        dict(job, name="batch", share=0.75, priority=30, count=3,
             type="batch", distinct_hosts=False)]
    config["server"]["preemption_enabled"] = True
    config["checks"] = ["tiers"]
    config["rehearsal"] = {"fleet_scale": 0.0256,
                           "job_count": {"prod": 4, "batch": 2}}
    json.dump(config, open(bench_dir / "configs" / "tiers-2shape.json", "w"))
    os.makedirs(bench_dir / "checks", exist_ok=True)
    open(bench_dir / "checks" / "tiers.py", "w").write(CHECK_FILE)
    json.dump({"kind": "open",
               "arrivals": {"process": "bursts", "rate_evals_per_s": 16,
                            "burst_size": 4},
               "max_in_flight": 32, "poll_wait_s": 5,
               "warmup": {"min_s": 3, "still_s": 2, "still_dispatches": 2,
                          "min_requests": 6, "max_s": 120, "rounds": [2]},
               "drain_s": 30, "trace": {"start_s": 1, "seconds": 2},
               "rehearsal": {}},
              open(bench_dir / "traffic" / "gusts.json", "w"))

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "tiers-2shape", "source": "test fixture",
        "file": "benchmark/configs/tiers-2shape.json", "reduced": [],
        "why": "fixture"})
    bench["workloads"].append({
        "name": "tiers-2shape.gusts", "config": "tiers-2shape",
        "traffic": "gusts", "chips": 1, "why": "fixture"})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    proc = subprocess.run(
        [sys.executable, str(bench_dir / "run.py"), "--workload",
         "tiers-2shape.gusts", "--seed", str(2**31 + 99), "--seconds", "6",
         "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1][len("REHEARSAL "):])
    assert result["correct"] is True, [l for l in lines if "FAIL" in l]
    assert result["attempted"] == 96 and result["failed"] == 0  # 16/s x 6 s
    # a quarter of the window is `prod` at 4 allocations, the rest `batch`
    # at 2: each evaluation was held to its own shape's count
    placed = result["metrics"]["placed_allocs_per_s"]["value"] * 6
    assert 0.8 * (24 * 4 + 72 * 2) <= placed <= 1.2 * (24 * 4 + 72 * 2)
    for name in ("victims_not_below_preemptor", "allocs_without_priority",
                 "evals_without_job", "templates_unknown"):
        assert result["compared"][f"tiers.{name}"] == {
            "value": 0, "limit": 0, "ok": True}
    fleet_line = next(l for l in lines if " fleet: " in l)
    assert "'filler_allocs': 1280" in fleet_line, fleet_line  # 256 x (3 + 2)
    assert list(result)[-1] == "compared"
    # the same numbers close standard error, each beside its limit
    assert "tiers.victims_not_below_preemptor 0 limit 0" in proc.stderr

    after = digests(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/tiers-2shape.json", "traffic/gusts.json", "checks/tiers.py"}


def test_filler_tiers_and_legacy_draws():
    """A list of filler rules gives each stated priority a job of its
    own; one rule without a priority loads exactly what it did: the job
    `filler` at 50, the same ids in the same order."""
    sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
    import random

    import fleet
    import store_dump
    from nomad_tpu.server import Server, ServerConfig

    node = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "northstar-10k.json")))["fleet"]
    node["classes"][0]["count"] = 4

    def load(filler, seed=11):
        node["classes"][0]["filler"] = filler
        server = Server(ServerConfig(num_schedulers=0))
        server.start()
        try:
            fleet.load_fleet(server, {"fleet": node}, seed)
            return store_dump.dump_store(server.fsm.state.snapshot()), \
                {a.id: (a.job_id, a.name, a.job.priority, a.job.type)
                 for a in server.fsm.state.allocs()}
        finally:
            server.shutdown()

    one = {"per_node": 2, "cpu": [50, 100], "memory_mb": [64, 128],
           "disk_mb": 150}
    store, allocs = load(one)
    # the draws of the accepted cells, replayed by hand
    rng = random.Random(11)
    want_nodes, want_allocs = [], []
    for _ in range(4):
        want_nodes.append(fleet.seeded_uuid(rng))
        fleet.seeded_uuid(rng)
        for _k in range(2):
            want_allocs.append(fleet.seeded_uuid(rng))
            rng.choice([50, 100]), rng.choice([64, 128])
    assert sorted(store["node_ids"]) == sorted(want_nodes)
    assert sorted(allocs) == sorted(want_allocs)
    assert set(allocs.values()) == {
        ("filler", "filler.web[0]", 50, "service"),
        ("filler", "filler.web[1]", 50, "service")}
    assert set(store["alloc_priority"]) == {50} and store["gone_ids"] == []

    store, allocs = load([dict(one, priority=20, type="batch"),
                          dict(one, per_node=1, priority=70)])
    assert sorted(store["alloc_priority"]) == [20] * 8 + [70] * 4
    assert {v[0] for v in allocs.values()} == {"filler-p20", "filler-p70"}
    assert {v[3] for v in allocs.values() if v[0] == "filler-p20"} == {"batch"}


def rehearse(tmp_path, cell, seed, trace, script="benchmark/run.py"):
    proc = subprocess.run(
        [sys.executable, script, "--workload", cell, "--seed", str(seed),
         "--seconds", "6", "--trace", str(trace), "--rehearse"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc, proc.stdout.strip().splitlines()


GANG_COUNTS = ("gangs_split_across_groups", "gangs_partial",
               "members_without_group", "no_gang_placed")


def test_a_deployment_of_racks_and_gangs_is_files_and_entries_only(tmp_path):
    """What the queue's gang deployment will need, at fixture size
    (`gang_racks_fixture.py`): two server shapes in racks stated by
    rule, fillers that leave room for a gang member in some racks only,
    a plain shape and two `slice: rack` gang shapes by share, a check
    that sees racks and task groups, a metric over `gang.select`: all of
    it new files and entries."""
    bench_dir = gang_racks_fixture.copy_benchmark(tmp_path)
    before = digests(bench_dir)
    gang_racks_fixture.install(tmp_path)

    _proc, lines = rehearse(tmp_path, gang_racks_fixture.CELL, 2**31 + 32, 1)
    result = json.loads(lines[-1][len("REHEARSAL "):])
    assert result["correct"] is True, [l for l in lines if "FAIL" in l]
    assert result["attempted"] == 48 and result["failed"] == 0  # 8/s x 6 s
    for name in GANG_COUNTS:
        assert result["compared"][f"gang_slices.{name}"] == {
            "value": 0, "limit": 0, "ok": True}
    # the gang pass ran on the device path and its span has samples
    assert result["metrics"]["gang_select_p50_ms"]["value"] > 0
    assert result["metrics"]["gang_select_p95_ms"]["value"] > 0
    assert "place_due_p95_ms" in result["metrics"]
    assert "preempt_select_p50_ms" not in result["metrics"]
    fleet_line = next(l for l in lines if " fleet: " in l)
    # 36 + 12 + 15 + 8 servers; fillers on the busy ones: 36 x 3 + 15 x 5
    assert "'nodes': 71, 'filler_allocs': 183" in fleet_line, fleet_line

    after = digests(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == gang_racks_fixture.ADDED


ROTATED = '''"""The control of the fixture's check: the run as it is, the check handed
a `node_meta` whose groups are rotated by one node."""
import os
import sys

sys.path[:0] = [os.path.join(os.getcwd(), "benchmark"), os.getcwd()]
import run
import store_dump

dump = store_dump.dump_store


def rotated(state):
    store = dump(state)
    store["node_meta"] = {level: names[1:] + names[:1]
                          for level, names in store["node_meta"].items()}
    return store


store_dump.dump_store = rotated
sys.exit(run.main(mark="CONTROL rotated "))
'''


def test_racks_rotated_by_one_node_are_not_correct(tmp_path):
    gang_racks_fixture.copy_benchmark(tmp_path)
    gang_racks_fixture.install(tmp_path)
    open(tmp_path / "rotated.py", "w").write(ROTATED)
    _proc, lines = rehearse(tmp_path, gang_racks_fixture.CELL, 2**31 + 33, 0,
                            script="rotated.py")
    prefix = "REHEARSAL CONTROL rotated "
    assert all(line.startswith(prefix) for line in lines)
    result = json.loads(lines[-1][len(prefix):])
    assert result["correct"] is False
    split = result["compared"]["gang_slices.gangs_split_across_groups"]
    assert split["value"] > 0 and split["ok"] is False
    assert list(result["compared"])[0] == "gang_slices.gangs_split_across_groups"
    # the placement itself was sound: nothing else failed
    assert [name for name, row in result["compared"].items()
            if not row["ok"]] == ["gang_slices.gangs_split_across_groups"]


class _Log:
    """Stands where `server.log` does: `load_fleet` applies entries and
    reads nothing."""

    def __init__(self):
        self.entries = []
        self.log = self

    def apply(self, kind, payload):
        self.entries.append((kind, payload))


def fleet_golden():
    """For each committed configuration: the job dictionaries of its
    shapes, and the nodes and fillers of a 50-node slice of its fleet in
    load order, each as a digest."""
    import fleet

    def sha(obj):
        return hashlib.sha256(json.dumps(obj).encode()).hexdigest()

    out = {}
    for name in ("northstar-10k", "c1m-5k", "borg-12k"):
        config = json.load(open(os.path.join(
            ROOT, "benchmark", "configs", f"{name}.json")))
        jobs = [fleet.job_template(spec) for spec in fleet.job_specs(config)]
        classes = config["fleet"]["classes"]
        per = -(-50 // len(classes))
        config["fleet"]["classes"] = [dict(c, count=per) for c in classes]
        server = _Log()
        loaded = fleet.load_fleet(server, config, 2**31 + 7)
        nodes, fillers = [], []
        for kind, payload in server.entries:
            if kind == "node_register":
                n = payload["node"]
                nodes.append([n.id, n.secret_id, n.node_class,
                              n.computed_class, sorted(n.meta.items())])
            else:
                for a in payload["allocs"]:
                    r = a.task_resources["web"]
                    fillers.append([a.id, a.node_id, a.name, a.job_id,
                                    a.job.priority, r.cpu, r.memory_mb])
        out[name] = {"jobs": sha(jobs), "nodes": sha(nodes),
                     "fillers": sha(fillers), "loaded": loaded}
    return out


def test_committed_configurations_load_as_the_parent_loaded_them():
    """`data/fleet_golden.json` was written by PR 32 from the parent's
    `fleet.py` (PR 31's commit), before `job_template` took a `gang` and
    `load_fleet` a `topology`: a configuration with neither gives the same
    job dictionaries, key for key, and the same fleet, id for id."""
    golden = json.load(open(os.path.join(
        ROOT, "benchmark", "tests", "data", "fleet_golden.json")))
    assert fleet_golden() == golden


def test_topology_rule_names_racks_and_nested_ici_groups():
    import fleet

    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "northstar-10k.json")))
    cls = config["fleet"]["classes"][0]
    cls["count"], cls["filler"] = 7, {"per_node": 0}

    def metas(topology):
        cls["topology"] = topology
        server = _Log()
        fleet.load_fleet(server, config, 5)
        nodes = [payload["node"] for _kind, payload in server.entries]
        # the class's own meta stays; the rack is part of the class
        assert all(n.meta["pci-dss"] == "true" for n in nodes)
        assert len({n.computed_class for n in nodes}) == len(
            {tuple(sorted(n.meta.items())) for n in nodes})
        return [(n.meta.get("rack"), n.meta.get("ici")) for n in nodes]

    assert metas({"rack": {"nodes_per_group": 4, "prefix": "a"}}) == [
        ("a0", None)] * 4 + [("a1", None)] * 3
    assert metas({"ici": {"nodes_per_group": 3}}) == [
        (None, "ici0")] * 3 + [(None, "ici1")] * 3 + [(None, "ici2")]
    assert metas({"rack": {"nodes_per_group": 4, "prefix": "a"},
                  "ici": {"nodes_per_group": 2, "prefix": "i"}}) == [
        ("a0", "a0-i0"), ("a0", "a0-i0"), ("a0", "a0-i1"), ("a0", "a0-i1"),
        ("a1", "a1-i0"), ("a1", "a1-i0"), ("a1", "a1-i1")]
    with pytest.raises(ValueError):
        metas({"rack": {"nodes_per_group": 4}, "ici": {"nodes_per_group": 3}})
    with pytest.raises(ValueError):
        metas({"row": {"nodes_per_group": 4}})
