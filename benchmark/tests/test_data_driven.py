"""A later PR adds a configuration, a traffic mix and a per-layer metric
as new files and new entries of BENCHMARK.json, and edits no file that
is there. Shown on a temporary copy, in rehearsal."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

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
    os.mkdir(bench_dir / "checks")
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
