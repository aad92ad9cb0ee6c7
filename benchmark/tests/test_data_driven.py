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
