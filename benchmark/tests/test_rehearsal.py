"""Each cell of BENCHMARK.json end to end at rehearsal size, as the
driver would start it (a process of its own), both with and without the
trace; and the three ways a run has to end with no result."""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}

# What the two cells accepted with PR 24 printed before the harness took
# an open loop, several job shapes and a deployment's own checks: the
# names of the numbers compared, in order, and of the metrics. A
# generalisation of the harness may not move them; a metric a later PR
# adds for every cell is named in ADDED_SINCE.
PINNED_CHECKS = [
    "evals_not_complete_with_all_allocs", "evals_registered_in_window",
    "reference.allocs_on_unknown_node", "reference.distinct_hosts_shared",
    "reference.nodes_not_ready_or_draining", "reference.nodes_over_bandwidth",
    "reference.nodes_over_cpu", "reference.nodes_over_disk",
    "reference.nodes_over_iops", "reference.nodes_over_memory",
    "reference.ports_held_twice", "reference.ports_out_of_range",
    "device_requests_in_window", "scheduler.host_fallback",
    "scheduler.gang_host_fallback", "scheduler.breaker_rejected",
    "scheduler.gang_breaker_rejected", "executive.host_fallbacks",
    "pipeline.breaker_routed", "pipeline.prefetch_failures",
    "batcher.unsharded_fallbacks", "broker.dead_lettered",
    "broker.nack_timeouts", "breaker.trips", "breaker.failures",
    "breaker.rejected", "breaker.state", "fit_score_placed_mean_vs_uniform",
    "resident_rows_differing", "resident_base_platform"]
PINNED_PER_LAYER = {
    "alloc_upsert_p50_ms", "api_register_p50_ms", "batch_wait_p50_ms",
    "batch_wait_p95_ms", "broker_wait_p50_ms", "device_busy_ms_per_eval",
    "device_idle_batch_wait_share", "device_idle_no_work_share",
    "device_idle_stack_share", "device_requests_per_eval",
    "dispatch_accumulate_p50_ms", "dispatch_launch_p50_ms",
    "dispatch_wait_p50_ms", "eval_e2e_p50_ms", "eval_e2e_p95_ms",
    "eval_uncovered_share", "eval_update_p50_ms", "gen_late_p95_ms",
    "http_register_p50_ms", "lanes_per_dispatch", "matrix_build_p50_ms",
    "plan_commit_p50_ms", "plan_conflicts_per_eval", "plan_queue_wait_p50_ms",
    "plan_queue_wait_p95_ms", "plan_submit_p50_ms", "plan_verify_p50_ms",
    "pool_wait_p50_ms", "routed_host_per_eval", "sched_self_p50_ms",
    "solve_p50_ms", "trivial_rtt_us", "window_compiles"}
PINNED_METRICS = {
    ("northstar-10k.storm", 0): {"placed_allocs_per_s", "place_p50_ms",
                                 "setup_s"},
    ("northstar-10k.storm", 1): PINNED_PER_LAYER | {"place_tail_p95_ms"},
    ("c1m-5k.ramp", 0): {"placed_allocs_per_s", "setup_s"},
    ("c1m-5k.ramp", 1): PINNED_PER_LAYER,
}
ADDED_SINCE = {"plans_per_commit", "small_route_host_evals_per_eval",
               "device_transfer_p50_ms"}


def start(args, cwd=ROOT, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = script or os.path.join(ROOT, "benchmark", "run.py")
    return subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def metrics_for(cell, kind):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(cell, trace):
    proc = start(["--workload", cell, "--seed", str(2**31 + 5 + trace),
                  "--seconds", "5", "--trace", str(trace), "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert all(line.startswith("REHEARSAL ") for line in lines)
    result = json.loads(lines[-1][len("REHEARSAL "):])
    want = RESULT_KEYS | ({"breakdown"} if trace else set())
    assert set(result) == want
    assert result["correct"] is True, [l for l in lines if "FAIL" in l]
    assert result["attempted"] > 0 and result["failed"] == 0
    device_keys = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        device_keys |= {"busy_s", "window_s"}
        assert result["device"]["busy_s"] > 0
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        reported = set(result["metrics"])
        # peak_hbm_mb has nothing to read on the CPU backend
        assert reported | {"peak_hbm_mb"} == metrics_for(cell, "per_layer")
    else:
        assert set(result["metrics"]) == metrics_for(cell, "end_to_end")
    assert set(result["device"]) == device_keys
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"}
    checks = [line.split()[2].rstrip(":") for line in lines
              if line.startswith("REHEARSAL check ")]
    assert checks == list(result["compared"]) and checks
    assert list(result)[-1] == "compared"
    for name, row in result["compared"].items():
        assert f"REHEARSAL {name} {row['value']} limit {row['limit']}" \
            in proc.stderr
    if (cell, trace) in PINNED_METRICS:
        assert checks == PINNED_CHECKS
        assert set(result["metrics"]) - (ADDED_SINCE if trace else set()) \
            == PINNED_METRICS[cell, trace]


def test_no_accelerator_no_result():
    proc = start(["--workload", CELLS[0], "--seed", "1", "--seconds", "3",
                  "--trace", "0"])
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_no_program_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    own files: non-zero, and no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = start(["--workload", CELLS[0], "--seed", "1", "--seconds", "3",
                  "--trace", "0", "--rehearse"], cwd=tmp_path,
                 script=str(tmp_path / "benchmark" / "run.py"))
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "not beside the benchmark" in proc.stderr
    assert not any("correct" in line for line in proc.stdout.splitlines())


def test_a_hopeless_run_ends_early_and_non_zero(tmp_path):
    """Jobs that never reach the device: two allocations an eval, which
    the dense scheduler hands to the host iterators, each larger than any
    node. Warm-up reaches its bound with nothing served by the device;
    the run says so in its last line and leaves with a code of its own,
    well inside the bound plus 30 s: no window, no drain, no result."""
    import gang_racks_fixture
    import run

    max_s = 8
    gang_racks_fixture.copy_benchmark(tmp_path)
    config = json.load(open(tmp_path / "benchmark" / "configs"
                            / "northstar-10k.json"))
    config["name"] = "too-big"
    config["job"].update(count=2)
    config["job"]["task"]["cpu"] = 100000
    config["rehearsal"]["job_count"] = 2
    json.dump(config, open(tmp_path / "benchmark" / "configs"
                           / "too-big.json", "w"))
    traffic = json.load(open(tmp_path / "benchmark" / "traffic"
                             / "steady.json"))
    traffic["rehearsal"]["warmup"]["max_s"] = max_s
    json.dump(traffic, open(tmp_path / "benchmark" / "traffic"
                            / "hopeless.json", "w"))
    bench = dict(BENCH)
    bench["configs"] = BENCH["configs"] + [{
        "name": "too-big", "source": "test fixture",
        "file": "benchmark/configs/too-big.json", "reduced": [],
        "why": "fixture"}]
    bench["workloads"] = BENCH["workloads"] + [{
        "name": "too-big.hopeless", "config": "too-big",
        "traffic": "hopeless", "chips": 1, "why": "fixture"}]
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    t0 = time.monotonic()
    proc = start(["--workload", "too-big.hopeless", "--seed", str(2**31 + 9),
                  "--seconds", "5", "--trace", "0", "--rehearse"],
                 cwd=tmp_path, script=str(tmp_path / "benchmark" / "run.py"))
    took = time.monotonic() - t0
    assert proc.returncode == run.EXIT_HOPELESS, proc.stderr[-2000:]
    assert took < max_s + 30, took
    lines = proc.stdout.strip().splitlines()
    assert lines[-1].startswith("REHEARSAL no result: warm-up reached its "
                                f"bound of {max_s} s and the device served "
                                "no request"), lines[-1]
    assert not any("correct" in line for line in lines)
    assert proc.stderr.strip().splitlines()[-1] == lines[-1]


@pytest.mark.parametrize("served,hopeless", [(0, True), (7, False)])
def test_a_bounded_warm_up_goes_on_only_with_work_served(monkeypatch, served,
                                                         hopeless):
    """A warm-up that reaches its bound because the program count never
    stood still goes on into the window as it always did, if the device
    served anything meanwhile; with nothing served it is a HopelessRun."""
    import run

    reads = []

    def read_counters(_conn):
        reads.append(None)
        n = len(reads)
        return {"batcher.jit_cache_size": n, "batcher.dispatches": n,
                "batcher.batched_requests": 5 + (served if n > 1 else 0)}

    class Child:
        def poll(self):
            return None

    monkeypatch.setattr(run.counters, "read_counters", read_counters)
    monkeypatch.setattr(run, "WARMUP_POLL_S", 0.01)
    rule = {"min_s": 0, "still_s": 1, "still_dispatches": 1,
            "min_requests": 1, "max_s": 0.05}
    quiet = run.Run(rehearse=True)
    if hopeless:
        with pytest.raises(run.HopelessRun, match="served no request"):
            run.warm_up(quiet, None, rule, Child())
    else:
        warm = run.warm_up(quiet, None, rule, Child())
        assert warm["bounded"] is True and warm["programs"] > 1
