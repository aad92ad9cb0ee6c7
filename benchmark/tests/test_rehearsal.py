"""Each cell of BENCHMARK.json end to end at rehearsal size, as the
driver would start it (a process of its own), both with and without the
trace; and the two ways a run has to end with no result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


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
    assert any(line.startswith("REHEARSAL check ") for line in lines)


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
