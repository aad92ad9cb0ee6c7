"""The cell PR 51 added, `borg-push-12k.releases`: its files and
entries are there; the cell is correct at rehearsal size, reads its
metrics and its own check's counts at 0; the check's control: an allocation of a pushed job
left live at the old version in the store's dump, and the run is not
correct. The schedule, the committed files and the check's counts on
doctored stores are held in tier-1 (`tests/test_benchmark_push.py`).
No test here pins the set of files under `benchmark/` for later PRs
(`ROADMAP.md` M1 (k))."""

import json
import os

import numpy as np

import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CONFIG = "borg-push-12k"
CELL = "borg-push-12k.releases"
NEW_METRICS = {"reconcile_p50_ms", "plan_patch_p50_ms",
               "plan_patch_lanes_per_eval"}
NEW_FILES = {"configs/borg-push-12k.json", "traffic/releases.json",
             "generators/pushes.py", "checks/borg_push.py",
             "tests/test_push.py",
             *(f"metrics/{name}.json" for name in NEW_METRICS)}
# the lists ISSUE 51 names (the open loop's median, and the per-layer
# metrics whose readers find something in this cell), the open loop's
# tails, and every list that names all the accepted cells (tier-1 holds
# those to every cell: tests/test_trace.py)
GAINED = {"place_due_p50_ms", "place_due_p95_ms", "place_due_p99_ms",
          "compact_dispatch_share", "dispatches_per_batch",
          "plain_handovers_per_eval"}
COUNTS = {"pushed_allocs_at_the_old_memory", "updated_jobs_off_their_count",
          "updated_jobs_with_allocs_of_two_evals",
          "in_place_jobs_with_a_stopped_alloc",
          "pushed_jobs_not_stopping_their_old_count",
          "window_allocs_on_unready_nodes", "no_push_completed"}


def test_the_cell_has_its_files_and_entries():
    for name in NEW_FILES:
        assert os.path.exists(os.path.join(BENCH, name)), name
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/borg-push-12k.json"
    assert config["reduced"] == ["rolling_limit", "standing_services"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="releases", chips=1)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert GAINED | NEW_METRICS <= listed
    for m in bench["per_layer"]:
        if m["name"] not in NEW_METRICS:
            continue
        # a metric of this cell alone: its span is this PR's, so no
        # cell an earlier program serves has the row
        assert m["workloads"] == [CELL] and m["layer"] == "cluster base"
        assert m["moves"] == "place_due_p50_ms"
        spec = json.load(open(os.path.join(
            BENCH, "metrics", f"{m['name']}.json")))
        assert os.path.exists(os.path.join(
            BENCH, "readers", f"{spec['reader']}.py"))


def rehearse(capsys, seed, trace=0, seconds=6):
    code = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace), "--rehearse"])
    assert code == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert all(line.startswith("REHEARSAL ") for line in lines)
    return (json.loads(lines[-1][len("REHEARSAL "):]),
            [line.split()[2].rstrip(":") for line in lines
             if line.endswith("FAIL")])


def test_releases_rehearsal_is_correct_and_reads_its_metrics(capsys):
    result, failed = rehearse(capsys, 2**31 + 5101, trace=1)
    assert result["correct"] is True and not failed, failed
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert {name.split(".", 1)[1] for name in compared
            if name.startswith("borg_push.")} == COUNTS
    for name in COUNTS:
        assert compared[f"borg_push.{name}"]["value"] == 0
    assert compared["resident_rows_differing"]["value"] == 0
    metrics = result["metrics"]
    assert NEW_METRICS | (GAINED - {"place_due_p50_ms"}) | {
        "base_delta_p50_ms", "gc_pause_p95_ms", "gc_passes_per_eval",
        "gil_wait_p50_ms", "register_server_p50_ms"} <= set(metrics)
    # update evals are lanes of compact dispatches on the shared base;
    # about 0.65 of the window's evals carry a patch (push and scale)
    assert metrics["compact_dispatch_share"]["value"] == 1.0
    assert 0.4 < metrics["plan_patch_lanes_per_eval"]["value"] < 0.9
    assert metrics["window_compiles"]["value"] == 0
    assert compared["device_requests_in_window"]["value"] > 0
    result, _failed = rehearse(capsys, 2**31 + 5102)
    assert result["correct"] is True
    assert {"place_due_p50_ms", "placed_allocs_per_s", "setup_s"} \
        <= set(result["metrics"])


def test_a_doctored_dump_is_not_correct(capsys, monkeypatch):
    """The check around a whole rehearsal: one allocation of a pushed
    job of the window reads the old version's memory in the store's
    dump on its way to the judge (the run itself is sound)."""
    real = run.store_dump.dump_store
    seen = {}

    def doctor(snapshot):
        store = real(snapshot)
        seen["store"] = store
        return store

    real_check = run.plugins.load

    def load(directory, name):
        module = real_check(directory, name)
        if (directory, name) != ("checks", "borg_push"):
            return module
        inner = module.check

        def check(store, window_jobs, config):
            job_of = {j: i for i, j in enumerate(store["job_ids"])}
            pushed = next(job for job, spec in window_jobs.items()
                          if spec["template"] == "push")
            row = int(np.flatnonzero(
                store["alloc_job"] == job_of[pushed])[0])
            usage = store["alloc_usage"].copy()
            usage[row, 1] = 16
            return inner(dict(store, alloc_usage=usage), window_jobs, config)

        module.check = check
        return module

    monkeypatch.setattr(run.store_dump, "dump_store", doctor)
    monkeypatch.setattr(run.plugins, "load", load)
    result, failed = rehearse(capsys, 2**31 + 5103)
    assert result["correct"] is False
    name = "borg_push.pushed_allocs_at_the_old_memory"
    assert failed == [name] and result["compared"][name]["value"] == 1
