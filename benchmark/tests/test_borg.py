"""The cells PR 28 added, at rehearsal size: `borg-12k.mixed` places
production work by evicting and its own check (`checks/borg_bands.py`)
holds the bands; `northstar-10k.bursts` is the steady cell's traffic in
bursts. The check on doctored stores, a timed path broken underneath
(victims taken out of order, patched from outside as `control.py`
patches), and the accepted benchmark's files byte for byte."""

import hashlib
import json
import os

import numpy as np

import plugins
import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
BORG, BURSTS = "borg-12k.mixed", "northstar-10k.bursts"
NEW_METRICS = {"preempt_select_p50_ms", "preempt_select_p95_ms",
               "preempt_victims_p50_ms", "preempt_solve_p50_ms",
               "preempt_share_of_eval", "preempt_passes_per_eval"}
BANDS = {"victims_not_below_preemptor", "victims_above_a_survivor",
         "evictions_without_placement", "arrivals_evicted",
         "no_eviction_in_window", "allocs_without_priority"}


def rehearse(capsys, cell, seed, trace=0, seconds=6):
    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace), "--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert all(line.startswith("REHEARSAL ") for line in lines)
    return (json.loads(lines[-1][len("REHEARSAL "):]),
            [line for line in lines if line.endswith("FAIL")], lines)


def test_borg_mixed_rehearsal_evicts_and_is_correct(capsys):
    result, failed, lines = rehearse(capsys, BORG, 2**31 + 2801, trace=1)
    assert result["correct"] is True and not failed, failed
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert {name.split(".", 1)[1] for name in compared
            if name.startswith("borg_bands.")} == BANDS
    # the mechanism did the work: something was evicted, and all three
    # shapes were held to their own counts
    assert compared["borg_bands.no_eviction_in_window"]["value"] == 0
    assert NEW_METRICS <= set(result["metrics"])
    for name in NEW_METRICS | {"place_due_p95_ms", "place_due_p99_ms"}:
        assert result["metrics"][name]["value"] > 0, name
    # 0.7 of the evaluations must evict; a conflict's retry passes again
    assert 0.5 <= result["metrics"]["preempt_passes_per_eval"]["value"] <= 1.5
    assert result["metrics"]["eval_uncovered_share"]["value"] < 0.01
    fleet_line = next(l for l in lines if " fleet: " in l)
    assert "'nodes': 294" in fleet_line, fleet_line   # ten shapes


def test_bursts_rehearsal_is_correct(capsys):
    result, failed, _lines = rehearse(capsys, BURSTS, 2**31 + 2802)
    assert result["correct"] is True and not failed, failed
    assert result["attempted"] == 72 and result["failed"] == 0   # 18 x 4
    assert set(result["metrics"]) == {"placed_allocs_per_s",
                                      "place_due_p50_ms", "setup_s"}


def test_victims_out_of_order_are_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: on each node the middle band
    goes before the free one. The applier verifies victims one by one,
    not their order, so the run commits; the deployment's own check
    reads it."""
    import nomad_tpu.migrate as migrate

    def middle_first(alloc):
        p = migrate.victim_priority(alloc)
        return (-p if p < 50 else p, alloc.create_index, alloc.id)

    monkeypatch.setattr(migrate, "victim_sort_key", middle_first)
    result, failed, _lines = rehearse(capsys, BORG, 2**31 + 2803)
    assert result["correct"] is False
    assert result["compared"]["borg_bands.victims_above_a_survivor"][
        "value"] > 0
    assert any("borg_bands.victims_above_a_survivor" in l for l in failed)
    assert list(result["compared"])[0].startswith("borg_bands.")


def doctored_store():
    """Two nodes; node 0 holds fillers of the three bands and one
    arrival, node 1 fillers only; one free-band filler of node 0 was
    evicted for the arrival."""
    config = {"server": {"preempt_priority_threshold": 50},
              "jobs": [{"priority": 70}, {"priority": 70}]}
    store = {
        "node_ids": ["n0", "n1"],
        "job_ids": ["filler-p10", "filler-p30", "filler-p70", "prod-1"],
        "alloc_job": np.array([0, 1, 2, 3, 0, 1]),
        "alloc_node": np.array([0, 0, 0, 0, 1, 1]),
        "alloc_priority": np.array([10, 30, 70, 70, 10, 30]),
        "gone_ids": ["g0"], "gone_node": np.array([0]),
        "gone_job": ["filler-p10"], "gone_priority": np.array([10]),
        "gone_desired": ["evict"],
    }
    return store, config


def counts_of(store, config):
    return plugins.load("checks", "borg_bands").check(store, {}, config)


def with_gone(store, node, job, priority, desired="evict"):
    out = dict(store)
    out["gone_ids"] = store["gone_ids"] + ["g+"]
    out["gone_node"] = np.append(store["gone_node"], node)
    out["gone_job"] = store["gone_job"] + [job]
    out["gone_priority"] = np.append(store["gone_priority"], priority)
    out["gone_desired"] = store["gone_desired"] + [desired]
    return out


def test_borg_bands_on_doctored_stores():
    store, config = doctored_store()
    sound = counts_of(store, config)
    assert set(sound) == BANDS and not any(sound.values()), sound

    # a victim above a survivor: a middle-band filler went from node 0
    # while a free-band one still stands there
    bad = counts_of(with_gone(store, 0, "filler-p30", 30), config)
    assert bad["victims_above_a_survivor"] == 1
    assert sum(bad.values()) == 1
    # a stopped allocation is no victim
    assert not any(counts_of(
        with_gone(store, 0, "filler-p30", 30, "stop"), config).values())

    # an eviction without its placement: node 1 lost a filler and holds
    # no arrival
    bad = counts_of(with_gone(store, 1, "filler-p10", 10), config)
    assert bad["evictions_without_placement"] == 1
    assert sum(bad.values()) == 1

    # an evicted arrival, which is also not below its preemptor
    bad = counts_of(with_gone(store, 0, "prod-0", 70), config)
    assert bad["arrivals_evicted"] == 1
    assert bad["victims_not_below_preemptor"] == 1

    # a production filler evicted: not strictly below
    bad = counts_of(with_gone(store, 0, "filler-p70", 70), config)
    assert bad["victims_not_below_preemptor"] == 1
    assert bad["arrivals_evicted"] == 0

    # nothing evicted at all: the mechanism never ran
    quiet = dict(store, gone_ids=[], gone_node=np.zeros(0, np.int64),
                 gone_job=[], gone_priority=np.zeros(0, np.int64),
                 gone_desired=[])
    assert counts_of(quiet, config)["no_eviction_in_window"] == 1

    # an allocation that carries no job
    bare = dict(store, alloc_priority=np.array([10, 30, 70, -1, 10, 30]))
    assert counts_of(bare, config)["allocs_without_priority"] == 1


def test_span_count_reader():
    reader = plugins.load("readers", "span_count")
    args = {"stage": "preempt.select", "den": "evals_completed"}
    zeros = [0] * 200
    ctx = {"spans_before": {"preempt.select": (3, zeros)},
           "spans_after": {"preempt.select": (24, zeros)},
           "evals_completed": 30}
    assert reader.read(args, ctx) == 0.7
    # the parent's program has no such span: nothing to read, no raise
    assert reader.read(args, dict(ctx, spans_after={})) is None
    assert reader.read(args, dict(ctx, evals_completed=0)) is None


def test_accepted_benchmark_files_are_byte_for_byte():
    """Every file the benchmark had before PR 28 (the digests of PR 27's
    commit) is still there and unchanged; BENCHMARK.json's accepted
    entries are unchanged but for the open-loop latency's list of
    cells. PR 32, a benchmark PR, edited five of them (`fleet.py`,
    `store_dump.py`, `run.py`, `tests/test_data_driven.py`,
    `tests/test_rehearsal.py`): their digests are that PR's, and what
    the first three still do for the committed configurations is held by
    `test_committed_configurations_load_as_the_parent_loaded_them` and
    the pinned checks and metrics of `test_rehearsal.py`."""
    accepted = json.load(open(os.path.join(
        HERE, "data", "accepted_digests.json")))
    for name, digest in accepted.items():
        with open(os.path.join(BENCH, name), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, name
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert [c["name"] for c in bench["configs"]][:2] == [
        "northstar-10k", "c1m-5k"]
    assert [w["name"] for w in bench["workloads"]][:3] == [
        "northstar-10k.storm", "c1m-5k.ramp", "northstar-10k.steady"]
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert len(bench["workloads"]) <= 5 and bench["run_seconds"] == 51
    due = next(m for m in bench["end_to_end"]
               if m["name"] == "place_due_p50_ms")
    assert due["workloads"][0] == "northstar-10k.steady"
    for metric in bench["per_layer"]:
        if metric["name"] in ("place_due_p95_ms", "place_due_p99_ms"):
            # the open loops' tails: the new cells appended, no more
            assert metric["workloads"] == [
                "northstar-10k.steady", BORG, BURSTS]
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [BORG]
            assert metric["moves"] == "place_due_p50_ms"
