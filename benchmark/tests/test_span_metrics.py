"""The per-layer metrics that read the program's account of an eval's
life from inside (PR 25): the `span_share` reader's arithmetic, and for
every metric file of the `span` and `span_share` readers, that its
stages exist in the program and that a traced rehearsal reads it."""

import glob
import json
import math
import os
import subprocess
import sys

import pytest

import plugins
import stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SPECS = {os.path.basename(path)[:-len(".json")]: json.load(open(path))
         for path in sorted(glob.glob(os.path.join(
             ROOT, "benchmark", "metrics", "*.json")))}
SPAN_METRICS = sorted(name for name, spec in SPECS.items()
                      if spec["reader"] in ("span", "span_share"))
CELLS = [w["name"] for w in BENCH["workloads"]]


def reading(samples_ms):
    """A `stage_buckets()` reading holding `samples_ms`, by the
    program's own bucket function."""
    from nomad_tpu.utils.metrics import hist_bucket

    buckets = [0] * stats.HIST_BUCKETS
    for ms in samples_ms:
        buckets[hist_bucket(ms)] += 1
    return len(samples_ms), buckets


def test_span_share_is_mass_over_mass():
    reader = plugins.load("readers", "span_share")
    ctx = {"spans_before": {"a": reading([10.0]), "b": None},
           "spans_after": {"a": reading([10.0, 30.0, 30.0]),
                           "b": reading([100.0]), "c": reading([40.0])}}
    # window: a = 30 + 30, b = 100, c = 40
    share = reader.read({"num": ["a"], "den": ["a", "b", "c"]}, ctx)
    assert share == pytest.approx(0.3, rel=0.10)
    parts = [reader.read({"num": [s], "den": ["a", "b", "c"]}, ctx)
             for s in "abc"]
    assert sum(parts) == pytest.approx(1.0)
    # a bucket is 19% wide: its middle is within 9% of any sample in it
    assert reader.mass_ms(["b"], ctx) == pytest.approx(100.0, rel=0.09)
    assert reader.bucket_middle_ms(0) == 0.0
    assert reader.bucket_middle_ms(1) == stats.HIST_MIN_MS
    assert reader.bucket_middle_ms(40) == pytest.approx(
        stats.bucket_upper_ms(40) / math.sqrt(stats.HIST_RATIO))


def test_span_share_reads_nothing_from_a_program_without_the_stage():
    """The parent of the PR that adds a stage has no such histogram: the
    metric is left out, and nothing raises."""
    reader = plugins.load("readers", "span_share")
    ctx = {"spans_before": {}, "spans_after": {"e2e": None}}
    assert reader.read({"num": ["eval.uncovered"], "den": ["e2e"]},
                       ctx) is None
    ctx = {"spans_before": {}, "spans_after": {"e2e": reading([50.0])}}
    assert reader.read({"num": ["eval.uncovered"], "den": ["e2e"]},
                       ctx) == 0.0


def stages_of(spec):
    args = spec["args"]
    return [args["stage"]] if spec["reader"] == "span" \
        else args["num"] + args["den"]


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_metric_file_names_a_stage_of_the_program(name):
    from nomad_tpu import trace

    spec = SPECS[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    known = set(trace.ALL_STAGES) | set(trace.DEVICE_IDLE_STAGES) \
        | {"e2e", trace.STAGE_EVAL_UNCOVERED}
    for stage in stages_of(spec):
        if stage.endswith(trace.SELF_SUFFIX):
            stage = stage[:-len(trace.SELF_SUFFIX)]
        assert stage in known, stage


@pytest.fixture(scope="module")
def traced_rehearsals():
    """One traced rehearsal of each cell, as the driver would start it."""
    out = {}
    for cell in CELLS:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", str(2**31 + 25), "--seconds", "5",
             "--trace", "1", "--rehearse"],
            cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = proc.stdout.strip().splitlines()[-1]
        out[cell] = json.loads(line[len("REHEARSAL "):])
    return out


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_traced_rehearsal_reads_the_metric(traced_rehearsals, name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    for cell, result in traced_rehearsals.items():
        if "workloads" in entry and cell not in entry["workloads"]:
            continue
        assert result["correct"] is True
        value = result["metrics"][name]["value"]
        assert value is not None and value >= 0.0, (cell, name)
        if SPECS[name]["reader"] == "span_share":
            assert value <= 1.0 + 1e-9, (cell, name, value)


def test_idle_shares_sum_to_one_and_little_is_uncovered(traced_rehearsals):
    for cell, result in traced_rehearsals.items():
        metrics = result["metrics"]
        total = sum(metrics[f"device_idle_{part}_share"]["value"]
                    for part in ("no_work", "batch_wait", "stack"))
        assert total == pytest.approx(1.0), cell
        assert metrics["eval_uncovered_share"]["value"] < 0.05, cell
