"""The benchmark's `borg-push-12k` deployment against a live dev server:
its committed configuration at rehearsal scale loaded by
`benchmark/fleet.py`, standing services registered over HTTP, then one
burst as the cell's generator sends it (pushes, a scale-up, an in-place
update and arrivals at once, each update under the id of a service that
is running), read back as `loadgen.read_back` reads a run and judged by
the deployment's own check, `benchmark/checks/borg_push.py`. Tier-1 does
not run `benchmark/tests/` (its `test_push.py` holds whole rehearsals),
so this keeps the program's update lanes, the store's eval index, the
generator's schedule and the check's reading of the dump together:
sound updates read 0 on every count; a doctored dump reads each count
in turn."""

import importlib.util
import json
import os
import random
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from nomad_tpu.api.http import HTTPServer
from nomad_tpu.scheduler.batcher import get_batcher
from nomad_tpu.server import Server, ServerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "borg-push-12k.releases"
COUNTS = ("pushed_allocs_at_the_old_memory", "updated_jobs_off_their_count",
          "updated_jobs_with_allocs_of_two_evals",
          "in_place_jobs_with_a_stopped_alloc",
          "pushed_jobs_not_stopping_their_old_count",
          "window_allocs_on_unready_nodes", "no_push_completed")
KINDS = ("push", "new", "scale", "touch")


def _load(name):
    """A module of benchmark/ under a name of its own (the directory is
    not a package and its module names are common ones)."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name.replace('/', '_')}",
        os.path.join(BENCH, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _generator():
    """`generators/pushes.py` loads `open.py` through the benchmark's
    own `plugins`, found on the path as it is in `loadgen.py`."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return _load("generators/pushes")


def committed():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "borg-push-12k")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    return bench, entry, cell, config, traffic


def test_the_committed_files_load_and_validate():
    bench, entry, cell, config, traffic = committed()
    fleet = _load("fleet")
    assert entry["reduced"] == config["reduced"] == [
        "rolling_limit", "standing_services"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert config["checks"] == ["borg_push"] and config["architecture"] is None
    assert config["assumed"] and set(config["reduced_why"]) == set(
        config["reduced"])
    assert {"updates", "in_place", "acknowledged", "placements"} <= set(
        config["guarantees"])
    # the fleet is borg-12k's ten shapes, whole, with borg-attrs-12k's
    # one-rule standing work and nothing else
    with open(os.path.join(BENCH, "configs", "borg-12k.json")) as f:
        borg = json.load(f)
    classes = config["fleet"]["classes"]
    assert [(c["count"], c["node"]) for c in classes] == [
        (c["count"], c["node"]) for c in borg["fleet"]["classes"]]
    assert sum(c["count"] for c in classes) == 12583
    assert sum(c["count"] * c["filler"]["per_node"] for c in classes) \
        == 213168
    assert all(isinstance(c["filler"], dict) and c["filler"]["type"] == "batch"
               and "priority" not in c["filler"] and "topology" not in c
               for c in classes)
    assert "preemption_enabled" not in config["server"]
    assert config["server"]["num_schedulers"] == 2

    specs = fleet.job_specs(config)
    assert [(s["name"], s["share"], s["count"], s["task"]["memory_mb"])
            for s in specs] == [("push", 0.55, 8, 24), ("new", 0.25, 8, 16),
                                ("scale", 0.10, 12, 16), ("touch", 0.10, 8, 16)]
    assert abs(sum(s["share"] for s in specs) - 1.0) < 1e-9
    bodies = {}
    for spec in specs:
        assert (spec["type"], spec["priority"], spec["group"],
                spec["distinct_hosts"]) == ("service", 50, "web", True)
        body = fleet.job_template(spec)     # validates, or raises
        assert body["task_groups"][0]["count"] == spec["count"]
        bodies[spec["name"]] = body
    # `touch` sends the standing shape's body again, letter for letter
    assert bodies["touch"] == bodies["new"]
    assert config["updates"]["standing"] == traffic["standing"] == "new"
    small = fleet.scaled(config, True)
    assert {s["name"]: s["count"] for s in fleet.job_specs(small)} \
        == config["rehearsal"]["job_count"]

    arrivals = traffic["arrivals"]
    assert (traffic["kind"], arrivals["process"], arrivals["burst_size"],
            traffic["max_in_flight"], traffic["drain_s"]) == (
        "pushes", "bursts", 8, 512, 60)
    assert arrivals["rate_evals_per_s"] <= 15
    assert str(arrivals["rate_evals_per_s"]) + " evals/s" in cell["why"]
    # every kind at least twice alone and twice mixed in one burst
    rounds = traffic["kind_rounds"]
    for kind in KINDS:
        assert sum(r == [kind] * len(r) and len(r) >= 2 for r in rounds) >= 1
    assert sum(set(r) == set(KINDS) for r in rounds) >= 2
    # the largest count as an arrival too: the ask rung a requeued
    # replan of a scale-up pads to (its job's twelve) is compiled
    counts = {s["name"]: s["count"] for s in specs}
    largest = max(counts, key=counts.get)
    assert sum(largest in r and len(r) >= 2
               for r in traffic["fresh_rounds"]) >= 2
    # the window cannot be named before the standing services are there
    services = sum(traffic["standing_rounds"])
    assert traffic["warmup"]["min_requests"] > services
    # the pool holds 1.2 times the updates warm-up (its rounds, then its
    # arrivals for as long as warm-up may last) and the window can send
    rate = arrivals["rate_evals_per_s"]
    updated = 1.0 - specs[1]["share"]
    need = (sum(1 for r in rounds for kind in r if kind != "new")
            + updated * rate * (traffic["warmup"]["max_s"] + 51.0))
    assert services >= 1.2 * need, (services, 1.2 * need)


# ---------------------------------------------------------------------
# the generator's schedule


class _FakeConn:
    """A server that completes every evaluation at once."""

    log: list = []
    lock = threading.Lock()

    def request(self, method, path, body=None):
        if method == "PUT":
            job = json.loads(body)["job"]
            with self.lock:
                self.log.append((job["id"], job["task_groups"][0]["count"],
                                 job["task_groups"][0]["tasks"][0][
                                     "resources"]["memory_mb"]))
                return {"eval_id": f"e{len(self.log)}"}, 1
        return {"status": "complete"}, 1

    def close(self):
        pass


class _Control:
    def __init__(self, seconds):
        self.told = threading.Event()
        self.window_start = self.stop_at = None
        self.born, self.seconds = time.monotonic(), seconds

    def name_window(self):
        self.window_start = time.monotonic()
        self.stop_at = self.window_start + self.seconds
        self.told.set()

    def stopped(self):
        return self.stop_at is not None and time.monotonic() >= self.stop_at

    def deadline(self):
        return (self.stop_at if self.stop_at is not None
                else self.born + 60.0) + 5.0


def _spec(seed, seconds=1.5):
    fleet = _load("fleet")
    _bench, _entry, _cell, config, traffic = committed()
    traffic = dict(traffic, **traffic["rehearsal"])
    jobs = [{"name": s["name"], "share": s["share"],
             "body": json.dumps({"job": dict(
                 fleet.job_template(s), id="@@JOB@@",
                 name="@@JOB@@")}).encode()}
            for s in fleet.job_specs(fleet.scaled(config, True))]
    return {"traffic": traffic, "jobs": jobs, "seed": seed,
            "seconds": seconds, "prefix": f"releases-{seed}"}


def _drive(seed, warm_s=0.6):
    gen = _generator()
    _FakeConn.log = []
    spec = _spec(seed)
    control = _Control(spec["seconds"])
    threading.Timer(warm_s, control.name_window).start()
    samples = gen.run(spec, control, _FakeConn)
    return spec, control, samples


@pytest.mark.parametrize("seed", [1, 2**31 + 4510, 2**32 - 1])
def test_the_schedule_has_the_same_kinds_on_every_seed(seed):
    gen = _generator()
    spec = _spec(seed, seconds=51.0)
    offsets, kinds = gen.schedule(spec, random.Random(seed))
    rate = spec["traffic"]["arrivals"]["rate_evals_per_s"]
    assert len(offsets) == len(kinds) == round(rate * 51.0 / 4) * 4
    by_kind = {spec["jobs"][i]["name"]: kinds.count(i) for i in range(4)}
    # largest remainders of 0.55, 0.25, 0.10, 0.10: the same on every seed
    n = len(kinds)
    assert by_kind == {"push": round(0.55 * n), "new": round(0.25 * n),
                       "scale": round(0.10 * n), "touch": round(0.10 * n)}


@pytest.mark.parametrize("seed", [2**31 + 4511, 2**31 + 4512])
def test_no_service_is_updated_twice_and_none_of_the_window(seed):
    spec, control, samples = _drive(seed)
    assert all(s["status"] == "complete" for s in samples)
    standing = {s["job_id"] for s in samples if "-s" in s["job_id"]
                and s["template"] == "new" and s["t_due"] is None
                and s["job_id"].split("-")[-1].startswith("s")}
    assert len(standing) == sum(spec["traffic"]["standing_rounds"])
    # `fresh_rounds`: a kind's body as an arrival of its own (tag `f`)
    arrivals = [s for s in samples if s["template"] != "new"
                and s["job_id"].split("-")[-1].startswith("f")]
    assert sorted(s["template"] for s in arrivals) == sorted(
        kind for r in spec["traffic"]["fresh_rounds"] for kind in r)
    assert all(s["t_due"] is None for s in arrivals)
    updates = [s for s in samples if s["template"] != "new"
               and s not in arrivals]
    targets = [s["job_id"] for s in updates]
    assert len(targets) == len(set(targets))        # at most once each
    assert set(targets) <= standing                 # and only of those
    window = [s for s in samples if s["t_due"] is not None
              and control.window_start <= s["t_due"] < control.stop_at]
    _offsets, kinds = _generator().schedule(spec, random.Random(seed))
    assert [s["template"] for s in sorted(window, key=lambda s: s["t_due"])] \
        .count("push") == kinds.count(0)
    assert len(window) == len(kinds)
    # a standing service was registered, and seen complete, before the
    # first update was sent; none is registered inside the window
    first_update = min(s["t_register"] for s in updates)
    for s in samples:
        if s["job_id"] in standing and s["template"] == "new":
            assert s["t_terminal"] <= first_update < control.stop_at
            assert s["t_register"] < control.window_start
    # what went over the wire: an update carries its kind's body under
    # the target's id, an arrival a fresh id
    shape = {s["name"]: json.loads(s["body"].decode())["job"]
             for s in spec["jobs"]}
    sent = {}
    for job_id, count, memory in _FakeConn.log:
        sent.setdefault(job_id, []).append((count, memory))
    for s in updates:
        tg = shape[s["template"]]["task_groups"][0]
        assert sent[s["job_id"]][-1] == (
            tg["count"], tg["tasks"][0]["resources"]["memory_mb"])
        assert len(sent[s["job_id"]]) == 2      # registered, then updated
    fresh = [s["job_id"] for s in samples
             if s["template"] == "new" and s["job_id"] not in standing]
    fresh += [s["job_id"] for s in arrivals]
    assert len(fresh) == len(set(fresh)) and all(
        len(sent[j]) == 1 for j in fresh)


def test_a_generator_out_of_targets_stops_with_an_error():
    gen = _generator()
    spec = _spec(2**31 + 4513)
    spec["traffic"] = dict(spec["traffic"], standing_rounds=[2, 6])
    control = _Control(spec["seconds"])
    with pytest.raises(gen.OutOfTargets):
        gen.run(spec, control, _FakeConn)


# ---------------------------------------------------------------------
# a burst of the cell on a live server, and the check


@pytest.fixture(scope="module")
def pushed():
    """(store dump, window jobs, configuration, samples, counters) after
    one burst of the cell (4 pushes, a scale-up, an in-place update and
    2 arrivals at once) on the committed fleet at rehearsal scale."""
    fleet, httpc, store_dump, loadgen = (_load(name) for name in (
        "fleet", "httpc", "store_dump", "loadgen"))
    config = fleet.scaled(committed()[3], True)
    shapes = {s["name"]: s for s in fleet.job_specs(config)}
    server = Server(ServerConfig(**config["server"]))
    server.start()
    http = HTTPServer(server, host="127.0.0.1", port=0)
    http.start()
    conn = httpc.Conn(http.addr)
    try:
        fleet.load_fleet(server, config, 2**31 + 4514)

        def register(item):
            kind, job_id = item
            body = json.dumps({"job": dict(
                fleet.job_template(shapes[kind]), id=job_id,
                name=job_id)}).encode()
            c = httpc.Conn(http.addr)
            try:
                return c.request("PUT", "/v1/jobs", body)[0]["eval_id"]
            finally:
                c.close()

        def at_once(items):
            with ThreadPoolExecutor(len(items)) as pool:
                evals = list(pool.map(register, items))
            deadline = time.monotonic() + 120.0
            for eval_id in evals:
                while time.monotonic() < deadline:
                    ev, _ = conn.request("GET", f"/v1/evaluation/{eval_id}")
                    if ev["status"] in ("complete", "failed", "cancelled"):
                        break
                    time.sleep(0.05)
                assert ev["status"] == "complete", ev
            return evals

        standing = [f"releases-s{i}" for i in range(6)]
        at_once([("new", job_id) for job_id in standing])
        before = get_batcher().stats()
        burst = ([("push", standing[i]) for i in range(4)]
                 + [("scale", standing[4]), ("touch", standing[5]),
                    ("new", "releases-c0"), ("new", "releases-c1")])
        evals = at_once(burst)
        after = get_batcher().stats()
        samples = [{"job_id": job_id, "template": kind, "eval_id": eval_id}
                   for (kind, job_id), eval_id in zip(burst, evals)]
        loadgen.read_back(samples, lambda: httpc.Conn(http.addr))
        window_jobs = {job_id: {
            "count": shapes[kind]["count"], "template": kind,
            "distinct_hosts": True} for kind, job_id in burst}
        store = store_dump.dump_store(server.fsm.state.snapshot())
        counters = {key: after[key] - before[key] for key in (
            "dispatches", "batched_requests", "compact_dispatches")}
        yield store, window_jobs, config, samples, counters
    finally:
        conn.close()
        http.stop()
        server.shutdown()


def test_a_burst_of_the_cell_reads_back_whole(pushed):
    store, window_jobs, config, samples, counters = pushed
    check = _load("checks/borg_push").check
    assert check(store, window_jobs, config) == dict.fromkeys(COUNTS, 0)
    # comparison 1, as the harness makes it: an evaluation lists `count`
    # allocations in `desired run`, those it rewrote in place included
    # (the store's eval index moved them: state/store.py upsert_allocs)
    counts = config["rehearsal"]["job_count"]
    for s in samples:
        assert s["read_status"] == "complete", s
        assert s["read_allocs"] == counts[s["template"]], s
    # the pushes and the scale-up went to the device as lanes of compact
    # dispatches beside the arrivals; the in-place update made none
    assert counters["batched_requests"] >= 6
    assert counters["compact_dispatches"] == counters["dispatches"] > 0


def _doctored(store, window_jobs, config, case):
    """The dump with one thing wrong, and the count that has to see it."""
    job_of = {job: i for i, job in enumerate(store["job_ids"])}
    kinds = {spec["template"]: job for job, spec in window_jobs.items()}
    store = dict(store)
    rows = {kind: np.flatnonzero(store["alloc_job"] == job_of[job])
            for kind, job in kinds.items()}
    if case == "pushed_allocs_at_the_old_memory":
        # one allocation of the old version left live
        usage = store["alloc_usage"].copy()
        usage[rows["push"][0], 1] = 16
        store["alloc_usage"] = usage
    elif case == "updated_jobs_off_their_count":
        window_jobs = dict(window_jobs)
        window_jobs[kinds["scale"]] = dict(
            window_jobs[kinds["scale"]], count=4)
    elif case == "updated_jobs_with_allocs_of_two_evals":
        evals = list(store["alloc_eval"])
        evals[rows["touch"][0]] = "the-eval-that-placed-it"
        store["alloc_eval"] = evals
    elif case == "in_place_jobs_with_a_stopped_alloc":
        store["gone_job"] = list(store["gone_job"]) + [kinds["touch"]]
        store["gone_desired"] = list(store["gone_desired"]) + ["stop"]
    elif case == "pushed_jobs_not_stopping_their_old_count":
        at = store["gone_job"].index(kinds["push"])
        store["gone_job"] = [j for i, j in enumerate(store["gone_job"])
                             if i != at]
        store["gone_desired"] = [d for i, d in enumerate(
            store["gone_desired"]) if i != at]
    elif case == "window_allocs_on_unready_nodes":
        ready = store["node_ready"].copy()
        ready[int(store["alloc_node"][rows["new"][0]])] = False
        store["node_ready"] = ready
    elif case == "no_push_completed":
        window_jobs = {job: spec for job, spec in window_jobs.items()
                       if spec["template"] != "push"}
    return store, window_jobs, config


@pytest.mark.parametrize("case", COUNTS)
def test_each_count_of_the_check_fires_on_a_doctored_store(pushed, case):
    store, window_jobs, config, _samples, _counters = pushed
    check = _load("checks/borg_push").check
    counts = check(*_doctored(store, window_jobs, config, case))
    assert counts[case] >= 1
    # and nothing else does, but for what the doctoring itself implies:
    # a push with an allocation of the old memory has not completed
    # whole only if it was the window's one push (here there are four)
    others = {k: v for k, v in counts.items() if k != case and v}
    assert not others, others
