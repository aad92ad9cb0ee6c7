"""Pushes: the open loop of `open.py` in which most requests re-register
a job that is RUNNING. The owners of long-running services on a shared
production cell make such traffic: a deploy tool pushes a release at
once, eight jobs, most of them new versions of services that are
running, a few scaled, a few new.

Every job shape of the configuration is a KIND of request. The kind
named by the traffic file's `standing` (`new`) is sent under a fresh id:
an arrival, and the shape of the standing services. Every other kind is
sent under the id of a standing service, a `standing`-shaped job that
warm-up registered and whose evaluation this generator saw `complete`
before anything was updated: the server then finds the job running and
reconciles it against the body sent (stops and placements in one plan,
allocations rewritten in place, or both). A sample is what `open.py`
makes, with `job_id` the target's and `template` the kind's name.

Each standing service is a target AT MOST ONCE in the process's life,
and none is registered inside the window: the harness reads an
evaluation's allocations back long after, and a later evaluation on the
same job would have moved them (`loadgen.read_back`). The order in which
the services are handed out is drawn from the seed; the window's
targets are set aside first (the window's schedule and kinds are drawn
before anything is sent: every seed the same number of each kind), so a
long warm-up cannot eat them. A generator that runs out of targets
stops with an error: it never turns an update into an arrival.

Parameters (the traffic file), beyond `open.py`'s: `standing` (the kind
that is an arrival), `standing_rounds` (how many services to register
at once, round after round, before anything else), `kind_rounds` (lists
of kinds, each sent at once: every kind alone and mixed in one burst,
so that every program an update can meet is compiled early),
`fresh_rounds` (lists of kinds, each sent at once under FRESH ids: an
arrival of that kind's body. A replan that comes back through the
queue pads its asks to its job's whole count, so a scale-up of four
asks whose plan was rejected scans the rung of its twelve: an arrival
of twelve compiles that program before the window). Then the arrival
process open-ended, in the configuration's shares, until the parent
names the window, and the window's schedule from its start.
"""

from __future__ import annotations

import random
import time

import plugins

_open = plugins.load("generators", "open")
follow = _open.follow
window_schedule = _open.window_schedule
window_shapes = _open.window_shapes
open_ended = _open.open_ended
Pool = _open.Pool


class OutOfTargets(RuntimeError):
    """No standing service is left to update."""


def schedule(spec: dict, rng) -> tuple:
    """(offsets, kinds) of the window: `open.py`'s schedule, and for
    each request the index of its kind (largest remainders of the
    configuration's shares, order drawn from the seed)."""
    offsets = window_schedule(spec["traffic"]["arrivals"], spec["seconds"],
                              rng)
    return offsets, window_shapes(len(offsets), spec["jobs"], rng)


def run(spec: dict, control, make_conn) -> list:
    samples: list = []
    traffic = spec["traffic"]
    jobs = spec["jobs"]
    names = [job["name"] for job in jobs]
    arrival = names.index(traffic["standing"])
    poll_wait = traffic["poll_wait_s"]
    prefix = spec["prefix"]
    rng = random.Random(spec["seed"])
    offsets, kinds = schedule(spec, rng)

    def send(item, state: dict) -> None:
        job_id, kind, t_due = item
        sample = {
            "job_id": job_id, "client": state["number"],
            "template": names[kind], "t_due": t_due,
            "t_register": None, "late_s": None, "eval_id": None,
            "t_registered": None, "t_terminal": None, "status": "unsent"}
        samples.append(sample)
        t_register = time.monotonic()
        if t_register >= control.deadline():
            return
        sample["t_register"] = t_register
        sample["status"] = "register_error"
        if t_due is not None:
            sample["late_s"] = t_register - t_due
        if "conn" not in state:
            state["conn"] = make_conn()
        conn = state["conn"]
        body = jobs[kind]["body"].replace(b"@@JOB@@", job_id.encode())
        try:
            out, _ = conn.request("PUT", "/v1/jobs", body)
        except Exception as e:  # noqa: BLE001 - counted; the schedule goes on
            sample["error"] = repr(e)
            return
        sample["t_registered"] = time.monotonic()
        sample["eval_id"] = out["eval_id"]
        try:
            status, seen = follow(conn, out["eval_id"], poll_wait,
                                  control.deadline)
        except Exception as e:  # noqa: BLE001
            sample["error"] = repr(e)
            status, seen = "follow_error", None
        sample["status"], sample["t_terminal"] = status, seen

    pool = Pool(traffic["max_in_flight"], send)

    # The standing services first: arrivals of the standing shape.
    for r, clients in enumerate(traffic["standing_rounds"]):
        for i in range(clients):
            pool.submit((f"{prefix}-s{r:02d}x{i:03d}", arrival, None))
        pool.wait(control.deadline)
    standing = sorted(s["job_id"] for s in samples
                      if s["status"] == "complete")
    rng.shuffle(standing)
    # The window's targets are set aside; the rest are warm-up's.
    need = sum(1 for kind in kinds if kind != arrival)
    if len(standing) < need:
        raise OutOfTargets(
            f"{len(standing)} standing services are complete and the "
            f"window updates {need}")
    window_targets, spare = standing[:need], standing[need:]
    fresh = iter(range(10**9))

    def request(kind: int, tag: str, targets: list, t_due) -> tuple:
        """One request of `kind`: an arrival (or any kind with no
        `targets`) under a fresh id, an update under the next target's."""
        if kind == arrival or targets is None:
            return (f"{prefix}-{tag}{next(fresh):06d}", kind, t_due)
        if not targets:
            raise OutOfTargets(
                f"no standing service left for a {names[kind]!r} "
                f"({tag}): warm-up outlasted its {len(standing) - need}")
        return (targets.pop(), kind, t_due)

    # Every kind alone and mixed, each round at once.
    for round_ in traffic["kind_rounds"]:
        for name in round_:
            pool.submit(request(names.index(name), "k", spare, None))
        pool.wait(control.deadline)

    # Every body named there as an arrival of its own.
    for round_ in traffic.get("fresh_rounds", ()):
        for name in round_:
            pool.submit(request(names.index(name), "f", None, None))
        pool.wait(control.deadline)

    # The arrivals themselves until the parent names the window.
    start = time.monotonic()
    share = [job["share"] for job in jobs]
    for offset in open_ended(traffic["arrivals"], rng):
        while not control.told.is_set() and not control.stopped():
            left = start + offset - time.monotonic()
            if left <= 0:
                break
            control.told.wait(min(left, 1.0))
        if control.told.is_set() or control.stopped():
            break
        kind = rng.choices(range(len(jobs)), share)[0]
        pool.submit(request(kind, "u", spare, start + offset))

    # The window: the schedule drawn above, from its start.
    if control.window_start is not None:
        for offset, kind in zip(offsets, kinds):
            t_due = control.window_start + offset
            while True:
                left = t_due - time.monotonic()
                if left <= 0:
                    break
                time.sleep(left)
            pool.submit(request(kind, "c", window_targets, t_due))

    pool.wait(control.deadline)
    pool.close()
    for t in pool.threads:
        t.join(timeout=max(0.0, control.deadline() - time.monotonic()) + 5.0)
    return samples
