"""Open loop: every request is due at a time of its own, drawn from the
seed before the window, whatever the server does meanwhile. Independent
users make such traffic: teams and pipelines that register jobs on a
schedule of their own and do not wait for each other.

Parameters (the traffic file): `arrivals` (`process`: `poisson`, or
`bursts` with a `burst_size`: burst epochs Poisson at rate / burst_size,
that many requests due at once; `rate_evals_per_s`), `max_in_flight`,
`poll_wait_s`, `drain_s`, and `warmup.rounds` as in `closed.py`.

The window's schedule is the arrival process given its count: every seed
gets round(rate x seconds) requests (bursts: whole bursts). Given their
count, the gaps of a Poisson process are exponential draws scaled to
fill the window; here they are the same set of gaps for every seed, an
even sample of the exponential distribution, and the seed draws their
order. So the seed moves when the requests come and which shape each
has, never how much work a run holds nor how many requests come close
together (which decides how many the program routes to the host alone
and how many it batches). Before the window (warm-up) the same process
runs open-ended, by exponential gaps; it is abandoned the moment the
parent names the window.

A pool of at most `max_in_flight` workers takes the requests in due
order: the dispatcher sleeps until the next is due and hands it over;
the worker registers the job and follows its evaluation as
`closed.follow` does. A sample carries `t_due`, and `late_s` is how long
after it the register call started. When every worker is busy the
request waits and is sent late, and its latency still counts from when
it was due: no coordinated omission. A request that was never sent by
the drain's end is a sample all the same (`unsent`), so it fails.
"""

from __future__ import annotations

import math
import queue
import random
import threading
import time

import plugins

follow = plugins.load("generators", "closed").follow


def burst_size(arrivals: dict) -> int:
    """How many requests are due at each epoch of the process."""
    if arrivals["process"] == "poisson":
        return 1
    if arrivals["process"] == "bursts":
        return arrivals["burst_size"]
    raise ValueError(f"no arrival process {arrivals['process']!r}")


def window_schedule(arrivals: dict, seconds: float, rng) -> list:
    """Due times in (0, seconds), sorted, as offsets from the window's
    start. The gaps between the epochs (and to the window's two ends)
    are one fixed set for a rate and a length, an even sample of the
    exponential distribution, scaled to fill the window; the seed draws
    their order."""
    size = burst_size(arrivals)
    epochs = round(arrivals["rate_evals_per_s"] * seconds / size)
    gaps = [-math.log(1.0 - (i + 0.5) / (epochs + 1))
            for i in range(epochs + 1)]
    rng.shuffle(gaps)
    scale, t, times = seconds / sum(gaps), 0.0, []
    for gap in gaps[:-1]:
        t += gap * scale
        times.append(t)
    return [t for t in times for _ in range(size)]


def open_ended(arrivals: dict, rng):
    """The same process with no end, for warm-up: offsets from its
    start."""
    size = burst_size(arrivals)
    t = 0.0
    while True:
        t += rng.expovariate(arrivals["rate_evals_per_s"] / size)
        for _ in range(size):
            yield t


def window_shapes(n: int, jobs: list, rng) -> list:
    """Which job shape each of the window's `n` requests has: each shape
    its share of them (largest remainders), in an order drawn from the
    seed. One shape: no draw."""
    if len(jobs) == 1:
        return [0] * n
    total = sum(job["share"] for job in jobs)
    exact = [n * job["share"] / total for job in jobs]
    counts = [int(x) for x in exact]
    for i in sorted(range(len(jobs)), key=lambda i: exact[i] - counts[i],
                    reverse=True)[:n - sum(counts)]:
        counts[i] += 1
    shapes = [i for i, c in enumerate(counts) for _ in range(c)]
    rng.shuffle(shapes)
    return shapes


class Pool:
    """Workers made as they are needed, up to `size`; a request that
    finds them all busy waits in the queue for the first to be free."""

    def __init__(self, size: int, work):
        self.size, self.work = size, work
        self.queue: queue.SimpleQueue = queue.SimpleQueue()
        self.cond = threading.Condition()
        self.threads: list = []
        self.idle = self.unassigned = self.outstanding = 0

    def submit(self, item) -> None:
        with self.cond:
            self.outstanding += 1
            if self.idle:
                self.idle -= 1
            elif len(self.threads) < self.size:
                t = threading.Thread(
                    target=self._worker, args=(len(self.threads),),
                    name=f"open-{len(self.threads)}", daemon=True)
                self.threads.append(t)
                t.start()
            else:
                self.unassigned += 1
            self.queue.put(item)

    def _worker(self, number: int) -> None:
        state: dict = {"number": number}
        while True:
            item = self.queue.get()
            if item is None:
                if "conn" in state:
                    state["conn"].close()
                return
            try:
                self.work(item, state)
            finally:
                with self.cond:
                    self.outstanding -= 1
                    if self.unassigned:
                        self.unassigned -= 1
                    else:
                        self.idle += 1
                    self.cond.notify_all()

    def wait(self, deadline_fn) -> None:
        """Until nothing is outstanding, or the deadline."""
        with self.cond:
            while self.outstanding and time.monotonic() < deadline_fn():
                self.cond.wait(0.25)

    def close(self) -> None:
        for _ in self.threads:
            self.queue.put(None)


def run(spec: dict, control, make_conn) -> list:
    """Drive the schedule until `control` says stop; every request that
    was due is in the returned samples, finished or not."""
    samples: list = []
    traffic = spec["traffic"]
    jobs = spec["jobs"]
    poll_wait = traffic["poll_wait_s"]
    rng = random.Random(spec["seed"])
    offsets = window_schedule(traffic["arrivals"], spec["seconds"], rng)
    shapes = window_shapes(len(offsets), jobs, rng)

    def send(item, state: dict) -> None:
        job_id, shape, t_due = item
        sample = {
            "job_id": job_id, "client": state["number"],
            "template": jobs[shape]["name"], "t_due": t_due,
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
        body = jobs[shape]["body"].replace(b"@@JOB@@", job_id.encode())
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
    prefix = spec["prefix"]

    # Rounds first, as the closed loop has them: that many requests at
    # once, each step of the batch ladder met early in warm-up.
    for r, clients in enumerate(traffic["warmup"].get("rounds", [])):
        for i in range(clients):
            pool.submit((f"{prefix}-w{r}x{i:03d}", i % len(jobs), None))
        pool.wait(control.deadline)

    # The arrivals themselves until the parent names the window.
    start = time.monotonic()
    share = [job["share"] for job in jobs]
    for i, offset in enumerate(open_ended(traffic["arrivals"], rng)):
        while not control.told.is_set() and not control.stopped():
            left = start + offset - time.monotonic()
            if left <= 0:
                break
            control.told.wait(min(left, 1.0))
        if control.told.is_set() or control.stopped():
            break
        shape = rng.choices(range(len(jobs)), share)[0] if len(jobs) > 1 else 0
        pool.submit((f"{prefix}-u{i:06d}", shape, start + offset))

    # The window: the schedule drawn above, from its start.
    if control.window_start is not None:
        for i, (offset, shape) in enumerate(zip(offsets, shapes)):
            t_due = control.window_start + offset
            while True:
                left = t_due - time.monotonic()
                if left <= 0:
                    break
                time.sleep(left)
            pool.submit((f"{prefix}-c{i:06d}", shape, t_due))

    pool.wait(control.deadline)
    pool.close()
    for t in pool.threads:
        t.join(timeout=max(0.0, control.deadline() - time.monotonic()) + 5.0)
    return samples
