#!/usr/bin/env python3
"""The load generator: a process of its own that speaks only HTTP.

`run.py` starts it, writes one JSON line of parameters to its stdin, and
later one line `{"window_start": t, "stop_at": t}` (time.monotonic(),
which is one clock for every process of a Linux machine). The generator
named by the traffic file's `kind` (`generators/<kind>.py`) drives the
server until `stop_at`, lets what is in flight finish for `drain_s`,
reads every evaluation registered in the window back, and writes the
samples as one JSON line to stdout. It never imports the program or JAX.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import httpc  # noqa: E402
import plugins  # noqa: E402
import stats  # noqa: E402

READBACK_THREADS = 8


class Control:
    """When to stop, as the parent tells it. Until told, run on (bounded
    by `max_life_s`, so an orphan ends)."""

    def __init__(self, max_life_s: float, drain_s: float):
        self.born = time.monotonic()
        self.max_life_s, self.drain_s = max_life_s, drain_s
        self.window_start = None
        self.stop_at = None
        self.told = threading.Event()   # set once the window is named

    def stopped(self) -> bool:
        now = time.monotonic()
        if self.stop_at is not None and now >= self.stop_at:
            return True
        return now - self.born >= self.max_life_s

    def deadline(self) -> float:
        if self.stop_at is not None:
            return self.stop_at + self.drain_s
        return self.born + self.max_life_s

    def listen(self, stream) -> None:
        line = stream.readline()
        if line:
            msg = json.loads(line)
            self.window_start = msg["window_start"]
            self.stop_at = msg["stop_at"]
            self.told.set()


def read_back(samples: list, make_conn) -> None:
    """Point 1 of `correct`: each evaluation's status as the API now
    lists it, and how many of its allocations read
    `desired_status == run`."""
    todo = [s for s in samples if s["eval_id"]]
    lock = threading.Lock()
    conn = make_conn()
    try:
        evals, _ = conn.request("GET", "/v1/evaluations")
    finally:
        conn.close()
    status = {ev["id"]: ev["status"] for ev in evals}
    for s in todo:
        s["read_status"] = status.get(s["eval_id"], "missing")

    def worker():
        conn = make_conn()
        try:
            while True:
                with lock:
                    if not todo:
                        return
                    s = todo.pop()
                try:
                    stubs, _ = conn.request(
                        "GET", f"/v1/evaluation/{s['eval_id']}/allocations")
                    s["read_allocs"] = sum(
                        1 for a in stubs if a["desired_status"] == "run")
                except Exception as e:  # noqa: BLE001 - judged as missing
                    s["read_error"] = repr(e)
        finally:
            conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(READBACK_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    # One body for each job shape; `job_body` is the first shape's, which
    # is all a generator of one shape reads.
    for shape in spec["jobs"]:
        shape["body"] = json.dumps({"job": dict(
            shape["job"], id="@@JOB@@", name="@@JOB@@")}).encode()
    spec["job_body"] = spec["jobs"][0]["body"]
    traffic = spec["traffic"]
    control = Control(spec["max_life_s"], traffic["drain_s"])
    threading.Thread(target=control.listen, args=(sys.stdin,),
                     daemon=True).start()

    def make_conn():
        return httpc.Conn(spec["addr"])

    samples = plugins.load("generators", traffic["kind"]).run(spec, control, make_conn)
    t_drained = time.monotonic()
    # The latency population (registered in the window) and the
    # throughput population (seen terminal in it).
    t0, t1 = control.window_start, control.stop_at
    window = [s for s in samples if t0 is not None and (
        t0 <= stats.due(s) < t1
        or (s["t_terminal"] is not None and t0 <= s["t_terminal"] < t1))]
    read_back(window, make_conn)
    if "jax" in sys.modules or "nomad_tpu" in sys.modules:
        print("loadgen: the generator imported jax or the program",
              file=sys.stderr)
        return 3
    json.dump({"samples": samples, "t_drained": t_drained,
               "t_read_back": time.monotonic(),
               "window_start": control.window_start,
               "stop_at": control.stop_at}, sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
