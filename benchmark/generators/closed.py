"""Closed loop: each client registers a job, follows its evaluation with
blocking queries until it is terminal, and registers the next at once.
Parameters (the traffic file): `clients`, `poll_wait_s`, `drain_s`, and
`warmup.rounds`: before the loop, one round for each entry, in which that
many clients register one job each at once and wait for it. The rounds
show the program each step of its batch ladder (4, 16, 64) early in
warm-up, which a loop in steady state meets only now and then."""

from __future__ import annotations

import threading
import time

TERMINAL = ("complete", "failed", "cancelled")


def follow(conn, eval_id: str, poll_wait: float, deadline_fn):
    """Long-poll the evaluation until terminal. (status, time seen)."""
    index = None
    while True:
        left = deadline_fn() - time.monotonic()
        if left <= 0:
            return "unfinished", None
        path = f"/v1/evaluation/{eval_id}"
        if index is not None:
            path += f"?index={index}&wait={min(left, poll_wait):.3f}"
        ev, index = conn.request("GET", path)
        if ev["status"] in TERMINAL:
            return ev["status"], time.monotonic()


def client_loop(number: int, spec: dict, control, make_conn, samples: list,
                tag: str = "c", once: bool = False):
    conn = make_conn()
    poll_wait = spec["traffic"]["poll_wait_s"]
    last_terminal = None
    n = 0
    try:
        while not control.stopped() and not (once and n):
            job_id = f"{spec['prefix']}-{tag}{number:03d}-{n:06d}"
            n += 1
            body = spec["job_body"].replace(b"@@JOB@@", job_id.encode())
            t_register = time.monotonic()
            sample = {
                "job_id": job_id, "client": number, "t_register": t_register,
                "late_s": (None if last_terminal is None
                           else t_register - last_terminal),
                "eval_id": None, "t_registered": None, "t_terminal": None,
                "status": "register_error"}
            samples.append(sample)
            try:
                out, _ = conn.request("PUT", "/v1/jobs", body)
            except Exception as e:  # noqa: BLE001 - counted, loop goes on
                sample["error"] = repr(e)
                last_terminal = None
                time.sleep(0.05)
                continue
            sample["t_registered"] = time.monotonic()
            sample["eval_id"] = out["eval_id"]
            try:
                status, seen = follow(conn, out["eval_id"], poll_wait,
                                      control.deadline)
            except Exception as e:  # noqa: BLE001
                sample["error"] = repr(e)
                status, seen = "follow_error", None
            sample["status"], sample["t_terminal"] = status, seen
            last_terminal = seen
    finally:
        conn.close()


def run(spec: dict, control, make_conn) -> list:
    """Drive the clients until `control` says stop; every eval they
    registered is in the returned samples, finished or not."""
    samples: list = []

    def wave(clients: int, tag: str, once: bool) -> None:
        threads = [threading.Thread(
            target=client_loop,
            args=(i, spec, control, make_conn, samples, tag, once),
            name=f"client-{tag}{i}", daemon=True) for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    traffic = spec["traffic"]
    for r, clients in enumerate(traffic["warmup"].get("rounds", [])):
        wave(clients, f"w{r}x", once=True)
    wave(traffic["clients"], "c", once=False)
    return samples
