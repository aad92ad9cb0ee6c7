#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served dense path still
starts, compiles and places on the accelerator.

    python chip_smoke.py            # on a machine with a TPU; exits 0

One process: the server (`ServerConfig` with the dense factories, the
way `agent -tpu` builds it), its HTTP API on a loopback port, and the
client that drives it all live here, so the process that holds the chip
is the one that reports it. Jobs go in and results come out through the
SDK over HTTP. The fleet (north-star size: 10,000 nodes, 50,000 running
allocations) has no clients behind it, so it is loaded through the raft
log — unheartbeated nodes registered over HTTP would expire in seconds.

Three waves of 60 jobs x 8 allocations (dynamic ports + distinct_hosts)
are registered concurrently. Wave 1 is cold and compiles; wave 2 runs
against wave 1's commits and takes the resident delta path; wave 3 is
the steady one. The run fails unless the chip did the work: every
host-fallback / breaker / dead-letter counter must read 0, the resident
base must sit on the accelerator, and the compiled-program count must
hold still across a steady wave. A plain reference then judges every
committed placement from the final store, and the oracle differential
(kernels/differential.py) runs its twelve seeded clusters on the chip.

Without an accelerator the bare command exits non-zero and prints no
result. `--rehearse` runs the same phases at a tiny size on whatever
backend JAX finds (for debugging the script on a CPU); its summary says
so and is never a chip result. The timings printed are observations
with the device beside them, not claims.

The line before last, `summary: {...}`, carries the sizes, counters,
timings and failed checks. The last line of stdout is the verdict and
nothing else: `{"ok": ..., "device": {"platform", "kind", "count"}}`,
the device as JAX reports it.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import importlib.metadata
import json
import logging
import os
import random
import sys
import time
import uuid

# The whole run, compilation included, must fit the 1200 s contract.
RUN_BUDGET_S = 1100.0
WAVE_NAMES = ("cold, includes compile", "warm, resident delta",
              "warm, steady")
# A concurrent storm fragments raggedly over the batcher's batch
# buckets (4/16/64) and delta-row buckets, so the third wave can still
# meet a bucket no earlier wave compiled. The ladders are finite: after
# the cold wave at most this many further waves can each compile a
# first-seen bucket. A steady wave that compiled is repeated, and only
# a run that never goes flat within the ladder's length is a leak.
MAX_WAVES = 7
FULL_SIZE = {"nodes": 10_000, "allocs_per_node": 5,
             "jobs_per_wave": 60, "allocs_per_job": 8}
REHEARSAL_SIZE = {"nodes": 256, "allocs_per_node": 2,
                  "jobs_per_wave": 12, "allocs_per_job": 4}
# agent -tpu's factory overlay (cli/main.py cmd_agent).
DENSE_FACTORIES = {"service": "service-tpu", "batch": "batch-tpu",
                   "system": "system-tpu"}


def say(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """Run state: the deadline and the failed checks."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.failures: list = []

    def remaining(self) -> float:
        left = RUN_BUDGET_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise TimeoutError(
                f"run budget of {RUN_BUDGET_S:.0f}s exhausted")
        return left

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            say(f"  FAIL: {what}")


def cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def trivial_rtt_us() -> float:
    """Median round trip of a near-empty jitted program: the floor any
    dispatch pays regardless of payload or compute."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def probe(x):
        return x + 1

    probe(jnp.float32(0)).block_until_ready()  # compile
    samples = []
    for i in range(21):
        t0 = time.perf_counter()
        probe(jnp.float32(i)).block_until_ready()
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples) * 1e6)


def seeded_uuid(rng: random.Random) -> str:
    return str(uuid.UUID(int=rng.getrandbits(128), version=4))


def load_fleet(server, rng: random.Random, size: dict) -> None:
    """The north-star fleet (BASELINE.json configs[3]), through the
    raft log: identical mock nodes, each carrying running filler
    allocations."""
    from nomad_tpu import mock
    from nomad_tpu.structs import consts

    filler = mock.job()
    filler.id = "filler"
    filler.task_groups[0].tasks[0].resources.networks = []
    for _ in range(size["nodes"]):
        node = mock.node()
        node.id = seeded_uuid(rng)
        node.secret_id = seeded_uuid(rng)
        node.compute_class()
        server.log.apply("node_register", {"node": node})
        fills = []
        for _ in range(size["allocs_per_node"]):
            alloc = mock.alloc()
            alloc.id = seeded_uuid(rng)
            alloc.node_id = node.id
            alloc.job_id = filler.id
            alloc.job = filler
            alloc.desired_status = consts.ALLOC_DESIRED_RUN
            alloc.client_status = consts.ALLOC_CLIENT_RUNNING
            for tr in alloc.task_resources.values():
                tr.cpu = rng.choice([50, 100])
                tr.memory_mb = rng.choice([64, 128])
                tr.networks = []
            alloc.resources = None
            fills.append(alloc)
        if fills:
            server.log.apply("alloc_update", {"allocs": fills})


def make_job(job_id: str, count: int):
    """One task group, `count` allocations, two dynamic ports each,
    distinct_hosts (the north-star job, BASELINE.json configs[3])."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Constraint, consts

    job = mock.job()
    job.id = job.name = job_id
    job.type = "service"
    tg = job.task_groups[0]
    tg.count = count
    tg.constraints.append(
        Constraint(operand=consts.CONSTRAINT_DISTINCT_HOSTS))
    tg.tasks[0].resources.cpu = 20
    tg.tasks[0].resources.memory_mb = 16
    return job


def register_and_wait(addr: str, job, deadline: float):
    """What `nomad run` does: register over HTTP, then long-poll the
    evaluation until it is terminal. Returns (eval_id, status, done)."""
    from nomad_tpu.api.client import Client
    from nomad_tpu.structs import consts

    terminal = (consts.EVAL_STATUS_COMPLETE, consts.EVAL_STATUS_FAILED,
                consts.EVAL_STATUS_CANCELLED)
    client = Client(addr)
    try:
        eval_id = client.jobs.register(job)
        index = None
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(
                    f"eval {eval_id} of job {job.id} not terminal in time")
            ev, index = client.evaluations.info(
                eval_id, index=index, wait=min(left, 30.0))
            if ev.status in terminal:
                return eval_id, ev.status, time.monotonic()
    finally:
        client.pool.close()


def prom_counter(text: str, suffix: str) -> float:
    """Value of the counter whose family name ends in `suffix`; a
    counter nobody incremented is absent from the exposition, so 0."""
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.partition(" ")
        if name.endswith(suffix):
            return float(value)
    return 0.0


def read_counters(client) -> dict:
    """Every countable route off the device, read from outside: over
    /v1/agent/self and /v1/metrics."""
    info = client.agent.self()
    prom = client.get_raw("/v1/metrics").decode()
    stats = info["stats"]
    breaker = stats["admission"]["breaker"]

    def pick(block: dict, *keys) -> dict:
        return {k: block[k] for k in keys}

    return {
        "batcher": pick(
            info["placement_batcher"], "dispatches", "batched_requests",
            "compact_dispatches", "base_uploads", "base_delta_updates",
            "sharded_bases", "unsharded_fallbacks", "jit_cache_size"),
        "pipeline": pick(
            stats["dispatch_pipeline"], "enabled", "batches",
            "largest_batch", "routed_host", "breaker_routed",
            "prefetch_failures", "plan_conflicts", "nacked"),
        "broker": pick(
            stats["broker"], "dead_lettered", "shed", "expired", "nacked",
            "nack_timeouts"),
        "breaker": pick(breaker, "state", "trips", "failures", "rejected"),
        "scheduler": {
            name: int(prom_counter(prom, f"_scheduler_{name}_total"))
            for name in ("host_fallback", "gang_host_fallback",
                         "breaker_rejected", "gang_breaker_rejected",
                         "small_route_host")},
    }


def check_device_did_the_work(smoke: Smoke, c: dict, where: str) -> None:
    chk = smoke.check
    sched, pipe = c["scheduler"], c["pipeline"]
    for name in ("host_fallback", "gang_host_fallback",
                 "breaker_rejected", "gang_breaker_rejected"):
        chk(sched[name] == 0, f"{where}: scheduler.{name} = {sched[name]}")
    chk(pipe["breaker_routed"] == 0,
        f"{where}: pipeline breaker_routed = {pipe['breaker_routed']}")
    chk(pipe["prefetch_failures"] == 0,
        f"{where}: pipeline prefetch_failures = "
        f"{pipe['prefetch_failures']}")
    chk(c["breaker"]["state"] == "closed" and c["breaker"]["failures"] == 0
        and c["breaker"]["rejected"] == 0,
        f"{where}: breaker {c['breaker']}")
    chk(c["broker"]["dead_lettered"] == 0,
        f"{where}: broker dead_lettered = {c['broker']['dead_lettered']}")
    chk(c["batcher"]["unsharded_fallbacks"] == 0,
        f"{where}: batcher unsharded_fallbacks = "
        f"{c['batcher']['unsharded_fallbacks']}")


def run_wave(smoke: Smoke, addr: str, client, number: int, size: dict,
             before: dict) -> tuple:
    from nomad_tpu.structs import consts

    name = (WAVE_NAMES[number - 1] if number <= len(WAVE_NAMES) else
            "warm, steady again: the previous wave compiled a "
            "first-seen bucket")
    n_jobs, count = size["jobs_per_wave"], size["allocs_per_job"]
    jobs = [make_job(f"smoke-w{number}-j{j:02d}", count)
            for j in range(n_jobs)]
    deadline = time.monotonic() + smoke.remaining()
    start = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(n_jobs) as pool:
        futures = [pool.submit(register_and_wait, addr, job, deadline)
                   for job in jobs]
        results = [f.result() for f in futures]
    wall = max(done for _, _, done in results) - start
    statuses = [status for _, status, _ in results]
    smoke.check(all(s == consts.EVAL_STATUS_COMPLETE for s in statuses),
                f"wave {number}: eval statuses "
                f"{sorted(set(statuses))}, want all complete")

    placed = 0
    for job in jobs:
        stubs, _ = client.jobs.allocations(job.id)
        live = [a for a in stubs
                if a["desired_status"] == consts.ALLOC_DESIRED_RUN]
        placed += len(live)
        smoke.check(len(live) == count,
                    f"wave {number}: job {job.id} has {len(live)} of "
                    f"{count} allocations")
    after = read_counters(client)

    def delta(group: str, key: str):
        return after[group][key] - before[group][key]

    wave = {
        "wave": number, "name": name, "wall_s": round(wall, 3),
        "evals": len(results), "allocs": placed,
        "device_requests": delta("batcher", "batched_requests"),
        "dispatches": delta("batcher", "dispatches"),
        "compact_dispatches": delta("batcher", "compact_dispatches"),
        "base_uploads": delta("batcher", "base_uploads"),
        "base_delta_updates": delta("batcher", "base_delta_updates"),
        "routed_host": delta("pipeline", "routed_host"),
        "small_route_host": delta("scheduler", "small_route_host"),
        "plan_conflicts": delta("pipeline", "plan_conflicts"),
        "nacked": delta("broker", "nacked"),
        "nack_timeouts": delta("broker", "nack_timeouts"),
        "jit_cache_size": after["batcher"]["jit_cache_size"],
    }
    say(f"wave {number} ({name}): {wall:.3f} s wall-clock, "
        f"{placed}/{n_jobs * count} allocations, "
        f"{wave['device_requests']} requests in {wave['dispatches']} "
        f"device dispatches, routed_host {wave['routed_host']}, "
        f"nacked {wave['nacked']} (timer {wave['nack_timeouts']}), "
        f"jit_cache_size {wave['jit_cache_size']}")
    smoke.check(wave["device_requests"] > 0,
                f"wave {number}: no eval was placed by the device")
    check_device_did_the_work(smoke, after, f"wave {number}")
    return wave, after, jobs


def judge_from_store(smoke: Smoke, state, jobs: list) -> dict:
    """The plain reference: nothing of the dense path. Every node a
    wave touched must fit its live allocations (resources, bandwidth,
    exact ports), be ready, and hold at most one allocation of a job."""
    from nomad_tpu.structs import consts
    from nomad_tpu.structs.funcs import allocs_fit

    touched = set()
    judged = 0
    for job in jobs:
        live = [a for a in state.allocs_by_job(job.id)
                if not a.terminal_status()]
        judged += len(live)
        nodes = [a.node_id for a in live]
        smoke.check(len(set(nodes)) == len(nodes),
                    f"reference: job {job.id} shares a node between "
                    f"allocations (distinct_hosts)")
        touched.update(nodes)
    for node_id in sorted(touched):
        node = state.node_by_id(node_id)
        smoke.check(
            node is not None and node.status == consts.NODE_STATUS_READY
            and not node.drain,
            f"reference: chosen node {node_id} is not ready")
        if node is None:
            continue
        fit, dimension, _ = allocs_fit(
            node, state.allocs_by_node_terminal(node_id, False))
        smoke.check(fit, f"reference: node {node_id} over-committed "
                         f"({dimension})")
    return {"allocs_judged": judged, "nodes_judged": len(touched)}


def check_residency(smoke: Smoke, devices, batcher_stats: dict) -> list:
    """Where the newest resident base actually sits."""
    from nomad_tpu.scheduler.batcher import get_batcher

    occupancy = get_batcher().shard_occupancy()
    platform = devices[0].platform
    smoke.check(bool(occupancy), "no resident base on any device")
    smoke.check(all(o["platform"] == platform for o in occupancy),
                f"resident base not on {platform} devices: {occupancy}")
    if len(devices) == 1:
        smoke.check(batcher_stats["sharded_bases"] == 0
                    and len(occupancy) == 1,
                    f"one device but sharded_bases = "
                    f"{batcher_stats['sharded_bases']}: {occupancy}")
    else:
        smoke.check(batcher_stats["sharded_bases"] >= 1,
                    f"{len(devices)} devices but sharded_bases = 0")
        smoke.check(len(occupancy) == len(devices)
                    and all(o["rows"] > 0 for o in occupancy),
                    f"base rows not on every device: {occupancy}")
    return occupancy


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny size on whatever backend JAX finds; not a chip result")
    args = ap.parse_args(argv)
    smoke = Smoke(time.monotonic())

    # ---- device first
    import jax
    import jaxlib

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu}
    say(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    say(f"versions: {versions}")
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: no accelerator: the default JAX backend is "
              f"{device['platform']!r}, not 'tpu'", file=sys.stderr)
        return 2
    if args.rehearse:
        say(f"REHEARSAL at a tiny size on platform "
            f"{device['platform']!r}: not a chip result")
    size = REHEARSAL_SIZE if args.rehearse else FULL_SIZE

    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s [%(levelname)s] %(name)s: %(message)s")

    # Importing the placement programs places the compile cache.
    import nomad_tpu.ops.binpack  # noqa: F401
    from nomad_tpu.api.client import Client
    from nomad_tpu.api.http import HTTPServer
    from nomad_tpu.kernels.differential import run_differential
    from nomad_tpu.scheduler.batcher import get_batcher
    from nomad_tpu.server import Server, ServerConfig

    cache_dir = jax.config.jax_compilation_cache_dir
    cache_before = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({cache_before} entries; "
        f"JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'not set'})")
    rtt_us = trivial_rtt_us()
    say(f"trivial jitted round trip on {device['platform']}: "
        f"{rtt_us:.1f} us (median of 21)")

    # ---- the server as it ships: agent -tpu's ServerConfig (cli/main.py
    # cmd_agent: dense factories, num_schedulers 2, all else default).
    server = Server(ServerConfig(
        num_schedulers=2, scheduler_factories=dict(DENSE_FACTORIES)))
    http = None
    client = None
    try:
        server.start()
        http = HTTPServer(server, host="127.0.0.1", port=0)
        http.start()
        client = Client(http.addr)
        say(f"server up: {http.addr}, factories {DENSE_FACTORIES}")

        t_load = time.monotonic()
        load_fleet(server, random.Random(args.seed), size)
        load_s = time.monotonic() - t_load
        n_resident = size["nodes"] * size["allocs_per_node"]
        say(f"fleet loaded through the raft log: {size['nodes']} nodes, "
            f"{n_resident} running allocations, {load_s:.1f} s")
        nodes_seen, _ = client.nodes.list()
        smoke.check(len(nodes_seen) == size["nodes"],
                    f"/v1/nodes lists {len(nodes_seen)} nodes")

        # ---- three waves over HTTP
        # The batcher is created by the first dense eval; create it
        # now so /v1/agent/self carries its block and wave 1's deltas
        # have a zero to start from.
        get_batcher()
        counters = read_counters(client)
        waves, jobs = [], []
        for number in range(1, MAX_WAVES + 1):
            wave, counters, wave_jobs = run_wave(
                smoke, http.addr, client, number, size, counters)
            waves.append(wave)
            jobs.extend(wave_jobs)
            if (number >= len(WAVE_NAMES) and wave["jit_cache_size"]
                    == waves[-2]["jit_cache_size"]):
                break
        batcher = counters["batcher"]
        smoke.check(waves[0]["jit_cache_size"] > 0,
                    "jit_cache_size is 0 after the cold wave")
        smoke.check(
            waves[-1]["jit_cache_size"] == waves[-2]["jit_cache_size"],
            f"jit_cache_size never held still across a steady wave: "
            f"{[w['jit_cache_size'] for w in waves]}")
        smoke.check(batcher["dispatches"] > 0
                    and batcher["compact_dispatches"] > 0,
                    f"no compact device dispatch: {batcher}")
        smoke.check(batcher["base_uploads"] >= 1, "no base upload")
        by_wave_2 = (waves[0]["base_delta_updates"]
                     + waves[1]["base_delta_updates"])
        smoke.check(by_wave_2 > 0,
                    "no resident delta update by the end of wave 2")
        occupancy = check_residency(smoke, devices, batcher)
        say(f"resident base: {occupancy}")
        say(f"eval-lifecycle stages on {device['platform']}, host clock, "
            f"all waves (the cold one included):")
        for stage, row in client.agent.self()["stats"]["trace"].items():
            say(f"  {stage}: n={row['count']} p50={row['p50_ms']} "
                f"p99={row['p99_ms']} max={row['max_ms']} ms")

        # ---- the plain reference, from the final store
        reference = judge_from_store(smoke, server.fsm.state, jobs)
        say(f"reference: {reference['allocs_judged']} allocations on "
            f"{reference['nodes_judged']} nodes judged")
        smoke.check(
            reference["allocs_judged"]
            == len(waves) * size["jobs_per_wave"] * size["allocs_per_job"],
            f"store holds {reference['allocs_judged']} live wave "
            f"allocations")

        # ---- the oracle differential on the same device
        smoke.remaining()
        diff = run_differential("greedy")
        say(f"differential(greedy): {diff['cases']} cases, "
            f"{len(diff['violations'])} violations")
        for violation in diff["violations"]:
            smoke.check(False, f"differential: {violation}")
        final = read_counters(client)
        smoke.check(
            final["batcher"]["dispatches"] > batcher["dispatches"],
            "the differential dispatched nothing to the device")
        check_device_did_the_work(smoke, final, "after the differential")

        # The busiest device's peak; the CPU backend reports none.
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        peak_bytes = max((p for p in peaks if p is not None), default=None)
        cache_after = cache_entries(cache_dir)
        say(f"peak_bytes_in_use on {device['kind']}: {peak_bytes}")
        say(f"compile cache entries: {cache_before} -> {cache_after}")
        summary = {
            "ok": not smoke.failures,
            "device": device,
            "rehearsal": args.rehearse,
            "versions": versions,
            "seed": args.seed,
            "sizes": {**size, "resident_allocs": n_resident,
                      "waves": len(waves)},
            "fleet_loaded_through": "raft log",
            "fleet_load_s": round(load_s, 1),
            "waves": waves,
            "counters": final,
            "resident_base": occupancy,
            "reference": reference,
            "differential": {"kernel": "greedy", "cases": diff["cases"],
                             "violations": len(diff["violations"])},
            "trivial_rtt_us": round(rtt_us, 1),
            "peak_bytes_in_use": peak_bytes,
            "compile_cache": {"dir": cache_dir,
                              "entries_before": cache_before,
                              "entries_after": cache_after},
            "elapsed_s": round(time.monotonic() - smoke.t0, 1),
            "failures": smoke.failures,
        }
    finally:
        if client is not None:
            client.pool.close()
        if http is not None:
            http.stop()
        server.shutdown()
    say(f"summary: {json.dumps(summary)}")
    say(json.dumps({"ok": summary["ok"], "device": device}))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
