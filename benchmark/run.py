#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it starts the JAX backend (and exits non-zero
with no result line unless the platform is `tpu`), builds the server as
`agent -tpu` ships it, serves HTTP on loopback, loads the fleet from
`--seed`, starts the load generator as a child that speaks only HTTP,
warms up on the cell's own traffic, measures for `--seconds`, drains,
judges the final store against the plain reference, and prints one JSON
object as the last line of its standard output.

A cell is data: `BENCHMARK.json` names a configuration
(`configs/<name>.json`), a traffic mix (`traffic/<name>.json`, whose
`kind` names `generators/<kind>.py`) and the per-layer metrics
(`metrics/<name>.json`, whose `reader` names `readers/<reader>.py`); a
configuration may name checks of its own (`checks/<name>.py`).

`--rehearse` runs the same phases at a tiny size on whatever backend JAX
finds; every line it prints starts with `REHEARSAL`, so the last line is
never a result the driver could read.
"""

from __future__ import annotations

import time

T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import counters  # noqa: E402
import fleet  # noqa: E402
import httpc  # noqa: E402
import plugins  # noqa: E402
import reference  # noqa: E402
import stats  # noqa: E402
import store_dump  # noqa: E402
import tracereduce  # noqa: E402

# A run has to end inside the contract's 360 s; the generator's own life
# is bounded a little above it so that an orphan ends by itself.
GENERATOR_MAX_LIFE_S = 400.0
READ_BACK_ALLOWANCE_S = 60.0
WARMUP_POLL_S = 0.5
# A run whose warm-up ends with nothing served by the device has no
# window worth measuring: it says so and leaves with this code, and a
# teardown that will not end is cut after LEAVE_S.
EXIT_HOPELESS = 4
LEAVE_S = 20.0


def process_start() -> float:
    """When this process started, on time.monotonic()'s clock (Linux:
    CLOCK_MONOTONIC counts from boot, as /proc/<pid>/stat's start time
    does). Falls back to when this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        started = ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= T_IMPORT - started < 10.0:
            return started
    except (OSError, ValueError, IndexError):
        pass
    return T_IMPORT


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def trivial_rtt_us() -> float:
    """Median round trip of a near-empty jitted program: the floor any
    dispatch pays (copy of chip_smoke.trivial_rtt_us)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return x + 1

    probe(jnp.float32(0)).block_until_ready()
    samples = []
    for i in range(21):
        t0 = time.perf_counter()
        probe(jnp.float32(i)).block_until_ready()
        samples.append(time.perf_counter() - t0)
    return stats.percentile(samples, 0.5) * 1e6


def span_snapshot() -> dict:
    from nomad_tpu import trace

    recorder = trace.get_recorder()
    return {stage: recorder.stage_buckets(stage)
            for stage in recorder.stage_stats()}


def resident_readback(snapshot, datacenters: list) -> dict:
    """The resident base as the device holds it for `snapshot`: bring it
    up to the snapshot the way the dispatch pipeline does before a batch
    (prefetch), then copy the utilisation, bandwidth and free-port
    columns back. Row order is the program's node universe."""
    from nomad_tpu.models.matrix import (prefetch_cluster_base,
                                         universe_nodes_cached)
    from nomad_tpu.scheduler.batcher import get_batcher

    view, _kind = prefetch_cluster_base(snapshot, list(datacenters))
    if view is None:
        return None
    batcher = get_batcher()
    batcher.prefetch_base(view)
    with batcher._lock:
        dev = batcher._device_bases.get(view.base_token)
    if dev is None:
        return None
    nodes, _by_dc, _sig = universe_nodes_cached(snapshot, list(datacenters))
    n = len(nodes)
    return {"node_ids": [node.id for node in nodes],
            "util": np.asarray(dev[2])[:n],
            "bw_used": np.asarray(dev[4])[:n],
            "ports_free": np.asarray(dev[5])[:n],
            "platform": next(iter(dev[2].devices())).platform}


def resident_mismatches(readback: dict, store: dict, sums: dict,
                        port_range) -> int:
    """Rows in which what the device holds differs from the float64 sums
    the reference takes from the final store. Integers under 2^24 are
    exact in float32, so the limit is 0."""
    row = {node_id: i for i, node_id in enumerate(store["node_ids"])}
    order = np.array([row[node_id] for node_id in readback["node_ids"]])
    lo, hi = port_range
    free = (hi - lo) - sums["dyn_ports_used"][order]
    wrong = (np.asarray(readback["util"], np.float64)
             != sums["util"][order]).any(axis=1)
    wrong |= np.asarray(readback["bw_used"], np.float64) \
        != sums["bw_used"][order]
    wrong |= np.asarray(readback["ports_free"], np.float64) != free
    return int(wrong.sum()) + abs(len(store["node_ids"]) - len(order))


class Run:
    """How one run prints its lines, and the checks it has made."""

    def __init__(self, rehearse: bool, mark: str = ""):
        self.prefix = ("REHEARSAL " if rehearse else "") + mark
        self.checks: list = []
        self.no_result = ""     # why the run ended without one

    def say(self, msg: str) -> None:
        print(self.prefix + msg, flush=True)

    def check(self, name: str, value, limit, ok: bool) -> None:
        self.checks.append((name, value, limit, ok))
        self.say(f"check {name}: value={value} limit={limit} "
                 f"{'ok' if ok else 'FAIL'}")

    def correct(self) -> bool:
        return all(ok for *_, ok in self.checks)

    def compared(self) -> dict:
        """Each number compared beside its limit, the failed ones first:
        the driver's record keeps only the end of what a run printed."""
        rows = sorted(self.checks, key=lambda row: row[3])
        return {name: {"value": value, "limit": limit, "ok": ok}
                for name, value, limit, ok in rows}

    def say_compared(self) -> None:
        """The same as the last lines of standard error, the failed ones
        last."""
        for name, row in reversed(list(self.compared().items())):
            print(f"{self.prefix}{name} {row['value']} limit "
                  f"{row['limit']}{'' if row['ok'] else ' FAIL'}",
                  file=sys.stderr)
        if self.no_result:
            print(self.prefix + self.no_result, file=sys.stderr)
        sys.stderr.flush()


class HopelessRun(Exception):
    """Warm-up reached its bound and the device had served nothing."""


def leave_within(seconds: float, code: int) -> None:
    """From now the process has `seconds` to end by itself; then it is
    ended, whatever thread of the server will not join."""
    def cut():
        time.sleep(seconds)
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(code)

    threading.Thread(target=cut, name="leave", daemon=True).start()


def warm_up(run: Run, conn, rule: dict, child) -> dict:
    """The cell's own traffic until the device has served `min_requests`
    evaluations and the compiled-program count has since its last change
    been still for `still_s` while `still_dispatches` device dispatches
    completed; no sooner than `min_s`, bounded by `max_s`. Counting work
    and not only seconds is for the run that compiles: while a program
    compiles the count is still too, but nothing completes. A warm-up
    that reaches its bound goes on into the window if the device served
    anything in it (`bounded`), and is a HopelessRun if it served
    nothing: the program cannot run this cell on the device, and a
    window of it would take minutes to say so."""
    start = time.monotonic()
    last_size, last_change, dispatches_then = -1, start, 0
    served_then = counters.read_counters(conn).get(
        "batcher.batched_requests", 0)
    while True:
        time.sleep(WARMUP_POLL_S)
        if child.poll() is not None:
            raise RuntimeError(
                f"the load generator exited during warm-up "
                f"(code {child.returncode})")
        now = time.monotonic()
        c = counters.read_counters(conn)
        size = c.get("batcher.jit_cache_size", 0)
        dispatches = c.get("batcher.dispatches", 0)
        if size != last_size:
            last_size, last_change, dispatches_then = size, now, dispatches
        still = now - last_change
        if (now - start >= rule["min_s"] and size > 0
                and still >= rule["still_s"]
                and dispatches - dispatches_then >= rule["still_dispatches"]
                and c.get("batcher.batched_requests", 0)
                >= rule["min_requests"]):
            return {"seconds": now - start, "programs": size, "bounded": False}
        if now - start >= rule["max_s"]:
            run.say(f"warm-up hit its bound of {rule['max_s']} s with "
                    f"{size} programs, still for {still:.1f} s")
            if c.get("batcher.batched_requests", 0) <= served_then:
                raise HopelessRun(
                    f"warm-up reached its bound of {rule['max_s']} s and "
                    f"the device served no request in it "
                    f"(batcher.batched_requests still {served_then}, "
                    f"{size} programs)")
            return {"seconds": now - start, "programs": size, "bounded": True}


def end_to_end_value(name: str, ctx: dict):
    if name == "setup_s":
        return ctx["setup_s"]
    if name == "placed_allocs_per_s":
        return ctx["placed_allocs"] / ctx["seconds"]
    # `place_due_*`: the same arithmetic under the name the open-loop
    # cells report it by (a bound is a metric's, and theirs is wider)
    m = re.fullmatch(r"place(?:_due)?_p(\d+)_ms", name)
    if m and ctx["client"]["place_ms"]:
        return stats.percentile(ctx["client"]["place_ms"], int(m.group(1)) / 100)
    return None


def reported(entries: list, cell: str, value_of) -> dict:
    """{name: {value, unit}} of the metrics of BENCHMARK.json's `entries`
    that apply to `cell` and have something to read."""
    out = {}
    for entry in entries:
        if applies(entry, cell):
            value = value_of(entry)
            if value is not None:
                out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def per_layer_value(entry: dict, ctx: dict):
    spec = load_json(os.path.join(HERE, "metrics", f"{entry['name']}.json"))
    return plugins.load("readers", spec["reader"]).read(spec["args"], ctx)


def start_generator(addr: str, cell: dict, config: dict, traffic: dict,
                    seed: int, seconds: float):
    """The child gets every job shape of the configuration with its
    share, and the seed and the window's length, so that a generator can
    draw its whole schedule before the window."""
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    jobs = [{"name": spec["name"], "share": spec["share"],
             "job": fleet.job_template(spec)}
            for spec in fleet.job_specs(config)]
    tell(child, {"addr": addr, "jobs": jobs, "traffic": traffic,
                 "prefix": f"{cell['traffic']}-{seed}",
                 "seed": seed, "seconds": seconds,
                 "max_life_s": GENERATOR_MAX_LIFE_S})
    return child


def tell(child, message: dict) -> None:
    child.stdin.write(json.dumps(message) + "\n")
    child.stdin.flush()


def collect(child, traffic: dict) -> dict:
    """The drain: the generator lets what is in flight finish, reads the
    window's evaluations back, and hands its samples over."""
    try:
        out, _ = child.communicate(
            timeout=traffic["drain_s"] + READ_BACK_ALLOWANCE_S)
    except subprocess.TimeoutExpired:
        child.kill()
        child.communicate()
        raise RuntimeError("the load generator did not finish its drain")
    if child.returncode != 0:
        raise RuntimeError(f"the load generator exited {child.returncode}")
    return json.loads(out.strip().splitlines()[-1])


def trace_stretch(spec: dict, seconds: float, trace_dir: str,
                  rehearse: bool, dispatches, drained, out: dict) -> None:
    """Profile a steady stretch inside the window; its (start, end) on
    time.monotonic() go into `out`. Device events only: the Python
    tracer would slow the host, which is what the window measures. Runs
    on a thread of its own: writing a long trace out can outlast the
    window, and the closing counter reading must not wait for it.

    The stretch is `spec["seconds"]` long. Where no device dispatch
    completed in it (`dispatches()` did not move: thousand-allocation
    evaluations reach the device every ten seconds or so), it goes on, a
    second at a time, until one has, or the window is about to end: a
    stretch in which nothing ran on the device says nothing (and one
    that ends on a dispatch reads a little busier than the window was).
    The trace is kept short because writing it out takes some four
    minutes for each second the device was busy in it.

    Where that is long (thousand-allocation programs), the traffic file
    says `"write_out": "after_drain"`: the stretch is then the window's
    last `spec["seconds"]` and goes on through the drain (`drained` is
    set when the generator has handed its samples over), so that the
    write-out runs when the closing readings are taken and nothing of the
    window is in flight any more. A write-out inside the window loads
    the host that the window measures, and evaluations that wait it out
    can fail for no fault of the program."""
    import jax

    lead = min(spec["start_s"], seconds / 4)
    length = max(0.5, min(spec["seconds"], seconds - 2 * lead))
    after_drain = spec.get("write_out") == "after_drain"
    time.sleep(seconds - length if after_drain else lead)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2 if rehearse else 1
    options.enable_hlo_proto = False
    t_a = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    seen = dispatches()
    if after_drain:
        drained.wait()
    else:
        time.sleep(length)
        while (dispatches() == seen
               and time.monotonic() - t_a < seconds - 2 * lead):
            time.sleep(1.0)
    t_b = time.monotonic()
    jax.profiler.stop_trace()
    out["stretch"] = (t_a, t_b)
    out["written_s"] = time.monotonic() - t_b


def judge(run: Run, config: dict, registered: list, snapshot, before: dict,
          after: dict, seed: int, platform: str) -> int:
    """The five comparisons of `correct` and the configuration's own
    checks, each number printed beside its limit. Returns `failed`."""
    shapes = {spec["name"]: spec for spec in fleet.job_specs(config)}
    first = next(iter(shapes))

    def shape_of(s):
        # a generator of one shape does not stamp its samples
        return shapes[s.get("template", first)]

    def short(s):
        return (s["status"] != "complete"
                or s.get("read_status") != "complete"
                or s.get("read_allocs") != shape_of(s)["count"])

    # 1. every acknowledged evaluation reads back complete, with all of
    # its allocations in `desired run`
    failed = sum(1 for s in registered if short(s))
    run.check("evals_not_complete_with_all_allocs", failed, 0, failed == 0)
    run.check("evals_registered_in_window", len(registered), ">0",
              len(registered) > 0)

    # 2. the plain reference judges the final store
    port_range = config["fleet"]["dynamic_port_range"]
    store = store_dump.dump_store(snapshot)
    window_jobs = {
        s["job_id"]: {"count": shape["count"],
                      "distinct_hosts": shape["distinct_hosts"],
                      "template": shape["name"], "priority": shape["priority"],
                      "gang": shape.get("gang")}
        for s, shape in ((s, shape_of(s)) for s in registered)}
    verdict = reference.judge(store, window_jobs, port_range)
    for name, value in sorted(verdict["counts"].items()):
        run.check(f"reference.{name}", value, 0, value == 0)
    run.say(f"reference judged {verdict['window_allocs']} allocations of "
            f"the window on {len(verdict['touched'])} nodes")

    # 3. the device did the work
    for name, value, limit, ok in counters.device_did_the_work(before, after):
        run.check(name, value, limit, ok)

    # 4. placement is still bin-packing
    pack = reference.packing(store, window_jobs, verdict["sums"],
                             np.random.default_rng(seed))
    run.check("fit_score_placed_mean_vs_uniform",
              pack["placed_mean"], f">={pack['uniform_mean']}",
              pack["placed_mean"] is not None
              and pack["placed_mean"] >= pack["uniform_mean"])

    # 5. what the device holds of the cluster is the store's sums
    readback = resident_readback(snapshot, [config["fleet"]["datacenter"]])
    if readback is None:
        run.check("resident_rows_differing", "no resident base", 0, False)
    else:
        wrong = resident_mismatches(readback, store, verdict["sums"],
                                    port_range)
        run.check("resident_rows_differing", wrong, 0, wrong == 0)
        run.check("resident_base_platform", readback["platform"], platform,
                  readback["platform"] == platform)

    # the guarantees only this deployment states: its own checks, each a
    # file that imports nothing of the program, every count held to 0
    for name in config.get("checks", []):
        counts = plugins.load("checks", name).check(store, window_jobs, config)
        for count_name, value in sorted(counts.items()):
            run.check(f"{name}.{count_name}", value, 0, value == 0)
    return failed


def run_cell(args, run: Run) -> int:
    t_start = process_start()
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "configuration")
    config = fleet.scaled(load_json(os.path.join(ROOT, config_entry["file"])),
                          args.rehearse)
    traffic = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    if args.rehearse:
        traffic = dict(traffic, **traffic["rehearsal"])

    # ---- the device first: no chip, no result
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    run.say(f"device: {device}")
    if not args.rehearse and (device["platform"] != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {device}", file=sys.stderr)
        return 2
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s [%(levelname)s] %(name)s: %(message)s")

    # Importing the placement programs places the compile cache: at
    # JAX_COMPILATION_CACHE_DIR, or <checkout>/.jax_cache.
    try:
        import nomad_tpu.ops.binpack  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not beside the benchmark: {e}",
              file=sys.stderr)
        return 2
    from nomad_tpu.api.http import HTTPServer
    from nomad_tpu.scheduler.batcher import get_batcher
    from nomad_tpu.server import Server, ServerConfig

    backend_s = time.monotonic() - t_start
    setup = {"trivial_rtt_us": trivial_rtt_us()}
    run.say(f"compile cache: {jax.config.jax_compilation_cache_dir}; "
            f"trivial round trip {setup['trivial_rtt_us']:.1f} us")

    server = Server(ServerConfig(**config["server"]))
    http = child = conn = None
    drained = threading.Event()
    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
    try:
        t0 = time.monotonic()
        server.start()
        # The fleet has no clients behind it, so it goes in through the
        # raft log (nodes registered over HTTP would miss heartbeats and
        # be marked down), in bulk, with the collector off: it would walk
        # the hundred thousand new objects again and again.
        gc.disable()
        try:
            loaded = fleet.load_fleet(server, config, args.seed)
        finally:
            gc.enable()
        http = HTTPServer(server, host="127.0.0.1", port=0)
        http.start()
        get_batcher()  # so that /v1/agent/self carries its block from 0
        conn = httpc.Conn(http.addr)
        fleet_s = time.monotonic() - t0
        run.say(f"fleet: {loaded} through the raft log in {fleet_s:.1f} s; "
                f"server at {http.addr}")

        child = start_generator(http.addr, cell, config, traffic, args.seed,
                                args.seconds)
        t0 = time.monotonic()
        try:
            warm = warm_up(run, conn, traffic["warmup"], child)
        except HopelessRun as e:
            # no window, no drain: the generator is stopped below, and
            # whatever of the server will not stop is cut
            leave_within(LEAVE_S, EXIT_HOPELESS)
            run.no_result = f"no result: {e}"
            run.say(run.no_result)
            return EXIT_HOPELESS
        warmup_s = time.monotonic() - t0

        # ---- the window
        before = counters.read_counters(conn)
        spans_before = span_snapshot()
        window_start = time.monotonic()
        stop_at = window_start + args.seconds
        tell(child, {"window_start": window_start, "stop_at": stop_at})
        setup_s = window_start - t_start
        run.say(f"set-up {setup_s:.2f} s: backend {backend_s:.2f}, fleet "
                f"{fleet_s:.2f}, warm-up {warmup_s:.2f} "
                f"({warm['programs']} programs)")
        traced: dict = {}
        tracer = None
        if args.trace:
            tracer = threading.Thread(
                target=trace_stretch, name="trace-stretch",
                args=(traffic["trace"], args.seconds, trace_dir.name,
                      args.rehearse, lambda: get_batcher().dispatches,
                      drained, traced))
            tracer.start()
        time.sleep(max(0.0, stop_at - time.monotonic()))
        after = counters.read_counters(conn)
        spans_after = span_snapshot()
        run.say(f"closing reading {time.monotonic() - stop_at:.2f} s after "
                f"the window's end")

        gen = collect(child, traffic)
        drained.set()
        if tracer is not None:
            tracer.join()
            run.say(f"trace written out in {traced['written_s']:.1f} s, "
                    f"{time.monotonic() - stop_at:.1f} s after the "
                    f"window's end")
        samples = gen["samples"]
        run.say(f"drain {gen['t_drained'] - stop_at:.2f} s, read-back "
                f"{gen['t_read_back'] - gen['t_drained']:.2f} s, "
                f"{len(samples)} evaluations registered in the process's life")

        win = stats.window_samples(samples, window_start, stop_at)
        registered, completed = win["registered"], win["completed"]
        failed = judge(run, config, registered, server.fsm.state.snapshot(),
                       before, after, args.seed, device["platform"])

        # ---- metrics
        place_ms = [stats.latency_ms(s) if s["t_terminal"] is not None
                    else (stop_at + traffic["drain_s"] - stats.due(s)) * 1e3
                    for s in registered]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        peak = max((p for p in peaks if p is not None), default=None)
        ctx = {
            "seconds": args.seconds, "setup_s": setup_s, "setup": setup,
            "placed_allocs": sum(s.get("read_allocs", 0) for s in completed),
            "evals_completed": len(completed),
            "memory_peak_bytes": peak,
            "client": {
                "place_ms": place_ms,
                "late_ms": [s["late_s"] * 1e3 for s in registered
                            if s["late_s"] is not None],
                "register_ms": [(s["t_registered"] - s["t_register"]) * 1e3
                                for s in registered
                                if s["t_registered"] is not None],
            },
            "counters_before": before, "counters_after": after,
            "spans_before": spans_before, "spans_after": spans_after,
        }
        device["memory_peak_bytes"] = peak if peak is not None else 0
        moved = {k: after[k] - before.get(k, 0) for k in sorted(after)
                 if isinstance(after[k], (int, float))
                 and not isinstance(after[k], bool)
                 and after[k] != before.get(k, 0)}
        run.say(f"counters moved in the window: {moved}")
        if place_ms:
            run.say("place_ms min/p25/p50/p75/p95/max: " + "/".join(
                f"{stats.percentile(place_ms, q):.0f}"
                for q in (0, 0.25, 0.5, 0.75, 0.95, 1)))
        run.say(f"window: {len(registered)} evaluations registered, "
                f"{len(completed)} completed, {ctx['placed_allocs']} "
                f"allocations placed, {failed} failed")

        result = {"correct": run.correct(), "attempted": len(registered),
                  "failed": failed, "device": device}
        if args.trace:
            t_a, t_b = traced["stretch"]
            reduced = (tracereduce.reduce_trace(trace_dir.name)
                       if not args.rehearse else tracereduce.reduce_trace(
                           trace_dir.name, "/host:CPU", "tf_XLA"))
            ctx["profile"] = {
                "busy_s": reduced["busy_s"],
                # completions over the whole stretch, the part of it
                # that lies in the drain included
                "evals_completed": len(stats.window_samples(
                    samples, t_a, t_b)["completed"])}
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = t_b - t_a
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            result["metrics"] = reported(
                bench["per_layer"], cell["name"],
                lambda entry: per_layer_value(entry, ctx))
        else:
            result["metrics"] = reported(
                bench["end_to_end"], cell["name"],
                lambda entry: end_to_end_value(entry["name"], ctx))
        result["compared"] = run.compared()
        run.say(json.dumps(result))
        return 0
    finally:
        drained.set()
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if conn is not None:
            conn.close()
        if http is not None:
            http.stop()
        server.shutdown()
        trace_dir.cleanup()


def main(argv=None, mark: str = "") -> int:
    """`mark` is for `control.py`: a prefix on every line, the last
    included, so that a run with the program patched is never read as a
    result."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever backend JAX finds; every "
                         "line is marked and none is a result")
    args = ap.parse_args(argv)
    run = Run(args.rehearse, mark)
    try:
        return run_cell(args, run)
    finally:
        run.say_compared()      # after the server's own last words


if __name__ == "__main__":
    sys.exit(main())
