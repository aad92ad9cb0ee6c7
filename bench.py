"""Benchmark: scheduler placement throughput, CPU iterator stack vs
batched TPU kernel, across the BASELINE.json config matrix.

Configs (BASELINE.json "configs", plus the live regimes 6-8):
  1  100 nodes, service job with 3 task groups (smoke)
  2  1k nodes, batch job, CPU+mem bin-pack only          <- default headline
  3  5k nodes, datacenter + meta constraints, mixed service/batch
  4  10k nodes, 50k existing allocs, ports + distinct_hosts (north star)
  5  system drain storm: system jobs replanned on node drain (CPU path;
     system scheduling is pinned-placement, no search to accelerate)

The CPU baseline runs the reference iterator pipeline (stack.select per
placement, scheduler/stack.go:37); the TPU path runs the same
placements as one batched dense program (ops/binpack.py), B evals
vmapped per dispatch against a shared on-device cluster matrix — the
broker drain-to-batch design from BASELINE.json's north star.

Usage:
  python bench.py            # headline config, ONE JSON line
  python bench.py --config 4 # one config, ONE JSON line
  python bench.py --all      # full matrix, one JSON line per config
"""

import argparse
import json
import random
import sys
import time

sys.path.insert(0, ".")

import numpy as np

HEADLINE_CONFIG = 4  # the north-star 10k-node/50k-alloc scenario


# ------------------------------------------------------------- builders


def build_cluster(n_nodes, datacenters=("dc1",), meta_partitions=0,
                  allocs_per_node=0, seed=0, alloc_skew=0,
                  filler_cpu=(50, 100), filler_mem=(64, 128)):
    """A mock cluster: nodes spread over datacenters, optional 'rack'
    meta partitions (stack_test.go's 64-way partition shape), optional
    pre-existing allocations consuming capacity. alloc_skew > 0 makes
    the pre-load HETEROGENEOUS — each node carries rng.randint(0,
    alloc_skew) filler allocs instead of a uniform count — the
    fragmentation-prone shape the --kernel-ab arm measures on."""
    from nomad_tpu import mock
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import consts

    rng = random.Random(seed)
    store = StateStore()
    index = 0
    filler = None
    if allocs_per_node or alloc_skew:
        filler = mock.job()
        filler.id = "filler"
        filler.type = "service"
        filler.task_groups[0].tasks[0].resources.networks = []
    for i in range(n_nodes):
        node = mock.node()
        node.datacenter = datacenters[i % len(datacenters)]
        if meta_partitions:
            node.meta["rack"] = f"r{i % meta_partitions}"
        node.compute_class()
        index += 1
        store.upsert_node(index, node)
        n_fill = allocs_per_node
        if alloc_skew:
            n_fill = rng.randint(0, alloc_skew)
        if n_fill:
            allocs = []
            for _ in range(n_fill):
                alloc = mock.alloc()
                alloc.node_id = node.id
                alloc.job_id = filler.id
                alloc.job = filler
                alloc.desired_status = consts.ALLOC_DESIRED_RUN
                alloc.client_status = consts.ALLOC_CLIENT_RUNNING
                # modest footprint so nodes stay schedulable
                for tr in alloc.task_resources.values():
                    tr.cpu = rng.choice(list(filler_cpu))
                    tr.memory_mb = rng.choice(list(filler_mem))
                    tr.networks = []
                alloc.resources = None
                allocs.append(alloc)
            index += 1
            store.upsert_allocs(index, allocs)
    return store, index


def service_job(n_groups=1, constraints=None, networks=True,
                distinct_hosts=False, job_type="service"):
    from nomad_tpu import mock
    from nomad_tpu.structs import Constraint, consts

    job = mock.job()
    job.type = job_type
    tg0 = job.task_groups[0]
    job.task_groups = []
    for gi in range(n_groups):
        tg = tg0.copy()
        tg.name = f"g{gi}"
        if not networks:
            tg.tasks[0].resources.networks = []
        if distinct_hosts:
            tg.constraints.append(
                Constraint(operand=consts.CONSTRAINT_DISTINCT_HOSTS))
        job.task_groups.append(tg)
    for c in constraints or []:
        job.constraints.append(c)
    return job


# ---------------------------------------------------------------- paths


def bench_cpu(store, job, k_placements, evals, tg_cycle=None):
    """Reference pipeline: per-eval stack.select loop."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.stack import GenericStack
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.structs import Allocation, Plan
    from nomad_tpu.utils.ids import generate_uuid

    snap = store.snapshot()
    groups = job.task_groups
    tg_cycle = tg_cycle or [0] * k_placements
    latencies = []
    placed = 0
    start = time.perf_counter()
    for i in range(evals):
        t0 = time.perf_counter()
        plan = Plan(job=job)
        ctx = EvalContext(snap, plan, rng=random.Random(i))
        stack = GenericStack(job.type == "batch", ctx)
        stack.set_job(job)
        nodes, _ = ready_nodes_in_dcs(snap, job.datacenters)
        stack.set_nodes(nodes)
        for gi in tg_cycle:
            tg = groups[gi]
            option, _ = stack.select(tg)
            if option is None:
                continue
            placed += 1
            plan.append_alloc(
                Allocation(
                    id=generate_uuid(),
                    job_id=job.id,
                    node_id=option.node.id,
                    task_group=tg.name,
                    task_resources=dict(option.task_resources),
                )
            )
        latencies.append(time.perf_counter() - t0)
    elapsed = time.perf_counter() - start
    assert placed == evals * len(tg_cycle), (
        f"cpu path placed {placed}/{evals * len(tg_cycle)}")
    return evals / elapsed, float(np.percentile(latencies, 99))


def bench_tpu(store, job, k_placements, batch, rounds, tg_cycle=None,
              require_all=True):
    """Batched dense program: `batch` evals per dispatch."""
    import jax

    from nomad_tpu.models.matrix import ClusterMatrix
    from nomad_tpu.ops.binpack import (
        PlacementConfig,
        batched_placement_program_shared,
        make_asks,
        make_node_state,
    )

    snap = store.snapshot()
    matrix = ClusterMatrix(snap, job)
    state = make_node_state(
        matrix.capacity, matrix.sched_capacity, matrix.util,
        matrix.bw_avail, matrix.bw_used, matrix.ports_free,
        matrix.job_count, matrix.tg_count, matrix.feasible, matrix.node_ok,
    )
    tg_cycle = tg_cycle or [0] * k_placements
    asks = make_asks(*matrix.build_asks(tg_cycle))

    # The cluster matrix lives on device across dispatches (it changes
    # only when the snapshot does); per dispatch only keys move.
    state = jax.tree.map(jax.device_put, state)
    asks = jax.tree.map(jax.device_put, asks)
    penalty = 5.0 if job.type == "batch" else 10.0
    config = PlacementConfig(anti_affinity_penalty=penalty)

    def dispatch(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), batch)
        choices, scores, _ = batched_placement_program_shared(
            state, asks, keys, config
        )
        return choices

    warm = np.asarray(dispatch(0))
    if require_all:
        assert (warm[:, : len(tg_cycle)] >= 0).all(), \
            "warmup produced failed placements"

    # Latency: one synchronous round including its result fetch — the
    # submit-to-answer time every eval in that batch observes.
    t0 = time.perf_counter()
    np.asarray(dispatch(1))
    sync_latency = time.perf_counter() - t0

    # Throughput: pipeline the dispatches (JAX async dispatch overlaps
    # them) and fetch all results in one device->host transfer — the
    # broker sidecar streams results the same way.
    start = time.perf_counter()
    outs = [dispatch(r + 2) for r in range(rounds)]
    results = [np.asarray(o) for o in outs]
    elapsed = time.perf_counter() - start
    if require_all:
        for out in results:
            assert (out[:, : len(tg_cycle)] >= 0).all()
    return batch * rounds / elapsed, sync_latency


def bench_tpu_e2e(store, job, k_placements, batch, rounds, tg_cycle=None,
                  workers=None, pre_resolve=True, kernel="greedy",
                  executive=True, executive_threads=4):
    """Honest FULL-PATH dense measurement: per
    eval — ClusterMatrix build (live shared-base cache), ask
    construction, a coalesced batcher dispatch, exact host-side port
    assignment, and Allocation materialization into a Plan — the same
    per-eval work the production dense scheduler does
    (scheduler/tpu.py _compute_placements), measured against
    bench_cpu's stack.select + plan-append loop. Evals run on a thread
    pool so their place() calls coalesce in the batcher exactly like
    concurrent workers' do.

    Also measures the conflict bill the plan applier would present:
    after each round, an applier-style sequential verification replays
    every eval's placements against shared claimed capacity
    (plan_apply.go:194 semantics for capacity/bandwidth/ports; evals
    model distinct jobs, so distinct_hosts is per-eval and out of
    scope). An eval with any rejected placement would replan — one
    extra dispatch round-trip in production. The claim state resets
    per round so the count isolates IN-DISPATCH conflicts, exactly
    what PlacementConfig.pre_resolve (the device-side eval-axis
    serialization) exists to remove — the live cross-batch residue is
    measured by configs 6/8's pipeline stats instead.

    Returns (rate, p99, stats) where stats carries the batcher delta
    (occupancy = batched_requests/dispatches) plus
    conflicted_evals/evals."""
    from concurrent.futures import ThreadPoolExecutor
    from types import SimpleNamespace

    from nomad_tpu.models.matrix import ClusterMatrix
    from nomad_tpu.ops.binpack import (
        PlacementConfig,
        host_prng_key,
        make_asks,
    )
    from nomad_tpu.scheduler.batcher import PlacementBatcher
    from nomad_tpu.scheduler.tpu import _build_allocation, _offer_networks
    from nomad_tpu.scheduler.util import AllocTuple
    from nomad_tpu.structs import AllocMetric, Plan

    snap = store.snapshot()
    tg_cycle = tg_cycle or [0] * k_placements
    penalty = 5.0 if job.type == "batch" else 10.0
    config = PlacementConfig(anti_affinity_penalty=penalty,
                             pre_resolve=pre_resolve, kernel=kernel)
    # Mirror the live dense scheduler (scheduler/tpu.py): a uniform
    # distinct-hosts ask set takes the one-pass top_k program
    # (greedy-only; other kernels run their own joint solve).
    from nomad_tpu.ops.binpack import uniform_dh_flag

    _probe_asks = ClusterMatrix(snap, job).build_asks(tg_cycle)
    config = config._replace(uniform_dh=(
        kernel == "greedy" and uniform_dh_flag(
            tg_cycle, _probe_asks[5], _probe_asks[6])))
    from nomad_tpu.chaos import chaos
    from nomad_tpu.trace import (
        STAGE_DEVICE_DISPATCH,
        STAGE_MATRIX_BUILD,
        get_recorder,
    )

    recorder = get_recorder()

    batcher = PlacementBatcher()
    sched_stub = SimpleNamespace(eval=SimpleNamespace(id="bench"), job=job)
    # Degraded-mode harness (--chaos): an injected device fault fails a
    # whole batch; each member retries its place() — the live dense
    # scheduler falls back to the host path instead, but the bench's
    # evals must stay on the device path to keep measuring it, so here
    # a retry IS the recovery and gets counted (under a lock: a failed
    # batch fails all its pool threads at once and += is not atomic
    # across a GIL switch).
    import threading

    device_retries = [0]
    retry_lock = threading.Lock()
    if workers is None:
        # The live drain-to-batch path processes a drained group fully
        # concurrently (server/worker.py submits the whole group to the
        # shared eval pool), so the honest mirror runs every eval of a
        # round at once — fragmenting the batch across a smaller pool
        # would pay extra device round-trips production doesn't.
        workers = batch

    from nomad_tpu import profile as _profile

    def one_eval(seed):
        # Trace spans mirror the live dense scheduler's stage
        # attribution (scheduler/tpu.py) so the bench's per-stage p99
        # table reads off the same flight recorder as production.
        eid = f"bench-{seed}"
        t0 = time.perf_counter()
        tm0 = time.monotonic()
        matrix = ClusterMatrix(snap, job)
        asks = make_asks(*matrix.build_asks(tg_cycle))
        recorder.record_span(eid, STAGE_MATRIX_BUILD, tm0)
        tm1 = time.monotonic()
        # Lock-wait attribution onto the dispatch span (the contention
        # observatory's per-thread contended-wait delta across the
        # batcher round-trip).
        wait0 = _profile.thread_wait_ms()
        for attempt in range(3):
            try:
                choices, scores = batcher.place(
                    matrix, asks, host_prng_key(seed), config,
                    span=(eid, ""))
                break
            except Exception:
                if not chaos.enabled or attempt == 2:
                    raise
                with retry_lock:
                    device_retries[0] += 1
        recorder.record_span(
            eid, STAGE_DEVICE_DISPATCH, tm1,
            ann={"lock_wait_ms": round(
                _profile.thread_wait_ms() - wait0, 3)})
        tm2 = time.monotonic()
        choices = np.asarray(choices)
        placed = materialize(seed, matrix, choices, np.asarray(scores))
        recorder.record_span(eid, "host.finalize", tm2)
        recorder.complete(eid)
        return placed, time.perf_counter() - t0, choices

    def materialize(seed, matrix, choices, scores):
        """The per-placement choices -> ports -> Allocation loop both
        arms of the A/B run (one_eval's tail and the executive's
        finalize) — factored so the two arms can never silently
        measure different per-eval work."""
        rng_local = random.Random(seed)
        plan = Plan(job=job)
        net_indexes = {}
        placed = 0
        for j, gi in enumerate(tg_cycle):
            tg = job.task_groups[gi]
            missing = AllocTuple(
                name=f"{job.id}.{tg.name}[{j}]", task_group=tg, alloc=None)
            choice = int(choices[j])
            node = (matrix.nodes[choice]
                    if 0 <= choice < matrix.n_real else None)
            if node is None:
                continue
            metrics = AllocMetric()
            metrics.nodes_evaluated = matrix.n_real
            metrics.nodes_available = matrix.nodes_by_dc
            metrics.score_node(node, "binpack", float(scores[j]))
            task_resources = _offer_networks(
                rng_local, missing, node, net_indexes, matrix)
            if task_resources is None:
                continue
            plan.append_alloc(_build_allocation(
                sched_stub, missing, node, task_resources, metrics))
            placed += 1
        return placed

    def build_eval(seed):
        """Executive mode, host side: one eval's matrix + asks (the
        per-row work the live executive does on/near its loop thread,
        server/executive.py _build_row)."""
        eid = f"bench-{seed}"
        tm0 = time.monotonic()
        matrix = ClusterMatrix(snap, job)
        asks = make_asks(*matrix.build_asks(tg_cycle))
        recorder.record_span(eid, STAGE_MATRIX_BUILD, tm0)
        return (eid, seed, matrix, asks)

    def finalize_eval(row, choices, scores, t_round):
        """Executive mode: exact ports + Allocation materialization for
        one cohort row (the shared `materialize` loop). Per-eval
        latency is round-open -> this row's plan materialized (at
        round open the whole cohort is 'ready', exactly like a
        drained batch)."""
        eid, seed, matrix, _asks = row
        tm2 = time.monotonic()
        choices = np.asarray(choices)
        placed = materialize(seed, matrix, choices, np.asarray(scores))
        recorder.record_span(eid, "host.finalize", tm2)
        recorder.complete(eid)
        return placed, time.perf_counter() - t_round, choices

    pool = ThreadPoolExecutor(
        max_workers=(executive_threads if executive else workers))
    # Separate finalize pool in executive mode: round k+1's builds must
    # not queue behind round k's finalize tail on one FIFO pool — the
    # whole point of the lookahead is the device dispatch (GIL-released
    # XLA) running UNDER the GIL-bound finalize work.
    finalize_pool = (ThreadPoolExecutor(max_workers=executive_threads)
                     if executive else pool)

    def run_round_executive_async(base_seed, n):
        """The scheduler-executive shape (ROADMAP open item 1): eval
        identity is a batch row, not a thread. Rows build on a SMALL
        pool (`executive_threads`; numpy releases the GIL — 4 helps, 64
        was the measured convoy), the whole cohort ships as ONE no-park
        device dispatch (PlacementBatcher.place_cohort), and results
        materialize on the same small pool — returned as futures so the
        NEXT round's build+dispatch overlaps this round's finalize tail
        (the live executive's overlap: `_process_cohort` hands its
        finalize futures back and goes straight to the next drain).
        Nothing ever parks on a batcher event, so the batch-boundary
        convoy (BENCH_r13: width 63/64, runq.batch_park p99 55.1ms)
        cannot form."""
        t_round = time.perf_counter()
        rows = [f.result() for f in [
            pool.submit(build_eval, base_seed + i) for i in range(n)]]
        tm1 = time.monotonic()
        wait0 = _profile.thread_wait_ms()
        for attempt in range(3):
            try:
                results = batcher.place_cohort([
                    (row[2], row[3], host_prng_key(row[1]), config,
                     (row[0], "")) for row in rows])
                break
            except Exception:
                if not chaos.enabled or attempt == 2:
                    raise
                with retry_lock:
                    device_retries[0] += 1
        ann = {"lock_wait_ms": round(
            _profile.thread_wait_ms() - wait0, 3), "cohort": n}
        for row in rows:
            recorder.record_span(row[0], STAGE_DEVICE_DISPATCH, tm1,
                                 ann=ann)
        return [finalize_pool.submit(finalize_eval, row, c, s, t_round)
                for row, (c, s) in zip(rows, results)]

    def run_round(base_seed, n=None):
        count = n if n is not None else batch
        if executive:
            return [f.result()
                    for f in run_round_executive_async(base_seed, count)]
        # Mirror the live dispatch pipeline's fan-out announcement so
        # the batcher holds the dispatch for the whole round's
        # staggered matrix builds.
        batcher.add_cohort(count)
        futs = [pool.submit(one_eval, base_seed + i)
                for i in range(count)]
        return [f.result() for f in futs]

    # Applier-style verification reference: one matrix + ask rows
    # (shared by construction — every eval asks the same tg_cycle).
    vmatrix = ClusterMatrix(snap, job)
    v_res, v_bw, v_ports, _vi, _va, _vj, _vt = vmatrix.build_asks(tg_cycle)

    def verify_round(results):
        """Sequential capacity claims over one round's placements;
        returns (evals that would replan, the round's ADMITTED claimed
        utilization) — the same applier-admission rule feeds both the
        conflict count and the quality columns, so the two can't
        drift."""
        claimed_util = np.zeros_like(vmatrix.util)
        claimed_bw = np.zeros_like(vmatrix.bw_used)
        claimed_ports = np.zeros_like(vmatrix.ports_free)
        conflicted = 0
        for _placed, _t, choices in results:
            bad = False
            for j in range(len(tg_cycle)):
                c = int(choices[j])
                if not (0 <= c < vmatrix.n_real):
                    continue
                ok = (
                    np.all(vmatrix.util[c] + claimed_util[c] + v_res[j]
                           <= vmatrix.capacity[c])
                    and (vmatrix.bw_used[c] + claimed_bw[c] + v_bw[j]
                         <= vmatrix.bw_avail[c])
                    and (vmatrix.ports_free[c] - claimed_ports[c]
                         >= v_ports[j])
                )
                if not ok:
                    bad = True
                    continue
                claimed_util[c] += v_res[j]
                claimed_bw[c] += v_bw[j]
                claimed_ports[c] += v_ports[j]
            conflicted += bad
        return conflicted, claimed_util

    # Warm EVERY batch bucket the dispatcher can produce (plus the
    # full size twice): ragged accumulation means a measured round can
    # fragment into any of the ladder sizes, and one unwarmed shape is
    # a trace+compile inside the measured window — enough to wreck a
    # p99 on its own.
    from nomad_tpu.scheduler.batcher import BATCH_BUCKETS

    # Warmup rounds stay OUT of the stage-attribution table (they
    # measure compile caches, not steady state); restore whatever arm
    # (--no-trace) the CLI selected afterwards.
    _trace_was = recorder.enabled
    recorder.set_enabled(False)
    for i, warm_n in enumerate((batch, batch) + tuple(BATCH_BUCKETS) + (1,)):
        if warm_n <= batch:
            run_round(10_000 + i * 1000, n=warm_n)
    recorder.set_enabled(_trace_was)
    stats0 = batcher.stats()
    latencies = []
    placed_total = 0
    conflicted_evals = 0
    start = time.perf_counter()
    round_results = []
    if executive:
        # One-round lookahead: round k+1's builds + device dispatch
        # (XLA releases the GIL) run under round k's GIL-bound finalize
        # tail — the executive's cohort pipelining, measured the same
        # way the live loop overlaps finalize futures with the next
        # drain.
        pending = None
        for r in range(rounds):
            futs = run_round_executive_async(20_000 + r * batch, batch)
            if pending is not None:
                round_results.append([f.result() for f in pending])
            pending = futs
        round_results.append([f.result() for f in pending])
        for results in round_results:
            for placed, t, _choices in results:
                latencies.append(t)
                placed_total += placed
    else:
        for r in range(rounds):
            results = run_round(20_000 + r * batch)
            round_results.append(results)
            for placed, t, _choices in results:
                latencies.append(t)
                placed_total += placed
    elapsed = time.perf_counter() - start
    # Verification outside the timed window: production pays it on the
    # applier thread, overlapped with the next dispatch.
    first_round_claims = None
    for results in round_results:
        conflicted, claimed = verify_round(results)
        conflicted_evals += conflicted
        if first_round_claims is None:
            first_round_claims = claimed
    stats1 = batcher.stats()
    pool.shutdown(wait=False)
    if finalize_pool is not pool:
        finalize_pool.shutdown(wait=False)
    assert placed_total > 0, "e2e path placed nothing"
    dstats = {k: stats1[k] - stats0[k] for k in stats1}
    n_evals = batch * rounds
    dstats["occupancy"] = (
        dstats["batched_requests"] / dstats["dispatches"]
        if dstats.get("dispatches") else 0.0)
    dstats["conflicts_per_eval"] = conflicted_evals / n_evals
    dstats["device_retries"] = device_retries[0]
    # Device-residency columns (models/resident.py): host->device
    # bytes per dispatched batch in steady state (a resident base
    # rides the cache/delta paths — re-shipping the full [N,R] matrix
    # here is the regression the design removed), and the jit
    # compile-cache GROWTH across the measured (post-warmup) rounds —
    # steady state must be 0; --check refuses dense numbers otherwise.
    dstats["transfer_bytes_per_batch"] = (
        dstats.get("upload_bytes", 0) / max(dstats.get("dispatches", 0), 1))
    dstats["jit_recompiles"] = dstats.get("jit_cache_size", 0)
    # Placement-quality columns (nomad_tpu/kernels/quality): score the
    # committed cluster state one round of this workload produces —
    # base utilization plus the round's verified sequential claims
    # (verify_round: exactly what the applier would admit) — against
    # the job's own ask. queueing_delay_ms here is the harness
    # measurement of the quality contract's "p99 time placement work
    # spent queued": this path has no broker, so the queue is the
    # batcher — place() round-trip p99 minus the jitted solve's p99
    # (both from the flight recorder; 0 when --no-trace disabled it).
    # The live configs measure the same contract at THEIR queue, the
    # broker (broker.wait p99 via the quality board).
    from nomad_tpu.kernels.quality import quality_from_arrays

    q = quality_from_arrays(vmatrix.util + first_round_claims,
                            vmatrix.capacity, vmatrix.node_ok, v_res[0])
    dstats["fragmentation"] = q["fragmentation"]
    dstats["binpack_score"] = q["binpack_score"]
    stages = recorder.stage_stats()
    dd = stages.get("device.dispatch", {}).get("p99_ms", 0.0)
    sv = stages.get("device.solve", {}).get("p99_ms", 0.0)
    dstats["queueing_delay_ms"] = max(0.0, dd - sv)
    return (n_evals / elapsed, float(np.percentile(latencies, 99)),
            dstats)


# -------------------------------------------------------------- configs


def config_1():
    """100-node smoke: service job, 3 task groups."""
    store, _ = build_cluster(100)
    job = service_job(n_groups=3, networks=False)
    cycle = [0, 1, 2] * 2  # 6 placements across the 3 groups
    cpu_rate, cpu_p99 = bench_cpu(store, job, len(cycle), evals=50,
                                  tg_cycle=cycle)
    tpu_rate, tpu_p99 = bench_tpu(store, job, len(cycle), batch=2048,
                                  rounds=8, tg_cycle=cycle)
    e2e_rate, e2e_p99, ds = bench_tpu_e2e(store, job, len(cycle), batch=64,
                                          rounds=4, tg_cycle=cycle)
    return {
        "name": "100 nodes, service x3 task groups",
        "cpu": cpu_rate, "cpu_p99_ms": cpu_p99 * 1000,
        "kernel": tpu_rate, "kernel_p99_ms": tpu_p99 * 1000,
        "e2e": e2e_rate, "e2e_p99_ms": e2e_p99 * 1000,
        "occupancy": ds["occupancy"],
        "retries_per_eval": ds["conflicts_per_eval"],
        **_quality_cols(ds),
    }


def _quality_cols(ds):
    """The placement-quality columns every config reports
    (kernels/quality.py: fragmentation / bin-pack / queueing)."""
    return {
        "fragmentation": ds.get("fragmentation", 0.0),
        "binpack_score": ds.get("binpack_score", 0.0),
        "queueing_delay_ms": ds.get("queueing_delay_ms", 0.0),
    }


def config_2():
    """1k nodes, batch, CPU+mem only."""
    store, _ = build_cluster(1000)
    job = service_job(networks=False, job_type="batch")
    job.task_groups[0].count = 8
    cpu_rate, cpu_p99 = bench_cpu(store, job, 8, evals=30)
    tpu_rate, tpu_p99 = bench_tpu(store, job, 8, batch=2048, rounds=8)
    e2e_rate, e2e_p99, ds = bench_tpu_e2e(store, job, 8, batch=64, rounds=4)
    return {
        "name": "1k nodes x 8 allocs/eval (cpu+mem bin-pack)",
        "cpu": cpu_rate, "cpu_p99_ms": cpu_p99 * 1000,
        "kernel": tpu_rate, "kernel_p99_ms": tpu_p99 * 1000,
        "e2e": e2e_rate, "e2e_p99_ms": e2e_p99 * 1000,
        "occupancy": ds["occupancy"],
        "retries_per_eval": ds["conflicts_per_eval"],
        **_quality_cols(ds),
    }


def config_3():
    """5k nodes, dc + meta constraints, mixed service/batch."""
    from nomad_tpu.structs import Constraint

    store, _ = build_cluster(
        5000, datacenters=("dc1", "dc2", "dc3", "dc4"), meta_partitions=64)
    cons = [Constraint(ltarget="${meta.rack}", operand="regexp",
                       rtarget="^r(1?[0-9]|2[0-9]|3[01])$")]  # racks 0-31
    svc = service_job(constraints=cons, networks=False)
    svc.datacenters = ["dc1", "dc2"]
    bat = service_job(constraints=cons, networks=False, job_type="batch")
    bat.datacenters = ["dc3", "dc4"]

    cpu_s, cpu_p99_s = bench_cpu(store, svc, 8, evals=10)
    cpu_b, cpu_p99_b = bench_cpu(store, bat, 8, evals=10)
    tpu_s, tpu_p99_s = bench_tpu(store, svc, 8, batch=1024, rounds=4)
    tpu_b, tpu_p99_b = bench_tpu(store, bat, 8, batch=1024, rounds=4)
    e2e_s, e2e_p99_s, ds_s = bench_tpu_e2e(store, svc, 8, batch=32, rounds=4)
    e2e_b, e2e_p99_b, ds_b = bench_tpu_e2e(store, bat, 8, batch=32, rounds=4)
    # mixed workload: aggregate rate = half service + half batch
    return {
        "name": "5k nodes, dc + rack-regexp constraints, mixed svc/batch",
        "cpu": 2.0 / (1.0 / cpu_s + 1.0 / cpu_b),
        "cpu_p99_ms": max(cpu_p99_s, cpu_p99_b) * 1000,
        "kernel": 2.0 / (1.0 / tpu_s + 1.0 / tpu_b),
        "kernel_p99_ms": max(tpu_p99_s, tpu_p99_b) * 1000,
        "e2e": 2.0 / (1.0 / e2e_s + 1.0 / e2e_b),
        "e2e_p99_ms": max(e2e_p99_s, e2e_p99_b) * 1000,
        "occupancy": (ds_s["occupancy"] + ds_b["occupancy"]) / 2,
        "retries_per_eval": (ds_s["conflicts_per_eval"]
                             + ds_b["conflicts_per_eval"]) / 2,
        "fragmentation": (ds_s["fragmentation"]
                          + ds_b["fragmentation"]) / 2,
        "binpack_score": (ds_s["binpack_score"]
                          + ds_b["binpack_score"]) / 2,
        "queueing_delay_ms": max(ds_s["queueing_delay_ms"],
                                 ds_b["queueing_delay_ms"]),
    }


def config_4(executive=True):
    """North star: 10k nodes, 50k existing allocs, dynamic ports +
    distinct_hosts. The e2e column runs full 64-lane batches with
    in-batch conflict pre-resolution, plus a pre-resolve-OFF A/B so the
    retries column shows what the device-side serialization buys.
    Since PR 12 the e2e arms run the scheduler-executive shape (cohort
    rows + one no-park dispatch) by default; `--executive-ab` pairs it
    against the legacy 64-thread worker shape."""
    store, _ = build_cluster(10_000, datacenters=("dc1", "dc2"),
                             allocs_per_node=5)
    job = service_job(networks=True, distinct_hosts=True)
    job.datacenters = ["dc1", "dc2"]
    job.task_groups[0].count = 8
    # 20 CPU evals: at 5 the column was so short (~0.15 s) that host
    # load swung the headline ratio ±40% run to run.
    cpu_rate, cpu_p99 = bench_cpu(store, job, 8, evals=20)
    tpu_rate, tpu_p99 = bench_tpu(store, job, 8, batch=512, rounds=4)
    e2e_rate, e2e_p99, ds = bench_tpu_e2e(store, job, 8, batch=64, rounds=4,
                                          executive=executive)
    _ab_rate, _ab_p99, ds_off = bench_tpu_e2e(
        store, job, 8, batch=64, rounds=2, pre_resolve=False,
        executive=executive)
    return {
        "name": "10k nodes, 50k allocs, ports + distinct_hosts",
        "cpu": cpu_rate, "cpu_p99_ms": cpu_p99 * 1000,
        "kernel": tpu_rate, "kernel_p99_ms": tpu_p99 * 1000,
        "e2e": e2e_rate, "e2e_p99_ms": e2e_p99 * 1000,
        "occupancy": ds["occupancy"],
        "retries_per_eval": ds["conflicts_per_eval"],
        "retries_per_eval_nopre": ds_off["conflicts_per_eval"],
        "device_retries": ds["device_retries"] + ds_off["device_retries"],
        "transfer_bytes_per_batch": ds["transfer_bytes_per_batch"],
        "jit_recompiles": ds["jit_recompiles"],
        **_quality_cols(ds),
    }


def _system_drain_storm(n_nodes, n_jobs, rack_partition):
    """System drain storm: every system job replans when nodes drain.
    System scheduling pins each placement to its node (no search), so
    the dense path ("system-tpu", scheduler/tpu.py
    DenseSystemScheduler) replaces the per-node iterator stack with one
    vectorized feasibility+fit pass per eval.

    At blueprint scale (10k x 200, BASELINE.json config 5) each system
    job is constrained to its rack partition (n_nodes/n_jobs nodes) —
    each eval still scans ALL nodes for feasibility (the storm cost
    that scales), while placement counts stay bounded the way real
    rack-scoped system jobs are."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs import Constraint, consts

    def build():
        harness = Harness()
        store = harness.state
        index = 0
        for i in range(n_nodes):
            node = mock.node()
            if rack_partition:
                node.meta["rack"] = f"r{i % n_jobs}"
            node.compute_class()
            index += 1
            store.upsert_node(index, node)
        jobs = []
        for j in range(n_jobs):
            job = mock.system_job()
            job.id = f"sys-{j}"
            if rack_partition:
                job.constraints.append(Constraint(
                    ltarget="${meta.rack}", operand="=", rtarget=f"r{j}"))
            job.task_groups[0].tasks[0].resources.networks = []
            job.task_groups[0].tasks[0].resources.cpu = 5
            job.task_groups[0].tasks[0].resources.memory_mb = 8
            index += 1
            store.upsert_job(index, job)
            jobs.append(job)
        # Drain 10% of nodes -> server creates one eval per system job
        # (node_endpoint.go:812 createNodeEvals).
        for node in store.nodes()[: n_nodes // 10]:
            index += 1
            store.update_node_drain(index, node.id, True)
        harness._next_index = index + 1
        evals = []
        for job in jobs:
            ev = mock.eval()
            ev.job_id = job.id
            ev.type = consts.JOB_TYPE_SYSTEM
            ev.triggered_by = consts.EVAL_TRIGGER_NODE_UPDATE
            evals.append(ev)
        return harness, evals

    def run(scheduler_name):
        harness, evals = build()
        latencies = []
        start = time.perf_counter()
        for ev in evals:
            t0 = time.perf_counter()
            harness.process(scheduler_name, ev)
            latencies.append(time.perf_counter() - t0)
        elapsed = time.perf_counter() - start
        return (len(evals) / elapsed, float(np.percentile(latencies, 99)),
                harness)

    cpu_rate, cpu_p99, _h = run("system")
    dense_rate, dense_p99, h_dense = run("system-tpu")
    # Quality columns from the COMMITTED post-storm store (the harness
    # applies plans sequentially — exactly the oracle's commit).
    from nomad_tpu.kernels.quality import quality_from_store

    q = quality_from_store(h_dense.state.snapshot(),
                           h_dense.state.job_by_id("sys-0"))
    return cpu_rate, cpu_p99, dense_rate, dense_p99, q


def _drain_migration_arm(n_nodes, n_jobs, allocs_per_job, budget=8,
                         drain_frac=0.1, seed=4242):
    """Service-job drain migration on the dense path (the churn PR's
    config-5 extension): place service allocs, drain a slice of the
    cluster, and drive the displaced set through the migration budget
    (nomad_tpu/migrate) — follow-up migration evals included — to a
    fully re-placed cluster. Reports:

    - migrations_per_s: committed displaced-alloc evictions+re-places
      per second of storm wall clock (allocs_per_job must exceed the
      budget: a job eval's migrate set is bounded by its own alloc
      count, and the arm asserts the deferral machinery engaged);
    - disruption_p99_ms: per displaced alloc, drain-to-replacement-
      committed latency (the wave that re-placed it), p99.

    The governor's high-water mark is asserted <= budget — numbers
    from an unbounded thundering herd would not be measuring the
    dense drain path this config claims to."""
    from nomad_tpu import mock
    from nomad_tpu.migrate import configure as migrate_configure
    from nomad_tpu.migrate import get_governor
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs import consts
    from nomad_tpu.structs.eval import new_eval

    h = Harness(seed=seed)
    nodes = []
    for _ in range(n_nodes):
        node = mock.node()
        node.compute_class()
        h.state.upsert_node(h.next_index(), node)
        nodes.append(node)
    jobs = []
    for j in range(n_jobs):
        job = mock.job()
        job.id = f"mig-{j}"
        job.task_groups[0].count = allocs_per_job
        task = job.task_groups[0].tasks[0]
        task.resources.cpu = 20
        task.resources.memory_mb = 16
        task.resources.networks = []
        h.state.upsert_job(h.next_index(), job)
        jobs.append(h.state.job_by_id(job.id))
    for job in jobs:
        h.process("service-tpu",
                  new_eval(job, consts.EVAL_TRIGGER_JOB_REGISTER))

    from nomad_tpu.migrate import DEFAULT_MAX_PARALLEL

    migrate_configure(migrate_max_parallel=budget)
    try:
        get_governor().reset_stats()
        # Drain the MOST-OCCUPIED nodes: BestFit concentrates the fleet
        # onto few nodes, so draining by creation order can displace
        # nothing (a vacuous measurement). Draining where the allocs live
        # also guarantees per-eval migrate sets larger than the budget —
        # the deferral/wave machinery this arm exists to measure.
        occupancy = {}
        for a in h.state.allocs():
            if not a.terminal_status():
                occupancy[a.node_id] = occupancy.get(a.node_id, 0) + 1
        by_load = sorted(occupancy, key=occupancy.get, reverse=True)
        n_drain = max(1, int(n_nodes * drain_frac))
        drained = set(by_load[:n_drain])
        drained |= {n.id for n in nodes[: n_drain - len(drained)]}
        displaced = {a.id for a in h.state.allocs()
                     if a.node_id in drained and not a.terminal_status()}
        assert displaced, "drain arm displaced nothing: not measuring"
        for nid in drained:
            h.state.update_node_drain(h.next_index(), nid, True)

        affected = [j for j in jobs
                    if any(a.node_id in drained
                           for a in h.state.allocs_by_job(j.id))]
        pending = [new_eval(j, consts.EVAL_TRIGGER_NODE_UPDATE)
                   for j in affected]
        seen_created = len(h.create_evals)
        disruption = {}
        t_drain = time.perf_counter()
        while pending:
            for ev in pending:
                h.process("service-tpu", ev)
                t_done = time.perf_counter()
                for a in h.state.allocs_by_eval(ev.id):
                    prev = a.previous_allocation
                    if prev in displaced and prev not in disruption:
                        disruption[prev] = t_done - t_drain
            created = h.create_evals[seen_created:]
            seen_created = len(h.create_evals)
            pending = [e for e in created
                       if e.triggered_by == consts.EVAL_TRIGGER_MIGRATION]
        elapsed = time.perf_counter() - t_drain

        migrated = [a for a in h.state.allocs()
                    if a.id in displaced
                    and a.desired_status == consts.ALLOC_DESIRED_STOP]
        g = get_governor().stats()
        assert migrated, "drain arm migrated nothing: not measuring"
        assert g["high_water"] <= max(budget, 1), g
        # The budget must have actually engaged (per-eval displacement
        # exceeds it by construction above) — a zero deferral count means
        # the numbers describe an unpressured path.
        assert g["deferred_total"] > 0, g
        live_by_job = {
            j.id: [a for a in h.state.allocs_by_job(j.id)
                   if not a.terminal_status()] for j in jobs}
        assert all(len(v) == allocs_per_job for v in live_by_job.values()), {
            k: len(v) for k, v in live_by_job.items()}
        assert all(a.node_id not in drained
                   for v in live_by_job.values() for a in v)
        p99 = (float(np.percentile(list(disruption.values()), 99))
               if disruption else 0.0)
        return {
            "migrations": len(migrated),
            "migrations_per_s": len(migrated) / elapsed if elapsed else 0.0,
            "disruption_p99_ms": p99 * 1000,
            "migration_budget": budget,
            "migration_high_water": g["high_water"],
            "migration_deferred": g["deferred_total"],
        }
    finally:
        # The governor is process-global: restore the default so a
        # later config/arm in the same run measures its own budget,
        # not whichever arm ran last (run_preempt_ab does the same).
        migrate_configure(migrate_max_parallel=DEFAULT_MAX_PARALLEL)


def config_5():
    """Blueprint-scale drain storm (BASELINE.json config 5): 10k nodes
    x 200 rack-scoped system jobs, 10% drained — plus the service-side
    migration arm (1k nodes) driving displaced allocs through the
    dense path under the migration budget."""
    cpu_rate, cpu_p99, dense_rate, dense_p99, q = _system_drain_storm(
        10_000, 200, rack_partition=True)
    mig = _drain_migration_arm(1000, 20, 24)
    return {
        "name": ("drain storm: 10k nodes x 200 system jobs (rack-scoped),"
                 " 10% drained (host stack vs dense pass) + service "
                 "migration arm (1k nodes, budgeted)"),
        "cpu": cpu_rate, "cpu_p99_ms": cpu_p99 * 1000,
        "e2e": dense_rate, "e2e_p99_ms": dense_p99 * 1000,
        **_quality_cols(q),
        **mig,
    }


def config_5s():
    """Smoke-scale drain storm (kept for quick runs): 1k x 50,
    unconstrained (every job spans every node), with a small service
    migration arm."""
    cpu_rate, cpu_p99, dense_rate, dense_p99, q = _system_drain_storm(
        1000, 50, rack_partition=False)
    mig = _drain_migration_arm(400, 12, 20)
    return {
        "name": ("drain storm smoke: 1k nodes x 50 system jobs, 10% "
                 "drained (host stack vs dense pass) + migration arm"),
        "cpu": cpu_rate, "cpu_p99_ms": cpu_p99 * 1000,
        "e2e": dense_rate, "e2e_p99_ms": dense_p99 * 1000,
        **_quality_cols(q),
        **mig,
    }


def _live_pipeline(n_nodes, n_jobs, allocs_per_job, lone_jobs=12,
                   allocs_per_node=0, networks=False,
                   distinct_hosts=False, warm_jobs=40):
    """End-to-end control plane: the REAL server pipeline (broker ->
    workers -> drain-to-batch -> scheduler -> plan queue -> pipelined
    applier -> FSM) with CPU vs TPU factories on identical clusters.
    This measures the BASELINE.json acceptance criterion directly:
    evals/sec at identical plan-apply success rate.

    Two regimes per factory set:
    - STORM: workers paused while all jobs register, then released
      against a deep broker — the drain-to-batch path coalesces evals
      into shared device dispatches (server/worker.py dequeue_many +
      scheduler/batcher.py overlay dispatch).
    - LONE: sequential single-eval registrations on an idle broker —
      with dense factories configured, latency-aware routing
      (dense_min_batch) must send these to the host path, so the p99
      should match the CPU column's.

    Returns per-factory rates plus the TPU run's batcher-stat delta
    (incl. the per-dispatch host/transfer/RTT breakdown)."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.batcher import get_batcher
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs import Constraint, consts

    rng = random.Random(11)

    def wait_evals(server, evals, deadline_s):
        deadline = time.perf_counter() + deadline_s
        while time.perf_counter() < deadline:
            st = [server.fsm.state.eval_by_id(e) for e in evals]
            if all(s is not None and s.status in
                   (consts.EVAL_STATUS_COMPLETE,
                    consts.EVAL_STATUS_FAILED) for s in st):
                return
            time.sleep(0.02)

    def make_job(jid):
        job = mock.job()
        job.id = jid
        job.type = "service"
        job.task_groups[0].count = allocs_per_job
        tg = job.task_groups[0]
        if not networks:
            tg.tasks[0].resources.networks = []
        if distinct_hosts:
            tg.constraints.append(
                Constraint(operand=consts.CONSTRAINT_DISTINCT_HOSTS))
        tg.tasks[0].resources.cpu = 20
        tg.tasks[0].resources.memory_mb = 16
        return job

    def run(factories):
        from nomad_tpu.kernels.quality import get_board

        get_board().reset()  # per-arm attribution, not cross-run
        server = Server(ServerConfig(
            num_schedulers=4, scheduler_factories=factories,
            # PR 12: the live dense path runs the scheduler executive
            # (cohort drain + no-park dispatch); inert for the CPU arm
            # (no dense factories). --executive-ab pairs it against the
            # legacy worker/pipeline shape.
            scheduler_executive=True,
            eval_nack_timeout=60.0))
        server.start()
        batcher = get_batcher()
        try:
            filler = None
            if allocs_per_node:
                filler = mock.job()
                filler.id = "filler"
                filler.type = "service"
                filler.task_groups[0].tasks[0].resources.networks = []
            for _ in range(n_nodes):
                node = mock.node()
                node.compute_class()
                server.log.apply("node_register", {"node": node})
                if allocs_per_node:
                    fills = []
                    for _ in range(allocs_per_node):
                        alloc = mock.alloc()
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
                    server.log.apply(
                        "alloc_update", {"allocs": fills})

            # WARMUP (unmeasured): TWO storm waves sized like the
            # measured one, so every program the storm will run is
            # compiled first — wave 1 hits the full-upload compact
            # programs across the B buckets, wave 2 (running against
            # the allocs wave 1 committed) hits the fused base-delta
            # variants. A live server is long-running — shapes compile
            # once per bucket and cache (utils/jaxcache persists them
            # across processes), so the steady state is what to
            # measure. Without wave 2, fused-delta compiles landed
            # inside the measured storm and dominated its wall-clock.
            for wave in ("warmA", "warmB"):
                warm = [make_job(f"{wave}-{j}")
                        for j in range(max(warm_jobs, n_jobs))]
                for w in server.workers:
                    w.set_pause(True)
                server.executive.set_pause(True)
                wevals = [server.job_register(job)[0] for job in warm]
                for w in server.workers:
                    w.set_pause(False)
                server.executive.set_pause(False)
                wait_evals(server, wevals, 600)
                for job in warm:
                    server.job_deregister(job.id)
                # Settle: dereg evals must drain before the next wave.
                deadline = time.perf_counter() + 120
                while time.perf_counter() < deadline:
                    s = server.broker.stats()
                    if not s["total_ready"] and not s["total_unacked"]:
                        break
                    time.sleep(0.05)

            jobs = [make_job(f"e2e-{j}") for j in range(n_jobs)]
            stats0 = batcher.stats()
            # STORM: fill the broker while workers (and the executive
            # drain) are parked, then release — the regime the cohort
            # drain exists for.
            for w in server.workers:
                w.set_pause(True)
            server.executive.set_pause(True)
            evals = [server.job_register(job)[0] for job in jobs]
            start = time.perf_counter()
            for w in server.workers:
                w.set_pause(False)
            server.executive.set_pause(False)
            wait_evals(server, evals, 300)
            storm_elapsed = time.perf_counter() - start
            placed = sum(len(server.fsm.state.allocs_by_job(j.id))
                         for j in jobs)
            success = placed / (n_jobs * allocs_per_job)

            # LONE: idle broker, one eval at a time, per-eval latency.
            lat = []
            for j in range(lone_jobs):
                job = make_job(f"lone-{j}")
                t0 = time.perf_counter()
                ev = server.job_register(job)[0]
                wait_evals(server, [ev], 60)
                lat.append(time.perf_counter() - t0)
            stats1 = batcher.stats()
            dstats = {k: stats1[k] - stats0[k] for k in stats1}
            # The dispatch pipeline + applier live per-server: their
            # stats ARE this run's deltas.
            dstats["pipeline"] = server.dispatch.stats()
            dstats["executive"] = server.stats()["scheduler_executive"]
            dstats["applier"] = server.plan_applier.stats()
            # Overload counters (nomad_tpu/admission): a non-overload
            # config that shed or expired evals measured a server
            # protecting itself, not the dense path — --check gates
            # dense-path numbers on this column staying zero.
            dstats["broker"] = server.broker.stats()
            # Placement-quality scoreboard (kernels/quality.py): the
            # dense run's committed-plan medians + broker-wait p99.
            dstats["placement_quality"] = server.stats()[
                "placement_quality"]
            return (n_jobs / storm_elapsed, success,
                    float(np.percentile(lat, 99)), dstats)
        finally:
            server.shutdown()

    cpu_rate, cpu_success, cpu_lone_p99, _ = run({})
    tpu_rate, tpu_success, tpu_lone_p99, dstats = run(
        {"service": "service-tpu", "batch": "batch-tpu"})
    assert abs(cpu_success - tpu_success) < 1e-9, (
        f"success-rate mismatch: cpu={cpu_success} tpu={tpu_success}")
    return (cpu_rate, cpu_success, cpu_lone_p99,
            tpu_rate, tpu_success, tpu_lone_p99, dstats)


def _trivial_rtt_us() -> float:
    """Round-trip of a near-empty jitted program: the floor any
    dispatch pays regardless of payload or compute."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return x + 1

    probe(jnp.float32(0)).block_until_ready()  # compile
    samples = []
    for i in range(5):
        t0 = time.perf_counter()
        probe(jnp.float32(i)).block_until_ready()
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples) * 1e6)


def _breakdown_str(dstats) -> str:
    """Per-dispatch cost breakdown: h->d payload / issue / device
    round-trip, plus the transport floor. (Host stacking and base
    upload are the `device.idle.stack` stage of the trace table.)"""
    n = max(dstats.get("dispatches", 0), 1)
    return (
        f"per-dispatch: "
        f"payload {dstats.get('payload_bytes', 0) / n / 1024:.0f}KB, "
        f"issue {dstats.get('issue_us', 0) / n:.0f}us, "
        f"sync {dstats.get('sync_us', 0) / n:.0f}us; "
        f"uploads {dstats.get('base_uploads', 0)} full "
        f"({dstats.get('upload_bytes', 0) / 1024:.0f}KB total) + "
        f"{dstats.get('base_delta_updates', 0)} delta; "
        f"trivial-RTT floor {_trivial_rtt_us():.0f}us"
    )


def config_6():
    """Live pipeline at storm scale: 1k nodes x 120 service jobs."""
    n_nodes, n_jobs, allocs_per_job = 1000, 120, 4
    (cpu_rate, cpu_success, cpu_lone_p99,
     tpu_rate, tpu_success, tpu_lone_p99, dstats) = _live_pipeline(
        n_nodes, n_jobs, allocs_per_job)
    return _live_result(
        f"end-to-end pipeline, {n_nodes} nodes x {n_jobs} jobs x "
        f"{allocs_per_job} allocs, 4 workers",
        cpu_rate, cpu_success, cpu_lone_p99,
        tpu_rate, tpu_success, tpu_lone_p99, dstats)


def config_8():
    """North-star LIVE regime (BASELINE.json config 4's shape): 10k nodes,
    50k existing allocs, ports + distinct_hosts, through the REAL
    control plane."""
    n_nodes, n_jobs, allocs_per_job = 10_000, 60, 8
    (cpu_rate, cpu_success, cpu_lone_p99,
     tpu_rate, tpu_success, tpu_lone_p99, dstats) = _live_pipeline(
        n_nodes, n_jobs, allocs_per_job, lone_jobs=6, allocs_per_node=5,
        networks=True, distinct_hosts=True, warm_jobs=16)
    return _live_result(
        f"north-star live pipeline, {n_nodes} nodes, {n_nodes * 5} "
        f"allocs, ports+distinct_hosts, {n_jobs} jobs x {allocs_per_job},"
        " 4 workers",
        cpu_rate, cpu_success, cpu_lone_p99,
        tpu_rate, tpu_success, tpu_lone_p99, dstats)


def _live_result(name, cpu_rate, cpu_success, cpu_lone_p99,
                 tpu_rate, tpu_success, tpu_lone_p99, dstats):
    """Per-rep live-run columns. Everything run-dependent goes into
    NUMERIC columns so run_config medianizes it — stats baked into the
    name string would silently report rep 1 only, the exact
    single-shot trap the median rework exists to close. (The per-rep
    batcher cost breakdown still prints on stderr for debugging.)"""
    occupancy = (dstats["batched_requests"] / dstats["dispatches"]
                 if dstats.get("dispatches") else 0.0)
    pipe = dstats.get("pipeline", {})
    exe = dstats.get("executive", {})
    if exe.get("enabled"):
        # The scheduler executive superseded the pipeline for this run:
        # its cohort columns fill the same slots (occupancy = evals per
        # cohort; conflicts = refresh-index'd plans; the requeue
        # machinery does not exist on the no-park path).
        done = max(exe.get("acked", 0) + exe.get("nacked", 0), 1)
        pipe = {
            "occupancy": exe.get("occupancy", 0.0),
            "largest_batch": exe.get("largest_cohort", 0),
            "plan_conflicts": exe.get("plan_conflicts", 0),
            "requeues": 0,
            "inline_retries": exe.get("plan_conflicts", 0),
            "retries_per_eval": exe.get("plan_conflicts", 0) / done,
            "prefetch_bytes": 0,
        }
    applier = dstats.get("applier", {})
    print(f"# {name} [rep detail] batcher: "
          f"{dstats.get('dispatches', 0)} dispatches x {occupancy:.1f} "
          f"evals, {dstats.get('compact_dispatches', 0)} compact; "
          + _breakdown_str(dstats), file=sys.stderr)
    return {
        "name": name,
        "cpu": cpu_rate,
        "cpu_p99_ms": cpu_lone_p99 * 1000,
        "e2e": tpu_rate,
        "e2e_p99_ms": tpu_lone_p99 * 1000,
        "success_cpu": cpu_success,
        "success_tpu": tpu_success,
        "occupancy": occupancy,
        "pipeline_occupancy": pipe.get("occupancy", 0.0),
        "pipeline_largest_batch": pipe.get("largest_batch", 0),
        "plan_conflicts": pipe.get("plan_conflicts", 0),
        "requeues": pipe.get("requeues", 0),
        "inline_retries": pipe.get("inline_retries", 0),
        "applier_plans_rejected": applier.get("plans_rejected", 0),
        "applier_plans_evaluated": applier.get("plans_evaluated", 0),
        "retries_per_eval": pipe.get("retries_per_eval", 0.0),
        "shed": (dstats.get("broker", {}).get("shed", 0)
                 + dstats.get("broker", {}).get("expired", 0)),
        "transfer_bytes_per_batch": (
            dstats.get("upload_bytes", 0)
            / max(dstats.get("dispatches", 0), 1)),
        "jit_recompiles": dstats.get("jit_cache_size", 0),
        "prefetch_bytes": pipe.get("prefetch_bytes", 0),
        "executive_fast_evals": exe.get("fast_evals", 0),
        "executive_legacy_evals": exe.get("legacy_evals", 0),
        "cohort_dispatches": dstats.get("cohort_dispatches", 0),
        **_live_quality_cols(dstats.get("placement_quality", {})),
    }


def _live_quality_cols(pq):
    """Quality columns for the live configs, read off the server's
    placement_quality snapshot: the ACTIVE kernel's medians (one
    kernel per run) + the broker-wait queueing p99."""
    kernels = pq.get("kernels", {})
    q = next(iter(kernels.values()), {}) if kernels else {}
    return {
        "fragmentation": q.get("fragmentation", 0.0),
        "binpack_score": q.get("binpack_score", 0.0),
        "queueing_delay_ms": pq.get("queueing_delay_ms", 0.0),
    }


CONFIGS = {1: config_1, 2: config_2, 3: config_3, 4: config_4, 5: config_5,
           6: config_6, 7: config_5s, 8: config_8}

# Default repetitions: ±30-40% run-to-run swings make a single shot
# meaningless — the headline gates on the MEDIAN of interleaved
# CPU/TPU reps. Each rep runs its CPU and TPU columns back to back, so
# drift hits both.
DEFAULT_REPS = 5


def _median_iqr(vals):
    med = float(np.median(vals))
    iqr = float(np.percentile(vals, 75) - np.percentile(vals, 25))
    return med, iqr


def run_config(n, reps=DEFAULT_REPS):
    from nomad_tpu.profile import get_profiler
    from nomad_tpu.trace import get_recorder

    get_recorder().reset()  # per-config stage attribution, not cross-config
    get_profiler().reset()  # per-config contention columns likewise
    runs = [CONFIGS[n]() for _ in range(reps)]
    return _summarize(n, runs, reps)


def run_config_trace_ab(n, reps=DEFAULT_REPS):
    """run_config with an INTERLEAVED traced/untraced arm per rep: each
    rep runs the config with the flight recorder on, then immediately
    again with it off, and the overhead is the MEDIAN of per-rep
    e2e ratios — pairing cancels host-load drift exactly like the
    cpu/tpu columns' interleaving (two sequential 3-rep arms measured
    ±12% 'overhead' in BOTH directions on an idle box). Returns
    (summary-of-traced-runs, median ratio)."""
    from nomad_tpu.trace import get_recorder

    rec = get_recorder()
    rec.reset()
    runs = []
    ratios = []
    untraced_rates = []
    try:
        for _ in range(reps):
            rec.set_enabled(True)
            r = CONFIGS[n]()
            runs.append(r)
            rec.set_enabled(False)
            u = CONFIGS[n]()
            ratios.append(r["e2e"] / u["e2e"])
            untraced_rates.append(u["e2e"])
    finally:
        rec.set_enabled(True)
    out = _summarize(n, runs, reps)
    ratio, _ = _median_iqr(ratios)
    out["trace_overhead"] = {
        "traced_e2e": out["columns"]["e2e"]["median"],
        "untraced_e2e": round(float(np.median(untraced_rates)), 3),
        "ratio": round(float(ratio), 4),
        "per_rep_ratios": [round(float(x), 4) for x in ratios],
    }
    out["metric"] += (
        f"; trace overhead: paired-ratio median x{ratio:.3f} "
        f"(traced {out['trace_overhead']['traced_e2e']:.1f} vs untraced "
        f"{out['trace_overhead']['untraced_e2e']:.1f} evals/s)")
    return out, float(ratio)


def run_config_profile_ab(n, reps=DEFAULT_REPS):
    """run_config with an INTERLEAVED observatory-on/fully-dark arm
    per rep (the --profile-off arm, paired): each rep runs the config
    with the contention observatory AND the flight recorder on — what
    production runs — then immediately again with BOTH off, and the
    overhead is the MEDIAN of per-rep e2e ratios (same pairing
    discipline as the trace A/B, gating the whole always-on
    observability stack at once). The dark arm also keeps its spans
    out of the recorder, so the stage table the gap attribution reads
    covers exactly the runs the contention histograms cover. Returns
    (summary-of-profiled-runs, median ratio). The summary additionally
    carries the contention attribution of the device.dispatch tail:
    the p99-p50 gap against the top wait sites (per-lock waits + the
    batch-park run-queue delay) — the measured answer to BENCH_r10's
    GIL-queuing inference, captured as BENCH_r13."""
    from nomad_tpu.profile import get_profiler
    from nomad_tpu.trace import get_recorder

    rec = get_recorder()
    rec.reset()
    prof = get_profiler()
    prof.reset()
    runs = []
    ratios = []
    off_rates = []
    try:
        for _ in range(reps):
            prof.configure(enabled=True)
            rec.set_enabled(True)
            r = CONFIGS[n]()
            runs.append(r)
            prof.configure(enabled=False)
            rec.set_enabled(False)
            u = CONFIGS[n]()
            ratios.append(r["e2e"] / u["e2e"])
            off_rates.append(u["e2e"])
    finally:
        prof.configure(enabled=True)
        rec.set_enabled(True)
    out = _summarize(n, runs, reps)
    ratio, _ = _median_iqr(ratios)
    out["profile_overhead"] = {
        "profiled_e2e": out["columns"]["e2e"]["median"],
        "unprofiled_e2e": round(float(np.median(off_rates)), 3),
        "ratio": round(float(ratio), 4),
        "per_rep_ratios": [round(float(x), 4) for x in ratios],
    }
    out["contention_attribution"] = _gap_attribution(out)
    att = out["contention_attribution"]
    out["metric"] += (
        f"; profile overhead: paired-ratio median x{ratio:.3f}; "
        f"dispatch p99-p50 gap {att['gap_ms']:.1f}ms, top sites cover "
        f"{att['attributed_frac']:.0%}")
    return out, float(ratio)


def _gap_attribution(out):
    """Where the device.dispatch tail comes from: the p99-p50 gap of
    the dispatch stage vs the top contention sites' p99s — per-lock
    contended waits plus the batch-park run-queue delay (the direct
    measurement of 'GIL queuing of 64 eval threads around the batch
    boundary'). attributed_frac >= 0.5 is the acceptance bar: the
    observatory must EXPLAIN the gap it was built to measure."""
    stages = out.get("stage_table", {})
    dd = stages.get("device.dispatch", {})
    gap = max(0.0, dd.get("p99_ms", 0.0) - dd.get("p50_ms", 0.0))
    prof = out.get("profile", {})
    sites = [
        {"site": site, "p99_ms": s["wait_p99_ms"], "kind": "lock_wait"}
        for site, s in prof.get("lock_sites", {}).items()
    ]
    for site, p99 in prof.get("runq_p99_ms", {}).items():
        sites.append({"site": f"runq.{site}", "p99_ms": p99,
                      "kind": "runq_delay"})
    sites.sort(key=lambda s: -s["p99_ms"])
    top = sites[:3]
    attributed = sum(s["p99_ms"] for s in top)
    return {
        "device_dispatch_p50_ms": dd.get("p50_ms", 0.0),
        "device_dispatch_p99_ms": dd.get("p99_ms", 0.0),
        "gap_ms": round(gap, 3),
        "top_sites": top,
        "attributed_ms": round(attributed, 3),
        "attributed_frac": round(attributed / gap, 4) if gap else 0.0,
    }


def _summarize(n, runs, reps):
    name = runs[0]["name"]
    cols = {}
    for key in runs[0]:
        if key == "name":
            continue
        vals = [float(r[key]) for r in runs if key in r]
        med, iqr = _median_iqr(vals)
        cols[key] = {"median": round(med, 3), "iqr": round(iqr, 3),
                     "n": len(vals)}
    # Ratios pair per-rep so host-load drift cancels; the headline
    # multiplier is their MEDIAN, never a single shot.
    e2e_x, _ = _median_iqr([r["e2e"] / r["cpu"] for r in runs])
    med_e2e = cols["e2e"]["median"]
    out = {
        "metric": (
            f"[config {n}] {name}; median-of-{reps}: "
            f"cpu={cols['cpu']['median']:.1f} evals/s "
            f"(iqr {cols['cpu']['iqr']:.1f}), e2e={med_e2e:.1f} "
            f"(iqr {cols['e2e']['iqr']:.1f}), e2e_x={e2e_x:.2f}"
        ),
        "value": round(med_e2e, 1),
        "unit": "evals/sec",
        "n": reps,
        "iqr": cols["e2e"]["iqr"],
        "e2e_x": round(e2e_x, 2),
        "vs_baseline": round(e2e_x, 2),
        # Parity is CLAIMED only when the median clears it.
        "parity_on_median": bool(e2e_x >= 1.0),
        "columns": cols,
    }
    if "kernel" in cols:
        kernel_x, _ = _median_iqr([r["kernel"] / r["cpu"] for r in runs])
        out["kernel_x"] = round(kernel_x, 2)
        out["metric"] += f", kernel_x={kernel_x:.1f}"
    if "occupancy" in cols:
        out["occupancy"] = cols["occupancy"]["median"]
        out["metric"] += f"; occupancy={out['occupancy']:.1f} lanes"
    if "retries_per_eval" in cols:
        out["retries_per_eval"] = cols["retries_per_eval"]["median"]
        out["metric"] += f", retries/eval={out['retries_per_eval']:.3f}"
    if "retries_per_eval_nopre" in cols:
        out["retries_per_eval_nopre"] = cols["retries_per_eval_nopre"][
            "median"]
        out["metric"] += (
            f" (pre-resolve OFF: {out['retries_per_eval_nopre']:.3f})")
    if "fragmentation" in cols:
        out["metric"] += (
            f"; quality: frag={cols['fragmentation']['median']:.3f}, "
            f"binpack={cols['binpack_score']['median']:.3f}, "
            f"queue_p99={cols['queueing_delay_ms']['median']:.1f}ms")
    # Per-stage latency attribution from the flight recorder
    # (nomad_tpu/trace): where each eval's time went across the reps —
    # the in-system answer to "what is the p99 made of". Empty when
    # --no-trace disabled the recorder.
    from nomad_tpu.trace import get_recorder

    stages = get_recorder().stage_stats()
    if stages:
        out["stage_p99_ms"] = {
            k: v["p99_ms"] for k, v in sorted(stages.items())}
        out["stage_table"] = stages
        top = sorted(
            ((k, v["p99_ms"]) for k, v in stages.items() if k != "e2e"),
            key=lambda kv: -kv[1])[:3]
        out["metric"] += "; stage p99 " + ", ".join(
            f"{k}={v:.1f}ms" for k, v in top)
    out.update(_profile_cols())
    if "lock_wait_p99_ms" in out:
        out["metric"] += (
            f"; contention: lock_wait_p99={out['lock_wait_p99_ms']:.2f}ms"
            f", gil_overshoot_p99={out['gil_overshoot_p99_ms']:.2f}ms"
            f", convoy_width={out['convoy_width']}")
    return out


def _profile_cols():
    """Contention-observatory columns for every config (the satellite
    triple: combined contended lock-wait p99, GIL sleep-overshoot p99,
    and the widest batch-boundary convoy), plus the per-site wait
    table BENCH_r13's gap attribution reads. Empty when --profile-off
    disabled the observatory."""
    from nomad_tpu.profile import get_profiler
    from nomad_tpu.utils.metrics import HIST_BUCKETS, hist_percentile

    prof = get_profiler()
    if not prof.enabled:
        return {}
    # Combined wait p99 across every profiled site: one number for the
    # "how contended was this run" column; the per-site table carries
    # the attribution.
    merged = [0] * HIST_BUCKETS
    count = 0
    sites = {}
    for site, (c, total, buckets) in prof.lock_site_buckets("wait").items():
        count += c
        for i, v in enumerate(buckets):
            if v:
                merged[i] += v
        sites[site] = {
            "contended": c,
            "wait_total_ms": round(total, 3),
            "wait_p99_ms": round(hist_percentile(buckets, c, 0.99), 4),
        }
    gil = prof.gil.stats()
    convoys = prof.convoy_table()
    out = {
        "lock_wait_p99_ms": round(
            hist_percentile(merged, count, 0.99), 4) if count else 0.0,
        "gil_overshoot_p99_ms": gil.get("p99_ms", 0.0),
        "convoy_width": convoys["max_width"],
    }
    extra = {
        "lock_sites": dict(sorted(
            sites.items(),
            key=lambda kv: -kv[1]["wait_total_ms"])[:8]),
        "runq_p99_ms": {site: s.get("p99_ms", 0.0)
                        for site, s in prof.runq_table().items()},
        "convoys": convoys["convoys"],
    }
    out["profile"] = extra
    return out


def run_chaos(seed, reps=1):
    """Degraded-mode A/B of config 4 (the north-star cluster shape):
    one clean pass, then the same pass under a mild seeded fault
    schedule — device-dispatch latency jitter plus a forced device
    fault burst — reporting occupancy and retries/eval side by side so
    BENCH_r07.json records what the dense path delivers while faults
    fire. Refuses to emit numbers if any scheduled fault never fired
    (a schedule that missed its path measured nothing: typo guard)."""
    from nomad_tpu.chaos import FaultSpec, chaos

    clean = [CONFIGS[HEADLINE_CONFIG]() for _ in range(reps)]
    schedule = [
        # Mild: a slow device adds ~20ms to a quarter of device
        # dispatches...
        FaultSpec("batcher.dispatch", "delay", delay=0.02, prob=0.25,
                  count=64),
        # ...and two dispatches fail outright (whole-batch retry).
        FaultSpec("binpack.device", "error", count=2, start=6),
    ]
    chaos.arm(seed, schedule)
    try:
        degraded = [CONFIGS[HEADLINE_CONFIG]() for _ in range(reps)]
        unfired = chaos.unfired()
        fired = len(chaos.firing_log())
    finally:
        chaos.disarm()
    if unfired:
        for spec in unfired:
            print(f"bench: scheduled fault never fired: {spec.to_dict()}",
                  file=sys.stderr)
        print("bench: REFUSING to emit chaos numbers — the schedule did "
              "not exercise its sites (typo or unreachable path)",
              file=sys.stderr)
        sys.exit(2)

    def med(runs, key):
        return float(np.median([r[key] for r in runs if key in r]))

    return {
        "metric": (
            f"[config {HEADLINE_CONFIG} +chaos seed={seed}] degraded-mode"
            f" A/B: clean e2e={med(clean, 'e2e'):.1f} evals/s occ="
            f"{med(clean, 'occupancy'):.1f}; chaos e2e="
            f"{med(degraded, 'e2e'):.1f} occ="
            f"{med(degraded, 'occupancy'):.1f}, "
            f"{fired} faults fired"
        ),
        "chaos_seed": seed,
        "faults_fired": fired,
        "clean": {
            "e2e": round(med(clean, "e2e"), 1),
            "occupancy": round(med(clean, "occupancy"), 2),
            "retries_per_eval": round(med(clean, "retries_per_eval"), 4),
            "device_retries": int(med(clean, "device_retries")),
        },
        "chaos": {
            "e2e": round(med(degraded, "e2e"), 1),
            "occupancy": round(med(degraded, "occupancy"), 2),
            "retries_per_eval": round(med(degraded, "retries_per_eval"), 4),
            "device_retries": int(med(degraded, "device_retries")),
        },
    }


def _overload_server(protection, cap):
    """Live server for one overload arm. Protection ON bounds the
    service ready queue, stamps deadlines, and arms the admission
    gate; OFF is the unbounded pre-PR-5 behaviour kept reachable for
    the A/B."""
    from nomad_tpu.server import Server, ServerConfig

    # eval_batch_size 8 (not the default 64): the pipeline's intake
    # backpressure engages at 2 full batches, so this keeps the
    # saturation bound (16) + ready cap at the storm's scale — the
    # protection being measured, not a queue too deep to ever fill.
    if protection:
        cfg = ServerConfig(
            num_schedulers=4,
            scheduler_factories={"service": "service-tpu"},
            eval_batch_size=8,
            eval_ready_caps={"service": cap},
            eval_deadline_ttl=15.0,
            eval_nack_timeout=60.0)
    else:
        cfg = ServerConfig(
            num_schedulers=4,
            scheduler_factories={"service": "service-tpu"},
            eval_batch_size=8,
            eval_ready_cap=0,
            admission_enabled=False,
            breaker_enabled=False,
            eval_nack_timeout=60.0)
    server = Server(cfg)
    server.start()
    return server


def _overload_job(jid, priority=None):
    from nomad_tpu import mock

    job = mock.job()
    job.id = jid
    job.type = "service"
    if priority is not None:
        job.priority = priority
    job.task_groups[0].count = 4
    tg = job.task_groups[0]
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.cpu = 20
    tg.tasks[0].resources.memory_mb = 16
    return job


def _overload_wait(server, eval_ids, deadline_s=300.0):
    from nomad_tpu.structs import consts

    deadline = time.perf_counter() + deadline_s
    state = server.fsm.state
    while time.perf_counter() < deadline:
        evs = [state.eval_by_id(e) for e in eval_ids]
        if all(e is not None and e.status in
               (consts.EVAL_STATUS_COMPLETE,
                consts.EVAL_STATUS_FAILED) for e in evs):
            return
        time.sleep(0.02)
    raise TimeoutError("overload arm did not settle")


def _overload_storm(server, rate, n_submit, rng):
    """Submit `n_submit` jobs paced at 3x the measured capacity
    `rate`, polling completions as they land; returns goodput
    (accepted evals/s), shed_rate, accepted-eval p99 (ms), and the
    broker-depth samples taken at each submission."""
    from nomad_tpu.structs import consts

    interval = 1.0 / (3.0 * rate)
    pending = {}  # eval_id -> submit time
    latencies = {}  # eval_id -> (seconds, triggered_by)
    depths = []
    state = server.fsm.state
    broker0 = server.broker.stats()

    def poll():
        done = []
        for eid, t0 in pending.items():
            ev = state.eval_by_id(eid)
            if ev is not None and ev.status in (
                    consts.EVAL_STATUS_COMPLETE, consts.EVAL_STATUS_FAILED):
                latencies[eid] = (time.perf_counter() - t0, ev.triggered_by)
                done.append(eid)
        for eid in done:
            del pending[eid]

    start = time.perf_counter()
    last_poll = 0.0
    for i in range(n_submit):
        target = start + i * interval
        while True:
            now = time.perf_counter()
            if now >= target:
                break
            if now - last_poll >= 0.02:  # completion scans are O(pending)
                poll()
                last_poll = now
            time.sleep(0.002)
        job = _overload_job(f"ovl-{i}", priority=rng.choice([20, 50, 80]))
        ev_id, _ = server.job_register(job)
        pending[ev_id] = time.perf_counter()
        depths.append(server.broker.ready_count())
    submit_elapsed = time.perf_counter() - start
    deadline = time.perf_counter() + 300.0
    while pending and time.perf_counter() < deadline:
        poll()
        time.sleep(0.02)
    if pending:
        raise TimeoutError(f"{len(pending)} overload evals never settled")
    end = time.perf_counter()

    shed_trigs = (consts.EVAL_TRIGGER_SHED, consts.EVAL_TRIGGER_EXPIRED)
    accepted = [lat for lat, trig in latencies.values()
                if trig not in shed_trigs]
    n_shed = n_submit - len(accepted)
    # Depth trend over the submission window, quarter-mean smoothed:
    # batch drains dip the raw samples a few evals between polls, but
    # an unbounded queue's quarter means climb monotonically while a
    # capped one's plateau at the cap.
    q = max(1, len(depths) // 4)
    quarter_means = [round(sum(depths[i * q:(i + 1) * q]) / q, 1)
                     for i in range(4)]
    return {
        "submitted": n_submit,
        "offered_rate": round(3.0 * rate, 1),
        "achieved_rate": round(n_submit / submit_elapsed, 1),
        "shed_rate": round(n_shed / n_submit, 4),
        "goodput": round(len(accepted) / (end - start), 1),
        "accepted_p99_ms": round(
            float(np.percentile(accepted, 99)) * 1000, 1),
        "depth_max": max(depths),
        "depth_final": depths[-1],
        "depth_quarter_means": quarter_means,
        "depth_monotonic_growth": bool(
            all(b > a for a, b in zip(quarter_means, quarter_means[1:]))),
        # Storm-window deltas, not server lifetime.
        "broker_shed": server.broker.stats()["shed"] - broker0["shed"],
        "broker_expired": (server.broker.stats()["expired"]
                           - broker0["expired"]),
    }


def run_overload(seed, n_nodes=400, probe_jobs=24, window_s=6.0, cap=16):
    """Overload A/B for the live pipeline (the soak's quantitative
    twin, tests/test_overload_soak.py): measure capacity with a
    capacity-sized storm, then submit at 3x that rate — once with
    protection ON (bounded service queue at `cap`, deadlines,
    admission), once with everything OFF. Protection ON should hold
    goodput near capacity with a bounded accepted-eval p99 and a
    capped queue; OFF shows the queue growing monotonically with the
    p99 inflating alongside it."""
    import random as _random

    from nomad_tpu import mock

    def seed_cluster(server):
        for _ in range(n_nodes):
            node = mock.node()
            node.compute_class()
            server.log.apply("node_register", {"node": node})

    def warm(server):
        # Two waves so both the full-upload and base-delta program
        # variants compile outside the measured windows (the
        # _live_pipeline warm-up discipline). Deregs mint NO evals —
        # a burst of dereg evals against the ON arm's capped queue
        # would shed, polluting the storm's counters.
        for wave in ("wA", "wB"):
            jobs = [_overload_job(f"{wave}-{j}") for j in range(probe_jobs)]
            evs = [server.job_register(job)[0] for job in jobs]
            _overload_wait(server, evs)
            for job in jobs:
                server.job_deregister(job.id, create_eval=False)

    def capacity(server):
        # Sustained-rate probe sized like the storm, not one batch: a
        # handful of jobs drains in a single device dispatch and reads
        # 3-5x the steady-state rate, which would turn "3x capacity"
        # into a meaningless instant burst.
        n = max(probe_jobs, 60)
        jobs = [_overload_job(f"capy-{j}") for j in range(n)]
        t0 = time.perf_counter()
        evs = [server.job_register(job)[0] for job in jobs]
        _overload_wait(server, evs)
        return n / (time.perf_counter() - t0)

    # Capacity is measured on the UNbounded arm (a capped queue would
    # shed the probe itself) and reused for the ON arm — both arms see
    # the identical offered load.
    off_server = _overload_server(protection=False, cap=0)
    try:
        seed_cluster(off_server)
        warm(off_server)
        rate = capacity(off_server)
        # A SUSTAINED overload window, not an instant burst: 3x the
        # measured rate held for ~window_s seconds (bounded so a fast
        # box cannot explode the job count).
        storm_jobs = int(min(900, max(120, 3.0 * rate * window_s)))
        off = _overload_storm(off_server, rate,
                              storm_jobs, _random.Random(seed))
    finally:
        off_server.shutdown()

    on_server = _overload_server(protection=True, cap=cap)
    try:
        seed_cluster(on_server)
        warm(on_server)
        on = _overload_storm(on_server, rate,
                             storm_jobs, _random.Random(seed))
        on["breaker_state"] = on_server.stats()["admission"][
            "breaker"]["state"]
    finally:
        on_server.shutdown()

    return {
        "metric": (
            f"[overload seed={seed}] {n_nodes} nodes, capacity "
            f"{rate:.1f} evals/s, storm at 3x: protection-ON "
            f"goodput={on['goodput']:.1f} shed_rate={on['shed_rate']:.2f} "
            f"accepted-p99={on['accepted_p99_ms']:.0f}ms "
            f"depth<= {on['depth_max']}; OFF "
            f"goodput={off['goodput']:.1f} shed_rate={off['shed_rate']:.2f} "
            f"p99={off['accepted_p99_ms']:.0f}ms depth-> "
            f"{off['depth_max']} "
            f"(monotonic={off['depth_monotonic_growth']})"
        ),
        "overload_seed": seed,
        "capacity_evals_per_s": round(rate, 1),
        "service_queue_cap": cap,
        "protection_on": on,
        "protection_off": off,
    }


def _read_storm_server(mux_enabled, scoped, n_watchers):
    """Live server + HTTP front end for one read-storm arm. Mux ON +
    scoped is the shipping read plane (parked continuations, zero
    handler threads, per-scope wakes); OFF + global is the pre-PR-19
    baseline kept reachable for the A/B — a thread per blocking query,
    woken by ANY commit."""
    from nomad_tpu.api import HTTPServer
    from nomad_tpu.server import Server, ServerConfig

    cfg = ServerConfig(
        num_schedulers=1,
        eval_nack_timeout=60.0,
        read_mux_enabled=mux_enabled,
        read_scoped_index=scoped,
        read_mux_max_parked=max(4096, 4 * n_watchers))
    server = Server(cfg)
    server.start()
    http = HTTPServer(server)
    http.start()
    host, port = http.addr.split("//")[1].split(":")
    return server, http, host, int(port)


def _read_storm_park(host, port, path):
    import socket as _socket

    s = _socket.create_connection((host, port), timeout=90)
    s.sendall(f"GET {path} HTTP/1.1\r\nHost: bench\r\n\r\n".encode())
    return s


def _read_storm_recv(sock, timeout=15.0):
    """Read one HTTP response off a parked socket; returns
    (status_ok, payload_bytes). Reads headers + Content-Length bytes
    rather than draining to EOF — the mux serve thunk closes the
    connection but the thread-park baseline answers over keep-alive
    and would block an EOF reader until the socket times out."""
    sock.settimeout(timeout)
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            break
        buf += chunk
    head, _, payload = buf.partition(b"\r\n\r\n")
    clen = 0
    for line in head.split(b"\r\n")[1:]:
        key, _, val = line.partition(b":")
        if key.strip().lower() == b"content-length":
            clen = int(val.strip())
    while len(payload) < clen:
        chunk = sock.recv(65536)
        if not chunk:
            break
        payload += chunk
    try:
        status = int(head.split(b"\r\n", 1)[0].split()[1])
    except (IndexError, ValueError):
        status = 0
    return status == 200, payload


def _read_storm_mode_ab(addr, n=150):
    """Stale-vs-consistent read latency A/B against the same (leader)
    server: `?stale` serves straight from the local snapshot, while
    `?consistent` first waits for the FSM to reach the last known
    commit index (a no-op barrier on the leader, a real wait on a
    follower)."""
    import urllib.request

    out = {}
    for mode in ("stale", "consistent"):
        lat = []
        for _ in range(n):
            t0 = time.perf_counter()
            with urllib.request.urlopen(
                    f"{addr}/v1/jobs?{mode}", timeout=10.0) as resp:
                resp.read()
            lat.append(time.perf_counter() - t0)
        out[mode] = {
            "p50_ms": round(float(np.percentile(lat, 50)) * 1000, 2),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1000, 2),
        }
    return out


def _read_storm_plain_reads(addr, n=200, stop=None, lat=None):
    """`n` non-blocking /v1/jobs reads (or until `stop` is set when
    given); appends latencies (s) to `lat` and returns it."""
    import urllib.request

    lat = [] if lat is None else lat
    for _ in range(n):
        if stop is not None and stop.is_set():
            break
        t0 = time.perf_counter()
        with urllib.request.urlopen(f"{addr}/v1/jobs", timeout=10.0) as r:
            r.read()
        lat.append(time.perf_counter() - t0)
        if stop is not None:
            time.sleep(0.002)
    return lat


def _read_storm_arm(mux_enabled, scoped, n_watchers, wait_s, rounds):
    """One read-storm arm: park `n_watchers` blocking queries on
    disjoint alloc_job scopes, measure the parked-thread footprint,
    then run `rounds` waves of scope writes (one writer client per
    10 watchers) with a concurrent plain reader, and time each
    wake-to-serve. The untouched sockets are then polled for spurious
    responses — on the scoped arm that must be none; on the
    global-index baseline EVERY commit satisfies every watcher, so
    the ratio reads ~1.0. The stale/consistent A/B runs on the mux
    arm under the still-parked load."""
    import select
    import threading as _threading

    from nomad_tpu import mock

    server, http, host, port = _read_storm_server(
        mux_enabled, scoped, n_watchers)
    socks = []
    out = {"mux_enabled": mux_enabled, "scoped_index": scoped,
           "watchers": n_watchers}
    try:
        state = server.fsm.state
        # Seed one commit so the first scope write lands at index >= 2:
        # a write AT the watchers' ?index=1 is correctly not-newer and
        # must not wake anyone — keep it out of the measurement.
        server.log.apply("node_register", {"node": mock.node()})
        idle = _read_storm_plain_reads(http.addr)
        out["read_idle_p99_ms"] = round(
            float(np.percentile(idle, 99)) * 1000, 2)
        thread_floor = _threading.active_count()
        for i in range(n_watchers):
            socks.append(_read_storm_park(
                host, port,
                f"/v1/job/rs-{i}/allocations?index=1&wait={wait_s}"))

        deadline = time.perf_counter() + 30.0
        if mux_enabled:
            while (server.read_mux.stats()["parked"] < n_watchers
                   and time.perf_counter() < deadline):
                time.sleep(0.02)
            if server.read_mux.stats()["parked"] < n_watchers:
                raise TimeoutError("read-storm watchers never parked")
            # Handler threads unwind once the socket is detached; give
            # the last few a moment before reading the footprint.
            settle = time.perf_counter() + 10.0
            while (_threading.active_count() > thread_floor + 8
                   and time.perf_counter() < settle):
                time.sleep(0.05)
        else:
            # Thread-park baseline: every watcher HOLDS its handler
            # thread, so the footprint itself is the settle signal.
            while (_threading.active_count() - thread_floor < n_watchers
                   and time.perf_counter() < deadline):
                time.sleep(0.02)
        out["parked_thread_delta"] = (_threading.active_count()
                                      - thread_floor)

        n_writers = max(1, n_watchers // 10)
        wlock = _threading.Lock()
        results = {}

        def wake_client(slot):
            a = mock.alloc()
            a.job_id = f"rs-{slot}"
            with wlock:
                t0 = time.perf_counter()
                state.upsert_allocs(state.latest_index() + 1, [a])
            try:
                ok, _payload = _read_storm_recv(socks[slot])
            except OSError:
                ok = False
            results[slot] = (time.perf_counter() - t0, ok)

        # Plain reads keep flowing while the write waves run — the
        # read-under-churn column the idle figure baselines.
        churn_stop = _threading.Event()
        churn_lat = []
        reader = _threading.Thread(
            target=_read_storm_plain_reads, name="rs-reader",
            args=(http.addr, 100000, churn_stop, churn_lat))
        reader.start()
        woken = 0
        try:
            for r in range(rounds):
                clients = [
                    _threading.Thread(target=wake_client,
                                      args=(r * n_writers + j,),
                                      name=f"rs-client-{r}-{j}")
                    for j in range(n_writers)]
                for t in clients:
                    t.start()
                for t in clients:
                    t.join(timeout=30.0)
                woken += n_writers
        finally:
            churn_stop.set()
            reader.join(timeout=15.0)
        out["read_churn_p99_ms"] = round(
            float(np.percentile(churn_lat, 99)) * 1000, 2) if churn_lat \
            else None

        lat = [s for s, ok in results.values() if ok]
        out["write_clients"] = n_writers
        out["wakes"] = woken
        out["wake_failures"] = woken - len(lat)
        out["wake_to_serve_p50_ms"] = round(
            float(np.percentile(lat, 50)) * 1000, 2) if lat else None
        out["wake_to_serve_p99_ms"] = round(
            float(np.percentile(lat, 99)) * 1000, 2) if lat else None

        # Spurious check, client side: the remaining sockets watch
        # scopes nothing wrote — any readable one got a response whose
        # body cannot have changed (a spurious wake). Scoped arm: must
        # be none. Global-index arm: every commit satisfied every
        # watcher, so expect ~all of them. Settle first, THEN count:
        # select returns on the FIRST readable fd, and the thread-park
        # baseline answers at its 1s re-check boundary — an immediate
        # select would tally only the earliest arrivals.
        time.sleep(1.5)
        remaining = socks[woken:]
        readable, _, _ = select.select(remaining, [], [], 0.2)
        out["spurious_responses"] = len(readable)
        out["spurious_ratio"] = round(
            len(readable) / max(1, len(remaining)), 4)
        if mux_enabled:
            out["mode_ab"] = _read_storm_mode_ab(http.addr)
            out["mux"] = server.read_mux.stats()
        return out
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        http.stop()
        server.shutdown()


def run_read_storm(n_watchers=200, check=False):
    """Read-plane storm A/B (the quantitative twin of
    tests/test_readplane.py's storm): park N blocking queries on
    disjoint scopes with one write client per 10 watchers, mux ON vs
    the thread-park baseline. ON must hold an O(1) parked-thread
    footprint and zero spurious wakes; OFF shows the thread-per-
    watcher scaling the mux removes. With --check, refuses numbers
    when the spurious ratio exceeds 1% or the mux footprint scales
    with the watcher count."""
    on = _read_storm_arm(True, True, n_watchers, wait_s=60, rounds=5)
    base_watchers = n_watchers
    off = _read_storm_arm(False, False, base_watchers, wait_s=30,
                          rounds=1)

    churn_x = (round(on["read_churn_p99_ms"] / on["read_idle_p99_ms"], 2)
               if on.get("read_churn_p99_ms") and on.get("read_idle_p99_ms")
               else None)
    out = {
        "metric": (
            f"[read-storm n={n_watchers}] mux+scoped ON: parked-thread "
            f"delta {on['parked_thread_delta']} (O(1)), wake p99 "
            f"{on['wake_to_serve_p99_ms']}ms, spurious "
            f"{on['spurious_ratio']:.4f}, churn/idle read p99 x"
            f"{churn_x}; thread-park global-index OFF: delta "
            f"{off['parked_thread_delta']} (~1/watcher), spurious "
            f"{off['spurious_ratio']:.4f}"
        ),
        "watchers": n_watchers,
        "read_churn_over_idle_p99": churn_x,
        "mux_on": on,
        "threadpark_off": off,
    }
    if check:
        if on["spurious_ratio"] > 0.01 or on["mux"]["spurious"] > 0:
            print(f"bench: REFUSING read-storm numbers: spurious wake "
                  f"ratio {on['spurious_ratio']} (client) / "
                  f"{on['mux']['spurious']} (mux) exceeds the 1% "
                  f"budget — scope routing is waking watchers whose "
                  f"scope did not move", file=sys.stderr)
            sys.exit(2)
        if on["parked_thread_delta"] > 8:
            print(f"bench: REFUSING read-storm numbers: mux arm held "
                  f"{on['parked_thread_delta']} extra threads with "
                  f"{n_watchers} parked watchers — the parked-watcher "
                  f"footprint must be O(1), not O(watchers)",
                  file=sys.stderr)
            sys.exit(2)
        if off["parked_thread_delta"] < base_watchers // 2:
            print(f"bench: REFUSING read-storm numbers: the thread-"
                  f"park baseline held only "
                  f"{off['parked_thread_delta']} threads for "
                  f"{base_watchers} watchers — the A/B's OFF arm is "
                  f"not measuring the pre-mux behaviour",
                  file=sys.stderr)
            sys.exit(2)
        if on["wake_failures"] or off["wake_failures"]:
            print(f"bench: REFUSING read-storm numbers: "
                  f"{on['wake_failures']} (ON) / "
                  f"{off['wake_failures']} (OFF) written scopes never "
                  f"served their watcher", file=sys.stderr)
            sys.exit(2)
    return out


def _shed_gate(out, n):
    """--check: a NON-overload config that shed or expired evals was
    measured while the server protected itself — its dense-path
    numbers describe a degraded run, not the pipeline. Refuse."""
    shed = out.get("columns", {}).get("shed", {}).get("median", 0)
    if shed > 0:
        print(f"bench: REFUSING to report config {n}: shed_rate > 0 "
              f"(median {shed} evals shed/expired) in a non-overload "
              f"config — raise eval_ready_cap / deadline TTL or fix "
              f"the regression that slowed the drain", file=sys.stderr)
        sys.exit(2)


def _recompile_gate(out, n):
    """--check: steady-state jit recompiles after warmup invalidate
    dense-path numbers — the measured rounds paid trace+compile stalls
    a long-running server would not (a shape-bucket leak, an unhashable
    static arg, a drifting padding ladder). Refuse."""
    rec = out.get("columns", {}).get("jit_recompiles", {}).get("median")
    if rec:
        print(f"bench: REFUSING to report config {n}: steady-state "
              f"jit_recompiles = {rec} after warmup — the dense path "
              f"recompiled mid-measurement (shape bucket leak?); fix "
              f"the bucket ladder or extend warmup", file=sys.stderr)
        sys.exit(2)


def run_resident_ab(reps=DEFAULT_REPS, configs=(None,)):
    """Device-resident state ON/OFF A/B -> BENCH_r10/r14: ON is the
    shipping default (universe matrix + node-axis deltas + prefetch),
    OFF reverts to the ready-subset rebuild-per-snapshot path. Reports
    both arms' full summaries (stage p99 tables included) plus the
    headline deltas per config. Since PR 12 the A/B carries an
    ON >= OFF acceptance flag per config: BENCH_r10 measured the
    inversion (ON 579 < OFF 636 on a static cluster — the delta
    machinery ran under 64-thread contention); on the executive's
    no-park shape the bookkeeping is cheaper than OFF's re-uploads and
    the inversion must stay flipped (--check refuses otherwise)."""
    from nomad_tpu.models import resident

    configs = tuple(HEADLINE_CONFIG if c is None else c for c in configs)
    per_config = {}
    for n in configs:
        resident.configure(enabled=True)
        on = run_config(n, reps=reps)
        try:
            resident.configure(enabled=False)
            off = run_config(n, reps=reps)
        finally:
            resident.configure(enabled=True)
        per_config[n] = {
            "resident_on": on, "resident_off": off,
            "on_ge_off": bool(on["value"] >= off["value"]),
        }
    headline = per_config[configs[0]]
    on, off = headline["resident_on"], headline["resident_off"]
    on_dd = on.get("stage_p99_ms", {}).get("device.dispatch", 0.0)
    off_dd = off.get("stage_p99_ms", {}).get("device.dispatch", 0.0)
    return {
        "metric": (
            f"[config {configs[0]} resident A/B] ON: "
            f"e2e={on['value']:.1f} evals/s (e2e_x {on['e2e_x']:.2f}), "
            f"device.dispatch p99 {on_dd:.1f}ms, "
            f"transfer/batch {on['columns']['transfer_bytes_per_batch']['median']:.0f}B, "
            f"recompiles {on['columns']['jit_recompiles']['median']:.0f}; "
            f"OFF: e2e={off['value']:.1f} (e2e_x {off['e2e_x']:.2f}), "
            f"device.dispatch p99 {off_dd:.1f}ms"
            + "".join(
                f"; config {n}: ON {'>=' if pc['on_ge_off'] else '<'} OFF"
                for n, pc in per_config.items())
        ),
        "resident_on": on,
        "resident_off": off,
        "configs": {str(n): {"on_ge_off": pc["on_ge_off"]}
                    for n, pc in per_config.items()},
        "on_ge_off_every_config": all(
            pc["on_ge_off"] for pc in per_config.values()),
    }


def config_frag_heavy(kernel="greedy"):
    """Fragmentation-heavy A/B workload (the --kernel-ab second arm):
    200 nodes with SKEWED light pre-load (0-3 filler allocs per node —
    heterogeneous headroom) taking 16 LARGE asks per eval (~40% of a
    node on cpu and mem, so 2 fit and a third strands the remainder).
    This is the shape where the greedy tie-break noise scatters
    placements across near-tie nodes and strands headroom; the convex
    kernel's joint solve sees all 16 asks and the load landscape at
    once and packs a deliberate node set."""
    # CHUNKY skewed pre-load (~half an ask per filler): node headrooms
    # land at 1.2x-2.6x the ask, so which headroom CLASS a kernel
    # fills decides how much capacity strands — the axis BestFit (and
    # its tie-break noise) cannot see.
    store, _ = build_cluster(200, alloc_skew=3, seed=17,
                             filler_cpu=(600, 800),
                             filler_mem=(1200, 1600))
    job = service_job(networks=False)
    job.task_groups[0].count = 16
    tg = job.task_groups[0].tasks[0]
    tg.resources.cpu = 1500
    tg.resources.memory_mb = 3000
    # batch=8: one 8-eval pre-resolved batch claims ~25% of the
    # cluster — contended enough that packing choices matter, not so
    # full that every node strands and the kernels converge.
    e2e_rate, e2e_p99, ds = bench_tpu_e2e(
        store, job, 16, batch=8, rounds=3, kernel=kernel)
    return {
        "name": "frag-heavy: 200 nodes skewed pre-load, 16x 40%-asks",
        "e2e": e2e_rate, "e2e_p99_ms": e2e_p99 * 1000,
        "occupancy": ds["occupancy"],
        "jit_recompiles": ds["jit_recompiles"],
        **_quality_cols(ds),
    }


def config_4_kernel(kernel="greedy"):
    """Config 4's cluster shape with a pinned kernel (the --kernel-ab
    first arm): the north-star 10k-node scenario, e2e only."""
    store, _ = build_cluster(10_000, datacenters=("dc1", "dc2"),
                             allocs_per_node=5)
    job = service_job(networks=True, distinct_hosts=True)
    job.datacenters = ["dc1", "dc2"]
    job.task_groups[0].count = 8
    e2e_rate, e2e_p99, ds = bench_tpu_e2e(
        store, job, 8, batch=64, rounds=3, kernel=kernel)
    return {
        "name": "10k nodes, 50k allocs, ports + distinct_hosts",
        "e2e": e2e_rate, "e2e_p99_ms": e2e_p99 * 1000,
        "occupancy": ds["occupancy"],
        "jit_recompiles": ds["jit_recompiles"],
        **_quality_cols(ds),
    }


KERNEL_AB_ARMS = {"config4": config_4_kernel, "frag_heavy": config_frag_heavy}
KERNEL_AB_KERNELS = ("greedy", "convex")


def run_kernel_ab(reps=3, check=False):
    """Throughput + quality A/B of the registered kernels (greedy vs
    convex) on config 4's shape and the fragmentation-heavy arm ->
    BENCH_r11.json. Interleaved reps (greedy then convex back to back
    per rep) so host drift hits both; medians reported. With --check,
    every kernel must first pass the oracle differential rig
    (kernels/differential.py) — red rigs refuse to report — and
    steady-state jit recompiles must stay 0."""
    from nomad_tpu.trace import get_recorder

    if check:
        from nomad_tpu.kernels.differential import run_differential

        for kernel in KERNEL_AB_KERNELS:
            report = run_differential(kernel)
            if not report["green"]:
                for v in report["violations"]:
                    print(f"bench: {v}", file=sys.stderr)
                print(f"bench: REFUSING to report kernel numbers: "
                      f"kernel {kernel!r} failed the oracle "
                      f"differential rig ({len(report['violations'])} "
                      f"violations across {report['cases']} cases)",
                      file=sys.stderr)
                sys.exit(2)
            print(f"bench: kernel {kernel!r} oracle differential green "
                  f"({report['cases']} cases)", file=sys.stderr)

    arms = {}
    for arm_name, builder in KERNEL_AB_ARMS.items():
        runs = {k: [] for k in KERNEL_AB_KERNELS}
        for _ in range(reps):
            for kernel in KERNEL_AB_KERNELS:
                get_recorder().reset()
                runs[kernel].append(builder(kernel=kernel))
        per_kernel = {}
        for kernel, rr in runs.items():
            cols = {}
            for key in rr[0]:
                if key == "name":
                    continue
                med, iqr = _median_iqr([float(r[key]) for r in rr])
                cols[key] = {"median": round(med, 4),
                             "iqr": round(iqr, 4)}
            per_kernel[kernel] = cols
        g, c = per_kernel["greedy"], per_kernel["convex"]
        speed_ratio = (c["e2e"]["median"] / g["e2e"]["median"]
                       if g["e2e"]["median"] else 0.0)
        arms[arm_name] = {
            "name": runs["greedy"][0]["name"],
            "kernels": per_kernel,
            "convex_vs_greedy": {
                "speed_ratio": round(speed_ratio, 3),
                "fragmentation_delta": round(
                    c["fragmentation"]["median"]
                    - g["fragmentation"]["median"], 4),
                "binpack_delta": round(
                    c["binpack_score"]["median"]
                    - g["binpack_score"]["median"], 4),
                # The acceptance bar: quality improves (lower frag or
                # higher binpack) at >= 0.5x greedy's throughput.
                "quality_improved": bool(
                    c["fragmentation"]["median"]
                    < g["fragmentation"]["median"] - 1e-9
                    or c["binpack_score"]["median"]
                    > g["binpack_score"]["median"] + 1e-9),
                "speed_ok": bool(speed_ratio >= 0.5),
            },
        }
        if check:
            for kernel in KERNEL_AB_KERNELS:
                rec = per_kernel[kernel]["jit_recompiles"]["median"]
                if rec:
                    print(f"bench: REFUSING kernel-ab numbers: kernel "
                          f"{kernel!r} recompiled mid-measurement on "
                          f"arm {arm_name!r} (jit_recompiles={rec})",
                          file=sys.stderr)
                    sys.exit(2)

    accepted = any(a["convex_vs_greedy"]["quality_improved"]
                   and a["convex_vs_greedy"]["speed_ok"]
                   for a in arms.values())
    summary = "; ".join(
        f"{name}: convex {a['convex_vs_greedy']['speed_ratio']:.2f}x "
        f"speed, frag {a['kernels']['convex']['fragmentation']['median']:.3f}"
        f" vs {a['kernels']['greedy']['fragmentation']['median']:.3f}, "
        f"binpack {a['kernels']['convex']['binpack_score']['median']:.3f}"
        f" vs {a['kernels']['greedy']['binpack_score']['median']:.3f}"
        for name, a in arms.items())
    return {
        "metric": f"[kernel-ab greedy vs convex, median-of-{reps}] "
                  + summary,
        "arms": arms,
        "acceptance_quality_at_half_speed": accepted,
    }


def _preempt_storm(preemption_on, seed, n_nodes=16, storm_x=3):
    """One priority-storm arm: a full cluster of prio-20 allocs, then
    high-priority (60) demand at `storm_x` times what the cluster can
    hold even WITH preemption. ON places to capacity by evicting
    lowest-priority allocs through the dense preempt pass; OFF sheds
    per the PR 5 policy (blocked evals, zero evictions)."""
    from nomad_tpu import mock
    from nomad_tpu.migrate import configure as migrate_configure
    from nomad_tpu.migrate import get_governor
    from nomad_tpu.ops.binpack import jit_cache_size
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs import consts
    from nomad_tpu.structs.eval import new_eval

    migrate_configure(preemption_enabled=preemption_on,
                      preempt_priority_threshold=50)
    get_governor().reset_stats()
    h = Harness(seed=seed)
    for _ in range(n_nodes):
        node = mock.node()
        node.resources.cpu = 1000
        node.resources.memory_mb = 4096
        node.compute_class()
        h.state.upsert_node(h.next_index(), node)
    low = mock.job()
    low.id = "low-prio"
    low.priority = 20
    low.task_groups[0].count = n_nodes
    t = low.task_groups[0].tasks[0]
    t.resources.cpu = 600
    t.resources.memory_mb = 256
    t.resources.networks = []
    h.state.upsert_job(h.next_index(), low)
    h.process("service-tpu", new_eval(h.state.job_by_id(low.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))

    # capacity with preemption = 1 high alloc per node; storm at 3x
    per_job = 4
    n_high = (n_nodes * storm_x) // per_job
    requested = n_high * per_job
    t0 = time.perf_counter()
    for j in range(n_high):
        job = mock.job()
        job.id = f"high-{j}"
        job.priority = 60
        job.task_groups[0].count = per_job
        t = job.task_groups[0].tasks[0]
        t.resources.cpu = 500
        t.resources.memory_mb = 128
        t.resources.networks = []
        h.state.upsert_job(h.next_index(), job)
        h.process("service-tpu", new_eval(
            h.state.job_by_id(job.id), consts.EVAL_TRIGGER_JOB_REGISTER))
    elapsed = time.perf_counter() - t0

    placed = sum(
        1 for a in h.state.allocs()
        if a.job_id.startswith("high-") and not a.terminal_status())
    evicted = [a for a in h.state.allocs_by_job(low.id)
               if a.desired_status == consts.ALLOC_DESIRED_EVICT]
    # every eviction must have committed through the raft funnel
    # (Harness.submit_plan IS the oracle's funnel): each evicted store
    # record traces to exactly one plan's preemption leg.
    staged_ids = []
    for plan in h.plans:
        for victims in plan.node_preemptions.values():
            staged_ids.extend(v.id for v in victims)
    blocked = sum(1 for e in h.create_evals
                  if e.status == consts.EVAL_STATUS_BLOCKED)
    return {
        "requested": requested,
        "placed": placed,
        "placed_frac": placed / requested if requested else 0.0,
        "evictions": len(evicted),
        "evictions_staged_in_plans": len(staged_ids),
        "evictions_funnel_ok": (
            sorted(staged_ids) == sorted(a.id for a in evicted)),
        "blocked_evals": blocked,
        "evals_per_s": n_high / elapsed if elapsed else 0.0,
        "jit_cache_size": jit_cache_size(),
    }


def run_preempt_ab(reps=3, check=False):
    """Preemption ON/OFF A/B under a 3x priority storm -> the
    BENCH_r12 arm. ON must place the cluster's preemption capacity
    with every eviction committing exactly once through the raft
    funnel; OFF must shed per the PR 5 policy unchanged (blocked
    evals, zero evictions). With --check, refuses to report if ANY
    eviction lacks a raft-funnel terminal (a store evict record with
    no staging plan, or a staged victim that never committed), or if
    the preemption leg recompiled after warmup."""
    from nomad_tpu.migrate import configure as migrate_configure

    arms = {"on": [], "off": []}
    try:
        for rep in range(reps):
            arms["on"].append(_preempt_storm(True, seed=9000 + rep))
            arms["off"].append(_preempt_storm(False, seed=9500 + rep))
    finally:
        migrate_configure(preemption_enabled=False)

    if check:
        for rep, r in enumerate(arms["on"]):
            if not r["evictions_funnel_ok"]:
                print(f"bench: REFUSING preempt-ab numbers: rep {rep} "
                      f"has evictions without a raft-funnel terminal "
                      f"(staged {r['evictions_staged_in_plans']} vs "
                      f"committed {r['evictions']})", file=sys.stderr)
                sys.exit(2)
        # warmup = rep 0; later reps must add no compiled programs
        sizes = [r["jit_cache_size"] for r in arms["on"]]
        if len(set(sizes[1:])) > 1:
            print(f"bench: REFUSING preempt-ab numbers: preemption leg "
                  f"recompiled after warmup (jit cache {sizes})",
                  file=sys.stderr)
            sys.exit(2)

    def med(rr, key):
        m, _ = _median_iqr([float(r[key]) for r in rr])
        return m

    on, off = arms["on"], arms["off"]
    out = {
        "metric": (f"[preempt-ab 3x priority storm, median-of-{reps}] "
                   f"ON: placed {med(on, 'placed'):.0f}/"
                   f"{on[0]['requested']} with "
                   f"{med(on, 'evictions'):.0f} evictions "
                   f"(funnel_ok={all(r['evictions_funnel_ok'] for r in on)})"
                   f"; OFF: placed {med(off, 'placed'):.0f}, "
                   f"{med(off, 'evictions'):.0f} evictions, "
                   f"{med(off, 'blocked_evals'):.0f} blocked"),
        "preemption_on": {k: med(on, k) for k in on[0] if k != "metric"},
        "preemption_off": {k: med(off, k) for k in off[0]},
        "acceptance": {
            "on_places_capacity": bool(med(on, "placed") >= 16),
            "on_funnel_exactly_once": all(
                r["evictions_funnel_ok"] for r in on),
            "off_sheds_unchanged": bool(
                med(off, "placed") == 0 and med(off, "evictions") == 0
                and med(off, "blocked_evals") > 0),
        },
    }
    return out


def _defrag_churn_arm(defrag_on, seed, n_nodes=200, churn_steps=12,
                      budget=16, max_moves=16, rounds_per_step=3):
    """One defrag-ab arm: a config-5-shaped churning SERVICE workload
    (mixed 600/300 asks on 1000-cap nodes; each step client-completes
    a random slice of small allocs and the reconciler refills the
    holes through the dense path — the scatter that fragments), with
    the defrag loop ON or OFF between steps. Deterministic: the
    Harness drives the scheduler, the REAL DefragLoop drives the
    waves (governor claims, budget cap, stale gate and all), and the
    arm's stub server processes each wave eval synchronously through
    the dense factory then commits its terminal to the store so the
    loop's watch releases the slots.

    Returns the fragmentation trajectory (cluster_fragmentation — the
    solver's own objective, measured identically in both arms), the
    governor high-water vs budget, the displaced-alloc funnel sweep
    (every moved alloc staged in EXACTLY one plan's eviction leg and
    carrying a desired-stop terminal, with exactly one replacement),
    warm/cold solve cost, and the jit program count after warmup."""
    import random as _random

    import types as _types

    from nomad_tpu.defrag import DefragLoop, cluster_fragmentation
    from nomad_tpu.migrate import configure as migrate_configure
    from nomad_tpu.migrate import DEFAULT_MAX_PARALLEL, get_governor
    from nomad_tpu.ops.binpack import jit_cache_size
    from nomad_tpu.scheduler.testing import (
        Harness,
        churn_stop_small_allocs,
        seed_consolidation_cluster,
    )
    from nomad_tpu.server.config import ServerConfig
    from nomad_tpu.structs import consts
    from nomad_tpu.structs.eval import new_eval as _new_eval

    rng = _random.Random(seed)
    h = Harness(seed=seed)
    # The SHARED fragmentation fixture (scheduler/testing.py) — the
    # defrag differential rig builds the identical workload shape, so
    # the rig and this trajectory never judge different clusters.
    seed_consolidation_cluster(h, n_nodes, factory="service-tpu")

    migrate_configure(migrate_max_parallel=budget)
    harness = h

    class _ArmServer:
        """The Server slice the loop touches; wave evals process
        synchronously through the dense factory and commit their
        terminal to the store (the dev-server applier analog)."""

        def __init__(self):
            self.config = ServerConfig(
                defrag_enabled=defrag_on, defrag_interval=10_000.0,
                defrag_min_gain=0.001, defrag_max_moves_per_wave=max_moves)
            self.fsm = _types.SimpleNamespace(state=harness.state)
            self.admission = _types.SimpleNamespace(level=lambda: "green")

        def is_leader(self):
            return True

        def eval_update(self, evals):
            for ev in evals:
                harness.state.upsert_evals(
                    harness.next_index(), [ev.copy()])
                harness.process("service-tpu", ev)
                done = ev.copy()
                done.status = consts.EVAL_STATUS_COMPLETE
                harness.state.upsert_evals(harness.next_index(), [done])

    loop = DefragLoop(_ArmServer())
    trajectory = []
    jit_warm = None
    try:
        get_governor().reset_stats()
        clock = [0.0]
        trajectory.append(cluster_fragmentation(
            h.state.snapshot(), ["dc1"]))
        for step in range(churn_steps):
            # churn: client-complete a slice of small allocs ...
            stops = churn_stop_small_allocs(h, rng, 0.10)
            # ... and refill the holes (the reconciler's job)
            refill_jobs = sorted({a.job_id for a in stops})
            for jid in refill_jobs:
                job = h.state.job_by_id(jid)
                h.process("service-tpu", _new_eval(
                    job, consts.EVAL_TRIGGER_NODE_UPDATE))
            if defrag_on:
                # each tick: one watch (releases the previous wave —
                # the stub's eval_update processed + terminalized it
                # synchronously) + one round
                for _ in range(rounds_per_step):
                    clock[0] += 20_000.0
                    loop.tick(now=clock[0])
                if step == 1:
                    # warmup = the cold + first-warm programs; any
                    # later growth is a steady-state recompile
                    jit_warm = jit_cache_size()
            trajectory.append(cluster_fragmentation(
                h.state.snapshot(), ["dc1"]))
        # final settle tick: release the last wave's slots
        clock[0] += 20_000.0
        loop.configure(enabled=False)
        loop.tick(now=clock[0])
        st = loop.stats()
        g = get_governor().stats()

        # Funnel sweep over every defrag eviction the arm staged: each
        # moved alloc appears in exactly ONE plan's eviction leg,
        # carries a desired-stop terminal in the store, and has exactly
        # one replacement alloc chained to it.
        staged_count = {}
        for plan in h.plans:
            for updates in plan.node_update.values():
                for victim in updates:
                    if victim.desired_description == "alloc is being migrated":
                        staged_count[victim.id] = (
                            staged_count.get(victim.id, 0) + 1)
        funnel_ok = True
        for alloc_id, count in staged_count.items():
            stored = h.state.alloc_by_id(alloc_id)
            replacements = [
                a for a in h.state.allocs()
                if a.previous_allocation == alloc_id]
            if (count != 1 or stored is None
                    or stored.desired_status != consts.ALLOC_DESIRED_STOP
                    or len(replacements) != 1):
                funnel_ok = False
        # every wave eval reached a terminal in the store
        for ev in h.state.evals():
            if ev.triggered_by == consts.EVAL_TRIGGER_DEFRAG \
                    and not ev.terminal_status():
                funnel_ok = False

        jit_end = jit_cache_size()
        return {
            "defrag": bool(defrag_on),
            "frag_start": round(trajectory[0], 4),
            "frag_final": round(trajectory[-1], 4),
            "frag_mean": round(float(np.mean(trajectory)), 4),
            "trajectory": [round(f, 4) for f in trajectory],
            "rounds": st["rounds"],
            "waves": st["waves"],
            "moves": st["moves_proposed"],
            "moves_completed": st["moves_completed"],
            "no_gain_rounds": st["no_gain_rounds"],
            "stale_discards": st["stale_discards"],
            "migration_budget": budget,
            "migration_high_water": g["high_water"],
            "governor_in_flight_end": g["in_flight"],
            "displaced_funnel_ok": bool(funnel_ok),
            "displaced_evictions": len(staged_count),
            "first_cold_solve_ms": st["first_cold_solve_ms"],
            "min_warm_solve_ms": st["min_warm_solve_ms"],
            "cold_solves": st["cold_solves"],
            "warm_solves": st["warm_solves"],
            "jit_after_warmup": jit_warm if jit_warm is not None else jit_end,
            "jit_end": jit_end,
            "jit_recompiles": (jit_end - jit_warm)
            if (defrag_on and jit_warm is not None) else 0,
        }
    finally:
        migrate_configure(migrate_max_parallel=DEFAULT_MAX_PARALLEL)


def run_defrag_ab(reps=2, check=False):
    """Continuous-defragmentation ON/OFF A/B -> BENCH_r15: identical
    seeded churn in both arms, the ON arm running the real DefragLoop
    between churn steps. Acceptance: the ON arm ends with measurably
    lower fragmentation than OFF, migration high-water <= the budget,
    every displaced alloc carries an exactly-once raft-funnel
    terminal, steady-state recompiles 0, and warm-started steady-state
    solves are measurably cheaper than the cold first solve. With
    --check, refuses to report numbers violating the funnel/recompile/
    budget contracts."""
    arms = {"on": [], "off": []}
    for rep in range(reps):
        arms["on"].append(_defrag_churn_arm(True, seed=15_000 + rep))
        arms["off"].append(_defrag_churn_arm(False, seed=15_000 + rep))

    if check:
        for rep, r in enumerate(arms["on"]):
            if not r["displaced_funnel_ok"]:
                print(f"bench: REFUSING defrag-ab numbers: rep {rep} "
                      "has a displaced alloc without an exactly-once "
                      "raft-funnel terminal", file=sys.stderr)
                sys.exit(2)
            if r["jit_recompiles"] > 0:
                print(f"bench: REFUSING defrag-ab numbers: rep {rep} "
                      f"recompiled after warmup "
                      f"({r['jit_after_warmup']} -> {r['jit_end']})",
                      file=sys.stderr)
                sys.exit(2)
            if r["migration_high_water"] > r["migration_budget"]:
                print(f"bench: REFUSING defrag-ab numbers: rep {rep} "
                      f"exceeded the migration budget "
                      f"(high-water {r['migration_high_water']} > "
                      f"{r['migration_budget']})", file=sys.stderr)
                sys.exit(2)
            if r["governor_in_flight_end"] != 0:
                print(f"bench: REFUSING defrag-ab numbers: rep {rep} "
                      f"leaked {r['governor_in_flight_end']} governor "
                      "slots", file=sys.stderr)
                sys.exit(2)

    def med(rr, key):
        m, _ = _median_iqr([float(r[key]) for r in rr])
        return m

    on, off = arms["on"], arms["off"]
    on_final = med(on, "frag_final")
    off_final = med(off, "frag_final")
    out = {
        "metric": (f"[defrag-ab churning service workload, "
                   f"median-of-{reps}] ON: final frag {on_final:.4f} "
                   f"(mean {med(on, 'frag_mean'):.4f}, "
                   f"{med(on, 'waves'):.0f} waves, "
                   f"{med(on, 'moves'):.0f} moves, high-water "
                   f"{med(on, 'migration_high_water'):.0f}/"
                   f"{on[0]['migration_budget']}); OFF: final frag "
                   f"{off_final:.4f} (mean {med(off, 'frag_mean'):.4f})"
                   f"; warm solve {med(on, 'min_warm_solve_ms'):.1f}ms"
                   f" vs cold {med(on, 'first_cold_solve_ms'):.0f}ms"),
        "defrag_on": {k: (on[0][k] if k == "trajectory"
                          else med(on, k) if isinstance(on[0][k],
                                                        (int, float))
                          else on[0][k])
                      for k in on[0]},
        "defrag_off": {k: (off[0][k] if k == "trajectory"
                           else med(off, k) if isinstance(off[0][k],
                                                          (int, float))
                           else off[0][k])
                       for k in off[0]},
        "acceptance": {
            "on_final_frag_below_off": bool(on_final < off_final),
            "frag_final_on_vs_off": [on_final, off_final],
            "migration_high_water_within_budget": all(
                r["migration_high_water"] <= r["migration_budget"]
                for r in on),
            "displaced_funnel_exactly_once": all(
                r["displaced_funnel_ok"] for r in on),
            "steady_state_recompiles_zero": all(
                r["jit_recompiles"] == 0 for r in on),
            "warm_solve_cheaper_than_cold": all(
                0 < r["min_warm_solve_ms"] < r["first_cold_solve_ms"]
                for r in on if r["warm_solves"] > 0),
        },
    }
    return out


GANG_STEP_MS = 1000.0  # sim-time per churn step (the DL-trace clock)


def _gang_churn_arm(gang_on, seed, n_racks=8, rack_size=4, steps=12,
                    k=6, arrivals_per_step=2, dl_lifetime=3):
    """One gang-ab arm: a DL-trace-shaped workload, Tesserae-style —
    large gangs (k=6 trainers, 1000cpu/1200mem each) ARRIVING OVER
    CHURN on a racked topology cluster (racks of 4 × 3000cpu/3000mem
    nodes, so an empty rack holds 8 members and a churned one may not
    hold 6), with small filler services fragmenting racks between
    arrivals. ON places each DL job as a slice gang (all-K on one
    rack); OFF places the identical asks as plain independent groups.

    Deterministic (Harness + dense factory, sim-time clock): each step
    churns a slice of filler (stop + refill — the scatter that
    fragments), lands ``arrivals_per_step`` new DL jobs, COMPLETES DL
    jobs placed ``dl_lifetime`` steps ago (training runs finish — the
    steady-state recycle that keeps gangs arriving onto partially-free
    slices instead of a saturated wall), and re-evaluates every
    not-fully-placed DL job (the blocked-eval re-run analog).
    ``gang_wait`` for a job = steps from arrival until ALL k members
    are live, in GANG_STEP_MS units — the queueing axis Tesserae says
    gang packing dominates.

    Returns gang_wait_p99_ms / slice_frag trajectory / contiguity /
    the partial-commit sweep (ON: every DL job's live member count is
    0 or exactly k at EVERY step — one partial observation anywhere
    poisons the arm) and the jit program count after warmup."""
    import random as _random

    from nomad_tpu import mock
    from nomad_tpu.gang import reset_gang_stats
    from nomad_tpu.kernels.quality import slice_frag_from_store
    from nomad_tpu.ops.binpack import jit_cache_size
    from nomad_tpu.scheduler.testing import Harness, seed_harness_cluster
    from nomad_tpu.structs import Gang, consts
    from nomad_tpu.structs.eval import new_eval as _new_eval

    rng = _random.Random(seed)
    reset_gang_stats()

    nodes = []
    for i in range(n_racks * rack_size):
        node = mock.node()
        node.resources.cpu = 3000
        node.resources.memory_mb = 3000
        node.meta["rack"] = f"r{i // rack_size}"
        node.meta["ici"] = f"r{i // rack_size}-i{(i % rack_size) // 2}"
        node.compute_class()
        nodes.append(node)
    h = Harness(seed=seed)
    seed_harness_cluster(h, nodes=nodes)
    node_rack = {n.id: n.meta["rack"] for n in nodes}

    def make_filler(idx):
        job = mock.job()
        job.id = f"filler-{idx}"
        tg = job.task_groups[0]
        tg.count = 2
        t = tg.tasks[0]
        t.resources.cpu = 600
        t.resources.memory_mb = 500
        t.resources.networks = []
        return job

    def make_dl(idx):
        job = mock.job()
        job.id = f"dl-{idx}"
        tg = job.task_groups[0]
        tg.count = k
        if gang_on:
            tg.gang = Gang(slice="rack")
        t = tg.tasks[0]
        t.resources.cpu = 1000
        t.resources.memory_mb = 1200
        t.resources.networks = []
        return job

    def register_and_eval(job):
        h.state.upsert_job(h.next_index(), job.copy())
        h.process("service-tpu", _new_eval(
            h.state.job_by_id(job.id), consts.EVAL_TRIGGER_JOB_REGISTER))

    def live_count(jid):
        return len([a for a in h.state.allocs_by_job(jid)
                    if not a.terminal_status()])

    fillers = []
    for i in range(2 * n_racks):
        job = make_filler(i)
        register_and_eval(job)
        fillers.append(job)

    pending = []  # (job, arrived_step)
    placed = {}  # job id -> (job, arrived_step, placed_step)
    placed_racks = {}  # job id -> rack set AT PLACEMENT TIME
    partial_events = 0
    frag_trajectory = []
    jit_warm = None
    arrived_total = 0
    frag_ref = make_dl(-1)  # the slice_frag reference ask/k

    for step in range(steps):
        # departures: DL jobs placed dl_lifetime steps ago complete —
        # the training run finished, the slice frees
        for jid, (dl_job, _arr, pl) in list(placed.items()):
            if pl is not None and step - pl >= dl_lifetime:
                for a in h.state.allocs_by_job(jid):
                    if not a.terminal_status():
                        done = a.copy()
                        done.desired_status = consts.ALLOC_DESIRED_STOP
                        done.client_status = consts.ALLOC_CLIENT_COMPLETE
                        h.state.upsert_allocs(h.next_index(), [done])
                placed[jid] = (dl_job, _arr, pl)

        # churn: client-complete a slice of filler allocs and refill
        # the holes — the scatter that fragments racks
        for job in fillers:
            for a in h.state.allocs_by_job(job.id):
                if not a.terminal_status() and rng.random() < 0.15:
                    stopped = a.copy()
                    stopped.desired_status = consts.ALLOC_DESIRED_STOP
                    stopped.client_status = consts.ALLOC_CLIENT_COMPLETE
                    h.state.upsert_allocs(h.next_index(), [stopped])
        for job in fillers:
            if live_count(job.id) < job.task_groups[0].count:
                h.process("service-tpu", _new_eval(
                    h.state.job_by_id(job.id),
                    consts.EVAL_TRIGGER_NODE_UPDATE))

        # arrivals: new DL jobs this step
        for _ in range(arrivals_per_step):
            job = make_dl(arrived_total)
            arrived_total += 1
            register_and_eval(job)
            pending.append((job, step))

        # blocked-gang re-runs: every not-fully-placed DL job retries
        still = []
        for dl_job, arrived in pending:
            if live_count(dl_job.id) < k and arrived != step:
                h.process("service-tpu", _new_eval(
                    h.state.job_by_id(dl_job.id),
                    consts.EVAL_TRIGGER_NODE_UPDATE))
            n_live = live_count(dl_job.id)
            if gang_on and n_live not in (0, k):
                partial_events += 1
            if n_live >= k:
                placed[dl_job.id] = (dl_job, arrived, step)
                placed_racks[dl_job.id] = {
                    node_rack[a.node_id]
                    for a in h.state.allocs_by_job(dl_job.id)
                    if not a.terminal_status()}
            else:
                still.append((dl_job, arrived))
        pending = still

        if step == 1:
            jit_warm = jit_cache_size()
        frag_trajectory.append(slice_frag_from_store(
            h.state.snapshot(), frag_ref, frag_ref.task_groups[0]))

    # contiguity: fraction of fully-placed DL jobs whose members
    # shared ONE rack at placement time (the ON arm's whole point;
    # OFF reports what scattering costs)
    contiguous = sum(1 for racks in placed_racks.values()
                     if len(racks) == 1)
    waits = [(pl - arr) * GANG_STEP_MS
             for _j, arr, pl in placed.values()]
    # still-unplaced jobs waited the whole remaining trace (censored
    # at the horizon — dropping them would reward never placing)
    waits += [(steps - arr) * GANG_STEP_MS for _j, arr in pending]
    jit_end = jit_cache_size()
    return {
        "gang": bool(gang_on),
        "jobs": arrived_total,
        "jobs_fully_placed": len(placed),
        "jobs_unplaced_at_horizon": len(pending),
        "members_live": sum(live_count(f"dl-{i}")
                            for i in range(arrived_total)),
        "partial_commit_events": partial_events,
        "placed_contiguous_frac": round(contiguous / len(placed), 4)
        if placed else 0.0,
        "gang_wait_p99_ms": round(float(np.percentile(waits, 99)), 1)
        if waits else 0.0,
        "gang_wait_mean_ms": round(float(np.mean(waits)), 1)
        if waits else 0.0,
        "slice_frag_final": round(frag_trajectory[-1], 4),
        "slice_frag_mean": round(float(np.mean(frag_trajectory)), 4),
        "slice_frag_trajectory": [round(f, 4) for f in frag_trajectory],
        "jit_after_warmup": jit_warm if jit_warm is not None else jit_end,
        "jit_end": jit_end,
        "jit_recompiles": (jit_end - jit_warm)
        if jit_warm is not None else 0,
    }


def run_gang_ab(reps=2, check=False):
    """Gang ON/OFF A/B -> BENCH_r16: the identical DL-trace-shaped
    seeded churn in both arms, ON placing slice gangs, OFF the same
    asks as independent groups. Acceptance: ON places every fully-
    placed gang on ONE contiguous rack with ZERO partial-commit
    observations and steady-state recompiles 0; the scoreboard gets
    the gang_wait_p99_ms / slice_frag columns both ways. With --check,
    refuses numbers on any partially-committed gang, a non-contiguous
    placed gang, or a recompile after warmup."""
    arms = {"on": [], "off": []}
    for rep in range(reps):
        arms["on"].append(_gang_churn_arm(True, seed=16_000 + rep))
        arms["off"].append(_gang_churn_arm(False, seed=16_000 + rep))

    if check:
        for rep, r in enumerate(arms["on"]):
            if r["partial_commit_events"]:
                print(f"bench: REFUSING gang-ab numbers: rep {rep} "
                      f"observed {r['partial_commit_events']} "
                      "partially-committed gang state(s) — the one "
                      "thing the subsystem exists to prevent",
                      file=sys.stderr)
                sys.exit(2)
            if r["jobs_fully_placed"] and \
                    r["placed_contiguous_frac"] < 1.0:
                print(f"bench: REFUSING gang-ab numbers: rep {rep} "
                      f"placed a slice gang across racks "
                      f"(contiguous {r['placed_contiguous_frac']})",
                      file=sys.stderr)
                sys.exit(2)
            if r["jit_recompiles"] > 0:
                print(f"bench: REFUSING gang-ab numbers: rep {rep} "
                      f"recompiled after warmup "
                      f"({r['jit_after_warmup']} -> {r['jit_end']})",
                      file=sys.stderr)
                sys.exit(2)

    def med(rr, key):
        m, _ = _median_iqr([float(r[key]) for r in rr])
        return m

    on, off = arms["on"], arms["off"]
    out = {
        "metric": (f"[gang-ab DL-trace churn, median-of-{reps}] "
                   f"ON: {med(on, 'jobs_fully_placed'):.0f}/"
                   f"{on[0]['jobs']} gangs on contiguous slices "
                   f"(contiguous {med(on, 'placed_contiguous_frac'):.2f},"
                   f" wait p99 {med(on, 'gang_wait_p99_ms'):.0f}ms, "
                   f"slice_frag {med(on, 'slice_frag_final'):.4f}, "
                   f"partials {med(on, 'partial_commit_events'):.0f}); "
                   f"OFF: {med(off, 'jobs_fully_placed'):.0f} placed "
                   f"(contiguous {med(off, 'placed_contiguous_frac'):.2f}"
                   f", wait p99 {med(off, 'gang_wait_p99_ms'):.0f}ms, "
                   f"slice_frag {med(off, 'slice_frag_final'):.4f})"),
        "gang_on": {k: (on[0][k] if k == "slice_frag_trajectory"
                        else med(on, k) if isinstance(on[0][k],
                                                      (int, float))
                        else on[0][k])
                    for k in on[0]},
        "gang_off": {k: (off[0][k] if k == "slice_frag_trajectory"
                         else med(off, k) if isinstance(off[0][k],
                                                        (int, float))
                         else off[0][k])
                     for k in off[0]},
        "acceptance": {
            "zero_partial_commits": all(
                r["partial_commit_events"] == 0 for r in on),
            "all_placed_gangs_contiguous": all(
                r["placed_contiguous_frac"] == 1.0
                for r in on if r["jobs_fully_placed"]),
            "steady_state_recompiles_zero": all(
                r["jit_recompiles"] == 0 for r in on),
            "gangs_placed_on": [r["jobs_fully_placed"] for r in on],
        },
    }
    return out


# ------------------------------------------------------------ scale arm

SCALE_SIZES = (10_000, 100_000)
SCALE_ALLOCS_PER_NODE = 5


def _scale_fleet(n_nodes, allocs_per_node=SCALE_ALLOCS_PER_NODE,
                 seed=17):
    """A class-compressible fleet at scale: 2 datacenters x 8 HUGE
    racks (i % 8 — rack meta enters the computed class, so per-8-node
    racks would explode C to N/8) x 2 capacity shapes = 32 signature
    classes regardless of N. Filler allocs ride build_cluster's shape
    (service, no networks, modest footprint) so every node stays
    schedulable."""
    from nomad_tpu import mock
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import consts

    rng = random.Random(seed)
    store = StateStore()
    index = 0
    filler = mock.job()
    filler.id = "filler"
    filler.type = "service"
    filler.task_groups[0].tasks[0].resources.networks = []
    for i in range(n_nodes):
        node = mock.node()
        node.datacenter = f"dc{i % 2 + 1}"
        node.meta["rack"] = f"r{i % 8}"
        if i % 4 == 0:
            node.resources.cpu //= 2
            node.resources.memory_mb //= 2
        node.compute_class()
        index += 1
        store.upsert_node(index, node)
        if allocs_per_node:
            allocs = []
            for _ in range(allocs_per_node):
                alloc = mock.alloc()
                alloc.node_id = node.id
                alloc.job_id = filler.id
                alloc.job = filler
                alloc.desired_status = consts.ALLOC_DESIRED_RUN
                alloc.client_status = consts.ALLOC_CLIENT_RUNNING
                for tr in alloc.task_resources.values():
                    tr.cpu = rng.choice((25, 50))
                    tr.memory_mb = rng.choice((32, 64))
                    tr.networks = []
                alloc.resources = None
                allocs.append(alloc)
            index += 1
            store.upsert_allocs(index, allocs)
    return store, index


def _scale_arm(n_nodes, rounds=12, seed=17):
    """One scale measurement: the compression plane's contract surface
    at N nodes / 5N allocs. The GATED placement column runs the
    class-granular path (score C class rows, expand the winning class
    to its least-filled member at rounding — the tentpole's design);
    the node-granular dense program is reported as an UNGATED reference
    column (compute-bound: it scales with N by construction, which is
    exactly why the compression plane exists). Adds the gang arm at
    scale (all-K atomicity both ways), the auto-compressed defrag
    solve (exactly-once eviction), per-shard occupancy + device-memory
    columns when a mesh is available, and steady-state recompile
    accounting across the timed rounds."""
    import jax

    from nomad_tpu.defrag.solver import WarmState, compute_defrag_plan
    from nomad_tpu.gang import build_gang_state
    from nomad_tpu.models.classes import best_member_rows
    from nomad_tpu.models.matrix import ClusterMatrix, bucket_size
    from nomad_tpu.ops.binpack import (
        PlacementConfig,
        batched_placement_program_shared,
        host_prng_key,
        jit_cache_size,
        make_asks,
        make_node_state,
        placement_program_jit,
    )
    from nomad_tpu.ops.gang import gang_placement_program_jit
    from nomad_tpu.structs import Gang

    t0 = time.perf_counter()
    store, _ = _scale_fleet(n_nodes, seed=seed)
    snap = store.snapshot()
    job = service_job(networks=False)
    job.datacenters = ["dc1", "dc2"]
    matrix = ClusterMatrix(snap, job)
    build_s = time.perf_counter() - t0
    cidx = matrix.class_index
    out = {
        "nodes": n_nodes,
        "allocs": n_nodes * SCALE_ALLOCS_PER_NODE,
        "classes": int(cidx.n_classes),
        "class_compression_ratio": round(cidx.compression_ratio(), 2),
        "fleet_build_s": round(build_s, 1),
    }

    # ---- compressed placement rounds (the gated column).
    c_pad = bucket_size(cidx.n_classes)
    ask_fields = matrix.build_asks([0] * 8)
    asks = make_asks(*ask_fields)
    ask_res = np.asarray(ask_fields[0])
    config = PlacementConfig(anti_affinity_penalty=10.0)
    batch = 8
    util = matrix.util.copy()

    def class_round(s):
        rows, cls_ok = best_member_rows(
            cidx, util, matrix.capacity, matrix.node_ok)
        g = np.zeros(c_pad, np.int64)
        g[: cidx.n_classes] = rows
        ok = np.zeros(c_pad, bool)
        ok[: cidx.n_classes] = cls_ok
        state = make_node_state(
            matrix.capacity[g], matrix.sched_capacity[g], util[g],
            matrix.bw_avail[g], matrix.bw_used[g], matrix.ports_free[g],
            matrix.job_count[g], matrix.tg_count[g],
            matrix.feasible[g] & ok[:, None], ok)
        keys = jax.random.split(jax.random.PRNGKey(s), batch)
        choices, _scores, _f = batched_placement_program_shared(
            state, asks, keys, config)
        choices = np.asarray(choices)
        # Expand: winning CLASS -> its chosen concrete member row, and
        # commit eval 0's placements so rounds see moving utilization.
        picked = np.where(choices >= 0,
                          g[np.clip(choices, 0, c_pad - 1)], -1)
        for j, row in enumerate(picked[0, :8]):
            if row >= 0:
                util[row] += ask_res[j]
        return picked

    warm = class_round(0)
    assert (warm[:, :8] >= 0).all(), "compressed warmup failed to place"
    # Steady-state recompile accounting brackets ONLY the timed rounds:
    # each later arm (dense / sharded / gang / defrag) legitimately
    # compiles its program once on first entry and brackets its own
    # timed region the same way.
    jit_before = jit_cache_size()
    lat = []
    for r in range(rounds):
        t1 = time.perf_counter()
        class_round(r + 1)
        lat.append(time.perf_counter() - t1)
    recompiles = jit_cache_size() - jit_before
    out["place_p50_ms"] = round(float(np.percentile(lat, 50)) * 1e3, 2)
    out["place_p99_ms"] = round(float(np.percentile(lat, 99)) * 1e3, 2)
    out["class_pad"] = int(c_pad)

    # ---- node-granular dense reference (UNGATED: compute-bound in N).
    state_n = make_node_state(
        matrix.capacity, matrix.sched_capacity, matrix.util,
        matrix.bw_avail, matrix.bw_used, matrix.ports_free,
        matrix.job_count, matrix.tg_count, matrix.feasible,
        matrix.node_ok)
    dev_state = jax.tree.map(jax.device_put, state_n)
    dev_asks = jax.tree.map(jax.device_put, asks)

    def dense_round(s):
        keys = jax.random.split(jax.random.PRNGKey(s), batch)
        return np.asarray(batched_placement_program_shared(
            dev_state, dev_asks, keys, config)[0])

    dense_round(0)
    jit_before = jit_cache_size()
    dlat = []
    for r in range(4):
        t1 = time.perf_counter()
        dense_round(r + 1)
        dlat.append(time.perf_counter() - t1)
    recompiles += jit_cache_size() - jit_before
    out["dense_p50_ms"] = round(float(np.percentile(dlat, 50)) * 1e3, 2)
    out["dense_p99_ms"] = round(float(np.percentile(dlat, 99)) * 1e3, 2)
    out["device_mb"] = round(
        sum(np.asarray(x).nbytes for x in dev_state) / 1e6, 1)

    # ---- sharded arm: node axis over the mesh, occupancy + memory
    # per shard (metadata reads, no extra transfers).
    n_pad = matrix.capacity.shape[0]
    n_dev = jax.device_count()
    if n_dev > 1 and n_pad % n_dev == 0:
        from nomad_tpu.parallel.mesh import (
            make_mesh,
            shard_placement_inputs,
        )
        from nomad_tpu.parallel.shard import per_shard_occupancy

        mesh = make_mesh(n_dev, dp=1)
        st_sh, asks_sh, _key_sh = shard_placement_inputs(
            mesh, state_n, asks, host_prng_key(0))
        out["per_shard_occupancy"] = per_shard_occupancy(tuple(st_sh))
        # Warm with a HOST key — the timed rounds pass one per round,
        # and a committed/uncommitted key mismatch is itself a
        # recompile the gate would (rightly) refuse.
        placement_program_jit(st_sh, asks_sh, host_prng_key(0), config)
        jit_before = jit_cache_size()
        slat = []
        for r in range(3):
            t1 = time.perf_counter()
            np.asarray(placement_program_jit(
                st_sh, asks_sh, host_prng_key(r + 1), config)[0])
            slat.append(time.perf_counter() - t1)
        recompiles += jit_cache_size() - jit_before
        out["sharded_p50_ms"] = round(
            float(np.percentile(slat, 50)) * 1e3, 2)
        out["shards"] = n_dev
    else:
        out["per_shard_occupancy"] = []
        out["shards"] = 1

    # ---- gang arm at scale: slice gangs against the 8 huge racks.
    gang_job = service_job(networks=False)
    gang_job.datacenters = ["dc1", "dc2"]
    tg = gang_job.task_groups[0]
    tg.count = 8
    tg.gang = Gang(slice="rack")
    gm = ClusterMatrix(snap, gang_job)
    gstate, active, (g_res, g_bw, g_ports), gconfig = build_gang_state(
        gm, gang_job, tg)
    choices = np.asarray(gang_placement_program_jit(
        gstate, g_res, g_bw, g_ports, active, host_prng_key(3),
        gconfig)[0])
    placed = choices[: tg.count]
    out["gang_all_k_placed"] = bool((placed >= 0).all())
    impossible = g_res.copy()
    impossible[0] = 1e9  # no node fits one member, let alone K
    rejected = np.asarray(gang_placement_program_jit(
        gstate, impossible, g_bw, g_ports, active, host_prng_key(4),
        gconfig)[0])
    out["gang_reject_atomic"] = bool((rejected == -1).all())

    # ---- defrag arm: the global solve auto-compresses past
    # CLASS_COMPRESS_MIN_NODES; moves must name distinct allocs
    # (exactly-once eviction).
    t1 = time.perf_counter()
    plan = compute_defrag_plan(snap, ["dc1", "dc2"], max_moves=8,
                               min_gain=0.0, warm=WarmState(),
                               movable_cap=256)
    out["defrag_s"] = round(time.perf_counter() - t1, 2)
    out["defrag_compressed"] = bool(plan.compressed)
    out["defrag_classes"] = int(plan.classes)
    out["defrag_moves"] = len(plan.moves)
    out["defrag_exactly_once"] = (
        len({m.alloc_id for m in plan.moves}) == len(plan.moves))

    out["jit_recompiles"] = int(recompiles)
    return out


def run_scale(check=False):
    """The 100k-node / 500k-alloc scale config -> BENCH_r17: compressed
    placement p50/p99 at 10k and 100k (acceptance: the 100k p99 within
    2x the 10k figure — the whole point of scoring C classes instead of
    N nodes), class_compression_ratio / per-shard occupancy /
    device-memory columns, the gang arm at scale, and the
    auto-compressed defrag solve. With --check, refuses numbers on
    steady-state recompiles > 0, compression ratio < 2x, a broken gang
    atomicity flag, a double-evicting defrag move set, or a 100k p99
    past the 2x envelope."""
    arms = {n: _scale_arm(n) for n in SCALE_SIZES}
    a10, a100 = arms[SCALE_SIZES[0]], arms[SCALE_SIZES[1]]
    within_2x = a100["place_p99_ms"] <= 2.0 * a10["place_p99_ms"]
    acceptance = {
        "p99_100k_within_2x_of_10k": bool(within_2x),
        "compression_ratio_ge_2": all(
            a["class_compression_ratio"] >= 2.0 for a in arms.values()),
        "steady_state_recompiles_zero": all(
            a["jit_recompiles"] == 0 for a in arms.values()),
        "gang_atomicity": all(
            a["gang_all_k_placed"] and a["gang_reject_atomic"]
            for a in arms.values()),
        "defrag_compressed_at_100k": a100["defrag_compressed"],
        "defrag_exactly_once": all(
            a["defrag_exactly_once"] for a in arms.values()),
    }
    if check:
        for name, ok in acceptance.items():
            if not ok:
                print(f"bench: REFUSING scale numbers: acceptance "
                      f"'{name}' failed "
                      f"(10k={a10}, 100k={a100})", file=sys.stderr)
                sys.exit(2)
    out = {
        "metric": (
            f"[scale {SCALE_SIZES[1] // 1000}k nodes / "
            f"{SCALE_SIZES[1] * SCALE_ALLOCS_PER_NODE // 1000}k allocs] "
            f"compressed placement p99 "
            f"{a100['place_p99_ms']:.1f}ms at 100k vs "
            f"{a10['place_p99_ms']:.1f}ms at 10k "
            f"({'within' if within_2x else 'OUTSIDE'} 2x; dense "
            f"node-granular reference {a100['dense_p99_ms']:.0f}ms), "
            f"ratio {a100['class_compression_ratio']:.0f}x "
            f"({a100['classes']} classes), "
            f"defrag {'compressed' if a100['defrag_compressed'] else 'dense'} "
            f"{a100['defrag_moves']} moves, recompiles "
            f"{a100['jit_recompiles']}"),
        "scale_10k": a10,
        "scale_100k": a100,
        "acceptance": acceptance,
    }
    return out


def _exec_profile_snapshot():
    """Per-arm convoy/runq/dispatch-gap columns — the exact axes
    BENCH_r13 measured on the pre-executive shape (convoy width 63/64,
    runq.batch_park p99 55.1ms, dispatch p99−p50 gap 44.7ms). Each
    executive-ab arm reads these off a freshly-reset profiler/recorder
    so the paired arms never share histograms."""
    from nomad_tpu.trace import get_recorder

    cols = _profile_cols()
    stages = get_recorder().stage_stats()
    dd = stages.get("device.dispatch", {})
    p50 = dd.get("p50_ms", 0.0)
    p99 = dd.get("p99_ms", 0.0)
    return {
        "convoy_width": cols.get("convoy_width", 0),
        "runq_batch_park_p99_ms": cols.get("profile", {}).get(
            "runq_p99_ms", {}).get("batch_park", 0.0),
        "lock_wait_p99_ms": cols.get("lock_wait_p99_ms", 0.0),
        "dispatch_p50_ms": p50,
        "dispatch_p99_ms": p99,
        "dispatch_gap_ms": round(max(0.0, p99 - p50), 3),
        "device_sync_p99_ms": stages.get("device.solve",
                                         {}).get("p99_ms", 0.0),
    }


def _exec_arm_config4(executive):
    """Config 4's e2e shape, one arm: the measured path BENCH_r13
    profiled. `executive=False` is the legacy 64-thread worker shape
    (the before picture); True is the cohort-row shape."""
    from nomad_tpu.profile import get_profiler
    from nomad_tpu.trace import get_recorder

    get_recorder().reset()
    get_profiler().reset()
    store, _ = build_cluster(10_000, datacenters=("dc1", "dc2"),
                             allocs_per_node=5)
    job = service_job(networks=True, distinct_hosts=True)
    job.datacenters = ["dc1", "dc2"]
    job.task_groups[0].count = 8
    e2e_rate, e2e_p99, ds = bench_tpu_e2e(
        store, job, 8, batch=64, rounds=3, executive=executive)
    return {
        "e2e": e2e_rate, "e2e_p99_ms": e2e_p99 * 1000,
        "occupancy": ds["occupancy"],
        "jit_recompiles": ds["jit_recompiles"],
        "funnel_terminals_ok": 1.0,  # harness shape: no live evals
        **_exec_profile_snapshot(),
    }


def _exec_live_arm(n_nodes, n_jobs, allocs_per_job, executive,
                   drain_frac=0.1, warm_jobs=None):
    """One LIVE executive-vs-workers arm (the configs-5/7 churn shape,
    scaled live-feasible): real server, storm against a parked drain,
    then a drain wave so displaced allocs flow through the executive's
    legacy lane + migration machinery. Returns throughput plus the
    BENCH_r13 contention axes and the two --check gate inputs:
    steady-state recompiles and the raft-funnel terminal sweep (every
    eval in FSM state terminal after settle)."""
    from nomad_tpu import mock
    from nomad_tpu.profile import get_profiler
    from nomad_tpu.scheduler.batcher import get_batcher
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs import consts
    from nomad_tpu.trace import get_recorder

    get_recorder().reset()
    get_profiler().reset()
    server = Server(ServerConfig(
        num_schedulers=4,
        scheduler_factories={"service": "service-tpu"},
        scheduler_executive=executive,
        eval_nack_timeout=60.0))
    server.start()

    def pause(flag):
        for w in server.workers:
            w.set_pause(flag)
        server.executive.set_pause(flag)

    def make_job(jid):
        job = mock.job()
        job.id = jid
        job.type = "service"
        job.task_groups[0].count = allocs_per_job
        t = job.task_groups[0].tasks[0]
        t.resources.networks = []
        t.resources.cpu = 20
        t.resources.memory_mb = 16
        return job

    def wait_evals(evs, deadline_s):
        deadline = time.perf_counter() + deadline_s
        while time.perf_counter() < deadline:
            st = [server.fsm.state.eval_by_id(e) for e in evs]
            if all(s is not None and s.terminal_status() for s in st):
                return True
            time.sleep(0.02)
        return False

    try:
        nodes = []
        for _ in range(n_nodes):
            node = mock.node()
            node.compute_class()
            server.log.apply("node_register", {"node": node})
            nodes.append(node)
        # Warm wave (unmeasured), sized LIKE the measured storm so its
        # cohort lands in the same batch bucket — a smaller warm wave
        # leaves the storm's padded program uncompiled and the
        # recompile gate would (rightly) refuse.
        warm = [make_job(f"xwarm-{j}")
                for j in range(warm_jobs or n_jobs)]
        pause(True)
        wevals = [server.job_register(j)[0] for j in warm]
        pause(False)
        assert wait_evals(wevals, 300), "warm wave never settled"
        for j in warm:
            server.job_deregister(j.id)
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            s = server.broker.stats()
            if not s["total_ready"] and not s["total_unacked"]:
                break
            time.sleep(0.05)
        jit0 = get_batcher().stats()["jit_cache_size"]

        # Measured storm.
        jobs = [make_job(f"xstorm-{j}") for j in range(n_jobs)]
        pause(True)
        evals = [server.job_register(j)[0] for j in jobs]
        t0 = time.perf_counter()
        pause(False)
        assert wait_evals(evals, 300), "storm never settled"
        storm_elapsed = time.perf_counter() - t0
        # The recompile gate reads the STORM window (the steady-state
        # claim); the drain wave below adds churn-shaped programs the
        # warm wave deliberately does not cover.
        jit_storm = get_batcher().stats()["jit_cache_size"]
        placed = sum(
            1 for j in jobs for a in server.fsm.state.allocs_by_job(j.id)
            if not a.terminal_status())

        # Drain wave: displaced allocs re-place (the churn shape the
        # executive's legacy lane + migration budget own).
        occupancy = {}
        for a in server.fsm.state.allocs():
            if not a.terminal_status():
                occupancy[a.node_id] = occupancy.get(a.node_id, 0) + 1
        by_load = sorted(occupancy, key=occupancy.get, reverse=True)
        drained = set(by_load[: max(1, int(n_nodes * drain_frac))])
        for nid in drained:
            server.node_update_drain(nid, True)
        deadline = time.perf_counter() + 180
        replaced = False
        while time.perf_counter() < deadline:
            live = {j.id: [a for a in server.fsm.state.allocs_by_job(j.id)
                           if not a.terminal_status()] for j in jobs}
            s = server.broker.stats()
            if (all(len(v) == allocs_per_job for v in live.values())
                    and all(a.node_id not in drained
                            for v in live.values() for a in v)
                    and not s["total_ready"] and not s["total_unacked"]
                    and not s["total_waiting"]):
                replaced = True
                break
            time.sleep(0.05)
        jit1 = get_batcher().stats()["jit_cache_size"]
        # Raft-funnel terminal sweep: every eval this arm minted must
        # hold exactly one terminal status in FSM state (the --check
        # refusal input — a pending/unacked eval after settle means a
        # lost terminal). Brief re-check loop: the last no-op
        # follow-up's status write can land milliseconds after the
        # broker reads quiet.
        terminal_ok = False
        deadline = time.perf_counter() + 15
        while time.perf_counter() < deadline and not terminal_ok:
            terminal_ok = all(
                e.terminal_status()
                or e.status == consts.EVAL_STATUS_BLOCKED
                for e in server.fsm.state.evals())
            if not terminal_ok:
                time.sleep(0.05)
        ex = server.stats()["scheduler_executive"]
        return {
            "e2e": n_jobs / storm_elapsed,
            "placed_frac": placed / (n_jobs * allocs_per_job),
            "drain_replaced": float(replaced),
            "jit_recompiles": jit_storm - jit0,
            "jit_drain_wave_programs": jit1 - jit_storm,
            "funnel_terminals_ok": float(terminal_ok),
            "executive_fast_evals": ex["fast_evals"],
            "executive_legacy_evals": ex["legacy_evals"],
            "executive_occupancy": ex["occupancy"],
            **_exec_profile_snapshot(),
        }
    finally:
        server.shutdown()


EXECUTIVE_AB_LIVE_ARMS = {
    # configs 5/7's churn shapes, scaled to live-feasible sizes.
    "config5": (600, 36, 4),
    "config7": (300, 24, 4),
}


def run_executive_ab(reps=2, check=False):
    """Paired executive-vs-workers A/B (the PR 12 tentpole's headline
    rig) -> BENCH_r14.json: config 4's measured e2e shape plus live
    churn arms at configs 5/7's shapes, each rep running both arms back
    to back so host drift cancels. Emits the BENCH_r13 before-picture
    axes per arm — convoy_width, runq.batch_park p99, dispatch p99−p50
    — and with --check refuses executive numbers if steady-state
    recompiles > 0 or any live eval lacks a raft-funnel terminal."""
    arms = {}
    plan = {"config4": None}
    plan.update(EXECUTIVE_AB_LIVE_ARMS)
    for arm_name, shape in plan.items():
        runs = {"executive": [], "workers": []}
        for _ in range(reps):
            for mode, flag in (("executive", True), ("workers", False)):
                if shape is None:
                    runs[mode].append(_exec_arm_config4(flag))
                else:
                    runs[mode].append(_exec_live_arm(*shape, flag))
        per_mode = {}
        for mode, rr in runs.items():
            per_mode[mode] = {
                k: round(_median_iqr([float(r[k]) for r in rr])[0], 4)
                for k in rr[0]}
        ex, wk = per_mode["executive"], per_mode["workers"]
        arms[arm_name] = {
            "modes": per_mode,
            "speed_ratio": round(ex["e2e"] / wk["e2e"], 3)
            if wk["e2e"] else 0.0,
            "convoy_width_before_after": [wk["convoy_width"],
                                          ex["convoy_width"]],
            "runq_batch_park_p99_before_after_ms": [
                wk["runq_batch_park_p99_ms"],
                ex["runq_batch_park_p99_ms"]],
            "dispatch_gap_before_after_ms": [wk["dispatch_gap_ms"],
                                             ex["dispatch_gap_ms"]],
        }
        if check:
            if ex["jit_recompiles"] > 0:
                print(f"bench: REFUSING executive-ab numbers: arm "
                      f"{arm_name!r} recompiled in steady state "
                      f"(jit_recompiles={ex['jit_recompiles']})",
                      file=sys.stderr)
                sys.exit(2)
            if ex["funnel_terminals_ok"] < 1.0:
                print(f"bench: REFUSING executive-ab numbers: arm "
                      f"{arm_name!r} left evals without a raft-funnel "
                      f"terminal after settle", file=sys.stderr)
                sys.exit(2)
    from nomad_tpu.server.config import ServerConfig as _SC

    bound = 2 * _SC().dispatch_max_inflight
    summary = "; ".join(
        f"{name}: x{a['speed_ratio']:.2f} speed, convoy "
        f"{a['convoy_width_before_after'][0]:.0f}->"
        f"{a['convoy_width_before_after'][1]:.0f}, batch_park p99 "
        f"{a['runq_batch_park_p99_before_after_ms'][0]:.1f}->"
        f"{a['runq_batch_park_p99_before_after_ms'][1]:.1f}ms"
        for name, a in arms.items())
    return {
        "metric": f"[executive-ab vs workers, median-of-{reps}] "
                  + summary,
        "arms": arms,
        "convoy_bound": bound,
        "acceptance": {
            # The tentpole's measured claims: the convoy is gone on
            # every arm, and the headline (config 4) shape is faster.
            # Live churn-arm ratios are reported as-is: on a CPU-only
            # host with a sub-ms inline "device", thread-per-eval's
            # fine-grained overlap can still edge out single-cohort
            # storms — the remote-device regime (~100ms RTT/dispatch,
            # the r05/r06 transport analysis) is where fewer, fuller,
            # no-park cohorts win outright.
            "convoy_within_bound": all(
                a["convoy_width_before_after"][1] <= bound
                for a in arms.values()),
            "config4_faster": bool(
                arms["config4"]["speed_ratio"] >= 1.0),
        },
    }


def _convoy_gate(out, n):
    """--check (PR 12): dense-path numbers measured through a wide
    batch-boundary convoy describe the thread-parked legacy shape, not
    the executive pipeline — a convoy wider than 2x the dispatch
    in-flight bound means eval threads piled up on batcher events
    (BENCH_r13's measured pathology). Refuse."""
    from nomad_tpu.server.config import ServerConfig as _SC

    bound = 2 * _SC().dispatch_max_inflight
    cw = out.get("columns", {}).get("convoy_width", {}).get("median", 0)
    if cw and cw > bound:
        print(f"bench: REFUSING to report config {n}: convoy_width "
              f"{cw:.0f} > {bound} (2x dispatch_max_inflight) — eval "
              f"threads convoyed at the batch boundary; run the "
              f"scheduler-executive shape or fix the park regression",
              file=sys.stderr)
        sys.exit(2)


# The dirs the --check gates sweep. Module constants so the ntalint
# self-checks (tests/test_static_analysis.py) can assert the kernels
# subsystem is inside both gates rather than trusting a string copy.
PURITY_GATE_DIRS = ("ops", "scheduler", "kernels", "migrate",
                    "defrag", "gang")
CONCURRENCY_GATE_DIRS = ("nomad_tpu/dispatch/", "nomad_tpu/scheduler/",
                         "nomad_tpu/server/", "nomad_tpu/kernels/",
                         "nomad_tpu/migrate/", "nomad_tpu/defrag/",
                         "nomad_tpu/gang/")
COMPILE_SURFACE_GATE_DIRS = ("nomad_tpu/ops/", "nomad_tpu/kernels/",
                             "nomad_tpu/models/", "nomad_tpu/parallel/")


def ntalint_compile_surface_gate():
    """Compile-surface findings invalidate dense-path numbers before a
    single device call runs: an unbucketed shape or a drifting static
    key IS the recompile storm the jit_recompiles column would catch a
    full bench rep later, and an unregistered jit entry point means
    that column is blind. This gate runs FIRST under --check — pure
    host AST work, so a compile-surface regression fails in ~1s
    instead of after warmup. Whole-tree analysis (whole-program
    rules), findings filtered to the jit-accounted dirs. Returns the
    non-baselined findings."""
    import os

    from nomad_tpu.analysis import (
        analyze_paths,
        apply_baseline,
        load_baseline,
    )
    from nomad_tpu.analysis.compile_surface import (
        RULE_DONATION,
        RULE_KEY_DRIFT,
        RULE_UNBUCKETED,
        RULE_UNREGISTERED,
    )

    root = os.path.dirname(os.path.abspath(__file__))
    findings = analyze_paths(
        [os.path.join(root, "nomad_tpu")],
        rules={RULE_UNBUCKETED, RULE_KEY_DRIFT, RULE_UNREGISTERED,
               RULE_DONATION, "parse-error"})
    new, _stale = apply_baseline(findings, load_baseline())
    return [f for f in new if f.path.startswith(COMPILE_SURFACE_GATE_DIRS)]


def ntalint_purity_gate():
    """Trace-purity findings in the kernel path (ops/, scheduler/,
    kernels/) invalidate dense-path numbers BY CONSTRUCTION: an impure
    call or a host sync inside a jitted program means the benchmark
    measured a host fallback or a trace-time constant, not the device
    path it claims to. Returns the non-baselined findings."""
    import os

    from nomad_tpu.analysis import (
        analyze_paths,
        apply_baseline,
        load_baseline,
    )
    from nomad_tpu.analysis import purity

    root = os.path.dirname(os.path.abspath(__file__))
    # The checker's own constants, not string copies: a renamed rule id
    # must break this gate loudly, not silently filter every finding.
    # parse-error rides along: a file the analyzer could not parse got
    # ZERO purity analysis — "gate clean" would be a lie for it.
    purity_rules = {purity.RULE_IMPURE, purity.RULE_HOST_SYNC,
                    purity.RULE_CLOSURE_MUT, purity.RULE_BRANCH,
                    purity.RULE_STATIC, "parse-error"}
    findings = analyze_paths(
        [os.path.join(root, "nomad_tpu", d) for d in PURITY_GATE_DIRS],
        rules=purity_rules)
    new, _stale = apply_baseline(findings, load_baseline())
    return new


def ntalint_concurrency_gate():
    """Deadlock-cycle / raft-funnel findings in the dispatch, scheduler
    or server paths invalidate dense-path numbers the same way purity
    findings do: a lock-order cycle means the measured throughput is
    one unlucky interleaving away from a frozen pipeline, and a
    raft-funnel violation means the eval terminals the benchmark
    counts can double-commit or never commit. Whole-tree analysis
    (these are whole-program rules — edges through utils/ and models/
    are the point), findings filtered to the gated dirs. Returns the
    non-baselined findings."""
    import os

    from nomad_tpu.analysis import (
        analyze_paths,
        apply_baseline,
        load_baseline,
    )
    from nomad_tpu.analysis.deadlock import RULE_DEADLOCK
    from nomad_tpu.analysis.protocol import RULE_FUNNEL

    root = os.path.dirname(os.path.abspath(__file__))
    findings = analyze_paths(
        [os.path.join(root, "nomad_tpu")],
        rules={RULE_DEADLOCK, RULE_FUNNEL, "parse-error"})
    new, _stale = apply_baseline(findings, load_baseline())
    return [f for f in new if f.path.startswith(CONCURRENCY_GATE_DIRS)]


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=int, default=HEADLINE_CONFIG,
                        choices=sorted(CONFIGS))
    parser.add_argument("--all", action="store_true")
    parser.add_argument("--reps", type=int, default=DEFAULT_REPS,
                        help="interleaved CPU/TPU repetitions per config;"
                             " medians + IQR are reported")
    parser.add_argument("--check", action="store_true",
                        help="run the ntalint compile-surface gate "
                             "(jit-cache bounding / shape buckets), "
                             "then the trace-purity and concurrency "
                             "gates, before any device warmup; refuse "
                             "to report dense-path numbers on findings")
    parser.add_argument("--chaos", type=int, default=None, metavar="SEED",
                        help="run config 4 clean AND under a mild seeded "
                             "fault schedule (nomad_tpu/chaos); reports "
                             "degraded-mode occupancy + retries/eval "
                             "alongside the clean numbers")
    parser.add_argument("--overload", type=int, default=None,
                        metavar="SEED",
                        help="overload A/B on the live pipeline "
                             "(nomad_tpu/admission): measure capacity, "
                             "storm at 3x, report shed_rate / goodput / "
                             "accepted-eval p99 with protection on vs "
                             "off")
    parser.add_argument("--resident-ab", action="store_true",
                        help="device-resident state ON/OFF A/B on "
                             "config 4 (models/resident.py) — the "
                             "BENCH_r10 arm. With --check, refuses "
                             "numbers unless ON >= OFF on every config "
                             "(the PR 12 inversion-flip gate)")
    parser.add_argument("--executive-ab", action="store_true",
                        help="paired scheduler-executive vs "
                             "thread-per-eval-workers A/B "
                             "(server/executive.py) on config 4's e2e "
                             "shape + live churn arms at configs 5/7's "
                             "shapes, emitting convoy_width / "
                             "runq.batch_park p99 / dispatch p99-p50 "
                             "against the BENCH_r13 before-picture — "
                             "the BENCH_r14 arm. With --check, refuses "
                             "executive numbers on steady-state "
                             "recompiles or missing raft-funnel "
                             "terminals")
    parser.add_argument("--executive-ab-reps", type=int, default=2,
                        help="paired reps per executive-ab arm")
    parser.add_argument("--resident-ab-configs", type=str, default="",
                        help="comma-separated config numbers for the "
                             "resident A/B (default: the headline "
                             "config); the --check ON >= OFF gate "
                             "applies to every listed config")
    parser.add_argument("--kernel-ab", action="store_true",
                        help="placement-kernel A/B (nomad_tpu/kernels):"
                             " greedy vs convex on config 4's shape + "
                             "a fragmentation-heavy arm, throughput "
                             "and quality columns — the BENCH_r11 arm."
                             " With --check, kernels must pass the "
                             "oracle differential rig first")
    parser.add_argument("--kernel-ab-reps", type=int, default=3,
                        help="interleaved reps per kernel-ab arm")
    parser.add_argument("--preempt-ab", action="store_true",
                        help="priority-preemption ON/OFF A/B under a "
                             "3x priority storm (nomad_tpu/migrate + "
                             "ops/preempt.py) — the BENCH_r12 arm. "
                             "With --check, refuses numbers if any "
                             "eviction lacks a raft-funnel terminal")
    parser.add_argument("--preempt-ab-reps", type=int, default=3,
                        help="reps per preempt-ab arm")
    parser.add_argument("--defrag-ab", action="store_true",
                        help="continuous-defragmentation ON/OFF A/B: "
                             "fragmentation trajectory under identical "
                             "seeded churn, waves through the real "
                             "DefragLoop under the migration budget "
                             "(BENCH_r15)")
    parser.add_argument("--defrag-ab-reps", type=int, default=2,
                        help="seeded churn reps per defrag-ab arm")
    parser.add_argument("--gang-ab", action="store_true",
                        help="gang ON/OFF A/B on a DL-trace-shaped "
                             "arm (large slice gangs arriving over "
                             "churn, Tesserae-style), scored on "
                             "gang_wait_p99_ms / slice_frag; with "
                             "--check refuses numbers on any "
                             "partially-committed gang, a "
                             "non-contiguous placed slice gang, or "
                             "steady-state recompiles > 0")
    parser.add_argument("--gang-ab-reps", type=int, default=2,
                        help="seeded churn reps per gang-ab arm")
    parser.add_argument("--scale", action="store_true",
                        help="the 100k-node / 500k-alloc compression-"
                             "plane config (models/classes.py + "
                             "parallel/shard.py) — the BENCH_r17 arm: "
                             "class-granular placement p50/p99 at 10k "
                             "vs 100k, class_compression_ratio / "
                             "per-shard occupancy / device-memory "
                             "columns, gang + defrag arms at scale. "
                             "With --check, refuses numbers on "
                             "steady-state recompiles > 0, ratio < 2x, "
                             "or a 100k p99 past 2x the 10k figure")
    parser.add_argument("--read-storm", action="store_true",
                        help="read-plane storm A/B (nomad_tpu/readplane):"
                             " park N blocking queries on disjoint "
                             "scopes with 1 write client per 10 "
                             "watchers, mux vs thread-park baseline — "
                             "parked-thread footprint, wake-to-serve "
                             "p99, spurious ratio, stale-vs-consistent "
                             "read latency. With --check, refuses "
                             "numbers on spurious > 1% or a mux "
                             "footprint that scales with watchers")
    parser.add_argument("--read-storm-watchers", type=int, default=200,
                        help="parked watchers per read-storm arm")
    parser.add_argument("--no-trace", action="store_true",
                        help="disable the eval-lifecycle flight recorder "
                             "(nomad_tpu/trace) for this run — the A/B "
                             "arm the --check overhead gate compares "
                             "against")
    parser.add_argument("--profile-off", action="store_true",
                        help="disable the contention observatory "
                             "(nomad_tpu/profile) for this run — the "
                             "paired arm --profile-ab compares against")
    parser.add_argument("--profile-ab", action="store_true",
                        help="paired profiler-on/profiler-off A/B on "
                             "one config: contention columns "
                             "(lock_wait_p99_ms / gil_overshoot_p99_ms "
                             "/ convoy_width), the device.dispatch "
                             "p99-p50 gap attribution, and the paired "
                             "overhead ratio — the BENCH_r13 arm. With "
                             "--check, refuses numbers if the median "
                             "paired e2e ratio < 0.95")
    args = parser.parse_args()

    from nomad_tpu.profile import get_profiler
    from nomad_tpu.trace import get_recorder

    if args.no_trace:
        get_recorder().set_enabled(False)
    if args.profile_off:
        get_profiler().configure(enabled=False)
    else:
        # Always-on means the bench measures what production runs:
        # recording enabled and the GIL sampler live.
        get_profiler().ensure_sampler()

    if args.check:
        bad = ntalint_compile_surface_gate()
        if bad:
            for f in bad:
                print(f.render(), file=sys.stderr)
            print(f"bench: REFUSING to report dense-path numbers: "
                  f"{len(bad)} compile-surface finding(s) in ops//"
                  f"kernels//models//parallel/ — the jit cache is no "
                  f"longer statically bounded (fix them or run "
                  f"without --check)", file=sys.stderr)
            sys.exit(2)
        print("bench: ntalint compile-surface gate clean",
              file=sys.stderr)
        bad = ntalint_purity_gate()
        if bad:
            for f in bad:
                print(f.render(), file=sys.stderr)
            print(f"bench: REFUSING to report dense-path numbers: "
                  f"{len(bad)} trace-purity finding(s) in ops//"
                  f"scheduler/ (fix them or run without --check)",
                  file=sys.stderr)
            sys.exit(2)
        print("bench: ntalint trace-purity gate clean", file=sys.stderr)
        bad = ntalint_concurrency_gate()
        if bad:
            for f in bad:
                print(f.render(), file=sys.stderr)
            print(f"bench: REFUSING to report dense-path numbers: "
                  f"{len(bad)} deadlock-cycle/raft-funnel finding(s) "
                  f"in dispatch//scheduler//server/ (fix them or run "
                  f"without --check)", file=sys.stderr)
            sys.exit(2)
        print("bench: ntalint deadlock/raft-funnel gate clean",
              file=sys.stderr)

    if args.check and not args.no_trace and (args.all
                                             or args.chaos is not None):
        # The trace-overhead A/B gate needs paired traced/untraced runs
        # of ONE config; doubling the whole matrix (--all) or the chaos
        # A/B would conflate arms. Say so loudly — a silent skip would
        # read as "gate passed".
        print("bench: NOTE --check's trace-overhead gate only applies "
              "to single-config runs; run `bench.py --check --config "
              f"{HEADLINE_CONFIG}` for the gated traced-vs-untraced "
              "comparison (the purity gate above DID run)",
              file=sys.stderr)

    if args.profile_ab:
        if args.profile_off:
            print("bench: --profile-ab and --profile-off are mutually "
                  "exclusive (the A/B runs both arms itself)",
                  file=sys.stderr)
            sys.exit(2)
        out, ratio = run_config_profile_ab(args.config, reps=args.reps)
        if args.check:
            _shed_gate(out, args.config)
            _recompile_gate(out, args.config)
            _convoy_gate(out, args.config)
            if ratio < 0.95:
                print(json.dumps(out), file=sys.stderr)
                print(f"bench: REFUSING to report — the contention "
                      f"observatory cost {(1 - ratio) * 100:.1f}% of "
                      f"median paired e2e (> 5% budget; per-rep ratios "
                      f"{out['profile_overhead']['per_rep_ratios']})",
                      file=sys.stderr)
                sys.exit(2)
        print(json.dumps(out))
        return

    if args.executive_ab:
        print(json.dumps(run_executive_ab(reps=args.executive_ab_reps,
                                          check=args.check)))
        return

    if args.kernel_ab:
        print(json.dumps(run_kernel_ab(reps=args.kernel_ab_reps,
                                       check=args.check)))
        return

    if args.preempt_ab:
        print(json.dumps(run_preempt_ab(reps=args.preempt_ab_reps,
                                        check=args.check)))
        return

    if args.defrag_ab:
        print(json.dumps(run_defrag_ab(reps=args.defrag_ab_reps,
                                       check=args.check)))
        return

    if args.scale:
        print(json.dumps(run_scale(check=args.check)))
        return

    if args.gang_ab:
        print(json.dumps(run_gang_ab(reps=args.gang_ab_reps,
                                     check=args.check)))
        return

    if args.resident_ab:
        configs = (tuple(int(c) for c in
                         args.resident_ab_configs.split(",") if c)
                   or (None,))
        out = run_resident_ab(reps=args.reps, configs=configs)
        if args.check:
            _shed_gate(out["resident_on"], HEADLINE_CONFIG)
            _recompile_gate(out["resident_on"], HEADLINE_CONFIG)
            _convoy_gate(out["resident_on"], HEADLINE_CONFIG)
            if not out["on_ge_off_every_config"]:
                print("bench: REFUSING resident-ab numbers: resident "
                      "ON < OFF — the delta machinery is paying "
                      "contention again (the BENCH_r10 inversion the "
                      "executive removed); fix the regression",
                      file=sys.stderr)
                sys.exit(2)
        print(json.dumps(out))
        return

    if args.read_storm:
        print(json.dumps(run_read_storm(
            n_watchers=args.read_storm_watchers, check=args.check)))
        return

    if args.chaos is not None:
        print(json.dumps(run_chaos(args.chaos)))
        return

    if args.overload is not None:
        print(json.dumps(run_overload(args.overload)))
        return

    if args.all:
        for n in sorted(CONFIGS):
            out = run_config(n, reps=args.reps)
            if args.check:
                _shed_gate(out, n)
                _recompile_gate(out, n)
                _convoy_gate(out, n)
            print(json.dumps(out))
        return

    if args.check and not args.no_trace:
        # Trace-overhead gate: the always-on recorder must be close to
        # free. Each rep runs traced then untraced back to back and
        # the gate reads the MEDIAN of per-rep ratios — refusing to
        # report if tracing cost more than 5% of median e2e.
        out, ratio = run_config_trace_ab(args.config, reps=args.reps)
        if ratio < 0.95:
            print(json.dumps(out), file=sys.stderr)
            print(f"bench: REFUSING to report — tracing cost "
                  f"{(1 - ratio) * 100:.1f}% of median e2e (> 5% "
                  f"budget; per-rep ratios "
                  f"{out['trace_overhead']['per_rep_ratios']})",
                  file=sys.stderr)
            sys.exit(2)
    else:
        out = run_config(args.config, reps=args.reps)
    if args.check:
        _shed_gate(out, args.config)
        _recompile_gate(out, args.config)
        _convoy_gate(out, args.config)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
