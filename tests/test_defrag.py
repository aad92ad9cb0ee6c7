"""Continuous defragmentation (nomad_tpu/defrag): solver units,
warm-start semantics, wave staging through the real scheduler, the
loop's gates (pressure / leadership / staleness / budget), chaos-site
determinism, and the stats/metrics/trace surfaces."""

import time
import types

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import FaultSpec, chaos
from nomad_tpu.defrag import (
    DefragLoop,
    WarmState,
    build_wave_evals,
    cluster_fragmentation,
    compute_defrag_plan,
    reference_asks,
    solve_cache_size,
)
from nomad_tpu.migrate import configure as migrate_configure
from nomad_tpu.migrate import get_governor
from nomad_tpu.scheduler.testing import Harness, seed_harness_cluster
from nomad_tpu.server.config import ServerConfig
from nomad_tpu.structs import consts
from nomad_tpu.structs.eval import Evaluation


# --------------------------------------------------------------- fixtures


@pytest.fixture(autouse=True)
def _governor_hygiene():
    """The migration governor is process-global and several tests here
    deliberately leave waves in flight (gate tests never settle their
    evals): return every leaked slot and re-baseline so neither the
    next test in this file nor the rest of the suite inherits a
    pre-spent budget."""
    migrate_configure(migrate_max_parallel=32)
    yield
    g = get_governor()
    leaked = g.stats()["in_flight"]
    if leaked:
        g.release(leaked)
    migrate_configure(migrate_max_parallel=32)
    g.reset_stats()


def _mkjob(jid, count, cpu, mem):
    job = mock.job()
    job.id = jid
    job.task_groups[0].count = count
    task = job.task_groups[0].tasks[0]
    task.resources.cpu = cpu
    task.resources.memory_mb = mem
    task.resources.networks = []
    return job


def _mkalloc(job, slot, node, cpu, mem):
    from nomad_tpu.structs import Resources

    a = mock.alloc()
    a.job = job
    a.job_id = job.id
    a.name = f"{job.name}.web[{slot}]"
    a.task_group = "web"
    a.node_id = node.id
    a.resources = None
    a.task_resources = {"web": Resources(cpu=cpu, memory_mb=mem)}
    a.shared_resources = None
    a.desired_status = consts.ALLOC_DESIRED_RUN
    a.client_status = consts.ALLOC_CLIENT_RUNNING
    return a


def fragmented_harness(seed=1, n_nodes=24):
    """A DETERMINISTIC fragmented service cluster (hand-placed, not
    scheduler-placed — uuid tie-breaks would vary the layout per
    process): nodes[0..7] hold one 600 each (free 400, strands the
    600-ref), nodes[8..15] hold two 300s each (free 400, same), the
    rest are empty. Consolidating 300s pairwise is a strict
    fragmentation win the solver must find."""
    h = Harness(seed=seed)
    nodes = []
    for _ in range(n_nodes):
        node = mock.node()
        node.resources.cpu = 1000
        node.resources.memory_mb = 1000
        node.reserved = None
        node.compute_class()
        nodes.append(node)
    big = _mkjob("fbig", 8, 600, 600)
    big.name = "fbig"
    s0 = _mkjob("fs0", 8, 300, 300)
    s0.name = "fs0"
    s1 = _mkjob("fs1", 8, 300, 300)
    s1.name = "fs1"
    allocs = [_mkalloc(big, i, nodes[i], 600, 600) for i in range(8)]
    for i in range(8):
        allocs.append(_mkalloc(s0, i, nodes[8 + i], 300, 300))
        allocs.append(_mkalloc(s1, i, nodes[8 + i], 300, 300))
    seed_harness_cluster(h, nodes=nodes, allocs=allocs,
                         jobs=[big, s0, s1])
    # re-point the denormalized job refs at the STORED jobs (the store
    # assigns modify indexes; a mismatch would route the diff's
    # existing allocs to the update bucket)
    stored = {j.id: h.state.job_by_id(j.id) for j in (big, s0, s1)}
    fixed = []
    for a in h.state.allocs():
        upd = a.copy()
        upd.job = stored[a.job_id]
        fixed.append(upd)
    seed_harness_cluster(h, allocs=fixed)
    return h


# ----------------------------------------------------------- solver units


def test_reference_asks_frequency_weighted():
    asks = np.array([[300, 300, 0, 0]] * 3 + [[600, 600, 0, 0]] * 1,
                    np.float64)
    refs = reference_asks(asks)
    assert len(refs) == 2
    # most-common first, weights sum to 1
    assert refs[0][1] == pytest.approx(0.75)
    assert list(refs[0][0][:2]) == [300, 300]
    assert sum(w for _a, w in refs) == pytest.approx(1.0)
    assert reference_asks(np.zeros((0, 4))) == []


def test_solver_finds_consolidation_gain_and_respects_cap():
    h = fragmented_harness()
    snap = h.state.snapshot()
    warm = WarmState()
    plan = compute_defrag_plan(snap, ["dc1"], max_moves=3,
                               min_gain=0.001, warm=warm)
    assert plan.movable > 0
    assert plan.gain > 0
    assert 0 < len(plan.moves) <= 3
    assert plan.frag_after < plan.frag_before
    # per-move gains sum to the net gain
    assert sum(m.gain for m in plan.moves) == pytest.approx(
        plan.gain, abs=1e-9)
    # every move names a real alloc, its real node, and a different
    # target
    for mv in plan.moves:
        stored = snap.alloc_by_id(mv.alloc_id)
        assert stored is not None and stored.node_id == mv.from_node
        assert mv.to_node != mv.from_node


def test_solver_min_gain_gate_suppresses_moves():
    h = fragmented_harness()
    plan = compute_defrag_plan(h.state.snapshot(), ["dc1"], max_moves=8,
                               min_gain=10.0, warm=WarmState())
    assert plan.moves == []
    assert plan.gain < 10.0


def test_warm_start_carries_and_key_mismatch_drops():
    h = fragmented_harness()
    snap = h.state.snapshot()
    warm = WarmState()
    p1 = compute_defrag_plan(snap, ["dc1"], max_moves=8, min_gain=0.0,
                             warm=warm)
    assert not p1.warm and p1.carried == 0
    p2 = compute_defrag_plan(snap, ["dc1"], max_moves=8, min_gain=0.0,
                             warm=warm)
    assert p2.warm and p2.carried == p2.movable
    # Node registration moves the family signature: the carry drops.
    node = mock.node()
    node.compute_class()
    seed_harness_cluster(h, nodes=[node])
    p3 = compute_defrag_plan(h.state.snapshot(), ["dc1"], max_moves=8,
                             min_gain=0.0, warm=warm)
    assert not p3.warm and p3.carried == 0


def test_steady_state_solver_compiles_stay_flat():
    h = fragmented_harness()
    snap = h.state.snapshot()
    warm = WarmState()
    compute_defrag_plan(snap, ["dc1"], max_moves=4, min_gain=0.0,
                        warm=warm)
    compute_defrag_plan(snap, ["dc1"], max_moves=4, min_gain=0.0,
                        warm=warm)
    programs = solve_cache_size()
    assert programs >= 2  # cold + warm for this shape
    for _ in range(3):
        compute_defrag_plan(snap, ["dc1"], max_moves=4, min_gain=0.0,
                            warm=warm)
    assert solve_cache_size() == programs  # steady state: FLAT
    # ... and the placement path's jit accounting sees the defrag
    # programs (a shape leak here must move jit_cache_size).
    from nomad_tpu.ops.binpack import jit_cache_size

    assert jit_cache_size() >= programs


def test_cluster_fragmentation_matches_plan_frag_before():
    h = fragmented_harness()
    snap = h.state.snapshot()
    measured = cluster_fragmentation(snap, ["dc1"])
    plan = compute_defrag_plan(snap, ["dc1"], max_moves=4, min_gain=0.0,
                               warm=WarmState())
    assert measured == pytest.approx(plan.frag_before, abs=1e-9)


# ------------------------------------------------- wave through scheduler


def _drive_wave(h, factory="service", max_moves=8):
    snap = h.state.snapshot()
    plan = compute_defrag_plan(snap, ["dc1"], max_moves=max_moves,
                               min_gain=0.001, warm=WarmState())
    evals = build_wave_evals(snap, plan.moves)
    for ev in evals:
        h.process(factory, ev)
    return plan, evals


@pytest.mark.parametrize("factory", ["service", "service-tpu"])
def test_wave_moves_allocs_with_exactly_once_evictions(factory):
    h = fragmented_harness()
    want_live = {
        j.id: len([a for a in h.state.allocs_by_job(j.id)
                   if not a.terminal_status()])
        for j in h.state.jobs()}
    frag0 = cluster_fragmentation(h.state.snapshot(), ["dc1"])
    plan, evals = _drive_wave(h, factory=factory)
    assert plan.moves and evals
    # every moved alloc: exactly one eviction terminal, a replacement
    # alloc exists, and the job never shrank
    for mv in plan.moves:
        stored = h.state.alloc_by_id(mv.alloc_id)
        assert stored is not None
        assert stored.desired_status == consts.ALLOC_DESIRED_STOP
        replacements = [
            a for a in h.state.allocs_by_job(mv.job_id)
            if a.previous_allocation == mv.alloc_id
            and not a.terminal_status()]
        assert len(replacements) == 1, mv
    for job_id, want in want_live.items():
        got = len([a for a in h.state.allocs_by_job(job_id)
                   if not a.terminal_status()])
        assert got >= want, (job_id, want, got)
    if factory == "service":
        # The wave evals also REFILL the churned holes (count
        # reconciliation), which the solver's move model does not
        # cover; the dense factory's noisy tie-breaks can spend in one
        # wave what the moves gained, so the single-wave trajectory
        # assert stays on the deterministic host factory — the
        # multi-wave trajectory (both paths) is the bench --defrag-ab
        # arm's acceptance, and the live e2e test below covers the
        # dense path without refills.
        frag1 = cluster_fragmentation(h.state.snapshot(), ["dc1"])
        assert frag1 < frag0


def test_wave_replacements_prefer_solver_targets():
    h = fragmented_harness()
    plan, _evals = _drive_wave(h)
    targets = {m.alloc_id: m.to_node for m in plan.moves}
    hits = total = 0
    for a in h.state.allocs():
        if a.previous_allocation in targets and not a.terminal_status():
            total += 1
            hits += a.node_id == targets[a.previous_allocation]
    assert total == len(plan.moves)
    # The target is a preference, not a mandate: per-job wave evals
    # process in job order while the solver's trail interleaves jobs,
    # so a later eval can find its target already taken by an earlier
    # replacement and fall back. The majority must still land where
    # the solver pointed, or the preference plumbing is dead.
    assert hits >= max(1, total // 2), (hits, total)


def test_defrag_eval_is_budget_exempt_but_drains_still_claim():
    """The loop pre-claims governor slots for marked allocs; the
    scheduler must NOT re-claim them (a max_parallel=1 budget would
    otherwise defer all but one move per wave)."""
    h = fragmented_harness()
    migrate_configure(migrate_max_parallel=1)
    try:
        get_governor().reset_stats()
        plan, _evals = _drive_wave(h, max_moves=4)
        assert len(plan.moves) >= 2
        g = get_governor().stats()
        # nothing claimed, nothing deferred by the scheduler side
        assert g["granted_total"] == 0 and g["deferred_total"] == 0
        for mv in plan.moves:
            stored = h.state.alloc_by_id(mv.alloc_id)
            assert stored.desired_status == consts.ALLOC_DESIRED_STOP
    finally:
        migrate_configure(migrate_max_parallel=32)


def test_defrag_eval_fields_survive_wire_roundtrip():
    from nomad_tpu.utils.codec import from_dict, to_dict

    ev = Evaluation(
        id="e1", type="service",
        triggered_by=consts.EVAL_TRIGGER_DEFRAG, job_id="j1",
        status=consts.EVAL_STATUS_PENDING,
        defrag_alloc_ids=["a1", "a2"],
        defrag_targets={"a1": "n1", "a2": "n2"})
    back = from_dict(Evaluation, to_dict(ev))
    assert back.defrag_alloc_ids == ["a1", "a2"]
    assert back.defrag_targets == {"a1": "n1", "a2": "n2"}


# -------------------------------------------------------- oracle judging


def test_judge_migration_plan_accepts_real_wave_and_catches_tampering():
    from nomad_tpu.kernels.differential import judge_migration_plan

    h = fragmented_harness()
    snap = h.state.snapshot()
    plan = compute_defrag_plan(snap, ["dc1"], max_moves=4,
                               min_gain=0.001, warm=WarmState())
    assert plan.moves
    wave_plans = []
    for ev in build_wave_evals(snap, plan.moves):
        # judge each plan against the snapshot its eval ran on (an
        # earlier eval's committed eviction frees real room)
        ev_snap = h.state.snapshot()
        seen = len(h.plans)
        h.process("service", ev)
        for wp in h.plans[seen:]:
            assert judge_migration_plan(ev_snap, wp) == []
            wave_plans.append(wp)
    assert wave_plans
    snap = h.state.snapshot()  # tampering is judged vs CURRENT state
    # Tamper: a victim that does not exist, and a terminal victim —
    # the oracle must name both.
    wp = wave_plans[0]
    node_id = next(iter(wp.node_update))
    ghost = wp.node_update[node_id][0].copy()
    ghost.id = "ghost-alloc"
    wp.node_update[node_id].append(ghost)
    bad = judge_migration_plan(snap, wp)
    assert any("ghost-alloc does not exist" in v for v in bad)
    wp.node_update[node_id].pop()
    terminal = next(a for a in snap.allocs() if a.terminal_status())
    wp.node_update.setdefault(terminal.node_id, []).append(
        terminal.copy())
    bad = judge_migration_plan(snap, wp)
    assert any("already terminal" in v for v in bad)


def test_defrag_differential_rig_green():
    from nomad_tpu.kernels.differential import run_defrag_differential

    report = run_defrag_differential(seeds=range(8100, 8103))
    assert report["waves"] > 0
    assert report["green"], report["violations"]


# ------------------------------------------------------------- loop gates


class _StubServer:
    """The slice of Server the loop touches, fully deterministic."""

    def __init__(self, harness, **cfg):
        defaults = dict(defrag_enabled=True, defrag_interval=0.01,
                        defrag_min_gain=0.001,
                        defrag_max_moves_per_wave=8)
        defaults.update(cfg)
        self.config = ServerConfig(**defaults)
        self.harness = harness
        self.fsm = types.SimpleNamespace(state=harness.state)
        self.leader = True
        self.level = "green"
        self.admission = types.SimpleNamespace(level=lambda: self.level)
        self.submitted = []

    def is_leader(self):
        return self.leader

    def eval_update(self, evals):
        self.submitted.extend(evals)
        # park them pending in the store so the wave watch sees them
        self.harness.state.upsert_evals(
            self.harness.next_index(), [e.copy() for e in evals])


def _terminalize(stub, evals):
    done = []
    for ev in evals:
        upd = ev.copy()
        upd.status = consts.EVAL_STATUS_COMPLETE
        done.append(upd)
    stub.harness.state.upsert_evals(stub.harness.next_index(), done)


def test_loop_round_claims_and_releases_governor_slots():
    h = fragmented_harness()
    stub = _StubServer(h, defrag_interval=10_000.0)
    loop = DefragLoop(stub)
    get_governor().reset_stats()
    base = get_governor().stats()["in_flight"]
    loop.tick(now=1000.0)
    st = loop.stats()
    assert st["rounds"] == 1 and st["waves"] == 1
    assert stub.submitted
    held = get_governor().stats()["in_flight"] - base
    assert held == st["wave_in_flight"] > 0
    # wave still pending: a second tick keeps holding (one wave at a
    # time, no new round)
    loop.tick(now=1001.0)
    assert loop.stats()["rounds"] == 1
    _terminalize(stub, stub.submitted)
    loop.tick(now=1002.0)
    st = loop.stats()
    assert st["wave_in_flight"] == 0
    assert st["moves_completed"] == held
    assert get_governor().stats()["in_flight"] == base


def test_loop_pressure_gate_backs_off():
    h = fragmented_harness()
    stub = _StubServer(h, defrag_interval=100.0)
    stub.level = "red"
    loop = DefragLoop(stub)
    loop.tick(now=1000.0)
    st = loop.stats()
    assert st["rounds"] == 0 and st["pressure_skips"] == 1
    # red compounds the backoff: the next eligible round is pushed
    # past interval * 2
    loop.tick(now=1000.0 + stub.config.defrag_interval * 1.5)
    assert loop.stats()["rounds"] == 0
    stub.level = "green"
    loop.tick(now=2000.0)
    assert loop.stats()["rounds"] == 1


def test_loop_leadership_loss_abandons_wave_and_pauses():
    h = fragmented_harness()
    stub = _StubServer(h, defrag_interval=10_000.0)
    loop = DefragLoop(stub)
    base = get_governor().stats()["in_flight"]
    loop.tick(now=1000.0)
    assert loop.stats()["wave_in_flight"] > 0
    stub.leader = False
    loop.tick(now=1001.0)
    st = loop.stats()
    assert st["wave_in_flight"] == 0 and st["waves_lost"] == 1
    assert get_governor().stats()["in_flight"] == base
    # paused: no rounds while not leader
    loop.tick(now=5000.0)
    assert loop.stats()["rounds"] == 1


def test_loop_wave_timeout_releases_slots():
    from nomad_tpu.defrag import WAVE_TIMEOUT

    h = fragmented_harness()
    stub = _StubServer(h, defrag_interval=10_000.0)
    loop = DefragLoop(stub)
    base = get_governor().stats()["in_flight"]
    loop.tick(now=1000.0)
    assert loop.stats()["wave_in_flight"] > 0
    with loop._lock:
        loop._wave_started = time.monotonic() - WAVE_TIMEOUT - 1
    loop.tick(now=1001.0)
    assert loop.stats()["waves_lost"] == 1
    assert get_governor().stats()["in_flight"] == base


def test_loop_disabled_does_nothing():
    h = fragmented_harness()
    stub = _StubServer(h, defrag_enabled=False)
    loop = DefragLoop(stub)
    loop.tick(now=1000.0)
    assert loop.stats()["rounds"] == 0 and not stub.submitted


# ------------------------------------------------------------ chaos sites


def test_chaos_solve_stale_discards_wave_and_warm_carry():
    h = fragmented_harness()
    stub = _StubServer(h, defrag_interval=100.0)
    loop = DefragLoop(stub)
    with chaos.armed(77, [FaultSpec("defrag.solve_stale", "drop",
                                    count=1)]):
        loop.tick(now=1000.0)
        st = loop.stats()
        assert st["stale_discards"] == 1
        assert st["waves"] == 0 and not stub.submitted
        assert loop._warm.key is None  # carry dropped with the chain
        assert chaos.firing_log()
    # next round proposes normally
    loop.tick(now=2000.0)
    assert loop.stats()["waves"] == 1


def test_chaos_wave_lost_releases_slots_exactly():
    h = fragmented_harness()
    stub = _StubServer(h, defrag_interval=10_000.0)
    loop = DefragLoop(stub)
    base = get_governor().stats()["in_flight"]
    loop.tick(now=1000.0)
    held = loop.stats()["wave_in_flight"]
    assert held > 0
    with chaos.armed(78, [FaultSpec("defrag.wave_lost", "drop",
                                    count=1)]):
        loop.tick(now=1001.0)
        st = loop.stats()
        assert st["waves_lost"] == 1 and st["wave_in_flight"] == 0
        assert get_governor().stats()["in_flight"] == base
        assert chaos.firing_log()


def test_defrag_chaos_sites_deterministic_firing_log():
    """Same seed + schedule -> identical firing log (the registry's
    replay contract, same shape as the churn-site test)."""

    def drive():
        h = fragmented_harness()
        stub = _StubServer(h, defrag_interval=100.0)
        loop = DefragLoop(stub)
        loop.tick(now=1000.0)  # solve fires defrag.solve_stale
        loop.tick(now=2000.0)  # wave watch fires defrag.wave_lost
        loop.tick(now=3000.0)
        return chaos.firing_log()

    schedule = [FaultSpec("defrag.solve_stale", "drop", prob=0.5),
                FaultSpec("defrag.wave_lost", "drop", prob=0.5)]
    with chaos.armed(2027, [FaultSpec(s.site, s.kind, prob=s.prob)
                            for s in schedule]):
        log1 = drive()
    with chaos.armed(2027, [FaultSpec(s.site, s.kind, prob=s.prob)
                            for s in schedule]):
        log2 = drive()
    assert log1 == log2
    assert {s for s, _n, _k, _d in log1} <= {"defrag.solve_stale",
                                             "defrag.wave_lost"}


def test_defrag_sites_registered_and_documented():
    import os

    from nomad_tpu.chaos.registry import KNOWN_SITES

    assert "defrag.solve_stale" in KNOWN_SITES
    assert "defrag.wave_lost" in KNOWN_SITES
    readme = open(os.path.join(os.path.dirname(__file__), "..",
                               "README.md")).read()
    for site in ("defrag.solve_stale", "defrag.wave_lost"):
        assert f"`{site}`" in readme, site


# --------------------------------------------------------------- surfaces


def test_defrag_stage_registered_and_documented():
    import os

    from nomad_tpu.trace import ALL_STAGES, STAGE_DEFRAG_SOLVE

    assert STAGE_DEFRAG_SOLVE in ALL_STAGES
    root = os.path.join(os.path.dirname(__file__), "..")
    readme = open(os.path.join(root, "README.md")).read()
    trace_readme = open(os.path.join(
        root, "nomad_tpu", "trace", "README.md")).read()
    assert STAGE_DEFRAG_SOLVE in readme
    assert STAGE_DEFRAG_SOLVE in trace_readme


def test_loop_round_records_trace_stage():
    from nomad_tpu import trace

    trace.get_recorder().reset()
    h = fragmented_harness()
    stub = _StubServer(h)
    loop = DefragLoop(stub)
    loop.run_round()
    stages = trace.get_recorder().stage_stats()
    assert stages.get("defrag.solve", {}).get("count", 0) >= 1


def test_defrag_knobs_flow_from_config():
    h = fragmented_harness()
    stub = _StubServer(h, defrag_enabled=True, defrag_interval=7.5,
                       defrag_min_gain=0.25,
                       defrag_max_moves_per_wave=3)
    loop = DefragLoop(stub)
    st = loop.stats()
    assert st["enabled"] and st["interval"] == 7.5
    assert st["min_gain"] == 0.25 and st["max_moves_per_wave"] == 3
    loop.configure(enabled=False, max_moves=5)
    st = loop.stats()
    assert not st["enabled"] and st["max_moves_per_wave"] == 5


def test_defrag_hcl_and_cli_knobs_registered():
    from nomad_tpu.cli.agent_config import _SCHEMA, ServerBlock

    for key in ("server.defrag_enabled", "server.defrag_interval",
                "server.defrag_min_gain",
                "server.defrag_max_moves_per_wave"):
        assert key in _SCHEMA, key
    blk = ServerBlock()
    for field_name in ("defrag_enabled", "defrag_interval",
                       "defrag_min_gain", "defrag_max_moves_per_wave"):
        assert hasattr(blk, field_name), field_name


# ---------------------------------------------------- live server e2e


def test_live_server_defrag_loop_end_to_end():
    """The real thing: a dev server with the loop enabled converges a
    churned cluster — waves committed under the governor cap, slots
    fully released, fragmentation measurably down, trace stage + stats
    populated, warm solves cheap."""
    from nomad_tpu.server import Server

    migrate_configure(migrate_max_parallel=32)
    get_governor().reset_stats()
    server = Server(ServerConfig(
        num_schedulers=2,
        defrag_enabled=True, defrag_interval=0.25,
        defrag_min_gain=0.001, defrag_max_moves_per_wave=8))
    server.start()
    try:
        for _ in range(24):
            node = mock.node()
            node.resources.cpu = 1000
            node.resources.memory_mb = 1000
            node.reserved = None
            node.compute_class()
            server.log.apply("node_register", {"node": node})
        jobs = ([_mkjob(f"big{j}", 4, 600, 600) for j in range(3)]
                + [_mkjob(f"small{j}", 6, 300, 300) for j in range(4)])
        for job in jobs:
            job.type = "service"
        eval_ids = [server.job_register(job)[0] for job in jobs]
        deadline = time.time() + 120
        while time.time() < deadline:
            evs = [server.fsm.state.eval_by_id(e) for e in eval_ids]
            if all(e is not None and e.terminal_status() for e in evs):
                break
            time.sleep(0.05)
        server.job_deregister("small0")  # churn: leave holes
        time.sleep(1.0)
        frag0 = cluster_fragmentation(
            server.fsm.state.snapshot(), ["dc1"])
        deadline = time.time() + 60
        while time.time() < deadline:
            st = server.stats()["defrag"]
            if st["waves"] >= 1 and st["wave_in_flight"] == 0 \
                    and st["warm_solves"] >= 1:
                break
            time.sleep(0.1)
        st = server.stats()["defrag"]
        assert st["waves"] >= 1, st
        assert st["moves_completed"] == st["moves_proposed"], st
        g = get_governor().stats()
        assert g["in_flight"] == 0, g
        assert g["high_water"] <= server.config.migrate_max_parallel
        # displaced allocs: exactly-once eviction terminals
        for a in server.fsm.state.allocs():
            if a.desired_description == "alloc is being migrated":
                assert a.desired_status == consts.ALLOC_DESIRED_STOP
        # the trajectory moved the right way (or was already optimal,
        # in which case no wave would have fired — asserted above)
        frag1 = cluster_fragmentation(
            server.fsm.state.snapshot(), ["dc1"])
        assert frag1 <= frag0 + 1e-9
        assert server.stats()["trace"].get("defrag.solve", {}).get(
            "count", 0) >= 1
        # warm solves measurably cheaper than the cold first solve
        assert st["warm_solves"] >= 1 and st["cold_solves"] >= 1
        assert st["min_warm_solve_ms"] < st["first_cold_solve_ms"]
    finally:
        server.shutdown()


def test_wave_evals_ride_the_dispatch_pipeline_on_a_live_dense_server():
    """A wave's evals on a dense server: handed to the pipeline as one
    batch, each stops exactly the allocation its move marks and places
    the replacement; nothing goes to the host route. The moves are
    made by hand, so the layout the scheduler happened to choose does
    not decide whether a wave exists."""
    from nomad_tpu.defrag.solver import Move
    from nomad_tpu.scheduler.util import ALLOC_MIGRATING
    from nomad_tpu.server import Server
    from nomad_tpu.server.worker import DEQUEUE_TIMEOUT

    def wait_until(fn, timeout=90.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if fn():
                return True
            time.sleep(0.02)
        return False

    def storm(register):
        """Run `register` against parked workers, release them at once."""
        for w in server.workers:
            w.set_pause(True)
        time.sleep(DEQUEUE_TIMEOUT + 0.3)
        ready = server.broker.ready_count()
        eval_ids = register()
        assert wait_until(
            lambda: server.broker.ready_count() >= ready + len(eval_ids),
            15.0)
        for w in server.workers:
            w.set_pause(False)
        state = server.fsm.state
        assert wait_until(lambda: all(
            (ev := state.eval_by_id(e)) is not None
            and ev.terminal_status() for e in eval_ids)), [
            getattr(state.eval_by_id(e), "status", None) for e in eval_ids]

    server = Server(ServerConfig(
        num_schedulers=2, scheduler_factories={"service": "service-tpu"}))
    server.start()
    try:
        nodes = []
        for _ in range(12):
            node = mock.node()
            node.compute_class()
            server.node_register(node)
            nodes.append(node)
        jobs = [_mkjob(f"wave{j}", 5, 100, 64) for j in range(3)]
        for job in jobs:
            job.type = "service"
        storm(lambda: [server.job_register(job)[0] for job in jobs])
        state = server.fsm.state

        def live(job):
            return [a for a in state.allocs_by_job(job.id)
                    if not a.terminal_status()]

        assert all(len(live(job)) == 5 for job in jobs)
        moves = []
        for job in jobs[:2]:
            victim = live(job)[0]
            taken = {a.node_id for a in live(job)}
            target = next(n.id for n in nodes if n.id not in taken)
            moves.append(Move(victim.id, job.id, victim.node_id, target,
                              0.01))
        routed = server.dispatch.stats()["routed_host"]
        batches = server.dispatch.stats()["batches"]
        wave = build_wave_evals(state.snapshot(), moves)
        assert len(wave) == 2

        def submit():
            server.eval_update(wave)
            return [ev.id for ev in wave]

        storm(submit)
        stats = server.dispatch.stats()
        assert stats["routed_host"] == routed, stats
        assert stats["batches"] > batches, stats
        for mv in moves:
            moved = state.alloc_by_id(mv.alloc_id)
            assert moved.desired_status == consts.ALLOC_DESIRED_STOP
            assert moved.desired_description == ALLOC_MIGRATING
        for job in jobs:
            assert len(live(job)) == 5
        marked = {mv.alloc_id for mv in moves}
        stopped = {a.id for job in jobs for a in state.allocs_by_job(job.id)
                   if a.desired_status == consts.ALLOC_DESIRED_STOP}
        assert stopped == marked  # exactly the wave's, exactly once
    finally:
        server.shutdown()


# ------------------------------------------------- quality windowing


def test_quality_board_window_snapshot_reads_only_new_samples():
    from nomad_tpu.kernels.quality import QualityBoard

    board = QualityBoard()
    for _ in range(10):
        board.note_plan("greedy", 0.5, 0.5)
    board.reset_window()
    snap = board.window_snapshot()
    assert snap["kernels"] == {}  # nothing since the mark
    for _ in range(4):
        board.note_plan("greedy", 0.1, 0.9)
    snap = board.window_snapshot(reset=True)
    q = snap["kernels"]["greedy"]
    assert q["samples"] == 4
    assert q["fragmentation"] == pytest.approx(0.1)
    assert q["binpack_score"] == pytest.approx(0.9)
    # lifetime medians still blend both eras
    life = board.snapshot()["kernels"]["greedy"]
    assert life["samples"] == 14
    assert life["fragmentation"] == pytest.approx(0.5)
    # the reset=True re-marked: an empty interval follows
    assert board.window_snapshot()["kernels"] == {}


def test_quality_window_queueing_delta():
    from nomad_tpu import trace
    from nomad_tpu.kernels.quality import QualityBoard

    rec = trace.get_recorder()
    rec.reset()
    board = QualityBoard()
    t0 = time.monotonic()
    rec.record_span("q1", "broker.wait", t0 - 0.5, t0)  # 500ms
    board.reset_window()
    snap = board.window_snapshot()
    assert snap["queueing_delay_ms"] == 0.0  # pre-mark sample excluded
    rec.record_span("q2", "broker.wait", t0 - 0.005, t0)  # 5ms
    snap = board.window_snapshot()
    assert 0 < snap["queueing_delay_ms"] < 100.0


def test_window_gauges_surface_on_metrics_exposition():
    from nomad_tpu.utils.metrics import Metrics, format_prometheus

    m = Metrics(prefix="nomad_tpu")
    m.set_gauge(("placement_quality", "greedy",
                 "window_fragmentation"), 0.125)
    m.set_gauge(("placement_quality", "window",
                 "queueing_delay_ms"), 2.5)
    m.set_gauge(("defrag", "last_gain"), 0.03)
    text = format_prometheus(m)
    assert ("nomad_tpu_placement_quality_greedy_window_fragmentation "
            "0.125") in text
    assert "nomad_tpu_placement_quality_window_queueing_delay_ms" in text
    assert "nomad_tpu_defrag_last_gain" in text


def test_server_stats_exposes_defrag_surface():
    h = fragmented_harness()
    stub = _StubServer(h)
    loop = DefragLoop(stub)
    st = loop.stats()
    for key in ("enabled", "rounds", "waves", "waves_lost",
                "moves_proposed", "moves_completed", "pressure_skips",
                "budget_skips", "stale_discards", "cold_solves",
                "warm_solves", "last_gain", "last_fragmentation",
                "last_solve_ms", "solve_programs", "wave_in_flight"):
        assert key in st, key
