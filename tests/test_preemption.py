"""Priority preemption as a dense kernel pass (ops/preempt.py +
scheduler/tpu.py + the Plan.node_preemptions leg): kernel-level victim
selection invariants, the plan applier's per-victim verification, the
CPU-oracle differential judgment, the full-cluster priority-storm soak
with preemption ON vs OFF (eligibility comes from capacity: a cluster
with headroom never evicts, a full one does whatever the control
plane's pressure reads), victim-lost chaos, and jit-cache stability
with the preemption leg compiled in."""

import random
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import FaultSpec, chaos
from nomad_tpu.migrate import (
    configure,
    preempt_stats,
    select_victims_host,
    victim_priority,
)
from nomad_tpu.ops.binpack import (
    PlacementConfig,
    host_prng_key,
    make_asks,
    make_node_state,
)
from nomad_tpu.ops.preempt import (
    PREEMPT_MAX_VICTIMS,
    make_victim_state,
    preempt_placement_program_jit,
    unpack_result,
)
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import consts
from nomad_tpu.structs.eval import new_eval

V = PREEMPT_MAX_VICTIMS


@pytest.fixture(autouse=True)
def _restore_globals():
    yield
    chaos.disarm()
    configure(migrate_max_parallel=32, preemption_enabled=False,
              preempt_priority_threshold=50)


def wait_until(fn, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------
# kernel units


def _kernel_state(n=4, util=90.0, cap=100.0):
    capacity = np.full((n, 4), cap, np.float32)
    return make_node_state(
        capacity=capacity, sched_capacity=capacity,
        util=np.full((n, 4), util, np.float32),
        bw_avail=np.full(n, 1000.0), bw_used=np.zeros(n),
        ports_free=np.full(n, 20.0),
        job_count=np.zeros(n), tg_count=np.zeros((n, 1)),
        feasible=np.ones((n, 1), bool), node_ok=np.ones(n, bool),
    )


def _kernel_asks(k, res):
    return make_asks(
        resources=np.full((k, 4), res, np.float32), bw=np.zeros(k),
        ports=np.zeros(k), tg_index=np.zeros(k, np.int32),
        active=np.ones(k, bool), job_distinct_hosts=False,
        tg_distinct_hosts=np.zeros(1, bool))


def _victims(n, entries):
    """entries: {node_row: [(res, prio), ...]} priority-ascending."""
    res = np.zeros((n, V, 4), np.float32)
    bw = np.zeros((n, V), np.float32)
    ports = np.zeros((n, V), np.float32)
    prio = np.full((n, V), np.inf, np.float32)
    ok = np.zeros((n, V), bool)
    for row, lst in entries.items():
        for v, (r, p) in enumerate(lst):
            res[row, v] = r
            prio[row, v] = p
            ok[row, v] = True
    # the program takes the node axis last: [4, V, N] and [V, N]
    return make_victim_state(res.T, bw.T, ports.T, prio.T, ok.T)


CFG = PlacementConfig(anti_affinity_penalty=10.0)


def test_kernel_selects_lowest_priority_prefix():
    state = _kernel_state()
    victims = _victims(4, {0: [(30.0, 10), (30.0, 20)]})
    asks = _kernel_asks(2, 25.0)
    choices, _s, counts = unpack_result(preempt_placement_program_jit(
        state, victims, asks, host_prng_key(7), np.float32(50.0), CFG))
    # Both asks land on node 0, each consuming ONE victim in sorted
    # order; the scan carries consumption so the second ask needs the
    # second victim.
    assert list(choices) == [0, 0]
    assert list(counts) == [1, 1]


def test_kernel_prefers_normal_fit_over_preemption():
    state = _kernel_state(util=90.0)
    # node 2 has headroom without eviction
    state.util[2, :] = 10.0
    victims = _victims(4, {0: [(60.0, 10)], 1: [(60.0, 10)]})
    asks = _kernel_asks(1, 25.0)
    choices, _s, counts = unpack_result(preempt_placement_program_jit(
        state, victims, asks, host_prng_key(3), np.float32(50.0), CFG))
    assert int(choices[0]) == 2
    assert int(counts[0]) == 0  # no eviction needed


def test_kernel_never_evicts_equal_or_higher_priority():
    state = _kernel_state()
    victims = _victims(4, {0: [(60.0, 50)], 1: [(60.0, 80)]})
    asks = _kernel_asks(1, 25.0)
    choices, _s, counts = unpack_result(preempt_placement_program_jit(
        state, victims, asks, host_prng_key(5), np.float32(50.0), CFG))
    # eval priority 50: neither the prio-50 nor the prio-80 victim is
    # outrankable -> no placement at all
    assert int(choices[0]) == -1
    assert int(counts[0]) == 0


def test_kernel_prefix_stops_at_first_fit():
    state = _kernel_state(util=95.0)
    # evicting the first (prio 5, 40 units) suffices for a 25 ask;
    # the prio-30 second victim must survive
    victims = _victims(4, {1: [(40.0, 5), (40.0, 30)]})
    asks = _kernel_asks(1, 25.0)
    choices, _s, counts = unpack_result(preempt_placement_program_jit(
        state, victims, asks, host_prng_key(9), np.float32(50.0), CFG))
    assert int(choices[0]) == 1
    assert int(counts[0]) == 1


# ---------------------------------------------------------------------
# host oracle


def _stub_alloc(prio, cpu, create_index=0):
    a = mock.alloc()
    job = mock.job()
    job.priority = prio
    a.job = job
    a.job_id = job.id
    a.create_index = create_index
    a.task_resources = {
        "web": __import__(
            "nomad_tpu.structs", fromlist=["Resources"]).Resources(
                cpu=cpu, memory_mb=10)}
    a.shared_resources = None
    return a


def test_select_victims_host_lowest_first_minimal_prefix():
    allocs = [_stub_alloc(30, 100, 2), _stub_alloc(10, 100, 1),
              _stub_alloc(20, 100, 3)]
    victims = select_victims_host(allocs, (150.0, 0, 0, 0), 50)
    assert [victim_priority(a) for a in victims] == [10, 20]
    assert select_victims_host(allocs, (1000.0, 0, 0, 0), 50) is None
    # priority gate: nothing outrankable
    assert select_victims_host(allocs, (50.0, 0, 0, 0), 10) is None


# ---------------------------------------------------------------------
# plan-applier verification of the preemption leg


def _applier_fixture():
    server = Server(ServerConfig(num_schedulers=0))
    server.start()
    node = mock.node()
    node.resources.cpu = 1000
    node.compute_class()
    server.node_register(node)
    low = mock.job()
    low.priority = 20
    low.task_groups[0].count = 1
    low.task_groups[0].tasks[0].resources.cpu = 600
    low.task_groups[0].tasks[0].resources.networks = []
    server.log.apply("job_register", {"job": low})
    victim = mock.alloc()
    victim.job = server.fsm.state.job_by_id(low.id)
    victim.job_id = low.id
    victim.node_id = node.id
    victim.task_group = low.task_groups[0].name
    victim.task_resources = {
        "web": low.task_groups[0].tasks[0].resources.copy()}
    server.log.apply("alloc_update", {"allocs": [victim],
                                      "job": victim.job})
    return server, node, victim


def _preempt_plan(server, node, victim, priority=60):
    from nomad_tpu.scheduler.util import ALLOC_PREEMPTED
    from nomad_tpu.structs import Plan
    from nomad_tpu.utils.ids import generate_uuid

    high = mock.job()
    high.priority = priority
    high.task_groups[0].tasks[0].resources.cpu = 700
    high.task_groups[0].tasks[0].resources.networks = []
    plan = Plan(eval_id=generate_uuid(), priority=priority, job=high)
    plan.append_preemption(victim, consts.ALLOC_DESIRED_EVICT,
                           ALLOC_PREEMPTED)
    new = mock.alloc()
    new.job = high
    new.job_id = high.id
    new.node_id = node.id
    new.task_group = high.task_groups[0].name
    new.task_resources = {
        "web": high.task_groups[0].tasks[0].resources.copy()}
    plan.append_alloc(new)
    return plan, new


def _submit(server, plan):
    # Straight into the plan queue: these tests target the applier's
    # verification/commit, not the broker's eval-token guard.
    return server.plan_queue.enqueue(plan).wait(timeout=10.0)


def test_applier_commits_verified_preemption_exactly_once():
    server, node, victim = _applier_fixture()
    try:
        before = preempt_stats()["evictions_committed"]
        plan, new = _preempt_plan(server, node, victim)
        result = _submit(server, plan)
        assert result.node_preemptions, result
        state = server.fsm.state
        stored = state.alloc_by_id(victim.id)
        assert stored.desired_status == consts.ALLOC_DESIRED_EVICT
        # the victim keeps ITS OWN job on the stored record, not the
        # preemptor's (the funnel's denormalization repair)
        assert stored.job is not None and stored.job.id == victim.job_id
        assert state.alloc_by_id(new.id) is not None
        assert preempt_stats()["evictions_committed"] == before + 1
    finally:
        server.shutdown()


def test_applier_rejects_lost_victim_and_commits_nothing():
    server, node, victim = _applier_fixture()
    try:
        # the victim completes before the plan verifies: its freed
        # capacity is void and the 700-cpu placement cannot fit
        done = victim.copy()
        done.client_status = consts.ALLOC_CLIENT_COMPLETE
        server.log.apply("alloc_client_update", {"allocs": [done]})
        plan, new = _preempt_plan(server, node, victim)
        result = _submit(server, plan)
        assert result.is_no_op(), result
        assert result.refresh_index > 0
        assert server.fsm.state.alloc_by_id(new.id) is None
    finally:
        server.shutdown()


def test_applier_rejects_outranked_preemption():
    server, node, victim = _applier_fixture()
    try:
        # plan priority 20 does NOT outrank the prio-20 victim
        plan, new = _preempt_plan(server, node, victim, priority=20)
        result = _submit(server, plan)
        assert result.is_no_op(), result
        stored = server.fsm.state.alloc_by_id(victim.id)
        assert stored.desired_status != consts.ALLOC_DESIRED_EVICT
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# scheduler end-to-end (harness): the priority storm, ON vs OFF


def _storm_harness(seed, n_nodes=4, node_cpu=1000):
    h = Harness(seed=seed)
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        n.resources.cpu = node_cpu
        n.resources.memory_mb = 4096
        n.compute_class()
        h.state.upsert_node(h.next_index(), n)
        nodes.append(n)
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
    live = [a for a in h.state.allocs_by_job(low.id)
            if not a.terminal_status()]
    assert len(live) == n_nodes  # one per node: the cluster is full
    high = mock.job()
    high.id = "high-prio"
    high.priority = 60
    high.task_groups[0].count = n_nodes
    t = high.task_groups[0].tasks[0]
    t.resources.cpu = 500
    t.resources.memory_mb = 128
    t.resources.networks = []
    h.state.upsert_job(h.next_index(), high)
    return h, low, high


def test_priority_storm_preemption_on_places_all():
    configure(preemption_enabled=True, preempt_priority_threshold=50)
    h, low, high = _storm_harness(seed=31)
    h.process("service-tpu", new_eval(h.state.job_by_id(high.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    state = h.state
    high_live = [a for a in state.allocs_by_job(high.id)
                 if not a.terminal_status()]
    assert len(high_live) == 4, h.evals[-1].failed_tg_allocs
    evicted = [a for a in state.allocs_by_job(low.id)
               if a.desired_status == consts.ALLOC_DESIRED_EVICT]
    assert len(evicted) == 4
    # lowest-priority-first per node: no surviving alloc on a victim
    # node outranks downward an evicted one (all victims were the
    # lowest-priority allocs on their nodes)
    for a in evicted:
        survivors = [s for s in state.allocs_by_node(a.node_id)
                     if not s.terminal_status() and s.job_id != high.id]
        assert all(victim_priority(s) >= victim_priority(a)
                   for s in survivors)
    # the victim job got its replacement eval through the funnel
    follow = [e for e in h.create_evals
              if e.triggered_by == consts.EVAL_TRIGGER_PREEMPTION]
    assert [e.job_id for e in follow] == [low.id]
    # eval completed
    assert h.evals[-1].status == consts.EVAL_STATUS_COMPLETE


def test_priority_storm_preemption_off_sheds_unchanged():
    configure(preemption_enabled=False)
    h, low, high = _storm_harness(seed=32)
    h.process("service-tpu", new_eval(h.state.job_by_id(high.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    state = h.state
    assert [a for a in state.allocs_by_job(high.id)
            if not a.terminal_status()] == []
    assert [a for a in state.allocs_by_job(low.id)
            if a.desired_status == consts.ALLOC_DESIRED_EVICT] == []
    # the PR 5 outcome: a blocked eval waits for capacity
    assert any(e.status == consts.EVAL_STATUS_BLOCKED
               for e in h.create_evals)


def test_priority_storm_green_cluster_never_preempts():
    """Eligibility comes from capacity: with preemption on and an
    outranking eval, a cluster that has headroom for every ask places
    them all without an eviction (nodes that fit without eviction
    always win, PREEMPT_VICTIM_PENALTY), and no pass runs."""
    configure(preemption_enabled=True, preempt_priority_threshold=50)
    passes = preempt_stats()["passes"]
    # 600 of 2,000 MHz used a node: the 500 MHz asks fit beside it
    h, low, high = _storm_harness(seed=33, node_cpu=2000)
    h.process("service-tpu", new_eval(h.state.job_by_id(high.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    assert len([a for a in h.state.allocs_by_job(high.id)
                if not a.terminal_status()]) == 4
    assert [a for a in h.state.allocs_by_job(low.id)
            if a.desired_status == consts.ALLOC_DESIRED_EVICT] == []
    assert preempt_stats()["passes"] == passes


def test_eligibility_is_priority_and_switch_alone():
    """`preemption_eligible` reads no pressure level: on, and strictly
    above the threshold."""
    from nomad_tpu.migrate import preemption_eligible

    configure(preemption_enabled=True, preempt_priority_threshold=50)
    assert preemption_eligible(51) and preemption_eligible(100)
    assert not preemption_eligible(50) and not preemption_eligible(10)
    configure(preemption_enabled=False)
    assert not preemption_eligible(100)


def test_preemption_leg_jit_cache_is_stable():
    """Steady-state jit_recompiles stays 0 with the preemption leg
    compiled in: a second storm of identical shape adds no programs."""
    from nomad_tpu.ops.binpack import jit_cache_size

    configure(preemption_enabled=True, preempt_priority_threshold=50)
    h, low, high = _storm_harness(seed=34)
    h.process("service-tpu", new_eval(h.state.job_by_id(high.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    warm = jit_cache_size()
    h2, low2, high2 = _storm_harness(seed=35)
    h2.process("service-tpu", new_eval(h2.state.job_by_id(high2.id),
                                       consts.EVAL_TRIGGER_JOB_REGISTER))
    assert jit_cache_size() == warm


def _scan_body_text(text, carried):
    """Of a lowered module's text, the body of the one `stablehlo.while`
    that carries a `carried` tensor, with every function it calls,
    however deep."""
    import re

    lines = text.splitlines()
    funcs, name = {}, None
    for line in lines:
        m = re.match(r"\s*func\.func \w+ @(\w+)\(", line)
        if m:
            name = m.group(1)
        if name:
            funcs.setdefault(name, []).append(line)
    (at,) = [i for i, line in enumerate(lines)
             if "stablehlo.while" in line and carried in line]
    indent = len(lines[at]) - len(lines[at].lstrip())
    stop = next(i for i in range(at + 1, len(lines))
                if lines[i] == " " * indent + "}")
    body, seen, todo = list(lines[at:stop]), set(), []
    while True:
        todo += [c for c in re.findall(r"@(\w+)", "\n".join(body))
                 if c in funcs and c not in seen]
        if not todo:
            return "\n".join(body)
        seen.add(todo[-1])
        body += funcs[todo.pop()]


def test_cell_shaped_program_keeps_nodes_minor_and_sums_nothing_an_ask():
    """The program lowered for borg-12k's shape (N 16,384, V 8): the
    scan over the asks carries the prefix tables node-minor, and its
    body holds no reduce-window (a cumsum an ask, as the program had
    three) and no array of victim granularity with the node axis
    leading. The [N, 4] NodeState the scoring rule reads may stay."""
    import re

    import jax
    import jax.numpy as jnp

    from nomad_tpu.ops.binpack import Asks, NodeState
    from nomad_tpu.ops.preempt import VictimState

    n, g = 16384, 1
    f32, i32, flag = jnp.float32, jnp.int32, jnp.bool_
    shape = jax.ShapeDtypeStruct
    state = NodeState(
        shape((n, 4), f32), shape((n, 4), f32), shape((n, 4), f32),
        shape((n,), f32), shape((n,), f32), shape((n,), f32),
        shape((n,), i32), shape((n, g), i32), shape((n, g), flag),
        shape((n,), flag))
    victims = VictimState(
        shape((4, V, n), f32), shape((V, n), f32), shape((V, n), f32),
        shape((V, n), f32), shape((V, n), flag))
    for k in (8, 16):
        asks = Asks(shape((k, 4), f32), shape((k,), f32), shape((k,), f32),
                    shape((k,), i32), shape((k,), flag), shape((), flag),
                    shape((g,), flag))
        text = preempt_placement_program_jit.lower(
            state, victims, asks, shape((2,), jnp.uint32), shape((), f32),
            CFG).as_text()
        # the tables ride the carry, node axis last
        body = _scan_body_text(text, f"tensor<4x{V}x{n}xf32>")
        assert "stablehlo.select" in body and "@closed_call" in body, k
        assert "reduce_window" not in body, k
        assert "cumsum" not in body and "cumprod" not in body, k
        assert not re.search(rf"tensor<{n}x{V}[x>]", body), k
        assert f"tensor<{n}x4xf32>" in body  # the scoring rule's own


@pytest.mark.parametrize("ask_floor,asks,k,job_rows", [
    (0, 3, 8, 16), (12, 3, 16, 16), (24, 7, 32, 64), (64, 1, 64, 64)])
def test_ask_floor_pads_asks_and_job_rows(ask_floor, asks, k, job_rows):
    """ClusterMatrix(ask_floor=n) pads the asks and the compact
    overlay's job rows as for n asks at least; padding asks are
    inactive."""
    from nomad_tpu.models.matrix import ClusterMatrix

    state, _nodes, jobs, _index = _banded_store(2830)
    matrix = ClusterMatrix(state.snapshot(), jobs[3], None,
                           ask_floor=ask_floor)
    arrays = matrix.build_asks([0] * asks)
    assert arrays[0].shape == (k, 4)
    assert int(arrays[4].sum()) == asks  # `active`
    assert matrix.compact_overlay.job_rows.shape == (job_rows,)


@pytest.mark.parametrize("priority,count,floor", [
    (60, 4, 4), (40, 4, 0), (60, 70, 0)])
def test_replan_padding_is_for_small_eligible_evals(monkeypatch, priority,
                                                    count, floor):
    """The dense scheduler asks for the padding only where an eval may
    preempt (it is replanned on the dense path) and its task groups ask
    for at most REPLAN_PAD_MAX_ASKS allocations in all."""
    from nomad_tpu.models import matrix as matrix_mod

    configure(preemption_enabled=True, preempt_priority_threshold=50)
    h, _low, high = _storm_harness(seed=37)
    high.priority = priority
    high.task_groups[0].count = count
    h.state.upsert_job(h.next_index(), high)
    seen = []
    init = matrix_mod.ClusterMatrix.__init__

    def spy(self, *args, **kwargs):
        seen.append(kwargs.get("ask_floor", 0))
        init(self, *args, **kwargs)

    monkeypatch.setattr(matrix_mod.ClusterMatrix, "__init__", spy)
    h.process("service-tpu", new_eval(h.state.job_by_id(high.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    assert seen and set(seen) == {floor}


def test_a_replan_of_a_preempting_eval_mints_no_program():
    """An eval that places the last 3 of its job's 12 allocations (the
    replan after a partially committed plan) runs the programs the
    12-ask attempt compiled: normal pass and preemption pass alike."""
    from nomad_tpu.ops.binpack import jit_cache_size

    configure(preemption_enabled=True, preempt_priority_threshold=50)
    h, _low, high = _storm_harness(seed=38, n_nodes=12)
    high.task_groups[0].count = 9
    h.state.upsert_job(h.next_index(), high)
    h.process("service-tpu", new_eval(h.state.job_by_id(high.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    warm = jit_cache_size()
    passes = preempt_stats()["passes"]
    high = h.state.job_by_id(high.id).copy()
    high.task_groups[0].count = 12
    h.state.upsert_job(h.next_index(), high)
    h.process("service-tpu", new_eval(h.state.job_by_id(high.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    live = [a for a in h.state.allocs_by_job(high.id)
            if not a.terminal_status()]
    assert len(live) == 12
    assert preempt_stats()["passes"] == passes + 1
    assert jit_cache_size() == warm


# ---------------------------------------------------------------------
# oracle differential: randomized clusters judge the kernel's choices


@pytest.mark.parametrize("seed", range(700, 708))
def test_preemption_differential_validity(seed):
    """Whatever the kernel chose, the committed state must satisfy the
    CPU oracle's invariants: victims strictly outranked, lowest-
    priority-first per node, and every node's post-commit load fits
    its capacity exactly (allocs_fit)."""
    from nomad_tpu.structs import allocs_fit

    rng = random.Random(seed)
    configure(preemption_enabled=True, preempt_priority_threshold=50)
    h = Harness(seed=seed)
    n_nodes = rng.choice([4, 6])
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        n.resources.cpu = 1000
        n.resources.memory_mb = 4096
        n.compute_class()
        h.state.upsert_node(h.next_index(), n)
        nodes.append(n)
    # random low-priority fill
    for j in range(rng.choice([2, 3])):
        job = mock.job()
        job.id = f"low-{j}"
        job.priority = rng.choice([10, 20, 30])
        job.task_groups[0].count = n_nodes
        t = job.task_groups[0].tasks[0]
        t.resources.cpu = rng.choice([300, 400])
        t.resources.memory_mb = 128
        t.resources.networks = []
        h.state.upsert_job(h.next_index(), job)
        h.process("service-tpu", new_eval(
            h.state.job_by_id(job.id), consts.EVAL_TRIGGER_JOB_REGISTER))
    high = mock.job()
    high.id = "high"
    high.priority = rng.choice([60, 80])
    high.task_groups[0].count = rng.choice([4, 5])
    t = high.task_groups[0].tasks[0]
    t.resources.cpu = rng.choice([400, 500])
    t.resources.memory_mb = 128
    t.resources.networks = []
    h.state.upsert_job(h.next_index(), high)
    h.process("service-tpu", new_eval(
        h.state.job_by_id(high.id), consts.EVAL_TRIGGER_JOB_REGISTER))

    state = h.state
    evicted = [a for a in state.allocs()
               if a.desired_status == consts.ALLOC_DESIRED_EVICT]
    for a in evicted:
        assert victim_priority(a) < high.priority, seed
        survivors = [s for s in state.allocs_by_node(a.node_id)
                     if not s.terminal_status() and s.job_id != high.id]
        assert all(victim_priority(s) >= victim_priority(a)
                   for s in survivors), seed
    # post-commit exact fit on every node the oracle can check
    for n in nodes:
        live = [a for a in state.allocs_by_node(n.id)
                if not a.terminal_status()]
        fit, _dim, _util = allocs_fit(n, live)
        assert fit, (seed, n.id)


# ---------------------------------------------------------------------
# live-server soak: victim lost mid-commit, exactly-once through raft


def test_server_preemption_soak_with_victim_lost_chaos():
    server = Server(ServerConfig(
        num_schedulers=2,
        scheduler_factories={"service": "service-tpu"},
        eval_nack_timeout=2.0,
        eval_delivery_limit=8,
        preemption_enabled=True,
        preempt_priority_threshold=50,
    ))
    server.start()
    try:
        nodes = []
        for _ in range(4):
            node = mock.node()
            node.resources.cpu = 1000
            node.compute_class()
            server.node_register(node)
            nodes.append(node)
        low = mock.job()
        low.id = "low-prio"
        low.priority = 20
        low.task_groups[0].count = 4
        t = low.task_groups[0].tasks[0]
        t.resources.cpu = 600
        t.resources.memory_mb = 256
        t.resources.networks = []
        server.job_register(low)

        def live(job_id):
            return [a for a in server.fsm.state.allocs_by_job(job_id)
                    if not a.terminal_status()]

        assert wait_until(lambda: len(live(low.id)) == 4, 60.0)

        # a full cluster under a healthy (green) control plane, and a
        # victim lost between selection and commit
        assert server.admission.level() == "green"
        chaos.arm(99, [FaultSpec("preempt.victim_lost", "drop", count=1)])
        high = mock.job()
        high.id = "high-prio"
        high.priority = 60
        high.task_groups[0].count = 4
        t = high.task_groups[0].tasks[0]
        t.resources.cpu = 500
        t.resources.memory_mb = 128
        t.resources.networks = []
        server.job_register(high)

        assert wait_until(lambda: len(live(high.id)) == 4, 60.0), (
            server.fsm.state.evals_by_job(high.id))
        fired = chaos.firing_log()
        chaos.disarm()
        assert [f for f in fired if f[0] == "preempt.victim_lost"]

        state = server.fsm.state
        evicted = [a for a in state.allocs_by_job(low.id)
                   if a.desired_status == consts.ALLOC_DESIRED_EVICT]
        assert len(evicted) == 4
        # exactly once: one store record per victim id, stamped evict
        assert len({a.id for a in evicted}) == 4
        # nothing placed on top of a surviving victim: per-node fit
        from nomad_tpu.structs import allocs_fit

        for node in nodes:
            livehere = [a for a in state.allocs_by_node(node.id)
                        if not a.terminal_status()]
            fit, _d, _u = allocs_fit(node, livehere)
            assert fit, node.id
        # the high-prio evals all completed; the victims' replacement
        # evals exist (blocked or pending — the cluster is full, which
        # is the correct outcome for prio-20 work on a full cluster)
        # (the allocations are readable when the plan's entry applies,
        # the eval's terminal status an instant later)
        assert wait_until(lambda: all(
            e.terminal_status() for e in state.evals_by_job(high.id)),
            10.0), state.evals_by_job(high.id)
        assert [e for e in state.evals_by_job(low.id)
                if e.triggered_by == consts.EVAL_TRIGGER_PREEMPTION]
    finally:
        chaos.disarm()
        server.shutdown()


# ---------------------------------------------------------------------
# the victim table beside the cached base, and the plan overlay (PR 28)


def _banded_store(seed, n_nodes=9):
    """A store of three node shapes holding allocations of four jobs in
    three priority bands, loaded through the store as the FSM does."""
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import Allocation, Resources

    rng = random.Random(seed)
    state = StateStore()
    index = 10
    nodes = []
    for i in range(n_nodes):
        node = mock.node()
        node.resources.cpu = (4000, 8000, 16000)[i % 3]
        node.resources.memory_mb = (8192, 16384, 32768)[i % 3]
        node.compute_class()
        index += 1
        state.upsert_node(index, node)
        nodes.append(node)
    jobs = []
    for prio in (10, 30, 70, 70):
        job = mock.job()
        job.id = f"band-{prio}-{len(jobs)}"
        job.priority = prio
        jobs.append(job)
    allocs = []
    for node in nodes:
        for k in range(rng.randint(0, 11)):
            job = rng.choice(jobs)
            allocs.append(Allocation(
                id=mock.alloc().id, eval_id="load", node_id=node.id,
                name=f"{job.id}.web[{k}]", job_id=job.id, job=job,
                task_group="web",
                shared_resources=Resources(disk_mb=rng.choice([10, 150])),
                task_resources={"web": Resources(
                    cpu=rng.choice([50, 100, 250]),
                    memory_mb=rng.choice([256, 300, 512]))},
                desired_status="run", client_status="running"))
    index += 1
    state.upsert_allocs(index, allocs)
    return state, nodes, jobs, index


def _victims_by_walk(matrix, max_priority):
    """What build_victims was before the table: every node's live
    allocations through the plan, filtered, sorted, the first V."""
    from nomad_tpu.migrate import victim_sort_key

    out = {}
    for i, node in enumerate(matrix.nodes):
        cands = sorted(
            (a for a in matrix._proposed_allocs(node.id)
             if not a.terminal_status() and a.job_id != matrix.job.id
             and victim_priority(a) < max_priority),
            key=victim_sort_key)[:V]
        if cands:
            out[i] = cands
    return out


def _assert_victims_equal_walk(matrix, max_priority):
    from nomad_tpu.models.matrix import _alloc_usage

    arrays, victims_of, total = matrix.build_victims(max_priority)
    # node axis last as handed to the program; read here node by node
    res, bw, ports, prio, ok = (a.T for a in arrays)
    want = _victims_by_walk(matrix, max_priority)
    assert total == sum(len(c) for c in want.values())
    for i in range(matrix.n):
        cands = want.get(i, [])
        assert int(ok[i].sum()) == len(cands), i
        # the eligible candidates are a prefix of the row, in order
        if i < matrix.n_real:    # rows beyond are padding
            assert [a.id for a in victims_of(i)[:len(cands)]] \
                == [a.id for a in cands], i
        for v, alloc in enumerate(cands):
            usage = _alloc_usage(alloc)
            assert ok[i, v] and prio[i, v] == victim_priority(alloc)
            assert tuple(res[i, v]) == usage[:4]
            assert bw[i, v] == usage[4] and ports[i, v] == usage[5]
        assert not ok[i, len(cands):].any()


@pytest.mark.parametrize("seed", range(2810, 2816))
def test_victim_table_equals_the_per_node_walk(seed):
    from nomad_tpu.models.matrix import ClusterMatrix

    state, _nodes, jobs, _index = _banded_store(seed)
    snap = state.snapshot()
    for job, priority in ((jobs[2], 70), (jobs[1], 30), (jobs[0], 100)):
        matrix = ClusterMatrix(snap, job, None, plan_overlay=True)
        _assert_victims_equal_walk(matrix, priority)


@pytest.mark.parametrize("seed", range(2816, 2822))
def test_victim_table_follows_the_delta_chain(seed):
    """A newer snapshot's base is a delta of the older one's; its table
    is the older table with the touched rows derived again, and reads
    as a walk over the newer snapshot would."""
    from nomad_tpu.models.matrix import ClusterMatrix

    state, nodes, jobs, index = _banded_store(seed)
    rng = random.Random(seed)
    first = ClusterMatrix(state.snapshot(), jobs[3], None, plan_overlay=True)
    _assert_victims_equal_walk(first, 70)      # builds the table
    table = first._base._victims
    assert table is not None
    listed = [[a.id for a in lst or []] for lst in table.lists]
    ok_then = table.ok.copy()
    # evict two allocations and add one: what a preempting plan commits
    live = [a for a in state.allocs() if not a.terminal_status()]
    gone = rng.sample(live, 2)
    changed = []
    for a in gone:
        c = a.copy()
        c.desired_status = consts.ALLOC_DESIRED_EVICT
        changed.append(c)
    new = live[0].copy()
    new.id = mock.alloc().id
    new.node_id = nodes[-1].id
    new.job, new.job_id = jobs[0], jobs[0].id
    changed.append(new)
    state.upsert_allocs(index + 1, changed)
    second = ClusterMatrix(state.snapshot(), jobs[3], None, plan_overlay=True)
    assert second.build_kind == "delta"
    assert second._base._victims is not None
    assert second._base._victims is not table   # carried, not shared
    _assert_victims_equal_walk(second, 70)
    # the older base's table still reads as its own snapshot did
    assert [[a.id for a in lst or []] for lst in table.lists] == listed
    assert np.array_equal(table.ok, ok_then)


@pytest.mark.parametrize("seed", range(2822, 2830))
def test_plan_overlay_equals_a_fresh_matrix(seed):
    """The node state of the preemption pass: the cached base with the
    rows the plan touches derived again equals a base built from
    scratch through the plan, array for array, and so do the victims."""
    from nomad_tpu.models.matrix import ClusterMatrix
    from nomad_tpu.structs import Plan

    state, nodes, jobs, _index = _banded_store(seed)
    rng = random.Random(seed)
    snap = state.snapshot()
    job = jobs[3]
    live = [a for a in snap.allocs() if not a.terminal_status()]
    plan = Plan(eval_id="e", priority=70, job=job)
    for a in rng.sample(live, 3):
        plan.append_update(a, consts.ALLOC_DESIRED_STOP, "test")
    for a in rng.sample(live, 2):
        plan.append_preemption(a, consts.ALLOC_DESIRED_EVICT, "test")
    for k in range(3):
        placed = live[0].copy()
        placed.id = mock.alloc().id
        placed.node_id = rng.choice(nodes).id
        placed.job, placed.job_id = job, job.id
        placed.task_group = job.task_groups[0].name
        plan.append_alloc(placed)
    # the plain reference: the walk over every node through the plan
    # (kernels/differential.py walked_view); a matrix without
    # plan_overlay keeps the base's token and carries the same plan as
    # a lane's patch
    from nomad_tpu.kernels.differential import patched_view, walked_view

    walk = walked_view(snap, job, plan)
    lane = ClusterMatrix(snap, job, plan)
    assert lane.base_token is not None
    over = ClusterMatrix(snap, job, plan, plan_overlay=True)
    assert over.build_kind in ("hit", "delta", "full", "rekey")
    assert over.base_token is None and over.compact_overlay is None
    for name in ("capacity", "sched_capacity", "bw_avail", "node_ok",
                 "feasible"):
        np.testing.assert_array_equal(
            getattr(over, name), getattr(lane, name), err_msg=name)
    patched = patched_view(lane)
    for name in ("util", "bw_used", "ports_free", "job_count", "tg_count"):
        np.testing.assert_array_equal(
            getattr(over, name), walk[name], err_msg=name)
        np.testing.assert_array_equal(
            patched[name], walk[name], err_msg=f"lane {name}")
    _assert_victims_equal_walk(over, 70)
    # the cached base itself was not written to
    again = ClusterMatrix(snap, job, None)
    assert again.base_token is not None
    clean = ClusterMatrix(snap, jobs[0], None, plan_overlay=True)
    np.testing.assert_array_equal(again.util, clean.util)


def test_churn_stats_carry_passes_and_failure_counters():
    from nomad_tpu.migrate import churn_stats, note_preemption_failure

    configure(preemption_enabled=True, preempt_priority_threshold=50)
    before = churn_stats()["preemption"]
    assert {"passes", "evictions_staged", "evictions_committed",
            "placements", "preempt_dispatch_failed",
            "preempt_breaker_rejected"} <= set(before)
    h, low, high = _storm_harness(seed=36)
    h.process("service-tpu", new_eval(h.state.job_by_id(high.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    note_preemption_failure(dispatch_failed=2, breaker_rejected=3)
    after = churn_stats()["preemption"]
    assert after["passes"] == before["passes"] + 1
    assert after["evictions_staged"] == before["evictions_staged"] + 4
    assert after["preempt_dispatch_failed"] \
        == before["preempt_dispatch_failed"] + 2
    assert after["preempt_breaker_rejected"] \
        == before["preempt_breaker_rejected"] + 3


def test_preempt_spans_nest_and_the_account_closes():
    """preempt.victims and preempt.solve lie inside preempt.select, and
    what they leave is the pass's self time."""
    from nomad_tpu import trace
    from nomad_tpu.trace import (
        ALL_STAGES,
        STAGE_PREEMPT_SELECT,
        STAGE_PREEMPT_SOLVE,
        STAGE_PREEMPT_VICTIMS,
    )

    assert {STAGE_PREEMPT_VICTIMS, STAGE_PREEMPT_SOLVE} <= set(ALL_STAGES)
    configure(preemption_enabled=True, preempt_priority_threshold=50)
    recorder = trace.get_recorder()
    before = {s: (recorder.stage_buckets(s) or (0, None))[0]
              for s in (STAGE_PREEMPT_SELECT, STAGE_PREEMPT_VICTIMS,
                        STAGE_PREEMPT_SOLVE)}
    h, low, high = _storm_harness(seed=37)
    ev = new_eval(h.state.job_by_id(high.id),
                  consts.EVAL_TRIGGER_JOB_REGISTER)
    trace.mark(ev.id, ev.trace_id)
    h.process("service-tpu", ev)
    for stage, count in before.items():
        assert recorder.stage_buckets(stage)[0] == count + 1, stage
