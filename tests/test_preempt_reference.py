"""The dense preemption program against a plain reference of the whole
pass (tests/preempt_reference.py: NumPy float64, sequential, nothing of
`ops/`), on seeded fleets of three machine shapes kept full by three
priority bands, with asks of two sizes; and the same through `Server`
end to end on a small full cluster.

The comparison has to notice a program that evicts out of order, that
evicts more than it must, or that sums what the victims free in less
than float32: each is shown below on a program doctored from outside.
"""

import time

import jax
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.migrate import configure, victim_priority
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
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import Allocation, Resources, consts

from preempt_reference import preempt_reference

V = PREEMPT_MAX_VICTIMS
CFG = PlacementConfig(anti_affinity_penalty=10.0)
SEEDS = range(2800, 2810)

# (cpu MHz, memory MB, how many): three machine shapes
SHAPES = ((8000, 32768, 20), (8000, 16384, 12), (16000, 65536, 8))
BANDS = (10, 30, 70)            # free, middle, production
# (cpu, memory, bandwidth, ports) an ask: one victim as a rule, or two
ASK_SIZES = ((500, 2051, 20, 2), (1000, 4099, 50, 2))


def seeded_fleet(seed, k_small=5, k_large=3, shapes=SHAPES, k_pad=0):
    """A full fleet as arrays: every node's free memory is under the
    smaller ask, so no ask fits anywhere without an eviction; victim
    sizes are odd numbers of MB, so that a sum in bfloat16 (8 bits: a
    step of 16 at 2,048) is a different sum. With `k_pad` the asks are
    followed by inactive ones up to that many, as a K bucket pads them.
    """
    rng = np.random.default_rng(seed)
    n = sum(count for _c, _m, count in shapes)
    capacity = np.zeros((n, 4))
    util = np.zeros((n, 4))
    res = np.zeros((n, V, 4))
    bw = np.zeros((n, V))
    ports = np.zeros((n, V))
    prio = np.full((n, V), np.inf)
    ok = np.zeros((n, V), bool)
    i = 0
    for cpu, mem, count in shapes:
        for _ in range(count):
            capacity[i] = (cpu, mem, 100000, 1000)
            sizes, left = [], mem - 256
            while left >= 2051:
                sizes.append(int(rng.integers(2049, min(2140, left) + 1)))
                left -= sizes[-1]
            sizes, fillers = np.array(sizes), len(sizes)
            cpus = rng.choice([50, 100, 250, 500], fillers)
            bands = np.sort(rng.choice(BANDS, fillers, p=(0.4, 0.35, 0.25)))
            util[i] = (100 + cpus.sum(), 256 + sizes.sum(),
                       4096 + 150 * fillers, 0)
            # the victim tensor holds the V lowest, lowest first
            take = min(V, fillers)
            res[i, :take, 0] = cpus[:take]
            res[i, :take, 1] = sizes[:take]
            res[i, :take, 2] = 150
            bw[i, :take] = rng.choice([0, 10], take)
            ports[i, :take] = rng.choice([0, 1], take)
            prio[i, :take] = bands[:take]
            ok[i, :take] = True
            i += 1
    order = rng.permutation(n)
    capacity, util = capacity[order], util[order]
    res, bw, ports = res[order], bw[order], ports[order]
    prio, ok = prio[order], ok[order]
    sched = capacity - np.array([100, 256, 4096, 0])
    node = dict(
        capacity=capacity, sched_capacity=sched, util=util,
        bw_avail=np.full(n, 1000.0), bw_used=bw.sum(axis=1) + 1,
        ports_free=np.full(n, 40.0) - ports.sum(axis=1),
        job_count=np.zeros(n, np.int32), tg_count=np.zeros((n, 2), np.int32),
        feasible=rng.random((n, 2)) < 0.9, node_ok=rng.random(n) < 0.95)
    victims = dict(res=res, bw=bw, ports=ports, prio=prio, ok=ok)
    kinds = rng.permutation([0] * k_small + [1] * k_large)
    sizes = np.array([ASK_SIZES[k] for k in kinds], np.float64)
    k = len(kinds)
    asks = dict(
        resources=np.column_stack([sizes[:, 0], sizes[:, 1],
                                   np.full(k, 150.0), np.zeros(k)]),
        bw=sizes[:, 2], ports=sizes[:, 3],
        tg_index=kinds.astype(np.int32), active=np.ones(k, bool),
        job_dh=False, tg_dh=np.array([True, False]))
    return node, victims, padded(asks, max(k, k_pad))


def padded(asks, k):
    """`asks` followed by inactive rows of zeros up to `k` of them."""
    more = k - len(asks["active"])
    if not more:
        return asks
    grow = {name: np.concatenate([asks[name],
                                  np.zeros((more,) + asks[name].shape[1:],
                                           asks[name].dtype)])
            for name in ("resources", "bw", "ports", "tg_index", "active")}
    return dict(asks, **grow)


def program_inputs(node, victims, asks):
    """The reference's dictionaries as the program takes them: the
    victims with the node axis last ([N, V, 4] to [4, V, N], [N, V] to
    [V, N])."""
    return (make_node_state(**node),
            make_victim_state(**{name: np.asarray(a).T
                                 for name, a in victims.items()}),
            make_asks(asks["resources"], asks["bw"], asks["ports"],
                      asks["tg_index"], asks["active"], asks["job_dh"],
                      asks["tg_dh"]))


def run_program(node, victims, asks, key, priority, config=CFG):
    return unpack_result(preempt_placement_program_jit(
        *program_inputs(node, victims, asks), key, np.float32(priority),
        config))


def noise_of(key, k, n, config=CFG):
    """The tie-break noise the program draws from `key`: handed to the
    reference as a plain array (float32 values, exact in float64)."""
    return np.asarray(jax.random.uniform(
        key, (k, n), minval=0.0, maxval=config.noise_scale), np.float64)


def compare(node, victims, asks, seed, priority=80.0):
    key = host_prng_key(seed)
    choices, scores, counts = run_program(node, victims, asks, key, priority)
    n, k = len(node["node_ok"]), len(asks["active"])
    want = preempt_reference(node, victims, asks, priority,
                             CFG.anti_affinity_penalty, noise_of(key, k, n))
    return (list(choices), list(scores), list(counts)), want


@pytest.mark.parametrize("seed", SEEDS)
def test_program_equals_reference_ask_by_ask(seed):
    node, victims, asks = seeded_fleet(seed)
    (choices, scores, counts), (w_choices, w_scores, w_counts) = compare(
        node, victims, asks, seed)
    assert choices == w_choices, seed
    assert counts == w_counts, seed
    np.testing.assert_allclose(scores, w_scores, atol=1e-4)
    # the fleet is full: every ask that found a node evicted for it,
    # the small ones one victim and the large ones two, as sized
    placed = [c >= 0 for c in choices]
    assert any(placed)
    for j, kind in enumerate(asks["tg_index"]):
        if placed[j]:
            assert counts[j] == kind + 1, (seed, j)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_equals_reference_below_the_production_band(seed):
    """A preemptor of the middle band's height (priority 30.5 stands
    for 31-69) may take the free and the middle band only; a node whose
    lowest candidates left are production is no node for it."""
    node, victims, asks = seeded_fleet(seed + 100, k_small=6, k_large=2)
    (choices, _s, counts), (w_choices, _ws, w_counts) = compare(
        node, victims, asks, seed, priority=31.0)
    assert choices == w_choices and counts == w_counts, seed
    for j, c in enumerate(choices):
        if c >= 0 and counts[j]:
            assert (victims["prio"][c][victims["ok"][c]][:counts[j]]
                    < 31.0).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_headroom_wins_over_eviction(seed):
    """One node is given room for the first ask: it goes there without
    a victim, in the program and in the reference, whatever BestFit
    says of the full nodes."""
    node, victims, asks = seeded_fleet(seed + 200)
    ok_rows = np.flatnonzero(node["node_ok"] & node["feasible"].all(axis=1))
    roomy = int(ok_rows[seed % len(ok_rows)])
    node["util"][roomy, 1] -= 2 * 2140
    (choices, _s, counts), (w_choices, _ws, w_counts) = compare(
        node, victims, asks, seed)
    assert choices == w_choices and counts == w_counts, seed
    assert choices[0] == roomy and counts[0] == 0


# What the node-minor program with carried prefix tables can get wrong
# and the parent's could not: each case against the reference.

# one BUCKETS step, and two sizes no multiple of the chip's 128 lanes
FLEET_SIZES = {
    "n128": ((8000, 32768, 64), (8000, 16384, 40), (16000, 65536, 24)),
    "n200": ((8000, 32768, 100), (8000, 16384, 60), (16000, 65536, 40)),
    "n131": ((8000, 32768, 66), (8000, 16384, 41), (16000, 65536, 24)),
}


@pytest.mark.parametrize("size", sorted(FLEET_SIZES))
def test_program_equals_reference_whatever_the_node_count(size):
    seed = 2840 + len(size) + int(size[1:])
    node, victims, asks = seeded_fleet(seed, shapes=FLEET_SIZES[size])
    assert len(node["node_ok"]) == int(size[1:])
    got, want = compare(node, victims, asks, seed)
    assert got[0] == want[0] and got[2] == want[2], size
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)
    assert any(c >= 0 for c in got[0])


@pytest.mark.parametrize("k_bucket,k_small,k_large", [
    (8, 3, 2), (16, 7, 4), (32, 12, 7)])
def test_program_equals_reference_with_trailing_inactive_asks(
        k_bucket, k_small, k_large):
    seed = 2850 + k_bucket
    node, victims, asks = seeded_fleet(seed, k_small, k_large,
                                       k_pad=k_bucket)
    assert len(asks["active"]) == k_bucket
    assert int(asks["active"].sum()) == k_small + k_large < k_bucket
    got, want = compare(node, victims, asks, seed)
    assert got[0] == want[0] and got[2] == want[2], k_bucket
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)
    live = k_small + k_large
    assert any(c >= 0 for c in got[0][:live])
    assert got[0][live:] == [-1] * (k_bucket - live)
    assert got[2][live:] == [0] * (k_bucket - live)


def crafted(utils, slots, ask_sizes, feasible=None):
    """A cell of len(utils) nodes of capacity 100 in every dimension,
    node i used to utils[i]; `slots[i]` is node i's victim row, slot by
    slot: (size, priority), or None for a padding slot (ok false,
    priority +inf, nothing held), wherever in the row it stands. One
    ask a size, all of one task group, no distinct_hosts."""
    n, k = len(utils), len(ask_sizes)
    res = np.zeros((n, V, 4))
    prio = np.full((n, V), np.inf)
    ok = np.zeros((n, V), bool)
    for i, row in slots.items():
        for slot, entry in enumerate(row):
            if entry is not None:
                res[i, slot], prio[i, slot] = entry
                ok[i, slot] = True
    capacity = np.full((n, 4), 100.0)
    node = dict(
        capacity=capacity, sched_capacity=capacity,
        util=np.repeat(np.asarray(utils, np.float64)[:, None], 4, axis=1),
        bw_avail=np.full(n, 1000.0), bw_used=np.zeros(n),
        ports_free=np.full(n, 20.0), job_count=np.zeros(n, np.int32),
        tg_count=np.zeros((n, 1), np.int32),
        feasible=(np.ones((n, 1), bool) if feasible is None
                  else np.asarray(feasible, bool)[:, None]),
        node_ok=np.ones(n, bool))
    victims = dict(res=res, bw=np.zeros((n, V)), ports=np.zeros((n, V)),
                   prio=prio, ok=ok)
    asks = dict(
        resources=np.repeat(np.asarray(ask_sizes, np.float64)[:, None], 4,
                            axis=1),
        bw=np.zeros(k), ports=np.zeros(k), tg_index=np.zeros(k, np.int32),
        active=np.ones(k, bool), job_dh=False, tg_dh=np.array([False]))
    return node, victims, padded(asks, 8)


ONLY_NODE_0 = [True, False, False, False]
CRAFTED = {
    # four asks of two victims each use node 0's eight slots up; the
    # fifth finds every slot consumed and no other node to evict on
    "all_eight_slots_consumed": (
        crafted([100] * 4, {0: [(10.0, p) for p in range(10, 18)]},
                [20, 20, 20, 20, 20], ONLY_NODE_0),
        [0, 0, 0, 0, -1], [2, 2, 2, 2, 0]),
    # the second ask's prefix starts after the first's victim
    "two_asks_evict_on_one_node": (
        crafted([100] * 4, {0: [(30.0, 10), (30.0, 20), (30.0, 30)]},
                [25, 25], ONLY_NODE_0),
        [0, 0], [1, 1]),
    # node 1 has room for the small ask alone: it goes there without a
    # victim between two asks that evict on node 0
    "a_fit_between_two_evictions": (
        crafted([100, 92, 100, 100], {0: [(30.0, 10), (30.0, 20)]},
                [25, 5, 25]),
        [0, 1, 0], [1, 0, 1]),
    # padding slots in the middle of the row neither count nor break a
    # prefix: 55 takes the first two live ones across a hole, then 25
    # the next across two more
    "padding_slots_inside_a_row": (
        crafted([100] * 4,
                {0: [(30.0, 10), None, (30.0, 20), None, None, (30.0, 30),
                     None, (30.0, 40)]},
                [55, 25, 25], ONLY_NODE_0),
        [0, 0, 0], [2, 1, 1]),
}


@pytest.mark.parametrize("case", sorted(CRAFTED))
def test_program_equals_reference_on_a_crafted_cell(case):
    (node, victims, asks), choices, counts = CRAFTED[case]
    got, want = compare(node, victims, asks, seed=2860, priority=50.0)
    live = len(choices)
    assert want[0][:live] == choices and want[2][:live] == counts, case
    assert got[0] == want[0] and got[2] == want[2], case
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)


def carried_tables_are_fresh(node, victims, asks, seed, priority):
    """Step the program's own scan by hand: after every ask the carried
    prefix tables are, number for number, the tables computed afresh
    from the carried live mask. Returns how many asks evicted."""
    from nomad_tpu.ops.preempt import _prefix_tables, _scan_parts

    state, vstate, asks_ = program_inputs(node, victims, asks)
    body, carry, xs = _scan_parts(
        state, vstate, asks_, host_prng_key(seed), np.float32(priority), CFG)
    step = jax.jit(body)
    tables = jax.jit(_prefix_tables)
    evicted = 0
    for j in range(len(asks["active"])):
        before = np.asarray(carry.vok)
        carry, out = step(carry, jax.tree_util.tree_map(lambda x: x[j], xs))
        fresh = tables(vstate, carry.vok, np.float32(priority))
        for name, got, want in zip(fresh._fields, carry.prefix, fresh):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want), err_msg=f"{name}, ask {j}")
        gone = before & ~np.asarray(carry.vok)
        assert not (~before & np.asarray(carry.vok)).any()
        assert int(gone.sum()) == int(out[2]), j
        if int(out[2]):
            evicted += 1
            assert gone[:, int(out[0])].sum() == int(out[2])
    return evicted


@pytest.mark.parametrize("seed", range(2870, 2876))
def test_carried_prefix_tables_equal_fresh_ones_after_every_ask(seed):
    """On random full fleets, with one roomy node so that asks that fit
    without a victim stand among those that evict."""
    node, victims, asks = seeded_fleet(seed, k_small=6, k_large=4,
                                       k_pad=16)
    rows = np.flatnonzero(node["node_ok"] & node["feasible"].all(axis=1))
    node["util"][int(rows[seed % len(rows)]), 1] -= 3 * 2140
    priority = 80.0 if seed % 2 else 31.0
    assert carried_tables_are_fresh(node, victims, asks, seed, priority) >= 3


@pytest.mark.parametrize("case", sorted(CRAFTED))
def test_carried_prefix_tables_equal_fresh_ones_on_a_crafted_cell(case):
    (node, victims, asks), _choices, counts = CRAFTED[case]
    assert carried_tables_are_fresh(node, victims, asks, 2860, 50.0) \
        == sum(1 for c in counts if c)


def test_a_stale_column_does_not_pass_unseen(monkeypatch):
    """The check above on a program that forgets to take the evicted
    prefix out of the carried tables."""
    from nomad_tpu.ops import preempt

    consume = preempt._consume
    monkeypatch.setattr(
        preempt, "_consume",
        lambda prefix, vok, hit, k_star, star:
            (prefix, consume(prefix, vok, hit, k_star, star)[1]))
    (node, victims, asks), _c, _n = CRAFTED["two_asks_evict_on_one_node"]
    with pytest.raises(AssertionError):
        carried_tables_are_fresh(node, victims, asks, 2860, 50.0)


def doctored(fn):
    """The program with its victim tensor rewritten by `fn` on the way
    in: what a faulty program would have computed."""
    def run(seed):
        node, victims, asks = seeded_fleet(seed)
        key = host_prng_key(seed)
        n, k = len(node["node_ok"]), len(asks["active"])
        got = run_program(node, fn(victims), asks, key, 80.0)
        want = preempt_reference(node, victims, asks, 80.0,
                                 CFG.anti_affinity_penalty,
                                 noise_of(key, k, n))
        return (list(got[0]) != want[0] or list(got[2]) != want[2]
                or not np.allclose(got[1], want[1], atol=1e-4))
    return run


def highest_first(victims):
    """Each node's live candidates in the opposite order."""
    out = {name: np.array(a) for name, a in victims.items()}
    for i in range(len(out["ok"])):
        live = int(out["ok"][i].sum())
        for name in out:
            out[name][i, :live] = out[name][i, :live][::-1]
    return out


def one_more_than_needed(victims):
    """Every victim frees a twentieth of what it holds, so the program
    evicts many where few would do."""
    return dict(victims, res=victims["res"] * 0.05)


def freed_in_bfloat16(victims):
    import jax.numpy as jnp

    res = np.asarray(jnp.asarray(victims["res"], jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32))
    return dict(victims, res=res)


@pytest.mark.parametrize("fault", [highest_first, one_more_than_needed,
                                   freed_in_bfloat16])
def test_comparison_notices_a_faulty_program(fault):
    noticed = [doctored(fault)(seed) for seed in SEEDS]
    assert all(noticed), (fault.__name__, noticed)


def test_reference_imports_nothing_of_the_program():
    import preempt_reference as ref

    text = open(ref.__file__).read()
    assert "nomad_tpu" not in text.split('"""', 2)[2]
    assert "import jax" not in text


# ---------------------------------------------------------------------
# end to end through Server: a small full cluster of three shapes


def wait_until(fn, timeout=90.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


@pytest.fixture
def full_cluster():
    server = Server(ServerConfig(
        num_schedulers=2,
        scheduler_factories={"service": "service-tpu", "batch": "batch-tpu"},
        preemption_enabled=True, preempt_priority_threshold=50))
    server.start()
    try:
        rng = np.random.default_rng(28)
        nodes, fillers = [], []
        jobs = {}
        for prio in BANDS:
            job = mock.job()
            job.id = job.name = f"filler-p{prio}"
            job.priority = prio
            jobs[prio] = job
        for cpu, mem, count in ((4000, 8192, 6), (4000, 4096, 4),
                                (8000, 16384, 2)):
            for _ in range(count):
                node = mock.node()
                node.resources.cpu = cpu
                node.resources.memory_mb = mem
                node.compute_class()
                server.node_register(node)
                nodes.append(node)
                usable = mem - node.reserved.memory_mb
                # fillers of ~1 GB until under 1,024 MB is free
                k = 0
                while usable >= 1024 + 1100:
                    size = int(rng.integers(1025, 1100))
                    prio = BANDS[k % 3]
                    fillers.append(Allocation(
                        id=mock.alloc().id, eval_id="filler",
                        node_id=node.id, name=f"filler-p{prio}.web[{k}]",
                        job_id=jobs[prio].id, job=jobs[prio],
                        task_group="web",
                        shared_resources=Resources(disk_mb=10),
                        task_resources={"web": Resources(
                            cpu=100, memory_mb=size)},
                        desired_status="run", client_status="running"))
                    usable -= size
                    k += 1
        server.log.apply("alloc_update", {"allocs": fillers})
        yield server, nodes, {a.id for a in fillers}
    finally:
        configure(preemption_enabled=False, preempt_priority_threshold=50)
        server.shutdown()


def production_job(name, count, memory_mb):
    job = mock.job()
    job.id = job.name = name
    job.priority = 70
    job.task_groups[0].count = count
    task = job.task_groups[0].tasks[0]
    task.resources.cpu = 200
    task.resources.memory_mb = memory_mb
    task.resources.networks = []
    return job


def test_server_places_production_by_evicting_lowest_first(full_cluster):
    server, nodes, filler_ids = full_cluster
    state = server.fsm.state
    arrivals = [production_job("prod-small", 4, 1024),
                production_job("prod-large", 3, 2048),
                production_job("prod-more", 4, 1024)]
    for job in arrivals:
        server.job_register(job)

    def live(job_id):
        return [a for a in state.allocs_by_job(job_id)
                if not a.terminal_status()]

    assert wait_until(lambda: all(
        len(live(job.id)) == job.task_groups[0].count for job in arrivals)), {
            job.id: state.evals_by_job(job.id) for job in arrivals}
    assert wait_until(lambda: all(
        e.terminal_status() for job in arrivals
        for e in state.evals_by_job(job.id)), 10.0)

    evicted = [a for a in state.allocs()
               if a.desired_status == consts.ALLOC_DESIRED_EVICT]
    assert evicted
    for a in evicted:
        # only standing work below the production band goes
        assert a.id in filler_ids and victim_priority(a) < 70
        # lowest first on each node
        left = [s for s in state.allocs_by_node(a.node_id)
                if not s.terminal_status() and s.id in filler_ids]
        assert all(victim_priority(s) >= victim_priority(a) for s in left)
        # no eviction without the placement it made room for
        assert [s for s in state.allocs_by_node(a.node_id)
                if not s.terminal_status() and s.job.priority == 70
                and s.id not in filler_ids]
    assert not [a for job in arrivals for a in state.allocs_by_job(job.id)
                if a.desired_status == consts.ALLOC_DESIRED_EVICT]

    # what the device holds of the cluster is the store's sums, row for
    # row, after the evictions too
    from nomad_tpu.models.matrix import (prefetch_cluster_base,
                                         universe_nodes_cached)
    from nomad_tpu.scheduler.batcher import get_batcher

    snapshot = state.snapshot()
    view, _kind = prefetch_cluster_base(snapshot, ["dc1"])
    batcher = get_batcher()
    batcher.prefetch_base(view)
    with batcher._lock:
        dev = batcher._device_bases[view.base_token]
    universe, _by_dc, _sig = universe_nodes_cached(snapshot, ["dc1"])
    want = np.zeros((len(universe), 4))
    for i, node in enumerate(universe):
        r = node.reserved
        want[i] = (r.cpu, r.memory_mb, r.disk_mb, r.iops)
        for a in snapshot.allocs_by_node_terminal(node.id, False):
            parts = list(a.task_resources.values()) + [a.shared_resources]
            want[i] += [sum(getattr(p, dim) for p in parts)
                        for dim in ("cpu", "memory_mb", "disk_mb", "iops")]
    np.testing.assert_array_equal(
        np.asarray(dev[2], np.float64)[:len(universe)], want)
