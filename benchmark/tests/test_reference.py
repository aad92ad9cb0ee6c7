"""The plain reference against hand-made stores that break each rule
once, and against the program's own `allocs_fit` / `score_fit` on seeded
stores. `reference.py` itself imports nothing of the program; this test
does, to cross-check it."""

import random

import numpy as np
import pytest

import reference
import store_dump

PORTS = (20000, 60000)


class FakeState:
    def __init__(self, nodes, allocs, evals=()):
        self._nodes, self._allocs, self._evals = nodes, allocs, evals

    def evals(self):
        return self._evals

    def nodes(self):
        return self._nodes

    def allocs(self):
        return self._allocs


def make_node(node_id, status="ready", drain=False):
    from nomad_tpu import mock

    node = mock.node()
    node.id = node_id
    node.status = status
    node.drain = drain
    return node


def make_alloc(node_id, job_id, cpu=100, mem=64, mbits=50, ports=(20001,)):
    from nomad_tpu import mock
    from nomad_tpu.structs import NetworkResource, Port, Resources

    alloc = mock.alloc()
    alloc.id = f"{job_id}-{node_id}-{random.random()}"
    alloc.node_id, alloc.job_id = node_id, job_id
    alloc.resources = None
    alloc.shared_resources = Resources(disk_mb=150)
    networks = [NetworkResource(
        device="eth0", ip="192.168.0.100", mbits=mbits,
        dynamic_ports=[Port(f"p{p}", p) for p in ports])] if ports else []
    alloc.task_resources = {"web": Resources(cpu=cpu, memory_mb=mem,
                                             networks=networks)}
    alloc.desired_status = "run"
    alloc.client_status = "pending"
    return alloc


def judge(nodes, allocs, jobs=None):
    store = store_dump.dump_store(FakeState(nodes, allocs))
    jobs = jobs or {"j1": {"count": 1, "distinct_hosts": True}}
    return reference.judge(store, jobs, PORTS)["counts"]


def test_a_valid_store_breaks_nothing():
    nodes = [make_node("n1"), make_node("n2")]
    allocs = [make_alloc("n1", "j1", ports=(20001, 20002)),
              make_alloc("n2", "j1", ports=(20001, 20002))]
    assert not any(judge(nodes, allocs).values())


@pytest.mark.parametrize("broken,counter", [
    ("cpu", "nodes_over_cpu"),
    ("memory", "nodes_over_memory"),
    ("bandwidth", "nodes_over_bandwidth"),
    ("port", "ports_held_twice"),
    ("reserved_port", "ports_held_twice"),
    ("distinct_hosts", "distinct_hosts_shared"),
    ("draining", "nodes_not_ready_or_draining"),
    ("down", "nodes_not_ready_or_draining"),
    ("unknown_node", "allocs_on_unknown_node"),
])
def test_each_broken_rule_is_counted_once(broken, counter):
    nodes = [make_node("n1"), make_node("n2")]
    allocs = [make_alloc("n1", "j1", ports=(20001,)),
              make_alloc("n2", "filler", ports=(20005,))]
    if broken == "cpu":        # 4000 MHz less 100 reserved
        allocs.append(make_alloc("n1", "filler", cpu=3801, ports=()))
    elif broken == "memory":   # 8192 MB less 256 reserved
        allocs.append(make_alloc("n1", "filler", mem=7873, ports=()))
    elif broken == "bandwidth":  # 1000 Mbit less 1 reserved
        allocs.append(make_alloc("n1", "filler", mbits=950, ports=(20009,)))
    elif broken == "port":
        allocs.append(make_alloc("n1", "filler", ports=(20001,)))
    elif broken == "reserved_port":   # the node reserves 22
        allocs.append(make_alloc("n1", "filler", ports=(22,)))
    elif broken == "distinct_hosts":
        allocs.append(make_alloc("n1", "j1", ports=(20002,)))
    elif broken == "draining":
        nodes[0].drain = True
    elif broken == "down":
        nodes[0].status = "down"
    elif broken == "unknown_node":
        allocs.append(make_alloc("gone", "j1", ports=()))
    counts = judge(nodes, allocs)
    assert counts.pop(counter) == 1
    assert not any(counts.values()), counts


def test_an_untouched_node_is_not_judged_and_dead_allocs_do_not_count():
    nodes = [make_node("n1"), make_node("n2", status="down")]
    dead = make_alloc("n1", "filler", cpu=3900, ports=(20001,))
    dead.desired_status = "stop"
    lost = make_alloc("n1", "filler", cpu=3900, ports=(20001,))
    lost.client_status = "lost"
    allocs = [make_alloc("n1", "j1", ports=(20001,)), dead, lost,
              make_alloc("n2", "filler", cpu=3900)]
    assert not any(judge(nodes, allocs).values())


def test_what_went_is_kept_apart_for_a_deployments_own_checks():
    """An evicted or stopped allocation is no part of any sum, and is
    copied out with its node, job and priority; evaluations come with
    their job, status, trigger and predecessor. Keys `reference.judge`
    reads are the live ones, as before."""
    from nomad_tpu import mock

    nodes = [make_node("n1"), make_node("n2")]
    live = make_alloc("n1", "j1")
    live.job.priority = 80
    evicted = make_alloc("n2", "low", ports=(20002,))
    evicted.desired_status = "evict"
    evicted.job.priority = 20
    stopped = make_alloc("n1", "old", ports=())
    stopped.desired_status = "stop"
    stopped.job = None
    follow_up = mock.eval()
    follow_up.job_id, follow_up.triggered_by = "low", "preemption"
    store = store_dump.dump_store(
        FakeState(nodes, [live, evicted, stopped], [follow_up]))
    assert store["alloc_ids"] == [live.id]
    assert list(store["alloc_priority"]) == [80]
    assert store["gone_ids"] == [evicted.id, stopped.id]
    assert list(store["gone_node"]) == [1, 0]
    assert store["gone_job"] == ["low", "old"]
    assert list(store["gone_priority"]) == [20, -1]
    assert store["gone_desired"] == ["evict", "stop"]
    assert store["eval_job"] == ["low"]
    assert store["eval_trigger"] == ["preemption"]
    assert store["eval_ids"] == [follow_up.id]
    assert store["eval_status"] == [follow_up.status]
    assert store["eval_previous"] == [follow_up.previous_eval]
    assert not any(reference.judge(
        store, {"j1": {"count": 1, "distinct_hosts": True}},
        PORTS)["counts"].values())


@pytest.mark.parametrize("seed", range(8))
def test_agrees_with_allocs_fit_and_score_fit(seed):
    """Seeded stores, some nodes packed past a limit: node by node, the
    reference's verdict and sums are the program's host functions'."""
    from nomad_tpu.structs.funcs import allocs_fit, score_fit

    rng = random.Random(seed)
    nodes = [make_node(f"n{i}") for i in range(24)]
    allocs = []
    for node in nodes:
        for k in range(rng.randrange(1, 14)):
            allocs.append(make_alloc(
                node.id, f"j{k}", cpu=rng.choice([50, 300, 700]),
                mem=rng.choice([64, 512, 1500]),
                mbits=rng.choice([0, 50, 200]),
                ports=(rng.choice([20000 + k, 20001, 59999]),)))
    jobs = {f"j{k}": {"count": 1, "distinct_hosts": False} for k in range(14)}
    store = store_dump.dump_store(FakeState(nodes, allocs))
    verdict = reference.judge(store, jobs, PORTS)
    sums = verdict["sums"]
    scores = reference.fit_scores(store, sums["util"])
    unfit = {"cpu": 0, "memory": 0, "bandwidth exceeded": 0,
             "reserved port collision": 0}
    for i, node in enumerate(nodes):
        mine = [a for a in allocs if a.node_id == node.id]
        fit, dimension, used = allocs_fit(node, mine)
        assert sums["util"][i, 0] == used.cpu
        assert sums["util"][i, 1] == used.memory_mb
        assert sums["util"][i, 2] == used.disk_mb
        assert scores[i] == pytest.approx(score_fit(node, used), abs=1e-12)
        if not fit:
            unfit[dimension] += 1
    counts = verdict["counts"]
    # allocs_fit names the first exhausted dimension only; the reference
    # counts each, so it finds at least as many and flags the same nodes.
    assert counts["nodes_over_cpu"] >= unfit["cpu"]
    assert counts["nodes_over_cpu"] + counts["nodes_over_memory"] \
        >= unfit["cpu"] + unfit["memory"]
    flagged = ((sums["util"] > store["node_cap"]).any(axis=1)
               | (sums["bw_used"] > store["node_mbits"]))
    port_nodes = np.concatenate([store["reserved_port_node"],
                                 store["alloc_node"][store["port_alloc"]]])
    port_vals = np.concatenate([store["reserved_port_value"],
                                store["port_value"]])
    for i, node in enumerate(nodes):
        vals = port_vals[port_nodes == i]
        collide = len(vals) != len(set(vals.tolist()))
        fit, _, _ = allocs_fit(
            node, [a for a in allocs if a.node_id == node.id])
        assert fit == (not flagged[i] and not collide), (i, fit)
    assert sum(unfit.values()) > 0      # the seeds do overfill some nodes


def test_packing_prefers_fuller_nodes():
    nodes = [make_node(f"n{i}") for i in range(50)]
    allocs = [make_alloc("n0", "j1", cpu=1500, mem=3000, ports=()),
              make_alloc("n1", "j1", cpu=1500, mem=3000, ports=())]
    store = store_dump.dump_store(FakeState(nodes, allocs))
    jobs = {"j1": {"count": 2, "distinct_hosts": True}}
    sums = reference.judge(store, jobs, PORTS)["sums"]
    out = reference.packing(store, jobs, sums, np.random.default_rng(3))
    assert out["allocs"] == 2
    assert out["placed_mean"] > out["uniform_mean"] + 1.0
