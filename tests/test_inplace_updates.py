"""In-place updates (scheduler/util.py tasks_updated rules +
inplace_update_batched): compatible env/meta-level job tweaks mutate
allocs with zero evictions and zero device placements; incompatible
updates (resource bumps, config changes) route to the dense placement
path — verified against the CPU oracle (host scheduler) differentially."""

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs import consts
from nomad_tpu.structs.eval import new_eval


def _cluster(seed, n_nodes=6, count=6):
    h = Harness(seed=seed)
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        n.compute_class()
        h.state.upsert_node(h.next_index(), n)
        nodes.append(n)
    job = mock.job()
    job.task_groups[0].count = count
    t = job.task_groups[0].tasks[0]
    t.resources.cpu = 100
    t.resources.memory_mb = 64
    t.resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    return h, job, nodes


def _place(h, job, factory):
    h.process(factory, new_eval(h.state.job_by_id(job.id),
                                consts.EVAL_TRIGGER_JOB_REGISTER))
    return {a.id: a for a in h.state.allocs_by_job(job.id)
            if not a.terminal_status()}


@pytest.mark.parametrize("factory", ["service", "service-tpu"])
def test_env_meta_update_is_in_place_zero_churn(factory):
    """A compatible update (env/meta tweak) rewrites every alloc in
    place: same ids, same nodes, zero evictions — and on the dense
    factory, zero device placements (the plan stages no node_update
    and the batcher sees no bulk set)."""
    from nomad_tpu.scheduler.batcher import get_batcher

    h, job, _nodes = _cluster(seed=41)
    before = _place(h, job, factory)
    assert len(before) == 6

    job2 = job.copy()
    job2.task_groups[0].tasks[0].env = {"FOO": "v2"}
    job2.task_groups[0].tasks[0].meta = {"team": "x"}
    h.state.upsert_job(h.next_index(), job2)

    pre_dispatches = get_batcher().stats()["dispatches"]
    h.process(factory, new_eval(h.state.job_by_id(job.id),
                                consts.EVAL_TRIGGER_JOB_REGISTER))
    plan = h.plans[-1]
    assert plan.node_update == {}  # zero evictions
    assert plan.node_preemptions == {}
    placed = [a for lst in plan.node_allocation.values() for a in lst]
    assert {a.id for a in placed} == set(before)  # in-place rewrites
    after = {a.id: a for a in h.state.allocs_by_job(job.id)
             if not a.terminal_status()}
    assert set(after) == set(before)
    assert all(after[i].node_id == before[i].node_id for i in before)
    # zero device placements: the batcher dispatched nothing for this
    assert get_batcher().stats()["dispatches"] == pre_dispatches
    assert h.evals[-1].status == consts.EVAL_STATUS_COMPLETE


@pytest.mark.parametrize("factory", ["service", "service-tpu"])
def test_resource_bump_routes_destructive(factory):
    """An incompatible update (resource bump) is destructive: old
    allocs evict, fresh ids place — through the dense path on the
    dense factory."""
    h, job, _nodes = _cluster(seed=42)
    before = _place(h, job, factory)

    job2 = job.copy()
    job2.task_groups[0].tasks[0].resources.cpu = 200
    h.state.upsert_job(h.next_index(), job2)
    h.process(factory, new_eval(h.state.job_by_id(job.id),
                                consts.EVAL_TRIGGER_JOB_REGISTER))
    plan = h.plans[-1]
    evicted = [a for lst in plan.node_update.values() for a in lst]
    placed = [a for lst in plan.node_allocation.values() for a in lst]
    assert len(evicted) == 6
    assert {a.id for a in placed}.isdisjoint(set(before))
    live = [a for a in h.state.allocs_by_job(job.id)
            if not a.terminal_status()]
    assert len(live) == 6
    assert all(a.task_resources["web"].cpu == 200 for a in live)


def test_inplace_parity_host_vs_dense():
    """The batched in-place pass must agree with the sequential CPU
    oracle update-for-update: same in-place set, same destructive set,
    on a mixed update (one TG compatible tweak + a node gone)."""
    results = {}
    for factory, seed in (("service", 43), ("service-tpu", 43)):
        h, job, nodes = _cluster(seed=seed)
        before = _place(h, job, factory)
        # make one node's allocs impossible to update in place
        victim_node = next(iter(before.values())).node_id
        h.state.update_node_status(
            h.next_index(), victim_node, consts.NODE_STATUS_DOWN)
        job2 = job.copy()
        job2.task_groups[0].tasks[0].env = {"X": "1"}
        h.state.upsert_job(h.next_index(), job2)
        h.process(factory, new_eval(h.state.job_by_id(job.id),
                                    consts.EVAL_TRIGGER_JOB_REGISTER))
        live = [a for a in h.state.allocs_by_job(job.id)
                if not a.terminal_status()]
        kept = len([a for a in live if a.id in before])
        results[factory] = (len(live), kept)
    assert results["service"] == results["service-tpu"], results


def test_constraint_tightening_is_destructive_for_offending_nodes():
    """A job-level constraint tightening must NOT be rewritten in
    place on nodes the new spec forbids (the batched path re-checks
    constraints host-side; the fuzz suite covers the randomized
    version)."""
    from nomad_tpu.structs import Constraint

    h = Harness(seed=44)
    nodes = []
    for i in range(6):
        n = mock.node()
        n.meta["rack"] = f"r{i % 2}"
        n.compute_class()
        h.state.upsert_node(h.next_index(), n)
        nodes.append(n)
    job = mock.job()
    job.task_groups[0].count = 4
    t = job.task_groups[0].tasks[0]
    t.resources.cpu = 100
    t.resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    before = _place(h, job, "service-tpu")

    job2 = job.copy()
    job2.constraints.append(Constraint(
        ltarget="${meta.rack}", operand="=", rtarget="r0"))
    h.state.upsert_job(h.next_index(), job2)
    h.process("service-tpu", new_eval(h.state.job_by_id(job.id),
                                      consts.EVAL_TRIGGER_JOB_REGISTER))
    r0 = {n.id for n in nodes if n.meta["rack"] == "r0"}
    live = [a for a in h.state.allocs_by_job(job.id)
            if not a.terminal_status()]
    assert len(live) == 4
    assert all(a.node_id in r0 for a in live), before


# ---------------------------------------------------------------------
# client side: the in-place update must actually reach the running
# task (restart with the re-rendered environment, same alloc id)


def test_inplace_env_update_rerenders_running_task(tmp_path):
    """An env-only update keeps the alloc (same id, no replacement)
    AND the live task restarts with the new environment — the client
    half of the in-place contract (AllocRunner.update →
    TaskRunner.update_inplace)."""
    import os
    import time

    from nomad_tpu.api import HTTPServer
    from nomad_tpu.client import ClientAgent, ClientConfig
    from nomad_tpu.server import Server, ServerConfig

    def wait_until(fn, timeout=30.0, interval=0.05):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if fn():
                return True
            time.sleep(interval)
        return False

    server = Server(ServerConfig(num_schedulers=1, eval_nack_timeout=5.0))
    server.start()
    http = HTTPServer(server)
    http.start()
    cfg = ClientConfig(
        servers=[http.addr],
        state_dir=str(tmp_path / "state"),
        alloc_dir=str(tmp_path / "allocs"),
        options={"driver.raw_exec.enable": "1"},
        dev_mode=True,
    )
    os.makedirs(cfg.state_dir, exist_ok=True)
    agent = ClientAgent(cfg)
    agent.start()
    try:
        job = mock.job()
        tg = job.task_groups[0]
        tg.count = 1
        task = tg.tasks[0]
        task.driver = "raw_exec"
        # Appends the rendered env value on every start.
        task.config = {
            "command": "/bin/sh",
            "args": ["-c",
                     'echo "$MARK$NOMAD_META_PHASE" '
                     '>> "$NOMAD_TASK_DIR/mark.txt"; '
                     "sleep 600"],
        }
        task.env = {"MARK": "v1"}
        task.resources.cpu = 10
        task.resources.memory_mb = 10
        task.resources.networks = []
        server.job_register(job)

        def running():
            for a in server.fsm.state.allocs_by_job(job.id):
                if a.client_status == consts.ALLOC_CLIENT_RUNNING:
                    return a
            return None

        assert wait_until(lambda: running() is not None)
        alloc1 = running()

        def marks():
            runner = agent.alloc_runners.get(alloc1.id)
            if runner is None:
                return []
            try:
                raw = runner.alloc_dir.read_at("web/local/mark.txt")
            except (FileNotFoundError, OSError):
                return []
            return raw.decode().split()

        assert wait_until(lambda: marks() == ["v1"])

        job2 = job.copy()
        job2.task_groups[0].tasks[0].env = {"MARK": "v2"}
        server.job_register(job2)

        # same alloc id survives; the task restarted and rendered v2
        assert wait_until(lambda: marks() == ["v1", "v2"], 30.0), marks()
        live = [a for a in server.fsm.state.allocs_by_job(job.id)
                if not a.terminal_status()]
        assert [a.id for a in live] == [alloc1.id]
        assert wait_until(lambda: (running() or live[0]).client_status
                          == consts.ALLOC_CLIENT_RUNNING)

        # group-level meta renders into NOMAD_META_* without living on
        # the Task: a tg.meta-ONLY tweak must ALSO restart-and-render
        # (the task-def diff alone cannot see it).
        job3 = job2.copy()
        job3.task_groups[0].meta = dict(job3.task_groups[0].meta,
                                        PHASE="-p3")
        server.job_register(job3)
        assert wait_until(lambda: marks() == ["v1", "v2", "v2-p3"],
                          30.0), marks()
        job4 = job3.copy()
        job4.task_groups[0].meta = dict(job4.task_groups[0].meta,
                                        PHASE="-p4")
        server.job_register(job4)
        assert wait_until(
            lambda: marks() == ["v1", "v2", "v2-p3", "v2-p4"],
            30.0), marks()
        live = [a for a in server.fsm.state.allocs_by_job(job.id)
                if not a.terminal_status()]
        assert [a.id for a in live] == [alloc1.id]
    finally:
        agent.shutdown(destroy_allocs=True)
        http.stop()
        server.shutdown()


def test_http_lists_an_alloc_updated_in_place_under_its_new_eval():
    """`GET /v1/evaluation/<id>/allocations` after the same job body is
    registered again: every allocation is rewritten in place under the
    new evaluation, is listed there, and is no longer listed under the
    evaluation that placed it (state/store.py upsert_allocs moves the
    eval index's entry). Nothing is stopped and the ids survive."""
    import json
    import time
    import urllib.request

    from nomad_tpu.api.http import HTTPServer
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.utils.codec import to_dict

    server = Server(ServerConfig(
        num_schedulers=1, scheduler_factories={"service": "service-tpu"}))
    server.start()
    http = HTTPServer(server, host="127.0.0.1", port=0)
    http.start()

    def call(method, path, body=None):
        req = urllib.request.Request(
            http.addr + path, method=method,
            data=None if body is None else json.dumps(body).encode())
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read())

    def registered(job):
        eval_id = call("PUT", "/v1/jobs", {"job": to_dict(job)})["eval_id"]
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if call("GET", f"/v1/evaluation/{eval_id}")["status"] \
                    == "complete":
                return eval_id
            time.sleep(0.02)
        raise AssertionError(f"evaluation {eval_id} did not complete")

    def listed(eval_id):
        return {a["id"]: a["desired_status"]
                for a in call("GET", f"/v1/evaluation/{eval_id}/allocations")}

    try:
        for _ in range(8):
            node = mock.node()
            node.compute_class()
            server.node_register(node)
        job = mock.job()
        job.task_groups[0].count = 5
        first = registered(job)
        placed = listed(first)
        assert len(placed) == 5 and set(placed.values()) == {"run"}
        second = registered(job)
        assert second != first
        assert listed(second) == placed
        assert listed(first) == {}
        live = [a for a in server.fsm.state.allocs_by_job(job.id)
                if not a.terminal_status()]
        assert {a.id for a in live} == set(placed)
        assert {a.eval_id for a in live} == {second}
    finally:
        http.stop()
        server.shutdown()
