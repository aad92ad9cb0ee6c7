"""scheduler/util unit tests (mirror scheduler/util_test.go):
materialize, diff_allocs buckets, tasks_updated sensitivity,
tainted_nodes, ready_nodes_in_dcs, retry_max."""

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.util import (
    SetStatusError,
    diff_allocs,
    diff_system_allocs,
    materialize_task_groups,
    ready_nodes_in_dcs,
    retry_max,
    tainted_nodes,
    tasks_updated,
)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import consts


def test_materialize_task_groups_counts():
    job = mock.job()
    job.task_groups[0].count = 3
    groups = materialize_task_groups(job)
    assert sorted(groups) == [f"{job.name}.web[{i}]" for i in range(3)]
    assert materialize_task_groups(None) == {}


def make_allocs(job, names, node="n1"):
    out = []
    for name in names:
        a = mock.alloc()
        a.job = job
        a.job_id = job.id
        a.name = name
        a.node_id = node
        a.task_group = "web"
        out.append(a)
    return out


def test_diff_allocs_buckets():
    """TestDiffAllocs: place/ignore/stop/migrate/lost all at once."""
    job = mock.job()
    job.task_groups[0].count = 4
    store = StateStore()
    store.upsert_job(1, job)
    job = store.job_by_id(job.id)  # stored copy: indexes advanced
    groups = materialize_task_groups(job)
    names = sorted(groups)

    existing = make_allocs(job, [names[0], names[1], names[2]])
    # names[3] missing -> place
    tainted = {"drained": None, "down": None}
    existing[1].node_id = "drained"  # tainted with node None -> lost
    existing[2].name = "not-in-job"  # no longer wanted -> stop

    diff = diff_allocs(job, tainted, groups, existing, {})
    # names[2]'s slot was vacated by the renamed alloc; names[3] never
    # existed — both get placed
    assert sorted(t.name for t in diff.place) == [names[2], names[3]]
    assert [t.alloc.name for t in diff.stop] == ["not-in-job"]
    assert [t.alloc.name for t in diff.lost] == [names[1]]
    # untouched alloc with same job version -> ignore
    assert [t.alloc.name for t in diff.ignore] == [names[0]]


def test_diff_system_allocs_per_node():
    job = mock.system_job()
    store = StateStore()
    store.upsert_job(1, job)
    job = store.job_by_id(job.id)
    n1, n2 = mock.node(), mock.node()
    a = mock.alloc()
    a.job = job
    a.job_id = job.id
    a.node_id = n1.id
    a.task_group = "web"
    a.name = f"{job.name}.web[0]"
    diff = diff_system_allocs(job, [n1, n2], {}, [a], {})
    # already on n1 -> ignore; n2 missing -> place pinned to n2
    assert len(diff.ignore) == 1
    assert [t.alloc.node_id for t in diff.place] == [n2.id]


def test_tasks_updated_sensitivity():
    a = mock.job().task_groups[0]
    same = mock.job().task_groups[0]
    assert not tasks_updated(a, same)
    for mutate in (
        lambda tg: tg.tasks[0].config.update({"x": 1}),
        lambda tg: setattr(tg.tasks[0], "driver", "other"),
        lambda tg: setattr(tg.tasks[0].resources, "cpu", 9999),
        lambda tg: setattr(tg.tasks[0].resources, "disk_mb", 9999),
        lambda tg: tg.tasks.append(a.tasks[0].copy()),
    ):
        changed = mock.job().task_groups[0]
        mutate(changed)
        assert tasks_updated(a, changed), mutate
    # env/meta-level tweaks are in-place compatible (README "Churn &
    # migration"): the client re-renders without the placement moving.
    for mutate in (
        lambda tg: tg.tasks[0].env.update({"K": "V"}),
        lambda tg: tg.tasks[0].meta.update({"team": "x"}),
    ):
        changed = mock.job().task_groups[0]
        mutate(changed)
        assert not tasks_updated(a, changed), mutate


def test_tainted_nodes():
    store = StateStore()
    ready = mock.node()
    drained = mock.node()
    drained.drain = True
    down = mock.node()
    down.status = consts.NODE_STATUS_DOWN
    for i, n in enumerate((ready, drained, down)):
        store.upsert_node(i + 1, n)
    allocs = []
    for node_id in (ready.id, drained.id, down.id, "vanished"):
        a = mock.alloc()
        a.node_id = node_id
        allocs.append(a)
    tainted = tainted_nodes(store.snapshot(), allocs)
    assert ready.id not in tainted
    assert tainted[drained.id] is not None
    assert tainted[down.id] is not None
    assert tainted["vanished"] is None  # deregistered node


def test_ready_nodes_in_dcs():
    store = StateStore()
    for i, (dc, status, drain) in enumerate((
        ("dc1", consts.NODE_STATUS_READY, False),
        ("dc2", consts.NODE_STATUS_READY, False),
        ("dc1", consts.NODE_STATUS_DOWN, False),
        ("dc1", consts.NODE_STATUS_READY, True),
        ("dc3", consts.NODE_STATUS_READY, False),
    )):
        n = mock.node()
        n.datacenter = dc
        n.status = status
        n.drain = drain
        store.upsert_node(i + 1, n)
    nodes, by_dc = ready_nodes_in_dcs(store.snapshot(), ["dc1", "dc2"])
    assert len(nodes) == 2  # down/drained/dc3 filtered
    assert by_dc == {"dc1": 1, "dc2": 1}


def test_retry_max():
    calls = []

    def fails():
        calls.append(1)
        return False

    with pytest.raises(SetStatusError):
        retry_max(3, fails, None)
    assert len(calls) == 3

    # a reset callback returning True restarts the attempt budget
    resets = iter([True, True, False, False, False, False, False])
    calls.clear()

    def fails2():
        calls.append(1)
        return False

    with pytest.raises(SetStatusError):
        retry_max(2, fails2, lambda: next(resets))
    assert len(calls) == 4  # 2 attempts, reset twice, then exhausted
