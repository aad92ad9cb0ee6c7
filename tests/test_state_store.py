"""State store tests (mirror nomad/state/state_store_test.go scenarios)."""

import threading

import pytest

from nomad_tpu import mock
from nomad_tpu.state import StateStore, watch
from nomad_tpu.structs import consts


def test_upsert_node_and_indexes():
    s = StateStore()
    n = mock.node()
    s.upsert_node(1000, n)
    out = s.node_by_id(n.id)
    assert out.id == n.id
    assert out.create_index == 1000 and out.modify_index == 1000
    assert s.index("nodes") == 1000
    assert s.latest_index() == 1000


def test_update_node_status():
    s = StateStore()
    n = mock.node()
    s.upsert_node(1, n)
    s.update_node_status(2, n.id, consts.NODE_STATUS_DOWN)
    assert s.node_by_id(n.id).status == consts.NODE_STATUS_DOWN
    assert s.node_by_id(n.id).modify_index == 2


def test_update_node_drain():
    s = StateStore()
    n = mock.node()
    s.upsert_node(1, n)
    s.update_node_drain(2, n.id, True)
    assert s.node_by_id(n.id).drain is True


def test_snapshot_isolation():
    s = StateStore()
    n = mock.node()
    s.upsert_node(1, n)
    snap = s.snapshot()
    n2 = mock.node()
    s.upsert_node(2, n2)
    assert len(snap.nodes()) == 1
    assert len(s.snapshot().nodes()) == 2
    assert snap.latest_index() == 1


def test_index_set_isolation_under_hot_key():
    """The secondary indexes mutate a set in place only while it is
    private (created/copied since the last snapshot) — a snapshot's
    view of a hot key must not grow or shrink under later writes."""
    s = StateStore()

    def mk():
        a = mock.alloc()
        a.job_id = "hot"
        return [a]

    s.upsert_allocs(1, mk())
    snap1 = s.snapshot()
    # These adds hit the in-place path (sets copied once post-share,
    # then mutated privately): snap1 must keep seeing exactly 1.
    for i in range(2, 6):
        s.upsert_allocs(i, mk())
    assert len(snap1.allocs_by_job("hot")) == 1
    assert len(s.snapshot().allocs_by_job("hot")) == 5
    # Same for removal: deleting from the live index leaves snapshots
    # intact, including one taken mid-burst.
    snap5 = s.snapshot()
    doomed = [a.id for a in s.snapshot().allocs_by_job("hot")][:3]
    s.delete_evals(6, [], doomed)
    assert len(snap5.allocs_by_job("hot")) == 5
    assert len(snap1.allocs_by_job("hot")) == 1
    assert len(s.snapshot().allocs_by_job("hot")) == 2


def test_upsert_job_preserves_create_index():
    s = StateStore()
    j = mock.job()
    s.upsert_job(10, j)
    j2 = j.copy()
    j2.priority = 70
    s.upsert_job(20, j2)
    out = s.job_by_id(j.id)
    assert out.create_index == 10
    assert out.modify_index == 20
    assert out.job_modify_index == 20
    assert out.priority == 70


def test_job_summary_created():
    s = StateStore()
    j = mock.job()
    s.upsert_job(10, j)
    summary = s.job_summary_by_id(j.id)
    assert summary is not None
    assert "web" in summary.summary


def test_upsert_allocs_and_queries():
    s = StateStore()
    j = mock.job()
    s.upsert_job(5, j)
    a = mock.alloc()
    a.job = j
    a.job_id = j.id
    s.upsert_allocs(10, [a])
    assert s.alloc_by_id(a.id).id == a.id
    assert [x.id for x in s.allocs_by_job(j.id)] == [a.id]
    assert [x.id for x in s.allocs_by_node(a.node_id)] == [a.id]
    assert [x.id for x in s.allocs_by_eval(a.eval_id)] == [a.id]
    # job derived status: alloc is non-terminal -> running
    assert s.job_by_id(j.id).status == consts.JOB_STATUS_RUNNING


def test_an_alloc_updated_in_place_moves_to_its_new_eval():
    """The eval index is by the field's value (memdb): an allocation
    that comes back under another eval (an in-place update,
    scheduler/util.py _stage_inplace_alloc) is listed there and no
    longer under the eval that placed it; snapshots taken before keep
    what they saw."""
    s = StateStore()
    j = mock.job()
    s.upsert_job(5, j)
    a, other = mock.alloc(), mock.alloc()
    for x in (a, other):
        x.job, x.job_id, x.eval_id = j, j.id, "eval-one"
    s.upsert_allocs(10, [a, other])
    before = s.snapshot()
    woke = s.notify.watch([watch.alloc_eval("eval-one")])
    updated = a.copy()
    updated.eval_id = "eval-two"
    s.upsert_allocs(11, [updated])
    assert [x.id for x in s.allocs_by_eval("eval-two")] == [a.id]
    assert [x.id for x in s.allocs_by_eval("eval-one")] == [other.id]
    assert s.alloc_by_id(a.id).create_index == 10
    assert {x.id for x in before.allocs_by_eval("eval-one")} == {
        a.id, other.id}
    assert before.allocs_by_eval("eval-two") == []
    assert woke.is_set()    # a reader parked on the old eval's list
    # the same eval again: nothing moves
    s.upsert_allocs(12, [updated.copy()])
    assert [x.id for x in s.allocs_by_eval("eval-two")] == [a.id]
    # a restored store indexes what it reads
    s2 = StateStore.restore(s.persist())
    assert [x.id for x in s2.allocs_by_eval("eval-two")] == [a.id]
    assert [x.id for x in s2.allocs_by_eval("eval-one")] == [other.id]


def test_upsert_allocs_copies_shared_metrics():
    """The TPU pinned-placement path shares ONE AllocMetric across a
    plan's successful allocs (scheduler/tpu.py); the store's upsert
    copy must deep-copy it per stored alloc so no later in-place
    mutation of one alloc's metrics can alter its siblings."""
    from nomad_tpu.structs.alloc import AllocMetric

    s = StateStore()
    shared = AllocMetric()
    shared.evaluate_node()
    a1, a2 = mock.alloc(), mock.alloc()
    a1.metrics = a2.metrics = shared
    s.upsert_allocs(10, [a1, a2])
    m1 = s.alloc_by_id(a1.id).metrics
    m2 = s.alloc_by_id(a2.id).metrics
    assert m1 is not shared and m2 is not shared and m1 is not m2
    m1.nodes_evaluated = 999
    assert m2.nodes_evaluated != 999


def test_upsert_allocs_preserves_client_status():
    s = StateStore()
    a = mock.alloc()
    s.upsert_allocs(10, [a])
    cl = a.copy()
    cl.client_status = consts.ALLOC_CLIENT_RUNNING
    s.update_allocs_from_client(11, [cl])
    # scheduler-side re-upsert must not clobber the client status
    sched = a.copy()
    sched.desired_status = consts.ALLOC_DESIRED_RUN
    s.upsert_allocs(12, [sched])
    out = s.alloc_by_id(a.id)
    assert out.client_status == consts.ALLOC_CLIENT_RUNNING
    assert out.modify_index == 12


def test_update_allocs_from_client_keeps_alloc_modify_index():
    s = StateStore()
    a = mock.alloc()
    s.upsert_allocs(10, [a])
    cl = a.copy()
    cl.client_status = consts.ALLOC_CLIENT_RUNNING
    s.update_allocs_from_client(11, [cl])
    out = s.alloc_by_id(a.id)
    assert out.alloc_modify_index == 10  # client writes don't bump it
    assert out.modify_index == 11


def test_allocs_by_node_terminal():
    s = StateStore()
    a1 = mock.alloc()
    a2 = mock.alloc()
    a2.node_id = a1.node_id
    a2.desired_status = consts.ALLOC_DESIRED_STOP
    s.upsert_allocs(10, [a1, a2])
    live = s.allocs_by_node_terminal(a1.node_id, False)
    term = s.allocs_by_node_terminal(a1.node_id, True)
    assert [a.id for a in live] == [a1.id]
    assert [a.id for a in term] == [a2.id]


def test_upsert_evals_and_job_summary_queued():
    s = StateStore()
    j = mock.job()
    s.upsert_job(5, j)
    e = mock.eval()
    e.job_id = j.id
    e.queued_allocations = {"web": 4}
    s.upsert_evals(10, [e])
    assert s.eval_by_id(e.id).modify_index == 10
    assert [x.id for x in s.evals_by_job(j.id)] == [e.id]
    assert s.job_summary_by_id(j.id).summary["web"].queued == 4
    # eval pending + no allocs -> job pending
    assert s.job_by_id(j.id).status == consts.JOB_STATUS_PENDING


def test_delete_evals_and_allocs():
    s = StateStore()
    e = mock.eval()
    a = mock.alloc()
    s.upsert_evals(10, [e])
    s.upsert_allocs(11, [a])
    s.delete_evals(12, [e.id], [a.id])
    assert s.eval_by_id(e.id) is None
    assert s.alloc_by_id(a.id) is None
    assert s.allocs_by_job(a.job_id) == []


def test_fresh_job_status_pending():
    """A new job with nothing outstanding is pending; dead only applies
    once terminal evals/allocs exist (state_store.go:1457)."""
    s = StateStore()
    j = mock.job()
    s.upsert_job(5, j)
    assert s.job_by_id(j.id).status == consts.JOB_STATUS_PENDING
    from nomad_tpu.structs import PeriodicConfig

    jp = mock.job()
    jp.periodic = PeriodicConfig(enabled=True, spec="0 0 * * *")
    s.upsert_job(6, jp)
    assert s.job_by_id(jp.id).status == consts.JOB_STATUS_RUNNING


def test_job_status_dead_after_eval_gc():
    s = StateStore()
    j = mock.job()
    s.upsert_job(5, j)
    e = mock.eval()
    e.job_id = j.id
    s.upsert_evals(6, [e])
    s.delete_evals(7, [e.id], [])
    assert s.job_by_id(j.id).status == consts.JOB_STATUS_DEAD


def test_job_status_dead_after_terminal():
    s = StateStore()
    j = mock.job()
    s.upsert_job(5, j)
    e = mock.eval()
    e.job_id = j.id
    s.upsert_evals(6, [e])
    assert s.job_by_id(j.id).status == consts.JOB_STATUS_PENDING
    e2 = e.copy()
    e2.status = consts.EVAL_STATUS_COMPLETE
    s.upsert_evals(7, [e2])
    assert s.job_by_id(j.id).status == consts.JOB_STATUS_DEAD


def test_watch_fires_on_write():
    s = StateStore()
    ev = s.watch([watch.table("nodes")])
    assert not ev.is_set()
    s.upsert_node(1, mock.node())
    assert ev.wait(1.0)


def test_watch_scoped_to_job():
    s = StateStore()
    j1, j2 = mock.job(), mock.job()
    s.upsert_job(1, j1)
    s.upsert_job(2, j2)
    a1 = mock.alloc()
    a1.job_id = j1.id
    ev = s.watch([watch.alloc_job(j2.id)])
    s.upsert_allocs(3, [a1])
    assert not ev.is_set()
    a2 = mock.alloc()
    a2.job_id = j2.id
    s.upsert_allocs(4, [a2])
    assert ev.wait(1.0)


def test_persist_restore_roundtrip():
    s = StateStore()
    j = mock.job()
    n = mock.node()
    e = mock.eval()
    a = mock.alloc()
    a.job_id = j.id
    s.upsert_job(1, j)
    s.upsert_node(2, n)
    s.upsert_evals(3, [e])
    s.upsert_allocs(4, [a])
    data = s.persist()
    s2 = StateStore.restore(data)
    assert s2.latest_index() == 4
    assert s2.job_by_id(j.id) is not None
    assert s2.node_by_id(n.id) is not None
    assert s2.eval_by_id(e.id) is not None
    assert [x.id for x in s2.allocs_by_job(j.id)] == [a.id]


def test_concurrent_snapshot_consistency():
    """Writers must never corrupt a reader's snapshot."""
    s = StateStore()
    stop = threading.Event()
    errors = []

    def writer():
        i = 1
        while not stop.is_set():
            s.upsert_node(i, mock.node())
            i += 1

    def reader():
        while not stop.is_set():
            snap = s.snapshot()
            nodes = snap.nodes()
            if len(nodes) != len(snap.nodes()):
                errors.append("snapshot changed size")

    threads = [threading.Thread(target=writer), threading.Thread(target=reader)]
    for t in threads:
        t.start()
    import time

    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert errors == []


def test_persist_restore_every_table_via_json():
    """Full per-table round-trip THROUGH JSON — exactly what the raft
    snapshot files store (fsm_test.go round-trips per SnapshotType)."""
    import json as _json

    from nomad_tpu.structs.alloc import VaultAccessor
    from nomad_tpu.state.store import PeriodicLaunch

    s = StateStore()
    j = mock.job()
    n = mock.node()
    e = mock.eval()
    a = mock.alloc()
    a.job_id = j.id
    a.node_id = n.id
    a.client_status = "running"
    s.upsert_job(1, j)
    s.upsert_node(2, n)
    s.upsert_evals(3, [e])
    s.upsert_allocs(4, [a])
    s.upsert_periodic_launch(5, PeriodicLaunch(id=j.id, launch=123.0))
    s.upsert_vault_accessors(6, [VaultAccessor(
        accessor="acc1", alloc_id=a.id, task="web", node_id=n.id,
        policies=["p1"])])

    data = _json.loads(_json.dumps(s.persist()))  # the raft wire format
    s2 = StateStore.restore(data)

    assert s2.latest_index() == 6
    assert s2.job_by_id(j.id).name == j.name
    assert s2.node_by_id(n.id).datacenter == n.datacenter
    assert s2.eval_by_id(e.id).priority == e.priority
    # secondary indexes rebuilt, not just primary rows
    assert [x.id for x in s2.allocs_by_job(j.id)] == [a.id]
    assert [x.id for x in s2.allocs_by_node(n.id)] == [a.id]
    assert [x.id for x in s2.allocs_by_eval(a.eval_id)] == [a.id]
    launch = s2.periodic_launch_by_id(j.id)
    assert launch is not None and launch.launch == 123.0
    accs = s2.vault_accessors_by_alloc(a.id)
    assert [v.accessor for v in accs] == ["acc1"]
    # derived job summary survives
    summary = s2.job_summary_by_id(j.id)
    assert summary is not None
    assert summary.summary["web"].running == 1
    # client-side fields preserved
    assert s2.alloc_by_id(a.id).client_status == "running"


# ---------------------------------------------------------------------
# The journal of allocation writes (allocs_changed_since): what the
# cluster base's delta reads instead of walking the table.
# ---------------------------------------------------------------------


def _journal_alloc(node_id="n1"):
    a = mock.alloc()
    a.node_id = node_id
    return a


def _walk(snap, index):
    """The reference: the walk over the whole table that the journal
    replaces."""
    return {a.id for a in snap.allocs() if a.modify_index > index}


def _ids(allocs):
    return [a.id for a in allocs]


def _journal_exact_set(s, _monkeypatch):
    """Upserts, an in-place update, an eviction and a client status
    update: at every earlier index the journal's answer is the walk's."""
    a, b, c, d = (_journal_alloc() for _ in range(4))
    s.upsert_allocs(10, [a, b])
    s.upsert_allocs(11, [c, d])
    s.upsert_allocs(12, [a.copy()])  # in place
    evicted = b.copy()
    evicted.desired_status = consts.ALLOC_DESIRED_EVICT
    s.upsert_allocs(13, [evicted])
    done = c.copy()
    done.client_status = consts.ALLOC_CLIENT_COMPLETE
    s.update_allocs_from_client(14, [done])
    snap = s.snapshot()
    for index in range(9, 16):
        got = snap.allocs_changed_since(index)
        assert len(got) == len(set(_ids(got)))
        assert set(_ids(got)) == _walk(snap, index), index
    assert set(_ids(snap.allocs_changed_since(11))) == {a.id, b.id, c.id}
    # as THIS snapshot holds them, not as they were written
    by_id = {x.id: x for x in snap.allocs_changed_since(9)}
    assert by_id[b.id].desired_status == consts.ALLOC_DESIRED_EVICT
    assert by_id[c.id].client_status == consts.ALLOC_CLIENT_COMPLETE
    assert by_id[c.id].modify_index == 14
    assert snap.allocs_changed_since(14) == []
    # a client update of an id the table does not hold writes nothing
    s.update_allocs_from_client(15, [_journal_alloc()])
    assert s.snapshot().allocs_changed_since(14) == []


def _journal_snapshot_isolation(s, _monkeypatch):
    a, b = _journal_alloc(), _journal_alloc()
    s.upsert_allocs(10, [a])
    before = s.snapshot()
    s.upsert_allocs(11, [b])
    s.upsert_allocs(12, [a])
    assert _ids(before.allocs_changed_since(0)) == [a.id]
    assert before.allocs_changed_since(10) == []
    assert before.allocs_changed_since(0)[0].modify_index == 10
    assert _ids(s.snapshot().allocs_changed_since(10)) == [b.id, a.id]


def _journal_trim(s, monkeypatch):
    """Past the cap the older half goes: below the new floor the answer
    is None, at and above it exact; a snapshot from before the trim
    keeps the journal it took."""
    from nomad_tpu.state import store as store_mod

    monkeypatch.setattr(store_mod, "_ALLOC_JOURNAL_CAP", 8)
    allocs = [_journal_alloc() for _ in range(12)]
    for i, a in enumerate(allocs[:8]):
        s.upsert_allocs(10 + i, [a])
    early = s.snapshot()
    for i, a in enumerate(allocs[8:], start=8):
        s.upsert_allocs(10 + i, [a])
    snap = s.snapshot()
    _indexes, _ids_, length, floor = snap._alloc_journal
    assert length <= 8 and floor > 10
    assert snap.allocs_changed_since(floor - 1) is None
    assert snap.allocs_changed_since(0) is None
    for index in range(floor, 23):
        assert set(_ids(snap.allocs_changed_since(index))) \
            == _walk(snap, index), index
    assert _ids(early.allocs_changed_since(0)) == _ids(allocs[:8])


def _journal_restore(s, _monkeypatch):
    a, b = _journal_alloc(), _journal_alloc()
    s.upsert_allocs(10, [a])
    s.upsert_allocs(11, [b])
    r = StateStore.restore(s.persist())
    assert "alloc_journal" not in s.persist()  # derived, not replicated
    assert r.allocs_changed_since(10) is None
    assert r.allocs_changed_since(0) is None
    assert r.allocs_changed_since(11) == []
    c = _journal_alloc()
    r.upsert_allocs(12, [c, b])
    assert _ids(r.allocs_changed_since(11)) == [c.id, b.id]
    assert r.allocs_changed_since(10) is None


def _journal_distinct_in_index_order(s, _monkeypatch):
    a, b, c = (_journal_alloc() for _ in range(3))
    s.upsert_allocs(10, [a, b])
    s.upsert_allocs(11, [c])
    s.upsert_allocs(12, [a])
    s.upsert_allocs(13, [b, a, b])
    got = s.snapshot().allocs_changed_since(9)
    # each once, in the order of its first write since the index
    assert _ids(got) == [a.id, b.id, c.id]
    assert _ids(s.snapshot().allocs_changed_since(10)) == [c.id, a.id, b.id]
    journal_indexes = s.snapshot()._alloc_journal[0]
    assert journal_indexes == sorted(journal_indexes)
    # the store answers through a fresh snapshot, as its other reads do
    assert _ids(s.allocs_changed_since(12)) == [b.id, a.id]


def _journal_collected_is_skipped(s, _monkeypatch):
    a, b = _journal_alloc(), _journal_alloc()
    s.upsert_allocs(10, [a, b])
    s.delete_evals(11, [], [a.id])
    assert _ids(s.snapshot().allocs_changed_since(0)) == [b.id]
    assert s.snapshot().alloc_count() == 1


def _journal_out_of_order_write(s, _monkeypatch):
    """Raft never writes out of index order; a test that does must get
    None (a full build), not a bisect over an unsorted list."""
    a, b = _journal_alloc(), _journal_alloc()
    s.upsert_allocs(20, [a])
    s.upsert_allocs(15, [b])
    assert s.snapshot().allocs_changed_since(10) is None
    assert _ids(s.snapshot().allocs_changed_since(20)) == []
    s.upsert_allocs(21, [a])
    assert _ids(s.snapshot().allocs_changed_since(20)) == [a.id]


@pytest.mark.parametrize("case", [
    _journal_exact_set, _journal_snapshot_isolation, _journal_trim,
    _journal_restore, _journal_distinct_in_index_order,
    _journal_collected_is_skipped, _journal_out_of_order_write,
], ids=lambda f: f.__name__.removeprefix("_journal_"))
def test_alloc_journal_contract(case, monkeypatch):
    case(StateStore(), monkeypatch)


def test_alloc_journal_appends_are_amortised():
    """A bulk load (set-up writes 177k fillers through upsert_allocs)
    must not feel the bound: trims are one copy of half the cap per
    half-cap appends, so the journal never holds more than the cap and
    the copies made are O(appends)."""
    from nomad_tpu.state.store import _ALLOC_JOURNAL_CAP, _AllocJournal

    j = _AllocJournal()
    replaced = 0
    batch = [str(i) for i in range(1000)]
    for index in range(1, 3 * _ALLOC_JOURNAL_CAP // 1000 + 2):
        held = j.ids
        j.record(index, batch)
        replaced += j.ids is not held
        assert len(j.ids) == len(j.indexes) <= _ALLOC_JOURNAL_CAP
    assert 4 <= replaced <= 6
    assert j.floor == j.indexes[0] - 1 or j.floor == j.indexes[0]


# ----------------------------------- reads that take no lock (PR 41)


def _seeded_store():
    s = StateStore()
    n, j, a = mock.node(), mock.job(), mock.alloc()
    e = mock.eval()
    a.job_id, a.node_id, a.eval_id, e.job_id = j.id, n.id, e.id, j.id
    s.upsert_node(1, n)
    s.upsert_job(2, j)
    s.upsert_evals(3, [e])
    s.upsert_allocs(4, [a])
    return s, n, j, e, a


_LOCK_FREE_READS = {
    "latest_index": lambda s, n, j, e, a: s.latest_index() == 4,
    "index": lambda s, n, j, e, a: s.index("evals") == 3,
    "scope_index": lambda s, n, j, e, a: s.scope_index(
        [watch.eval_item(e.id), watch.node(n.id)]) == 3,
    "node_by_id": lambda s, n, j, e, a: s.node_by_id(n.id).id == n.id,
    "job_by_id": lambda s, n, j, e, a: s.job_by_id(j.id).id == j.id,
    "eval_by_id": lambda s, n, j, e, a: s.eval_by_id(e.id).id == e.id,
    "alloc_by_id": lambda s, n, j, e, a: s.alloc_by_id(a.id).id == a.id,
}


@pytest.mark.parametrize("read", sorted(_LOCK_FREE_READS))
def test_read_returns_while_a_writer_holds_the_lock(read):
    """The index reads and the by-id reads queue behind nobody: with
    the writers' lock held by another thread each returns at once."""
    s, *rows = _seeded_store()
    held, release = threading.Event(), threading.Event()

    def hold():
        with s._lock:
            held.set()
            release.wait(10.0)

    holder = threading.Thread(target=hold, daemon=True)
    holder.start()
    assert held.wait(5.0)
    got = []
    reader = threading.Thread(
        target=lambda: got.append(_LOCK_FREE_READS[read](s, *rows)),
        daemon=True)
    try:
        reader.start()
        reader.join(2.0)
        assert not reader.is_alive(), f"{read} waited for the lock"
        assert got == [True]
    finally:
        release.set()
        holder.join(5.0)
    assert not holder.is_alive()


def test_indexes_never_decrease_for_a_lock_free_reader(monkeypatch):
    """A writer commits rising indexes through several prunes of the
    scope table while a reader, which takes no lock, watches a stamped
    scope (pruned on the way: it falls back to the floor), a scope
    never stamped (the floor alone) and the global index: none may
    ever read lower than it did. The dict under the scope table also
    checks the order that makes it so: the floor is at or above an
    entry's index BEFORE the entry goes."""
    import sys

    monkeypatch.setattr(StateStore, "_SCOPE_CAP", 16)
    s = StateStore()
    floor_late = []

    class Watched(dict):
        def __delitem__(self, key):
            if s._scope_floor < self[key]:
                floor_late.append((key, self[key], s._scope_floor))
            super().__delitem__(key)

    s._scope_indexes = Watched()
    fixed = mock.eval()
    s.upsert_evals(1, [fixed])
    stamped = [watch.eval_item(fixed.id)]
    never = [watch.eval_item("never-written")]
    stop = threading.Event()
    went_down = []

    def reader():
        last = [0, 0, 0]
        while not stop.is_set():
            now = [s.scope_index(stamped), s.scope_index(never),
                   s.latest_index()]
            for k, (was, is_) in enumerate(zip(last, now)):
                if is_ < was:
                    went_down.append((k, was, is_))
            last = now

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t = threading.Thread(target=reader, daemon=True)
    try:
        t.start()
        for index in range(2, 400):
            s.upsert_evals(index, [mock.eval()])
    finally:
        stop.set()
        t.join(10.0)
        sys.setswitchinterval(interval)
    assert not t.is_alive()
    assert s._scope_floor > 1, "no prune took the fixed scope"
    assert watch.eval_item(fixed.id) not in s._scope_indexes
    assert floor_late == []
    assert went_down == []
    assert s.scope_index(stamped) == s._scope_floor


def test_no_index_moves_before_a_txn_s_last_table_write(monkeypatch):
    """The lock-free readers rely on it: a txn writes ALL its rows
    before it raises any index. The derived job status used to bump
    the global and the jobs index in the middle of upsert_evals, once
    a job whose status changed, with later evals still unwritten."""
    s = StateStore()
    j = mock.job()
    e1, e2 = mock.eval(), mock.eval()
    e1.job_id = e2.job_id = j.id
    s.upsert_job(1, j)
    s.upsert_evals(2, [e1])
    seen = []

    table = s._tables["evals"]
    put = type(table).put

    def spy(self, key, value):
        if self is table:
            seen.append((s.latest_index(), s.index("jobs")))
        put(self, key, value)

    monkeypatch.setattr(type(table), "put", spy)
    done = e1.copy()
    done.status = e2.status = consts.EVAL_STATUS_COMPLETE
    # e1 turning terminal flips the job to dead BEFORE e2 is written
    s.upsert_evals(3, [done, e2])
    assert s.job_by_id(j.id).status == consts.JOB_STATUS_DEAD
    assert seen == [(2, 1), (2, 1)]
    assert (s.latest_index(), s.index("jobs"), s.index("evals")) == (3, 3, 3)
    assert s.scope_index([watch.job(j.id)]) == 3


@pytest.mark.parametrize(
    "read", ["node_by_id", "job_by_id", "eval_by_id", "alloc_by_id"])
def test_by_id_read_shares_no_table(read):
    """A point read on the store is one bucket read of the live table:
    it marks nothing shared, so the next write txn copies nothing. A
    read that spans rows still takes a snapshot, and the write after
    it copies the top and the bucket it writes, no other."""
    s, n, j, e, a = _seeded_store()
    ids = {"node_by_id": n.id, "job_by_id": j.id, "eval_by_id": e.id,
           "alloc_by_id": a.id}
    for t in list(s._tables.values()) + list(s._indexes.values()):
        t.shared = False
    held = s._tables["evals"].top
    assert getattr(s, read)(ids[read]).id == ids[read]
    assert getattr(s, read)("no-such-id") is None
    assert not any(t.shared for t in s._tables.values())
    assert not any(i.shared for i in s._indexes.values())
    s.upsert_evals(5, [mock.eval()])
    assert s._tables["evals"].top is held  # written in place
    assert [x.id for x in s.allocs_by_eval(e.id)] == [a.id]
    assert all(t.shared for t in s._tables.values())
    before = list(held)
    new = mock.eval()
    s.upsert_evals(6, [new])
    top = s._tables["evals"].top
    assert top is not held and held == before  # the top copied first
    fresh = [n for n, (x, y) in enumerate(zip(top, held)) if x is not y]
    assert fresh == [hash(new.id) & s._tables["evals"].mask]


# ---------------------------------------------------------------------
# The tables share structure between snapshots (PR 52): a plain-dict
# model of the store, and every snapshot held to its model for ever.
# ---------------------------------------------------------------------


class _Model:
    """What the store holds, as plain dicts and lists."""

    def __init__(self):
        self.index = 0
        self.nodes = []    # ids, in insertion order
        self.jobs = set()
        self.evals = {}    # id -> job id
        self.allocs = {}   # id -> (modify_index, node, job, eval)
        self.journal = []  # (index, alloc id), as written

    def frozen(self):
        m = _Model()
        m.index, m.nodes, m.jobs = self.index, list(self.nodes), set(self.jobs)
        m.evals, m.allocs = dict(self.evals), dict(self.allocs)
        m.journal = list(self.journal)
        return m

    def ids_where(self, field, value):
        return {i for i, row in self.allocs.items() if row[field] == value}

    def changed_since(self, index):
        ids = dict.fromkeys(i for at, i in self.journal if at > index)
        return [i for i in ids if i in self.allocs]


def _assert_reads(view, model, ever, floor=0):
    """`view` (a snapshot, or a store) returns exactly `model`; `ever`
    holds every node, job, eval and alloc id the run has used, `floor`
    is the index its journal of allocation writes reaches back to."""
    rows = {a.id: (a.modify_index, a.node_id, a.job_id, a.eval_id)
            for a in view.allocs()}
    assert rows == model.allocs
    assert view.alloc_count() == len(model.allocs)
    for aid in ever["allocs"]:
        got = view.alloc_by_id(aid)
        assert (got and got.id) == (aid if aid in model.allocs else None)
    for field, by, ids in ((1, view.allocs_by_node, ever["nodes"]),
                           (2, view.allocs_by_job, ever["jobs"]),
                           (3, view.allocs_by_eval, ever["evals"])):
        for key in list(ids) + ["no-such-key"]:
            got = [a.id for a in by(key)]
            assert len(got) == len(set(got))
            assert set(got) == model.ids_where(field, key), (field, key)
    assert {e.id: e.job_id for e in view.evals()} == model.evals
    for eid in ever["evals"]:
        got = view.eval_by_id(eid)
        assert (got and got.id) == (eid if eid in model.evals else None)
    for jid in ever["jobs"]:
        assert ({e.id for e in view.evals_by_job(jid)}
                == {e for e, j in model.evals.items() if j == jid})
        assert (view.job_by_id(jid) is not None) == (jid in model.jobs)
    assert {j.id for j in view.jobs()} == model.jobs
    assert [n.id for n in view.nodes()] == model.nodes
    for nid in ever["nodes"]:
        assert (view.node_by_id(nid) is not None) == (nid in model.nodes)
    assert view.latest_index() == model.index
    for index in {0, model.index // 2, model.index - 1, model.index}:
        got = view.allocs_changed_since(index)
        if index < floor:
            assert got is None
        else:
            assert [a.id for a in got] == model.changed_since(index), index


class _Run:
    """A store and its model under one stream of random write txns."""

    def __init__(self, seed):
        import random

        self.rng = random.Random(seed)
        self.store, self.model = StateStore(), _Model()
        self.ever = {"nodes": [], "jobs": [], "evals": [], "allocs": []}
        for _ in range(3):
            self.add_node()

    def _next(self):
        self.model.index += 1
        return self.model.index

    def add_node(self):
        n = mock.node()
        self.store.upsert_node(self._next(), n)
        self.model.nodes.append(n.id)
        self.ever["nodes"].append(n.id)

    def touch_node(self):
        nid = self.rng.choice(self.model.nodes)
        self.store.update_node_status(
            self._next(), nid, self.rng.choice(
                [consts.NODE_STATUS_READY, consts.NODE_STATUS_DOWN]))

    def drop_node(self):
        if len(self.model.nodes) > 2:
            nid = self.model.nodes.pop(
                self.rng.randrange(len(self.model.nodes)))
            self.store.delete_node(self._next(), nid)

    def _eval(self, job_id):
        e = mock.eval()
        e.job_id = job_id
        self.store.upsert_evals(self._next(), [e])
        self.model.evals[e.id] = job_id
        self.ever["evals"].append(e.id)
        return e.id

    def place(self):
        """A new job (or one more eval of a standing one) places."""
        if self.model.jobs and self.rng.random() < 0.4:
            job = self.store.job_by_id(
                self.rng.choice(sorted(self.model.jobs)))
        else:
            job = mock.job()
            self.store.upsert_job(self._next(), job)
            self.model.jobs.add(job.id)
            self.ever["jobs"].append(job.id)
        eid = self._eval(job.id)
        new = []
        for _ in range(self.rng.randint(1, 6)):
            a = mock.alloc()
            a.job_id, a.job, a.eval_id = job.id, job, eid
            a.node_id = self.rng.choice(self.ever["nodes"])
            new.append(a)
        index = self._next()
        self.store.upsert_allocs(index, new)
        for a in new:
            self.model.allocs[a.id] = (index, a.node_id, a.job_id, eid)
            self.model.journal.append((index, a.id))
            self.ever["allocs"].append(a.id)

    def _some_allocs(self):
        ids = sorted(self.model.allocs)
        return self.rng.sample(ids, min(len(ids), self.rng.randint(1, 5)))

    def inplace(self):
        """Standing allocations come back under the eval that updated
        them (the store moves them in `allocs_by_eval`)."""
        ids = self._some_allocs()
        if not ids:
            return
        job_id = self.model.allocs[ids[0]][2]
        ids = [i for i in ids if self.model.allocs[i][2] == job_id]
        eid = self._eval(job_id)
        updated = []
        for i in ids:
            a = self.store.alloc_by_id(i).copy()
            a.eval_id = eid
            updated.append(a)
        index = self._next()
        self.store.upsert_allocs(index, updated)
        for a in updated:
            self.model.allocs[a.id] = (index, a.node_id, a.job_id, eid)
            self.model.journal.append((index, a.id))

    def client(self):
        """A client's status sync, one id of it unknown to the store."""
        updates = []
        for i in self._some_allocs():
            a = self.store.alloc_by_id(i).copy()
            a.client_status = consts.ALLOC_CLIENT_RUNNING
            updates.append(a)
        updates.append(mock.alloc())
        index = self._next()
        self.store.update_allocs_from_client(index, updates)
        for a in updates[:-1]:
            _, node, job, ev = self.model.allocs[a.id]
            self.model.allocs[a.id] = (index, node, job, ev)
            self.model.journal.append((index, a.id))

    def reap(self):
        """The eval GC: evals go with their allocations."""
        evals = sorted(self.model.evals)
        if not evals:
            return
        gone = self.rng.sample(evals, min(len(evals), self.rng.randint(1, 3)))
        allocs = [i for i, row in self.model.allocs.items() if row[3] in gone]
        self.store.delete_evals(self._next(), gone + ["no-such-eval"],
                                allocs + ["no-such-alloc"])
        for e in gone:
            del self.model.evals[e]
        for i in allocs:
            del self.model.allocs[i]

    def deregister(self):
        if self.model.jobs:
            jid = self.rng.choice(sorted(self.model.jobs))
            self.store.delete_job(self._next(), jid)
            self.model.jobs.discard(jid)

    def step(self):
        op = self.rng.choices(
            [self.place, self.inplace, self.client, self.reap,
             self.deregister, self.add_node, self.touch_node,
             self.drop_node],
            weights=[8, 4, 3, 2, 1, 1, 1, 1])[0]
        op()


def _fanout(monkeypatch, fanout):
    """Few buckets make keys share one; 1,024 is what the store runs."""
    from nomad_tpu.state import store as store_mod

    monkeypatch.setattr(store_mod, "_FANOUT", fanout)


_FANOUTS = [2, 16, 1024]


@pytest.mark.parametrize("fanout", _FANOUTS)
@pytest.mark.parametrize("seed", range(4))
def test_every_snapshot_keeps_returning_its_model(seed, fanout, monkeypatch):
    """Random interleavings of placements, in-place updates, client
    syncs, eval GC, deregisters, node writes, snapshots and reads:
    every snapshot taken returns exactly what the store held when it
    was taken, after any number of later writes; the store itself
    reads as the model at every check."""
    _fanout(monkeypatch, fanout)
    run = _Run(seed)
    held = []
    for step in range(90):
        run.step()
        if run.rng.random() < 0.35:
            held.append((run.store.snapshot(), run.model.frozen()))
        if step % 15 == 14:
            for snap, model in held:
                _assert_reads(snap, model, run.ever)
            _assert_reads(run.store, run.model, run.ever)
    assert len(held) > 10
    for snap, model in held:
        _assert_reads(snap, model, run.ever)


@pytest.mark.parametrize("fanout", _FANOUTS)
def test_persist_restore_round_trips_the_bucketed_tables(fanout, monkeypatch):
    import json as _json

    _fanout(monkeypatch, fanout)
    run = _Run(seed=11)
    for _ in range(60):
        run.step()
    snap = run.store.snapshot()  # the persisted store is a shared one
    restored = StateStore.restore(_json.loads(_json.dumps(run.store.persist())))
    # The journal is derived state, not persisted: a restored store
    # answers None below its floor and lists nothing above it.
    floor = restored.index("allocs")
    assert 0 < floor <= run.model.index
    model = run.model.frozen()
    model.journal = []
    _assert_reads(restored, model, run.ever, floor)
    # and goes on as a store: a write after the restore, a snapshot
    # before it untouched
    before = restored.snapshot()
    a = mock.alloc()
    a.node_id = run.ever["nodes"][0]
    restored.upsert_allocs(model.index + 1, [a])
    _assert_reads(before, model, run.ever, floor)
    assert restored.alloc_count() == len(model.allocs) + 1
    _assert_reads(snap, run.model, run.ever)


@pytest.mark.parametrize("fanout", _FANOUTS)
def test_a_reader_iterates_a_snapshot_while_a_writer_commits(
        fanout, monkeypatch):
    """A thread reads one snapshot over and over, whole tables and by
    every index, while another commits: it finds the snapshot's model
    every time (switch interval shortened so the threads interleave
    inside the table writes)."""
    import sys

    _fanout(monkeypatch, fanout)
    run = _Run(seed=5)
    for _ in range(40):
        run.step()
    snap, model = run.store.snapshot(), run.model.frozen()
    ever = {k: list(v) for k, v in run.ever.items()}
    stop, errors, passes = threading.Event(), [], []

    def reader():
        try:
            while not stop.is_set():
                _assert_reads(snap, model, ever)
                passes.append(1)
        except BaseException as exc:  # the assert is the finding
            errors.append(exc)
            raise

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    thread = threading.Thread(target=reader, daemon=True)
    thread.start()
    try:
        for step in range(150):
            run.step()
            if step % 10 == 0:
                run.store.snapshot()  # every table shared again
        while not passes and not errors:
            stop.wait(0.01)
    finally:
        stop.set()
        thread.join(30.0)
        sys.setswitchinterval(interval)
    assert not thread.is_alive()
    assert errors == []
    assert passes
    _assert_reads(run.store, run.model, run.ever)


# -- the property PR 52 is about: a commit copies what it writes --------


@pytest.fixture(scope="module")
def big_store():
    """200,000 allocations of 2,000 jobs on 12,000 nodes, loaded with
    no snapshot taken."""
    from nomad_tpu.structs import Allocation

    s = StateStore()
    allocs = [
        Allocation(id=f"alloc-{i:06d}", node_id=f"node-{i % 12000:05d}",
                   job_id=f"job-{i % 2000:04d}", eval_id=f"eval-{i % 2000:04d}",
                   task_group="web")
        for i in range(200_000)
    ]
    for at in range(0, len(allocs), 10_000):
        s.upsert_allocs(at // 10_000 + 1, allocs[at:at + 10_000])
    return s


def _sixteen(tag):
    from nomad_tpu.structs import Allocation

    return [
        Allocation(id=f"new-{tag}-{i:02d}", node_id=f"node-{i:05d}",
                   job_id=f"job-{tag}", eval_id=f"eval-{tag}",
                   task_group="web")
        for i in range(16)
    ]


def test_a_write_after_a_snapshot_copies_buckets_not_the_table(big_store):
    from nomad_tpu.state.store import _FANOUT

    s = big_store
    assert s.write_stats()["entries_copied"] == 0  # the bulk load
    count = s.alloc_count()  # a snapshot: every table is shared
    index = s.latest_index()

    def largest(table):
        return max(len(bucket) for bucket in table.top)

    # Each of the five tables and indexes the txn writes copies its
    # top once; allocs copies a bucket a row (some 200 entries each),
    # allocs_by_node a bucket and a node's set a row, the three keyed
    # by the new job and eval one bucket.
    by_node = s._indexes["allocs_by_node"]
    bound = 5 * _FANOUT + 16 * largest(s._tables["allocs"]) + 16 * (
        largest(by_node) + max(len(ids) for ids in by_node.values())) + sum(
        largest(t) for t in (s._indexes["allocs_by_job"],
                             s._indexes["allocs_by_eval"],
                             s._tables["job_summary"]))
    assert largest(s._tables["allocs"]) < 1.5 * count / _FANOUT
    s.upsert_allocs(index + 1, _sixteen("a"))
    copied, written = s.last_write
    assert 0 < copied <= bound < count // 10
    assert written == 16 * 5
    stats = s.write_stats()
    assert stats["largest_txn_copy"] == stats["entries_copied"] == copied
    assert 2 <= stats["buckets_copied"] <= 2 * 16 + 3
    # the same write again with no snapshot between: its buckets are
    # the writer's own since the first, nothing is copied
    s.upsert_allocs(index + 2, _sixteen("a"))
    assert s.last_write == (0, 16 * 2)
    assert s.write_stats()["entries_copied"] == copied
    # a snapshot held from before either write still reads 200,000
    assert s.alloc_count() == count + 16


def test_a_write_with_no_snapshot_between_copies_nothing():
    s, n, j, e, a = _seeded_store()
    for index in range(5, 9):
        s.upsert_allocs(index, _sixteen(f"t{index}"))
        assert s.last_write[0] == 0
    assert s.write_stats()["entries_copied"] == 0
    assert s.write_stats()["write_txns"] == 8


def test_nodes_lists_in_insertion_order_across_snapshots():
    """`nodes()` fixes the row order of the cluster base: the order is
    the parent's, a dict's insertion order (an update keeps a node's
    place, a node deleted and registered again goes last), whatever
    was snapshotted in between."""
    s = StateStore()
    model = {}
    nodes = [mock.node() for _ in range(300)]
    index = 0
    for n in nodes:
        index += 1
        s.upsert_node(index, n)
        model[n.id] = None
    first = s.snapshot()
    order = list(model)
    for n in nodes[10:200:7]:
        index += 1
        s.update_node_status(index, n.id, consts.NODE_STATUS_DOWN)
        s.snapshot()
        index += 1
        s.update_node_drain(index, n.id, True)
    for n in nodes[5:100:9]:
        index += 1
        s.delete_node(index, n.id)
        del model[n.id]
        s.snapshot()
        index += 1
        s.upsert_node(index, n)
        model[n.id] = None
    assert [n.id for n in s.nodes()] == list(model)
    assert list(model) != order and sorted(model) == sorted(order)
    assert [n.id for n in first.nodes()] == order
    restored = StateStore.restore(s.persist())
    assert [n.id for n in restored.nodes()] == list(model)
