"""Placement batcher tests: concurrent same-shape requests share one
device dispatch, results match the unbatched program, and mixed shapes
keep separate queues (the broker drain-to-batch shim of the north
star)."""

import threading

import jax
import numpy as np
import pytest

from nomad_tpu.ops.binpack import (
    PlacementConfig,
    make_asks,
    make_node_state,
    placement_program_jit,
)
from nomad_tpu.scheduler.batcher import PlacementBatcher


def tiny_inputs(n=128, k=8, g=2, seed=0):
    state = make_node_state(
        capacity=np.tile([4000, 8192, 100000, 150], (n, 1)),
        sched_capacity=np.tile([3900, 7936, 96000, 150], (n, 1)),
        util=np.tile([100.0, 256.0, 4096.0, 0.0], (n, 1)),
        bw_avail=np.full(n, 1000.0),
        bw_used=np.zeros(n),
        ports_free=np.full(n, 40000.0),
        job_count=np.zeros(n, np.int32),
        tg_count=np.zeros((n, g), np.int32),
        feasible=np.ones((n, g), bool),
        node_ok=np.ones(n, bool),
    )
    asks = make_asks(
        resources=np.tile([500, 256, 150, 0], (k, 1)),
        bw=np.full(k, 50.0),
        ports=np.full(k, 2.0),
        tg_index=np.arange(k, dtype=np.int32) % g,
        active=np.ones(k, bool),
        job_distinct_hosts=False,
        tg_distinct_hosts=np.zeros(g, bool),
    )
    return state, asks, jax.random.PRNGKey(seed)


CONFIG = PlacementConfig(anti_affinity_penalty=10.0)


def test_single_request_matches_direct_program():
    batcher = PlacementBatcher(window=0.001)
    state, asks, key = tiny_inputs(seed=3)
    choices, scores = batcher.place(state, asks, key, CONFIG)
    direct_c, direct_s, _ = placement_program_jit(state, asks, key, CONFIG)
    np.testing.assert_array_equal(choices, np.asarray(direct_c))
    np.testing.assert_allclose(scores, np.asarray(direct_s), rtol=1e-5)
    # The recompile gates read this counter; after a real dispatch it
    # must count a real program (jax's private _cache_size still works).
    assert batcher.stats()["jit_cache_size"] > 0


def test_concurrent_requests_share_one_dispatch():
    batcher = PlacementBatcher(window=0.25)  # wide window: all join
    results = {}
    errors = []

    def worker(i):
        try:
            state, asks, key = tiny_inputs(seed=i)
            results[i] = batcher.place(state, asks, key, CONFIG)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    assert len(results) == 6
    # all six rode a small number of dispatches (1 ideally; allow 2 for
    # a straggler that missed the window)
    assert batcher.dispatches <= 2
    assert batcher.batched_requests == 6
    # batched results equal the unbatched program per request
    for i in range(6):
        state, asks, key = tiny_inputs(seed=i)
        direct_c, _, _ = placement_program_jit(state, asks, key, CONFIG)
        np.testing.assert_array_equal(results[i][0], np.asarray(direct_c))


def test_mixed_shapes_do_not_batch_together():
    batcher = PlacementBatcher(window=0.05)
    out = {}

    def worker(name, n):
        state, asks, key = tiny_inputs(n=n)
        out[name] = batcher.place(state, asks, key, CONFIG)

    threads = [threading.Thread(target=worker, args=("a", 128)),
               threading.Thread(target=worker, args=("b", 256))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(out) == 2
    assert out["a"][0].shape == out["b"][0].shape  # both [K]
    assert batcher.dispatches == 2  # different node buckets: no mixing


def test_dispatch_error_propagates_to_all_requests():
    batcher = PlacementBatcher(window=0.2)

    state, asks, key = tiny_inputs()
    bad_asks = asks._replace(resources=np.asarray([[1.0]]))  # wrong shape

    with pytest.raises(Exception):
        batcher.place(state, bad_asks, key, CONFIG)


def test_tpu_scheduler_uses_batcher():
    """The service-tpu factory's placements flow through the global
    batcher (observability counters move)."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.batcher import get_batcher
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs import consts, new_eval

    batcher = get_batcher()
    before = batcher.batched_requests
    h = Harness(seed=9)
    for _ in range(4):
        n = mock.node()
        n.compute_class()
        h.state.upsert_node(h.next_index(), n)
    job = mock.job()
    job.task_groups[0].count = 4  # >3: below that the host fallback runs
    h.state.upsert_job(h.next_index(), job)
    h.process("service-tpu", new_eval(job, consts.EVAL_TRIGGER_JOB_REGISTER))
    assert len(h.state.allocs_by_job(job.id)) == 4
    assert batcher.batched_requests > before


def test_overflow_beyond_max_batch_all_served():
    """More same-shaped requests than max_batch in one window: the tail
    rides a follow-up dispatch instead of deadlocking its workers."""
    batcher = PlacementBatcher(max_batch=3, window=0.25)
    results = {}

    def worker(i):
        state, asks, key = tiny_inputs(seed=i)
        results[i] = batcher.place(state, asks, key, CONFIG)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    assert all(not t.is_alive() for t in threads), "worker deadlocked"
    assert len(results) == 8
    assert batcher.batched_requests == 8
    assert batcher.dispatches >= 3  # ceil(8/3)


def test_pad_ladders():
    """Shape-bucket ladders: every distinct padded size is an XLA
    program, so the ladders must be coarse and deterministic."""
    import numpy as np

    from nomad_tpu.scheduler.batcher import (
        BATCH_BUCKETS,
        ROW_BUCKETS,
        _pad_batch,
        _pad_rows,
    )

    for n in range(1, 65):
        b = _pad_batch(n, 64)
        assert b >= n and (b in BATCH_BUCKETS or b == 64)
    assert _pad_batch(3, 64) == 4
    assert _pad_batch(17, 64) == 64
    assert _pad_batch(100, 64) == 64  # capped at max_batch

    rows = _pad_rows([7, 3, 9])
    assert len(rows) == ROW_BUCKETS[0]
    assert rows.dtype == np.int32
    assert list(rows[:3]) == [7, 3, 9]
    assert (rows[3:] == 7).all()  # padding repeats the FIRST row
    assert len(_pad_rows(list(range(300)))) == ROW_BUCKETS[1]
    # Beyond the ladder: fall back to pow2.
    assert len(_pad_rows(list(range(5000)))) == 8192


def test_place_self_rescues_when_dispatcher_never_runs(monkeypatch):
    """PR 7 regression (ntalint unbounded-wait): place() used to park
    on a bare event.wait() — a dispatcher whose thread failed to spawn
    (Thread.start under OS thread pressure) left its requesters wedged
    forever. The bounded wait now observes the ownerless queue twice
    and claims dispatchership inline (self-rescue)."""
    batcher = PlacementBatcher(window=0.01)
    state, asks, key = tiny_inputs(seed=5)

    real_dispatch = PlacementBatcher._dispatch
    died_once = []

    def flaky(self, shape_key, config, wait_window):
        if not died_once:
            # First dispatcher: its thread "never starts" — the
            # failed-spawn path un-claims the slot and does no work.
            died_once.append(shape_key)
            with self._lock:
                self._dispatchers.pop(shape_key, None)
            return None
        return real_dispatch(self, shape_key, config, wait_window)

    monkeypatch.setattr(PlacementBatcher, "_dispatch", flaky)

    result = {}

    def run():
        result["v"] = batcher.place(state, asks, key, CONFIG)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive(), "place() wedged: self-rescue did not fire"
    assert "v" in result
    choices, scores = result["v"]
    direct_c, direct_s, _ = placement_program_jit(state, asks, key, CONFIG)
    np.testing.assert_array_equal(choices, np.asarray(direct_c))
    np.testing.assert_allclose(scores, np.asarray(direct_s), rtol=1e-5)


def test_spawn_dispatcher_start_failure_unclaims_slot(monkeypatch):
    """Thread.start failing inside _spawn_dispatcher must release the
    dispatcher slot it was counted for — otherwise the queue looks
    owned forever and no self-rescue can trigger."""
    batcher = PlacementBatcher(window=0.01)

    def boom(self):
        raise RuntimeError("can't start new thread")

    monkeypatch.setattr(threading.Thread, "start", boom)
    with batcher._lock:
        batcher._dispatchers["shape"] = 1
    batcher._spawn_dispatcher("shape", CONFIG)
    with batcher._lock:
        assert batcher._dispatchers.get("shape", 0) == 0
