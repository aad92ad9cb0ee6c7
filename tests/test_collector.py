"""The program's policy for the interpreter's collector
(nomad_tpu/profile/collector.py): the one rule on real passes and at its
edges, the hook's no-lock contract, the policy's lifetime over servers,
the bound on a frozen dead cycle, and the two rows of the stage table.

The interpreter's collector is the process's, and so are the profiler's
sampler and the servers other test files may have left running: every
test here gets a `Collector` of its own in the module's slot, with the
sampler stopped until a server of the test starts it, and leaves the
process thawed. Every wait has a deadline of its own."""

import gc
import os
import sys
import threading
import time
import weakref

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nomad_tpu.profile import collector as collector_module
from nomad_tpu.profile import get_profiler
from nomad_tpu.profile.collector import BARREN_PER_MS, FLOOR_MS, Collector
from nomad_tpu.server.config import ServerConfig
from nomad_tpu.server.fsm import FSM
from nomad_tpu.server.server import Server
from nomad_tpu.trace import (
    STAGE_RUNTIME_GC_FULL_PAUSE,
    STAGE_RUNTIME_GC_PAUSE,
    get_recorder,
)

WAIT_S = 10.0


@pytest.fixture
def collector(monkeypatch):
    prof = get_profiler()
    sampled = prof.gil.running()
    prof.gil.stop()
    fresh = Collector()
    monkeypatch.setattr(collector_module, "_collector", fresh)
    assert collector_module.get_collector() is fresh
    gc.collect()  # what earlier tests left is not this test's yield
    try:
        yield fresh
    finally:
        prof.gil.stop()  # bound to `fresh` if a server started it
        while fresh.stats()["installed"]:
            fresh.uninstall()
        gc.unfreeze()
        gc.enable()
        monkeypatch.undo()
        if sampled:
            prof.gil.start()


def wait_for(cond, what):
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, f"timed out: {what}"
        time.sleep(0.01)


def start_server(**over):
    server = Server(ServerConfig(num_schedulers=1, **over))
    server.start()
    return server


class Link:
    """One half of a reference cycle, weakly referenceable."""

    def __init__(self):
        self.other = None


def dead_cycle_after(freeze):
    """A two-object cycle that is alive when `freeze()` runs and dropped
    after it: the one kind of garbage a frozen heap does not give back."""
    a, b = Link(), Link()
    a.other, b.other = b, a
    ref = weakref.ref(a)
    freeze()
    del a, b
    return ref


def built_with_the_collector_off(build):
    """As benchmark/run.py loads its fleet: everything stays young."""
    gc.disable()
    try:
        return build()
    finally:
        gc.enable()


# ---------------------------------------------------------------------
# the rule, on real passes


def test_long_barren_pass_freezes_and_the_next_full_pass_is_short(collector):
    collector.install()
    fleet = built_with_the_collector_off(
        lambda: [[i] for i in range(400_000)])
    frozen_before = gc.get_freeze_count()
    # everything is young, as after the benchmark's load: the pass that
    # walks it may be the interpreter's own generation-0 one
    gc.collect(1)
    barren_ms = max(ms for _generation, ms in collector.drain())
    assert barren_ms >= FLOOR_MS
    probe = Link()
    ref = weakref.ref(probe)
    collector.freeze_if_asked()     # the sampler's thread's half
    assert collector.freezes == 1
    assert gc.get_freeze_count() >= frozen_before + len(fleet)
    gc.collect(2)
    generation, full_ms = list(collector.drain())[-1]
    assert generation == 2
    assert full_ms * 10 <= barren_ms
    del probe
    assert ref() is None            # frozen, and dead by reference count
    stats = collector.stats()
    assert stats["passes"] >= 2 and stats["full_passes"] >= 1
    assert stats["max_pause_ms"] == round(barren_ms, 3)
    assert stats["frozen_objects"] == gc.get_freeze_count()


def test_pass_that_frees_a_large_share_does_not_freeze(collector):
    collector.install()

    def garbage():
        for _ in range(300_000):
            a = []
            a.append(a)

    built_with_the_collector_off(garbage)
    frozen_before = gc.get_freeze_count()
    gc.collect(1)
    assert max(ms for _generation, ms in collector.drain()) >= FLOOR_MS
    collector.freeze_if_asked()     # long, and worth its length
    assert collector.freezes == 0
    assert gc.get_freeze_count() == frozen_before


def test_short_pass_does_not_freeze(collector):
    collector.install()
    frozen_before = gc.get_freeze_count()
    for _ in range(5):
        gc.collect(0)
    lengths = [ms for _generation, ms in collector.drain()]
    assert len(lengths) >= 5 and min(lengths) < FLOOR_MS
    if max(lengths) < FLOOR_MS:     # no pass was descheduled
        collector.freeze_if_asked()
        assert collector.freezes == 0
        assert gc.get_freeze_count() == frozen_before


# the rule at its edges: (pass length ms, objects freed, freeze?)
SHARE_240 = int(240.0 * BARREN_PER_MS)
RULE_EDGES = {
    "under_the_floor_and_barren": (FLOOR_MS - 2.0, 0, False),
    "at_the_floor_and_barren": (FLOOR_MS, 0, True),
    "fleet_length_and_barren": (240.0, 0, True),
    "fleet_length_under_the_share": (240.0, SHARE_240 - 50, True),
    "fleet_length_over_the_share": (240.0, SHARE_240 + 50, False),
    "uncollectable_counts_as_yield": (240.0, (0, SHARE_240 + 50), False),
}


@pytest.mark.parametrize("edge", sorted(RULE_EDGES))
@pytest.mark.parametrize("generation", [0, 1, 2])
def test_rule_edges_in_any_generation(collector, edge, generation):
    ms, freed, expect = RULE_EDGES[edge]
    collected, uncollectable = freed if isinstance(freed, tuple) else (
        freed, 0)
    collector.install()
    gc.disable()    # no pass of the interpreter's between the two calls
    info = {"generation": generation, "collected": collected,
            "uncollectable": uncollectable}
    collector._on_gc("start", info)
    collector._t0 -= ms / 1000.0    # the pass began that long ago
    collector._on_gc("stop", info)
    frozen_before = gc.get_freeze_count()
    collector.freeze_if_asked()
    assert collector.freezes == (1 if expect else 0)
    assert (gc.get_freeze_count() > frozen_before) is expect
    (seen_generation, seen_ms), = list(collector.drain())
    assert seen_generation == generation
    assert ms <= seen_ms < ms + 1.0


def test_hook_installed_between_a_passes_two_calls_counts_nothing(collector):
    collector.install()
    collector._on_gc("stop", {"generation": 2, "collected": 0,
                              "uncollectable": 0})
    assert list(collector.drain()) == []
    collector.freeze_if_asked()
    assert collector.freezes == 0 and collector.passes == 0


# ---------------------------------------------------------------------
# the hook takes no lock


def test_hook_runs_while_every_stripe_and_its_own_lock_are_held(collector):
    collector.install()
    held = [stripe.lock for stripe in get_recorder()._stripes]
    held.append(collector._lock)
    done = threading.Event()

    def collect():
        gc.collect(0)
        done.set()

    for lock in held:
        assert lock.acquire(timeout=WAIT_S)
    try:
        thread = threading.Thread(target=collect, daemon=True)
        thread.start()
        assert done.wait(WAIT_S), "the hook waited for a lock"
        thread.join(WAIT_S)
        assert not thread.is_alive()
    finally:
        for lock in held:
            lock.release()
    assert len(list(collector.drain())) >= 1


def test_queue_of_passes_is_bounded(collector):
    collector.install()
    info = {"generation": 0, "collected": 0, "uncollectable": 0}
    for _ in range(collector_module.PENDING_MAX + 50):
        collector._on_gc("start", info)
        collector._on_gc("stop", info)
    assert len(list(collector.drain())) == collector_module.PENDING_MAX


# ---------------------------------------------------------------------
# lifetime: counted per server, the last one out thaws


def test_two_servers_leave_no_hook_and_nothing_frozen(collector):
    hook = collector._on_gc
    first = start_server()
    second = start_server()
    try:
        assert gc.callbacks.count(hook) == 1
        ref = dead_cycle_after(gc.freeze)
        gc.collect()
        assert ref() is not None    # what a freeze costs
        first.shutdown()
        first.shutdown()            # idempotent: counted once
        assert gc.callbacks.count(hook) == 1
        assert gc.get_freeze_count() > 0
    finally:
        first.shutdown()
        second.shutdown()
    assert hook not in gc.callbacks
    assert gc.get_freeze_count() == 0
    assert not collector.stats()["installed"]
    gc.collect()
    assert ref() is None            # a dead server's cycle goes again


def test_server_never_started_uninstalls_nothing(collector):
    other = start_server()
    try:
        Server(ServerConfig(num_schedulers=1)).shutdown()
        assert collector.stats()["installed"]
    finally:
        other.shutdown()
    assert not collector.stats()["installed"]


# ---------------------------------------------------------------------
# the bound on a frozen dead cycle; restore


def test_force_gc_collects_a_cycle_that_died_frozen(collector):
    server = start_server()
    try:
        ref = dead_cycle_after(gc.freeze)
        gc.collect()
        assert ref() is not None
        server.force_gc()
        wait_for(lambda: ref() is None and collector.freezes >= 1,
                 "force_gc's settle")
        assert gc.get_freeze_count() > 0    # and frozen again
    finally:
        server.shutdown()


def test_periodic_eval_gc_settles_too(collector):
    server = start_server(eval_gc_interval=0.05, job_gc_interval=3600.0,
                          node_gc_interval=3600.0)
    try:
        ref = dead_cycle_after(gc.freeze)
        wait_for(lambda: ref() is None, "the eval-GC tick's settle")
    finally:
        server.shutdown()


@pytest.mark.parametrize("server_runs", [True, False])
def test_fsm_restore_ends_frozen_where_a_server_runs(collector, server_runs):
    fsm = FSM()
    data = fsm.snapshot_data()
    if server_runs:
        collector.install()
    gc.unfreeze()
    fsm.restore(data)
    if server_runs:
        assert collector.freezes == 1
        assert gc.get_freeze_count() > 0
        # the same call, so a cycle that died frozen goes with it
        ref = dead_cycle_after(gc.freeze)
        fsm.restore(data)
        assert ref() is None
    else:
        assert collector.freezes == 0
        assert gc.get_freeze_count() == 0


# ---------------------------------------------------------------------
# the rows, and who freezes with the observatory off


def test_rows_get_samples_from_the_samplers_thread(collector):
    rec = get_recorder()
    rec.reset()
    rec.set_enabled(True)
    server = start_server()
    try:
        assert get_profiler().gil.running()
        fleet = built_with_the_collector_off(
            lambda: [[i] for i in range(400_000)])
        gc.collect(1)
        gc.collect(2)

        def count(stage):
            return (rec.stage_buckets(stage) or (0,))[0]

        wait_for(lambda: count(STAGE_RUNTIME_GC_FULL_PAUSE) >= 1
                 and collector.freezes >= 1, "the sampler's drain")
        assert count(STAGE_RUNTIME_GC_PAUSE) >= 2
        assert (count(STAGE_RUNTIME_GC_PAUSE)
                > count(STAGE_RUNTIME_GC_FULL_PAUSE))
        assert gc.get_freeze_count() >= len(fleet)
        gc_block = get_profiler().snapshot()["gc"]
        assert gc_block["installed"] and gc_block["freezes"] >= 1
        assert gc_block["frozen_objects"] >= len(fleet)
    finally:
        server.shutdown()
        rec.reset()


def test_observatory_off_the_telemetry_thread_freezes_and_rows_stay_empty(
        collector):
    rec = get_recorder()
    rec.reset()
    rec.set_enabled(True)
    server = start_server(profile_enabled=False, telemetry_interval=0.05)
    try:
        assert not get_profiler().gil.running()
        fleet = built_with_the_collector_off(
            lambda: [[i] for i in range(400_000)])
        gc.collect(1)
        wait_for(lambda: collector.freezes >= 1, "the telemetry tick")
        assert gc.get_freeze_count() >= len(fleet)
        assert rec.stage_buckets(STAGE_RUNTIME_GC_PAUSE) is None
    finally:
        server.shutdown()
        get_profiler().set_enabled(True)
        rec.reset()
