#!/usr/bin/env python3
"""What the plan applier's verification costs against what STANDS on
the plan's nodes: `PlanApplier._evaluate_plan` alone, over a real store
of c1m-5k's hosts and containers, in thread CPU time.

    python tools/applier_sizing.py            # the table, then ten plans
    python tools/applier_sizing.py --reps 5
    python tools/applier_sizing.py --scheduler-twin

`--scheduler-twin` sizes the same cost where the dense scheduler still
pays it: the `NetworkIndex` that `scheduler/tpu.py` `_offer_networks`
builds over a chosen node's standing allocations (ROADMAP.md, S6).

A pointer for sizing on whatever CPU runs it, not a device number: the
cell's own `plan_verify_p50_ms` comes from a chip run (PERF.md,
section 5).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nomad_tpu import mock  # noqa: E402
from nomad_tpu.server.fsm import FSM  # noqa: E402
from nomad_tpu.server.plan_apply import PlanApplier  # noqa: E402
from nomad_tpu.server.plan_queue import PlanQueue  # noqa: E402
from nomad_tpu.structs import Allocation, Plan, Resources, consts  # noqa: E402
from nomad_tpu.utils.ids import generate_uuid  # noqa: E402

# (nodes the plan touches, placed a node, standing a node)
ROWS = ((10, 100, 0), (10, 100, 100), (1000, 1, 0), (1000, 1, 16),
        (1000, 1, 80), (143, 7, 50))


def container(node_id: str, job) -> Allocation:
    """One container of c1m-5k's job as the dense scheduler stages it."""
    return Allocation(
        id=generate_uuid(), eval_id="e", name="c1m.c1m[0]", job_id=job.id,
        task_group="c1m", node_id=node_id,
        task_resources={"container": Resources(cpu=19, memory_mb=32)},
        shared_resources=Resources(disk_mb=300),
        desired_status=consts.ALLOC_DESIRED_RUN,
        client_status=consts.ALLOC_CLIENT_PENDING)


class World:
    def __init__(self, n_nodes: int, standing: int):
        self.fsm = FSM()
        self.store = self.fsm.state
        self.index = 10
        self.job = mock.batch_job()
        self.nodes = []
        for _ in range(n_nodes):
            node = mock.node()  # c1m-5k's host is the mock node
            self.index += 1
            self.store.upsert_node(self.index, node)
            self.nodes.append(node)
        for _ in range(standing):
            self.commit([container(n.id, self.job) for n in self.nodes])
        self.applier = PlanApplier(PlanQueue(), self.fsm, None)

    def commit(self, allocs) -> int:
        self.index += 1
        self.store.upsert_allocs(self.index, allocs)
        return self.index

    def plan(self, placed: int) -> Plan:
        plan = Plan(job=self.job)
        for node in self.nodes:
            for _ in range(placed):
                plan.append_alloc(container(node.id, self.job))
        return plan

    def overlay(self):
        """The applier's view of fresh state, as `_run` takes it."""
        fresh = getattr(self.applier, "_fresh_overlay", None)
        if fresh is not None:
            return fresh()
        from nomad_tpu.server.plan_apply import OptimisticSnapshot

        return OptimisticSnapshot(self.store.snapshot())

    def landed(self, index: int) -> None:
        """Tell the applier its own commit's index, as `_wait_commit`
        does (a tree without the node summaries has nothing to tell)."""
        summaries = getattr(self.applier, "_summaries", None)
        if summaries is not None:
            summaries.note_commit(index)


def verify_ms(world: World, overlay, plan: Plan) -> float:
    t0 = time.thread_time()
    result = world.applier._evaluate_plan(overlay, plan)
    ms = (time.thread_time() - t0) * 1e3
    assert not result.refresh_index, "the sizing plan must verify whole"
    return ms


def scheduler_twin(reps: int) -> None:
    from nomad_tpu.scheduler.util import proposed_allocs_for_node
    from nomad_tpu.structs import NetworkIndex

    print("| standing a node | index and list, CPU ms a 1,000 nodes | "
          "the list alone |")
    print("| --- | --- | --- |")
    for standing in (0, 16, 80):
        world = World(1000, standing)
        state, plan = world.store.snapshot(), Plan(job=world.job)
        both, alone = [], []
        for _ in range(reps):
            t0 = time.thread_time()
            for node in world.nodes:
                index = NetworkIndex()
                index.set_node(node)
                index.add_allocs(
                    proposed_allocs_for_node(state, plan, node.id))
            t1 = time.thread_time()
            for node in world.nodes:
                proposed_allocs_for_node(state, plan, node.id)
            both.append((t1 - t0) * 1e3)
            alone.append((time.thread_time() - t1) * 1e3)
        print(f"| {standing} | {min(both):.1f}-{max(both):.1f} | "
              f"{min(alone):.1f}-{max(alone):.1f} |", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--scheduler-twin", action="store_true")
    args = ap.parse_args()
    if args.scheduler_twin:
        scheduler_twin(args.reps)
        return

    print("| nodes | placed a node | standing a node | "
          "`_evaluate_plan`, CPU ms: first plan | the next plan |")
    print("| --- | --- | --- | --- | --- |")
    for n_nodes, placed, standing in ROWS:
        world = World(n_nodes, standing)
        first, again = [], []
        for _ in range(args.reps):
            # A new applier a pair: the first plan finds nothing
            # carried, the next one what the first left (where the tree
            # carries anything).
            world.applier = PlanApplier(PlanQueue(), world.fsm, None)
            first.append(verify_ms(world, world.overlay(),
                                   world.plan(placed)))
            again.append(verify_ms(world, world.overlay(),
                                   world.plan(placed)))
        print(f"| {n_nodes} | {placed} | {standing} | "
              f"{min(first):.1f}-{max(first):.1f} | "
              f"{min(again):.1f}-{max(again):.1f} |", flush=True)

    # Ten consecutive plans of 1,000 on the same thousand nodes, each
    # committed before the next is verified on fresh state: the ramp.
    world = World(1000, 16)
    times = []
    for _ in range(10):
        overlay = world.overlay()
        plan = world.plan(1)
        times.append(verify_ms(world, overlay, plan))
        overlay.add_result(_accepted(plan))
        world.landed(world.commit(
            [a for allocs in plan.node_allocation.values() for a in allocs]))
    print("ten plans of 1,000 on 1,000 nodes from 16 standing, CPU ms: "
          + " ".join(f"{t:.1f}" for t in times))


def _accepted(plan: Plan):
    from nomad_tpu.structs import PlanResult

    return PlanResult(node_allocation=dict(plan.node_allocation))


if __name__ == "__main__":
    main()
