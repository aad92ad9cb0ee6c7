"""Device-mesh sharding of the placement program.

The cluster-scheduling analog of model parallelism: the *node axis* is
the model dimension (a 10k+-node matrix shards across chips over ICI)
and the *eval batch* is the data dimension (independent evaluations =
optimistic concurrency). Following the standard recipe: pick a mesh,
annotate input shardings, and let XLA insert the collectives — the
masked argmax over the sharded node axis lowers to an all-reduce, and
the one-hot state update stays node-local.

The reference has no tensor math to shard; its parallelism is N worker
goroutines (SURVEY.md section 2.4). Here one device-mesh program
subsumes both: `dp` x `nodes` = workers x cluster-shards.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.binpack import Asks, NodeState

DP_AXIS = "dp"  # independent evals (data parallel)
NODE_AXIS = "nodes"  # cluster node matrix (model parallel)


def make_mesh(n_devices: Optional[int] = None, dp: Optional[int] = None) -> Mesh:
    """Build a dp x nodes mesh over the default backend's devices.
    When dp is not given, prefer sharding the node axis (the big
    dimension).

    The default backend is the only source of devices: asking for more
    than it has raises, and a backend that fails to initialize raises
    from jax.devices() — a mesh silently built on host CPU devices
    would run the sharded path somewhere nobody deploys it. (Under
    tests/conftest.py the default backend IS eight virtual CPU
    devices.)"""
    devices = np.array(jax.devices())
    if n_devices is not None:
        if devices.size < n_devices:
            raise ValueError(
                f"need {n_devices} devices, the default backend "
                f"({devices[0].platform}) has {devices.size}")
        devices = devices[:n_devices]
    total = devices.size
    if dp is None:
        dp = 1
    if total % dp:
        # A real error, not an assert: asserts vanish under `python
        # -O` and a silently ragged reshape would shard the node axis
        # unevenly.
        raise ValueError(f"{total} devices not divisible by dp={dp}")
    return Mesh(devices.reshape(dp, total // dp), (DP_AXIS, NODE_AXIS))


def _node_state_specs(batched: bool) -> NodeState:
    """PartitionSpecs for each NodeState leaf: shard the leading node
    dim (after the optional batch dim) across NODE_AXIS."""
    b = (DP_AXIS,) if batched else ()
    vec = P(*b, NODE_AXIS)  # [.., N]
    mat = P(*b, NODE_AXIS, None)  # [.., N, R]
    return NodeState(
        capacity=mat,
        sched_capacity=mat,
        util=mat,
        bw_avail=vec,
        bw_used=vec,
        ports_free=vec,
        job_count=vec,
        tg_count=mat,
        feasible=mat,
        node_ok=vec,
    )


def base_specs() -> Tuple:
    """PartitionSpecs for the batcher's cluster-base tuple, IN ITS
    ORDER: (capacity, sched_capacity, util, bw_avail, bw_used,
    ports_free, node_ok, class_ids). Lives here so the pairing between
    field and spec cannot drift from the dispatch-side shardings
    above."""
    s = _node_state_specs(batched=False)
    return (s.capacity, s.sched_capacity, s.util, s.bw_avail,
            s.bw_used, s.ports_free, s.node_ok, P(NODE_AXIS))


def delta_row_specs() -> Tuple:
    """PartitionSpecs for the resident-base delta payload, IN
    apply_base_delta's argument order after the four target arrays:
    (rows, util_rows, bw_rows, ports_rows, ok_rows). Replicated on
    purpose: a delta touches a handful of rows whose home shard the
    scatter resolves on device — pre-splitting each row to its shard
    would cost more host work than the few-hundred-byte payload it
    ships. Lives here (with base_specs) so a sharded resident base and
    its update path can't drift apart."""
    return (P(), P(None, None), P(), P(), P())


def _asks_specs(batched: bool) -> Asks:
    b = (DP_AXIS,) if batched else ()
    return Asks(
        resources=P(*b, None, None),
        bw=P(*b, None),
        ports=P(*b, None),
        tg_index=P(*b, None),
        active=P(*b, None),
        job_distinct_hosts=P(*b),
        tg_distinct_hosts=P(*b, None),
    )


def shard_placement_inputs(
    mesh: Mesh, state: NodeState, asks: Asks, keys, batched: bool = False
) -> Tuple[NodeState, Asks, object]:
    """Place the inputs on the mesh with the canonical shardings. The
    node count must divide the nodes-axis size (callers bucket to
    multiples of 128, models/matrix.py).

    ONE device_put per pytree (the shardings ride as a matching
    pytree), not one per leaf: jax batches the transfer into a single
    commit."""
    state_sh = jax.device_put(
        state,
        jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                     _node_state_specs(batched)),
    )
    asks_sh = jax.device_put(
        asks,
        jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                     _asks_specs(batched)),
    )
    key_spec = P(DP_AXIS) if batched else P()
    keys_sh = jax.device_put(keys, NamedSharding(mesh, key_spec))
    return state_sh, asks_sh, keys_sh


def gang_state_specs() -> "object":
    """PartitionSpecs for ops/gang.py's GangState, IN FIELD ORDER:
    node-axis leaves shard, everything is per-node. Lives here (with
    base_specs) so the gang program's sharded inputs can't drift from
    the dispatch-side layout. The topology-group scatter-add inside the
    program crosses shards (a gang slice can span them) — GSPMD lowers
    it to a segment-sum + all-reduce, the same collective the explicit
    parallel/shard.py sharded_group_capacity states by hand."""
    from ..ops.gang import GangState

    vec = P(NODE_AXIS)
    mat = P(NODE_AXIS, None)
    return GangState(
        capacity=mat,
        sched_capacity=mat,
        util=mat,
        bw_avail=vec,
        bw_used=vec,
        ports_free=vec,
        feas_row=vec,
        job_count=vec,
        dh_presence=vec,
        topo_ids=vec,
    )


def shard_gang_inputs(mesh: Mesh, state) -> "object":
    """Place a GangState on the mesh, node axis sharded. One
    device_put for the whole pytree (single transfer commit, like
    shard_placement_inputs)."""
    return jax.device_put(
        state,
        jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                     gang_state_specs()),
    )


def defrag_solve_specs() -> Tuple:
    """PartitionSpecs for the defrag global solve's arguments, IN
    defrag/solver.py _solve_jit order: (logits0, fresh, base_util,
    capacity, sched_capacity, node_ok, bw_avail, bw_used, ports_free,
    ask_res, ask_bw, ask_ports, active). The x[K, N] tensor (logits0
    and the program's intermediates) shards over its NODE column —
    the biggest tensor in the system is what caps the fleet on one
    device. Ask-axis arrays replicate (K is bounded by
    MAX_SOLVE_ALLOCS)."""
    vec = P(NODE_AXIS)
    mat = P(NODE_AXIS, None)
    return (P(None, NODE_AXIS), P(), mat, mat, mat, vec, vec, vec, vec,
            P(None, None), P(), P(), P())


def shard_defrag_inputs(mesh: Mesh, args: Tuple) -> Tuple:
    """Place the defrag solve's argument tuple on the mesh
    (defrag_solve_specs order). GSPMD propagates through mirror
    descent: the per-alloc softmax over the sharded node axis lowers
    to a cross-device reduction, the gradient terms stay node-local."""
    return jax.device_put(
        args,
        tuple(NamedSharding(mesh, s) for s in defrag_solve_specs()),
    )


def sharded_placement(mesh: Mesh, state: NodeState, asks: Asks, keys, config,
                      batched: bool = False):
    """Run the placement program with mesh-sharded inputs. GSPMD
    propagates the shardings through the scan; the argmax over the
    sharded node axis becomes a cross-device reduction on ICI."""
    from ..ops.binpack import batched_placement_program, placement_program_jit

    state, asks, keys = shard_placement_inputs(mesh, state, asks, keys, batched)
    if batched:
        return batched_placement_program(state, asks, keys, config)
    return placement_program_jit(state, asks, keys, config)
