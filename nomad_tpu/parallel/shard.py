"""Explicit shard_map programs over the node axis of the resident base.

parallel/mesh.py is the GSPMD half of the scale-out story: annotate
input shardings, let XLA infer the collectives. This module is the
explicit half — shard_map programs whose bodies are written against
LOCAL node-axis slices, for the operations where the collective
structure is part of the contract and must not depend on what the
partitioner infers:

- ``sharded_base_delta``: the resident-base row scatter
  (ops/binpack.py apply_base_delta) with a replicated payload; each
  shard keeps only the rows that land in its slice, so the delta stays
  node-local — zero collectives, and the scattered rows are
  bit-identical to the single-device program's (every shard writes the
  same replicated values, `__graft_entry__.py` proves it at 8 devices).
- ``sharded_group_capacity``: the gang program's topology-group
  scatter-add (ops/gang.py _group_capacity). A gang slice can span
  shards, so each shard scatter-adds its local members into the padded
  group vector and a psum over the node axis assembles the global
  per-group capacity.

No host->device transfer lives here — ntalint's full-matrix-reship
scope covers this module with a ZERO baseline (unlike mesh.py, which is
deliberately out of scope as the placement infrastructure the
sanctioned upload path calls). Callers hand in arrays already placed by
scheduler/batcher.py's rebuild entry point or parallel/mesh.py.

Programs are cached per mesh (and static shape knobs) and registered in
ops/binpack.py's jit accounting via ``shard_cache_size()`` — the
steady-state-recompiles-0 contract covers the sharded programs too.
The factory names are declared in binpack's ``NTA_JIT_ACCOUNTED``
manifest, so ntalint's `unregistered-jit` rule holds this module's
nested ``jax.jit`` sites to that accounting statically
(tests/test_compile_surface.py diffs manifest, AST scan, and the
runtime registry both ways).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import NODE_AXIS

# key -> jitted program; guarded by _PROGRAM_LOCK. One entry per
# (program kind, mesh[, static knob]) — bounded by the process's mesh
# count (one), not by traffic.
_PROGRAMS: Dict[Tuple, object] = {}
_PROGRAM_LOCK = threading.Lock()


def _cached(key: Tuple, build):
    with _PROGRAM_LOCK:
        fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    built = build()
    with _PROGRAM_LOCK:
        return _PROGRAMS.setdefault(key, built)


def node_shard_count(mesh) -> int:
    """Shards along the node axis of a parallel/mesh.py mesh."""
    return int(mesh.shape[NODE_AXIS])


def sharded_base_delta(mesh):
    """The shard_map analog of ops/binpack.py apply_base_delta for a
    node-axis-sharded resident base: mutable arrays arrive sharded
    (parallel/mesh.py base_specs), the few-row payload replicated
    (delta_row_specs). Each shard rebases the global row indices into
    its local slice and drops the rest — the scatter never gathers the
    node axis. Padding rows (duplicates of real rows, batcher
    _pad_rows) write identical values, so duplicate indices stay
    deterministic."""

    def build():
        def local(util, bw_used, ports_free, node_ok,
                  rows, util_rows, bw_rows, ports_rows, ok_rows):
            n_local = util.shape[0]
            lo = jax.lax.axis_index(NODE_AXIS) * n_local
            local_rows = rows - lo
            here = (local_rows >= 0) & (local_rows < n_local)
            # Out-of-slice rows route to n_local and drop in the
            # scatter (the same drop idiom as placement_step's invalid
            # placements, ops/binpack.py).
            safe = jnp.where(here, local_rows, n_local)
            return (util.at[safe].set(util_rows, mode="drop"),
                    bw_used.at[safe].set(bw_rows, mode="drop"),
                    ports_free.at[safe].set(ports_rows, mode="drop"),
                    node_ok.at[safe].set(ok_rows, mode="drop"))

        mapped = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(NODE_AXIS, None), P(NODE_AXIS), P(NODE_AXIS),
                      P(NODE_AXIS), P(), P(None, None), P(), P(), P()),
            out_specs=(P(NODE_AXIS, None), P(NODE_AXIS), P(NODE_AXIS),
                       P(NODE_AXIS)))
        return jax.jit(mapped)

    return _cached(("base_delta", mesh), build)


def sharded_group_capacity(mesh, g_pad: int):
    """The gang program's topology-group scatter-add, shard_mapped:
    per-shard local scatter-add of member units into the padded group
    vector, assembled with a psum over the node axis (a gang slice can
    span shards). ``g_pad`` is a static shape knob (models/topology.py
    topo_group_pad), so one program exists per (mesh, pad bucket)."""

    def build():
        from ..ops.gang import _group_capacity

        def local(units, topo_ids):
            partial = _group_capacity(units, topo_ids, g_pad)
            return jax.lax.psum(partial, NODE_AXIS)

        mapped = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(NODE_AXIS), P(NODE_AXIS)),
            out_specs=P())
        return jax.jit(mapped)

    return _cached(("group_capacity", mesh, g_pad), build)


def per_shard_occupancy(arrays) -> List[dict]:
    """[{device, platform, rows, bytes}] per shard of a
    device-resident base tuple (or a single array) — chip_smoke.py's
    proof of WHERE the base sits. Pure metadata: reads shard layouts,
    moves no data. Single-device arrays report one row."""
    if not isinstance(arrays, (tuple, list)):
        arrays = (arrays,)
    per: Dict[str, dict] = {}
    for j, arr in enumerate(arrays):
        shards = getattr(arr, "addressable_shards", None)
        if shards is None:
            continue
        for s in shards:
            d = str(s.device)
            ent = per.setdefault(d, {
                "device": d, "platform": s.device.platform,
                "rows": 0, "bytes": 0})
            ent["bytes"] += int(s.data.nbytes)
            if j == 0:
                ent["rows"] += int(s.data.shape[0])
    return [per[d] for d in sorted(per)]


def shard_cache_size() -> int:
    """Compiled-program count across the cached shard_map programs —
    an input to ops/binpack.py jit_cache_size, so the recompile count
    covers the sharded paths too."""
    with _PROGRAM_LOCK:
        fns = list(_PROGRAMS.values())
    return sum(fn._cache_size() for fn in fns)
