"""Pluggable dense placement kernels (ROADMAP item 4).

A *placement kernel* is the per-batch solve at the heart of the dense
scheduler: a pure function with `ops/binpack.py placement_program`'s
exact signature —

    kernel(state: NodeState, asks: Asks, key, config: PlacementConfig)
        -> (choices [K] int32, scores [K] f32, final_state)

`placement_program` dispatches to the registered kernel named by
``PlacementConfig.kernel`` (a static/compile-time field, so every
kernel gets its own cached XLA program and rides the batcher's
overlay / compact / fused-delta paths unchanged — the
kernel swaps only HOW the solve is computed, never how batches form,
how bases become device-resident, or how plans commit).

Selection surfaces:

- ``placement_kernel`` config knob (ServerConfig + agent HCL
  ``server.placement_kernel`` + CLI), validated at server init so a
  typo fails loudly before the first eval;
- scheduler factory registry: every kernel K also registers
  ``service-K-tpu`` / ``batch-K-tpu`` factories
  (scheduler/__init__.py), pinning that kernel per scheduler type the
  same way ``scheduler_factories`` routes evals.

Built-ins: ``greedy`` (the sequential masked-argmax scan in
ops/binpack.py — the BestFit-v3 reference reformulation) and
``convex`` (kernels/convex.py — a CvxCluster-style convex-relaxation
bin-packer: simplex-relaxed assignment solved by a fixed-iteration
jitted mirror-descent loop, then rounded by a feasibility-mask-
respecting repair scan).

Validity contract: a kernel may trade placement QUALITY, never
VALIDITY — the oracle differential rig (kernels/differential.py) runs
every registered kernel against the sequential CPU oracle on
randomized clusters and asserts feasibility, capacity, and
plan-apply acceptance. The quality scoreboard (kernels/quality.py)
measures the trade: fragmentation, bin-pack utilization, queueing
delay.

Preemption interplay (ops/preempt.py): the dense priority-preemption
pass is NOT part of the kernel contract — kernels place into free
capacity only. When an outranking eval's kernel solve leaves asks
unplaced (the machines are full), the scheduler runs the separate
preemption program over the cached base (its own compiled entry point, greedy
scoring) regardless of which kernel failed first; evictions commit
through the plan's verified node_preemptions leg either way. A kernel
therefore never needs victim-awareness to stay correct under
preemption — it just sees the post-eviction capacity on the replan.

This module stays JAX-free at import time (the scheduler package and
server init import it; only the dense dispatch path may pull in jax):
kernel programs register as LAZY loaders resolved on first dispatch.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List

# The kernel ops/binpack.py implements natively: `placement_program`
# runs its own scan when config.kernel == DEFAULT_KERNEL, so the
# default entry needs no loader.
DEFAULT_KERNEL = "greedy"

# name -> zero-arg loader returning the kernel program (lazy: loading
# pulls in jax). guarded-by: _LOCK
_LOADERS: Dict[str, Callable[[], Callable]] = {}
# name -> resolved program (memoized loads). guarded-by: _LOCK
_PROGRAMS: Dict[str, Callable] = {}
# Sorted name tuple, rebuilt on registration: kernel_names() sits on
# the per-eval routing path (worker.host_factory), so reads are a
# lock-free immutable-ref load. guarded-by: _LOCK (writes)
_NAMES: tuple = ()
_LOCK = threading.Lock()
# Process-global active kernel, set by kernels.configure() from
# ServerConfig.placement_kernel (process-global like the batcher's
# device cache and the breaker: one device path per process).
_ACTIVE = DEFAULT_KERNEL  # guarded-by: _LOCK


def _load_greedy():
    # The native sequential masked-argmax program. Calling it through
    # the registry is equivalent to calling it directly: its dispatch
    # branch is a no-op when config.kernel == DEFAULT_KERNEL.
    from ..ops.binpack import placement_program

    return placement_program


def _load_convex():
    from .convex import convex_placement_program

    return convex_placement_program


def register_kernel(name: str, loader: Callable[[], Callable]) -> None:
    """Register a placement kernel under `name`. `loader` is a
    zero-arg callable returning the kernel program (resolved lazily on
    first dispatch so registration never imports jax). Third-party
    kernels register here and become selectable through every surface
    (placement_kernel knob, `service-<name>-tpu` factories)."""
    if not name or "-" in name:
        # Kernel names embed into factory names ("service-<k>-tpu") and
        # host_factory() strips them back out; a dash would make that
        # mapping ambiguous.
        raise ValueError(
            f"invalid kernel name {name!r}: non-empty, no dashes")
    if name == DEFAULT_KERNEL and DEFAULT_KERNEL in _LOADERS:
        # placement_program runs the native scan for the default name
        # without consulting the registry — accepting a replacement
        # loader here would silently never run it.
        raise ValueError(
            f"the native {DEFAULT_KERNEL!r} kernel cannot be replaced; "
            f"register under a new name")
    global _NAMES
    with _LOCK:
        _LOADERS[name] = loader
        _PROGRAMS.pop(name, None)
        _NAMES = tuple(sorted(_LOADERS))


register_kernel(DEFAULT_KERNEL, _load_greedy)
register_kernel("convex", _load_convex)


def kernel_names() -> List[str]:
    # Lock-free: _NAMES is an immutable tuple swapped atomically on
    # registration (this sits on the per-eval routing path).
    return list(_NAMES)


def kernel_program(name: str) -> Callable:
    """Resolve a kernel name to its program (loading it on first use).
    `placement_program` calls this for every non-default kernel."""
    with _LOCK:
        prog = _PROGRAMS.get(name)
        loader = _LOADERS.get(name)
    if prog is not None:
        return prog
    if loader is None:
        raise ValueError(
            f"unknown placement kernel {name!r} "
            f"(registered: {', '.join(kernel_names())})")
    prog = loader()
    with _LOCK:
        _PROGRAMS[name] = prog
    return prog


def validate(kernel: str) -> None:
    """Raise ValueError unless `kernel` is registered — server init
    calls this so a typo'd ``placement_kernel`` fails at startup, not
    at the first eval."""
    if kernel not in _NAMES:
        raise ValueError(
            f"unknown placement kernel {kernel!r} "
            f"(registered: {', '.join(_NAMES)})")


def configure(kernel: str = None) -> None:
    """Set the process-global active kernel (the one `*-tpu` factories
    without an explicit kernel use). Raises ValueError on an unknown
    name. Like the breaker and resident-state globals this is
    process-wide — the LAST explicit configuration wins; Server init
    therefore only calls this for a non-default ``placement_kernel``
    (a second default-configured server in the process must not
    silently flip an explicitly-configured one back to greedy)."""
    global _ACTIVE
    if kernel is None:
        return
    validate(kernel)
    with _LOCK:
        _ACTIVE = kernel


def active_kernel() -> str:
    with _LOCK:
        return _ACTIVE


__all__ = [
    "DEFAULT_KERNEL",
    "active_kernel",
    "configure",
    "kernel_names",
    "kernel_program",
    "register_kernel",
    "validate",
]
