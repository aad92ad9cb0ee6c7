"""Deterministic, seed-driven fault-injection registry.

The dispatch pipeline (PR 1) concentrated the dense placement path
onto a single leader-side dispatcher — a leader flap, a slow follower,
a worker crash, or a device-lane failure now has one high-blast-radius
place to hurt us. This registry makes those failures *injectable,
deterministic, and replayable*: named injection sites are wired into
the layers that matter (transport, raft, broker, dispatch pipeline,
device dispatch, heartbeats), and an armed seed + fault schedule
injects drops, delays and exceptions whose firing sequence is a pure
function of (seed, site, call-ordinal) — replaying the same seed
against the same per-site call sequence produces an identical firing
log.

Production cost: sites guard with ``chaos.enabled`` (a plain attribute
read) before calling :meth:`ChaosRegistry.fire`, and ``fire`` itself
is a constant-false check when disarmed — zero allocation, zero lock.

Site semantics (what a fired action means is defined BY the site):

=====================  =======================================================
site                   wired into
=====================  =======================================================
``transport.send``     TCP raft RPC about to go out (drop = peer unreachable)
``transport.recv``     TCP raft RPC response received (drop = response lost)
``raft.apply``         RaftNode.apply entry (delay = apply latency)
``raft.commit``        commit-index advance (drop = skip a round)
``raft.heartbeat``     leader heartbeat broadcast (drop = missed round ->
                       election timeout -> leader flap)
``broker.deliver``     eval handed to a dequeuer (drop = delivery lost; the
                       lease is burned and the eval redelivers)
``broker.nack_timer``  nack-timeout firing (drop = timer re-armed; delay =
                       late redelivery)
``dispatch.launch``    pipeline batch launch prologue (error = launch fails,
                       whole batch nacks)
``dispatch.submit``    pipeline plan submit (error = submit fails, eval nacks)
``dispatch.finish``    pipeline ack/nack (drop = worker crash holding an
                       unacked eval; the broker nack timer reclaims it)
``batcher.dispatch``   placement batcher device dispatch (delay = slow device)
``binpack.device``     device execution gate (error = device fault; the dense
                       scheduler falls back to the host path)
``heartbeat.expire``   leader-side TTL expiry (drop = invalidation lost, the
                       timer re-arms; delay = late node-down)
``client.heartbeat``   client heartbeat tick (drop = heartbeat lost -> TTL
                       expiry -> node down)
``admission.slow_consumer``  pipeline stage consumer about to process an
                       eval (delay = a wedged scheduler thread: e2e p99
                       inflates and the pressure monitor must react;
                       error = the consumer dies, the eval nacks)
``device.breaker_trip``  device dispatch at the circuit breaker's gate
                       (error = device fault the breaker counts — K of
                       them trip the dense path to the host iterators;
                       delay = a slow batch for the slow-trip rule)
``matrix.stale_delta``  incremental cluster-base delta application
                       (drop = one delta record is lost: a changed node
                       row keeps its stale values on host AND device,
                       so the scheduler plans against wrong state — the
                       plan applier's exact verification must catch the
                       bad placement and force a full rebuild,
                       models/resident.py)
``drain.mid_migration``  top of a scheduler's migrate leg, before any
                       budget claim or staged eviction (error = the
                       eval dies mid-migration and must redeliver with
                       nothing committed — the drain soak's exactly-
                       once contract; delay = a slow migration wave)
``preempt.victim_lost``  per-victim at preemption commit (drop = the
                       victim is NOT staged in the plan though the
                       kernel already counted its freed capacity —
                       the plan applier's exact verification must
                       reject the under-freed node and force a replan)
``defrag.solve_stale``  defrag-loop round, after the solve completes
                       (drop = the solve raced a resident-base
                       rejection purge: the wave is discarded and the
                       warm carry dropped — NOTHING commits from a
                       chain the applier convicted, nomad_tpu/defrag)
``defrag.wave_lost``   defrag-loop wave watch (drop = the in-flight
                       wave is declared dead: every remaining
                       MigrationGovernor slot the loop claimed is
                       released; the wave's evals keep their own
                       exactly-once terminal path)
``gang.partial_commit``  plan-applier gang verification
                       (drop = one gang member's node is treated as
                       under-fitting at verification time — the WHOLE
                       gang must reject, every member filtered off
                       accepted nodes too, nothing partial commits;
                       server/plan_apply.py)
``gang.member_lost``   gang reconciliation in the scheduler (drop =
                       one live gang member is treated as lost — its
                       node died mid-flight — which must trigger the
                       whole-gang replacement: survivors stopped and
                       all K re-placed atomically;
                       scheduler/generic.py)
=====================  =======================================================
"""

from __future__ import annotations

import random
import threading
import time
from typing import Dict, List, Optional, Tuple

# Every wire-able site. arm() validates the schedule against this set so
# a typo'd site name fails loudly instead of silently never firing.
KNOWN_SITES = frozenset({
    "transport.send",
    "transport.recv",
    "raft.apply",
    "raft.commit",
    "raft.heartbeat",
    "broker.deliver",
    "broker.nack_timer",
    "dispatch.launch",
    "dispatch.submit",
    "dispatch.finish",
    "batcher.dispatch",
    "binpack.device",
    "heartbeat.expire",
    "client.heartbeat",
    "admission.slow_consumer",
    "device.breaker_trip",
    "matrix.stale_delta",
    "drain.mid_migration",
    "preempt.victim_lost",
    "defrag.solve_stale",
    "defrag.wave_lost",
    "gang.partial_commit",
    "gang.member_lost",
})

DROP = "drop"
DELAY = "delay"
ERROR = "error"
_KINDS = (DROP, DELAY, ERROR)


class ChaosInjectedError(Exception):
    """Raised out of an armed injection site configured kind='error'.

    Carries the site and per-site call ordinal so a failure seen in a
    test log maps straight back to the schedule entry that fired."""

    def __init__(self, site: str, seq: int):
        super().__init__(f"chaos-injected fault at {site!r} (call #{seq})")
        self.site = site
        self.seq = seq


class FaultSpec:
    """One scheduled fault at one site.

    - ``site``: a :data:`KNOWN_SITES` name.
    - ``kind``: ``drop`` | ``delay`` | ``error`` (the site defines what
      each means — see the module docstring table).
    - ``start``: first eligible call ordinal at the site (0-based): the
      fault arms only from the ``start``-th fire() call on.
    - ``count``: max times this spec fires (None = unlimited).
    - ``prob``: per-call firing probability, decided by the seeded RNG.
    - ``delay``: seconds to sleep for kind='delay'.
    - ``match``: optional {key: value} filter against the fire() call's
      context kwargs — e.g. ``match={"node": node_id}`` drops one
      node's heartbeats only.
    """

    __slots__ = ("site", "kind", "start", "count", "prob", "delay",
                 "match", "fired")

    def __init__(self, site: str, kind: str, start: int = 0,
                 count: Optional[int] = None, prob: float = 1.0,
                 delay: float = 0.0, match: Optional[dict] = None):
        if kind not in _KINDS:
            raise ValueError(f"unknown fault kind {kind!r}")
        self.site = site
        self.kind = kind
        self.start = start
        self.count = count
        self.prob = prob
        self.delay = delay
        self.match = dict(match) if match else None
        self.fired = 0  # guarded by the registry lock once armed

    def to_dict(self) -> dict:
        return {
            "site": self.site, "kind": self.kind, "start": self.start,
            "count": self.count, "prob": self.prob, "delay": self.delay,
            "match": self.match, "fired": self.fired,
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<FaultSpec {self.to_dict()}>"


class _Armed:
    """Context manager returned by ChaosRegistry.armed()."""

    def __init__(self, registry: "ChaosRegistry"):
        self._registry = registry

    def __enter__(self) -> "ChaosRegistry":
        return self._registry

    def __exit__(self, *exc) -> None:
        self._registry.disarm()


class ChaosRegistry:
    def __init__(self):
        # Plain attribute, read un-locked on every site: the production
        # fast path is one attribute load + branch. Arming happens-before
        # any fire that must see the schedule because arm() publishes
        # under the lock and fire() re-checks under it.
        self.enabled = False
        self._lock = threading.Lock()
        self._seed = 0
        self._specs: Dict[str, List[FaultSpec]] = {}
        self._calls: Dict[str, int] = {}  # site -> fire() calls seen
        # (site, call ordinal, kind, delay) in per-site order; read back
        # sorted so the log is deterministic given deterministic
        # per-site call sequences, regardless of thread interleaving.
        self._log: List[Tuple[str, int, str, float]] = []

    # ------------------------------------------------------ arm/disarm

    def arm(self, seed: int, schedule: List[FaultSpec]) -> None:
        """Arm the registry: from now on fire() decides faults from the
        seed + schedule. Unknown site names raise (typo guard)."""
        bad = sorted({s.site for s in schedule} - KNOWN_SITES)
        if bad:
            raise ValueError(
                f"unknown chaos site(s) {bad}; known sites: "
                f"{sorted(KNOWN_SITES)}")
        with self._lock:
            self._seed = seed
            self._specs = {}
            for spec in schedule:
                spec.fired = 0
                self._specs.setdefault(spec.site, []).append(spec)
            self._calls = {}
            self._log = []
            self.enabled = True

    def armed(self, seed: int, schedule: List[FaultSpec]) -> _Armed:
        """arm() as a context manager: always disarms on exit (the
        registry is process-global — a leaked schedule would inject
        faults into whatever test runs next)."""
        self.arm(seed, schedule)
        return _Armed(self)

    def disarm(self) -> None:
        with self._lock:
            self.enabled = False
            self._specs = {}

    # ------------------------------------------------------------ fire

    def fire(self, site: str, **ctx) -> Optional[str]:
        """Injection-site hook. Disabled: returns None (constant-false
        check). Armed: deterministically decides whether a scheduled
        fault fires for this site's next call ordinal; performs 'delay'
        in-line, raises ChaosInjectedError for 'error', and returns
        'drop'/'delay'/None for the site to act on."""
        if not self.enabled:
            return None
        with self._lock:
            if not self.enabled:  # disarmed between check and lock
                return None
            n = self._calls.get(site, 0)
            self._calls[site] = n + 1
            spec = self._decide_locked(site, n, ctx)
            if spec is None:
                return None
            spec.fired += 1
            action = spec.kind
            delay = spec.delay
            self._log.append((site, n, action, delay))
        # Trace correlation: when the firing call carries an eval
        # context, stamp (site, ordinal, kind) onto that eval's trace —
        # at completion it lands on the span covering the firing time,
        # so a seeded replay pinpoints which stage a fault inflated.
        eval_id = ctx.get("eval_id")
        if eval_id:
            from ..trace import annotate_fault

            annotate_fault(eval_id, site, n, action)
        # Side effects OUTSIDE the lock: a delay must never hold up
        # unrelated sites' decisions, and the raise must not poison the
        # registry state.
        if action == DELAY:
            time.sleep(delay)
            return DELAY
        if action == ERROR:
            raise ChaosInjectedError(site, n)
        return DROP

    def _decide_locked(self, site: str, n: int,
                       ctx: dict) -> Optional[FaultSpec]:
        specs = self._specs.get(site)
        if not specs:
            return None
        # The per-call RNG seeds from a STRING (CPython hashes str/bytes
        # seeds via sha512 — stable across processes, unlike hash()
        # under PYTHONHASHSEED randomization), so the n-th call at a
        # site decides identically on every replay of the same seed.
        rng = random.Random(f"{self._seed}:{site}:{n}")
        for spec in specs:
            if n < spec.start:
                continue
            if spec.count is not None and spec.fired >= spec.count:
                continue
            if spec.match is not None and any(
                    ctx.get(k) != v for k, v in spec.match.items()):
                continue
            if spec.prob < 1.0 and rng.random() >= spec.prob:
                continue
            return spec
        return None

    # ----------------------------------------------------- observation

    def firing_log(self) -> List[Tuple[str, int, str, float]]:
        """Fired faults as (site, call ordinal, kind, delay), sorted by
        (site, ordinal) — the deterministic replay artifact."""
        with self._lock:
            return sorted(self._log)

    def unfired(self) -> List[FaultSpec]:
        """Scheduled specs that never fired — the soaks' typo guard:
        a schedule that never exercised its path measured nothing."""
        with self._lock:
            return [s for specs in self._specs.values()
                    for s in specs if s.fired == 0]

    def calls(self, site: str) -> int:
        with self._lock:
            return self._calls.get(site, 0)

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "seed": self._seed,
                "fired": len(self._log),
                "calls": dict(self._calls),
                "specs": [s.to_dict()
                          for specs in self._specs.values()
                          for s in specs],
            }


# The process-wide registry every injection site imports. Module-level
# so the disabled check compiles down to two attribute loads + a branch.
chaos = ChaosRegistry()
