"""Server configuration.

Reference: nomad/config.go (defaults at :225-238) and
command/agent/agent.go:129 (num_schedulers overlay).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class ServerConfig:
    region: str = "global"
    datacenter: str = "dc1"
    node_name: str = ""
    bootstrap_expect: int = 1

    # Scheduling workers (reference default 1; the agent sets NumCPU).
    num_schedulers: int = 1
    # Which scheduler types this server's workers service.
    enabled_schedulers: List[str] = field(
        default_factory=lambda: ["service", "batch", "system", "_core"]
    )
    # Per-type factory overrides, e.g. {"service": "service-tpu"} routes
    # service evals to the TPU placement backend (BASELINE north star:
    # new factories, unchanged control plane).
    scheduler_factories: Dict[str, str] = field(default_factory=dict)

    # Eval broker (config.go:233-234)
    eval_nack_timeout: float = 60.0
    eval_delivery_limit: int = 3

    # Max evals the dispatch pipeline packs into one batch of dense
    # (TPU) factory evals, so their placement programs share one
    # batched device dispatch (extension over the reference's single
    # dequeue, eval_broker.go:259). 1 disables batching: each worker
    # then runs a dense eval itself on the dense factory.
    # Default = the batcher's MAX_BATCH: a batch can fill one device
    # dispatch and no more. The value has not been re-measured on an
    # attached chip. A lone eval is a batch of one: a dispatch of one
    # lane.
    eval_batch_size: int = 64

    # Central dispatch pipeline (nomad_tpu/dispatch): dense-path evals
    # from EVERY worker flow into one leader-side accumulator that
    # packs full device batches, launches them pipelined (next batch
    # accumulates during the in-flight device sync + plan submits),
    # and requeues plan-conflict retries into the ACCUMULATING batch.
    # Batches allowed in flight at once: overlap hides the device
    # round-trip + plan-submit tail behind the next accumulation. The
    # most, not the number: after a plan conflict the pipeline sends
    # batches through one at a time (dispatch/pipeline.py).
    dispatch_max_inflight: int = 2
    # Accumulation window while another batch is in flight (its
    # round-trip is the budget being amortized). With nothing in
    # flight a batch waits only for arrivals that are known to be on
    # the way (a register between its entry and its return,
    # Server.registers_on_the_way): the idle grace is the CAP on that
    # wait. A lone interactive eval, with nobody on the way, is cut at
    # once and pays none of it.
    dispatch_window: float = 0.05
    dispatch_idle_grace: float = 0.004

    # ---- Placement kernel (nomad_tpu/kernels) ----
    # Which dense placement kernel the *-tpu factories run: "greedy"
    # (the sequential masked-argmax reference reformulation) or
    # "convex" (the convex-relaxation bin-packer), plus any kernel a
    # plugin registered. Validated at server init — a typo fails
    # before the first eval, not inside it. None = leave the
    # process-global active kernel alone (it starts as "greedy"); an
    # EXPLICIT value — including "greedy" — sets it. Per-scheduler-
    # type pins are also available through scheduler_factories (e.g.
    # {"service": "service-convex-tpu"}).
    placement_kernel: Optional[str] = None

    # ---- Churn control (nomad_tpu/migrate) ----
    # In-flight migration budget: how many drain-displaced allocs may
    # be claimed by scheduling attempts at once, cluster-wide (the
    # reference's drain max_parallel analog). Displaced allocs past
    # the budget ride follow-up migration evals — a 100-node drain
    # storm re-places in bounded waves instead of thundering-herding
    # the plan queue. 0 = unbounded.
    migrate_max_parallel: int = 32
    # Priority preemption (ops/preempt.py): allow an eval whose
    # priority is above the threshold, and whose asks the normal dense
    # pass found no room for, to place them by evicting the
    # lowest-priority allocations. Decided by the machines' capacity,
    # not by the control plane's pressure: a cluster with headroom
    # never evicts. Off by default: with it off such an eval blocks
    # until capacity returns.
    preemption_enabled: bool = False
    # Evals must STRICTLY outrank this to preempt (50 = the default
    # job priority, so only above-normal work may evict).
    preempt_priority_threshold: int = 50

    # ---- Continuous defragmentation (nomad_tpu/defrag) ----
    # Leader-side background optimizer: periodically solves the relaxed
    # GLOBAL re-placement (the convex kernel's mirror-descent program,
    # warm-started across rounds) over the device-resident node state
    # and proposes bounded migration waves through the migration budget
    # + verified eviction legs. Off by default — it moves healthy
    # allocs, which is an operator's call to enable.
    defrag_enabled: bool = False
    # Seconds between optimization rounds on a green, led cluster
    # (yellow/red pressure backs off multiplicatively).
    defrag_interval: float = 30.0
    # Minimum NET fragmentation gain (0..1, the quality scoreboard's
    # fragmentation units) a round must measure before it proposes any
    # wave — below it, churning allocs isn't worth the disruption.
    defrag_min_gain: float = 0.01
    # Per-wave move cap; each wave also claims MigrationGovernor slots,
    # so disruption is additionally bounded by migrate_max_parallel
    # (one budget shared with drain storms).
    defrag_max_moves_per_wave: int = 16

    # ---- Read plane (nomad_tpu/readplane) ----
    # Parked-watcher multiplexer: blocking queries past their ?index
    # register a continuation with the mux and free their HTTP handler
    # thread; one wake-owner thread + a small serve pool re-run them
    # on scope notifications. False reverts to thread-parking long
    # polls.
    read_mux_enabled: bool = True
    # Continuations parked at once before new blocking queries fall
    # back to thread-parking (bounds mux memory under a watcher storm).
    read_mux_max_parked: int = 4096

    # ---- Overload protection (nomad_tpu/admission) ----
    # Bounded broker ready queues: default per-scheduler-type depth cap
    # (0 = unbounded) plus per-type overrides. A full queue sheds the
    # lowest-priority newest eval with a structured outcome.
    eval_ready_cap: int = 0
    eval_ready_caps: Dict[str, int] = field(default_factory=dict)
    # Eval deadline base TTL in seconds (0 = no deadlines). The
    # effective TTL scales with priority (admission/deadline.py):
    # default-priority evals get exactly this, priority 100 gets 1.5x.
    eval_deadline_ttl: float = 0.0
    # Token-bucket admission control on the HTTP/RPC intake. Buckets
    # only engage past green pressure, so the defaults are inert on an
    # unloaded server; leader-forward + raft + client control traffic
    # and the observability routes are always exempt.
    admission_enabled: bool = True
    admission_write_rate: float = 50.0
    admission_write_burst: float = 100.0
    admission_read_rate: float = 200.0
    admission_read_burst: float = 400.0
    # Retry-After hint (seconds) on red-pressure 503 sheds.
    admission_red_retry_after: float = 1.0
    # Absolute broker-depth thresholds (ready+unacked) used when ready
    # queues are UNcapped; capped queues use fractions of the cap.
    admission_depth_yellow: int = 256
    admission_depth_red: int = 1024
    # Rolling e2e p99 thresholds in ms (0 disables the latency input —
    # absolute latency bars are deployment-specific).
    admission_p99_yellow_ms: float = 0.0
    admission_p99_red_ms: float = 0.0
    # Device-path circuit breaker (admission/breaker.py): trip to the
    # host path after this many CONSECUTIVE device failures (or slow
    # batches when breaker_slow_ms > 0), cool down, then half-open
    # probe back.
    breaker_enabled: bool = True
    breaker_failure_threshold: int = 5
    breaker_slow_ms: float = 0.0
    breaker_slow_batches: int = 8
    breaker_cooldown: float = 5.0

    # ---- Contention observatory (nomad_tpu/profile) ----
    # Always-on lock/GIL/pipeline profiler, like the flight recorder:
    # ProfiledLock wait/hold histograms on the hot locks, the
    # GIL-pressure sampler thread, and the batch-boundary convoy
    # detector. False disables recording and stops the sampler; the
    # lock wrappers stay in place either way.
    profile_enabled: bool = True
    # Pressure-monitor thresholds on the WORST per-site contended
    # lock-wait p99 in ms (0 disables the input — like the e2e p99
    # thresholds, absolute bars are deployment-specific). When set,
    # yellow/red pressure reasons cite the hottest lock site.
    admission_lock_wait_yellow_ms: float = 0.0
    admission_lock_wait_red_ms: float = 0.0

    # Telemetry gauge emission period (command.go:570 setupTelemetry)
    telemetry_interval: float = 10.0
    statsd_addr: str = ""

    # Heartbeats (config.go:235-238)
    min_heartbeat_ttl: float = 10.0
    max_heartbeats_per_second: float = 50.0
    heartbeat_grace: float = 10.0

    # GC (config.go:227-232)
    eval_gc_interval: float = 300.0
    eval_gc_threshold: float = 3600.0
    job_gc_interval: float = 300.0
    job_gc_threshold: float = 4 * 3600.0
    node_gc_interval: float = 300.0
    node_gc_threshold: float = 24 * 3600.0

    # Blocked-evals failed-eval unblock cadence (leader.go:441).
    failed_eval_unblock_interval: float = 60.0

    # Vault token authority (nomad/vault.go). With vault_addr set the
    # server talks to a real Vault over HTTP using vault_token as its
    # own token (renewed at half-life); otherwise an in-process stub
    # keeps the derive→renew→revoke lifecycle working vault-less.
    vault_enabled: bool = True
    vault_addr: str = ""
    vault_token: str = ""
    vault_token_ttl: float = 3600.0
    # None = any policy except root; else an allowlist.
    vault_allowed_policies: Optional[List[str]] = None

    def factory_for(self, eval_type: str) -> str:
        return self.scheduler_factories.get(eval_type, eval_type)
