"""Command-line interface.

Reference: command/ + commands.go:28-146 — run/plan/status/stop/
validate/init/inspect/node-status/node-drain/alloc-status/eval-status/
agent-info and the agent entrypoint. Talks to the agent over the HTTP
SDK; `agent -dev` runs an in-process server+client.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

from ..api.client import APIError, Client
from ..utils.codec import to_dict

EXAMPLE_JOB = '''\
# Example job file (reference: command/init.go)
job "example" {
  datacenters = ["dc1"]
  type = "service"

  update {
    stagger = "10s"
    max_parallel = 1
  }

  group "cache" {
    count = 1

    restart {
      attempts = 10
      interval = "5m"
      delay = "25s"
      mode = "delay"
    }

    ephemeral_disk {
      size = 300
    }

    task "redis" {
      driver = "exec"

      config {
        command = "/bin/sleep"
        args = ["3600"]
      }

      resources {
        cpu = 500
        memory = 256

        network {
          mbits = 10
          port "db" {}
        }
      }
    }
  }
}
'''


def _client(args) -> Client:
    address = args.address or os.environ.get("NOMAD_ADDR", "http://127.0.0.1:4646")
    region = getattr(args, "region", "") or os.environ.get("NOMAD_REGION", "")
    return Client(address, timeout=30.0, region=region)


def _fmt_table(rows: List[List[str]], header: List[str]) -> str:
    all_rows = [header] + rows
    widths = [max(len(str(r[i])) for r in all_rows) for i in range(len(header))]
    lines = []
    for r in all_rows:
        lines.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)).rstrip())
    return "\n".join(lines)


def _short(ident: str) -> str:
    return ident[:8] if ident else ""


def _monitor_eval(client: Client, eval_id: str, timeout: float = 60.0) -> int:
    """Poll the eval until terminal; print placement results
    (command/monitor.go)."""
    deadline = time.monotonic() + timeout
    printed_blocked = False
    while time.monotonic() < deadline:
        ev, _ = client.evaluations.info(eval_id)
        if ev.status in ("complete", "failed", "canceled"):
            print(f'Evaluation "{_short(eval_id)}" finished with status "{ev.status}"')
            if ev.failed_tg_allocs:
                for tg, metric in ev.failed_tg_allocs.items():
                    print(f"\nTask Group {tg!r} (failed to place all allocations):")
                    for constraint, count in metric.constraint_filtered.items():
                        print(f"  * Constraint {constraint!r} filtered {count} nodes")
                    for dim, count in metric.dimension_exhausted.items():
                        print(f"  * Resources exhausted on {count} nodes: {dim}")
                    if metric.nodes_evaluated == 0:
                        print("  * No nodes were eligible for evaluation")
                if ev.blocked_eval and not printed_blocked:
                    print(
                        f'\nEvaluation "{_short(ev.blocked_eval)}" waiting for '
                        "additional capacity to place remainder"
                    )
            return 0 if ev.status == "complete" else 1
        time.sleep(0.2)
    print(f"Timed out waiting for evaluation {_short(eval_id)}")
    return 1


# ------------------------------------------------------------- commands


def cmd_version(args) -> int:
    from .. import API_MAJOR_VERSION, __version__

    print(f"nomad-tpu v{__version__} (api {API_MAJOR_VERSION})")
    return 0


def cmd_init(args) -> int:
    path = "example.nomad"
    if os.path.exists(path):
        print(f"Job file {path!r} already exists", file=sys.stderr)
        return 1
    with open(path, "w") as f:
        f.write(EXAMPLE_JOB)
    print(f"Example job file written to {path}")
    return 0


def cmd_validate(args) -> int:
    from ..jobspec import parse_file

    try:
        job = parse_file(args.file)
        errors = job.validate()
    except (ValueError, OSError) as e:
        print(f"Error validating job: {e}", file=sys.stderr)
        return 1
    if errors:
        for err in errors:
            print(f"Validation error: {err}", file=sys.stderr)
        return 1
    print("Job validation successful")
    return 0


def cmd_run(args) -> int:
    from ..jobspec import parse_file

    job = parse_file(args.file)
    client = _client(args)
    if args.check_index is not None:
        eval_id = client.jobs.enforce_register(job, args.check_index)
    else:
        eval_id = client.jobs.register(job)
    if not eval_id:
        print(f'Job "{job.id}" registered (periodic, no evaluation)')
        return 0
    print(f'==> Evaluation "{_short(eval_id)}" created for job "{job.id}"')
    if args.detach:
        print(eval_id)
        return 0
    return _monitor_eval(client, eval_id)


_DIFF_MARK = {"Added": "+", "Deleted": "-", "Edited": "+/-", "None": " "}


def _print_field_diffs(fields, indent: str) -> None:
    for f in fields:
        mark = _DIFF_MARK.get(f.get("type"), " ")
        if f.get("type") == "Edited":
            print(f'{indent}{mark} {f["name"]}: {f["old"]!r} => {f["new"]!r}')
        elif f.get("type") == "Added":
            print(f'{indent}{mark} {f["name"]}: {f["new"]!r}')
        elif f.get("type") == "Deleted":
            print(f'{indent}{mark} {f["name"]}: {f["old"]!r}')
        elif f.get("type") == "None":
            print(f'{indent}  {f["name"]}: {f["old"]!r}')


def _print_object_diffs(objects, indent: str) -> None:
    for o in objects or []:
        mark = _DIFF_MARK.get(o.get("type"), " ")
        print(f'{indent}{mark} {o["name"]} {{')
        _print_field_diffs(o.get("fields") or [], indent + "    ")
        _print_object_diffs(o.get("objects") or [], indent + "    ")
        print(f"{indent}}}")


def cmd_plan(args) -> int:
    from ..jobspec import parse_file

    job = parse_file(args.file)
    client = _client(args)
    result = client.jobs.plan(job, diff=True, contextual=args.verbose)
    diff = result.get("diff") or {}
    mark = _DIFF_MARK.get(diff.get("type", "None"), " ")
    print(f"{mark} Job: {job.id!r}")
    _print_field_diffs(diff.get("fields") or [], "  ")
    _print_object_diffs(diff.get("objects") or [], "  ")
    for tgd in diff.get("task_groups") or []:
        mark = _DIFF_MARK.get(tgd.get("type", "None"), " ")
        counts = ", ".join(
            f"{n} {label}" for label, n in (tgd.get("updates") or {}).items() if n
        )
        print(f'{mark} Task Group: {tgd["name"]!r}' + (f" ({counts})" if counts else ""))
        if args.verbose or tgd.get("type") != "None":
            _print_field_diffs(tgd.get("fields") or [], "    ")
            _print_object_diffs(tgd.get("objects") or [], "    ")
            for td in tgd.get("tasks") or []:
                tmark = _DIFF_MARK.get(td.get("type", "None"), " ")
                notes = ", ".join(td.get("annotations") or [])
                print(f'    {tmark} Task: {td["name"]!r}' + (f" ({notes})" if notes else ""))
                _print_field_diffs(td.get("fields") or [], "        ")
                _print_object_diffs(td.get("objects") or [], "        ")

    failed = result.get("failed_tg_allocs") or {}
    if failed:
        print("\nPlacement failures:")
        for tg, metric in failed.items():
            print(f"  Task Group {tg!r}:")
            for constraint, count in (metric.get("constraint_filtered") or {}).items():
                print(f"    * Constraint {constraint!r} filtered {count} nodes")
    else:
        print("\nAll tasks successfully allocated.")
    print(f'\nJob Modify Index: {result.get("job_modify_index", 0)}')
    print('To submit the job with version verification run:\n')
    print(f'nomad-tpu run -check-index {result.get("job_modify_index", 0)} {args.file}')
    return 0


def cmd_status(args) -> int:
    client = _client(args)
    if not args.job:
        jobs, _ = client.jobs.list()
        if not jobs:
            print("No running jobs")
            return 0
        rows = [
            [_stub["id"], _stub["type"], str(_stub["priority"]), _stub["status"]]
            for _stub in jobs
        ]
        print(_fmt_table(rows, ["ID", "Type", "Priority", "Status"]))
        return 0
    try:
        job, _ = client.jobs.info(args.job)
    except APIError as e:
        print(f"Error querying job: {e}", file=sys.stderr)
        return 1
    print(f"ID            = {job.id}")
    print(f"Name          = {job.name}")
    print(f"Type          = {job.type}")
    print(f"Priority      = {job.priority}")
    print(f"Datacenters   = {','.join(job.datacenters)}")
    print(f"Status        = {job.status}")
    print(f"Periodic      = {job.is_periodic()}")
    summary, _ = client.jobs.summary(job.id)
    print("\nSummary")
    rows = [
        [tg, str(s["queued"]), str(s["starting"]), str(s["running"]),
         str(s["failed"]), str(s["complete"]), str(s["lost"])]
        for tg, s in (summary.get("summary") or {}).items()
    ]
    print(_fmt_table(
        rows, ["Task Group", "Queued", "Starting", "Running", "Failed",
               "Complete", "Lost"]
    ))
    allocs, _ = client.jobs.allocations(job.id)
    if allocs:
        print("\nAllocations")
        rows = [
            [_short(a["id"]), _short(a["eval_id"]), _short(a["node_id"]),
             a["task_group"], a["desired_status"], a["client_status"]]
            for a in allocs
        ]
        print(_fmt_table(
            rows, ["ID", "Eval ID", "Node ID", "Task Group", "Desired", "Status"]
        ))
    return 0


def cmd_stop(args) -> int:
    client = _client(args)
    eval_id = client.jobs.deregister(args.job)
    if not eval_id:
        print(f'Job "{args.job}" deregistered')
        return 0
    print(f'==> Evaluation "{_short(eval_id)}" created for deregistration')
    if args.detach:
        return 0
    return _monitor_eval(client, eval_id)


def cmd_inspect(args) -> int:
    client = _client(args)
    job, _ = client.jobs.info(args.job)
    print(json.dumps(to_dict(job), indent=2, sort_keys=True))
    return 0


def cmd_node_status(args) -> int:
    client = _client(args)
    if not args.node:
        nodes, _ = client.nodes.list()
        rows = [
            [_short(n["id"]), n["datacenter"], n["name"], n["node_class"],
             str(n["drain"]), n["status"]]
            for n in nodes
        ]
        print(_fmt_table(rows, ["ID", "DC", "Name", "Class", "Drain", "Status"]))
        return 0
    node, _ = client.nodes.info(args.node)
    print(f"ID         = {node.id}")
    print(f"Name       = {node.name}")
    print(f"Class      = {node.node_class}")
    print(f"DC         = {node.datacenter}")
    print(f"Drain      = {node.drain}")
    print(f"Status     = {node.status}")
    if node.resources:
        print(
            f"Resources  = cpu:{node.resources.cpu}MHz "
            f"mem:{node.resources.memory_mb}MB disk:{node.resources.disk_mb}MB"
        )
    drivers = sorted(
        k.removeprefix("driver.")
        for k in node.attributes
        if k.startswith("driver.") and not k.endswith(".enable")
    )
    print(f"Drivers    = {','.join(drivers)}")
    return 0


def cmd_node_drain(args) -> int:
    client = _client(args)
    if not (args.enable or args.disable):
        print("Either -enable or -disable is required", file=sys.stderr)
        return 1
    client.nodes.drain(args.node, drain=bool(args.enable))
    state = "enabled" if args.enable else "disabled"
    print(f"Node {_short(args.node)} drain {state}")
    return 0


def cmd_alloc_status(args) -> int:
    client = _client(args)
    alloc, _ = client.allocations.info(args.alloc)
    print(f"ID            = {alloc.id}")
    print(f"Eval ID       = {_short(alloc.eval_id)}")
    print(f"Name          = {alloc.name}")
    print(f"Node ID       = {_short(alloc.node_id)}")
    print(f"Job ID        = {alloc.job_id}")
    print(f"Desired       = {alloc.desired_status}  {alloc.desired_description}")
    print(f"Status        = {alloc.client_status}  {alloc.client_description}")
    for task, state in alloc.task_states.items():
        print(f"\nTask {task!r} is {state.state!r} (failed={state.failed})")
        for event in state.events[-5:]:
            details = []
            if event.exit_code:
                details.append(f"exit={event.exit_code}")
            if event.driver_error:
                details.append(event.driver_error)
            if event.message:
                details.append(event.message)
            print(f"  {event.type}" + (f" ({', '.join(details)})" if details else ""))
    metrics = alloc.metrics
    if metrics is not None and args.verbose:
        print("\nPlacement Metrics")
        print(f"  Nodes evaluated: {metrics.nodes_evaluated}")
        print(f"  Nodes filtered:  {metrics.nodes_filtered}")
        print(f"  Nodes exhausted: {metrics.nodes_exhausted}")
        for name, score in metrics.scores.items():
            print(f"  Score {name}: {score:.3f}")
    return 0


def cmd_eval_status(args) -> int:
    client = _client(args)
    ev, _ = client.evaluations.info(args.eval)
    print(f"ID                 = {ev.id}")
    print(f"Status             = {ev.status}  {ev.status_description}")
    print(f"Type               = {ev.type}")
    print(f"Triggered By       = {ev.triggered_by}")
    print(f"Job ID             = {ev.job_id}")
    print(f"Priority           = {ev.priority}")
    if ev.blocked_eval:
        print(f"Blocked Eval       = {_short(ev.blocked_eval)}")
    if ev.queued_allocations:
        print(f"Queued Allocations = {ev.queued_allocations}")
    if ev.failed_tg_allocs:
        print("\nFailed Placements")
        for tg, metric in ev.failed_tg_allocs.items():
            print(f"Task Group {tg!r}:")
            for constraint, count in metric.constraint_filtered.items():
                print(f"  * Constraint {constraint!r} filtered {count} nodes")
            for dim, count in metric.dimension_exhausted.items():
                print(f"  * {dim} exhausted on {count} nodes")
    return 0


def cmd_fs(args) -> int:
    """Browse an allocation's filesystem (command/fs.go)."""
    client = _client(args)
    path = args.path or "/"
    if args.stat:
        st = client.alloc_fs.stat(args.alloc, path)
        kind = "dir" if st["is_dir"] else "file"
        print(f'{st["name"]}\t{kind}\t{st["size"]} bytes')
        return 0
    st = client.alloc_fs.stat(args.alloc, path)
    if st["is_dir"]:
        for ent in client.alloc_fs.list(args.alloc, path):
            kind = "d" if ent["is_dir"] else "-"
            print(f'{kind} {ent["size"]:>10}  {ent["name"]}')
    else:
        sys.stdout.buffer.write(client.alloc_fs.cat(args.alloc, path))
    return 0


def cmd_logs(args) -> int:
    """Stream a task's stdout/stderr (command/logs.go): offset-poll the
    logs endpoint; -f keeps following."""
    client = _client(args)
    ltype = "stderr" if args.stderr else "stdout"
    task = args.task
    if not task:
        alloc, _ = client.allocations.info(args.alloc)
        names = list(alloc.task_states or {})
        if len(names) != 1:
            print(
                f"allocation has {len(names)} tasks, specify one of: {names}",
                file=sys.stderr,
            )
            return 1
        task = names[0]
    if args.tail and args.n > 0:
        out = client.alloc_fs.logs(args.alloc, task, ltype, offset=args.n, origin="end")
    else:
        out = client.alloc_fs.logs(args.alloc, task, ltype)
    sys.stdout.buffer.write(out["data"])
    sys.stdout.flush()
    offset = out["offset"]
    while args.follow:
        time.sleep(1.0)
        out = client.alloc_fs.logs(args.alloc, task, ltype, offset=offset)
        if out["data"]:
            sys.stdout.buffer.write(out["data"])
            sys.stdout.flush()
            offset = out["offset"]
    return 0


def cmd_server_members(args) -> int:
    client = _client(args)
    members = client.agent.members()
    if not members:
        print("No known members")
        return 0
    print(f"{'Name':<28} {'Addr':<22} {'Status':<8} {'Region':<10} DC")
    for m in sorted(members, key=lambda m: m["name"]):
        print(f"{m['name']:<28} {m['addr']:<22} {m['status']:<8} "
              f"{m['region']:<10} {m['datacenter']}")
    return 0


def cmd_server_join(args) -> int:
    client = _client(args)
    joined = client.agent.join(args.addrs)
    print(f"Joined {joined} servers successfully")
    return 0 if joined else 1


def cmd_server_force_leave(args) -> int:
    client = _client(args)
    client.agent.force_leave(args.node)
    print(f"Force-leave of {args.node} requested")
    return 0


def cmd_agent_info(args) -> int:
    client = _client(args)
    info = client.agent.self()
    print(json.dumps(info["stats"], indent=2, sort_keys=True))
    return 0


def _resolve_agent_config(args):
    """defaults (< dev) < -config files in order < CLI flags
    (command.go:909 flag overlay)."""
    from .agent_config import (
        default_config,
        dev_config,
        load_config,
        merge_config,
    )

    cfg = dev_config() if args.dev else default_config()
    for path in args.config or []:
        cfg = merge_config(cfg, load_config(path))
    if args.bind:
        cfg.bind_addr = args.bind
    if args.port:
        cfg.ports.http = args.port
    if getattr(args, "serf_port", 0):
        cfg.ports.serf = args.serf_port
    if args.region:
        cfg.region = args.region
    if args.node_name:
        cfg.name = args.node_name
    if args.num_schedulers is not None:
        cfg.server.num_schedulers = args.num_schedulers
    if args.statsd:
        cfg.telemetry.statsd_address = args.statsd
    if args.consul:
        cfg.consul.address = args.consul
    if args.advertise:
        cfg.advertise_addr = args.advertise
    if args.join:
        cfg.server.start_join = cfg.server.start_join + args.join.split(",")
    if args.log_level:
        cfg.log_level = args.log_level
    return cfg


def _advertise_addr(cfg):
    """A wildcard bind is not routable — advertise a real interface
    address instead."""
    import socket as _socket

    advertise = cfg.advertise_addr or cfg.bind_addr
    if advertise in ("0.0.0.0", "::"):
        s = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
        try:
            s.connect(("10.255.255.255", 1))
            advertise = s.getsockname()[0]
        except OSError:
            advertise = "127.0.0.1"
        finally:
            s.close()
    return advertise


def cmd_agent(args) -> int:
    """Run an agent: server, client, or both, from merged config
    (agent.go:61 — the Agent composes nomad.Server and client.Client
    per config; -dev enables both with permissive defaults)."""
    import logging
    import socket as _socket

    from ..api import HTTPServer
    from ..client import ClientAgent, ClientConfig
    from ..server import Server, ServerConfig
    from ..utils import metrics
    from .agent_config import parse_duration

    try:
        cfg = _resolve_agent_config(args)
        collection_interval = parse_duration(cfg.telemetry.collection_interval)
        heartbeat_grace = (parse_duration(cfg.server.heartbeat_grace)
                           if cfg.server.heartbeat_grace else None)
        node_gc_threshold = (parse_duration(cfg.server.node_gc_threshold)
                             if cfg.server.node_gc_threshold else None)
    except (ValueError, OSError) as e:
        print(f"error loading config: {e}", file=sys.stderr)
        return 1
    logging.basicConfig(
        level=getattr(logging, cfg.log_level.upper(), logging.INFO),
        format="%(asctime)s [%(levelname)s] %(name)s: %(message)s",
    )
    if not cfg.server.enabled and not cfg.client.enabled:
        print("agent must have server, client, or both enabled "
              "(use -dev or a -config file)", file=sys.stderr)
        return 1

    metrics.configure(
        statsd_addr=cfg.telemetry.statsd_address,
        statsite_addr=cfg.telemetry.statsite_address,
        disable_hostname=cfg.telemetry.disable_hostname,
        interval=collection_interval,
        circonus_url=cfg.telemetry.circonus_submission_url,
    )
    # SIGUSR1 dumps recent telemetry to stderr (in-memory sink).
    try:
        metrics.install_signal_dump()
    except ValueError:
        pass  # not on the main thread (tests)

    scheduler_factories = {}
    device_banner = ""
    if cfg.server.scheduler_factories:
        scheduler_factories = dict(cfg.server.scheduler_factories)
    if args.tpu:
        # CLI flags win over config files (the module's documented
        # precedence): -tpu overlays the dense factories on whatever
        # the HCL mapped.
        scheduler_factories.update({"service": "service-tpu",
                                    "batch": "batch-tpu",
                                    "system": "system-tpu"})
    if cfg.server.enabled and any(
            f.endswith("-tpu") for f in scheduler_factories.values()):
        # Backend start-up at agent boot: with dense factories
        # configured this SERVER needs the device backend, and a broken
        # device environment fails here — at startup, on the operator's
        # console — rather than as per-eval scheduler errors (or a
        # silent host fallback) in the middle of the first placement
        # storm. It also takes backend start-up (seconds on a TPU) off
        # the first eval's dispatcher thread. JAX_PLATFORMS is the only
        # way to name a backend. Client-only agents never schedule and
        # skip the cost.
        import jax

        try:
            devices = jax.devices()
        except RuntimeError as e:
            print(f"error initializing the JAX backend for the dense "
                  f"scheduler factories: {e}", file=sys.stderr)
            return 1
        device_banner = (f"{devices[0].platform} "
                         f"({devices[0].device_kind}) x{len(devices)}")

    # Unique gossip identity per agent: two same-region agents with the
    # same member name would clobber each other in the serf pool.
    node_name = cfg.name or f"{_socket.gethostname()}-{cfg.ports.http}"

    # TLS contexts from the agent tls block: fail at boot with a clear
    # message, not mid-election (rpc.go:23-30 rpcTLS discipline).
    from ..utils.tlsutil import contexts_from_block

    tls_rpc_ctx, tls_http_ctx, tls_client_ctx = contexts_from_block(cfg.tls)

    server = http = raft_transport = None
    server_addr = None
    if cfg.server.enabled:
        server_cfg = ServerConfig(
            num_schedulers=(cfg.server.num_schedulers
                            if cfg.server.num_schedulers is not None else 2),
            scheduler_factories=scheduler_factories,
            region=cfg.region, datacenter=cfg.datacenter,
            node_name=node_name,
            bootstrap_expect=cfg.server.bootstrap_expect or 1,
            statsd_addr=cfg.telemetry.statsd_address,
        )
        if cfg.server.enabled_schedulers:
            server_cfg.enabled_schedulers = list(cfg.server.enabled_schedulers)
            if "_core" not in server_cfg.enabled_schedulers:
                server_cfg.enabled_schedulers.append("_core")
        if heartbeat_grace is not None:
            server_cfg.heartbeat_grace = heartbeat_grace
        if node_gc_threshold is not None:
            server_cfg.node_gc_threshold = node_gc_threshold
        if cfg.server.eval_batch_size is not None:
            server_cfg.eval_batch_size = cfg.server.eval_batch_size
        if cfg.server.dispatch_max_inflight is not None:
            server_cfg.dispatch_max_inflight = (
                cfg.server.dispatch_max_inflight)
        # Placement kernel (nomad_tpu/kernels); Server init validates,
        # so a typo'd name aborts agent startup with the known list.
        if cfg.server.placement_kernel is not None:
            server_cfg.placement_kernel = cfg.server.placement_kernel
        # Churn control (nomad_tpu/migrate): migration budget +
        # preemption policy. CLI flags win over HCL, as everywhere.
        if args.migrate_max_parallel is not None:
            server_cfg.migrate_max_parallel = args.migrate_max_parallel
        elif cfg.server.migrate_max_parallel is not None:
            server_cfg.migrate_max_parallel = cfg.server.migrate_max_parallel
        if args.preemption:
            server_cfg.preemption_enabled = True
        elif cfg.server.preemption_enabled is not None:
            server_cfg.preemption_enabled = cfg.server.preemption_enabled
        if cfg.server.preempt_priority_threshold is not None:
            server_cfg.preempt_priority_threshold = (
                cfg.server.preempt_priority_threshold)
        # Continuous defragmentation (nomad_tpu/defrag): the CLI flag
        # only turns it ON (HCL can do either); tuning knobs are HCL.
        if args.defrag:
            server_cfg.defrag_enabled = True
        elif cfg.server.defrag_enabled is not None:
            server_cfg.defrag_enabled = cfg.server.defrag_enabled
        if cfg.server.defrag_interval is not None:
            server_cfg.defrag_interval = cfg.server.defrag_interval
        if cfg.server.defrag_min_gain is not None:
            server_cfg.defrag_min_gain = cfg.server.defrag_min_gain
        if cfg.server.defrag_max_moves_per_wave is not None:
            server_cfg.defrag_max_moves_per_wave = (
                cfg.server.defrag_max_moves_per_wave)
        # Overload protection (nomad_tpu/admission): bounded broker
        # queues, deadlines, intake gate, device-path breaker.
        if cfg.server.eval_ready_cap is not None:
            server_cfg.eval_ready_cap = cfg.server.eval_ready_cap
        if cfg.server.eval_deadline_ttl is not None:
            server_cfg.eval_deadline_ttl = cfg.server.eval_deadline_ttl
        if cfg.server.admission_enabled is not None:
            server_cfg.admission_enabled = cfg.server.admission_enabled
        if cfg.server.breaker_enabled is not None:
            server_cfg.breaker_enabled = cfg.server.breaker_enabled
        if cfg.server.breaker_failure_threshold is not None:
            server_cfg.breaker_failure_threshold = (
                cfg.server.breaker_failure_threshold)
        if cfg.server.breaker_cooldown is not None:
            server_cfg.breaker_cooldown = cfg.server.breaker_cooldown
        # Contention observatory (nomad_tpu/profile).
        if cfg.server.profile_enabled is not None:
            server_cfg.profile_enabled = cfg.server.profile_enabled
        if cfg.server.admission_lock_wait_yellow_ms is not None:
            server_cfg.admission_lock_wait_yellow_ms = (
                cfg.server.admission_lock_wait_yellow_ms)
        if cfg.server.admission_lock_wait_red_ms is not None:
            server_cfg.admission_lock_wait_red_ms = (
                cfg.server.admission_lock_wait_red_ms)
        if "vault.enabled" in cfg.set_keys:
            server_cfg.vault_enabled = cfg.vault.enabled
        if cfg.vault.address:
            server_cfg.vault_addr = cfg.vault.address
            server_cfg.vault_token = cfg.vault.token
        server = Server(server_cfg)
        # TLS material for the server's own outbound/inbound channels:
        # the follower->leader HTTP forwards and cross-region proxying
        # must verify against the cluster CA, and gossip terminates
        # the same mTLS as raft (its member records carry the
        # addresses forwarding trusts).
        # Outbound contexts are passed UNGATED: the dial sites apply
        # them only to https:// targets, so a mixed rolling-TLS cluster
        # (this agent still plaintext, the leader already https) keeps
        # verifying peers against the cluster CA.
        server.tls_client_ctx = tls_client_ctx
        server.tls_rpc_server_ctx = tls_rpc_ctx
        server.tls_rpc_client_ctx = (
            tls_client_ctx if tls_rpc_ctx else None)
        # bootstrap_expect > 1: real raft consensus over TCP; the
        # cluster forms once enough servers gossip a raft address
        # (server.go bootstrap_expect). Otherwise single-server mode.
        multi_server = cfg.server.bootstrap_expect > 1
        raft_transport = None
        adv_raft = ""
        if multi_server:
            from ..server.transport import TCPTransport, fsm_payload_decoder

            raft_transport = TCPTransport(
                fsm_payload_decoder,
                ssl_server_ctx=tls_rpc_ctx,
                ssl_client_ctx=tls_client_ctx if tls_rpc_ctx else None)
            raft_bind = raft_transport.serve(cfg.bind_addr, cfg.ports.rpc)
            raft_port = int(raft_bind.rsplit(":", 1)[1])
            adv_raft = f"{_advertise_addr(cfg)}:{raft_port}"
            # Enter cluster mode (writes fail with no-leader) BEFORE the
            # HTTP API serves: an early write must never land in the
            # pre-raft dev log and silently diverge from the cluster.
            raft_dir = (os.path.join(cfg.data_dir, "raft")
                        if cfg.data_dir else "")
            server.setup_raft_cluster(
                raft_transport, adv_raft, cfg.server.bootstrap_expect,
                data_dir=raft_dir)
        else:
            server.start()
        http = HTTPServer(server, host=cfg.bind_addr, port=cfg.ports.http,
                          enable_debug=cfg.enable_debug,
                          ssl_context=tls_http_ctx,
                          forward_ssl_context=tls_client_ctx)
        http.start()
        server_addr = http.addr
        # Gossip peers and federated regions must receive a routable
        # address, not a wildcard bind (server.go setupSerf tags).
        scheme = "https" if tls_http_ctx is not None else "http"
        advertised_http = f"{scheme}://{_advertise_addr(cfg)}:{http.port}"
        serf_addr = server.setup_serf(host=cfg.bind_addr,
                                      port=cfg.ports.serf,
                                      http_addr=advertised_http,
                                      rpc_addr=adv_raft)
        if cfg.server.start_join:
            joined = server.serf_join(cfg.server.start_join)
            print(f"==> Joined {joined} gossip peers")
        if cfg.server.retry_join:
            # retry_join keeps trying until it lands (command.go
            # retryJoin loop) — that's its difference from start_join.
            import threading as _threading

            def _retry_join(srv=server, addrs=list(cfg.server.retry_join),
                            interval=3.0 if cfg.dev_mode else 15.0):
                while True:
                    try:
                        if srv.serf_join(addrs) > 0:
                            print(f"==> Retry-join succeeded: {addrs}")
                            return
                    except Exception:  # noqa: BLE001 - keep retrying
                        pass
                    time.sleep(interval)

            _threading.Thread(target=_retry_join, daemon=True,
                              name="retry-join").start()
        mode = "dev mode" if cfg.dev_mode else "server"
        print(f"==> nomad-tpu agent started ({mode})! HTTP: {http.addr}")
        print(f"    Gossip: {serf_addr} (region {cfg.region})")
        print(f"    Scheduler factories: {scheduler_factories or 'cpu defaults'}")
        if device_banner:
            print(f"    Placement device: {device_banner}")

    client_agent = None
    if cfg.client.enabled:
        servers = list(cfg.client.servers)
        if server_addr and server_addr not in servers:
            servers.insert(0, server_addr)
        # Keyed on the HTTP context, not the client one: an rpc-only
        # TLS rollout (tls { rpc=true http=false }) leaves the HTTP API
        # plaintext, and bare addresses must keep dialing http://.
        default_scheme = "https" if tls_http_ctx is not None else "http"
        servers = [s if "://" in s else f"{default_scheme}://{s}"
                   for s in servers]
        client_cfg = ClientConfig(
            servers=servers,
            region=cfg.region, datacenter=cfg.datacenter,
            node_name=node_name if cfg.name else "",
            node_class=cfg.client.node_class,
            options=dict(cfg.client.options),
            meta=dict(cfg.client.meta),
            dev_mode=cfg.dev_mode,
            consul_addr=cfg.consul.address,
            consul_service=cfg.consul.server_service_name,
            network_speed=cfg.client.network_speed,
            ssl_context=tls_client_ctx,
            chroot_env=dict(cfg.client.chroot_env) or None,
        )
        if cfg.client.reserved:
            from ..structs import Resources

            res = cfg.client.reserved
            client_cfg.reserved = Resources(
                cpu=int(res.get("cpu", 0)),
                memory_mb=int(res.get("memory", 0)),
                disk_mb=int(res.get("disk", 0)),
                iops=int(res.get("iops", 0)),
            )
        if cfg.client.state_dir:
            client_cfg.state_dir = cfg.client.state_dir
        elif cfg.data_dir:
            client_cfg.state_dir = os.path.join(cfg.data_dir, "client")
        if cfg.client.alloc_dir:
            client_cfg.alloc_dir = cfg.client.alloc_dir
        elif cfg.data_dir:
            client_cfg.alloc_dir = os.path.join(cfg.data_dir, "alloc")
        for d in (client_cfg.state_dir, client_cfg.alloc_dir):
            if d:
                os.makedirs(d, exist_ok=True)
        client_only = http is None
        if client_only:
            # Every agent serves HTTP (agent.go): a client-only node
            # still exposes its fs/logs/stats endpoints. Started before
            # the agent so the advertised port is known at registration.
            http = HTTPServer(None, host=cfg.bind_addr,
                              port=cfg.ports.http,
                              enable_debug=cfg.enable_debug,
                              ssl_context=tls_http_ctx,
                              forward_ssl_context=tls_client_ctx)
            http.start()
        # The node must register with a routable HTTP endpoint: peer
        # clients GET /v1/client/allocation/<id>/snapshot from it for
        # sticky-disk migration (client.go:1441 migrateRemoteAllocDir);
        # an empty http_addr makes every remote migration a no-op.
        client_cfg.http_addr = (
            f"{'https' if tls_http_ctx is not None else 'http'}://"
            f"{_advertise_addr(cfg)}:{http.port}")
        try:
            client_agent = ClientAgent(client_cfg)
            client_agent.start()
        except (ValueError, APIError) as e:
            print(f"error starting client: {e}", file=sys.stderr)
            if client_agent is not None:
                client_agent.shutdown()
            if http is not None:
                http.stop()
            if server is not None:
                server.shutdown()
            return 1
        # fs/stats endpoints are served off the co-located client.
        http.client = client_agent
        if client_only:
            print(f"==> nomad-tpu agent started (client)! HTTP: {http.addr}")
        print(f"    Client node: {client_agent.node.id}")

    # Agent-level consul registration: advertise this agent's HTTP
    # endpoint under the configured catalog services so clients can
    # bootstrap through discovery (consul/syncer.go agent services).
    agent_syncer = None
    if cfg.consul.address and cfg.consul.auto_advertise:
        from ..consul import ConsulAPI, ConsulService, ConsulSyncer

        consul_api = ConsulAPI(cfg.consul.address)
        agent_syncer = ConsulSyncer(consul_api, address=cfg.consul.address,
                                    instance=node_name)
        services = []
        if server is not None:
            services.append(ConsulService(
                name=cfg.consul.server_service_name, tags=["http"],
                port=http.port, address=_advertise_addr(cfg)))
            # Advertise the gossip endpoint too, and bootstrap-join
            # through the catalog when we know no peers
            # (server.go:398 setupBootstrapHandler).
            serf_port = int(serf_addr.rsplit(":", 1)[1])
            services.append(ConsulService(
                name=cfg.consul.server_service_name, tags=["serf"],
                port=serf_port, address=_advertise_addr(cfg)))
            from ..consul import serf_bootstrap
            import threading as _threading

            _threading.Thread(
                target=serf_bootstrap,
                args=(server, consul_api, cfg.consul.server_service_name),
                kwargs={"interval": 3.0 if cfg.dev_mode else 15.0,
                        "self_addr": f"{_advertise_addr(cfg)}:{serf_port}"},
                daemon=True, name="consul-serf-bootstrap",
            ).start()
        if client_agent is not None:
            services.append(ConsulService(
                name=cfg.consul.client_service_name, tags=["http"],
                port=http.port, address=_advertise_addr(cfg)))
        agent_syncer.set_services("agent", services)
        agent_syncer.start()

    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        print("\n==> Caught interrupt, shutting down...")
        if client_agent is not None:
            client_agent.shutdown(destroy_allocs=cfg.dev_mode)
        if agent_syncer is not None:
            agent_syncer.shutdown()
        if http is not None:
            http.stop()
        if server is not None:
            server.shutdown()
        if raft_transport is not None:
            raft_transport.close()
    return 0


# ---------------------------------------------------------------- main


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="nomad-tpu", description="TPU-native cluster scheduler"
    )
    parser.add_argument("--address", default=None, help="agent HTTP address")
    parser.add_argument("--region", default=None,
                        help="target region (forwarded by the agent)")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("agent", help="run an agent")
    p.add_argument("-dev", dest="dev", action="store_true")
    p.add_argument("-config", dest="config", action="append", default=[],
                   help="config file or directory (repeatable; merged in order)")
    p.add_argument("-statsd", dest="statsd", default="", help="statsd UDP addr host:port")
    p.add_argument("-bind", dest="bind", default="")
    p.add_argument("-port", dest="port", type=int, default=0)
    p.add_argument("-serf-port", dest="serf_port", type=int, default=0)
    p.add_argument("-num-schedulers", dest="num_schedulers", type=int,
                   default=None)
    p.add_argument("-region", dest="region", default="")
    p.add_argument("-node-name", dest="node_name", default="",
                   help="unique agent name (default hostname-port)")
    p.add_argument("-join", dest="join", default="",
                   help="comma-separated gossip addrs to join at start")
    p.add_argument("-tpu", dest="tpu", action="store_true",
                   help="route service/batch evals to the TPU backend")
    p.add_argument("-migrate-max-parallel", dest="migrate_max_parallel",
                   type=int, default=None,
                   help="in-flight migration budget for drain storms "
                        "(0 = unbounded)")
    p.add_argument("-preemption", dest="preemption", action="store_true",
                   help="allow priority preemption on a full cluster")
    p.add_argument("-defrag", dest="defrag", action="store_true",
                   help="enable the leader-side continuous "
                        "defragmentation loop (nomad_tpu/defrag)")
    p.add_argument("-consul", dest="consul", default="",
                   help="consul agent addr for service sync + discovery")
    p.add_argument("-advertise", dest="advertise", default="",
                   help="address advertised to consul (default: bind addr)")
    p.add_argument("-log-level", dest="log_level", default="")
    p.set_defaults(fn=cmd_agent)

    p = sub.add_parser("version", help="print version")
    p.set_defaults(fn=cmd_version)

    p = sub.add_parser("init", help="create an example job file")
    p.set_defaults(fn=cmd_init)

    p = sub.add_parser("validate", help="validate a job file")
    p.add_argument("file")
    p.set_defaults(fn=cmd_validate)

    p = sub.add_parser("run", help="run a job")
    p.add_argument("file")
    p.add_argument("-detach", dest="detach", action="store_true")
    p.add_argument("-check-index", dest="check_index", type=int, default=None)
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("plan", help="dry-run a job update")
    p.add_argument("file")
    p.add_argument("-verbose", dest="verbose", action="store_true")
    p.set_defaults(fn=cmd_plan)

    p = sub.add_parser("status", help="display job status")
    p.add_argument("job", nargs="?")
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("stop", help="stop a job")
    p.add_argument("job")
    p.add_argument("-detach", dest="detach", action="store_true")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("inspect", help="dump a job's definition")
    p.add_argument("job")
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser("node-status", help="display node status")
    p.add_argument("node", nargs="?")
    p.set_defaults(fn=cmd_node_status)

    p = sub.add_parser("node-drain", help="toggle node drain mode")
    p.add_argument("node")
    p.add_argument("-enable", dest="enable", action="store_true")
    p.add_argument("-disable", dest="disable", action="store_true")
    p.set_defaults(fn=cmd_node_drain)

    p = sub.add_parser("alloc-status", help="display allocation status")
    p.add_argument("alloc")
    p.add_argument("-verbose", dest="verbose", action="store_true")
    p.set_defaults(fn=cmd_alloc_status)

    p = sub.add_parser("eval-status", help="display evaluation status")
    p.add_argument("eval")
    p.set_defaults(fn=cmd_eval_status)

    p = sub.add_parser("fs", help="browse an allocation's filesystem")
    p.add_argument("alloc")
    p.add_argument("path", nargs="?", default="/")
    p.add_argument("-stat", dest="stat", action="store_true")
    p.set_defaults(fn=cmd_fs)

    p = sub.add_parser("logs", help="stream a task's logs")
    p.add_argument("alloc")
    p.add_argument("task", nargs="?", default="")
    p.add_argument("-stderr", dest="stderr", action="store_true")
    p.add_argument("-f", dest="follow", action="store_true")
    p.add_argument("-tail", dest="tail", action="store_true")
    p.add_argument("-n", dest="n", type=int, default=0)
    p.set_defaults(fn=cmd_logs)

    p = sub.add_parser("server-members", help="display gossip pool members")
    p.set_defaults(fn=cmd_server_members)

    p = sub.add_parser("server-join", help="join the agent to a gossip pool")
    p.add_argument("addrs", nargs="+", help="gossip addresses host:port")
    p.set_defaults(fn=cmd_server_join)

    p = sub.add_parser("server-force-leave", help="force a member to leave")
    p.add_argument("node", help="member name")
    p.set_defaults(fn=cmd_server_force_leave)

    p = sub.add_parser("agent-info", help="display agent stats")
    p.set_defaults(fn=cmd_agent_info)

    return parser


def main(argv: Optional[List[str]] = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    try:
        return args.fn(args)
    except APIError as e:
        print(f"Error: {e}", file=sys.stderr)
        return 1
    except (OSError, ValueError) as e:
        # unreadable job files, parse errors, connection failures
        print(f"Error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
