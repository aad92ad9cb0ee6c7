"""Agent configuration: HCL/JSON files, directory merge, CLI overlay.

Reference: command/agent/config.go (Config struct, Merge semantics,
DefaultConfig/DevConfig) and config_parse.go (HCL decoding). A config
value resolves as: defaults < config files (in load order; a directory
loads its *.hcl/*.json sorted by name) < CLI flags. Merge is per-field:
later non-zero scalars win, maps union (later wins per key), lists
concatenate (retry_join) or replace (client.servers follows the
reference's "later file wins" for servers).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ..jobspec.hcl import parse_hcl


@dataclass
class ServerBlock:
    enabled: bool = False
    bootstrap_expect: int = 0
    num_schedulers: Optional[int] = None
    enabled_schedulers: List[str] = field(default_factory=list)
    node_gc_threshold: str = ""
    heartbeat_grace: str = ""
    retry_join: List[str] = field(default_factory=list)
    start_join: List[str] = field(default_factory=list)
    # Per-type factory overrides, e.g. { "service" = "service-tpu" } —
    # finer-grained than the all-or-nothing -tpu flag.
    scheduler_factories: Dict[str, str] = field(default_factory=dict)
    # Batch tuning (server/config.py): max dense-factory evals the
    # dispatch pipeline packs into one batch.
    eval_batch_size: Optional[int] = None
    # Central dispatch pipeline (server/config.py dispatch_*): batches
    # in flight.
    dispatch_max_inflight: Optional[int] = None
    # Placement kernel (nomad_tpu/kernels): the dense solve the *-tpu
    # factories run ("greedy" / "convex" / a plugin's); validated at
    # server init.
    placement_kernel: Optional[str] = None
    # Churn control (nomad_tpu/migrate; server/config.py): the
    # in-flight migration budget (drain max_parallel analog) and the
    # dense priority-preemption switch + threshold.
    migrate_max_parallel: Optional[int] = None
    preemption_enabled: Optional[bool] = None
    preempt_priority_threshold: Optional[int] = None
    # Continuous defragmentation (nomad_tpu/defrag; server/config.py):
    # the leader-side background optimizer loop — enable switch, round
    # interval, minimum net fragmentation gain, per-wave move cap.
    defrag_enabled: Optional[bool] = None
    defrag_interval: Optional[float] = None
    defrag_min_gain: Optional[float] = None
    defrag_max_moves_per_wave: Optional[int] = None
    # Overload protection (nomad_tpu/admission; server/config.py):
    # bounded broker ready queues, eval deadlines, the token-bucket
    # intake gate, and the device-path circuit breaker.
    eval_ready_cap: Optional[int] = None
    eval_deadline_ttl: Optional[float] = None
    admission_enabled: Optional[bool] = None
    breaker_enabled: Optional[bool] = None
    breaker_failure_threshold: Optional[int] = None
    breaker_cooldown: Optional[float] = None
    # Contention observatory (nomad_tpu/profile; server/config.py):
    # recording + GIL sampler switch, and the pressure-monitor
    # lock-wait p99 thresholds (ms; 0 disables).
    profile_enabled: Optional[bool] = None
    admission_lock_wait_yellow_ms: Optional[float] = None
    admission_lock_wait_red_ms: Optional[float] = None


@dataclass
class ClientBlock:
    enabled: bool = False
    state_dir: str = ""
    alloc_dir: str = ""
    servers: List[str] = field(default_factory=list)
    node_class: str = ""
    options: Dict[str, str] = field(default_factory=dict)
    meta: Dict[str, str] = field(default_factory=dict)
    network_speed: int = 0
    reserved: Dict[str, Any] = field(default_factory=dict)
    # Operator chroot embed map for the exec driver (reference
    # client-config chroot_env); empty = built-in defaults. Job specs
    # cannot set this — the driver rejects chroot_env in task config.
    chroot_env: Dict[str, str] = field(default_factory=dict)


@dataclass
class TelemetryBlock:
    statsite_address: str = ""
    statsd_address: str = ""
    disable_hostname: bool = False
    collection_interval: str = "1s"
    circonus_submission_url: str = ""


@dataclass
class Ports:
    http: int = 4646
    rpc: int = 4647
    serf: int = 4648


@dataclass
class ConsulBlock:
    address: str = ""
    server_service_name: str = "nomad"
    client_service_name: str = "nomad-client"
    auto_advertise: bool = True


@dataclass
class VaultBlock:
    enabled: bool = False
    address: str = ""
    token: str = ""


@dataclass
class TLSBlock:
    """Reference: config.go TLSConfig / nomad/structs/config/tls.go —
    one CA + node cert/key pair covers both wire protocols (the raft
    transport terminates mTLS, the HTTP API terminates server TLS)."""

    enabled: bool = False
    ca_file: str = ""
    cert_file: str = ""
    key_file: str = ""
    # Reference's EnableRPC/EnableHTTP split: either channel can stay
    # plaintext during a rolling TLS rollout.
    rpc: bool = True
    http: bool = True


@dataclass
class AgentConfig:
    region: str = "global"
    datacenter: str = "dc1"
    name: str = ""
    data_dir: str = ""
    log_level: str = "INFO"
    bind_addr: str = "127.0.0.1"
    advertise_addr: str = ""
    enable_debug: bool = False
    dev_mode: bool = False
    ports: Ports = field(default_factory=Ports)
    server: ServerBlock = field(default_factory=ServerBlock)
    client: ClientBlock = field(default_factory=ClientBlock)
    telemetry: TelemetryBlock = field(default_factory=TelemetryBlock)
    consul: ConsulBlock = field(default_factory=ConsulBlock)
    vault: VaultBlock = field(default_factory=VaultBlock)
    tls: TLSBlock = field(default_factory=TLSBlock)
    # Dotted paths explicitly assigned (by a config file, dev preset, or
    # flag). Merge copies exactly these from the override — so a file
    # CAN set a field back to its default ("explicitly set to the
    # default" is not the same as "unset").
    set_keys: set = field(default_factory=set)

    def assign(self, dotted: str, value: Any) -> None:
        obj = self
        parts = dotted.split(".")
        for part in parts[:-1]:
            obj = getattr(obj, part)
        setattr(obj, parts[-1], value)
        self.set_keys.add(dotted)


def default_config() -> AgentConfig:
    """DefaultConfig (config.go): neither server nor client enabled."""
    return AgentConfig()


def dev_config() -> AgentConfig:
    """DevConfig (config.go): combined server+client, permissive client
    options, in-memory everything."""
    cfg = AgentConfig()
    cfg.assign("dev_mode", True)
    cfg.assign("server.enabled", True)
    cfg.assign("server.num_schedulers", 2)
    cfg.assign("client.enabled", True)
    cfg.client.options["driver.raw_exec.enable"] = "1"
    cfg.set_keys.add("client.options")
    return cfg


# ---------------------------------------------------------------- parse


def _expect_block(raw: Any, what: str) -> Dict[str, Any]:
    """HCL repeated blocks arrive as lists; config blocks must be
    single (config_parse.go errors on duplicates too)."""
    if isinstance(raw, list):
        raise ValueError(f"duplicate {what!r} block")
    if not isinstance(raw, dict):
        raise ValueError(f"{what!r} must be a block")
    return raw


def _str_map(raw: Any, what: str) -> Dict[str, str]:
    if not isinstance(raw, dict):
        raise ValueError(f"{what!r} must be a block of key = value")
    return {str(k): str(v) for k, v in raw.items()}


def _str_list(raw: Any) -> List[str]:
    if isinstance(raw, str):
        return [raw]
    return [str(v) for v in raw or []]


# (dotted key -> caster); the cast doubles as light validation.
_SCHEMA: Dict[str, Any] = {
    "region": str, "datacenter": str, "name": str, "data_dir": str,
    "log_level": str, "bind_addr": str, "advertise_addr": str,
    "enable_debug": bool,
    "ports.http": int, "ports.rpc": int, "ports.serf": int,
    "server.enabled": bool, "server.bootstrap_expect": int,
    "server.num_schedulers": int, "server.enabled_schedulers": _str_list,
    "server.node_gc_threshold": str, "server.heartbeat_grace": str,
    "server.retry_join": _str_list, "server.start_join": _str_list,
    "server.eval_batch_size": int,
    "server.dispatch_max_inflight": int,
    "server.placement_kernel": str,
    "server.migrate_max_parallel": int,
    "server.preemption_enabled": bool,
    "server.preempt_priority_threshold": int,
    "server.defrag_enabled": bool,
    "server.defrag_interval": float,
    "server.defrag_min_gain": float,
    "server.defrag_max_moves_per_wave": int,
    "server.eval_ready_cap": int, "server.eval_deadline_ttl": float,
    "server.admission_enabled": bool, "server.breaker_enabled": bool,
    "server.breaker_failure_threshold": int,
    "server.breaker_cooldown": float,
    "server.profile_enabled": bool,
    "server.admission_lock_wait_yellow_ms": float,
    "server.admission_lock_wait_red_ms": float,
    "client.enabled": bool, "client.state_dir": str,
    "client.alloc_dir": str, "client.node_class": str,
    "client.servers": _str_list, "client.network_speed": int,
    "telemetry.statsite_address": str, "telemetry.statsd_address": str,
    "telemetry.collection_interval": str, "telemetry.disable_hostname": bool,
    "telemetry.circonus_submission_url": str,
    "consul.address": str, "consul.server_service_name": str,
    "consul.client_service_name": str, "consul.auto_advertise": bool,
    "vault.enabled": bool, "vault.address": str, "vault.token": str,
    "tls.enabled": bool, "tls.ca_file": str, "tls.cert_file": str,
    "tls.key_file": str, "tls.rpc": bool, "tls.http": bool,
}
_MAP_KEYS = {"client.options", "client.meta", "client.reserved",
             "client.chroot_env", "server.scheduler_factories"}
_BLOCKS = {"ports", "server", "client", "telemetry", "consul", "vault",
           "tls"}


def config_from_dict(data: Dict[str, Any]) -> AgentConfig:
    cfg = AgentConfig()
    for key, raw in data.items():
        if key in _BLOCKS:
            block = _expect_block(raw, key)
            for sub, val in block.items():
                dotted = f"{key}.{sub}"
                if dotted in _MAP_KEYS:
                    if dotted == "client.reserved":
                        cfg.assign(dotted, _expect_block(val, dotted))
                    else:
                        cfg.assign(dotted, _str_map(val, dotted))
                elif dotted in _SCHEMA:
                    cfg.assign(dotted, _SCHEMA[dotted](val))
                else:
                    raise ValueError(f"unknown config keys: {dotted}")
        elif key in _SCHEMA:
            cfg.assign(key, _SCHEMA[key](raw))
        else:
            raise ValueError(f"unknown config keys: {key}")
    return cfg


def parse_config_file(path: str) -> AgentConfig:
    """One file: .json parses as JSON, anything else as HCL
    (config_parse.go sniffs the same way)."""
    with open(path) as f:
        src = f.read()
    if path.endswith(".json"):
        data = json.loads(src)
    else:
        data = parse_hcl(src)
    try:
        return config_from_dict(data)
    except ValueError as e:
        raise ValueError(f"{path}: {e}") from None


def load_config(path: str) -> AgentConfig:
    """A file loads directly; a directory loads every *.hcl/*.json in
    lexical order and merges them (config.go LoadConfigDir)."""
    if os.path.isdir(path):
        cfg = AgentConfig()
        found = False
        for name in sorted(os.listdir(path)):
            if not (name.endswith(".hcl") or name.endswith(".json")):
                continue
            cfg = merge_config(cfg, parse_config_file(os.path.join(path, name)))
            found = True
        if not found:
            raise ValueError(f"no .hcl or .json config files in {path}")
        return cfg
    return parse_config_file(path)


def load_configs(paths: List[str]) -> AgentConfig:
    """Merge defaults with every -config path in order."""
    cfg = default_config()
    for path in paths:
        cfg = merge_config(cfg, load_config(path))
    return cfg


def parse_duration(text: str) -> float:
    """Go-style duration to seconds: "30s", "10m", "1h30m", "250ms",
    bare numbers are seconds."""
    text = text.strip()
    if not text:
        raise ValueError("empty duration")
    try:
        return float(text)
    except ValueError:
        pass
    units = {"ms": 0.001, "s": 1.0, "m": 60.0, "h": 3600.0}
    total = 0.0
    num = ""
    i = 0
    while i < len(text):
        c = text[i]
        if c.isdigit() or c == ".":
            num += c
            i += 1
            continue
        unit = text[i:i + 2] if text[i:i + 2] == "ms" else c
        if unit not in units or not num:
            raise ValueError(f"bad duration {text!r}")
        total += float(num) * units[unit]
        num = ""
        i += len(unit)
    if num:
        raise ValueError(f"bad duration {text!r}")
    return total


# ---------------------------------------------------------------- merge


def merge_config(a: AgentConfig, b: AgentConfig) -> AgentConfig:
    """a < b; returns a new config. Exactly b's explicitly-set keys are
    copied over (maps union, with b winning per entry), so "set back to
    the default" works and unset fields never clobber."""
    import copy

    out = copy.deepcopy(a)
    for dotted in sorted(b.set_keys):
        obj = b
        dst = out
        parts = dotted.split(".")
        for part in parts[:-1]:
            obj = getattr(obj, part)
            dst = getattr(dst, part)
        val = copy.deepcopy(getattr(obj, parts[-1]))
        if isinstance(val, dict):
            getattr(dst, parts[-1]).update(val)
        elif dotted in ("server.retry_join", "server.start_join"):
            # Join seed lists accumulate across files (config.go Merge
            # appends); other lists follow later-file-wins.
            merged = getattr(dst, parts[-1]) + val
            setattr(dst, parts[-1], list(dict.fromkeys(merged)))
        else:
            setattr(dst, parts[-1], val)
        out.set_keys.add(dotted)
    return out
