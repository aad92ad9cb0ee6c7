"""Agent config tests: HCL/JSON parsing, directory merge, flag overlay,
duration parsing (mirror command/agent/config_parse_test.go and
config_test.go TestConfig_Merge)."""

import argparse
import json

import pytest

from nomad_tpu.cli.agent_config import (
    AgentConfig,
    config_from_dict,
    default_config,
    dev_config,
    load_config,
    load_configs,
    merge_config,
    parse_config_file,
    parse_duration,
)
from nomad_tpu.cli.main import _resolve_agent_config

HCL = """
region     = "eu"
datacenter = "dc7"
name       = "agent-1"
data_dir   = "/var/nomad"
log_level  = "DEBUG"
bind_addr  = "0.0.0.0"

ports {
  http = 5646
}

server {
  enabled            = true
  bootstrap_expect   = 3
  num_schedulers     = 4
  enabled_schedulers = ["service", "batch"]
  heartbeat_grace    = "30s"
  retry_join         = ["10.0.0.1:4648", "10.0.0.2:4648"]
}

client {
  enabled    = true
  state_dir  = "/var/nomad/client"
  node_class = "linux-64bit"
  servers    = ["10.0.0.1:4646"]

  options {
    "driver.raw_exec.enable" = "1"
  }

  meta {
    rack = "r1"
  }
}

telemetry {
  statsd_address      = "127.0.0.1:8125"
  statsite_address    = "127.0.0.1:8126"
  disable_hostname    = true
  collection_interval = "5s"
}

consul {
  address = "127.0.0.1:8500"
}

vault {
  enabled = true
  address = "https://vault:8200"
}
"""


def test_parse_hcl_config(tmp_path):
    path = tmp_path / "agent.hcl"
    path.write_text(HCL)
    cfg = parse_config_file(str(path))
    assert cfg.region == "eu"
    assert cfg.datacenter == "dc7"
    assert cfg.name == "agent-1"
    assert cfg.bind_addr == "0.0.0.0"
    assert cfg.ports.http == 5646
    assert cfg.server.enabled and cfg.server.bootstrap_expect == 3
    assert cfg.server.num_schedulers == 4
    assert cfg.server.enabled_schedulers == ["service", "batch"]
    assert cfg.server.heartbeat_grace == "30s"
    assert cfg.server.retry_join == ["10.0.0.1:4648", "10.0.0.2:4648"]
    assert cfg.client.enabled
    assert cfg.client.options["driver.raw_exec.enable"] == "1"
    assert cfg.client.meta["rack"] == "r1"
    assert cfg.client.servers == ["10.0.0.1:4646"]
    assert cfg.telemetry.statsd_address == "127.0.0.1:8125"
    assert cfg.telemetry.statsite_address == "127.0.0.1:8126"
    assert cfg.telemetry.disable_hostname is True
    assert cfg.consul.address == "127.0.0.1:8500"
    assert cfg.vault.enabled and cfg.vault.address == "https://vault:8200"


def test_parse_json_config(tmp_path):
    path = tmp_path / "agent.json"
    path.write_text(json.dumps({
        "region": "ap",
        "server": {"enabled": True, "num_schedulers": 8},
    }))
    cfg = parse_config_file(str(path))
    assert cfg.region == "ap"
    assert cfg.server.num_schedulers == 8


def test_unknown_key_rejected(tmp_path):
    path = tmp_path / "bad.hcl"
    path.write_text('regoin = "typo"\n')
    with pytest.raises(ValueError, match="unknown config keys: regoin"):
        parse_config_file(str(path))


def test_config_dir_merge_lexical_order(tmp_path):
    (tmp_path / "10-base.hcl").write_text('region = "eu"\nserver { enabled = true }\n')
    (tmp_path / "20-override.hcl").write_text('region = "us"\n')
    (tmp_path / "ignored.txt").write_text("not config")
    cfg = load_config(str(tmp_path))
    assert cfg.region == "us"  # later file wins
    assert cfg.server.enabled  # earlier file's block preserved


def test_config_dir_empty_rejected(tmp_path):
    with pytest.raises(ValueError, match="no .hcl or .json"):
        load_config(str(tmp_path))


def test_merge_semantics():
    a = config_from_dict({"region": "eu",
                          "client": {"enabled": True,
                                     "meta": {"a": "1", "b": "1"}}})
    b = config_from_dict({"datacenter": "dc9",
                          "client": {"meta": {"b": "2", "c": "3"}}})
    out = merge_config(a, b)
    assert out.region == "eu"  # untouched by b (zero value there)
    assert out.datacenter == "dc9"
    assert out.client.enabled  # bool true survives merge
    assert out.client.meta == {"a": "1", "b": "2", "c": "3"}  # map union


def test_merge_can_set_back_to_default(tmp_path):
    """A later file explicitly setting a field to its default value must
    win over an earlier non-default (set != unset)."""
    (tmp_path / "10-base.hcl").write_text('bind_addr = "0.0.0.0"\n')
    (tmp_path / "20-local.hcl").write_text('bind_addr = "127.0.0.1"\n')
    cfg = load_config(str(tmp_path))
    assert cfg.bind_addr == "127.0.0.1"


def test_merge_join_lists_accumulate(tmp_path):
    """retry_join/start_join seed lists concatenate across files
    (config.go Merge appends); other lists follow later-file-wins."""
    (tmp_path / "10-a.hcl").write_text(
        'server { retry_join = ["10.0.0.1:4648"] '
        'enabled_schedulers = ["service"] }\n')
    (tmp_path / "20-b.hcl").write_text(
        'server { retry_join = ["10.0.0.2:4648"] '
        'enabled_schedulers = ["batch"] }\n')
    cfg = load_config(str(tmp_path))
    assert cfg.server.retry_join == ["10.0.0.1:4648", "10.0.0.2:4648"]
    assert cfg.server.enabled_schedulers == ["batch"]


def test_load_configs_order(tmp_path):
    p1 = tmp_path / "a.hcl"
    p2 = tmp_path / "b.hcl"
    p1.write_text('region = "eu"\nports { http = 1111 }\n')
    p2.write_text('ports { http = 2222 }\n')
    cfg = load_configs([str(p1), str(p2)])
    assert cfg.region == "eu"
    assert cfg.ports.http == 2222


def test_dev_config_enables_both():
    cfg = dev_config()
    assert cfg.dev_mode and cfg.server.enabled and cfg.client.enabled
    assert cfg.client.options["driver.raw_exec.enable"] == "1"
    base = default_config()
    assert not base.server.enabled and not base.client.enabled


def fake_args(**kw):
    defaults = dict(dev=False, config=[], bind="", port=0, region="",
                    node_name="", num_schedulers=None, statsd="", consul="",
                    advertise="", join="", log_level="", tpu=False)
    defaults.update(kw)
    return argparse.Namespace(**defaults)


def test_flag_overlay_beats_config_file(tmp_path):
    path = tmp_path / "agent.hcl"
    path.write_text('region = "eu"\nports { http = 5646 }\n'
                    'server { enabled = true  num_schedulers = 4 }\n')
    cfg = _resolve_agent_config(fake_args(
        config=[str(path)], region="us", port=7777, num_schedulers=1))
    assert cfg.region == "us"
    assert cfg.ports.http == 7777
    assert cfg.server.num_schedulers == 1
    assert cfg.server.enabled  # from the file


def test_dev_plus_config_overlay(tmp_path):
    path = tmp_path / "agent.hcl"
    path.write_text('telemetry { statsd_address = "127.0.0.1:9999" }\n')
    cfg = _resolve_agent_config(fake_args(dev=True, config=[str(path)]))
    assert cfg.dev_mode and cfg.server.enabled and cfg.client.enabled
    assert cfg.telemetry.statsd_address == "127.0.0.1:9999"


@pytest.mark.parametrize("text,seconds", [
    ("30s", 30.0),
    ("10m", 600.0),
    ("1h30m", 5400.0),
    ("250ms", 0.25),
    ("1.5s", 1.5),
    ("42", 42.0),
])
def test_parse_duration(text, seconds):
    assert parse_duration(text) == seconds


@pytest.mark.parametrize("text", ["", "abc", "10x", "s", "1h30"])
def test_parse_duration_rejects(text):
    with pytest.raises(ValueError):
        parse_duration(text)


def test_duplicate_block_rejected(tmp_path):
    path = tmp_path / "dup.hcl"
    path.write_text('server { enabled = true }\nserver { enabled = false }\n')
    with pytest.raises(ValueError, match="duplicate 'server' block"):
        parse_config_file(str(path))


def test_scheduler_factories_and_batching_knobs(tmp_path):
    """Operators tune the dense backend from HCL: per-type factory map
    plus drain-to-batch sizes (server/config.py knobs)."""
    p = tmp_path / "a.hcl"
    p.write_text('''
server {
  enabled = true
  scheduler_factories {
    service = "service-tpu"
    batch = "batch"
  }
  eval_batch_size = 32
  dispatch_max_inflight = 1
}
''')
    cfg = load_config(str(p))
    assert cfg.server.scheduler_factories == {
        "service": "service-tpu", "batch": "batch"}
    assert cfg.server.eval_batch_size == 32
    assert cfg.server.dispatch_max_inflight == 1

    # Later files override per entry (maps union, b wins).
    q = tmp_path / "b.hcl"
    q.write_text('server { scheduler_factories { batch = "batch-tpu" } }')
    from nomad_tpu.cli.agent_config import merge_config
    merged = merge_config(cfg, load_config(str(q)))
    assert merged.server.scheduler_factories == {
        "service": "service-tpu", "batch": "batch-tpu"}


@pytest.mark.parametrize("line", [
    "scheduler_executive = true",
    "executive_threads = 6",
    "dispatch_pipeline = false",
    "dense_min_batch = 2",
    "dense_pre_resolve = false",
    "device_resident = false",
    "resident_rebuild_rows = 512",
    "gil_sampler_interval = 0.01",
])
def test_removed_dense_driver_keys_are_refused(tmp_path, line):
    """There is one dense driver, it has no batch-size route to the
    host (PR 46) and its device path has one configuration (PR 49):
    the switches of the other two drivers, `dense_min_batch`, the
    in-batch and resident-base switches and the sampler's cadence are
    unknown keys like any typo, not silently accepted."""
    p = tmp_path / "a.hcl"
    p.write_text("server {\n  enabled = true\n  %s\n}\n" % line)
    key = "server." + line.split()[0]
    with pytest.raises(ValueError, match="unknown config keys: " + key):
        load_config(str(p))


def test_overload_protection_knobs(tmp_path):
    """Operators tune the overload-protection surfaces from HCL
    (nomad_tpu/admission; server/config.py): bounded broker queues,
    eval deadlines, the intake gate, and the device-path breaker."""
    p = tmp_path / "a.hcl"
    p.write_text('''
server {
  enabled = true
  eval_ready_cap = 512
  eval_deadline_ttl = 30.0
  admission_enabled = false
  breaker_enabled = true
  breaker_failure_threshold = 3
  breaker_cooldown = 2.5
}
''')
    cfg = load_config(str(p))
    assert cfg.server.eval_ready_cap == 512
    assert cfg.server.eval_deadline_ttl == 30.0
    assert cfg.server.admission_enabled is False
    assert cfg.server.breaker_enabled is True
    assert cfg.server.breaker_failure_threshold == 3
    assert cfg.server.breaker_cooldown == 2.5
    # Unset knobs stay None so merge/default semantics hold.
    assert default_config().server.eval_ready_cap is None
