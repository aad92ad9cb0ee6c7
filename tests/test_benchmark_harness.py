"""The benchmark's racks and gangs against a live dev server.

A fleet of two racks stated by rule (`benchmark/fleet.py` `load_fleet`),
one job shape made by `fleet.job_template` with each kind of gang stanza
and with none, registered over HTTP as the generator registers it, and
the store's dump (`benchmark/store_dump.py` `dump_store`) as a
deployment's check reads it. Tier-1 does not run `benchmark/tests/`
(its `test_harness_gang.py` holds the same four cases), so this keeps
the program's gang stanza, node meta and task-group names and the
harness's reading of them together."""

import importlib.util
import json
import os
import time

import pytest

from nomad_tpu.api.http import HTTPServer
from nomad_tpu.server import Server, ServerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    """A module of benchmark/ under a name of its own (the directory is
    not a package and its module names are common ones)."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name}", os.path.join(REPO, "benchmark", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


NODE = {"node_class": "rack-node",
        "attributes": {"kernel.name": "linux", "driver.exec": "1"},
        "meta": {"fabric": "rdma"},
        "cpu": 4000, "memory_mb": 8192, "disk_mb": 102400, "iops": 150,
        "device": "eth0", "cidr": "192.168.0.100/32", "ip": "192.168.0.100",
        "mbits": 1000,
        "reserved": {"cpu": 100, "memory_mb": 256, "disk_mb": 4096,
                     "iops": 0, "mbits": 1, "ports": [22]}}
CONFIG = {"fleet": {"datacenter": "dc1", "classes": [
    {"count": 20, "node": NODE, "filler": {"per_node": 0},
     "topology": {"rack": {"nodes_per_group": 10, "prefix": "r"}}}]}}
SHAPE = {"name": "train", "share": 1.0, "type": "service", "priority": 50,
         "datacenters": ["dc1"], "constraints": [], "group": "train",
         "count": 4, "distinct_hosts": True, "ephemeral_disk_mb": 150,
         "task": {"name": "worker", "driver": "exec", "cpu": 500,
                  "memory_mb": 256, "mbits": 0, "dynamic_ports": []}}


@pytest.mark.parametrize("gang", [{"slice": "rack"}, {"affinity": "rack"},
                                  {"spread": "rack"}, None])
def test_a_gang_shape_on_racks_by_rule(gang):
    fleet, httpc, store_dump = (_load(name) for name in (
        "fleet", "httpc", "store_dump"))
    shape = dict(SHAPE) if gang is None else dict(SHAPE, gang=gang)
    template = fleet.job_template(shape)
    want = None if gang is None else {
        "slice": "", "affinity": "", "spread": "", **gang}
    assert template["task_groups"][0]["gang"] == want

    server = Server(ServerConfig(
        num_schedulers=2,
        scheduler_factories={"service": "service-tpu", "batch": "batch-tpu",
                             "system": "system-tpu"}))
    server.start()
    http = HTTPServer(server, host="127.0.0.1", port=0)
    http.start()
    conn = httpc.Conn(http.addr)
    try:
        assert fleet.load_fleet(server, CONFIG, 2**31 + 3) == {
            "nodes": 20, "filler_allocs": 0}
        body = json.dumps({"job": dict(template, id="g1", name="g1")})
        out, _ = conn.request("PUT", "/v1/jobs", body.encode())
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            ev, _ = conn.request("GET", f"/v1/evaluation/{out['eval_id']}")
            if ev["status"] in ("complete", "failed", "cancelled"):
                break
            time.sleep(0.05)
        assert ev["status"] == "complete", ev

        # the job the server stores has the stanza the shape stated
        stored = server.fsm.state.job_by_id("g1").task_groups[0].gang
        if gang is None:
            assert stored is None
        else:
            assert (stored.slice, stored.affinity, stored.spread) == (
                want["slice"], want["affinity"], want["spread"])

        store = store_dump.dump_store(server.fsm.state.snapshot())
        assert sorted(store["node_meta"]["rack"]) == ["r0"] * 10 + ["r1"] * 10
        assert store["node_meta"]["ici"] == [""] * 20
        # the lists a check reads line up with `alloc_ids`
        assert len(store["alloc_ids"]) == 4
        by_id = {a.id: a for a in server.fsm.state.allocs()}
        assert store["alloc_group"] == [by_id[i].task_group
                                        for i in store["alloc_ids"]]
        assert store["alloc_name"] == [by_id[i].name
                                       for i in store["alloc_ids"]]
        assert store["alloc_group"] == ["train"] * 4
        assert sorted(store["alloc_name"]) == [
            f"g1.train[{k}]" for k in range(4)]
        assert store["gone_group"] == [] and store["gone_ids"] == []
        racks = [store["node_meta"]["rack"][row]
                 for row in store["alloc_node"]]
        if gang == {"slice": "rack"}:
            assert len(set(racks)) == 1
        if gang == {"spread": "rack"}:
            assert sorted(racks) == ["r0", "r0", "r1", "r1"]
    finally:
        conn.close()
        http.stop()
        server.shutdown()
