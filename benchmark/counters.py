"""The program's counters, read from outside over `/v1/agent/self` and
`/v1/metrics` (a copy of `chip_smoke.read_counters` on the benchmark's own
HTTP client), flattened to `<group>.<key>`, and the check that the device
did the work."""

from __future__ import annotations

# Every countable route off the device that is a failure, not a design:
# each has to read 0. `pipeline.routed_host` and
# `scheduler.small_route_host` are by design and are metrics instead.
FAILURE_ROUTES = (
    "scheduler.host_fallback", "scheduler.gang_host_fallback",
    "scheduler.breaker_rejected", "scheduler.gang_breaker_rejected",
    "executive.host_fallbacks", "pipeline.breaker_routed",
    "pipeline.prefetch_failures", "batcher.unsharded_fallbacks",
    "broker.dead_lettered", "broker.nack_timeouts",
    "breaker.trips", "breaker.failures", "breaker.rejected")

PICK = {
    "batcher": ("dispatches", "batched_requests", "compact_dispatches",
                "base_uploads", "base_delta_updates", "sharded_bases",
                "unsharded_fallbacks", "jit_cache_size"),
    "pipeline": ("batches", "largest_batch", "routed_host", "breaker_routed",
                 "prefetch_failures", "plan_conflicts", "nacked"),
    "executive": ("routed_host", "host_fallbacks"),
    "broker": ("dead_lettered", "shed", "expired", "nacked", "nack_timeouts"),
    "breaker": ("trips", "failures", "rejected"),
    "plan_applier": ("commits", "plans_committed"),
}
PROM = ("host_fallback", "gang_host_fallback", "breaker_rejected",
        "gang_breaker_rejected", "small_route_host",
        "small_route_host_evals")


def prom_counter(text: str, suffix: str) -> float:
    """Value of the counter whose family name ends in `suffix`; one that
    nobody incremented is absent from the exposition, so 0."""
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.partition(" ")
        if name.endswith(suffix):
            return float(value)
    return 0.0


def read_counters(conn) -> dict:
    info, _ = conn.request("GET", "/v1/agent/self")
    prom, _ = conn.request_raw("GET", "/v1/metrics")
    stats = info["stats"]
    blocks = {
        "batcher": info.get("placement_batcher") or {},
        "pipeline": stats["dispatch_pipeline"],
        "executive": stats["scheduler_executive"],
        "broker": stats["broker"],
        "breaker": stats["admission"]["breaker"],
        "plan_applier": stats.get("plan_applier") or {},
    }
    flat = {}
    for group, keys in PICK.items():
        for key in keys:
            if key in blocks[group]:
                flat[f"{group}.{key}"] = blocks[group][key]
    flat["breaker.state"] = blocks["breaker"].get("state")
    text = prom.decode()
    for name in PROM:
        flat[f"scheduler.{name}"] = int(
            prom_counter(text, f"_scheduler_{name}_total"))
    return flat


def device_did_the_work(before: dict, after: dict) -> list:
    """[(name, value, limit, ok)]: requests reached the device inside the
    window, and no failure route off it was ever taken."""
    rose = after.get("batcher.batched_requests", 0) \
        - before.get("batcher.batched_requests", 0)
    rows = [("device_requests_in_window", rose, ">0", rose > 0)]
    for name in FAILURE_ROUTES:
        value = after.get(name, 0)
        rows.append((name, value, "0", value == 0))
    state = after.get("breaker.state")
    rows.append(("breaker.state", state, "closed", state == "closed"))
    return rows
