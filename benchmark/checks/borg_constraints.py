"""The guarantee `borg-attrs-12k` states beyond the five comparisons,
held on the final store: an allocation lies only on a machine whose
attributes, meta and class meet every constraint of its job. Imports
nothing of the program (only `re`, for the `regexp` operand); every
count is held to 0.

A machine's attribute class is read from its rack's name
(`node_meta["rack"]`): a class of the configuration states `topology.
rack.prefix`, and its machines stand in racks `<prefix><number>`. What
the class says of its machines (`attributes`, `meta`, `node_class`, the
fleet's `datacenter`) is what a constraint's `${attr.*}`, `${meta.*}`,
`${node.class}` and `${node.datacenter}` resolve to; anything else that
starts with `${` resolves to nothing and the constraint fails, and a
target that does not is a literal. A job's constraints are its shape's
(`window_jobs[job]["template"]` names the shape in the configuration's
`jobs`). The operands are the reference scheduler's (feasible.go
checkConstraint): `=`, `==`, `is`; `!=`, `not`; `<`, `<=`, `>`, `>=` on
strings, by code point; `version` (the left side a dotted version, the
right a comma-separated list of `<op> <version>`, `~>` the pessimistic
operator); `regexp` (a search); `distinct_hosts` is not this check's.

- `allocs_on_infeasible_machines`: live allocations of the window's jobs
  on a machine that fails one of their job's constraints.
- `machines_without_a_class`: live allocations of the window's jobs on a
  machine whose rack names no class of the configuration (or on no known
  machine).
- `no_constrained_job_placed`: 1 where no job of the window whose shape
  states more than the one constraint every machine meets is live with
  all its allocations: the cell exists to run the feasibility mask.
"""

import re


def _version(text):
    """(segments padded to three, prerelease) or None."""
    m = re.match(r"^v?(\d+(?:\.\d+)*)(?:-([0-9A-Za-z.\-~]+))?"
                 r"(?:\+[0-9A-Za-z.\-~]+)?$", text.strip())
    if not m:
        return None
    segments = [int(p) for p in m.group(1).split(".")]
    return tuple(segments + [0] * (3 - len(segments))), m.group(2) or ""


def _version_order(a, b) -> int:
    """-1, 0 or 1. A prerelease sorts before its release; two
    prereleases by their dotted parts, numbers as numbers and before
    words."""
    if a[0] != b[0]:
        return -1 if a[0] < b[0] else 1
    if a[1] == b[1]:
        return 0
    if not a[1] or not b[1]:
        return 1 if not a[1] else -1

    def key(part):
        return (0, int(part), "") if part.isdigit() else (1, 0, part)

    ka = [key(p) for p in a[1].split(".")]
    kb = [key(p) for p in b[1].split(".")]
    return -1 if ka < kb else 1


def version_meets(have: str, wanted: str) -> bool:
    version = _version(have)
    if version is None:
        return False
    parts = [part for part in wanted.split(",") if part.strip()]
    for part in parts:
        m = re.match(r"^\s*(=|!=|>=|<=|>|<|~>)?\s*(\S+)\s*$", part)
        if not m:
            return False
        op, bound = m.group(1) or "=", _version(m.group(2))
        if bound is None:
            return False
        order = _version_order(version, bound)
        if op == "~>":
            # at least the bound, and equal to it in every segment
            # the bound wrote out but the last
            stated = len(m.group(2).split("-")[0].lstrip("v").split("."))
            keep = max(stated - 1, 1)
            ok = order >= 0 and version[0][:keep] == bound[0][:keep]
        else:
            ok = {"=": order == 0, "!=": order != 0, ">": order > 0,
                  ">=": order >= 0, "<": order < 0, "<=": order <= 0}[op]
        if not ok:
            return False
    return bool(parts)


def operand_holds(operand: str, left, right) -> bool:
    if operand == "distinct_hosts":
        return True
    if operand in ("=", "==", "is"):
        return left == right
    if operand in ("!=", "not"):
        return left != right
    if not isinstance(left, str) or not isinstance(right, str):
        return False
    if operand in ("<", "<=", ">", ">="):
        return {"<": left < right, "<=": left <= right,
                ">": left > right, ">=": left >= right}[operand]
    if operand == "version":
        return version_meets(left, right)
    if operand == "regexp":
        try:
            return re.search(right, left) is not None
        except re.error:
            return False
    return False


def resolve(target: str, machine: dict):
    """(value, found) of one side of a constraint on a machine of the
    class `machine` (`node` of a fleet class, plus `datacenter`)."""
    if not target.startswith("${"):
        return target, True
    if target == "${node.class}":
        return machine["node_class"], True
    if target == "${node.datacenter}":
        return machine["datacenter"], True
    for prefix, table in (("${attr.", "attributes"), ("${meta.", "meta")):
        if target.startswith(prefix):
            key = target[len(prefix):-1]
            if key in machine[table]:
                return machine[table][key], True
            return None, False
    return None, False


def meets(constraints: list, machine: dict) -> bool:
    for c in constraints:
        left, found = resolve(c["ltarget"], machine)
        if not found:
            return False
        right, found = resolve(c["rtarget"], machine)
        if not found:
            return False
        if not operand_holds(c["operand"], left, right):
            return False
    return True


def _is_pinned(constraints: list) -> bool:
    """More than `${attr.kernel.name} = linux`, which every machine
    meets."""
    return any((c["ltarget"], c["operand"], c["rtarget"])
               != ("${attr.kernel.name}", "=", "linux")
               for c in constraints)


def check(store, window_jobs, config):
    datacenter = config["fleet"]["datacenter"]
    by_prefix = {
        cls["topology"]["rack"]["prefix"]: dict(cls["node"],
                                                datacenter=datacenter)
        for cls in config["fleet"]["classes"]}
    shapes = {spec["name"]: spec["constraints"] for spec in config["jobs"]}
    racks = store["node_meta"]["rack"]

    def machine_of(node: int):
        if node < 0:
            return None
        # "<prefix><number>": the prefix is the name less its digits
        return by_prefix.get(racks[node].rstrip("0123456789"))

    verdicts: dict = {}      # (shape, rack prefix) -> bool
    infeasible = bare = 0
    live: dict = {}          # job id -> live allocations
    for job_row, node in zip(store["alloc_job"], store["alloc_node"]):
        job_id = store["job_ids"][int(job_row)]
        spec = window_jobs.get(job_id)
        if spec is None:
            continue
        live[job_id] = live.get(job_id, 0) + 1
        machine = machine_of(int(node))
        if machine is None:
            bare += 1
            continue
        key = (spec["template"], racks[int(node)])
        if key not in verdicts:
            verdicts[key] = meets(shapes[spec["template"]], machine)
        infeasible += not verdicts[key]
    pinned_whole = sum(
        1 for job_id, n in live.items()
        if n >= window_jobs[job_id]["count"]
        and _is_pinned(shapes[window_jobs[job_id]["template"]]))
    return {"allocs_on_infeasible_machines": infeasible,
            "machines_without_a_class": bare,
            "no_constrained_job_placed": int(pinned_whole == 0)}
