"""Device-residency checker: the full-matrix re-ship must not creep
back.

The device-resident design (models/resident.py) exists because every
dense batch used to re-ship the whole ``[N, R]`` node matrix to the
device before placing. The fix keeps the matrix resident and scatters small row
deltas; the regression mode is silent: a ``jax.device_put`` (or a
``device_resident()`` upload) creeping into a steady-state dispatch or
scheduler path still *works*, it just ships 10-100x the bytes per
batch and nobody notices until the tail blows up again.

One rule:

- ``full-matrix-reship`` (``dispatch/``, ``scheduler/``, ``models/``):
  any host->device transfer call — ``jax.device_put`` /
  ``device_put`` / ``device_resident`` — outside the functions a
  module declares in its rebuild manifest::

      NTA_REBUILD_ENTRYPOINTS = ("PlacementBatcher._build_device_base",)

  The manifest names the ONE sanctioned full-upload path (the rebuild
  safety net + first-touch upload); everything else on the steady
  state must ride the delta/cached paths. Modules without a manifest
  allow NO transfer calls at all in the scoped dirs. Escape hatch, as
  everywhere: ``# nta: disable=full-matrix-reship`` with a reason.

``parallel/mesh.py``'s sharding helpers are deliberately out of scope:
they are infrastructure the manifest functions call, not a dispatch
path of their own. ``parallel/shard.py`` (the explicit shard_map
programs) and ``models/classes.py`` (the compression plane's
class-expansion helpers) ARE in scope, with a ZERO baseline: their
whole design is that no transfer lives there, and the scope keeps an
expansion helper from smuggling a ``device_put`` into the hot path.
"""

from __future__ import annotations

import ast
from typing import List

from .core import Finding, Module

RULE_RESHIP = "full-matrix-reship"

SCOPE_MARKERS = ("/dispatch/", "/scheduler/", "/models/", "/kernels/",
                 "/gang/", "/parallel/shard")

REBUILD_MANIFEST = "NTA_REBUILD_ENTRYPOINTS"
# Call names that move host arrays onto the device. `device_put`
# matches both `jax.device_put(...)` and a bare imported `device_put`;
# `device_resident` is ops/binpack.py's jitted-identity upload.
TRANSFER_ATTRS = {"device_put"}
TRANSFER_NAMES = {"device_put", "device_resident"}


def _in_scope(rel_path: str) -> bool:
    p = "/" + rel_path
    return any(m in p for m in SCOPE_MARKERS)


def manifest_entries(mod: Module) -> List[str]:
    """The module's declared rebuild manifest (public: the static-
    analysis suite's uniqueness gate walks every scoped module and
    asserts the union stays the ONE sanctioned full-upload path)."""
    return _rebuild_manifest(mod)


def _rebuild_manifest(mod: Module) -> List[str]:
    out: List[str] = []
    for node in mod.tree.body:
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name) and tgt.id == REBUILD_MANIFEST:
                if isinstance(node.value, (ast.Tuple, ast.List)):
                    for el in node.value.elts:
                        if isinstance(el, ast.Constant) and isinstance(
                                el.value, str):
                            out.append(el.value)
    return out


def _is_transfer_call(node: ast.Call) -> bool:
    func = node.func
    if isinstance(func, ast.Attribute):
        return func.attr in TRANSFER_ATTRS
    if isinstance(func, ast.Name):
        return func.id in TRANSFER_NAMES
    return False


def check(mod: Module) -> List[Finding]:
    if not _in_scope(mod.rel):
        return []
    allowed = set(_rebuild_manifest(mod))
    findings: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not _is_transfer_call(node):
            continue
        qual = mod.symbol_of(node)
        if qual in allowed:
            continue
        findings.append(Finding(
            RULE_RESHIP, mod.rel, node.lineno, node.col_offset,
            f"host->device transfer outside the rebuild manifest "
            f"({REBUILD_MANIFEST}) — steady-state dispatch/scheduler "
            f"paths must ride the delta/cached resident-base paths; a "
            f"full re-ship here regresses silently (10-100x bytes/"
            f"batch)", qual))
    return findings
