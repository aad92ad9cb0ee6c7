"""Compile-surface prover: the static side of the benchmark's
``window_compiles == 0``.

Every headline number since PR 6 assumes the jit cache is BOUNDED: a
fixed set of entry points, each compiled once per (shape bucket,
static key). That invariant was only enforced at runtime — a recompile
bug shipped silently until someone ran the right workload. These four
whole-program rules prove the bound statically, riding the
`core.Program` call graph (the same graph every manifest rule uses):

- ``unbucketed-shape`` — in functions reachable from a jit-feeding
  entry (any function that calls a jitted callable or a jit-program
  factory), an array whose dimension derives from a raw data-dependent
  int (``len(...)`` and arithmetic over it) may not ESCAPE toward the
  device path: assigned to an attribute, passed to a jitted call, fed
  to ``device_put``, or built through ``jnp`` directly. Every distinct
  raw shape is one more compiled program; sizes must route through a
  registered bucket function (``bucket_size`` over the ladder of
  ``*_BUCKETS``, anything returning one, or a hand-rolled sizer the
  module registers via a ``NTA_BUCKET_FNS`` manifest). Locally
  consumed host arrays (masks, tallies) stay quiet — a raw shape is
  only a compile key once it can reach the device.

- ``static-key-drift`` — call sites of jitted functions must pass
  STABLE static args: config objects, names, constants, bools. An
  ad-hoc per-eval key — an f-string, a ``str(...)``/``%``-format
  build, a computed number, a tuple holding computed elements — is
  one-compile-per-eval. ``build_placement_config`` (scheduler/tpu.py)
  is the sanctioned factory; opaque calls stay quiet so routing
  through it (or any constructor) is always clean. Unhashable
  literals (list/dict/set) are purity's ``jit-unhashable-static``.

- ``unregistered-jit`` — every ``jax.jit``-compiled entry point
  (decorated def, ``x = jax.jit(f)`` wrap, or a jit call inside a
  program factory) and every ``functools.lru_cache`` compile cache in
  ``ops//kernels//models//parallel/`` must appear in the
  ``NTA_JIT_ACCOUNTED`` manifest (ops/binpack.py), which mirrors the
  runtime ``jit_cache_size()`` accounting — an unaccounted entry
  point blinds the recompile count exactly the way the PR 7
  SARIF rule-list omission blinded CI. Inert when no analyzed module
  declares the manifest (fixture subsets). The manifest<->runtime
  agreement is itself tested (tests/test_compile_surface.py).

- ``donation-unsafe-read`` — a buffer passed in a donated position
  (``donate_argnums``/``donate_argnames``) of a jitted callable is
  dead after the call; any later read in the caller is a
  use-after-free the moment the backend actually reuses the buffer.
  The real tree is donation-free by construction today (PR 6
  deliberately does not donate resident parents — the registry-empty
  TN self-check encodes that); the rule is the pre-laid rail for
  ROADMAP item 3's fused cohort programs with donated buffers.

All four run in the PROGRAM pass so findings carry `Finding.related`
witness chains (entry -> ... -> site for reachability, def/call sites
for call-site rules) and share the tree-digest cache under
RULESET_VERSION.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, Module, Program
from .purity import _call_name, _is_jit_expr, _root_name

RULE_UNBUCKETED = "unbucketed-shape"
RULE_KEY_DRIFT = "static-key-drift"
RULE_UNREGISTERED = "unregistered-jit"
RULE_DONATION = "donation-unsafe-read"

# Module-level manifests (collected by core.Program like every NTA_*):
# the jit entry points the runtime cache accounting covers, and
# hand-rolled bucket/pad sizers beyond the bucket_size family.
JIT_MANIFEST = "NTA_JIT_ACCOUNTED"
BUCKET_MANIFEST = "NTA_BUCKET_FNS"

# Where unregistered-jit enforces: the dirs jit_cache_size() accounts.
JIT_SCOPE_MARKERS = ("/ops/", "/kernels/", "/models/", "/parallel/")
# Where unbucketed-shape enforces: the device-feeding path.
SHAPE_SCOPE_MARKERS = ("/ops/", "/kernels/", "/models/", "/parallel/",
                       "/scheduler/", "/dispatch/", "/defrag/",
                       "/gang/", "/migrate/")

# The root of the sanctioned sizer family; NTA_BUCKET_FNS and the
# returns-a-bucketizer closure extend it (topo_group_pad, _k_bucket).
BASE_BUCKET_FNS = ("bucket_size",)
# Array constructors whose first arg / shape= kwarg is a shape.
SHAPE_CTORS = {"zeros", "ones", "empty", "full", "arange"}
# Host->device boundary calls: a dirty array passed here IS on the
# compile surface, no further escape needed.
DEVICE_XFER_NAMES = {"device_put"}
DEVICE_ROOTS = {"jnp"}


class JitCallable:
    """One jitted callable visible at call sites: a decorated def or a
    module-level ``x = jax.jit(f, ...)`` wrap."""

    __slots__ = ("name", "rel", "line", "params", "statics", "donated")

    def __init__(self, name: str, rel: str, line: int,
                 params: List[str], statics: Set[str],
                 donated: Set[str]):
        self.name = name
        self.rel = rel
        self.line = line
        self.params = params
        self.statics = statics
        self.donated = donated


class JitEntryPoint:
    """One accountable compile cache: the module-level symbol that owns
    a jit (or lru_cache) site — the def itself, the enclosing factory
    for a nested ``jax.jit(...)`` call, or the assignment target of a
    module-level wrap."""

    __slots__ = ("rel", "name", "line", "kind")

    def __init__(self, rel: str, name: str, line: int, kind: str):
        self.rel = rel
        self.name = name
        self.line = line
        self.kind = kind  # "jit" | "lru_cache"


def _donate_from_call(call: ast.Call) -> Tuple[Set[int], Set[str]]:
    """(donated positional indices, donated param names) declared on a
    jit(...) / partial(jax.jit, ...) expression."""
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in call.keywords:
        vals: List[ast.AST] = []
        if isinstance(kw.value, (ast.Tuple, ast.List)):
            vals = list(kw.value.elts)
        elif isinstance(kw.value, ast.Constant):
            vals = [kw.value]
        if kw.arg == "donate_argnums":
            for el in vals:
                if isinstance(el, ast.Constant) and isinstance(
                        el.value, int):
                    nums.add(el.value)
        elif kw.arg == "donate_argnames":
            for el in vals:
                if isinstance(el, ast.Constant) and isinstance(
                        el.value, str):
                    names.add(el.value)
    return nums, names


def _jit_spec(dec: ast.AST):
    """(statics, donate_nums, donate_names) when `dec` is a
    jit-wrapping expression, else None."""
    statics = _is_jit_expr(dec)
    if statics is None:
        return None
    nums: Set[int] = set()
    names: Set[str] = set()
    if isinstance(dec, ast.Call):
        nums, names = _donate_from_call(dec)
    return statics, nums, names


def _fn_params(fn: ast.AST) -> List[str]:
    args = fn.args
    return [a.arg for a in args.posonlyargs + args.args]


def _is_lru_expr(dec: ast.AST) -> bool:
    if isinstance(dec, ast.Call):
        dec = dec.func
    return _call_name(dec) == "lru_cache" or (
        isinstance(dec, ast.Name) and dec.id == "lru_cache")


def _top_level_owner(mod: Module, node: ast.AST) -> Tuple[str, int]:
    """(accountable name, line) of the module-level statement that owns
    `node`: a nested jit inside a factory is accounted to the factory
    (shard.py's ``sharded_base_delta``), a module-level wrap to its
    assignment target."""
    top = node
    cur = node
    while cur is not None:
        parent = mod.parents.get(cur)
        if isinstance(parent, ast.Module):
            top = cur
            break
        cur = parent
    if isinstance(top, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return top.name, top.lineno
    if isinstance(top, ast.ClassDef):
        return mod.symbol_of(node), getattr(node, "lineno", top.lineno)
    if isinstance(top, ast.Assign):
        for tgt in top.targets:
            if isinstance(tgt, ast.Name):
                return tgt.id, top.lineno
    return mod.symbol_of(node), getattr(node, "lineno", 0)


def scan_jit_callables(program: Program) -> Dict[str, JitCallable]:
    """Bare name -> JitCallable over every analyzed module: decorated
    defs (including nested ones) and module-level ``x = jax.jit(f)``
    wraps whose wrapped def is local. Call sites in this codebase
    import these directly, so bare-name keying matches purity's
    registry."""
    out: Dict[str, JitCallable] = {}
    for mod in program.modules:
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    spec = _jit_spec(dec)
                    if spec is None:
                        continue
                    statics, nums, names = spec
                    params = _fn_params(node)
                    donated = set(names)
                    donated.update(params[i] for i in nums
                                   if i < len(params))
                    out[node.name] = JitCallable(
                        node.name, mod.rel, node.lineno, params,
                        statics, donated)
                    break
            elif isinstance(node, ast.Assign):
                if not (isinstance(node.value, ast.Call)
                        and _is_jit_expr(node.value) is not None
                        and node.value.args):
                    continue
                wrapped = node.value.args[0]
                if not isinstance(wrapped, ast.Name):
                    continue
                fn = program.functions.get((mod.rel, wrapped.id))
                if fn is None:
                    continue
                statics, nums, names = _jit_spec(node.value)
                params = _fn_params(fn)
                donated = set(names)
                donated.update(params[i] for i in nums
                               if i < len(params))
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        out[tgt.id] = JitCallable(
                            tgt.id, mod.rel, node.lineno, params,
                            statics, donated)
    return out


def scan_jit_entry_points(mod: Module) -> List[JitEntryPoint]:
    """Every accountable compile cache declared in `mod`: jit-decorated
    defs, jit Call sites that are not decorators (module-level wraps,
    factory-nested compiles), and lru_cache-decorated defs. De-duped
    per accountable name (a factory compiling once per build() is one
    cache)."""
    decorator_calls = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                for sub in ast.walk(dec):
                    decorator_calls.add(id(sub))
    seen: Dict[str, JitEntryPoint] = {}
    for node in ast.walk(mod.tree):
        entry: Optional[JitEntryPoint] = None
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            kind = None
            for dec in node.decorator_list:
                if _is_jit_expr(dec) is not None:
                    kind = "jit"
                    break
                if _is_lru_expr(dec):
                    kind = "lru_cache"
                    break
            if kind is not None:
                name, line = _top_level_owner(mod, node)
                entry = JitEntryPoint(mod.rel, name, line, kind)
        elif (isinstance(node, ast.Call) and id(node) not in
                decorator_calls and _is_jit_expr(node) is not None):
            name, line = _top_level_owner(mod, node)
            entry = JitEntryPoint(mod.rel, name, node.lineno, "jit")
        if entry is not None and entry.name not in seen:
            seen[entry.name] = entry
    return [seen[k] for k in sorted(seen)]


def _in_scope(rel: str, markers) -> bool:
    return any(m in "/" + rel for m in markers)


# ------------------------------------------------- unregistered-jit


def _check_unregistered(program: Program,
                        findings: List[Finding]) -> None:
    declared: Set[str] = set()
    manifest_sites: List[str] = []
    for rel, entries in sorted(
            program.manifests.get(JIT_MANIFEST, {}).items()):
        declared.update(entries)
        line = program.manifest_lines.get(JIT_MANIFEST, {}).get(rel, 0)
        manifest_sites.append(f"{rel}:{line}")
    if not declared:
        return  # no manifest in the analyzed set: rule is inert
    for mod in program.modules:
        if not _in_scope(mod.rel, JIT_SCOPE_MARKERS):
            continue
        for ep in scan_jit_entry_points(mod):
            if ep.name in declared:
                continue
            what = ("compile cache 'functools.lru_cache'"
                    if ep.kind == "lru_cache" else "jit entry point")
            findings.append(Finding(
                RULE_UNREGISTERED, mod.rel, ep.line, 0,
                f"{what} '{ep.name}' is absent from the "
                f"{JIT_MANIFEST} manifest — jit_cache_size() cannot "
                f"account it and the recompile count is blind to "
                f"it; register it (and its runtime accounting) in "
                f"ops/binpack.py", ep.name,
                related=list(manifest_sites)))


# ------------------------------------------------- unbucketed-shape


def _bucket_functions(program: Program) -> Set[str]:
    """Sanctioned sizer names: bucket_size, NTA_BUCKET_FNS manifest
    entries, and (to a fixed point) any function with a return that is
    a call to an already-sanctioned sizer (topo_group_pad, _k_bucket)."""
    names: Set[str] = set(BASE_BUCKET_FNS)
    for entries in program.manifests.get(BUCKET_MANIFEST, {}).values():
        names.update(entries)
    changed = True
    while changed:
        changed = False
        for (_rel, qual), fn in program.functions.items():
            name = qual.split(".")[-1]
            if name in names:
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.Return)
                        and isinstance(node.value, ast.Call)
                        and _call_name(node.value.func) in names):
                    names.add(name)
                    changed = True
                    break
    return names


class _ShapeTaint:
    """Per-function taint over data-dependent ints and the arrays they
    size. `len(...)` (outside a sanctioned sizer call) is the dirty
    source; names assigned from dirty expressions stay dirty; a
    bucketizer call sanitizes its whole subtree. IfExp TESTS are
    excluded — ``pad if rows else BUCKETS[0]`` branches on a dirty
    count without sizing anything by it."""

    def __init__(self, fn: ast.AST, bucket_fns: Set[str]):
        self.bucket_fns = bucket_fns
        self.dirty_ints: Set[str] = set()
        self.dirty_arrays: Set[str] = set()
        self._fixed_point(fn)

    def _fixed_point(self, fn: ast.AST) -> None:
        changed = True
        while changed:
            changed = False
            for stmt in ast.walk(fn):
                if isinstance(stmt, ast.Assign):
                    targets = stmt.targets
                elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
                    targets = [stmt.target]
                else:
                    continue
                value = getattr(stmt, "value", None)
                if value is None:
                    continue
                dirty_int = self.int_dirty(value)
                dirty_arr = self.array_dirty(value)
                for tgt in targets:
                    if not isinstance(tgt, ast.Name):
                        continue
                    if dirty_int and tgt.id not in self.dirty_ints:
                        self.dirty_ints.add(tgt.id)
                        changed = True
                    if dirty_arr and tgt.id not in self.dirty_arrays:
                        self.dirty_arrays.add(tgt.id)
                        changed = True

    def _walk(self, expr: ast.AST):
        """Walk pruning sanitized subtrees: bucketizer calls, IfExp
        tests, nested defs/lambdas."""
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.Lambda, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                continue
            if (isinstance(node, ast.Call)
                    and _call_name(node.func) in self.bucket_fns):
                continue
            yield node
            if isinstance(node, ast.IfExp):
                stack.extend((node.body, node.orelse))
                continue
            stack.extend(ast.iter_child_nodes(node))

    def int_dirty(self, expr: ast.AST) -> bool:
        """True when `expr` carries a raw data-dependent int."""
        for node in self._walk(expr):
            if (isinstance(node, ast.Call)
                    and _call_name(node.func) == "len"):
                return True
            if (isinstance(node, ast.Name)
                    and node.id in self.dirty_ints):
                return True
        return False

    def dirty_shape_ctor(self, call: ast.Call) -> bool:
        """True when `call` is an array constructor sized by a dirty
        int (first positional arg or shape= kwarg)."""
        if _call_name(call.func) not in SHAPE_CTORS:
            return False
        shape_args = list(call.args[:1])
        shape_args += [kw.value for kw in call.keywords
                       if kw.arg == "shape"]
        return any(self.int_dirty(a) for a in shape_args)

    def array_dirty(self, expr: ast.AST) -> bool:
        """True when `expr` yields an array sized by a dirty int: a
        dirty-shape ctor, a dirty array name, its .copy()/slices."""
        if isinstance(expr, ast.Call):
            if self.dirty_shape_ctor(expr):
                return True
            if (isinstance(expr.func, ast.Attribute)
                    and _root_name(expr.func) in self.dirty_arrays):
                return True
            return False
        if isinstance(expr, ast.Name):
            return expr.id in self.dirty_arrays
        if isinstance(expr, ast.Subscript):
            return self.array_dirty(expr.value)
        if isinstance(expr, ast.IfExp):
            return (self.array_dirty(expr.body)
                    or self.array_dirty(expr.orelse))
        return False

    def first_dirty_site(self, expr: ast.AST) -> Optional[ast.AST]:
        """The node to report: an embedded dirty-shape ctor, or a
        dirty array/int reference."""
        for node in self._walk(expr):
            if isinstance(node, ast.Call) and self.dirty_shape_ctor(node):
                return node
            if isinstance(node, ast.Name) and (
                    node.id in self.dirty_arrays):
                return node
        return None


def _check_fn_shapes(mod: Module, qual: str, fn: ast.AST,
                     bucket_fns: Set[str],
                     jit_names: Set[str], note: str,
                     related: List[str],
                     findings: List[Finding]) -> None:
    taint = _ShapeTaint(fn, bucket_fns)

    def emit(node: ast.AST, how: str) -> None:
        findings.append(Finding(
            RULE_UNBUCKETED, mod.rel, node.lineno, node.col_offset,
            f"array sized by a raw data-dependent int (len(...)) "
            f"{how} on a jit-feeding path{note}; route the size "
            f"through a registered bucket function (bucket_size / "
            f"{BUCKET_MANIFEST}) — every distinct shape is one more "
            f"compiled program", qual, related=list(related)))

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            # attribute stores escape the function toward the device
            # path (resident bases, matrix fields)
            if (any(isinstance(t, ast.Attribute) for t in node.targets)
                    and taint.array_dirty(node.value)):
                site = taint.first_dirty_site(node.value)
                emit(site if site is not None else node.value,
                     "stored to an attribute")
        elif isinstance(node, ast.Call):
            fname = _call_name(node.func)
            root = _root_name(node.func)
            if root in DEVICE_ROOTS and taint.dirty_shape_ctor(node):
                emit(node, "built on device")
                continue
            is_sink = (fname in jit_names
                       or fname in DEVICE_XFER_NAMES
                       or (root in DEVICE_ROOTS
                           and fname in ("asarray", "array")))
            if not is_sink:
                continue
            for arg in list(node.args) + [kw.value
                                          for kw in node.keywords]:
                site = taint.first_dirty_site(arg)
                if site is not None and (taint.array_dirty(arg)
                                         or taint.int_dirty(arg)):
                    emit(site, f"passed to '{fname}'")


def _check_unbucketed(program: Program,
                      callables: Dict[str, JitCallable],
                      findings: List[Finding]) -> None:
    if not callables:
        return
    bucket_fns = _bucket_functions(program)
    jit_names = set(callables)
    jit_def_keys = {(c.rel, c.name) for c in callables.values()}
    entries = []
    for key, fn in program.functions.items():
        if key in jit_def_keys:
            continue  # the jitted body itself traces; purity owns it
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and _call_name(node.func) in jit_names):
                entries.append(key)
                break
    if not entries:
        return
    via = program.reachable_with_paths(sorted(entries))
    for key in sorted(via):
        rel, qual = key
        if not _in_scope(rel, SHAPE_SCOPE_MARKERS):
            continue
        if key in jit_def_keys or qual.split(".")[-1] in bucket_fns:
            continue
        mod = program.by_rel.get(rel)
        if mod is None:
            continue
        note, related = program.witness_info(via, key)
        _check_fn_shapes(mod, qual, program.functions[key], bucket_fns,
                         jit_names, note, related, findings)


# ------------------------------------------------- static-key-drift

_ARITH_OPS = (ast.Add, ast.Sub, ast.Mult, ast.Div, ast.FloorDiv,
              ast.Pow, ast.Mod)
_STRING_BUILDERS = {"str", "repr", "format", "hex", "oct", "chr",
                    "join"}


def _constant_only(expr: ast.AST) -> bool:
    return all(isinstance(n, (ast.Constant, ast.expr_context,
                              ast.operator, ast.unaryop, ast.BinOp,
                              ast.UnaryOp, ast.Tuple))
               for n in ast.walk(expr))


def _drift_reason(expr: ast.AST) -> Optional[str]:
    """Why `expr` mints a fresh compile key per call, or None when it
    is a stable static (name, constant, attribute, config factory —
    opaque calls are sanctioned so build_placement_config is always
    clean)."""
    if isinstance(expr, ast.JoinedStr):
        return "an f-string (a fresh key per call)"
    if isinstance(expr, ast.Call):
        if _call_name(expr.func) in _STRING_BUILDERS:
            return f"a per-call '{_call_name(expr.func)}(...)' build"
        return None
    if isinstance(expr, ast.BinOp):
        if _constant_only(expr):
            return None  # folded once, stable
        if (isinstance(expr.op, ast.Mod)
                and isinstance(expr.left, ast.Constant)
                and isinstance(expr.left.value, str)):
            return "a %-formatted string (a fresh key per call)"
        if isinstance(expr.op, _ARITH_OPS):
            return ("a computed value (one compile per distinct "
                    "result)")
        return None
    if isinstance(expr, ast.Tuple):
        for el in expr.elts:
            r = _drift_reason(el)
            if r is not None:
                return f"a fresh tuple holding {r}"
        return None
    if isinstance(expr, ast.IfExp):
        return _drift_reason(expr.body) or _drift_reason(expr.orelse)
    return None


def _check_key_drift(program: Program,
                     callables: Dict[str, JitCallable],
                     findings: List[Finding]) -> None:
    for mod in program.modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            info = callables.get(_call_name(node.func) or "")
            if info is None or not info.statics:
                continue
            related = [f"{info.rel}:{info.line}"]
            checks: List[Tuple[str, ast.AST]] = []
            for i, arg in enumerate(node.args):
                if (i < len(info.params)
                        and info.params[i] in info.statics):
                    checks.append((info.params[i], arg))
            for kw in node.keywords:
                if kw.arg in info.statics:
                    checks.append((kw.arg, kw.value))
            for pname, arg in checks:
                reason = _drift_reason(arg)
                if reason is None:
                    continue
                findings.append(Finding(
                    RULE_KEY_DRIFT, mod.rel, arg.lineno,
                    arg.col_offset,
                    f"static arg '{pname}' of jitted '{info.name}' is "
                    f"{reason} — one compile per eval; derive statics "
                    f"from the declared config surface "
                    f"(build_placement_config / PlacementConfig "
                    f"fields)", mod.symbol_of(node),
                    related=related))


# --------------------------------------------- donation-unsafe-read


def _chain_text(expr: ast.AST) -> Optional[str]:
    """Stable text for a Name / dotted-attribute buffer reference."""
    parts: List[str] = []
    node = expr
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _check_donation(program: Program,
                    callables: Dict[str, JitCallable],
                    findings: List[Finding]) -> None:
    donating = {n: c for n, c in callables.items() if c.donated}
    if not donating:
        return
    for key, fn in sorted(program.functions.items()):
        rel, qual = key
        mod = program.by_rel.get(rel)
        if mod is None:
            continue
        # (buffer text, call end line, jit def site, call site)
        donated_bufs: List[Tuple[str, int, str, str]] = []
        store_lines: Dict[str, List[int]] = {}
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.For)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for tgt in targets:
                    text = _chain_text(tgt)
                    if text is not None:
                        store_lines.setdefault(text, []).append(
                            node.lineno)
            if not isinstance(node, ast.Call):
                continue
            info = donating.get(_call_name(node.func) or "")
            if info is None:
                continue
            bound: List[Tuple[str, ast.AST]] = []
            for i, arg in enumerate(node.args):
                if i < len(info.params):
                    bound.append((info.params[i], arg))
            for kw in node.keywords:
                if kw.arg:
                    bound.append((kw.arg, kw.value))
            for pname, arg in bound:
                if pname not in info.donated:
                    continue
                text = _chain_text(arg)
                if text is None:
                    continue
                donated_bufs.append((
                    text, getattr(node, "end_lineno", node.lineno),
                    f"{info.rel}:{info.line}",
                    f"{mod.rel}:{node.lineno}"))
        if not donated_bufs:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, (ast.Name, ast.Attribute)):
                continue
            if not isinstance(getattr(node, "ctx", None), ast.Load):
                continue
            text = _chain_text(node)
            if text is None:
                continue
            for buf, end, def_site, call_site in donated_bufs:
                if text != buf or node.lineno <= end:
                    continue
                call_line = int(call_site.rsplit(":", 1)[1])
                rebound = any(call_line <= s <= node.lineno
                              for s in store_lines.get(buf, ()))
                if rebound:
                    continue
                findings.append(Finding(
                    RULE_DONATION, mod.rel, node.lineno,
                    node.col_offset,
                    f"read of '{buf}' after it was donated at "
                    f"{call_site} — a donated buffer is dead the "
                    f"moment the jitted call runs; copy before "
                    f"donating or drop the read", qual,
                    related=[def_site, call_site]))
                break


# ----------------------------------------------------------- driver


def program_check(program: Program) -> List[Finding]:
    """All four compile-surface rules over one Program."""
    findings: List[Finding] = []
    callables = scan_jit_callables(program)
    _check_unregistered(program, findings)
    _check_unbucketed(program, callables, findings)
    _check_key_drift(program, callables, findings)
    _check_donation(program, callables, findings)
    return findings
