"""JAX trace-purity checker.

A function is TRACED when it is jitted (``@jax.jit``,
``@functools.partial(jax.jit, ...)``, ``jax.jit(f)``), passed to a
transform (``vmap``/``pmap``) or a control-flow primitive
(``lax.scan``/``while_loop``/``cond``/``fori_loop``/``map``) — directly,
as a nested def, or as a lambda — or called (direct intra-module call)
from another traced function.

Rules inside traced code:

- ``trace-impure-call`` — Python RNG (``random.*``, ``np.random.*``,
  ``os.urandom``, ``uuid.*``), wall clocks (``time.*``,
  ``datetime.*``), ``print``/``input``/``open``: all run at TRACE time
  only, baking one draw/timestamp into the compiled program — the
  classic silent-staleness bug.

- ``trace-host-sync`` — ``float()``/``int()``/``bool()`` on traced
  values, ``.item()``/``.tolist()``, and any call through a numpy
  import alias (``np.asarray(...)`` etc.): forces device→host
  materialization, which either errors under trace or silently falls
  back to host, the 100-1000x cliff the dense path exists to avoid.

- ``trace-closure-mutation`` — assigning ``self.X``/globals/nonlocals
  or calling a mutating method (``append``/``update``/...) on a
  closed-over name: runs once at trace time, not per call.

- ``trace-python-branch`` — ``if``/``while``/``assert`` whose test
  depends on traced values (concretization error / silent recompile
  per shape). Tests over STATIC parameters (``static_argnames``),
  shape/dtype queries (``x.shape``, ``len()``, ``np.shape``), module
  globals, and constants are fine and common (``if config.uniform_dh``).

Call-site rule (applies everywhere, not just traced code):

- ``jit-unhashable-static`` — a call to a known-jitted function passing
  a list/dict/set literal (or ``list()``/``dict()``/``set()``/numpy
  array call) in a static-arg position: unhashable statics raise at
  call time, and a fresh mutable object per call would defeat the jit
  cache even if it hashed.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, Module

RULE_IMPURE = "trace-impure-call"
RULE_HOST_SYNC = "trace-host-sync"
RULE_CLOSURE_MUT = "trace-closure-mutation"
RULE_BRANCH = "trace-python-branch"
RULE_STATIC = "jit-unhashable-static"

IMPURE_ROOTS = {"random", "time", "datetime", "os", "uuid"}
IMPURE_NAMES = {"print", "input", "open"}
HOST_CAST_NAMES = {"float", "int", "bool"}
HOST_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
MUTATING_ATTRS = {"append", "extend", "update", "add", "pop", "remove",
                  "insert", "setdefault", "clear", "popitem"}
TRANSFORM_NAMES = {"vmap", "pmap"}
CONTROL_FLOW = {"scan", "while_loop", "cond", "fori_loop", "map",
                "switch"}
SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}
SAFE_BUILTINS = {"len", "range", "min", "max", "abs", "sorted", "sum",
                 "isinstance", "tuple", "enumerate", "zip"}


class JitInfo:
    """One jitted function's signature, for call-site checks."""

    def __init__(self, name: str, params: List[str],
                 static_names: Set[str]):
        self.name = name
        self.params = params
        self.static_names = static_names


def _call_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _root_name(node: ast.AST) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        if isinstance(node, ast.Call):
            node = node.func
        else:
            node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_lax_call(func: ast.AST) -> bool:
    """True when a CONTROL_FLOW-named call goes through ``lax`` —
    ``jax.lax.scan``/``lax.map``/bare ``while_loop``. Guards against
    host-side namesakes: ``jax.tree.map`` and builtin ``map`` run their
    function argument on the HOST, so marking it traced would
    false-positive every numpy call inside."""
    if isinstance(func, ast.Name):
        return func.id in ("while_loop", "fori_loop", "scan")
    node = func.value if isinstance(func, ast.Attribute) else None
    while isinstance(node, ast.Attribute):
        if node.attr == "lax":
            return True
        node = node.value
    return isinstance(node, ast.Name) and node.id == "lax"


def _is_jit_expr(node: ast.AST) -> Optional[Set[str]]:
    """When `node` is a jit-wrapping expression (``jax.jit``,
    ``functools.partial(jax.jit, static_argnames=...)``), return its
    static argnames (possibly empty); else None."""
    # bare jax.jit / jit
    if _call_name(node) in ("jit",) or (
            isinstance(node, ast.Name) and node.id == "jit"):
        return set()
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return set()
    if isinstance(node, ast.Call):
        fname = _call_name(node.func)
        if fname == "jit":
            return _static_from_kwargs(node)
        if fname == "partial":
            if node.args and _is_jit_expr(node.args[0]) is not None:
                return _static_from_kwargs(node)
    return None


def _static_from_kwargs(call: ast.Call) -> Set[str]:
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                for el in kw.value.elts:
                    if isinstance(el, ast.Constant) and isinstance(
                            el.value, str):
                        out.add(el.value)
            elif isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, str):
                out.add(kw.value.value)
    return out


def _numpy_aliases(mod: Module) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "numpy":
                    out.add(alias.asname or "numpy")
    return out


def build_jit_registry(modules: List[Module]) -> Dict[str, JitInfo]:
    """Cross-module registry of jitted defs: called-name -> signature.
    Keyed on the bare function name — call sites import these directly
    and the names are unique in this codebase."""
    registry: Dict[str, JitInfo] = {}
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            statics: Optional[Set[str]] = None
            for dec in node.decorator_list:
                s = _is_jit_expr(dec)
                if s is not None:
                    statics = s
                    break
            if statics is None:
                continue
            params = [a.arg for a in node.args.posonlyargs
                      + node.args.args]
            registry[node.name] = JitInfo(node.name, params, statics)
    return registry


class _TracedCollector:
    """Find every traced function in a module: jit-decorated defs,
    defs/lambdas passed to transforms, and the transitive closure over
    direct intra-module calls."""

    def __init__(self, mod: Module):
        self.mod = mod
        # id(funcdef/lambda) -> static param-name set
        self.traced: Dict[int, Tuple[ast.AST, Set[str]]] = {}
        # name -> [def nodes] (several nested fns may share a name,
        # e.g. the `body` passed to each lax.scan).
        self.defs_by_name: Dict[str, List[ast.AST]] = {}
        self.global_statics: Set[str] = set()
        self._collect_defs(mod.tree)
        self._seed()
        self._closure()

    def _collect_defs(self, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs_by_name.setdefault(node.name, []).append(node)

    def _resolve_def(self, name: str, site: ast.AST) -> Optional[ast.AST]:
        """The def `name` refers to at `site`: prefer the candidate
        whose enclosing scope is an ancestor of the reference (nested
        fns shadow same-named siblings in other scopes)."""
        cands = self.defs_by_name.get(name)
        if not cands:
            return None
        if len(cands) == 1:
            return cands[0]
        # Rank the reference's ancestor chain innermost-first; a def
        # whose enclosing scope sits earliest in that chain is the one
        # Python's scoping resolves to.
        rank: Dict[int, int] = {}
        cur = site
        i = 0
        while cur is not None:
            rank.setdefault(id(cur), i)
            i += 1
            cur = self.mod.parents.get(cur)
        best = None
        best_rank = None
        for d in cands:
            scope = self.mod.parents.get(d)
            r = rank.get(id(scope))
            if r is not None and (best_rank is None or r < best_rank):
                best, best_rank = d, r
        return best or cands[0]

    def _seed(self) -> None:
        for node in ast.walk(self.mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    statics = _is_jit_expr(dec)
                    if statics is not None:
                        self._mark(node, statics)
                        self.global_statics |= statics
            elif isinstance(node, ast.Call):
                fname = _call_name(node.func)
                if fname == "jit" and node.args:
                    self._mark_arg(node.args[0],
                                   _static_from_kwargs(node))
                elif fname in TRANSFORM_NAMES and node.args:
                    self._mark_arg(node.args[0], set())
                elif fname in CONTROL_FLOW and node.args \
                        and _is_lax_call(node.func):
                    self._mark_arg(node.args[0], set())

    def _mark_arg(self, arg: ast.AST, statics: Set[str]) -> None:
        if isinstance(arg, ast.Lambda):
            self._mark(arg, statics)
        elif isinstance(arg, ast.Name):
            target = self._resolve_def(arg.id, arg)
            if target is not None:
                self._mark(target, statics)

    def _mark(self, fn: ast.AST, statics: Set[str]) -> None:
        cur = self.traced.get(id(fn))
        if cur is None:
            self.traced[id(fn)] = (fn, set(statics))
        else:
            cur[1].update(statics)

    def _closure(self) -> None:
        # Functions called directly from traced bodies are traced too.
        # Their own statics are unknown; params sharing a name with any
        # jit static (e.g. 'config') are treated static — pragmatic,
        # and exactly how this codebase threads statics through.
        changed = True
        while changed:
            changed = False
            for _fid, (fn, _statics) in list(self.traced.items()):
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    if not isinstance(node.func, ast.Name):
                        continue
                    target = self._resolve_def(node.func.id, node)
                    if target is not None and id(target) not in \
                            self.traced:
                        self._mark(target, set())
                        changed = True

    def statics_for(self, fn: ast.AST) -> Set[str]:
        explicit = self.traced[id(fn)][1]
        if explicit:
            return explicit
        # transitively-traced: inherit global static names that match
        # a param.
        params = set()
        args = fn.args
        for a in args.posonlyargs + args.args + args.kwonlyargs:
            params.add(a.arg)
        return params & self.global_statics


def _local_bindings(fn: ast.AST) -> Set[str]:
    """Names bound inside `fn` (params + assignments) — everything else
    referenced is closed-over or global."""
    out: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs
              + ([args.vararg] if args.vararg else [])
              + ([args.kwarg] if args.kwarg else [])):
        out.add(a.arg)
    if isinstance(fn, ast.Lambda):
        return out
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                out.update(_target_names(t))
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.For)):
            tgt = node.target
            out.update(_target_names(tgt))
        elif isinstance(node, ast.withitem) and node.optional_vars:
            out.update(_target_names(node.optional_vars))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn:
                out.add(node.name)
    return out


def _target_names(t: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(t):
        if isinstance(node, ast.Name):
            out.add(node.id)
    return out


class _TracedChecker:
    def __init__(self, mod: Module, collector: _TracedCollector,
                 np_aliases: Set[str], findings: List[Finding]):
        self.mod = mod
        self.collector = collector
        self.np_aliases = np_aliases
        self.findings = findings
        # set per checked function (_check_fn): names closed over from
        # enclosing scopes that carry traced values.
        self._closure_unsafe: Set[str] = set()

    def run(self) -> None:
        for _fid, (fn, _s) in self.collector.traced.items():
            self._check_fn(fn)

    def _emit(self, rule: str, node: ast.AST, msg: str,
              fn: ast.AST) -> None:
        symbol = self.mod.symbol_of(fn if not isinstance(fn, ast.Lambda)
                                    else node)
        self.findings.append(Finding(
            rule, self.mod.rel, node.lineno, node.col_offset, msg,
            symbol))

    def _check_fn(self, fn: ast.AST) -> None:
        statics = self.collector.statics_for(fn)
        locals_ = _local_bindings(fn)
        # Names closed over from ENCLOSING functions are traced values
        # unless the enclosing scope declares them static: a nested
        # scan/vmap body branching on its outer jitted function's array
        # is the flagship bug, and treating those names as "module
        # globals" would silence it. Enclosing statics (config threaded
        # into a lambda) stay safe.
        closure_unsafe: Set[str] = set()
        anc = self.mod.parents.get(fn)
        while anc is not None:
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                closure_unsafe |= _local_bindings(anc)
                if id(anc) in self.collector.traced:
                    closure_unsafe -= self.collector.statics_for(anc)
            anc = self.mod.parents.get(anc)
        closure_unsafe -= locals_ | statics
        # Kept SEPARATE from locals_: the mutation rules use locals_ to
        # detect closed-over receivers, which these names still are.
        self._closure_unsafe = closure_unsafe
        safe = set(statics)  # statics + shape-derived locals
        # Nested traced functions are checked on their own; skip their
        # bodies here to avoid double reports.
        nested_traced = {
            id(n) for n in ast.walk(fn)
            if id(n) in self.collector.traced and n is not fn
        }

        def walk(stmts):
            for stmt in stmts:
                if id(stmt) in nested_traced:
                    continue
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    if id(stmt) not in self.collector.traced:
                        walk(stmt.body)
                    continue
                self._check_stmt(stmt, fn, statics, locals_, safe)
                # recurse into compound bodies
                for field in ("body", "orelse", "finalbody"):
                    inner = getattr(stmt, field, None)
                    if isinstance(inner, list) and inner and isinstance(
                            inner[0], ast.stmt):
                        walk(inner)
                for h in getattr(stmt, "handlers", []) or []:
                    walk(h.body)

        if isinstance(fn, ast.Lambda):
            self._check_exprs(fn.body, fn, statics, locals_, safe)
        else:
            walk(fn.body)

    # ------------------------------------------------------ statements

    def _check_stmt(self, stmt, fn, statics, locals_, safe) -> None:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                self._check_store(t, fn, locals_)
            if self._expr_safe(stmt.value, safe, locals_):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        safe.add(t.id)
            else:
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        safe.discard(t.id)
            self._check_exprs(stmt.value, fn, statics, locals_, safe)
        elif isinstance(stmt, ast.AugAssign):
            self._check_store(stmt.target, fn, locals_)
            self._check_exprs(stmt.value, fn, statics, locals_, safe)
        elif isinstance(stmt, (ast.Global, ast.Nonlocal)):
            self._emit(RULE_CLOSURE_MUT, stmt,
                       "global/nonlocal rebinding inside a traced "
                       "function runs at trace time only", fn)
        elif isinstance(stmt, (ast.If, ast.While)):
            if not self._expr_safe(stmt.test, safe, locals_):
                self._emit(
                    RULE_BRANCH, stmt,
                    "Python branch on a traced value (concretization "
                    "error or silent per-shape recompile); use "
                    "jnp.where/lax.cond, or derive the test from "
                    "static args / shapes", fn)
            self._check_exprs(stmt.test, fn, statics, locals_, safe)
        elif isinstance(stmt, ast.Assert):
            if not self._expr_safe(stmt.test, safe, locals_):
                self._emit(
                    RULE_BRANCH, stmt,
                    "assert on a traced value concretizes under trace",
                    fn)
        else:
            for child in ast.iter_child_nodes(stmt):
                if not isinstance(child, ast.stmt):
                    self._check_exprs(child, fn, statics, locals_, safe)

    def _check_store(self, target: ast.AST, fn, locals_) -> None:
        if isinstance(target, ast.Attribute):
            root = _root_name(target)
            if root == "self" or (root is not None
                                  and root not in locals_):
                self._emit(RULE_CLOSURE_MUT, target,
                           f"mutating closed-over state "
                           f"'{ast.unparse(target)}' inside a traced "
                           f"function runs at trace time only", fn)
        elif isinstance(target, ast.Subscript):
            root = _root_name(target)
            if root is not None and root not in locals_ and root != "_":
                self._emit(RULE_CLOSURE_MUT, target,
                           f"item-assigning closed-over '{root}' "
                           f"inside a traced function", fn)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._check_store(el, fn, locals_)

    # ----------------------------------------------------- expressions

    def _check_exprs(self, node: ast.AST, fn, statics, locals_,
                     safe) -> None:
        # Manual stack so nested function/lambda subtrees are PRUNED —
        # they execute in their own traced context (checked separately
        # when traced) and their bodies must not double-report here.
        stack = [node]
        while stack:
            sub = stack.pop()
            for child in ast.iter_child_nodes(sub):
                if not isinstance(child, (ast.Lambda, ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                    stack.append(child)
            if not isinstance(sub, ast.Call):
                continue
            fname = _call_name(sub.func)
            root = _root_name(sub.func)
            if isinstance(sub.func, ast.Attribute):
                # jax.random / jnp are the sanctioned namespaces.
                if root in ("jax", "jnp", "lax"):
                    continue
                if root in IMPURE_ROOTS:
                    self._emit(
                        RULE_IMPURE, sub,
                        f"impure call '{root}.{fname}' in traced code "
                        f"executes at trace time only", fn)
                elif root in self.np_aliases:
                    self._emit(
                        RULE_HOST_SYNC, sub,
                        f"numpy call '{root}.{fname}' in traced code "
                        f"forces host materialization; use jnp", fn)
                elif fname in HOST_SYNC_ATTRS:
                    self._emit(
                        RULE_HOST_SYNC, sub,
                        f"'.{fname}()' in traced code forces a host "
                        f"sync", fn)
                elif fname in MUTATING_ATTRS and root is not None \
                        and root not in locals_:
                    self._emit(
                        RULE_CLOSURE_MUT, sub,
                        f"mutating closed-over '{root}.{fname}(...)' "
                        f"inside a traced function", fn)
            elif isinstance(sub.func, ast.Name):
                if fname in IMPURE_NAMES:
                    self._emit(
                        RULE_IMPURE, sub,
                        f"impure call '{fname}' in traced code", fn)
                elif fname in HOST_CAST_NAMES:
                    if any(not self._expr_safe(a, safe, locals_)
                           for a in sub.args):
                        self._emit(
                            RULE_HOST_SYNC, sub,
                            f"'{fname}()' on a traced value forces "
                            f"concretization; keep it an array or "
                            f"derive from statics", fn)

    def _expr_safe(self, node: ast.AST, safe, locals_) -> bool:
        """True when every root of `node` is trace-static: static
        params, shape queries, constants, module globals (names never
        bound locally)."""
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            if node.id in safe:
                return True
            if node.id in self._closure_unsafe:
                return False  # closed-over traced value
            if node.id not in locals_:
                return True  # module global / builtin: static object
            return False
        if isinstance(node, ast.Attribute):
            if node.attr in SHAPE_ATTRS:
                return True
            return self._expr_safe(node.value, safe, locals_)
        if isinstance(node, ast.Subscript):
            return (self._expr_safe(node.value, safe, locals_)
                    and self._expr_safe(node.slice, safe, locals_))
        if isinstance(node, ast.Call):
            fname = _call_name(node.func)
            if fname in SAFE_BUILTINS or fname in ("shape",):
                return all(self._expr_safe(a, safe, locals_)
                           for a in node.args)
            if isinstance(node.func, ast.Attribute):
                # x.bit_length(), np.shape(x): safe iff receiver safe
                return self._expr_safe(node.func.value, safe, locals_) \
                    and all(self._expr_safe(a, safe, locals_)
                            for a in node.args)
            return False
        if isinstance(node, (ast.BoolOp, ast.BinOp, ast.UnaryOp,
                             ast.Compare)):
            return all(self._expr_safe(c, safe, locals_)
                       for c in ast.iter_child_nodes(node)
                       if not isinstance(c, (ast.operator, ast.boolop,
                                             ast.unaryop, ast.cmpop)))
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(self._expr_safe(e, safe, locals_)
                       for e in node.elts)
        return False


def _check_static_call_sites(mod: Module, registry: Dict[str, JitInfo],
                             findings: List[Finding]) -> None:
    np_aliases = _numpy_aliases(mod)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _call_name(node.func)
        info = registry.get(fname or "")
        if info is None or not info.static_names:
            continue
        # positional
        for i, arg in enumerate(node.args):
            if i < len(info.params) and info.params[i] in \
                    info.static_names:
                self_msg = _unhashable_reason(arg, np_aliases)
                if self_msg:
                    findings.append(Finding(
                        RULE_STATIC, mod.rel, arg.lineno,
                        arg.col_offset,
                        f"static arg '{info.params[i]}' of jitted "
                        f"'{fname}' is {self_msg}: statics must be "
                        f"hashable (and stable across calls)",
                        mod.symbol_of(node)))
        for kw in node.keywords:
            if kw.arg in info.static_names:
                self_msg = _unhashable_reason(kw.value, np_aliases)
                if self_msg:
                    findings.append(Finding(
                        RULE_STATIC, mod.rel, kw.value.lineno,
                        kw.value.col_offset,
                        f"static arg '{kw.arg}' of jitted '{fname}' "
                        f"is {self_msg}: statics must be hashable",
                        mod.symbol_of(node)))


def _unhashable_reason(node: ast.AST, np_aliases) -> Optional[str]:
    if isinstance(node, (ast.List, ast.ListComp)):
        return "a list"
    if isinstance(node, (ast.Dict, ast.DictComp)):
        return "a dict"
    if isinstance(node, (ast.Set, ast.SetComp)):
        return "a set"
    if isinstance(node, ast.Call):
        fname = _call_name(node.func)
        if fname in ("list", "dict", "set", "bytearray"):
            return f"a {fname}()"
        root = _root_name(node.func)
        if root in np_aliases and fname in ("array", "asarray", "zeros",
                                            "ones", "full", "arange"):
            return "a numpy array"
    return None


def check(mod: Module, registry: Dict[str, JitInfo]) -> List[Finding]:
    findings: List[Finding] = []
    collector = _TracedCollector(mod)
    if collector.traced:
        _TracedChecker(mod, collector, _numpy_aliases(mod),
                       findings).run()
    _check_static_call_sites(mod, registry, findings)
    return findings
