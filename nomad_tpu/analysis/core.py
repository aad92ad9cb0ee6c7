"""ntalint driver: module parsing, whole-program call graph,
suppressions, caching, baseline machinery.

Pure stdlib (`ast` + `hashlib` + line scans): the suite must run in
the tier-1 path on any box the tests run on, with zero dependencies
beyond the interpreter.

Baseline entries match findings by (rule, path, symbol) — line numbers
drift with every edit, while the enclosing def/class is stable across
reformatting. An entry carries a ``count`` so N pre-existing findings
in one function stay N: an N+1th is a NEW finding, and an entry whose
symbol no longer produces a finding is STALE (the non-growing-baseline
test fails on it — fixed findings must leave the baseline).

PR 7 split the suite into two passes:

- **local rules** run one module at a time (guarded-by, lock-blocking,
  purity, snapshot, unbounded-wait-in-scope, swallowed-exception,
  full-matrix-reship). Their findings are cached per file, keyed on
  (file sha, jit-registry digest, RULESET_VERSION).
- **program rules** run over the whole-program call graph built here
  (dispatcher-blocking-call, record-path-blocking, cross-module
  unbounded-wait, deadlock-cycle, raft-funnel). Their findings are
  cached on the digest of every analyzed (path, sha) pair — any edit
  anywhere re-runs them, which is the only sound invalidation for
  cross-module reachability.

The `Program` class is THE definition of "reachable from" for every
manifest rule: `from x import y` / `module.attr` / `self.method` /
constructor / typed-attribute calls resolve across `nomad_tpu/`;
dynamic dispatch (dict-of-handlers, references handed to pools or
`Thread(target=...)`) is deliberately NOT followed — handing work to
another thread is exactly the sanctioned fix for a dispatcher/record-
path finding, and guessing at dynamic targets would drown the rules
in false paths.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from typing import Dict, List, Optional, Set, Tuple

_DISABLE_RE = re.compile(r"#\s*nta:\s*disable=([A-Za-z0-9_,\- ]+)")
_GUARDED_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")

# Bump whenever any rule's behavior changes: every cache key includes
# it, so a stale on-disk cache from an older rule set can never mask a
# new finding (or resurrect a fixed one).
RULESET_VERSION = "9.0-compile-surface"


class Finding:
    """One rule violation at one site. `related` optionally carries
    the witness chain ("path:line" strings) for program-rule findings —
    the call path from the manifest entrypoint (or lock-cycle edges)
    to this site."""

    __slots__ = ("rule", "path", "line", "col", "message", "symbol",
                 "related")

    def __init__(self, rule: str, path: str, line: int, col: int,
                 message: str, symbol: str = "",
                 related: Optional[List[str]] = None):
        self.rule = rule
        self.path = path
        self.line = line
        self.col = col
        self.message = message
        self.symbol = symbol  # enclosing Class.method / function
        self.related = related

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.symbol)

    def to_dict(self) -> dict:
        d = {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "symbol": self.symbol,
            "message": self.message,
        }
        if self.related:
            d["related"] = list(self.related)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Finding":
        return cls(d["rule"], d["path"], d["line"], d["col"],
                   d["message"], d.get("symbol", ""),
                   list(d["related"]) if d.get("related") else None)

    def render(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: " \
               f"{self.message}{sym}"

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<Finding {self.render()}>"


class Module:
    """One parsed source file plus the per-line metadata every checker
    needs: raw lines (for `# guarded-by:` / `# nta: disable=` comment
    scans — ast drops comments) and a child->parent node map."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel  # repo-relative, forward slashes (baseline key)
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def guarded_comment(self, lineno: int) -> Optional[str]:
        m = _GUARDED_RE.search(self.line_text(lineno))
        return m.group(1) if m else None

    def disabled_rules(self, lineno: int) -> set:
        """Rules disabled on this line (or 'all')."""
        m = _DISABLE_RE.search(self.line_text(lineno))
        if not m:
            return set()
        return {r.strip() for r in m.group(1).split(",") if r.strip()}

    def statement_line(self, node: ast.AST) -> int:
        """Line of the statement enclosing `node` (suppressions placed
        on a multi-line statement's first line cover the whole
        statement)."""
        cur = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = self.parents.get(cur)
        return getattr(cur, "lineno", getattr(node, "lineno", 0))

    def symbol_of(self, node: ast.AST) -> str:
        """Dotted Class.method / function name enclosing `node`."""
        parts: List[str] = []
        cur = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = self.parents.get(cur)
        return ".".join(reversed(parts)) if parts else "<module>"


# -------------------------------------------- whole-program call graph

# A function's global identity: (module rel path, qualname).
FnKey = Tuple[str, str]
# A class's global identity: (module rel path, class name).
ClsKey = Tuple[str, str]


def _flatten_attr_chain(node: ast.AST) -> Optional[List[str]]:
    """["a", "b", "c"] for `a.b.c`; None when the chain roots in
    anything but a bare Name (calls, subscripts: dynamic, give up)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return None


class _ModInfo:
    """Per-module raw facts gathered in pass 1 of the Program build."""

    __slots__ = ("mod", "dotted", "is_pkg", "bindings", "plain_imports",
                 "classes", "class_base_exprs", "init_attr_calls")

    def __init__(self, mod: Module, dotted: str, is_pkg: bool):
        self.mod = mod
        self.dotted = dotted
        self.is_pkg = is_pkg
        # local name -> ("mod", dotted) | ("sym", dotted, origname)
        self.bindings: Dict[str, tuple] = {}
        self.plain_imports: Set[str] = set()  # `import a.b.c` dotted names
        self.classes: Dict[str, ast.ClassDef] = {}
        self.class_base_exprs: Dict[str, List[ast.expr]] = {}
        # cls -> [(attr, ctor-call func expr)] from __init__ bodies
        self.init_attr_calls: Dict[str, List[Tuple[str, ast.AST]]] = {}


class Program:
    """Whole-program symbol table + call graph over one analyzed set
    of modules. Conservative on dynamic dispatch: a call is an edge
    only when its target resolves statically through

    - same-module defs (bare ``f()``) and ``self.method()`` (including
      inherited methods through resolvable base classes),
    - ``from x import y`` symbols (functions, classes -> ``__init__``,
      ``Class.method`` classmethod-style calls),
    - ``import x`` / ``from pkg import submod`` module-attribute calls
      (``mod.f()``, chasing re-exports through ``__init__`` modules),
    - attributes typed by construction (``self.state = StateStore()``
      in ``__init__`` makes ``self.state.upsert_evals()`` an edge), and
    - locals typed by construction (``h = Harness(); h.submit_plan()``).

    References handed to pools/threads/handler dicts are not followed.
    """

    def __init__(self, modules: List[Module]):
        self.modules = [m for m in modules]
        self.by_rel: Dict[str, Module] = {m.rel: m for m in modules}
        self._infos: Dict[str, _ModInfo] = {}
        self._by_dotted: Dict[str, str] = {}  # dotted -> rel
        self.functions: Dict[FnKey, ast.AST] = {}
        self.classes: Dict[ClsKey, ast.ClassDef] = {}
        self.class_bases: Dict[ClsKey, List[ClsKey]] = {}
        # ClsKey -> attr -> ClsKey (types inferred from __init__ ctors)
        self.attr_types: Dict[ClsKey, Dict[str, ClsKey]] = {}
        self.calls: Dict[FnKey, Set[FnKey]] = {}
        # FnKey -> ClsKey (factory return types, from annotations or
        # ctor-returning bodies: `def get_batcher() -> PlacementBatcher`)
        self.return_types: Dict[FnKey, ClsKey] = {}
        # manifest var name -> {rel: [entries]}
        self.manifests: Dict[str, Dict[str, List[str]]] = {}
        # manifest var name -> {rel: assignment line}
        self.manifest_lines: Dict[str, Dict[str, int]] = {}
        self._build()

    # ------------------------------------------------------- pass 1

    @staticmethod
    def module_dotted(rel: str) -> str:
        p = rel[:-3] if rel.endswith(".py") else rel
        p = p.lstrip("/")
        if p.endswith("/__init__"):
            p = p[: -len("/__init__")]
        return p.replace("/", ".")

    def _build(self) -> None:
        for mod in self.modules:
            info = _ModInfo(mod, self.module_dotted(mod.rel),
                            mod.rel.endswith("/__init__.py"))
            self._infos[mod.rel] = info
            self._by_dotted[info.dotted] = mod.rel
            self._scan_module(info)
        for rel, info in self._infos.items():
            self._resolve_bases(rel, info)
        for rel, info in self._infos.items():
            self._resolve_attr_types(rel, info)
        for key, fn in self.functions.items():
            self._infer_return_type(key, fn)
        for key, fn in self.functions.items():
            self.calls[key] = self._function_calls(key, fn)

    def _scan_module(self, info: _ModInfo) -> None:
        mod = info.mod
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.asname:
                        info.bindings[alias.asname] = ("mod", alias.name)
                    else:
                        info.plain_imports.add(alias.name)
            elif isinstance(node, ast.ImportFrom):
                target = self._import_from_target(info, node)
                if target is None:
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    info.bindings[alias.asname or alias.name] = (
                        "sym", target, alias.name)
        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[(mod.rel, node.name)] = node
            elif isinstance(node, ast.ClassDef):
                info.classes[node.name] = node
                self.classes[(mod.rel, node.name)] = node
                info.class_base_exprs[node.name] = list(node.bases)
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        self.functions[
                            (mod.rel, f"{node.name}.{sub.name}")] = sub
                        if sub.name == "__init__":
                            self._scan_init_attrs(info, node.name, sub)
            elif isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if (isinstance(tgt, ast.Name)
                            and tgt.id.startswith("NTA_")):
                        vals = _string_elems(node.value)
                        if vals:
                            self.manifests.setdefault(
                                tgt.id, {})[mod.rel] = vals
                            self.manifest_lines.setdefault(
                                tgt.id, {})[mod.rel] = node.lineno

    def _import_from_target(self, info: _ModInfo,
                            node: ast.ImportFrom) -> Optional[str]:
        if node.level == 0:
            return node.module
        # Relative: level 1 = this module's package, each extra level
        # pops one more component. For an __init__ module the dotted
        # name (which dropped the "__init__" segment) IS the package.
        parts = info.dotted.split(".")
        base = parts if info.is_pkg else parts[:-1]
        for _ in range(node.level - 1):
            if not base:
                return None
            base = base[:-1]
        if node.module:
            base = base + node.module.split(".")
        return ".".join(base) if base else None

    def _scan_init_attrs(self, info: _ModInfo, cls: str,
                         init: ast.AST) -> None:
        rows = info.init_attr_calls.setdefault(cls, [])
        for stmt in ast.walk(init):
            if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                continue
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            value = stmt.value
            for tgt in targets:
                if not (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    continue
                for call in _ctor_candidates(value):
                    rows.append((tgt.attr, call.func))

    # ------------------------------------------------------- pass 2

    def resolve_module(self, importer_rel: str,
                       dotted: Optional[str]) -> Optional[str]:
        """rel path of the module named `dotted`, preferring an exact
        match, falling back to a unique dotted-suffix match (fixture
        trees are not importable packages — `from helper import nap`
        in a tmp dir must still resolve to the sibling).

        The suffix fallback is ONLY for out-of-repo importers (their
        rel paths are absolute): inside the repo package every import
        resolves exactly (relative imports expand to exact dotted
        names), and suffix-matching there would misresolve stdlib
        imports onto same-named repo modules (`import select` in
        utils/httppool.py must NOT become scheduler/select.py — a
        phantom edge from server-reachable code into scheduler/)."""
        if not dotted:
            return None
        rel = self._by_dotted.get(dotted)
        if rel is not None:
            return rel
        if not importer_rel.startswith("/"):
            return None  # in-repo importer: exact matches only
        suffix = "." + dotted
        cands = [r for d, r in self._by_dotted.items()
                 if d.endswith(suffix)]
        if len(cands) == 1:
            return cands[0]
        if len(cands) > 1:
            # prefer a sibling of the importer
            base = os.path.dirname(importer_rel)
            sibs = [r for r in cands if os.path.dirname(r) == base]
            if len(sibs) == 1:
                return sibs[0]
        return None

    def _resolve_symbol(self, importer_rel: str, mod_dotted: str,
                        name: str, seen: Optional[set] = None):
        """('fn', FnKey) | ('cls', ClsKey) | ('modref', dotted) | None
        for symbol `name` in module `mod_dotted`, chasing re-export
        chains (`from .recorder import record_span` in __init__)."""
        if seen is None:
            seen = set()
        if (mod_dotted, name) in seen:
            return None
        seen.add((mod_dotted, name))
        rel = self.resolve_module(importer_rel, mod_dotted)
        if rel is not None:
            if (rel, name) in self.functions:
                return ("fn", (rel, name))
            if (rel, name) in self.classes:
                return ("cls", (rel, name))
            binding = self._infos[rel].bindings.get(name)
            if binding is not None:
                if binding[0] == "sym":
                    res = self._resolve_symbol(rel, binding[1],
                                               binding[2], seen)
                    if res is not None:
                        return res
                elif binding[0] == "mod":
                    return ("modref", binding[1])
        # `from pkg import submod`: the symbol IS a module
        sub = f"{mod_dotted}.{name}"
        if self.resolve_module(importer_rel, sub) is not None:
            return ("modref", sub)
        return None

    def _resolve_bases(self, rel: str, info: _ModInfo) -> None:
        for cls, base_exprs in info.class_base_exprs.items():
            out: List[ClsKey] = []
            for expr in base_exprs:
                res = self._resolve_class_expr(rel, expr)
                if res is not None:
                    out.append(res)
            self.class_bases[(rel, cls)] = out

    def _resolve_class_expr(self, rel: str,
                            expr: ast.AST) -> Optional[ClsKey]:
        parts = _flatten_attr_chain(expr)
        if not parts:
            return None
        info = self._infos[rel]
        if len(parts) == 1:
            name = parts[0]
            if name in info.classes:
                return (rel, name)
            binding = info.bindings.get(name)
            if binding and binding[0] == "sym":
                res = self._resolve_symbol(rel, binding[1], binding[2])
                if res and res[0] == "cls":
                    return res[1]
            return None
        # module.Class chains
        res = self._resolve_dotted_value(rel, parts)
        if res and res[0] == "cls":
            return res[1]
        return None

    def _resolve_dotted_value(self, rel: str, parts: List[str]):
        """Resolve `a.b.c` value chains through import bindings."""
        info = self._infos[rel]
        binding = info.bindings.get(parts[0])
        if binding is None:
            # plain `import a.b.c` usage: longest module prefix wins
            for k in range(len(parts) - 1, 0, -1):
                dotted = ".".join(parts[:k])
                if any(p == dotted or p.startswith(dotted + ".")
                       for p in info.plain_imports):
                    if self.resolve_module(rel, dotted) is not None:
                        return self._chase_modref(rel, dotted, parts[k:])
            return None
        if binding[0] == "mod":
            return self._chase_modref(rel, binding[1], parts[1:])
        # ("sym", M, orig)
        res = self._resolve_symbol(rel, binding[1], binding[2])
        if res is None:
            return None
        if res[0] == "modref":
            return self._chase_modref(rel, res[1], parts[1:])
        if res[0] == "cls" and len(parts) == 2:
            # ImportedClass.method / ImportedClass.classmethod
            m = self.lookup_method(res[1], parts[1])
            if m is not None:
                return ("fn", m)
            return ("cls_attr", res[1])
        if len(parts) == 1:
            return res
        return None

    def _chase_modref(self, importer_rel: str, dotted: str,
                      rest: List[str]):
        """Walk remaining attribute parts down from a module ref."""
        while len(rest) > 1:
            nxt = f"{dotted}.{rest[0]}"
            if self.resolve_module(importer_rel, nxt) is not None:
                dotted, rest = nxt, rest[1:]
                continue
            break
        if not rest:
            return ("modref", dotted)
        if len(rest) == 1:
            res = self._resolve_symbol(importer_rel, dotted, rest[0])
            return res
        # module.Class.method
        res = self._resolve_symbol(importer_rel, dotted, rest[0])
        if res and res[0] == "cls" and len(rest) == 2:
            m = self.lookup_method(res[1], rest[1])
            if m is not None:
                return ("fn", m)
        return None

    def lookup_method(self, clskey: ClsKey, name: str,
                      seen: Optional[set] = None) -> Optional[FnKey]:
        if seen is None:
            seen = set()
        if clskey in seen:
            return None
        seen.add(clskey)
        rel, cls = clskey
        key = (rel, f"{cls}.{name}")
        if key in self.functions:
            return key
        for base in self.class_bases.get(clskey, ()):
            found = self.lookup_method(base, name, seen)
            if found is not None:
                return found
        return None

    def _resolve_attr_types(self, rel: str, info: _ModInfo) -> None:
        for cls, rows in info.init_attr_calls.items():
            out = self.attr_types.setdefault((rel, cls), {})
            for attr, func_expr in rows:
                res = None
                parts = _flatten_attr_chain(func_expr)
                if parts:
                    if len(parts) == 1 and parts[0] in info.classes:
                        res = (rel, parts[0])
                    else:
                        r = self._resolve_dotted_value(rel, parts)
                        if r and r[0] == "cls":
                            res = r[1]
                if res is not None:
                    out[attr] = res

    # ------------------------------------------------------- pass 3

    def _infer_return_type(self, key: FnKey, fn: ast.AST) -> None:
        """Factory return types: a resolvable `-> Cls` annotation, or
        every-return-is-a-ctor bodies. Lets `get_batcher().place(...)`
        resolve through the singleton accessor."""
        rel, _qual = key
        ann = getattr(fn, "returns", None)
        if ann is not None:
            res = self._resolve_class_expr_or_value(rel, ann)
            if res is not None:
                self.return_types[key] = res
                return
        for node in ast.walk(fn):
            if isinstance(node, ast.Return) and isinstance(
                    node.value, ast.Call):
                res = self._resolve_class_expr_or_value(
                    rel, node.value.func)
                if res is not None:
                    self.return_types[key] = res
                    return

    def _local_types(self, rel: str, cls: Optional[str],
                     fn: ast.AST) -> Dict[str, ClsKey]:
        """Locals typed by construction: `x = Ctor(...)` — or by a
        typed factory: `b = get_batcher()`."""
        out: Dict[str, ClsKey] = {}
        for stmt in ast.walk(fn):
            if not isinstance(stmt, ast.Assign):
                continue
            for call in _ctor_candidates(stmt.value):
                res = self._resolve_class_expr_or_value(rel, call.func)
                if res is None:
                    target = self.resolve_call(rel, cls, call.func)
                    if target is not None:
                        res = self.return_types.get(target)
                if res is None:
                    continue
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        out[tgt.id] = res
        return out

    def _resolve_class_expr_or_value(self, rel: str,
                                     expr: ast.AST) -> Optional[ClsKey]:
        parts = _flatten_attr_chain(expr)
        if not parts:
            return None
        info = self._infos[rel]
        if len(parts) == 1 and parts[0] in info.classes:
            return (rel, parts[0])
        res = (self._resolve_dotted_value(rel, parts)
               if len(parts) > 1 or parts[0] in info.bindings else None)
        if res and res[0] == "cls":
            return res[1]
        return None

    def resolve_call(self, rel: str, cls: Optional[str],
                     func: ast.AST,
                     local_types: Optional[Dict[str, ClsKey]] = None,
                     ) -> Optional[FnKey]:
        """FnKey the call expression `func` targets, or None."""
        if (isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Call)):
            # factory().method(): resolve through the factory's
            # inferred return type
            inner = self.resolve_call(rel, cls, func.value.func,
                                      local_types)
            if inner is not None:
                t = self.return_types.get(inner)
                if t is not None:
                    return self.lookup_method(t, func.attr)
            return None
        parts = _flatten_attr_chain(func)
        if not parts:
            return None
        info = self._infos.get(rel)
        if info is None:
            return None
        if parts[0] == "self" and cls is not None:
            if len(parts) == 2:
                return self.lookup_method((rel, cls), parts[1])
            if len(parts) == 3:
                t = self.attr_types.get((rel, cls), {}).get(parts[1])
                if t is not None:
                    return self.lookup_method(t, parts[2])
            return None
        if local_types and parts[0] in local_types and len(parts) == 2:
            return self.lookup_method(local_types[parts[0]], parts[1])
        if len(parts) == 1:
            name = parts[0]
            if (rel, name) in self.functions:
                return (rel, name)
            if name in info.classes:
                return self.lookup_method((rel, name), "__init__")
            binding = info.bindings.get(name)
            if binding and binding[0] == "sym":
                res = self._resolve_symbol(rel, binding[1], binding[2])
                if res is not None:
                    if res[0] == "fn":
                        return res[1]
                    if res[0] == "cls":
                        return self.lookup_method(res[1], "__init__")
            return None
        res = self._resolve_dotted_value(rel, parts)
        if res is not None:
            if res[0] == "fn":
                return res[1]
            if res[0] == "cls":
                return self.lookup_method(res[1], "__init__")
        return None

    def _function_calls(self, key: FnKey, fn: ast.AST) -> Set[FnKey]:
        rel, qual = key
        cls = qual.split(".")[0] if "." in qual else None
        local_types = self._local_types(rel, cls, fn)
        out: Set[FnKey] = set()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            target = self.resolve_call(rel, cls, node.func, local_types)
            if target is not None and target != key:
                out.add(target)
        return out

    # ------------------------------------------------- reachability

    def manifest_entries(self, var: str) -> List[FnKey]:
        out: List[FnKey] = []
        for rel, quals in self.manifests.get(var, {}).items():
            for q in quals:
                if (rel, q) in self.functions:
                    out.append((rel, q))
        return out

    def reachable_with_paths(
        self, entries: List[FnKey],
    ) -> Dict[FnKey, Tuple[FnKey, Optional[FnKey]]]:
        """BFS closure: fn -> (entry it is reachable from, calling fn
        one step back toward the entry, or None for the entry itself).
        First discovery wins, so chains are shortest-path witnesses."""
        via: Dict[FnKey, Tuple[FnKey, Optional[FnKey]]] = {}
        todo = []
        for e in entries:
            if e in self.functions and e not in via:
                via[e] = (e, None)
                todo.append(e)
        while todo:
            cur = todo.pop(0)
            entry = via[cur][0]
            for nxt in sorted(self.calls.get(cur, ())):
                if nxt not in via:
                    via[nxt] = (entry, cur)
                    todo.append(nxt)
        return via

    def witness_chain(self, via, key: FnKey) -> List[FnKey]:
        """entry -> ... -> key, reconstructed from `via`."""
        chain = [key]
        seen = {key}
        while True:
            parent = via[chain[-1]][1]
            if parent is None or parent in seen:
                break
            chain.append(parent)
            seen.add(parent)
        chain.reverse()
        return chain

    def witness_info(self, via, key: FnKey) -> Tuple[str, List[str]]:
        """(note, related) for a program-rule finding at `key`: the
        entry/chain suffix for the message, and the "path:line"
        witness locations for `Finding.related` — ONE formatting for
        every manifest rule, so --diff region attribution and SARIF
        relatedLocations cannot drift between rules."""
        chain = self.witness_chain(via, key)
        entry = via[key][0]
        note = f": entry '{entry[1]}' ({entry[0]})"
        if len(chain) > 1:
            note += " via " + " -> ".join(q for (_r, q) in chain)
        related = [
            f"{r}:{getattr(self.functions[(r, q)], 'lineno', 0)}"
            for (r, q) in chain]
        return note, related


def _string_elems(node: ast.AST) -> List[str]:
    out = []
    if isinstance(node, (ast.Tuple, ast.List)):
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
    return out


def _ctor_candidates(value: Optional[ast.AST]) -> List[ast.Call]:
    """Call nodes that may type an assignment target: a direct call,
    or the operands of `x or Ctor()` defaulting idioms."""
    if isinstance(value, ast.Call):
        return [value]
    if isinstance(value, ast.BoolOp):
        return [v for v in value.values if isinstance(v, ast.Call)]
    if isinstance(value, ast.IfExp):
        return [v for v in (value.body, value.orelse)
                if isinstance(v, ast.Call)]
    return []


# ------------------------------------------------------- file loading

def _iter_py_files(paths: List[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                out.append(p)
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs
                    if d != "__pycache__" and not d.startswith(".")
                )
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(root, f))
    # de-dup, stable order
    seen = set()
    uniq = []
    for f in out:
        a = os.path.abspath(f)
        if a not in seen:
            seen.add(a)
            uniq.append(f)
    return uniq


def repo_root() -> str:
    """The repository root (two levels above this package)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _rel_path(path: str) -> str:
    root = repo_root()
    ap = os.path.abspath(path)
    if ap.startswith(root + os.sep):
        ap = ap[len(root) + 1:]
    return ap.replace(os.sep, "/")


def _sha1(data: bytes) -> str:
    return hashlib.sha1(data).hexdigest()


# In-process caches. Keyed on content hashes + RULESET_VERSION, never
# on mtimes: the tier-1 test analyzes the tree several times per
# process (gate + non-growing-baseline + per-dir self-checks) and must
# pay the whole-program pass once.
_PARSE_CACHE: Dict[str, tuple] = {}  # abspath -> (sha, Module|None, err)
_LOCAL_CACHE: Dict[tuple, List[Finding]] = {}
_PROGRAM_CACHE: Dict[tuple, List[Finding]] = {}
_REGISTRY_CACHE: Dict[str, tuple] = {}  # tree digest -> (registry, digest)


def clear_caches() -> None:
    _PARSE_CACHE.clear()
    _LOCAL_CACHE.clear()
    _PROGRAM_CACHE.clear()
    _REGISTRY_CACHE.clear()


def _load_file(path: str) -> tuple:
    """(sha, Module|None, parse_error Finding|None), parse-cached."""
    ap = os.path.abspath(path)
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    sha = _sha1(source.encode("utf-8"))
    hit = _PARSE_CACHE.get(ap)
    if hit is not None and hit[0] == sha:
        return hit
    rel = _rel_path(path)
    try:
        entry = (sha, Module(path, rel, source), None)
    except SyntaxError as e:
        entry = (sha, None, Finding(
            "parse-error", rel, e.lineno or 0, (e.offset or 1) - 1,
            f"file does not parse: {e.msg}", "<module>"))
    _PARSE_CACHE[ap] = entry
    return entry


def load_modules(
    paths: List[str],
) -> Tuple[List[Module], List[Finding]]:
    """(parsed modules, parse-error findings). A file that does not
    parse — common for --diff against a mid-edit working tree — is
    reported as a `parse-error` finding, not a crash: scripted
    consumers must be able to tell "findings" from "tool blew up"."""
    mods: List[Module] = []
    errors: List[Finding] = []
    for f in _iter_py_files(paths):
        sha, mod, err = _load_file(f)
        if mod is not None:
            mods.append(mod)
        if err is not None:
            errors.append(err)
    return mods, errors


def _registry_digest(registry) -> str:
    rows = sorted(
        (name, tuple(info.params), tuple(sorted(info.static_names)))
        for name, info in registry.items())
    return _sha1(repr(rows).encode("utf-8"))


def _suppressed(mod: Optional[Module], f: Finding) -> bool:
    if mod is None:
        return False
    # Union, not fallback: a suppression on the opening line of a
    # multi-line simple statement covers findings anywhere inside it,
    # even when an inner line carries its own (different-rule) disable
    # comment.
    disabled = mod.disabled_rules(f.line) | mod.disabled_rules(
        _enclosing_stmt_line(mod, f.line))
    return "all" in disabled or f.rule in disabled


def analyze_paths(paths: List[str],
                  rules: Optional[set] = None) -> List[Finding]:
    """Run every checker over `paths`; returns findings with inline
    `# nta: disable=` suppressions already applied, sorted by
    (path, line, rule).

    Local rules come from the per-file cache when (sha, registry
    digest) match; program rules from the tree-digest cache when no
    analyzed file changed."""
    from . import (compile_surface, deadlock, locks, protocol, purity,
                   residency, robustness, snapshot)

    files = _iter_py_files(paths)
    loaded = [(_load_file(f)) for f in files]
    modules = [m for (_sha, m, _e) in loaded if m is not None]
    parse_errors = [e for (_sha, _m, e) in loaded if e is not None]
    by_rel = {m.rel: m for m in modules}

    tree_digest = _sha1("\n".join(
        f"{m.rel}:{sha}" for (sha, m, _e) in loaded
        if m is not None).encode("utf-8"))
    reg_hit = _REGISTRY_CACHE.get(tree_digest)
    if reg_hit is None:
        registry = purity.build_jit_registry(modules)
        reg_hit = (registry, _registry_digest(registry))
        _REGISTRY_CACHE[tree_digest] = reg_hit
    registry, reg_digest = reg_hit

    findings: List[Finding] = list(parse_errors)

    # ---- local pass (per-file cache)
    for (sha, mod, _err), path in zip(loaded, files):
        if mod is None:
            continue
        key = (os.path.abspath(path), sha, reg_digest, RULESET_VERSION)
        cached = _LOCAL_CACHE.get(key)
        if cached is None:
            local: List[Finding] = []
            local.extend(locks.check(mod))
            local.extend(purity.check(mod, registry))
            local.extend(snapshot.check(mod))
            local.extend(robustness.check(mod))
            local.extend(residency.check(mod))
            cached = [f for f in local if not _suppressed(mod, f)]
            _LOCAL_CACHE[key] = cached
        findings.extend(cached)

    # ---- program pass (tree-digest cache). Skipped outright when the
    # rules filter excludes every program rule:
    # building the cross-module graph to discard its findings is the
    # most expensive no-op in the suite.
    program_rules = {"dispatcher-blocking-call", "record-path-blocking",
                     "unbounded-wait", "deadlock-cycle", "raft-funnel",
                     "unbucketed-shape", "static-key-drift",
                     "unregistered-jit", "donation-unsafe-read"}
    if rules is not None and not (rules & program_rules):
        findings = [f for f in findings if f.rule in rules]
        findings.sort(key=lambda f: (f.path, f.line, f.rule))
        return findings
    pkey = (tree_digest, RULESET_VERSION)
    prog_findings = _PROGRAM_CACHE.get(pkey)
    if prog_findings is None:
        program = Program(modules)
        raw: List[Finding] = []
        raw.extend(locks.program_check(program))
        raw.extend(robustness.program_check(program))
        raw.extend(deadlock.program_check(program))
        raw.extend(protocol.program_check(program))
        raw.extend(compile_surface.program_check(program))
        prog_findings = [f for f in raw
                         if not _suppressed(by_rel.get(f.path), f)]
        _PROGRAM_CACHE[pkey] = prog_findings
    findings.extend(prog_findings)

    if rules is not None:
        findings = [f for f in findings if f.rule in rules]
    findings = list(findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def _enclosing_stmt_line(mod: Module, lineno: int) -> int:
    """Opening line of the innermost SIMPLE statement spanning
    `lineno`. Compound statements (with/if/for/def...) are excluded on
    purpose: a suppression on `with lock:` must not blanket the whole
    body."""
    best = None
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.stmt) or isinstance(
                node, (ast.With, ast.If, ast.For, ast.While, ast.Try,
                       ast.FunctionDef, ast.AsyncFunctionDef,
                       ast.ClassDef)):
            continue
        start = getattr(node, "lineno", None)
        end = getattr(node, "end_lineno", None)
        if start is None or end is None:
            continue
        # Innermost span wins = the latest opening line that still
        # covers the finding.
        if start <= lineno <= end and (best is None or start > best):
            best = start
    return best if best is not None else lineno


# ------------------------------------------------------ disk cache
#
# Cross-process reuse for the CLI (`tools/ntalint.py`): local findings
# per (rel, sha, registry digest), program findings per tree digest.
# The cache can only SKIP work whose inputs hash identically under the
# same RULESET_VERSION — a version bump or any content change falls
# back to a full compute, so a poisoned cache at worst costs time.

def load_disk_cache(path: str) -> None:
    """Prime the in-process caches from a cache file. Best-effort in
    the strongest sense: a missing, truncated, corrupted or
    old-schema cache primes nothing (and at worst costs a recompute)
    — it must never crash the CLI."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
        if data.get("version") != RULESET_VERSION:
            return
        root = repo_root()
        for rel, ent in data.get("local", {}).items():
            ap = os.path.join(root, rel.replace("/", os.sep))
            key = (os.path.abspath(ap), ent["sha"], ent["registry"],
                   RULESET_VERSION)
            _LOCAL_CACHE.setdefault(key, [
                Finding.from_dict(d) for d in ent["findings"]])
        prog = data.get("program")
        if isinstance(prog, dict):
            for digest, ent in prog.items():
                if not isinstance(ent, list):
                    continue  # pre-PR-review schema: skip
                _PROGRAM_CACHE.setdefault(
                    (digest, RULESET_VERSION),
                    [Finding.from_dict(d) for d in ent])
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        clear_caches()  # half-primed state is worse than cold


def save_disk_cache(path: str) -> None:
    """Serialize the in-process caches for the next CLI run."""
    root = os.path.abspath(repo_root())
    local = {}
    for (ap, sha, reg, _ver), fnds in _LOCAL_CACHE.items():
        if not ap.startswith(root + os.sep):
            continue  # fixture/tmp files: not worth persisting
        rel = ap[len(root) + 1:].replace(os.sep, "/")
        local[rel] = {"sha": sha, "registry": reg,
                      "findings": [f.to_dict() for f in fnds]}
    # Every digest entry survives: one CLI process may analyze several
    # path subsets (a loaded full-tree entry plus this run's ops/
    # subset), and keeping only the last would evict the expensive
    # full-tree entry. Entries are digest-keyed, so extras are inert.
    program = {
        digest: [f.to_dict() for f in fnds]
        for (digest, _ver), fnds in _PROGRAM_CACHE.items()
    }
    data = {"version": RULESET_VERSION, "local": local,
            "program": program}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(data, fh)
    os.replace(tmp, path)


# ---------------------------------------------------------------- baseline

def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path: Optional[str] = None) -> List[dict]:
    path = path or default_baseline_path()
    if not os.path.exists(path):
        return []
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return list(data.get("findings", []))


def apply_baseline(
    findings: List[Finding], baseline: List[dict]
) -> Tuple[List[Finding], List[dict]]:
    """Split `findings` against the baseline. Returns
    (new_findings, stale_entries): a baseline entry absorbs up to
    `count` (default 1) findings with its (rule, path, symbol); entries
    that absorb nothing are STALE — the finding they recorded was fixed
    and the entry must be deleted (non-growing baseline)."""
    budget: Dict[Tuple[str, str, str], int] = {}
    for ent in baseline:
        key = (ent["rule"], ent["path"], ent.get("symbol", ""))
        budget[key] = budget.get(key, 0) + int(ent.get("count", 1))
    used: Dict[Tuple[str, str, str], int] = {k: 0 for k in budget}
    new: List[Finding] = []
    for f in findings:
        k = f.key()
        if budget.get(k, 0) > used.get(k, 0):
            used[k] += 1
        else:
            new.append(f)
    # Staleness is judged per KEY (entries sharing a key pooled their
    # counts above), reported once on the key's first entry — judging
    # per entry would call a sibling stale when the first one already
    # accounted for the key's findings.
    stale: List[dict] = []
    reported = set()
    for ent in baseline:
        key = (ent["rule"], ent["path"], ent.get("symbol", ""))
        if key in reported:
            continue
        reported.add(key)
        have = used.get(key, 0)
        want = budget.get(key, 0)
        if have == 0:
            stale.append(ent)
        elif want > have:
            # partial staleness: more budget than findings
            over = dict(ent)
            over["stale_count"] = want - have
            stale.append(over)
    return new, stale


def write_baseline(findings: List[Finding],
                   path: Optional[str] = None) -> str:
    """Serialize current findings as the new baseline (counts folded
    per (rule, path, symbol))."""
    path = path or default_baseline_path()
    counts: Dict[Tuple[str, str, str], int] = {}
    for f in findings:
        counts[f.key()] = counts.get(f.key(), 0) + 1
    entries = [
        {"rule": r, "path": p, "symbol": s, "count": c}
        for (r, p, s), c in sorted(counts.items())
    ]
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"findings": entries}, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return path
