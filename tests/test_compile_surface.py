"""ntalint compile-surface rules (nomad_tpu/analysis/compile_surface):
per-rule TP/TN/suppression fixtures with asserted witness chains, the
jit-registry introspection test (the static NTA_JIT_ACCOUNTED manifest,
the AST scan of ops//kernels//models//parallel/, and the runtime
jit_cache_size() registry must agree), the real-tree self-checks (all
four rules clean with an EMPTY baseline — findings there are fixed,
never baselined).

Fixture sets per rule are analyzed in separate directories: an
NTA_JIT_ACCOUNTED manifest anywhere in an analyzed set arms
unregistered-jit for every in-scope module of that set (by design —
and why the manifest-free sets double as the inert-without-manifest
true negative).
"""

import json
import os
import subprocess
import sys

from nomad_tpu.analysis import analyze_paths, load_baseline
from nomad_tpu.analysis.core import Module, repo_root
from nomad_tpu.analysis.compile_surface import (
    JIT_SCOPE_MARKERS,
    RULE_DONATION,
    RULE_KEY_DRIFT,
    RULE_UNBUCKETED,
    RULE_UNREGISTERED,
    scan_jit_entry_points,
)

REPO = repo_root()

COMPILE_SURFACE_RULES = (RULE_UNBUCKETED, RULE_KEY_DRIFT,
                         RULE_UNREGISTERED, RULE_DONATION)


def run_dir(tmp_path, files, subdir="ops"):
    """Write {name: source} under tmp_path/<subdir>/ (the scope marker
    the compile-surface rules enforce in) and analyze the tree."""
    d = tmp_path / subdir
    d.mkdir(parents=True, exist_ok=True)
    for name, src in files.items():
        (d / name).write_text(src)
    return analyze_paths([str(d)])


def rules_of(findings):
    return [f.rule for f in findings]


def only(findings, rule):
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------
# unbucketed-shape


JIT_KERNEL = """\
import jax

@jax.jit
def program(util):
    return util.sum()
"""

SHAPES_BAD = """\
import numpy as np

def build_util(nodes, sink):
    sink.util = np.zeros((len(nodes), 4), np.float32)
"""

DRIVER = """\
from kernel import program
from shapes import build_util

def place(nodes, sink):
    build_util(nodes, sink)
    return program(sink.util)
"""

SHAPES_BUCKETED = """\
import numpy as np
from sizes import bucket_size

def build_util(nodes, sink):
    n = bucket_size(len(nodes))
    sink.util = np.zeros((n, 4), np.float32)
"""

SIZES = """\
def bucket_size(n, buckets=(8, 64, 512)):
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]
"""

DIRECT_PASS_BAD = """\
import jax
import numpy as np

@jax.jit
def program(util):
    return util.sum()

def score(jobs):
    ask = np.zeros(len(jobs), np.float32)
    return program(ask)
"""

LOCAL_HOST_OK = """\
import jax
import numpy as np

@jax.jit
def program(util):
    return util.sum()

def tally(jobs, util):
    # Locally-consumed host mask: raw len() shape never escapes
    # toward the device, so it is not a compile key.
    mask = np.zeros(len(jobs), bool)
    out = program(util)
    return int(mask.sum()) + float(out)
"""

MANIFEST_SIZER_OK = """\
import jax
import numpy as np

NTA_BUCKET_FNS = ("pad_rows",)

@jax.jit
def program(util):
    return util.sum()

def pad_rows(n):
    p = 8
    while p < n:
        p *= 2
    return p

def score(jobs):
    ask = np.zeros(pad_rows(len(jobs)), np.float32)
    return program(ask)
"""


def test_unbucketed_fires_with_cross_module_witness_chain(tmp_path):
    findings = run_dir(tmp_path, {"kernel.py": JIT_KERNEL,
                                  "shapes.py": SHAPES_BAD,
                                  "driver.py": DRIVER})
    assert rules_of(findings) == [RULE_UNBUCKETED]
    f = findings[0]
    assert f.path.endswith("shapes.py") and f.line == 4
    # The witness chain: reachability entry (the jit-calling driver)
    # plus the flagged helper's def site.
    assert "entry 'place'" in f.message
    assert "via place -> build_util" in f.message
    assert any(r.endswith("driver.py:4") for r in f.related)
    assert any(r.endswith("shapes.py:3") for r in f.related)


def test_unbucketed_fires_on_direct_jit_arg(tmp_path):
    findings = run_dir(tmp_path, {"mod.py": DIRECT_PASS_BAD})
    assert rules_of(findings) == [RULE_UNBUCKETED]
    assert "passed to 'program'" in findings[0].message
    # The reported site is the dirty array reference AT the call.
    assert findings[0].line == 10


def test_unbucketed_quiet_when_routed_through_bucket_size(tmp_path):
    assert run_dir(tmp_path, {"kernel.py": JIT_KERNEL,
                              "shapes.py": SHAPES_BUCKETED,
                              "sizes.py": SIZES,
                              "driver.py": DRIVER}) == []


def test_unbucketed_quiet_on_local_host_array(tmp_path):
    assert run_dir(tmp_path, {"mod.py": LOCAL_HOST_OK}) == []


def test_unbucketed_quiet_on_manifest_registered_sizer(tmp_path):
    assert run_dir(tmp_path, {"mod.py": MANIFEST_SIZER_OK}) == []


def test_unbucketed_out_of_scope_dir(tmp_path):
    # server/ is not on the device-feeding path.
    assert run_dir(tmp_path, {"kernel.py": JIT_KERNEL,
                              "shapes.py": SHAPES_BAD,
                              "driver.py": DRIVER},
                   subdir="server") == []


def test_unbucketed_inline_suppression(tmp_path):
    src = SHAPES_BAD.replace(
        "np.float32)",
        "np.float32)  # nta: disable=unbucketed-shape", 1)
    assert run_dir(tmp_path, {"kernel.py": JIT_KERNEL,
                              "shapes.py": src,
                              "driver.py": DRIVER}) == []


# ---------------------------------------------------------------------
# static-key-drift


DRIFT = """\
import jax
from functools import partial

@partial(jax.jit, static_argnames=("mode",))
def run(matrix, mode):
    return matrix.sum()

def bad_fstring(matrix, n):
    return run(matrix, mode=f"dense-{n}")

def bad_positional_computed(matrix, name):
    return run(matrix, "dense-" + name)

def good_attribute(matrix, cfg):
    return run(matrix, mode=cfg.mode)

def good_constant(matrix):
    return run(matrix, mode="dense")

def good_factory(matrix, cfg):
    # Opaque calls are sanctioned: routing statics through a config
    # factory (build_placement_config) is always clean.
    return run(matrix, mode=make_mode(cfg))

def make_mode(cfg):
    return cfg.mode
"""


def test_key_drift_fires_on_per_eval_keys(tmp_path):
    findings = run_dir(tmp_path, {"mod.py": DRIFT})
    assert rules_of(findings) == [RULE_KEY_DRIFT] * 2
    fstr, computed = findings
    assert fstr.line == 9 and "f-string" in fstr.message
    assert computed.line == 12 and "computed value" in computed.message
    # Both point back at the jitted def (the witness for "which cache
    # does this key mint entries in").
    for f in findings:
        assert "'mode'" in f.message and "'run'" in f.message
        assert len(f.related) == 1 and f.related[0].endswith("mod.py:5")


def test_key_drift_inline_suppression(tmp_path):
    src = DRIFT.replace(
        'mode=f"dense-{n}")',
        'mode=f"dense-{n}")  # nta: disable=static-key-drift', 1)
    findings = run_dir(tmp_path, {"mod.py": src})
    assert [f.line for f in findings] == [12]


# ---------------------------------------------------------------------
# unregistered-jit


REGISTRY = """\
import jax

NTA_JIT_ACCOUNTED = ("solve",)

@jax.jit
def solve(x):
    return x * 2

@jax.jit
def rogue(x):
    return x + 1
"""

REGISTRY_LRU = """\
from functools import lru_cache

NTA_JIT_ACCOUNTED = ("solve",)

@lru_cache(maxsize=64)
def plan(n):
    return n * 2
"""

REGISTRY_FACTORY = """\
import jax

NTA_JIT_ACCOUNTED = ("make_program",)

def make_program(mesh):
    def mapped(x):
        return x.sum()
    return jax.jit(mapped)
"""


def test_unregistered_jit_fires_with_manifest_witness(tmp_path):
    findings = run_dir(tmp_path, {"mod.py": REGISTRY})
    assert rules_of(findings) == [RULE_UNREGISTERED]
    f = findings[0]
    assert f.symbol == "rogue" and f.line == 10
    assert "jit_cache_size()" in f.message
    # related names the manifest declaration site.
    assert len(f.related) == 1 and f.related[0].endswith("mod.py:3")


def test_unregistered_jit_fires_on_lru_cache(tmp_path):
    findings = run_dir(tmp_path, {"mod.py": REGISTRY_LRU})
    assert rules_of(findings) == [RULE_UNREGISTERED]
    assert findings[0].symbol == "plan"
    assert "lru_cache" in findings[0].message


def test_unregistered_jit_accounts_nested_factory_jit_to_owner(tmp_path):
    # A jit call nested in a module-level factory is ONE cache owned
    # by the factory (parallel/shard.py's sharded_base_delta) — the
    # manifest registers the factory name and the rule is satisfied.
    assert run_dir(tmp_path, {"mod.py": REGISTRY_FACTORY}) == []


def test_unregistered_jit_inert_without_manifest(tmp_path):
    # Analyzing a subset with no NTA_JIT_ACCOUNTED module must not
    # flag every jit in sight (fixture dirs, single-module runs).
    src = REGISTRY.replace('NTA_JIT_ACCOUNTED = ("solve",)\n', "")
    assert run_dir(tmp_path, {"mod.py": src}) == []


def test_unregistered_jit_out_of_scope_dir(tmp_path):
    assert run_dir(tmp_path, {"mod.py": REGISTRY},
                   subdir="server") == []


def test_unregistered_jit_inline_suppression(tmp_path):
    src = REGISTRY.replace("def rogue(x):",
                           "def rogue(x):  # nta: disable=unregistered-jit")
    assert run_dir(tmp_path, {"mod.py": src}) == []


# ---------------------------------------------------------------------
# donation-unsafe-read


DONATE = """\
import jax
from functools import partial

@partial(jax.jit, donate_argnums=(0,))
def update(state, delta):
    return state + delta

def bad(state, delta):
    new = update(state, delta)
    return state.sum() + new

def good_rebind(state, delta):
    state = update(state, delta)
    return state.sum()

def good_read_before(state, delta):
    total = state.sum()
    return update(state, delta) + total
"""

DONATE_ARGNAMES = """\
import jax

@jax.jit(donate_argnames=("state",))
def update(state, delta):
    return state + delta

def bad(state, delta):
    new = update(delta=delta, state=state)
    return float(state[0])
"""


def test_donation_read_after_donated_call_fires(tmp_path):
    findings = run_dir(tmp_path, {"mod.py": DONATE})
    assert rules_of(findings) == [RULE_DONATION]
    f = findings[0]
    assert f.symbol == "bad" and f.line == 10
    assert "'state'" in f.message
    # Witnesses: the donating jit def and the donating call site.
    assert [r.rsplit(":", 1)[1] for r in f.related] == ["5", "9"]


def test_donation_tracks_donate_argnames_kwargs(tmp_path):
    findings = run_dir(tmp_path, {"mod.py": DONATE_ARGNAMES})
    assert rules_of(findings) == [RULE_DONATION]
    assert findings[0].line == 9


def test_donation_quiet_on_rebind_and_read_before(tmp_path):
    src = DONATE.replace(
        "def bad(state, delta):\n"
        "    new = update(state, delta)\n"
        "    return state.sum() + new\n", "")
    assert run_dir(tmp_path, {"mod.py": src}) == []


def test_donation_inline_suppression(tmp_path):
    src = DONATE.replace(
        "    return state.sum() + new",
        "    return state.sum() + new  # nta: disable=donation-unsafe-read")
    assert run_dir(tmp_path, {"mod.py": src}) == []


def test_real_tree_is_donation_free_by_construction():
    """PR 6 deliberately does NOT donate resident parents (the base
    stays alive across delta clones); the rule's registry must be
    empty on the real tree — this is the TN self-check and the rail
    for ROADMAP item 3's donated cohort programs."""
    hits = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "nomad_tpu")):
        if os.sep + "analysis" in root:
            continue  # the checker itself names the kwargs
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path, "r", encoding="utf-8") as fh:
                if "donate_arg" in fh.read():
                    hits.append(path)
    assert hits == [], f"donation appeared outside the rail: {hits}"


# ---------------------------------------------------------------------
# real-tree self-checks: zero compile-surface findings, EMPTY baseline.


def _tree_findings():
    return analyze_paths([os.path.join(REPO, "nomad_tpu")])


def test_real_tree_clean_for_all_compile_surface_rules():
    offenders = [f for f in _tree_findings()
                 if f.rule in COMPILE_SURFACE_RULES]
    assert offenders == [], "\n".join(f.render() for f in offenders)


def test_compile_surface_rules_never_baselined():
    assert [e for e in load_baseline()
            if e["rule"] in COMPILE_SURFACE_RULES] == []


# ---------------------------------------------------------------------
# the jit-registry introspection: manifest == static scan, and the
# runtime jit_cache_size() registry covers it.


def _scan_real_entry_points():
    names = {}
    for marker in JIT_SCOPE_MARKERS:
        base = os.path.join(REPO, "nomad_tpu", marker.strip("/"))
        for root, _dirs, files in os.walk(base):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(root, fn)
                rel = os.path.relpath(path, REPO).replace(os.sep, "/")
                with open(path, "r", encoding="utf-8") as fh:
                    mod = Module(path, rel, fh.read())
                for ep in scan_jit_entry_points(mod):
                    names.setdefault(ep.name, ep)
    return names


def test_jit_manifest_matches_static_scan_both_ways():
    """NTA_JIT_ACCOUNTED must equal the AST scan of every jit /
    lru_cache entry point in ops//kernels//models//parallel/ — a
    missing entry is a blind compile cache (the rule catches that
    direction on the tree), and a STALE entry is a manifest lying
    about coverage (only this diff catches that one)."""
    from nomad_tpu.ops import binpack

    scanned = set(_scan_real_entry_points())
    declared = set(binpack.NTA_JIT_ACCOUNTED)
    assert scanned == declared, (
        f"unaccounted: {sorted(scanned - declared)}; "
        f"stale manifest entries: {sorted(declared - scanned)}")


def test_jit_manifest_matches_runtime_cache_accounting():
    """Every decorated entry point the manifest declares is accounted
    by jit_cache_size(): the direct registry covers the decorated
    defs, and the two parallel/shard.py program factories (nested
    jax.jit per mesh) are accounted via shard_cache_size()."""
    from nomad_tpu.ops import binpack
    from nomad_tpu.parallel import shard

    declared = set(binpack.NTA_JIT_ACCOUNTED)
    direct = {getattr(fn, "__name__", "?")
              for fn in binpack._jit_entry_points()}
    assert direct <= declared
    factories = declared - direct
    assert factories == {"sharded_base_delta", "sharded_group_capacity"}
    for name in factories:
        assert callable(getattr(shard, name))
    assert callable(shard.shard_cache_size)
    # and jit_cache_size() composes both accountings without devices.
    assert binpack.jit_cache_size() >= 0


# ---------------------------------------------------------------------
# SARIF: compile-surface findings ride the witness chain out as
# relatedLocations (what CI annotates).


def test_cli_sarif_carries_compile_surface_witness_chain(tmp_path):
    d = tmp_path / "ops"
    d.mkdir()
    (d / "kernel.py").write_text(JIT_KERNEL)
    (d / "shapes.py").write_text(SHAPES_BAD)
    (d / "driver.py").write_text(DRIVER)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ntalint.py"),
         "--sarif", "--no-baseline", "--no-cache", str(d)],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 1, res.stderr
    sarif = json.loads(res.stdout)
    results = sarif["runs"][0]["results"]
    assert [r["ruleId"] for r in results] == [RULE_UNBUCKETED]
    related = results[0]["relatedLocations"]
    uris = [loc["physicalLocation"]["artifactLocation"]["uri"]
            for loc in related]
    assert any(u.endswith("driver.py") for u in uris)
