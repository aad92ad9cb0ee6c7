"""ntalint (nomad_tpu/analysis): per-rule fixture tests — each rule
fires exactly where expected (true positive) and stays quiet on the
sanctioned pattern (true negative) — plus the tier-1 gate: the whole
`nomad_tpu/` tree must be clean modulo the committed baseline, the
baseline must be non-growing (no stale entries), and the dirs the
concurrency core lives in (dispatch/, scheduler/, ops/, parallel/)
must carry NO baseline entries at all: findings there are fixed, not
recorded."""

import json
import os
import subprocess
import sys

from nomad_tpu.analysis import (
    analyze_paths,
    apply_baseline,
    load_baseline,
)
from nomad_tpu.analysis.core import repo_root

REPO = repo_root()


def run_on(tmp_path, source, name="mod.py", subdir=""):
    d = tmp_path / subdir if subdir else tmp_path
    d.mkdir(parents=True, exist_ok=True)
    f = d / name
    f.write_text(source)
    return analyze_paths([str(f)])


def rules_of(findings):
    return [f.rule for f in findings]


def lines_of(findings, rule):
    return [f.line for f in findings if f.rule == rule]


# ---------------------------------------------------------------------
# lock discipline: guarded-by


GUARDED_BAD = """\
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.count = 0  # guarded-by: _lock
        self.free = 0

    def bump(self):
        self.count += 1

    def peek(self):
        return self.count
"""

GUARDED_GOOD = """\
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.count = 0  # guarded-by: _lock
        self.free = 0

    def bump(self):
        with self._lock:
            self.count += 1
        self.free += 1

    def bump_via_cond(self):
        # Condition(self._lock) aliases the lock: holding the cond IS
        # holding the lock.
        with self._cond:
            self.count += 1
"""


def test_guarded_by_fires_on_unlocked_access(tmp_path):
    findings = run_on(tmp_path, GUARDED_BAD)
    assert rules_of(findings) == ["guarded-by", "guarded-by"]
    assert lines_of(findings, "guarded-by") == [11, 14]


def test_guarded_by_quiet_under_lock_and_cond_alias(tmp_path):
    assert run_on(tmp_path, GUARDED_GOOD) == []


def test_guarded_by_inline_suppression(tmp_path):
    src = GUARDED_BAD.replace(
        "        self.count += 1",
        "        self.count += 1  # nta: disable=guarded-by", 1)
    findings = run_on(tmp_path, src)
    assert lines_of(findings, "guarded-by") == [14]


# ---------------------------------------------------------------------
# lock discipline: blocking call under a lock


LOCK_BLOCKING_BAD = """\
import threading
import time

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._other = threading.Event()

    def slow(self):
        with self._lock:
            time.sleep(0.5)

    def foreign_wait(self):
        with self._lock:
            self._other.wait()
"""

LOCK_BLOCKING_GOOD = """\
import threading
import time

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)

    def parked(self):
        # cond.wait on the HELD cond's own lock releases it: exempt.
        with self._cond:
            self._cond.wait(0.5)

    def slow(self):
        time.sleep(0.5)  # no lock held: fine
"""


def test_lock_blocking_fires_inside_lock(tmp_path):
    findings = run_on(tmp_path, LOCK_BLOCKING_BAD)
    assert rules_of(findings) == ["lock-blocking-call"] * 2
    assert lines_of(findings, "lock-blocking-call") == [11, 15]


def test_lock_blocking_quiet_on_own_cond_wait(tmp_path):
    assert run_on(tmp_path, LOCK_BLOCKING_GOOD) == []


# ---------------------------------------------------------------------
# lock discipline: dispatcher-thread entrypoints never block


DISPATCHER_BAD = """\
import threading
import time

NTA_DISPATCHER_ENTRYPOINTS = ("Pipe._run",)

class Pipe:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)

    def _run(self):
        while True:
            self._accumulate()
            self._launch()

    def _accumulate(self):
        with self._cond:
            self._cond.wait(0.1)

    def _launch(self):
        self._wait_for_index(7)

    def _wait_for_index(self, index):
        time.sleep(0.01)
"""

DISPATCHER_GOOD = """\
import threading
import time

NTA_DISPATCHER_ENTRYPOINTS = ("Pipe._run",)

class Pipe:
    def __init__(self, pool):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.pool = pool

    def _run(self):
        while True:
            self._accumulate()
            # handed to a stage thread, not called: not followed
            self.pool.submit(self._launch)

    def _accumulate(self):
        with self._cond:
            self._cond.wait(0.1)

    def _launch(self):
        self._wait_for_index(7)

    def _wait_for_index(self, index):
        time.sleep(0.01)
"""


def test_dispatcher_blocking_fires_through_call_chain(tmp_path):
    findings = run_on(tmp_path, DISPATCHER_BAD)
    assert rules_of(findings) == ["dispatcher-blocking-call"]
    # the sleep inside _wait_for_index, reached via _run -> _launch
    assert findings[0].symbol == "Pipe._wait_for_index"
    assert findings[0].line == 24


def test_dispatcher_quiet_when_blocking_moves_to_stage_thread(tmp_path):
    assert run_on(tmp_path, DISPATCHER_GOOD) == []


# ---------------------------------------------------------------------
# trace purity: impure calls


IMPURE_BAD = """\
import random
import time
import jax

@jax.jit
def f(x):
    return x * random.random() + time.time()
"""

IMPURE_GOOD = """\
import random
import jax
import jax.numpy as jnp

@jax.jit
def f(x, key):
    return x + jax.random.uniform(key, x.shape)

def host(rng):
    # not traced: host-side RNG is fine
    return random.Random(7).random() + rng.getrandbits(31)
"""


def test_impure_call_fires_in_traced_fn(tmp_path):
    findings = run_on(tmp_path, IMPURE_BAD)
    assert rules_of(findings) == ["trace-impure-call"] * 2


def test_impure_quiet_on_jax_random_and_host_code(tmp_path):
    assert run_on(tmp_path, IMPURE_GOOD) == []


# ---------------------------------------------------------------------
# trace purity: host sync


HOST_SYNC_BAD = """\
import jax
import numpy as np

@jax.jit
def f(x):
    y = np.asarray(x)
    return float(x) + y.sum()
"""

HOST_SYNC_GOOD = """\
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def f(x):
    n = float(x.shape[0])  # shape is static under trace
    return jnp.asarray(x) * n

def host(x):
    return np.asarray(x)  # not traced
"""


def test_host_sync_fires_on_numpy_and_float(tmp_path):
    findings = run_on(tmp_path, HOST_SYNC_BAD)
    assert rules_of(findings) == ["trace-host-sync"] * 2


def test_host_sync_quiet_on_shapes_and_host_code(tmp_path):
    assert run_on(tmp_path, HOST_SYNC_GOOD) == []


# ---------------------------------------------------------------------
# trace purity: closure mutation


CLOSURE_BAD = """\
import jax

class Kernel:
    def run(self, xs):
        hits = []

        def body(carry, x):
            hits.append(x)
            self.calls = 1
            return carry + x, x

        return jax.lax.scan(body, 0.0, xs)
"""

CLOSURE_GOOD = """\
import jax

def run(xs):
    def body(carry, x):
        acc = []
        acc.append(x)  # local: trace-time only but self-contained
        return carry + x, x

    return jax.lax.scan(body, 0.0, xs)
"""


def test_closure_mutation_fires(tmp_path):
    findings = run_on(tmp_path, CLOSURE_BAD)
    assert sorted(rules_of(findings)) == ["trace-closure-mutation"] * 2


def test_closure_mutation_quiet_on_locals(tmp_path):
    assert run_on(tmp_path, CLOSURE_GOOD) == []


# ---------------------------------------------------------------------
# trace purity: python branch on traced values


BRANCH_BAD = """\
import jax

@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
"""

BRANCH_GOOD = """\
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg):
    if cfg:  # static arg: branch resolves at trace time
        return jnp.where(x > 0, x, -x)
    n = x.shape[0]
    if n > 2:  # shape-derived: static under trace
        return x
    return -x
"""


def test_branch_fires_on_traced_test(tmp_path):
    findings = run_on(tmp_path, BRANCH_BAD)
    assert rules_of(findings) == ["trace-python-branch"]


def test_branch_quiet_on_static_and_shape_tests(tmp_path):
    assert run_on(tmp_path, BRANCH_GOOD) == []


# ---------------------------------------------------------------------
# trace purity: unhashable static args at jit call sites


STATIC_BAD = """\
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg):
    return x

def caller(x):
    return f(x, cfg=[1, 2])
"""

STATIC_GOOD = """\
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg):
    return x

def caller(x, cfg):
    f(x, cfg=(1, 2))
    return f(x, cfg)
"""


def test_unhashable_static_fires_on_list_literal(tmp_path):
    findings = run_on(tmp_path, STATIC_BAD)
    assert rules_of(findings) == ["jit-unhashable-static"]


def test_unhashable_static_quiet_on_tuple_and_names(tmp_path):
    assert run_on(tmp_path, STATIC_GOOD) == []


# ---------------------------------------------------------------------
# snapshot discipline


SNAPSHOT_BAD = """\
class Sched:
    def plan(self):
        nodes = self.server.fsm.state.nodes()
        store = self.server.fsm.state
        return nodes, store
"""

SNAPSHOT_GOOD = """\
class Sched:
    def plan(self):
        snap = self.server.fsm.state.snapshot()
        idx = self.server.fsm.state.latest_index()
        return snap.nodes(), idx
"""


def test_live_state_read_fires_in_scheduler_dir(tmp_path):
    findings = run_on(tmp_path, SNAPSHOT_BAD, subdir="scheduler")
    assert rules_of(findings) == ["live-state-read"] * 2


def test_live_state_quiet_on_snapshot_handles(tmp_path):
    assert run_on(tmp_path, SNAPSHOT_GOOD, subdir="dispatch") == []


def test_live_state_out_of_scope_dirs_ignored(tmp_path):
    # the rule is scoped: server-side code MAY touch the live store
    assert run_on(tmp_path, SNAPSHOT_BAD, subdir="server") == []


# ---------------------------------------------------------------------
# baseline machinery


def test_apply_baseline_absorbs_and_reports_stale(tmp_path):
    findings = run_on(tmp_path, GUARDED_BAD)
    assert len(findings) == 2
    baseline = [
        {"rule": "guarded-by", "path": findings[0].path,
         "symbol": "C.bump", "count": 1},
        {"rule": "guarded-by", "path": findings[0].path,
         "symbol": "C.gone_function", "count": 1},
    ]
    new, stale = apply_baseline(findings, baseline)
    # C.bump absorbed; C.peek is new; C.gone_function is stale
    assert [f.symbol for f in new] == ["C.peek"]
    assert [e["symbol"] for e in stale] == ["C.gone_function"]


def test_apply_baseline_count_is_a_ceiling(tmp_path):
    findings = run_on(tmp_path, GUARDED_BAD)
    path = findings[0].path
    baseline = [{"rule": "guarded-by", "path": path, "symbol": "C.bump",
                 "count": 3}]
    new, stale = apply_baseline(findings, baseline)
    assert [f.symbol for f in new] == ["C.peek"]
    # over-budgeted entry is partially stale (2 of 3 unused)
    assert stale and stale[0].get("stale_count") == 2


# ---------------------------------------------------------------------
# device residency: full-matrix-reship


RESHIP_BAD = """\
import jax
import numpy as np

class Batcher:
    def place(self, state):
        # Full matrix re-shipped per batch: the regression the
        # resident design removed.
        dev = jax.device_put(np.zeros((1024, 4)))
        return dev

def upload(base):
    return device_resident(*base)
"""

RESHIP_GOOD = """\
import jax
import numpy as np

NTA_REBUILD_ENTRYPOINTS = ("Batcher._build_device_base",)

class Batcher:
    def _build_device_base(self, token, base, delta):
        # The ONE sanctioned full-upload path (first touch + the
        # staleness-rebuild safety net).
        return jax.device_put(base)

    def place(self, state):
        return self._build_device_base(state, None, None)
"""


def test_reship_flags_transfers_outside_manifest(tmp_path):
    findings = run_on(tmp_path, RESHIP_BAD, subdir="dispatch")
    assert rules_of(findings) == ["full-matrix-reship"] * 2
    assert {f.symbol for f in findings} == {"Batcher.place", "upload"}


def test_reship_quiet_inside_manifest(tmp_path):
    assert run_on(tmp_path, RESHIP_GOOD, subdir="scheduler") == []


def test_reship_out_of_scope_dirs_quiet(tmp_path):
    # parallel/ (sharding infrastructure) and server/ are not dispatch
    # steady state; the rule stays out of them.
    assert run_on(tmp_path, RESHIP_BAD, subdir="parallel") == []
    assert run_on(tmp_path, RESHIP_BAD, subdir="server") == []


def test_reship_inline_suppression(tmp_path):
    src = RESHIP_BAD.replace(
        "dev = jax.device_put(np.zeros((1024, 4)))",
        "dev = jax.device_put(np.zeros((1024, 4)))  "
        "# nta: disable=full-matrix-reship")
    findings = run_on(tmp_path, src, subdir="models")
    assert rules_of(findings) == ["full-matrix-reship"]
    assert findings[0].symbol == "upload"


def test_real_batcher_passes_its_own_manifest():
    """The actual device cache: every transfer call in
    scheduler/batcher.py sits inside its declared rebuild entry point."""
    findings = analyze_paths(
        [os.path.join(REPO, "nomad_tpu", "scheduler", "batcher.py")])
    assert [f for f in findings if f.rule == "full-matrix-reship"] == []


def test_reship_scopes_parallel_shard(tmp_path):
    """parallel/ as a whole stays out of scope (mesh.py is the
    sanctioned upload infrastructure), but the explicit shard_map
    module IS scoped: a device_put creeping into parallel/shard.py
    must flag."""
    findings = run_on(tmp_path, RESHIP_BAD, name="shard.py",
                      subdir="parallel")
    assert rules_of(findings) == ["full-matrix-reship"] * 2


def test_compression_plane_modules_are_raw_clean():
    """The compression plane's zero-baseline self-check:
    models/classes.py and parallel/shard.py carry no findings at all
    AND no inline suppressions — their design premise is that no
    transfer (or any other lint debt) lives there."""
    paths = [os.path.join(REPO, "nomad_tpu", "models", "classes.py"),
             os.path.join(REPO, "nomad_tpu", "parallel", "shard.py")]
    findings = analyze_paths(paths)
    assert findings == [], "\n".join(f.render() for f in findings)
    for p in paths:
        with open(p) as fh:
            assert "nta: disable" not in fh.read(), p


def test_reship_manifest_globally_unique():
    """The ONE sanctioned full-upload path stays unique: across every
    module in the residency scope, the union of declared
    NTA_REBUILD_ENTRYPOINTS manifests is exactly the batcher's rebuild
    entry point, and beside it the upload of a topology id column
    (once a rebuild of the node set's tensor: the gang dispatch reads
    it resident beside the base). A further manifest anywhere (e.g. a
    class-expansion helper sanctioning its own device_put) widens the
    steady-state upload surface and must be a deliberate, reviewed
    change here."""
    from nomad_tpu.analysis.core import Module
    from nomad_tpu.analysis.residency import _in_scope, manifest_entries

    entries = {}
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, "nomad_tpu")):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, REPO).replace(os.sep, "/")
            if not _in_scope(rel):
                continue
            with open(path) as fh:
                mod = Module(path, rel, fh.read())
            for ent in manifest_entries(mod):
                entries.setdefault(ent, []).append(rel)
    assert set(entries) == {"PlacementBatcher._build_device_base",
                            "PlacementBatcher._device_topology"}, entries
    for sites in entries.values():
        assert sites == ["nomad_tpu/scheduler/batcher.py"]


# ---------------------------------------------------------------------
# the tier-1 gate: whole tree clean modulo baseline, baseline
# non-growing, concurrency-core dirs baseline-free


CORE_DIRS = ("nomad_tpu/dispatch/", "nomad_tpu/scheduler/",
             "nomad_tpu/ops/", "nomad_tpu/parallel/",
             "nomad_tpu/trace/", "nomad_tpu/admission/",
             "nomad_tpu/models/", "nomad_tpu/kernels/",
             "nomad_tpu/migrate/", "nomad_tpu/profile/",
             "nomad_tpu/defrag/", "nomad_tpu/gang/",
             "nomad_tpu/readplane/")


def _tree_findings():
    return analyze_paths([os.path.join(REPO, "nomad_tpu")])


def test_tree_is_clean_modulo_baseline():
    findings = _tree_findings()
    new, _stale = apply_baseline(findings, load_baseline())
    assert not new, "ntalint findings (fix or baseline):\n" + "\n".join(
        f.render() for f in new)


def test_baseline_is_non_growing():
    """Every committed baseline entry must still match a real finding:
    fixing a finding must delete its entry, or the baseline quietly
    becomes a grant of future regressions."""
    findings = _tree_findings()
    _new, stale = apply_baseline(findings, load_baseline())
    assert not stale, f"stale baseline entries (delete them): {stale}"


def test_concurrency_core_has_no_baseline_entries():
    """dispatch/, scheduler/, ops/, parallel/ — where the dispatcher
    threads, the batcher, and the jitted kernels live — must be
    actually clean: no recorded debt, no inline suppressions hiding
    real findings behind the baseline."""
    for ent in load_baseline():
        assert not ent["path"].startswith(CORE_DIRS), (
            f"baseline entry in a must-be-clean dir: {ent}")


# ---------------------------------------------------------------------
# CLI


def test_cli_json_mode(tmp_path):
    f = tmp_path / "fix.py"
    f.write_text(GUARDED_BAD)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ntalint.py"),
         "--json", "--no-baseline", str(f)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 1, res.stderr
    out = json.loads(res.stdout)
    assert [e["rule"] for e in out["findings"]] == ["guarded-by"] * 2
    assert {"rule", "path", "line", "col", "symbol", "message"} <= set(
        out["findings"][0])


def test_apply_baseline_duplicate_key_entries_pool_counts(tmp_path):
    """Two baseline entries sharing one (rule, path, symbol) pool
    their counts: with both absorbed, NEITHER is stale — reporting the
    sibling stale would tell the maintainer to delete coverage for a
    live finding."""
    findings = run_on(tmp_path, GUARDED_BAD)
    bump = [f for f in findings if f.symbol == "C.bump"]
    peek = [f for f in findings if f.symbol == "C.peek"]
    assert len(bump) == 1 and len(peek) == 1
    path = findings[0].path
    baseline = [
        {"rule": "guarded-by", "path": path, "symbol": "C.bump",
         "count": 1},
        {"rule": "guarded-by", "path": path, "symbol": "C.peek",
         "count": 1},
        # duplicate key for C.bump: pooled, not double-reported
        {"rule": "guarded-by", "path": path, "symbol": "C.bump",
         "count": 1},
    ]
    new, stale = apply_baseline(findings, baseline)
    assert new == []
    # the duplicated C.bump key has budget 2 for 1 finding: partially
    # stale, reported ONCE
    assert len(stale) == 1 and stale[0]["symbol"] == "C.bump"
    assert stale[0].get("stale_count") == 1


BRANCH_CLOSURE_BAD = """\
import jax

@jax.jit
def outer(x):
    def body(c, t):
        if x[0] > 0:  # closed-over traced value
            return c + t, t
        return c, t

    return jax.lax.scan(body, 0.0, x)
"""

BRANCH_CLOSURE_GOOD = """\
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnames=("cfg",))
def outer(x, cfg):
    n = x.shape[0]

    def body(c, t):
        if cfg:  # closed-over STATIC: resolves at trace time
            return c + t, t
        return c, t

    return jax.lax.scan(body, jnp.zeros(n)[0], x)
"""


def test_branch_fires_on_closed_over_traced_value(tmp_path):
    """A nested scan body branching on its outer jitted function's
    array is the flagship bug — closure capture must not launder a
    traced value into a 'module global'."""
    findings = run_on(tmp_path, BRANCH_CLOSURE_BAD)
    assert rules_of(findings) == ["trace-python-branch"]


def test_branch_quiet_on_closed_over_static(tmp_path):
    assert run_on(tmp_path, BRANCH_CLOSURE_GOOD) == []


def test_suppression_on_opening_line_covers_inner_lines(tmp_path):
    """The opening-line suppression of a multi-line statement applies
    even when an inner line carries its own different-rule disable
    comment (union, not first-match)."""
    src = """\
import threading
import time

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0  # guarded-by: _lock

    def bump(self):
        x = (  # nta: disable=guarded-by
            self.count
            + 1  # nta: disable=lock-blocking-call
        )
        return x
"""
    assert run_on(tmp_path, src) == []


def test_syntax_error_reported_as_parse_error_finding(tmp_path):
    """A file that does not parse (mid-edit working tree under --diff)
    must surface as a `parse-error` finding, not a traceback — exit 1
    with a rendered location, distinguishable from a tool crash."""
    findings = run_on(tmp_path, "def broken(:\n    pass\n")
    assert rules_of(findings) == ["parse-error"]
    assert findings[0].line == 1
    # valid files analyzed alongside are unaffected
    good = tmp_path / "ok.py"
    good.write_text(GUARDED_GOOD)
    findings = analyze_paths([str(tmp_path)])
    assert rules_of(findings) == ["parse-error"]


# ---------------------------------------------------------------------
# robustness: unbounded waits (server/ + dispatch/ scope)


UNBOUNDED_BAD = """\
import queue
import threading

class C:
    def __init__(self):
        self._q = queue.Queue()
        self._done = threading.Event()
        self._t = threading.Thread(target=lambda: None)

    def run(self):
        item = self._q.get()
        self._done.wait()
        self._t.join()
        return item
"""

UNBOUNDED_GOOD = """\
import queue
import threading

class C:
    def __init__(self):
        self._q = queue.Queue()
        self._done = threading.Event()
        self._t = threading.Thread(target=lambda: None)
        self._stop = threading.Event()

    def run(self):
        while not self._stop.is_set():
            try:
                item = self._q.get(timeout=0.2)
            except queue.Empty:
                continue
            while not self._done.wait(1.0):
                if self._stop.is_set():
                    break
            self._t.join(timeout=2.0)
            return item

    def lookup(self, d):
        return d.get("key")  # dict.get always has args: untouched
"""


def test_unbounded_wait_fires_in_server_dir(tmp_path):
    findings = run_on(tmp_path, UNBOUNDED_BAD, subdir="server")
    assert rules_of(findings) == ["unbounded-wait"] * 3
    assert lines_of(findings, "unbounded-wait") == [11, 12, 13]


def test_unbounded_wait_quiet_on_bounded_waits(tmp_path):
    assert run_on(tmp_path, UNBOUNDED_GOOD, subdir="dispatch") == []


def test_unbounded_wait_out_of_scope_dirs_ignored(tmp_path):
    # utils/-style helpers may block forever by design (daemon pools).
    assert run_on(tmp_path, UNBOUNDED_BAD, subdir="utils") == []


def test_unbounded_wait_inline_suppression(tmp_path):
    src = UNBOUNDED_BAD.replace(
        "        item = self._q.get()",
        "        item = self._q.get()  # nta: disable=unbounded-wait")
    findings = run_on(tmp_path, src, subdir="server")
    assert lines_of(findings, "unbounded-wait") == [12, 13]


# ---------------------------------------------------------------------
# robustness: swallowed broad exceptions (server/dispatch/client scope)


SWALLOWED_BAD = """\
def risky():
    pass

def a():
    try:
        risky()
    except Exception:
        pass

def b():
    try:
        risky()
    except:
        pass

def c():
    try:
        risky()
    except (ValueError, BaseException):
        ...
"""

SWALLOWED_GOOD = """\
import logging

log = logging.getLogger(__name__)

def risky():
    pass

def narrow():
    try:
        risky()
    except ValueError:
        pass  # specific protocol: a late ack is rejected by design

def logged():
    try:
        risky()
    except Exception:
        log.debug("risky failed", exc_info=True)

def rethrown():
    try:
        risky()
    except Exception as e:
        raise RuntimeError("wrapped") from e
"""


def test_swallowed_exception_fires_on_broad_silent_handlers(tmp_path):
    findings = run_on(tmp_path, SWALLOWED_BAD, subdir="client")
    assert rules_of(findings) == ["swallowed-exception"] * 3
    assert lines_of(findings, "swallowed-exception") == [7, 13, 19]


def test_swallowed_exception_quiet_on_narrow_logged_rethrown(tmp_path):
    assert run_on(tmp_path, SWALLOWED_GOOD, subdir="server") == []


def test_swallowed_exception_out_of_scope_dirs_ignored(tmp_path):
    assert run_on(tmp_path, SWALLOWED_BAD, subdir="scheduler") == []


def test_swallowed_exception_inline_suppression(tmp_path):
    src = SWALLOWED_BAD.replace(
        "    except Exception:",
        "    except Exception:  # nta: disable=swallowed-exception", 1)
    findings = run_on(tmp_path, src, subdir="client")
    assert lines_of(findings, "swallowed-exception") == [13, 19]


# ---------------------------------------------------------------------
# robustness: flight-recorder record path (NTA_RECORD_PATH manifest)


RECORD_BAD = """\
import time

NTA_RECORD_PATH = ("Rec.record",)

class Rec:
    def __init__(self):
        self.items = []
        self.ring = [None] * 8
        self.idx = 0

    def record(self, x):
        self._hist(x)
        self.items.append(x)

    def _hist(self, x):
        time.sleep(0.001)
"""

RECORD_GOOD = """\
import threading

NTA_RECORD_PATH = ("Rec.record",)

class Rec:
    def __init__(self):
        self._lock = threading.Lock()
        self.ring = [None] * 8
        self.idx = 0
        self.seen = []

    def record(self, x):
        with self._lock:
            self.ring[self.idx % 8] = x
            self.idx += 1
            scratch = [x]
            scratch.append(x)  # local scratch: bounded, quiet

    def flush(self):
        # NOT reachable from the manifest: growth is allowed here.
        self.seen.append(self.ring[0])
        return self.seen
"""


def test_record_path_fires_on_blocking_and_growth(tmp_path):
    """sleep reached through the call chain AND attribute-rooted
    .append both fire; the manifest drives reachability exactly like
    NTA_DISPATCHER_ENTRYPOINTS."""
    findings = run_on(tmp_path, RECORD_BAD)
    assert rules_of(findings) == ["record-path-blocking"] * 2
    # the append in record (line 13) and the sleep in _hist (line 16)
    assert lines_of(findings, "record-path-blocking") == [13, 16]
    assert {f.symbol for f in findings} == {"Rec.record", "Rec._hist"}


def test_record_path_quiet_on_slot_writes_and_off_path_growth(tmp_path):
    assert run_on(tmp_path, RECORD_GOOD) == []


def test_record_path_ignored_without_manifest(tmp_path):
    """No NTA_RECORD_PATH manifest -> the rule does not apply (the
    same sleep/append patterns are ordinary code elsewhere)."""
    src = RECORD_BAD.replace('NTA_RECORD_PATH = ("Rec.record",)\n', "")
    assert lines_of(run_on(tmp_path, src), "record-path-blocking") == []


def test_real_recorder_record_path_is_clean():
    """The actual flight recorder must satisfy its own manifest: no
    blocking call, no unbounded growth, reachable from any of the
    NTA_RECORD_PATH entrypoints the broker/dispatcher threads call."""
    from nomad_tpu.trace import recorder as rec_mod

    findings = analyze_paths(
        [os.path.join(REPO, "nomad_tpu", "trace", "recorder.py")])
    assert rec_mod.NTA_RECORD_PATH  # the manifest exists and is non-empty
    assert [f for f in findings
            if f.rule == "record-path-blocking"] == []


def test_real_profiler_record_path_is_clean():
    """The contention observatory's own self-check (the recorder's
    discipline, one subsystem over): the sampler and lock-record paths
    — Profiler.record_runq/park/unpark/event/_note_thread_wait, the
    histogram observe leaf, and the timeline/convoy updates — must
    never park (leaf `with lock:` around constant work only) and never
    grow a container, asserted against the REAL implementation."""
    import nomad_tpu.profile as prof_mod
    from nomad_tpu.profile import timeline as timeline_mod
    from nomad_tpu.utils import metrics as metrics_mod

    assert prof_mod.NTA_RECORD_PATH
    assert "Profiler.record_runq" in prof_mod.NTA_RECORD_PATH
    # The shared histogram leaf (recorder + profiler both store into
    # it) carries its manifest where it is defined.
    assert metrics_mod.NTA_RECORD_PATH == ("LatencyHist.observe",)
    assert "Timeline.push" in timeline_mod.NTA_RECORD_PATH
    assert "ConvoyTracker.park" in timeline_mod.NTA_RECORD_PATH
    # Whole-program run (the record path crosses profile/ modules).
    findings = [f for f in _tree_findings()
                if f.rule == "record-path-blocking"
                and f.path.startswith("nomad_tpu/profile/")]
    assert findings == [], "\n".join(f.render() for f in findings)


PROFILED_GUARDED = '''
from nomad_tpu.profile import ProfiledCondition, ProfiledLock


class C:
    def __init__(self):
        self._lock = ProfiledLock("t")
        self._cond = ProfiledCondition(self._lock, "t")
        self.n = 0  # guarded-by: _lock

    def good_lock(self):
        with self._lock:
            self.n += 1

    def good_cond(self):
        with self._cond:
            self.n += 1

    def bad(self):
        self.n += 1
'''


WAIT_DELEGATION_FOREIGN_LOCK = '''
import threading


class W:
    def __init__(self):
        self._lock = threading.Lock()
        self._other = threading.Lock()
        self._cond = threading.Condition(self._lock)

    def wait(self):
        with self._other:
            self._cond.wait(1.0)
'''


def test_wait_delegation_exemption_requires_nothing_held(tmp_path):
    """The condition-wrapper delegation exemption (a method named
    `wait` parking on its own condition) only applies with NOTHING
    else held: waiting while holding a DIFFERENT lock is the convoy
    the lock-blocking rule exists to catch, wrapper-shaped or not."""
    findings = run_on(tmp_path, WAIT_DELEGATION_FOREIGN_LOCK)
    assert "lock-blocking-call" in rules_of(findings)


def test_profiled_wrappers_preserve_guarded_by_and_aliasing(tmp_path):
    """The wrappers are registered lock constructors: guarded-by
    contracts keep firing on unguarded access, and
    ProfiledCondition(self._lock) aliases to its backing lock exactly
    like threading.Condition(self._lock) — holding either satisfies a
    guard on the other."""
    findings = run_on(tmp_path, PROFILED_GUARDED)
    assert rules_of(findings) == ["guarded-by"]
    assert findings[0].symbol == "C.bad"


def test_real_hot_locks_are_profiled():
    """The tentpole wiring: the hot locks the issue names — batcher,
    dispatch pipeline, broker, matrix position index, recorder stripes
    — construct Profiled primitives, not raw threading ones."""
    expect = {
        ("scheduler", "batcher.py"): 'ProfiledLock("scheduler.batcher")',
        ("dispatch", "pipeline.py"): 'ProfiledLock("dispatch.pipeline")',
        ("server", "broker.py"): 'ProfiledRLock("server.broker")',
        ("models", "matrix.py"):
            'ProfiledLock("models.matrix.positions")',
        ("trace", "recorder.py"):
            'ProfiledLock("trace.recorder.stripe")',
    }
    for (pkg, fname), needle in expect.items():
        path = os.path.join(REPO, "nomad_tpu", pkg, fname)
        with open(path) as f:
            src = f.read()
        assert needle in src, f"{pkg}/{fname} lost its profiled lock"


# =====================================================================
# PR 7: whole-program analysis — cross-module reachability, deadlock
# detection, raft-funnel protocol, caches, SARIF.
# =====================================================================


def run_dir(tmp_path, files):
    """Write {relpath: source} under tmp_path and analyze the tree."""
    for rel, src in files.items():
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(src)
    return analyze_paths([str(tmp_path)])


# ---------------------------------------------------------------------
# cross-module dispatcher reachability: the acceptance fixture pair.
# The SAME logic, split across two modules: analyzed one module at a
# time (the PR 2-era intra-module graph), the pipeline looks clean —
# whole-program analysis follows the import and flags the sleep two
# calls deep in the helper.


XMOD_PIPE = """\
from helper import nap_for

NTA_DISPATCHER_ENTRYPOINTS = ("Pipe._run",)

class Pipe:
    def _run(self):
        while True:
            self._accumulate()
            nap_for(7)

    def _accumulate(self):
        pass
"""

XMOD_HELPER = """\
import time

def nap_for(n):
    _snooze(n)

def _snooze(n):
    time.sleep(0.01)
"""

XMOD_PIPE_POOLED = """\
from helper import nap_for

NTA_DISPATCHER_ENTRYPOINTS = ("Pipe._run",)

class Pipe:
    def __init__(self, pool):
        self.pool = pool

    def _run(self):
        while True:
            self._accumulate()
            # handed to a stage thread, not called: not followed
            self.pool.submit(nap_for, 7)

    def _accumulate(self):
        pass
"""


def test_cross_module_dispatcher_blocking_v1_intra_module_is_blind(
        tmp_path):
    """v1 of the pair: the pipeline module ALONE (exactly what the
    intra-module call graph saw) carries no finding — the blocking
    call lives behind the import boundary."""
    (tmp_path / "helper.py").write_text(XMOD_HELPER)
    pipe = tmp_path / "pipe.py"
    pipe.write_text(XMOD_PIPE)
    assert analyze_paths([str(pipe)]) == []


def test_cross_module_dispatcher_blocking_v2_whole_program_flags(
        tmp_path):
    """v2: the same code analyzed whole-program — the sleep TWO
    modules deep (pipe._run -> helper.nap_for -> helper._snooze) is a
    dispatcher-blocking-call, reported at the sleep with the entry
    chain as the witness."""
    findings = run_dir(tmp_path, {"pipe.py": XMOD_PIPE,
                                  "helper.py": XMOD_HELPER})
    assert rules_of(findings) == ["dispatcher-blocking-call"]
    f = findings[0]
    assert f.path.endswith("helper.py")
    assert f.symbol == "_snooze"
    assert "Pipe._run" in f.message
    assert f.related and any("pipe.py" in loc for loc in f.related)


def test_cross_module_dispatcher_quiet_on_pool_submitted_reference(
        tmp_path):
    """The pool-submitted reference is NOT followed: handing the
    helper to a stage thread is the sanctioned fix."""
    assert run_dir(tmp_path, {"pipe.py": XMOD_PIPE_POOLED,
                              "helper.py": XMOD_HELPER}) == []


# ---------------------------------------------------------------------
# cross-module unbounded-wait: a wait-scope dir calling into a utils
# helper that parks forever.


XWAIT_SERVER = """\
from helper import wait_done

class Serv:
    def run(self, ev):
        wait_done(ev)
"""

XWAIT_HELPER = """\
def wait_done(ev):
    ev.wait()
"""

XWAIT_SERVER_POOLED = """\
from helper import wait_done

class Serv:
    def __init__(self, pool):
        self.pool = pool

    def run(self, ev):
        self.pool.submit(wait_done, ev)
"""


def test_cross_module_unbounded_wait_flagged_in_helper(tmp_path):
    findings = run_dir(tmp_path, {"server/mod.py": XWAIT_SERVER,
                                  "utils/helper.py": XWAIT_HELPER})
    assert rules_of(findings) == ["unbounded-wait"]
    f = findings[0]
    assert f.path.endswith("utils/helper.py")
    assert f.symbol == "wait_done"
    assert "Serv.run" in f.message


def test_cross_module_unbounded_wait_pooled_reference_not_followed(
        tmp_path):
    assert run_dir(tmp_path, {"server/mod.py": XWAIT_SERVER_POOLED,
                              "utils/helper.py": XWAIT_HELPER}) == []


def test_unbounded_wait_now_covers_scheduler_dir(tmp_path):
    """scheduler/ joined the wait scope in PR 7 (the dense path parks
    worker threads there — the batcher's request wait was the real
    finding this surfaced)."""
    findings = run_on(tmp_path, UNBOUNDED_BAD, subdir="scheduler")
    assert rules_of(findings) == ["unbounded-wait"] * 3


# ---------------------------------------------------------------------
# deadlock-cycle: seeded TP/TN fixtures.


DEADLOCK_2 = """\
import threading

class C:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def ab(self):
        with self._a:
            with self._b:
                pass

    def ba(self):
        with self._b:
            with self._a:
                pass
"""

DEADLOCK_2_CONSISTENT = """\
import threading

class C:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def ab(self):
        with self._a:
            with self._b:
                pass

    def ab2(self):
        with self._a:
            with self._b:
                pass
"""

DEADLOCK_3 = """\
import threading

class C:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
        self._c = threading.Lock()

    def step1(self):
        with self._a:
            self._grab_b()

    def _grab_b(self):
        with self._b:
            pass

    def step2(self):
        with self._b:
            self._grab_c()

    def _grab_c(self):
        with self._c:
            pass

    def step3(self):
        with self._c:
            self._grab_a()

    def _grab_a(self):
        with self._a:
            pass
"""

DEADLOCK_COND_ALIAS_TP = """\
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._other = threading.Lock()

    def through_cond(self):
        # Holding the cond IS holding _lock: this is a _lock -> _other
        # edge.
        with self._cond:
            with self._other:
                pass

    def reverse(self):
        with self._other:
            with self._lock:
                pass
"""

DEADLOCK_COND_ALIAS_TN = """\
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)

    def nested_alias(self):
        # cond and its backing lock are ONE lock: no distinct-lock
        # edge, no cycle.
        with self._cond:
            with self._lock:
                pass

    def other_order(self):
        with self._lock:
            with self._cond:
                pass
"""


def test_deadlock_two_lock_cycle(tmp_path):
    findings = run_on(tmp_path, DEADLOCK_2)
    assert rules_of(findings) == ["deadlock-cycle"]
    msg = findings[0].message
    assert "_a" in msg and "_b" in msg and "Witness" in msg


def test_deadlock_quiet_on_consistent_order(tmp_path):
    assert run_on(tmp_path, DEADLOCK_2_CONSISTENT) == []


def test_deadlock_three_lock_cycle_with_witness_path(tmp_path):
    """The acceptance fixture: a->b->c->a through three functions,
    each acquisition behind a call — the witness must carry the full
    acquisition path."""
    findings = run_on(tmp_path, DEADLOCK_3)
    assert rules_of(findings) == ["deadlock-cycle"]
    f = findings[0]
    msg = f.message
    for lock in ("_a", "_b", "_c"):
        assert lock in msg
    # the witness names the call chain into at least one acquisition
    assert "_grab_" in msg
    assert f.related  # edge sites for CI annotation surfaces


def test_deadlock_condition_alias_edge_fires(tmp_path):
    findings = run_on(tmp_path, DEADLOCK_COND_ALIAS_TP)
    assert rules_of(findings) == ["deadlock-cycle"]
    assert "_other" in findings[0].message


def test_deadlock_condition_alias_is_not_a_cycle(tmp_path):
    assert run_on(tmp_path, DEADLOCK_COND_ALIAS_TN) == []


DEADLOCK_XMOD_A = """\
import threading
from other import grab_right

LEFT = threading.Lock()

def left_then_right():
    with LEFT:
        grab_right()

def grab_left():
    with LEFT:
        pass
"""

DEADLOCK_XMOD_B = """\
import threading
from mod import grab_left

RIGHT = threading.Lock()

def grab_right():
    with RIGHT:
        pass

def right_then_left():
    with RIGHT:
        grab_left()
"""


def test_deadlock_cross_module_cycle(tmp_path):
    """The classic two-thread wrap-around with no nesting in any one
    module: mod holds LEFT and calls into other (RIGHT); other holds
    RIGHT and calls back into mod (LEFT)."""
    findings = run_dir(tmp_path, {"mod.py": DEADLOCK_XMOD_A,
                                  "other.py": DEADLOCK_XMOD_B})
    assert rules_of(findings) == ["deadlock-cycle"]
    msg = findings[0].message
    assert "LEFT" in msg and "RIGHT" in msg


def test_deadlock_detector_silent_on_real_tree():
    """The real (fixed) tree has a cycle-free lock order."""
    assert [f for f in _tree_findings()
            if f.rule == "deadlock-cycle"] == []


# ---------------------------------------------------------------------
# raft-funnel protocol checker.


FUNNEL_STAMP_BAD = """\
class Broker:
    def finish(self, ev):
        # terminal stamped on a shared eval, never routed through the
        # funnel: commits nowhere (or twice, later).
        ev.status = consts.EVAL_STATUS_COMPLETE
        return ev
"""

FUNNEL_MUTATOR_BAD = """\
class Svc:
    def rewrite(self, store, evals):
        store.upsert_evals(7, evals)
"""

FUNNEL_SUBMIT_GOOD = """\
class Reaper:
    def reap(self, ev):
        upd = ev.copy()
        upd.status = consts.EVAL_STATUS_FAILED
        self.server.eval_update([upd])

    def reap_many(self, evs):
        cancelled = []
        for ev in evs:
            upd = ev.copy()
            upd.status = consts.EVAL_STATUS_CANCELLED
            cancelled.append(upd)
        self.server.eval_update(cancelled)
"""

FUNNEL_MANIFEST_GOOD = """\
NTA_RAFT_FUNNELS = ("Fsm.apply_eval",)

class Fsm:
    def apply_eval(self, index, evals):
        self._commit(index, evals)

    def _commit(self, index, evals):
        # reachable from the declared funnel: sanctioned
        self.state.upsert_evals(index, evals)
"""

FUNNEL_PARK_GOOD = """\
NTA_RAFT_FUNNELS = ("Broker._park",)

class Broker:
    def shed(self, ev):
        dead = ev.copy()
        dead.triggered_by = consts.EVAL_TRIGGER_SHED
        self._park(dead)

    def _park(self, ev):
        self.failed[ev.id] = ev
"""

FUNNEL_PARK_BAD = """\
class Broker:
    def shed(self, ev):
        ev.triggered_by = consts.EVAL_TRIGGER_SHED
        return ev
"""


def test_raft_funnel_flags_unrouted_terminal_stamp(tmp_path):
    findings = run_on(tmp_path, FUNNEL_STAMP_BAD, subdir="server")
    assert rules_of(findings) == ["raft-funnel"]
    assert findings[0].symbol == "Broker.finish"
    assert "EVAL_STATUS_COMPLETE" in findings[0].message


def test_raft_funnel_flags_store_mutator_outside_funnel(tmp_path):
    findings = run_on(tmp_path, FUNNEL_MUTATOR_BAD, subdir="dispatch")
    assert rules_of(findings) == ["raft-funnel"]
    assert "upsert_evals" in findings[0].message


def test_raft_funnel_quiet_when_stamp_flows_into_eval_update(tmp_path):
    """Both the direct [upd] argument and the one-container-hop
    (cancelled.append(upd); eval_update(cancelled)) idioms are the
    sanctioned stamp-a-copy-then-submit shape."""
    assert run_on(tmp_path, FUNNEL_SUBMIT_GOOD, subdir="server") == []


def test_raft_funnel_quiet_inside_declared_funnel(tmp_path):
    assert run_on(tmp_path, FUNNEL_MANIFEST_GOOD, subdir="server") == []


def test_raft_funnel_park_trigger_needs_funnel_flow(tmp_path):
    good = run_on(tmp_path, FUNNEL_PARK_GOOD, subdir="server",
                  name="good.py")
    assert good == []
    bad = run_on(tmp_path, FUNNEL_PARK_BAD, subdir="server2",
                 name="bad.py")
    assert rules_of(bad) == ["raft-funnel"]
    assert "EVAL_TRIGGER_SHED" in bad[0].message


def test_raft_funnel_client_dir_out_of_scope(tmp_path):
    """The client owns its local status lifecycle; it commits through
    the alloc_client_update RPC, which IS the funnel."""
    assert run_on(tmp_path, FUNNEL_STAMP_BAD, subdir="client") == []


def test_raft_funnel_inline_suppression(tmp_path):
    src = FUNNEL_MUTATOR_BAD.replace(
        "store.upsert_evals(7, evals)",
        "store.upsert_evals(7, evals)  # nta: disable=raft-funnel")
    assert run_on(tmp_path, src, subdir="state") == []


def test_raft_funnel_clean_on_real_tree_with_fsm_manifest():
    """Acceptance: the real tree passes with NTA_RAFT_FUNNELS naming
    the fsm/apply funnels (+ the broker's exactly-once park and the
    CPU-oracle harness apply), with ZERO baseline entries for the
    rule."""
    from nomad_tpu.server import fsm

    assert fsm.NTA_RAFT_FUNNELS
    assert all(q.startswith("FSM.") for q in fsm.NTA_RAFT_FUNNELS)
    assert [f for f in _tree_findings() if f.rule == "raft-funnel"] == []
    assert [e for e in load_baseline() if e["rule"] == "raft-funnel"] == []


# ---------------------------------------------------------------------
# self-checks: the concurrency core passes every NEW rule with no
# baseline and no findings at all (not even baselined ones).


NEW_RULES = ("deadlock-cycle", "raft-funnel", "dispatcher-blocking-call",
             "record-path-blocking", "unbounded-wait")


def test_new_rules_raw_clean_in_baseline_free_dirs():
    core = CORE_DIRS  # dispatch/scheduler/ops/parallel/trace/admission/models
    offenders = [f for f in _tree_findings()
                 if f.rule in NEW_RULES and f.path.startswith(core)]
    assert offenders == [], "\n".join(f.render() for f in offenders)


def test_kernels_subsystem_raw_clean_and_in_every_scope():
    """The placement-kernel subsystem's self-check (the PR-8 analog of
    the dispatch/admission acceptance below): nomad_tpu/kernels/ is
    inside the baseline-free core set and the residency scope; the
    tree shows ZERO findings of
    ANY rule there (not even baselined ones) — in particular no
    raft-funnel findings: kernels never touch the state store, they
    only return plans (the differential rig's store seeding routes
    through scheduler/testing.py's sanctioned fixture funnel)."""
    assert "nomad_tpu/kernels/" in CORE_DIRS
    from nomad_tpu.analysis.residency import SCOPE_MARKERS

    assert "/kernels/" in SCOPE_MARKERS

    offenders = [f for f in _tree_findings()
                 if f.path.startswith("nomad_tpu/kernels/")]
    assert offenders == [], "\n".join(f.render() for f in offenders)
    assert [e for e in load_baseline()
            if e["path"].startswith("nomad_tpu/kernels/")] == []


def test_real_server_dispatch_admission_pass_program_rules():
    """The acceptance self-check: the live server/, dispatch/ and
    admission/ modules satisfy the whole-program rules with an empty
    baseline (server/ allows inline-suppressed findings — the shadow
    store dry-run — but nothing baselined)."""
    findings = _tree_findings()
    new, _stale = apply_baseline(findings, load_baseline())
    dirs = ("nomad_tpu/server/", "nomad_tpu/dispatch/",
            "nomad_tpu/admission/")
    offenders = [f for f in new
                 if f.rule in NEW_RULES and f.path.startswith(dirs)]
    assert offenders == [], "\n".join(f.render() for f in offenders)
    assert [e for e in load_baseline()
            if e["rule"] in NEW_RULES and e["path"].startswith(dirs)] == []


# ---------------------------------------------------------------------
# caches.


def test_cache_invalidates_on_content_change(tmp_path):
    """The per-file cache keys on content sha: editing the file must
    re-analyze it (a mtime-keyed cache would serve stale findings)."""
    f = tmp_path / "m.py"
    f.write_text(GUARDED_BAD)
    assert rules_of(analyze_paths([str(f)])) == ["guarded-by"] * 2
    f.write_text(GUARDED_GOOD)
    assert analyze_paths([str(f)]) == []
    f.write_text(GUARDED_BAD)
    assert rules_of(analyze_paths([str(f)])) == ["guarded-by"] * 2


def test_repeated_whole_tree_analysis_is_cached():
    """Second whole-tree run must come from the in-process caches —
    this is what keeps the tier-1 suite inside its wall-clock now that
    the program pass exists."""
    import time as _time

    _tree_findings()  # ensure warm
    t0 = _time.monotonic()
    _tree_findings()
    warm = _time.monotonic() - t0
    assert warm < 1.0, f"cached whole-tree run took {warm:.2f}s"


def test_disk_cache_round_trip(tmp_path):
    from nomad_tpu.analysis import (clear_caches, load_disk_cache,
                                    save_disk_cache)

    target = os.path.join(REPO, "nomad_tpu", "trace")
    try:
        clear_caches()
        before = [f.render() for f in analyze_paths([target])]
        cache_file = str(tmp_path / "cache.json")
        save_disk_cache(cache_file)
        clear_caches()
        load_disk_cache(cache_file)
        after = [f.render() for f in analyze_paths([target])]
        assert after == before
    finally:
        clear_caches()  # leave no half-primed state for other tests


# ---------------------------------------------------------------------
# CLI: SARIF + cache flags (the tools/ smoke tests).


def test_cli_sarif_mode(tmp_path):
    f = tmp_path / "fix.py"
    f.write_text(GUARDED_BAD)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ntalint.py"),
         "--sarif", "--no-baseline", "--no-cache", str(f)],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 1, res.stderr
    sarif = json.loads(res.stdout)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "ntalint"
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"deadlock-cycle", "raft-funnel",
            "dispatcher-blocking-call"} <= rules
    results = run["results"]
    assert [r["ruleId"] for r in results] == ["guarded-by"] * 2
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] == 11
    assert loc["artifactLocation"]["uri"].endswith("fix.py")


def test_cli_disk_cache_flag(tmp_path):
    f = tmp_path / "fix.py"
    f.write_text(GUARDED_BAD)
    cache = str(tmp_path / "c.json")
    for _ in range(2):
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ntalint.py"),
             "--json", "--no-baseline", "--cache", cache, str(f)],
            capture_output=True, text=True, timeout=120)
        assert res.returncode == 1, res.stderr
        out = json.loads(res.stdout)
        assert [e["rule"] for e in out["findings"]] == ["guarded-by"] * 2
    assert os.path.exists(cache)


DEADLOCK_THROUGH_RECURSION = """\
import threading

class C:
    def __init__(self):
        self._l1 = threading.Lock()
        self._l2 = threading.Lock()

    def a_warm(self):
        # Sorts before 'holder' and walks the recursive pair first: a
        # memoized-DFS closure would cache the cycle-cut partial
        # result for g and mask the edge below.
        self.g(1)

    def g(self, n):
        self.h(n)

    def h(self, n):
        self.g(n - 1)
        self.z()

    def z(self):
        with self._l2:
            pass

    def holder(self):
        with self._l1:
            self.g(3)

    def reverse(self):
        with self._l2:
            with self._l1:
                pass
"""


def test_deadlock_edge_survives_call_graph_recursion(tmp_path):
    """The acquisition closure is a worklist fixpoint, not a memoized
    DFS: locks reachable only through a call-graph cycle (g <-> h,
    with h also reaching the acquire) must still produce the edge —
    and the cycle — no matter which function warms the closure
    first."""
    findings = run_on(tmp_path, DEADLOCK_THROUGH_RECURSION)
    assert rules_of(findings) == ["deadlock-cycle"]
    assert "_l1" in findings[0].message and "_l2" in findings[0].message


FUNNEL_STAMP_AFTER_SUBMIT = """\
class Reaper:
    def reap(self, ev):
        self.server.eval_update([ev])
        # stamped AFTER the submit: the terminal never reaches raft
        ev.status = consts.EVAL_STATUS_FAILED
"""


def test_raft_funnel_stamp_after_submit_is_flagged(tmp_path):
    """The flow scan is order-sensitive: a funnel call ABOVE the stamp
    does not sanction it — mutating the shared eval after submitting
    is the lost-terminal bug, not the stamp-a-copy idiom."""
    findings = run_on(tmp_path, FUNNEL_STAMP_AFTER_SUBMIT,
                      subdir="server")
    assert rules_of(findings) == ["raft-funnel"]


def test_stdlib_import_does_not_suffix_match_repo_modules():
    """In-repo importers resolve imports exactly: `import select` in a
    nomad_tpu module must NOT resolve to nomad_tpu/scheduler/select.py
    (a phantom edge into scheduler/ would mint false deadlock/
    dispatcher findings the moment a name collides). The suffix
    fallback exists only for fixture trees, whose rel paths are
    absolute."""
    from nomad_tpu.analysis.core import Module, Program

    importer = Module(
        "fake.py", "nomad_tpu/utils/fake_pool.py",
        "import select\nimport http\n\n"
        "def tick():\n    select.poll()\n    http.client()\n")
    target = Module(
        "select.py", "nomad_tpu/scheduler/select.py",
        "def poll():\n    pass\n")
    program = Program([importer, target])
    key = ("nomad_tpu/utils/fake_pool.py", "tick")
    assert program.calls[key] == set(), (
        f"stdlib import misresolved: {program.calls[key]}")
    # the fixture-tree fallback still works for out-of-repo importers
    fix_imp = Module("/tmp/x/main.py", "/tmp/x/main.py",
                     "from helper import nap\n\ndef f():\n    nap()\n")
    fix_help = Module("/tmp/x/helper.py", "/tmp/x/helper.py",
                      "def nap():\n    pass\n")
    p2 = Program([fix_imp, fix_help])
    assert p2.calls[("/tmp/x/main.py", "f")] == {
        ("/tmp/x/helper.py", "nap")}


FUNNEL_GENERIC_NAME_LEAK = """\
NTA_RAFT_FUNNELS = ("FSM.apply",)

class FSM:
    def apply(self, index, payload):
        pass

class Other:
    def leak(self, ev):
        ev.status = consts.EVAL_STATUS_CANCELLED
        self.breaker.apply(ev)
"""

FUNNEL_APPEND_THEN_STAMP = """\
class R:
    def reap(self, evs):
        out = []
        for ev in evs:
            upd = ev.copy()
            out.append(upd)
            upd.status = consts.EVAL_STATUS_FAILED
        self.server.eval_update(out)
"""


def test_raft_funnel_generic_manifest_name_does_not_sanction(tmp_path):
    """Funnel calls are matched by RESOLUTION against the declared
    entries, not by bare method name: 'FSM.apply' in the manifest must
    not let any `.apply()` call anywhere sanction a terminal stamp."""
    findings = run_on(tmp_path, FUNNEL_GENERIC_NAME_LEAK,
                      subdir="server")
    assert rules_of(findings) == ["raft-funnel"]
    assert findings[0].symbol == "Other.leak"


def test_raft_funnel_append_before_stamp_is_sanctioned(tmp_path):
    """The container holds a reference: append-then-stamp-then-submit
    commits the terminal exactly like stamp-then-append. Only the
    SUBMIT must come after the stamp."""
    assert run_on(tmp_path, FUNNEL_APPEND_THEN_STAMP,
                  subdir="server") == []


# ---------------------------------------------------------------------
# churn-PR acceptance: the migrate module sits in every enforcement
# scope and the eviction/churn terminal stamps joined the raft-funnel
# stamp set — with the real tree raw-clean under them.


def test_migrate_module_raw_clean_and_in_every_scope():
    """nomad_tpu/migrate/ (the churn control plane) is in the
    baseline-free core set and the unbounded-wait / swallowed-
    exception scopes, and the tree shows ZERO findings of ANY rule
    there — the governor/policy run inside scheduler attempts, where
    a silent swallow or unbounded wait wedges the migration budget
    for every worker at once."""
    from nomad_tpu.analysis.robustness import (
        SWALLOW_SCOPE_MARKERS,
        WAIT_SCOPE_MARKERS,
    )

    assert "nomad_tpu/migrate/" in CORE_DIRS
    assert "/migrate/" in WAIT_SCOPE_MARKERS
    assert "/migrate/" in SWALLOW_SCOPE_MARKERS
    offenders = [f for f in _tree_findings()
                 if f.path.startswith("nomad_tpu/migrate/")]
    assert offenders == [], "\n".join(f.render() for f in offenders)
    assert [e for e in load_baseline()
            if e["path"].startswith("nomad_tpu/migrate/")] == []


def test_defrag_module_raw_clean_and_in_every_scope():
    """Defrag-PR acceptance (the ISSUE's ntalint satellite):
    nomad_tpu/defrag/ (the background optimizer) is in the
    baseline-free core set and the unbounded-wait /
    swallowed-exception scopes, with ZERO findings of ANY rule and
    ZERO baseline entries or inline suppressions — the loop
    holds migration-budget slots across waves, where a swallowed
    exception or an unbounded wait leaks budget every drain storm
    then fights."""
    from nomad_tpu.analysis.robustness import (
        SWALLOW_SCOPE_MARKERS,
        WAIT_SCOPE_MARKERS,
    )

    assert "nomad_tpu/defrag/" in CORE_DIRS
    assert "/defrag/" in WAIT_SCOPE_MARKERS
    assert "/defrag/" in SWALLOW_SCOPE_MARKERS
    offenders = [f for f in _tree_findings()
                 if f.path.startswith("nomad_tpu/defrag/")]
    assert offenders == [], "\n".join(f.render() for f in offenders)
    assert [e for e in load_baseline()
            if e["path"].startswith("nomad_tpu/defrag/")] == []
    for fname in ("__init__.py", "solver.py"):
        src = open(os.path.join(
            REPO, "nomad_tpu", "defrag", fname)).read()
        assert "nta: disable" not in src, fname


def test_gang_module_raw_clean_and_in_every_scope():
    """Gang-PR acceptance (the ISSUE's ntalint satellite):
    nomad_tpu/gang/ (all-or-nothing multi-node placement) is in the
    baseline-free core set and the unbounded-wait /
    swallowed-exception / device-residency scopes, with ZERO findings
    of ANY rule and ZERO baseline entries or inline suppressions —
    gang staging runs inside scheduler attempts where a
    swallowed exception would leave a HALF-STAGED gang on the plan,
    the one state this subsystem exists to make unrepresentable. The
    raft-funnel sweep covers it too: gang terminals only ever stamp
    through the applier/FSM funnels, never from gang/ itself."""
    from nomad_tpu.analysis.residency import (
        SCOPE_MARKERS as RESIDENCY_SCOPE_MARKERS,
    )
    from nomad_tpu.analysis.robustness import (
        SWALLOW_SCOPE_MARKERS,
        WAIT_SCOPE_MARKERS,
    )

    assert "nomad_tpu/gang/" in CORE_DIRS
    assert "/gang/" in WAIT_SCOPE_MARKERS
    assert "/gang/" in SWALLOW_SCOPE_MARKERS
    assert "/gang/" in RESIDENCY_SCOPE_MARKERS
    offenders = [f for f in _tree_findings()
                 if f.path.startswith("nomad_tpu/gang/")
                 or f.path.endswith(("models/topology.py", "ops/gang.py"))]
    assert offenders == [], "\n".join(f.render() for f in offenders)
    assert [e for e in load_baseline()
            if e["path"].startswith("nomad_tpu/gang/")
            or e["path"].endswith(("models/topology.py",
                                   "ops/gang.py"))] == []
    for rel in ("gang/__init__.py", "gang/host.py", "models/topology.py",
                "ops/gang.py"):
        src = open(os.path.join(REPO, "nomad_tpu", rel)).read()
        assert "nta: disable" not in src, rel


def test_readplane_manifests_and_raw_clean():
    """The read plane's self-check (PR 19): readplane/ declares the
    wake owner as a never-blocking dispatcher entrypoint, sits inside
    the unbounded-wait + swallowed-exception scopes and the must-be-
    clean CORE_DIRS, and the real tree shows ZERO findings of ANY rule
    in it — empty baseline, no inline suppressions."""
    from nomad_tpu.analysis.robustness import (
        SWALLOW_SCOPE_MARKERS,
        WAIT_SCOPE_MARKERS,
    )
    from nomad_tpu.readplane import mux as mux_mod

    assert mux_mod.NTA_DISPATCHER_ENTRYPOINTS == ("ReadMux._wake_loop",)
    assert "nomad_tpu/readplane/" in CORE_DIRS
    assert "/readplane/" in WAIT_SCOPE_MARKERS
    assert "/readplane/" in SWALLOW_SCOPE_MARKERS
    offenders = [f for f in _tree_findings()
                 if f.path.startswith("nomad_tpu/readplane/")]
    assert offenders == [], "\n".join(f.render() for f in offenders)
    assert [e for e in load_baseline()
            if e["path"].startswith("nomad_tpu/readplane/")] == []
    for rel in ("readplane/__init__.py", "readplane/mux.py"):
        src = open(os.path.join(REPO, "nomad_tpu", rel)).read()
        assert "nta: disable" not in src, rel


def test_raft_funnel_stamp_set_covers_eviction_terminals():
    """The raft-funnel checker's terminal stamp set includes the
    eviction stamp and the churn follow-up triggers: a
    `.desired_status = ALLOC_DESIRED_EVICT` (or a migration/preemption
    trigger stamp) outside the funnel that never flows into a submit
    is the double-evict / dropped-work bug class — and the real tree
    is raw-clean under the widened set (the sanctioned paths pass the
    constants as Plan.append_preemption / Evaluation-constructor
    arguments, the parameter idiom the checker documents)."""
    from nomad_tpu.analysis.protocol import TERMINAL_BY_FIELD

    assert "ALLOC_DESIRED_EVICT" in TERMINAL_BY_FIELD["desired_status"]
    assert "EVAL_TRIGGER_MIGRATION" in TERMINAL_BY_FIELD["triggered_by"]
    assert "EVAL_TRIGGER_PREEMPTION" in TERMINAL_BY_FIELD["triggered_by"]
    offenders = [f for f in _tree_findings() if f.rule == "raft-funnel"]
    assert offenders == [], "\n".join(f.render() for f in offenders)
    assert [e for e in load_baseline()
            if e["rule"] == "raft-funnel"] == []


def test_raft_funnel_flags_unfunneled_evict_stamp(tmp_path):
    """TP fixture for the widened stamp set: an evict stamped on a
    shared alloc outside the funnel and never submitted is flagged."""
    bad = '''
from nomad_tpu.structs import consts

def drop_quietly(alloc):
    alloc.desired_status = consts.ALLOC_DESIRED_EVICT
'''
    findings = run_on(tmp_path, bad, subdir="server")
    assert any(f.rule == "raft-funnel"
               and "ALLOC_DESIRED_EVICT" in f.message for f in findings), (
        [f.render() for f in findings])


# ---------------------------------------------------------------------
# PR 17: ruleset-version skew, SARIF rule-table completeness, and the
# --diff CLI gate.


def test_old_version_disk_cache_primes_nothing_and_is_rewritten():
    """The disk cache keys on RULESET_VERSION: a cache written by an
    OLD ruleset must prime NOTHING (its entries were computed by rules
    that no longer exist / have different semantics), and the next
    save must rewrite the file clean under the current version. The
    poison probe: every cached entry is doctored to claim a fabricated
    finding — if the stale cache primed anything, analysis would
    report it."""
    import tempfile

    from nomad_tpu.analysis import (RULESET_VERSION, clear_caches,
                                    load_disk_cache, save_disk_cache)

    target = os.path.join(REPO, "nomad_tpu", "trace")
    poison = {"rule": "guarded-by", "path": "nomad_tpu/poisoned.py",
              "line": 1, "col": 0, "message": "stale-cache ghost",
              "symbol": "", "related": []}
    with tempfile.TemporaryDirectory() as td:
        cache_file = os.path.join(td, "cache.json")
        try:
            clear_caches()
            before = [f.render() for f in analyze_paths([target])]
            save_disk_cache(cache_file)
            with open(cache_file, "r", encoding="utf-8") as fh:
                data = json.load(fh)
            assert data["version"] == RULESET_VERSION
            data["version"] = "0.0-stale"
            for ent in data["local"].values():
                ent["findings"] = [dict(poison)]
            data["program"] = {d: [dict(poison)]
                               for d in data.get("program", {})}
            with open(cache_file, "w", encoding="utf-8") as fh:
                json.dump(data, fh)
            clear_caches()
            load_disk_cache(cache_file)
            after = [f.render() for f in analyze_paths([target])]
            assert after == before  # no ghost: stale cache primed nothing
            save_disk_cache(cache_file)
            with open(cache_file, "r", encoding="utf-8") as fh:
                rewritten = json.load(fh)
            assert rewritten["version"] == RULESET_VERSION
            assert not any(
                ent["findings"] for ent in rewritten["local"].values())
        finally:
            clear_caches()


def test_rule_docs_cover_all_rules_exactly():
    """Every rule has a RULE_DOCS entry and no entry is stale — the
    generalized fix for the PR 7 SARIF rule-list omission: a new rule
    that forgets its one-liner fails tier-1 here."""
    from nomad_tpu.analysis import ALL_RULES, RULE_DOCS

    assert set(RULE_DOCS) == set(ALL_RULES)
    assert all(isinstance(v, str) and v for v in RULE_DOCS.values())


def test_sarif_driver_rule_table_complete():
    """The SARIF driver advertises EVERY rule with its doc — CI
    annotation surfaces key on this table."""
    import importlib.util

    from nomad_tpu.analysis import ALL_RULES, RULE_DOCS

    spec = importlib.util.spec_from_file_location(
        "ntalint_cli_probe", os.path.join(REPO, "tools", "ntalint.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    driver = cli._to_sarif([])["runs"][0]["tool"]["driver"]
    assert [r["id"] for r in driver["rules"]] == list(ALL_RULES)
    for r in driver["rules"]:
        assert r["shortDescription"]["text"] == RULE_DOCS[r["id"]]


def test_cli_diff_gate_clean_tree_exits_zero():
    """`python tools/ntalint.py --diff` IS the tier-1 pre-commit gate:
    on the current work tree it must exit 0 (json and sarif modes
    agree) — any new finding in the changed call-graph region fails
    the suite right here."""
    base = [sys.executable, os.path.join(REPO, "tools", "ntalint.py"),
            "--diff", "--no-cache"]
    res = subprocess.run(base, capture_output=True, text=True,
                         timeout=300, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    res = subprocess.run(base + ["--json"], capture_output=True,
                         text=True, timeout=300, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    out = json.loads(res.stdout)
    assert out["findings"] == []
    res = subprocess.run(base + ["--sarif"], capture_output=True,
                         text=True, timeout=300, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert json.loads(res.stdout)["runs"][0]["results"] == []


def test_cli_diff_flags_new_finding_in_changed_region():
    """The exit-1 arm: an untracked file with a finding is inside the
    changed region, so --diff must report it and fail — in SARIF mode
    too (the satellite regression: every output mode gates)."""
    probe = os.path.join(REPO, "nomad_tpu", "_diff_smoke_fixture.py")
    assert not os.path.exists(probe)
    try:
        with open(probe, "w", encoding="utf-8") as fh:
            fh.write(GUARDED_BAD)
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ntalint.py"),
             "--diff", "--no-cache", "--sarif"],
            capture_output=True, text=True, timeout=300, cwd=REPO)
        assert res.returncode == 1, res.stdout + res.stderr
        results = json.loads(res.stdout)["runs"][0]["results"]
        assert {r["ruleId"] for r in results} == {"guarded-by"}
        uris = {r["locations"][0]["physicalLocation"]
                 ["artifactLocation"]["uri"] for r in results}
        assert uris == {"nomad_tpu/_diff_smoke_fixture.py"}
    finally:
        os.unlink(probe)
