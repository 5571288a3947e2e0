"""wukong-analyze framework tests: positive/negative fixtures per gate,
lockdep cycle/leaf detection, CLI/shim compatibility, and THE tier-1
repo-wide gate (`test_repo_is_clean`).

Fixture style: every static gate is exercised against a synthetic temp
tree (never the real package), so a gate's failure mode is pinned
independently of the repo's current state; `test_repo_is_clean` is the
one test that runs everything against the live tree.
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from wukong_tpu.analysis import lockdep, plugin_names, run_analysis

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "wukong_tpu")


def write_tree(root, files: dict):
    """Lay out {relpath: source} under root; returns str(root)."""
    for rel, src in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
    return str(root)


# ---------------------------------------------------------------------------
# THE tier-1 gate: every plugin, over the real tree
# ---------------------------------------------------------------------------

def test_repo_is_clean():
    """All analysis gates pass on the repo (the CI contract behind
    ``python -m wukong_tpu.analysis``)."""
    bad = run_analysis(PKG)
    assert bad == [], "\n".join(str(v) for v in bad)


def test_plugin_registry():
    assert set(plugin_names()) == {
        "no-bare-print", "batcher-route", "wal-hook", "guarded-by",
        "fault-sites", "config-readme", "metrics-readme", "error-taxonomy",
        "heat-telemetry", "join-strategy", "slo-telemetry",
        "placement-telemetry", "migration-safety", "cache-coherence",
        "admission-contract", "vector-coherence", "device-telemetry",
        "transport-contract"}


def test_unknown_plugin_rejected():
    with pytest.raises(KeyError):
        run_analysis(PKG, plugins=["no-such-gate"])


# ---------------------------------------------------------------------------
# guarded-by gate
# ---------------------------------------------------------------------------

GUARDED_BAD = '''
import threading

class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self._jobs = []  # guarded by: _lock

    def submit(self, j):
        self._jobs.append(j)

    def drain(self):
        with self._lock:
            out = list(self._jobs)
        return out
'''


def test_guarded_attr_access_outside_lock_flagged(tmp_path):
    pkg = write_tree(tmp_path, {"pool.py": GUARDED_BAD})
    bad = run_analysis(pkg, plugins=["guarded-by"])
    assert len(bad) == 1
    v = bad[0]
    assert v.path == "pool.py" and "submit" in v.message \
        and "_jobs" in v.message and "_lock" in v.message


def test_guarded_attr_access_under_lock_passes(tmp_path):
    good = GUARDED_BAD.replace(
        "    def submit(self, j):\n        self._jobs.append(j)\n",
        "    def submit(self, j):\n        with self._lock:\n"
        "            self._jobs.append(j)\n")
    pkg = write_tree(tmp_path, {"pool.py": good})
    assert run_analysis(pkg, plugins=["guarded-by"]) == []


def test_caller_holds_annotation_passes(tmp_path):
    good = GUARDED_BAD.replace(
        "    def submit(self, j):",
        "    def submit(self, j):  # caller holds: _lock")
    pkg = write_tree(tmp_path, {"pool.py": good})
    assert run_analysis(pkg, plugins=["guarded-by"]) == []


def test_unguarded_inline_allowlist_passes(tmp_path):
    good = GUARDED_BAD.replace(
        "        self._jobs.append(j)",
        "        self._jobs.append(j)  # unguarded: test fixture reason")
    pkg = write_tree(tmp_path, {"pool.py": good})
    assert run_analysis(pkg, plugins=["guarded-by"]) == []


def test_lockfree_declaration_not_enforced(tmp_path):
    good = GUARDED_BAD.replace("# guarded by: _lock",
                               "# lock-free: atomic list append")
    pkg = write_tree(tmp_path, {"pool.py": good})
    assert run_analysis(pkg, plugins=["guarded-by"]) == []


def test_single_entry_point_class_skipped(tmp_path):
    """One public method = cannot race with itself; the gate stays out."""
    src = GUARDED_BAD.replace("    def drain(self):",
                              "    def _drain(self):")
    pkg = write_tree(tmp_path, {"pool.py": src})
    assert run_analysis(pkg, plugins=["guarded-by"]) == []


def test_thread_target_counts_as_entry_point(tmp_path):
    """A private method used as a Thread target makes the class
    multi-threaded even with one public method."""
    src = GUARDED_BAD.replace(
        "    def drain(self):",
        "    def start(self):\n"
        "        threading.Thread(target=self._drain).start()\n\n"
        "    def _drain(self):")
    # now: submit (public) unguarded + _drain is a thread target
    src = src.replace("    def submit(self, j):\n        self._jobs.append",
                      "    def _submit(self, j):\n        self._jobs.append")
    pkg = write_tree(tmp_path, {"pool.py": src})
    bad = run_analysis(pkg, plugins=["guarded-by"])
    assert len(bad) == 1 and "_submit" in bad[0].message


def test_nested_class_attr_annotation_collected(tmp_path):
    """Class-level attribute annotations are anchored to cls.body
    membership, not a hardcoded indent column — a nested class's guarded
    attr must still be enforced."""
    src = '''
import threading

class Outer:
    class Inner:
        shared = {}  # guarded by: _lock

        def __init__(self):
            self._lock = threading.Lock()

        def put(self, k, v):
            self.shared[k] = v

        def get(self, k):
            with self._lock:
                return self.shared.get(k)
'''
    pkg = write_tree(tmp_path, {"mod.py": src})
    bad = run_analysis(pkg, plugins=["guarded-by"])
    assert len(bad) == 1 and "put" in bad[0].message \
        and "shared" in bad[0].message


def test_module_level_guarded_global(tmp_path):
    src = '''
import threading

_lock = threading.Lock()
_state = {}  # guarded by: _lock

def good(k, v):
    with _lock:
        _state[k] = v

def bad(k):
    return _state.get(k)
'''
    pkg = write_tree(tmp_path, {"mod.py": src})
    bad = run_analysis(pkg, plugins=["guarded-by"])
    assert len(bad) == 1 and "bad" not in bad[0].message  # flags the line
    assert bad[0].path == "mod.py" and "_state" in bad[0].message


def test_factory_call_lock_spec(tmp_path):
    """`# guarded by: mutation_lock()` matches `with mutation_lock():`."""
    src = '''
def mutation_lock():
    ...

class Ingestor:
    def __init__(self):
        self.epoch = 0  # guarded by: mutation_lock()

    def commit(self):
        with mutation_lock():
            self.epoch += 1

    def peek(self):
        return self.epoch
'''
    pkg = write_tree(tmp_path, {"ing.py": src})
    bad = run_analysis(pkg, plugins=["guarded-by"])
    assert len(bad) == 1 and "peek" in bad[0].message


# ---------------------------------------------------------------------------
# drift gates (synthetic repo with config/README/tests surfaces)
# ---------------------------------------------------------------------------

CONFIG_SRC = '''
from dataclasses import dataclass, field

@dataclass
class GlobalConfig:
    knob_a: int = 1
    knob_b: bool = False
    derived: int = field(default=0, init=False)
'''


def _drift_repo(tmp_path, readme: str, config: str = CONFIG_SRC,
                tests: dict | None = None):
    pkg = tmp_path / "pkg"
    write_tree(pkg, {"config.py": config})
    (tmp_path / "README.md").write_text(readme)
    tdir = tmp_path / "tests"
    tdir.mkdir(exist_ok=True)
    for name, src in (tests or {}).items():
        (tdir / name).write_text(src)
    return str(pkg), str(tmp_path / "README.md"), str(tdir)


def test_config_readme_missing_knob_flagged(tmp_path):
    pkg, readme, tdir = _drift_repo(tmp_path, "only `knob_a` documented")
    bad = run_analysis(pkg, plugins=["config-readme"], readme_path=readme,
                       tests_dir=tdir)
    assert len(bad) == 1 and "knob_b" in bad[0].message
    # derived (init=False) fields are never knobs
    assert not any("derived" in v.message for v in bad)


def test_config_readme_stale_table_row_flagged(tmp_path):
    readme = ("`knob_a` `knob_b`\n\n"
              "| knob | default |\n|---|---|\n| `ghost_knob` | 0 |\n")
    pkg, readme_p, tdir = _drift_repo(tmp_path, readme)
    bad = run_analysis(pkg, plugins=["config-readme"], readme_path=readme_p,
                       tests_dir=tdir)
    assert len(bad) == 1 and "ghost_knob" in bad[0].message


def test_metrics_readme_both_directions(tmp_path):
    src = ('from x import get_registry\n'
           'M = get_registry().counter("wukong_real_total", "h")\n')
    readme = ("| metric | type |\n|---|---|\n"
              "| `wukong_ghost_total` | counter |\n")
    pkg = write_tree(tmp_path / "pkg", {"m.py": src})
    (tmp_path / "README.md").write_text(readme)
    bad = run_analysis(pkg, plugins=["metrics-readme"],
                       readme_path=str(tmp_path / "README.md"))
    msgs = "\n".join(v.message for v in bad)
    assert "wukong_real_total" in msgs  # registered but undocumented
    assert "wukong_ghost_total" in msgs  # documented but unregistered
    assert len(bad) == 2


FAULTS_SRC = '''
KNOWN_FAULT_SITES = frozenset({"a.site", "b.site"})

def site(name, shard=None):
    ...
'''


def test_fault_sites_three_directions(tmp_path):
    pkg = write_tree(tmp_path / "pkg", {
        "runtime/faults.py": FAULTS_SRC,
        "eng.py": ('from . import faults\n'
                   'def f():\n'
                   '    faults.site("a.site")\n'
                   '    faults.site("rogue.site")\n'),
    })
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "test_x.py").write_text('PLAN = "a.site:transient"\n')
    bad = run_analysis(pkg, plugins=["fault-sites"], tests_dir=str(tdir))
    msgs = "\n".join(v.message for v in bad)
    assert "rogue.site" in msgs      # used but undeclared
    assert "b.site" in msgs          # declared but unused
    assert len(bad) == 2
    # now exercise the declared-but-untested direction
    (tdir / "test_x.py").write_text("nothing here\n")
    bad = run_analysis(pkg, plugins=["fault-sites"], tests_dir=str(tdir))
    msgs = "\n".join(v.message for v in bad)
    assert "never exercised" in msgs and "a.site" in msgs


def test_error_taxonomy_gate(tmp_path):
    src = '''
from wukong_tpu.utils.errors import ErrorCode, WukongError

def good():
    raise WukongError(ErrorCode.SYNTAX_ERROR, "x")

def propagated(child):
    raise WukongError(child.result.status_code, "child failed")

def bad():
    raise WukongError(13, "bare int")
'''
    pkg = write_tree(tmp_path, {"m.py": src})
    bad = run_analysis(pkg, plugins=["error-taxonomy"])
    assert len(bad) == 1 and bad[0].path == "m.py"


# ---------------------------------------------------------------------------
# CLI + shim compatibility
# ---------------------------------------------------------------------------

def test_cli_json_output():
    proc = subprocess.run(
        [sys.executable, "-m", "wukong_tpu.analysis", "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["count"] == 0 and doc["violations"] == []
    assert set(doc["gates"]) == set(plugin_names())


def test_cli_nonzero_on_violation(tmp_path):
    pkg = write_tree(tmp_path, {"m.py": "def f():\n    print('x')\n"})
    proc = subprocess.run(
        [sys.executable, "-m", "wukong_tpu.analysis", "--gate",
         "no-bare-print", str(pkg)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    assert "bare print()" in proc.stdout


def test_lint_obs_shim_exit_codes(tmp_path):
    """`python scripts/lint_obs.py` keeps its exact CLI contract."""
    script = os.path.join(REPO, "scripts", "lint_obs.py")
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True)
    assert proc.returncode == 0 and "lint_obs: clean" in proc.stdout
    pkg = write_tree(tmp_path, {"m.py": "def f():\n    print('x')\n"})
    proc = subprocess.run([sys.executable, script, str(pkg)],
                          capture_output=True, text=True)
    assert proc.returncode == 1 and "1 violation(s)" in proc.stdout


# ---------------------------------------------------------------------------
# lockdep: the runtime half
# ---------------------------------------------------------------------------

@pytest.fixture()
def _lockdep_on():
    lockdep.install(True)
    yield
    lockdep.install(False)


def test_lockdep_detects_abba_cycle(_lockdep_on):
    """The synthetic ABBA interleaving: A->B recorded, then B->A closes
    the cycle — reported once, with BOTH acquisition stacks."""
    A, B = lockdep.make_lock("t.A"), lockdep.make_lock("t.B")
    with A:
        with B:
            pass
    assert lockdep.cycles() == []  # one order alone is fine
    with B:
        with A:
            pass
    cyc = lockdep.cycles()
    assert len(cyc) == 1
    c = cyc[0]
    assert c["cycle"] == ["t.A", "t.B", "t.A"]
    assert c["this_order"] == ("t.B", "t.A")
    # both stacks at first detection: the historical edge's and this one's
    assert "test_analysis" in c["stack_first"]
    assert "test_analysis" in c["stack_here"]
    # repeating the inversion does not re-report
    with B:
        with A:
            pass
    assert len(lockdep.cycles()) == 1


def test_lockdep_abba_across_threads(_lockdep_on):
    """The classic two-thread ABBA, serialized with events so it never
    actually deadlocks — lockdep still reports the potential."""
    A, B = lockdep.make_lock("x.A"), lockdep.make_lock("x.B")
    step = threading.Event()

    def t1():
        with A:
            with B:
                step.set()

    def t2():
        step.wait(2)
        with B:
            with A:
                pass

    ts = [threading.Thread(target=t1), threading.Thread(target=t2)]
    [t.start() for t in ts]
    [t.join(5) for t in ts]
    assert len(lockdep.cycles()) == 1
    c = lockdep.cycles()[0]
    assert c["thread"] != c["thread_first"]  # both sides named


def test_lockdep_consistent_order_is_silent(_lockdep_on):
    A, B, C = (lockdep.make_lock(f"o.{n}") for n in "ABC")
    for _ in range(3):
        with A:
            with B:
                with C:
                    pass
    assert lockdep.cycles() == []
    assert lockdep.leaf_violations() == []


def test_lockdep_leaf_violation(_lockdep_on):
    lockdep.declare_leaf("leaf.L")
    L = lockdep.make_lock("leaf.L")
    X = lockdep.make_lock("leaf.X")
    with L:
        with X:
            pass
    lv = lockdep.leaf_violations()
    assert len(lv) == 1
    assert lv[0]["holding"] == "leaf.L" and lv[0]["acquiring"] == "leaf.X"
    assert "test_analysis" in lv[0]["stack"]


def test_lockdep_flags_mutation_lock_under_leaf(_lockdep_on):
    """The WAL-specific rule from the issue: taking the coarse outer
    mutation_lock() while holding a declared-leaf lock (the WAL's own
    segment lock) is an inversion."""
    from wukong_tpu.store import wal

    seg = lockdep.make_lock("wal.segment")  # declared leaf in wal.py
    with seg:
        with wal.mutation_lock():
            pass
    lv = lockdep.leaf_violations()
    assert any(v["holding"] == "wal.segment"
               and v["acquiring"] == "wal.mutation_lock" for v in lv)


def test_lockdep_rlock_reentrancy_no_self_cycle(_lockdep_on):
    R = lockdep.make_rlock("t.R")
    with R:
        with R:  # reentrant: must not self-edge or double-record
            pass
    assert lockdep.cycles() == []
    assert lockdep.report()["edges"] == []


def test_lockdep_condition_wait_releases_held_state(_lockdep_on):
    """Condition.wait releases the underlying mutex through the wrapper:
    a lock taken by another thread during the wait must NOT look like a
    nested acquisition."""
    cond = lockdep.make_condition("t.cond")
    other = lockdep.make_lock("t.other")
    got = []

    def waiter():
        with cond:
            cond.wait(timeout=2)
            got.append(True)

    t = threading.Thread(target=waiter)
    t.start()
    import time

    time.sleep(0.1)
    with other:  # while the waiter sleeps inside wait()
        pass
    with cond:
        cond.notify()
    t.join(5)
    assert got and lockdep.cycles() == []
    # no edge cond->other was ever created: the wait had released it
    assert ("t.cond", "t.other") not in {
        (e["from"], e["to"]) for e in lockdep.report()["edges"]}


def test_lockdep_metrics_exported(_lockdep_on):
    from wukong_tpu.obs.metrics import get_registry

    L = lockdep.make_lock("m.L")
    with L:
        pass
    snap = get_registry().snapshot()
    hold = snap["wukong_lock_hold_us"]["series"]
    assert any(s["labels"].get("name") == "m.L" and s["count"] >= 1
               for s in hold)


def test_lockdep_contention_counted(_lockdep_on):
    from wukong_tpu.obs.metrics import get_registry

    L = lockdep.make_lock("m.C")
    entered = threading.Event()
    release = threading.Event()

    def holder():
        with L:
            entered.set()
            release.wait(2)

    t = threading.Thread(target=holder)
    t.start()
    entered.wait(2)
    t2 = threading.Thread(target=lambda: L.acquire() or L.release())
    t2.start()
    import time

    time.sleep(0.05)  # let t2 block
    release.set()
    t.join(5)
    t2.join(5)
    val = get_registry().counter(
        "wukong_lock_contended_total",
        labels=("name",)).labels(name="m.C").value
    assert val >= 1


def test_zero_cost_when_off():
    """The overhead contract: with debug_locks off the factories return
    PLAIN threading primitives — not pass-through wrappers."""
    assert not __import__("wukong_tpu.config", fromlist=["Global"]) \
        .Global.debug_locks
    assert type(lockdep.make_lock("z")) is type(threading.Lock())
    assert type(lockdep.make_rlock("z")) is type(threading.RLock())
    assert isinstance(lockdep.make_condition("z"), threading.Condition)
    assert type(lockdep.make_condition("z")._lock) is type(threading.RLock())


def test_install_rebinds_module_level_locks():
    """wal.mutation_lock() is created at import time; install() must swap
    it into checked mode and back."""
    from wukong_tpu.store import wal

    assert type(wal.mutation_lock()) is type(threading.RLock())
    lockdep.install(True)
    try:
        assert isinstance(wal.mutation_lock(), lockdep.DebugRLock)
        assert wal.mutation_lock().name == "wal.mutation_lock"
    finally:
        lockdep.install(False)
    assert type(wal.mutation_lock()) is type(threading.RLock())


def test_lockdep_wired_through_real_runtime(_lockdep_on):
    """Integration: a real EnginePool + WAL + breaker exercise under
    checked mode records edges and stays cycle-free — the same invariant
    the chaos/recovery/batch suites enforce at module teardown."""
    from wukong_tpu.runtime.scheduler import EnginePool

    class Echo:
        def execute(self, q):
            return q

    pool = EnginePool(num_engines=2, make_engine=lambda tid: Echo())
    pool.start()
    try:
        qids = [pool.submit(i) for i in range(16)]
        for qid in qids:
            pool.wait(qid, timeout=5)
    finally:
        pool.stop()
    rep = lockdep.report()
    assert rep["enabled"] and rep["cycles"] == []
    assert any(e["from"] == "pool.route" and e["to"] == "pool.queue"
               for e in rep["edges"])


# ---------------------------------------------------------------------------
# cache-coherence gate: the serving-plane (actuator) half
# ---------------------------------------------------------------------------

_REUSE_OK = (
    "CACHE_INPUTS = {'template_popularity': 'wukong_ok_total',"
    " 'uncacheable': 'wukong_ok_total'}\n"
    "INVALIDATION_CAUSES = ('insert', 'restore')\n"
    "def reg(r):\n"
    "    return r.counter('wukong_ok_total', 'h')\n")


def test_cache_gate_serve_plane_fixtures(tmp_path):
    """The actuator checks fire only on trees WITH serve/ files: consumed
    inputs must be declared CACHE_INPUTS signals, MUTATION_EDGES must
    equal INVALIDATION_CAUSES exactly, every cause must reach a
    notify_mutation call site, and serve locks/state follow the reuse
    module's leaf/annotation discipline."""
    from wukong_tpu.analysis import run_analysis

    bad = write_tree(tmp_path / "bad", {
        "obs/reuse.py": _REUSE_OK,
        "serve/result_cache.py": (
            "CONSUMED_INPUTS = ('template_popularity', 'phantom_signal')\n"
            "MUTATION_EDGES = {'insert': 'kill', 'ghost_edge': 'x'}\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.entries = {}\n"
            "        self.lock = make_lock('serve.x')\n"),
        "store/dynamic.py": (
            "def insert_batch(stores):\n"
            "    maybe_note_invalidation('insert')\n"
            "    notify_mutation('insert')\n"
            "    notify_mutation('bogus_edge')\n")})
    out = run_analysis(bad, plugins=["cache-coherence"])
    msgs = "\n".join(str(v) for v in out)
    assert "phantom_signal" in msgs      # consumed input not in CACHE_INPUTS
    assert "'restore'" in msgs           # journaled cause missing from EDGES
    assert "ghost_edge" in msgs          # phantom edge not a declared cause
    assert "bogus_edge" in msgs          # undeclared cause at a notify site
    assert "serve.x" in msgs             # undeclared leaf lock in serve/
    assert "C.entries" in msgs           # unannotated shared serve state

    good = write_tree(tmp_path / "good", {
        "obs/reuse.py": _REUSE_OK + "declare_leaf('serve.x')\n",
        "serve/result_cache.py": (
            "CONSUMED_INPUTS = ('template_popularity', 'uncacheable')\n"
            "MUTATION_EDGES = {'insert': 'kill stale', 'restore': 'purge'}\n"
            "declare_leaf('serve.x')\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.entries = {}  # guarded by: lock\n"
            "        self.lock = make_lock('serve.x')\n"),
        "store/dynamic.py": (
            "def insert_batch(stores):\n"
            "    maybe_note_invalidation('insert')\n"
            "    notify_mutation('insert')\n"),
        "runtime/recovery.py": (
            "def recover():\n"
            "    maybe_note_invalidation('restore')\n"
            "    notify_mutation('restore')\n")})
    assert run_analysis(good, plugins=["cache-coherence"]) == []


def test_cache_gate_observe_only_tree_skips_serve_checks(tmp_path):
    """A tree WITHOUT serve/ (the PR 13 posture) is not required to have
    an actuator: the notify_mutation coverage rule must not fire."""
    from wukong_tpu.analysis import run_analysis

    tree = write_tree(tmp_path / "obs", {
        "obs/reuse.py": _REUSE_OK,
        "store/dynamic.py": (
            "def insert_batch(stores):\n"
            "    maybe_note_invalidation('insert')\n"),
        "runtime/recovery.py": (
            "def recover():\n"
            "    maybe_note_invalidation('restore')\n")})
    assert run_analysis(tree, plugins=["cache-coherence"]) == []


# ---------------------------------------------------------------------------
# vector-coherence gate: the hybrid graph+vector plane
# ---------------------------------------------------------------------------

def test_vector_gate_fixtures(tmp_path):
    """Declared VECTOR_METRICS must be registered (and vice versa for
    wukong_vector_* names), slot state is written only by the declared
    writers with a version bump, module mutation paths bump the store
    version, vector locks are leaves, and shared state is annotated."""
    from wukong_tpu.analysis import run_analysis

    bad = write_tree(tmp_path / "bad", {
        "vector/__init__.py": (
            "VECTOR_METRICS = {'upserts': 'wukong_vector_up_total',"
            " 'phantom': 'wukong_vector_ghost_total'}\n"),
        "vector/vstore.py": (
            "def reg(r):\n"
            "    r.counter('wukong_vector_up_total', 'h')\n"
            "    r.counter('wukong_vector_rogue_total', 'h')\n"
            "class VectorStore:\n"
            "    def __init__(self):\n"
            "        self.slot_of = {}\n"
            "        self._lock = make_lock('vector.slots')\n"
            "    def _apply_slots(self, vids):\n"
            "        with self._lock:\n"
            "            self.vids = vids\n"
            "    def refresh(self):\n"
            "        self.alive = None\n"
            "def apply_batch(g, vs):\n"
            "    return vs.upsert([1])\n")})
    out = run_analysis(bad, plugins=["vector-coherence"])
    msgs = "\n".join(str(v) for v in out)
    assert "wukong_vector_ghost_total" in msgs  # declared, never registered
    assert "wukong_vector_rogue_total" in msgs  # registered, undeclared
    assert "refresh() writes slot state" in msgs
    assert "never bumps `.version`" in msgs
    assert "apply_batch() applies a vector mutation" in msgs
    assert "vector.slots" in msgs              # undeclared leaf lock
    assert "VectorStore.slot_of" in msgs       # unannotated shared state

    good = write_tree(tmp_path / "good", {
        "vector/__init__.py": (
            "VECTOR_METRICS = {'upserts': 'wukong_vector_up_total'}\n"),
        "vector/vstore.py": (
            "declare_leaf('vector.slots')\n"
            "def reg(r):\n"
            "    r.counter('wukong_vector_up_total', 'h')\n"
            "class VectorStore:\n"
            "    def __init__(self):\n"
            "        self.slot_of = {}  # guarded by: _lock\n"
            "        self._lock = make_lock('vector.slots')\n"
            "    def _apply_slots(self, vids):\n"
            "        with self._lock:\n"
            "            self.vids = vids\n"
            "            self.version += 1\n"
            "def apply_batch(g, vs):\n"
            "    n = vs.upsert([1])\n"
            "    bump_store_version(g)\n"
            "    return n\n")})
    assert run_analysis(good, plugins=["vector-coherence"]) == []


def test_vector_gate_skips_trees_without_vector_plane(tmp_path):
    """Pre-vector trees (and foreign packages) are not required to grow
    a VECTOR_METRICS registry."""
    from wukong_tpu.analysis import run_analysis

    tree = write_tree(tmp_path / "plain", {
        "store/gstore.py": "def build():\n    return 1\n"})
    assert run_analysis(tree, plugins=["vector-coherence"]) == []


# ---------------------------------------------------------------------------
# device-telemetry gate: the device observatory
# ---------------------------------------------------------------------------

def test_device_gate_fixtures(tmp_path):
    """DEVICE_INPUTS must be registered (and vice versa for
    wukong_device_* names), every jit-minting engine/join/vector module
    charges the dispatch seam or justifies itself in the allowlist
    (non-empty, non-stale), device locks are leaves, and the
    observatory's shared state is annotated."""
    from wukong_tpu.analysis import run_analysis

    bad = write_tree(tmp_path / "bad", {
        "obs/device.py": (
            "DEVICE_INPUTS = {'dispatches': 'wukong_device_d_total',"
            " 'phantom': 'wukong_device_ghost_total'}\n"
            "DEVICE_DISPATCH_ALLOWLIST = {"
            "'engine/kernels.py': '',"              # empty justification
            "'engine/retired.py': 'charged at the chain seam'}\n"
            "def reg(r):\n"
            "    r.counter('wukong_device_d_total', 'h')\n"
            "    r.counter('wukong_device_rogue_total', 'h')\n"
            "class Ledger:\n"
            "    def __init__(self):\n"
            "        self.stats = {}\n"
            "        self._lock = make_lock('device.dispatch')\n"),
        # mints jax.jit, never charges the seam, not allowlisted
        "join/probe.py": (
            "import jax\n"
            "def mint():\n"
            "    return jax.jit(lambda x: x)\n"),
        # allowlisted as 'retired' but actually charges the seam → stale
        "engine/retired.py": (
            "import jax\n"
            "def run(fn):\n"
            "    out = jax.jit(fn)(1)\n"
            "    maybe_device_dispatch('engine.retired', live=1)\n"
            "    return out\n")})
    out = run_analysis(bad, plugins=["device-telemetry"])
    msgs = "\n".join(str(v) for v in out)
    assert "wukong_device_ghost_total" in msgs  # declared, unregistered
    assert "wukong_device_rogue_total" in msgs  # registered, undeclared
    assert "join/probe.py" in msgs              # uncharged jit site
    assert "empty" in msgs and "engine/kernels.py" in msgs
    assert "stale" in msgs and "engine/retired.py" in msgs
    assert "device.dispatch" in msgs            # undeclared leaf lock
    assert "Ledger.stats" in msgs               # unannotated shared state

    good = write_tree(tmp_path / "good", {
        "obs/device.py": (
            "declare_leaf('device.dispatch')\n"
            "DEVICE_INPUTS = {'dispatches': 'wukong_device_d_total'}\n"
            "DEVICE_DISPATCH_ALLOWLIST = {"
            "'engine/kernels.py': 'dispatched and charged in "
            "engine/run.py at the sync point'}\n"
            "def reg(r):\n"
            "    r.counter('wukong_device_d_total', 'h')\n"
            "class Ledger:\n"
            "    def __init__(self):\n"
            "        self.stats = {}  # guarded by: _lock\n"
            "        self._lock = make_lock('device.dispatch')\n"),
        # definition-only module, justified in the allowlist
        "engine/kernels.py": (
            "import jax\n"
            "def wk_walk_compact(x):\n"
            "    return x\n"
            "compact = jax.jit(wk_walk_compact)\n"),
        # invoking module charges the seam itself
        "engine/run.py": (
            "import jax\n"
            "def run(wk_walk_fn, x):\n"
            "    out = jax.jit(wk_walk_fn)(x)\n"
            "    maybe_device_dispatch('engine.run', live=1)\n"
            "    return out\n")})
    assert run_analysis(good, plugins=["device-telemetry"]) == []


_NAMED_OK = (
    "import functools\n"
    "import jax\n"
    "from jax import shard_map\n"
    "@jax.jit\n"
    "def wk_walk_a(x):\n"
    "    return x\n"
    "@functools.partial(jax.jit, static_argnames=('n',))\n"
    "def wk_walk_b(x, n):\n"
    "    return x\n"
    "def mint(mesh):\n"
    "    def wk_dist_chain(x):\n"
    "        return x\n"
    "    c = jax.jit(jax.vmap(wk_dist_chain))\n"
    "    d = functools.partial(jax.jit, static_argnames=())(wk_dist_chain)\n"
    "    return c, d, jax.jit(shard_map(wk_dist_chain, mesh=mesh))\n"
    "def wk_template(x):\n"
    "    return x\n"
    "wk_template.__name__ = 'wk_template_t0123abcd'\n"
    "program = jax.jit(wk_template)\n")


@pytest.mark.parametrize("unnamed,who", [
    ("f = jax.jit(lambda x: x)\n", "'<lambda>'"),
    ("def run(x):\n    return x\nf = jax.jit(run)\n", "'run'"),
    ("@jax.jit\ndef expand(x):\n    return x\n", "'expand'"),
    ("@functools.partial(jax.jit, static_argnames=('n',))\n"
     "def scan(x, n):\n    return x\n", "'scan'"),
    ("def one(x):\n    return x\nf = jax.jit(jax.vmap(one))\n", "'one'"),
])
def test_device_gate_names_every_jitted_function(tmp_path, unnamed, who):
    """A function handed to jax.jit under engine/join/vector/stream/
    parallel carries a wk_ name, whatever form hands it: one unnamed
    function in a module of named ones is one violation, at its line."""
    from wukong_tpu.analysis import run_analysis

    allow = ("DEVICE_DISPATCH_ALLOWLIST = {'stream/k.py': 'charged at the "
             "sync seam of stream/run.py'}\n")
    tree = write_tree(tmp_path / "t", {
        "obs/device.py": _DEV_OK.replace("DEVICE_DISPATCH_ALLOWLIST = {}\n",
                                         allow),
        "stream/k.py": _NAMED_OK + unnamed})
    out = run_analysis(tree, plugins=["device-telemetry"])
    assert len(out) == 1 and who in str(out[0]), out
    assert out[0].path == "stream/k.py"
    assert out[0].line > _NAMED_OK.count("\n")
    tree = write_tree(tmp_path / "ok", {
        "obs/device.py": _DEV_OK.replace("DEVICE_DISPATCH_ALLOWLIST = {}\n",
                                         allow),
        "stream/k.py": _NAMED_OK})
    assert run_analysis(tree, plugins=["device-telemetry"]) == []


def test_every_jitted_function_of_the_package_is_named():
    """The tree itself: no jitted function without its wk_ name, and the
    gate finds the programs the profile shows."""
    import ast

    from wukong_tpu.analysis import run_analysis
    from wukong_tpu.analysis.devicegate import NAMED_PREFIXES, _jitted_names

    import wukong_tpu

    pkg = os.path.dirname(os.path.abspath(wukong_tpu.__file__))
    assert run_analysis(pkg, plugins=["device-telemetry"]) == []
    names = set()
    for rel in NAMED_PREFIXES:
        for dirpath, _dirs, files in os.walk(os.path.join(pkg, rel)):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(dirpath, f)) as fh:
                        names |= {n for _l, n in
                                  _jitted_names(ast.parse(fh.read()))}
    assert {"wk_walk_expand", "wk_walk_merge_expand", "wk_template",
            "wk_level_probe", "wk_dist_chain", "wk_knn_scan",
            "wk_stream_seed_masks"} <= names
    assert all(n.startswith("wk_") for n in names)


def test_device_gate_skips_trees_without_device_plane(tmp_path):
    """Pre-observatory trees (and foreign packages) are not required to
    grow a DEVICE_INPUTS registry."""
    from wukong_tpu.analysis import run_analysis

    tree = write_tree(tmp_path / "plain", {
        "engine/tpu.py": "import jax\nf = jax.jit(lambda x: x)\n"})
    assert run_analysis(tree, plugins=["device-telemetry"]) == []


_DEV_OK = (
    "declare_leaf('device.dispatch')\n"
    "DEVICE_INPUTS = {'dispatches': 'wukong_device_d_total',"
    " 'padding_efficiency': 'wukong_device_pe'}\n"
    "DEVICE_DISPATCH_ALLOWLIST = {}\n"
    "def reg(r):\n"
    "    r.counter('wukong_device_d_total', 'h')\n"
    "    r.gauge('wukong_device_pe', 'h')\n")


def test_template_coherence_fixtures(tmp_path):
    """PR 19's actuator contract: the compiled-program cache key fills
    on store version + the route-knob set, TEMPLATE_ROUTES is a literal
    registry, and the route chooser's every signal read is a
    read_device_input() call against a declared DEVICE_INPUTS member —
    never a direct reach into the observatory."""
    from wukong_tpu.analysis import run_analysis

    bad = write_tree(tmp_path / "bad", {
        "obs/device.py": _DEV_OK,
        "engine/template_compile.py": (
            # no TEMPLATE_ROUTES literal; key ignores store version and
            # knobs; chooser reads a ghost signal, a non-literal signal,
            # and pokes the observatory directly
            "def _program_key(tsig, caps):\n"
            "    return (tsig, tuple(caps))\n"
            "def choose_template_route(tsig, est):\n"
            "    sig = 'pad' + 'ding'\n"
            "    read_device_input(sig)\n"
            "    read_device_input('ghost_signal')\n"
            "    return 'device' if _observatory else 'host'\n")})
    msgs = "\n".join(str(v) for v in
                     run_analysis(bad, plugins=["device-telemetry"]))
    assert "TEMPLATE_ROUTES" in msgs
    assert "store_version" in msgs
    assert "knob" in msgs
    assert "non-literal signal" in msgs
    assert "ghost_signal" in msgs
    assert "directly" in msgs

    good = write_tree(tmp_path / "good", {
        "obs/device.py": _DEV_OK,
        "engine/template_compile.py": (
            "TEMPLATE_ROUTES = {'device': 'fused whole-plan program',"
            " 'host': 'the NumPy walk'}\n"
            "def _route_knobs():\n"
            "    return (str(Global.template_device),)\n"
            "def _program_key(tsig, store_version, caps):\n"
            "    return (tsig, store_version, tuple(caps),"
            " _route_knobs())\n"
            "def choose_template_route(tsig, est):\n"
            "    eff = read_device_input('padding_efficiency')\n"
            "    n = read_device_input('dispatches')\n"
            "    return 'host' if eff is None else 'device'\n")})
    assert run_analysis(good, plugins=["device-telemetry"]) == []


def test_template_coherence_skips_trees_without_template_plane(tmp_path):
    """A device plane without the compiled-template engine (PR 18
    trees) is exempt from the template-coherence checks."""
    from wukong_tpu.analysis import run_analysis

    tree = write_tree(tmp_path / "pre", {"obs/device.py": _DEV_OK})
    assert run_analysis(tree, plugins=["device-telemetry"]) == []
