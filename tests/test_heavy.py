"""Heavy-lane serving (runtime/batcher.py HeavyGroup + scheduler heavy lane).

Pins the PR's contract: fused index-origin dispatches settle every waiter
with counts byte-identical to sequential execution (and to the independent
BGP oracle), the split path's slice-range parts sum exactly through the
gather barrier, a member's deadline/budget degrades only that member, a
failed or killed slice falls back per-slice without stranding a waiter,
the scheduler's weighted heavy lane never occupies every engine, the slice
count is plan-cache-backed (no more per-query-object ``_heavy_b``), and
plan-time lane routing keeps wide const-start templates out of light fused
groups.
"""

import threading
import time

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.tpu import TPUEngine
from wukong_tpu.loader.lubm import UB, VirtualLubmStrings, generate_lubm
from wukong_tpu.planner.optimizer import Planner
from wukong_tpu.planner.stats import Stats
from wukong_tpu.runtime.batcher import (
    HeavyGroup,
    _HeavySlice,
    _Pending,
    batchable,
    heavy_batchable,
    heavy_key,
)
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.runtime.resilience import Deadline
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.utils.errors import ErrorCode, WukongError

pytestmark = pytest.mark.batch

RDF_TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"


@pytest.fixture(autouse=True, scope="module")
def _lockdep_checked():
    """The heavy-lane suite runs fully checked: the gather barrier's slice
    locks, the scheduler's heavy-lane lock, and the batcher condition all
    feed the lockdep acquisition-order graph on every test."""
    from wukong_tpu.analysis import lockdep

    lockdep.install(True)
    yield
    try:
        assert lockdep.cycles() == [], lockdep.cycles()
        assert lockdep.leaf_violations() == [], lockdep.leaf_violations()
    finally:
        lockdep.install(False)


@pytest.fixture(scope="module")
def world():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    stats = Stats.generate(triples)
    proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss, stats=stats),
                  planner=Planner(stats))
    return {"g": g, "ss": ss, "proxy": proxy, "triples": triples,
            "stats": stats}


@pytest.fixture(autouse=True)
def _knobs_reset(monkeypatch):
    """Every test starts and ends at the defaults (enable_tpu pinned on:
    heavy admission needs the device engine, and an earlier module's
    console run may have loaded a config that turned it off)."""
    monkeypatch.setattr(Global, "enable_batching", False)
    monkeypatch.setattr(Global, "enable_tpu", True)
    monkeypatch.setattr(Global, "heavy_lane", True)
    monkeypatch.setattr(Global, "heavy_split_threshold", 100000)
    monkeypatch.setattr(Global, "heavy_split_max", 4)
    yield


def _heavy_text(world, cls="GraduateStudent"):
    return (f"SELECT ?x ?y WHERE {{ ?x {RDF_TYPE} <{UB}{cls}> . "
            f"?x <{UB}takesCourse> ?y . }}")


def _light_text(world):
    """A const-start 1-hop (the light serving template shape)."""
    from wukong_tpu.types import OUT

    ss, g = world["ss"], world["g"]
    pid = ss.str2id(f"<{UB}memberOf>")
    dept = int(np.asarray(g.get_index(pid, OUT))[0])
    return f"SELECT ?s WHERE {{ ?s <{UB}memberOf> {ss.id2str(dept)} . }}"


def _planned(proxy, text, blind=True, deadline=None):
    q = proxy._parse_text(text)
    proxy._plan_prepared(q, blind, None)
    q.deadline = deadline
    return q


def _counter(name, **labels):
    from wukong_tpu.obs import get_registry

    m = get_registry()._metrics.get(name)
    if m is None:
        return 0.0
    return m.value(**labels) if labels else m.value()


# ---------------------------------------------------------------------------
# recognition + routing
# ---------------------------------------------------------------------------

def test_heavy_batchable_recognition(world):
    proxy = world["proxy"]
    q = _planned(proxy, _heavy_text(world))
    assert q.start_from_index()
    assert heavy_batchable(q)
    assert not batchable(q)
    # non-blind: the sliced dispatch returns counts, not tables
    assert not heavy_batchable(_planned(proxy, _heavy_text(world),
                                        blind=False))
    # const-start light template is not heavy-batchable
    light = _planned(proxy, f"SELECT ?s WHERE {{ ?s {RDF_TYPE} "
                            f"<{UB}FullProfessor> . }}")
    assert heavy_batchable(light)  # 1-hop index scan still qualifies
    # filters need the materialized table
    filt = _planned(proxy, f"SELECT ?x ?y WHERE {{ ?x {RDF_TYPE} "
                           f"<{UB}GraduateStudent> . ?x <{UB}takesCourse> "
                           f"?y . FILTER (?x != ?y) }}")
    assert not heavy_batchable(filt)


def test_heavy_key_groups_identical_templates_only(world):
    proxy = world["proxy"]
    a1 = _planned(proxy, _heavy_text(world, "GraduateStudent"))
    a2 = _planned(proxy, _heavy_text(world, "GraduateStudent"))
    b = _planned(proxy, _heavy_text(world, "UndergraduateStudent"))
    assert heavy_key(a1) == heavy_key(a2)
    assert heavy_key(a1) != heavy_key(b)


def test_classify_lane_routes_index_origin_heavy(world):
    proxy = world["proxy"]
    hq = _planned(proxy, _heavy_text(world))
    assert hq.lane == "heavy"
    lq = _planned(proxy, _light_text(world))
    assert lq.lane == "light"


def test_heavy_routed_const_template_bypasses_light_coalescer(
        world, monkeypatch):
    """A const-start template the optimizer estimates past
    heavy_rows_threshold is tagged heavy and must not join a light fused
    group (heavy_route bypass)."""
    proxy = world["proxy"]
    monkeypatch.setattr(Global, "enable_batching", True)
    monkeypatch.setattr(Global, "heavy_rows_threshold", 1)
    proxy._plan_cache.clear()  # lane memos were recorded at the default
    q = _planned(proxy, _light_text(world))
    assert q.lane == "heavy" and batchable(q)
    before = _counter("wukong_batch_bypass_total", reason="heavy_route")
    assert proxy.batcher().offer(q) is None
    assert _counter("wukong_batch_bypass_total",
                    reason="heavy_route") == before + 1
    proxy._plan_cache.clear()  # drop the threshold=1 lane memos


# ---------------------------------------------------------------------------
# fused heavy dispatch: byte-identical counts
# ---------------------------------------------------------------------------

def test_fused_heavy_counts_match_sequential_and_oracle(world, monkeypatch):
    from tests.bgp_oracle import TripleIndex, eval_bgp

    proxy, ss = world["proxy"], world["ss"]
    text = _heavy_text(world)
    seq = proxy.serve_query(text, blind=True)
    assert seq.result.status_code == ErrorCode.SUCCESS
    want = seq.result.nrows
    assert want > 0
    # the independent oracle agrees with sequential execution
    idx = TripleIndex(world["triples"])
    type_pid = ss.str2id(RDF_TYPE)
    grad = ss.str2id(f"<{UB}GraduateStudent>")
    takes = ss.str2id(f"<{UB}takesCourse>")
    oracle = eval_bgp(idx, [(-1, type_pid, grad), (-1, takes, -2)], [-1, -2])
    assert len(oracle) == want

    monkeypatch.setattr(Global, "enable_batching", True)
    monkeypatch.setattr(Global, "batch_window_us", 100_000)
    # An arrival that finds the batcher idle is dispatched alone at once,
    # and a lone heavy under the split threshold is not a fused dispatch:
    # a group forms only from arrivals DURING a dispatch. On a loaded host
    # threads started one after the other each find it idle, so the
    # batcher is built and warm first and all five arrive together.
    assert proxy.serve_query(text, blind=True).result.nrows == want
    before = _counter("wukong_batch_heavy_fused_total")
    out = [None] * 5
    together = threading.Barrier(len(out))
    def go(i):
        together.wait(timeout=30)
        out[i] = proxy.serve_query(text, blind=True)
    ths = [threading.Thread(target=go, args=(i,)) for i in range(len(out))]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    for i, q in enumerate(out):
        assert q.result.status_code == ErrorCode.SUCCESS, i
        assert q.result.nrows == want, i
    # at least one fused dispatch served multiple members
    assert _counter("wukong_batch_heavy_fused_total") > before


def test_mt_sliced_parts_sum_to_full_total(world):
    """The split path's primitive: mt_factor carrier copies of an
    index-origin batch partition the index list exactly."""
    import copy

    proxy = world["proxy"]
    q = _planned(proxy, _heavy_text(world))
    full = int(np.asarray(
        proxy.tpu.execute_batch_index(q, 8, slice_mode=True)).sum())
    parts = []
    for k in range(3):
        qk = copy.deepcopy(q)
        qk.mt_factor, qk.mt_tid = 3, k
        parts.append(int(np.asarray(
            proxy.tpu.execute_batch_index(qk, 8, slice_mode=True)).sum()))
    assert sum(parts) == full
    assert all(p > 0 for p in parts)


# ---------------------------------------------------------------------------
# member deadline/budget isolation inside a heavy group
# ---------------------------------------------------------------------------

def test_heavy_member_deadline_degrades_only_that_member(world):
    proxy = world["proxy"]
    text = _heavy_text(world)
    bt = proxy.batcher()
    t_frozen = [0.0]
    expired = Deadline(timeout_ms=1, clock=lambda: t_frozen[0])
    t_frozen[0] = 10.0  # expired before the flush
    members = [
        _Pending(_planned(proxy, text)),
        _Pending(_planned(proxy, text, deadline=expired)),
        _Pending(_planned(proxy, text)),
    ]
    HeavyGroup(members, bt, engine=None).run(None)
    ok0, bad, ok2 = (m.q.result for m in members)
    assert ok0.status_code == ErrorCode.SUCCESS and ok0.nrows > 0
    assert ok2.status_code == ErrorCode.SUCCESS and ok2.nrows == ok0.nrows
    assert bad.status_code == ErrorCode.QUERY_TIMEOUT
    assert not bad.complete


def test_heavy_member_budget_charged_per_member(world):
    proxy = world["proxy"]
    text = _heavy_text(world)
    bt = proxy.batcher()
    members = [
        _Pending(_planned(proxy, text)),
        _Pending(_planned(proxy, text, deadline=Deadline(budget_rows=1))),
    ]
    HeavyGroup(members, bt, engine=None).run(None)
    ok, bad = (m.q.result for m in members)
    assert ok.status_code == ErrorCode.SUCCESS and ok.nrows > 0
    assert bad.status_code == ErrorCode.BUDGET_EXCEEDED
    assert not bad.complete


# ---------------------------------------------------------------------------
# split groups: gather barrier + chaos
# ---------------------------------------------------------------------------

def test_split_group_gather_barrier_counts_identical(world, monkeypatch):
    proxy = world["proxy"]
    text = _heavy_text(world)
    want = proxy.serve_query(text, blind=True).result.nrows
    pool = proxy.engine_pool()
    monkeypatch.setattr(Global, "enable_batching", True)
    monkeypatch.setattr(Global, "batch_window_us", 100_000)
    monkeypatch.setattr(Global, "heavy_split_threshold", 1)
    monkeypatch.setattr(Global, "heavy_split_max", 2)
    before = _counter("wukong_batch_heavy_dispatch_total", mode="split")
    out = [None] * 4
    def go(i):
        out[i] = proxy.serve_query(text, blind=True)
    ths = [threading.Thread(target=go, args=(i,)) for i in range(len(out))]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    for i, q in enumerate(out):
        assert q.result.status_code == ErrorCode.SUCCESS, i
        assert q.result.nrows == want, i
    assert _counter("wukong_batch_heavy_dispatch_total",
                    mode="split") > before
    # a SINGLE huge heavy query also takes the split path (solo fuse)
    solo = proxy.serve_query(text, blind=True)
    assert solo.result.status_code == ErrorCode.SUCCESS
    assert solo.result.nrows == want


@pytest.mark.chaos
def test_injected_heavy_dispatch_fault_retries_per_slice(world, monkeypatch):
    """A transient fault at the batch.heavy.dispatch site fails ONE slice;
    the gather barrier re-runs it inline — every waiter settles with the
    correct count (fallback per-slice, not per-group)."""
    from wukong_tpu.runtime import faults
    from wukong_tpu.runtime.faults import FaultPlan, FaultSpec

    proxy = world["proxy"]
    text = _heavy_text(world)
    want = proxy.serve_query(text, blind=True).result.nrows
    proxy.engine_pool()  # split needs live engines
    monkeypatch.setattr(Global, "enable_batching", True)
    monkeypatch.setattr(Global, "batch_window_us", 100_000)
    monkeypatch.setattr(Global, "heavy_split_threshold", 1)
    monkeypatch.setattr(Global, "heavy_split_max", 2)
    before = _counter("wukong_batch_heavy_fallback_total",
                      reason="slice_retry")
    prev = faults.active()
    faults.install(FaultPlan([FaultSpec("batch.heavy.dispatch",
                                        "transient", count=1)]))
    try:
        out = [None] * 3
        def go(i):
            out[i] = proxy.serve_query(text, blind=True)
        ths = [threading.Thread(target=go, args=(i,)) for i in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
    finally:
        faults.install(prev)
    for i, q in enumerate(out):
        assert q.result.status_code == ErrorCode.SUCCESS, i
        assert q.result.nrows == want, i
    assert _counter("wukong_batch_heavy_fallback_total",
                    reason="slice_retry") == before + 1


@pytest.mark.chaos
def test_engine_death_mid_split_dispatch_no_stranded_waiters(
        world, monkeypatch):
    """One engine of a split group dies mid-dispatch (a thread-killing
    exception inside the slice run): the scheduler's death handler fails
    the in-flight slice, the gather barrier re-runs it inline, every
    waiter settles, and the pool respawns the engine."""
    proxy = world["proxy"]
    text = _heavy_text(world)
    want = proxy.serve_query(text, blind=True).result.nrows
    pool = proxy.engine_pool()
    monkeypatch.setattr(Global, "enable_batching", True)
    monkeypatch.setattr(Global, "batch_window_us", 100_000)
    monkeypatch.setattr(Global, "heavy_split_threshold", 1)
    monkeypatch.setattr(Global, "heavy_split_max", 2)

    killed = []
    orig_run = _HeavySlice.run

    def dying_run(self, engine=None):
        # the first pool-dispatched slice (mt_tid > 0) kills its engine
        # thread — SystemExit is not an Exception, so it escapes the
        # engine loop's per-item guard and reaches the death handler
        if self.fq.mt_tid > 0 and not killed:
            if self.claim():
                killed.append(True)
                raise SystemExit("engine killed mid-dispatch")
        return orig_run(self, engine)

    monkeypatch.setattr(_HeavySlice, "run", dying_run)
    respawns_before = _counter("wukong_pool_engine_respawns_total")
    out = [None] * 3
    def go(i):
        out[i] = proxy.serve_query(text, blind=True)
    ths = [threading.Thread(target=go, args=(i,)) for i in range(3)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=60)
    assert killed  # the scenario actually fired
    for i, q in enumerate(out):
        assert q is not None, f"stranded waiter {i}"
        assert q.result.status_code == ErrorCode.SUCCESS, i
        assert q.result.nrows == want, i
    # the dying slice crashed its engine thread; the pool respawned it
    assert _counter("wukong_pool_engine_respawns_total") > respawns_before
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if all(h["alive"] for h in pool.health().values()):
            break
        time.sleep(0.05)
    assert all(h["alive"] for h in pool.health().values())


# ---------------------------------------------------------------------------
# scheduler: weighted heavy lane
# ---------------------------------------------------------------------------

class _Probe:
    """A fire-and-forget heavy-lane item recording run concurrency."""

    lane = "heavy"

    def __init__(self, state, hold_s=0.15):
        self.state = state
        self.hold_s = hold_s
        self.done = threading.Event()

    def run(self, engine=None):
        with self.state["lock"]:
            self.state["cur"] += 1
            self.state["max"] = max(self.state["max"], self.state["cur"])
        time.sleep(self.hold_s)
        with self.state["lock"]:
            self.state["cur"] -= 1
        self.done.set()

    def fail_all(self, exc):
        self.done.set()


def test_heavy_lane_weighted_cap_and_no_light_starvation(world, monkeypatch):
    from wukong_tpu.runtime.scheduler import EnginePool

    monkeypatch.setattr(Global, "heavy_lane_pct", 50)
    pool = EnginePool(num_engines=2,
                      make_engine=lambda tid: CPUEngine(world["g"],
                                                        world["ss"]))
    pool.start()
    try:
        assert pool._heavy_cap() == 1  # 2 engines x 50% = 1
        state = {"cur": 0, "max": 0, "lock": threading.Lock()}
        probes = [_Probe(state) for _ in range(4)]
        for p in probes:
            pool.submit(p, lane="heavy")
        # with a heavy backlog occupying its one allowed engine, a light
        # interactive query still gets served promptly by the other
        q = _planned(world["proxy"], _light_text(world))
        t0 = time.monotonic()
        qid = pool.submit(q)
        pool.wait(qid, timeout=10)
        light_latency = time.monotonic() - t0
        for p in probes:
            assert p.done.wait(timeout=20)
        assert state["max"] <= 1  # the weighted cap held
        assert light_latency < 2 * sum(p.hold_s for p in probes)
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# plan-cache-backed slice sizing (the retired q._heavy_b hack)
# ---------------------------------------------------------------------------

def test_heavy_index_batch_memoized_in_plan_cache(world, monkeypatch):
    proxy = world["proxy"]
    q = _planned(proxy, _heavy_text(world))
    calls = []
    orig = type(proxy.tpu).suggest_index_batch

    def spy(self, qq, cap=1024):
        calls.append(cap)
        return orig(self, qq, cap=cap)

    monkeypatch.setattr(type(proxy.tpu), "suggest_index_batch", spy)
    proxy._plan_cache.clear()
    b1 = proxy.heavy_index_batch(q)
    b2 = proxy.heavy_index_batch(q)
    assert b1 == b2
    assert 1 <= b1 <= Global.heavy_batch_max
    assert len(calls) == 1  # second lookup hit the plan cache
    # the planned query object carries no mutable sizing state anymore
    assert not hasattr(q, "_heavy_b")


def test_emulator_heavy_route_decision_replaces_sentinel(world, monkeypatch):
    """A device failure records an explicit per-class route decision
    ("pool"), not a -1 sentinel on the shared query object."""
    from wukong_tpu.runtime.emulator import Emulator
    from wukong_tpu.runtime.monitor import Monitor

    proxy = world["proxy"]
    emu = Emulator(proxy)
    q0 = _planned(proxy, _heavy_text(world))
    emu._p_cap = 1
    emu._mixed_fail = {}
    emu._heavy_route = {}
    emu._planned = [("heavy", None, q0)]
    emu._probs = np.asarray([1.0])
    emu._served = 0
    emu.class_mode = {}
    rng = np.random.default_rng(0)

    monkeypatch.setattr(
        type(proxy.tpu), "execute_batch_index",
        lambda self, q, B, slice_mode=False: (_ for _ in ()).throw(
            WukongError(ErrorCode.UNKNOWN_PATTERN, "device refused")))
    assert emu._device_batch("heavy", None, q0, rng, 8, cls=0) is False
    assert emu._heavy_route[0] == "pool"
    assert not hasattr(q0, "_heavy_b")
    # routed to the pool, the device path is never tried again
    assert emu._device_batch("heavy", None, q0, rng, 8, cls=0) is False


# ---------------------------------------------------------------------------
# observability: /top lanes + Monitor rolling line
# ---------------------------------------------------------------------------

def test_top_lane_view_and_monitor_line(world, monkeypatch):
    from wukong_tpu.obs.profile import render_top

    proxy = world["proxy"]
    proxy.engine_pool()  # the per-lane depth gauge needs a live pool
    monkeypatch.setattr(Global, "enable_batching", True)
    monkeypatch.setattr(Global, "batch_window_us", 50_000)
    out = [None] * 3
    text = _heavy_text(world)
    def go(i):
        out[i] = proxy.serve_query(text, blind=True)
    ths = [threading.Thread(target=go, args=(i,)) for i in range(3)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert all(q.result.status_code == ErrorCode.SUCCESS for q in out)
    txt, js = render_top(k=4)
    assert "depth[heavy]" in js["lanes"]
    assert "LANES" in txt
    lines = proxy.monitor.lane_lines()
    assert lines and "HeavyLane" in lines[0]


def test_heavy_lane_off_bypasses(world, monkeypatch):
    """heavy_lane off: index-origin queries bypass the batcher (the PR 4
    posture) and still execute correctly."""
    proxy = world["proxy"]
    monkeypatch.setattr(Global, "enable_batching", True)
    monkeypatch.setattr(Global, "heavy_lane", False)
    q = _planned(proxy, _heavy_text(world))
    before = _counter("wukong_batch_bypass_total", reason="shape")
    assert proxy.batcher().offer(q) is None
    assert _counter("wukong_batch_bypass_total", reason="shape") == before + 1
    out = proxy.serve_query(_heavy_text(world), blind=True)
    assert out.result.status_code == ErrorCode.SUCCESS
    assert out.result.nrows > 0
