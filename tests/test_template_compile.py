"""Whole-plan compiled template execution (ISSUE 19 acceptance).

The acceptance bar: the fused XLA program returns BYTE-IDENTICAL result
rows — including row order — to the host walk across chain, const-start,
index-start, filter (known-known / known-const / const-known) and
projection shapes plus six cyclic cases; a compile-time or mid-flight
dispatch fault degrades the SAME query to the walk (SUCCESS, identical
bytes, fallback counted, per-template demotion latched); a dynamic
insert makes stale programs unreachable and re-arms the latch; the
program cache evicts under ``template_budget_mb``; and the stream-epoch
/ view-maintenance device frontier is byte-identical to the host
oracle. The serve-path drills run fully lockdep-checked.
"""

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.template_compile import (
    TEMPLATE_ROUTES,
    TemplateCompiledEngine,
    TemplateOverflow,
    choose_template_route,
    demotion_report,
    extract_template,
    is_demoted,
    latch_demotion,
    reset_demotions,
    route_why,
)
from wukong_tpu.join.kernels import capacity_class
from wukong_tpu.loader.datagen import (
    CyclicStrings,
    cyclic_query_text,
    generate_clique4,
    generate_diamond,
    generate_triangle,
)
from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.planner.optimizer import Planner
from wukong_tpu.planner.stats import Stats
from wukong_tpu.runtime import faults
from wukong_tpu.runtime.faults import FaultPlan, FaultSpec
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.types import IN, OUT, PREDICATE_ID, TYPE_ID
from wukong_tpu.utils.errors import ErrorCode

pytestmark = pytest.mark.template

WORLDS = {
    "triangle": lambda: generate_triangle(m=60, noise=3, seed=1),
    "diamond": lambda: generate_diamond(m=40, noise=2, seed=1),
    "clique4": lambda: generate_clique4(n=120, fan=6, ncliques=8, seed=1),
}


@pytest.fixture(scope="module", params=sorted(WORLDS))
def world(request):
    triples, meta = WORLDS[request.param]()
    g = build_partition(triples, 0, 1)
    stats = Stats.generate(triples)
    return request.param, triples, g, stats, meta


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Every test starts with no fault plan, no demotion latches, a
    clean observatory, and the template knobs at their defaults
    (monkeypatch rolls any per-test knob override back)."""
    from wukong_tpu.obs.device import get_device_obs

    faults.clear()
    reset_demotions()
    get_device_obs().reset()
    monkeypatch.setattr(Global, "template_device", "auto")
    monkeypatch.setattr(Global, "template_min_rows", 4096)
    monkeypatch.setattr(Global, "template_capacity_retries", 3)
    monkeypatch.setattr(Global, "template_budget_mb", 256)
    monkeypatch.setattr(Global, "join_strategy", "auto")
    monkeypatch.setattr(Global, "join_device_min_candidates", 65536)
    yield
    faults.clear()
    reset_demotions()
    get_device_obs().reset()


def mkq(meta, blind=False) -> SPARQLQuery:
    q = SPARQLQuery()
    q.pattern_group.patterns = [Pattern(s, p, OUT, o)
                                for (s, p, o) in meta["patterns"]]
    q.result.nvars = len(meta["vars"])
    q.result.required_vars = list(meta["vars"])
    q.result.blind = blind
    return q


def handq(pats, vars_, blind=False) -> SPARQLQuery:
    """A query with an explicit pattern order (no planner reordering):
    the shape-matrix tests pin each fused op kind this way."""
    q = SPARQLQuery()
    q.pattern_group.patterns = [Pattern(s, p, d, o) for (s, p, d, o) in pats]
    q.result.nvars = len(vars_)
    q.result.required_vars = list(vars_)
    q.result.blind = blind
    return q


def assert_identical(qh: SPARQLQuery, qc: SPARQLQuery) -> None:
    """Byte identity INCLUDING row order — the compiled path's contract
    is the host walk's exact reply, not a row-set match."""
    assert qh.result.status_code == qc.result.status_code
    assert qh.result.nrows == qc.result.nrows
    assert qh.result.col_num == qc.result.col_num
    assert qh.result.v2c_map == qc.result.v2c_map
    th = np.asarray(qh.result.table)
    tc = np.asarray(qc.result.table)
    assert th.dtype == tc.dtype
    assert th.shape == tc.shape
    assert np.array_equal(th, tc)


def run_pair(g, build, plan=False):
    """(host walk, compiled) executions of the same query builder."""
    qh = build()
    if plan:
        heuristic_plan(qh)
    CPUEngine(g).execute(qh)
    qc = build()
    if plan:
        heuristic_plan(qc)
    served = TemplateCompiledEngine(g).try_execute(qc)
    return qh, qc, served


# ---------------------------------------------------------------------------
# byte identity: six cyclic cases (three worlds x projected/blind)
# ---------------------------------------------------------------------------

def test_compiled_matches_walk_cyclic(world):
    name, _triples, g, _stats, meta = world
    qh, qc, served = run_pair(g, lambda: mkq(meta), plan=True)
    assert served, name
    assert qc._template_compiled
    assert_identical(qh, qc)


def test_compiled_matches_walk_cyclic_blind(world):
    """Blind replies take the unfused path: the full table plus the
    host engine's ``_final_process`` replayed verbatim."""
    name, _triples, g, _stats, meta = world
    qh, qc, served = run_pair(g, lambda: mkq(meta, blind=True), plan=True)
    assert served, name
    assert qh.result.status_code == qc.result.status_code
    assert qh.result.nrows == qc.result.nrows, name


# ---------------------------------------------------------------------------
# byte identity: the fused-op shape matrix (hand-ordered plans)
# ---------------------------------------------------------------------------

def _tri_world():
    triples, meta = generate_triangle(m=60, noise=3, seed=1)
    return triples, build_partition(triples, 0, 1), meta


def test_const_start_chain_identity():
    triples, g, _meta = _tri_world()
    a = int(triples[triples[:, 1] == 2][0, 0])
    qh, qc, served = run_pair(
        g, lambda: handq([(a, 2, OUT, -1), (-1, 3, OUT, -2)], [-1, -2]))
    assert served
    spec = extract_template(handq([(a, 2, OUT, -1), (-1, 3, OUT, -2)],
                                  [-1, -2]))
    assert [op[0] for op in spec[0]] == ["const_list", "expand"]
    assert_identical(qh, qc)


def test_index_start_chain_identity():
    _triples, g, _meta = _tri_world()
    pats = [(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2), (-2, 3, OUT, -3)]
    qh, qc, served = run_pair(g, lambda: handq(pats, [-1, -2, -3]))
    assert served
    spec = extract_template(handq(pats, [-1, -2, -3]))
    assert [op[0] for op in spec[0]] == ["index", "expand", "expand"]
    assert_identical(qh, qc)


def test_filter_pair_const_identity():
    triples, g, _meta = _tri_world()
    c = int(triples[triples[:, 1] == 4][0, 2])
    pats = [(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2), (-1, 4, OUT, c)]
    qh, qc, served = run_pair(g, lambda: handq(pats, [-1, -2]))
    assert served
    spec = extract_template(handq(pats, [-1, -2]))
    assert [op[0] for op in spec[0]] == ["index", "expand",
                                         "filter_pair_const"]
    assert qh.result.nrows > 0  # a vacuous filter proves nothing
    assert_identical(qh, qc)


def test_filter_member_identity():
    triples, g, _meta = _tri_world()
    a = int(triples[triples[:, 1] == 2][0, 0])
    pats = [(3, PREDICATE_ID, IN, -1), (a, 2, OUT, -1)]
    qh, qc, served = run_pair(g, lambda: handq(pats, [-1]))
    assert served
    spec = extract_template(handq(pats, [-1]))
    assert [op[0] for op in spec[0]] == ["index", "filter_member"]
    assert qh.result.nrows > 0
    assert_identical(qh, qc)


def test_projection_subset_fused_identity():
    """A strict-subset projection fuses on device (only the projected
    columns come back) and still matches the walk's reply bytes."""
    _triples, g, _meta = _tri_world()
    pats = [(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2), (-2, 3, OUT, -3)]
    qh, qc, served = run_pair(g, lambda: handq(pats, [-3]))
    assert served
    spec = extract_template(handq(pats, [-3]))
    assert spec[2] == (2,)  # proj fused to the one required column
    assert qc.result.col_num == 1
    assert_identical(qh, qc)


def test_distinct_replays_host_final_process():
    """DISTINCT keeps the full fused table and replays the host
    ``_final_process`` verbatim — reply bytes identical to the walk."""
    _triples, g, _meta = _tri_world()
    pats = [(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2)]

    def build():
        q = handq(pats, [-2])
        q.distinct = True
        return q

    qh, qc, served = run_pair(g, build)
    assert served
    assert extract_template(build())[2] is None  # proj NOT fused
    assert_identical(qh, qc)


def test_unsupported_shapes_leave_query_untouched():
    """FILTER / OPTIONAL / deadline shapes are refused (False) with the
    query untouched — the walk owns them, nothing is latched."""
    _triples, g, _meta = _tri_world()
    eng = TemplateCompiledEngine(g)

    q = handq([(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2)], [-1, -2])
    q.pattern_group.filters = [object()]
    assert not eng.try_execute(q)
    assert q.pattern_step == 0 and q.result.table.size == 0

    q2 = handq([(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2)], [-1, -2])
    q2.mt_factor = 4
    assert not eng.try_execute(q2)
    assert demotion_report() == {}  # refusal is not a failure


# ---------------------------------------------------------------------------
# capacity classes: retry growth + overflow ceiling
# ---------------------------------------------------------------------------

def test_capacity_retry_regrows_and_matches(monkeypatch):
    """Deliberately undersized capacity classes overflow, regrow
    (``_grow_caps``) and converge to the identical reply — the good
    classes are memoized so the next query dispatches once."""
    from wukong_tpu.obs.device import get_device_obs, read_device_input

    monkeypatch.setattr(Global, "enable_device_obs", True)
    get_device_obs().reset()
    _triples, g, meta = _tri_world()

    def build():
        q = mkq(meta)
        heuristic_plan(q)
        return q

    spec, _v2c, _proj, _width = extract_template(build())
    eng = TemplateCompiledEngine(g)
    version = eng._version()
    eng._good_caps[(spec, version)] = (128, 64, 64)  # far too small
    qc = build()
    assert eng.try_execute(qc)
    qh = build()
    CPUEngine(g).execute(qh)
    assert_identical(qh, qc)
    counts = read_device_input("dispatches", "template.plan")
    assert int(counts["count"]) >= 2  # at least one overflow retry
    assert eng._good_caps[(spec, version)] != (128, 64, 64)


def test_overflow_past_ceiling_degrades_on_serve_path():
    """When the capacity ceiling makes the template untenable the serve
    path degrades to the walk — SUCCESS, identical bytes, fallback
    counted, per-template demotion latched."""
    proxy, text = _mk_tri_proxy()
    Global.join_strategy = "walk"
    Global.template_device = "host"
    qw = proxy.run_single_query(text, blind=False)
    Global.template_device = "device"
    old_max = Global.table_capacity_max
    old_min = Global.table_capacity_min
    Global.table_capacity_min = 64
    Global.table_capacity_max = 128
    try:
        before = _fallbacks(proxy)
        q = proxy.run_single_query(text, blind=False)
    finally:
        Global.table_capacity_max = old_max
        Global.table_capacity_min = old_min
    assert q.result.status_code == ErrorCode.SUCCESS
    assert not getattr(q, "_template_compiled", False)
    assert_identical(qw, q)
    assert _fallbacks(proxy) == before + 1
    assert "TemplateOverflow" in demotion_report().values()


# ---------------------------------------------------------------------------
# the route chooser (TEMPLATE_ROUTES contract)
# ---------------------------------------------------------------------------

def test_route_chooser_knobs_and_thresholds():
    """Under ``auto`` programs win at both ends: every capacity class
    under ``template_min_rows`` (the calls are the cost), or the estimated
    peak at or over it (the device is the cost); the walk keeps the
    middle. With no classes given (the walk is NumPy on the host) only the
    estimate routes to a program."""
    sig = ("t", 1)
    Global.template_device = "host"
    assert choose_template_route(sig, 10 ** 6) == "host"
    assert choose_template_route(sig, 10, caps=(8, 8)) == "host"
    Global.template_device = "device"
    assert choose_template_route(sig, None) == "device"
    Global.template_device = "auto"
    Global.template_min_rows = 1000
    assert choose_template_route(sig, 999) == "host"
    assert choose_template_route(sig, None) == "host"
    assert choose_template_route(sig, 1000) == "device"
    # the small end: decided by the classes, whatever the estimate
    assert choose_template_route(sig, 10, caps=(512, 512)) == "device"
    assert choose_template_route(sig, None, caps=(512,)) == "device"
    assert route_why(10, (512, 512)) == "small_classes"
    # the middle: one class at the threshold and a small estimate
    assert choose_template_route(sig, 999, caps=(512, 1024)) == "host"
    assert choose_template_route(sig, 999, caps=(512, 1000)) == "host"
    assert route_why(999, (512, 1024)) is None
    # a plan that cannot be compiled has no classes
    assert choose_template_route(sig, 999, caps=()) == "host"
    # the large end as before
    assert choose_template_route(sig, 1000, caps=(512, 4096)) == "device"
    assert route_why(1000, (512, 4096)) == "estimate"
    # a latch outranks both halves
    latch_demotion(sig, "small_measured", version=3)
    assert choose_template_route(sig, 10, 3, caps=(512,)) == "latched_host"
    assert set(TEMPLATE_ROUTES) == {"device", "host", "latched_host"}


def test_demotion_latch_and_store_version_rearm():
    sig = ("t", 2)
    latch_demotion(sig, "compile_failed", version=7)
    assert is_demoted(sig, 7)
    Global.template_device = "device"
    assert choose_template_route(sig, 10 ** 6, version=7) == "latched_host"
    # a store mutation re-arms the device attempt
    assert not is_demoted(sig, 8)
    assert choose_template_route(sig, 10 ** 6, version=8) == "device"
    assert "compile_failed" in demotion_report().values()
    reset_demotions()
    assert demotion_report() == {}


# ---------------------------------------------------------------------------
# serve-path: chaos degrade, invalidation, feedback, EXPLAIN (lockdep)
# ---------------------------------------------------------------------------

@pytest.fixture()
def lockdep_checked():
    from wukong_tpu.analysis import lockdep

    lockdep.install(True)
    yield
    try:
        assert lockdep.cycles() == [], lockdep.cycles()
        assert lockdep.leaf_violations() == [], lockdep.leaf_violations()
    finally:
        lockdep.install(False)


def _mk_tri_proxy():
    triples, meta = generate_triangle(m=60, noise=3, seed=1)
    g = build_partition(triples, 0, 1)
    ss = CyclicStrings(meta)
    stats = Stats.generate(triples)
    proxy = Proxy(g, ss, cpu_engine=CPUEngine(g, ss),
                  planner=Planner(stats))
    return proxy, cyclic_query_text(meta)


@pytest.fixture()
def tri_proxy():
    return _mk_tri_proxy()


def _fallbacks(proxy) -> float:
    total = 0.0
    for s in proxy.metrics.snapshot().get(
            "wukong_template_fallback_total", {}).get("series", []):
        total += s["value"]
    return total


def test_serve_path_routes_device_and_matches_walk(tri_proxy,
                                                   lockdep_checked):
    proxy, text = tri_proxy
    Global.join_strategy = "walk"
    Global.template_device = "host"
    qw = proxy.run_single_query(text, blind=False)
    assert getattr(qw, "template_route", None) == "host"
    Global.template_device = "device"
    qd = proxy.run_single_query(text, blind=False)
    assert qd.template_route == "device"
    assert qd._template_compiled
    assert_identical(qw, qd)


@pytest.mark.chaos
@pytest.mark.parametrize("site", ["template.compile", "template.dispatch"])
def test_template_fault_degrades_to_walk_and_latches(tri_proxy, site,
                                                     lockdep_checked):
    """An injected compile-time or MID-FLIGHT dispatch transient fires
    with the query untouched; the serve path degrades the SAME query to
    the walk (SUCCESS, identical bytes, fallback counted) and latches
    the per-template demotion so the next query never re-pays the
    failed device attempt."""
    proxy, text = tri_proxy
    Global.join_strategy = "walk"
    Global.template_device = "host"
    qw = proxy.run_single_query(text, blind=False)
    Global.template_device = "device"
    before = _fallbacks(proxy)
    faults.install(FaultPlan([FaultSpec(site=site, kind="transient")],
                             seed=7))
    q = proxy.run_single_query(text, blind=False)
    faults.clear()
    assert q.result.status_code == ErrorCode.SUCCESS
    assert q.result.complete
    assert not getattr(q, "_template_compiled", False)
    assert_identical(qw, q)
    assert _fallbacks(proxy) == before + 1
    assert "TransientFault" in demotion_report().values()
    # the latch routes the next same-template query straight to host
    q2 = proxy.run_single_query(text, blind=False)
    assert q2.template_route == "latched_host"
    assert_identical(qw, q2)


def test_store_version_invalidation_via_dynamic_insert(tri_proxy,
                                                       lockdep_checked):
    """A dynamic insert bumps the store version: the next compiled
    execution sees the new rows (stale programs are unreachable AND
    reaped from the cache) — byte-identical to the host walk on the
    mutated store."""
    from wukong_tpu.store.dynamic import insert_triples
    from wukong_tpu.types import NORMAL_ID_START

    proxy, text = tri_proxy
    Global.join_strategy = "walk"
    Global.template_device = "device"
    base = proxy.run_single_query(text, blind=False)
    assert base._template_compiled
    # the first sound run settled the template's classes; the second builds
    # the program that stays, and the guessed one goes then (ISSUE 33)
    eng = proxy.template_engine()
    assert proxy.run_single_query(text, blind=False)._template_compiled
    assert eng.program_count() == 1
    assert {k[1] for k in eng._programs} == {eng._version()}
    a, b, c = (NORMAL_ID_START + 7001, NORMAL_ID_START + 7002,
               NORMAL_ID_START + 7003)
    insert_triples(proxy.g, np.asarray(
        [[a, 2, b], [b, 3, c], [a, 4, c]], dtype=np.int64))
    q = proxy.run_single_query(text, blind=False)
    assert q._template_compiled
    rows = set(map(tuple, q.result.table.tolist()))
    base_rows = set(map(tuple, base.result.table.tolist()))
    assert rows - base_rows == {(a, b, c)}
    q2 = proxy.run_single_query(text, blind=False)  # at the settled classes
    assert q2._template_compiled
    Global.template_device = "host"
    qw = proxy.run_single_query(text, blind=False)
    assert_identical(qw, q)
    assert_identical(qw, q2)
    # every cached program is keyed at the post-insert version
    version = int(proxy.g.version)
    assert eng.program_count() == 1
    assert all(k[1] == version for k in eng._programs)


def test_small_measured_feedback_demotes_auto_route(tri_proxy,
                                                    monkeypatch):
    """Under ``auto`` a successful compiled run whose MEASURED live
    rows undershoot ``template_min_rows`` latches the template back to
    host — the estimate over-predicted."""
    from wukong_tpu.obs.device import get_device_obs

    monkeypatch.setattr(Global, "enable_device_obs", True)
    get_device_obs().reset()
    proxy, text = tri_proxy
    Global.join_strategy = "walk"
    Global.template_device = "auto"
    Global.template_min_rows = 1000  # est 1500 routes device; live 715
    q = proxy.run_single_query(text, blind=False)
    assert q.template_route == "device"
    assert q._template_compiled
    assert "small_measured" in demotion_report().values()
    # the demoted template's program goes with the latch
    assert proxy.template_engine().program_count() == 0
    q2 = proxy.run_single_query(text, blind=False)
    assert q2.template_route == "latched_host"


def test_numpy_walk_keeps_small_plans(tri_proxy):
    """A proxy whose walk is the NumPy engine makes no jitted call a step:
    there the small end of the rule does not apply, and a plan estimated
    under ``template_min_rows`` walks as it always did."""
    proxy, text = tri_proxy
    Global.join_strategy = "walk"
    Global.template_min_rows = 1 << 20  # over the estimate and the classes
    q = proxy.run_single_query(text, blind=False)
    assert q.template_route == "host"
    assert q._template_plan_caps is None
    assert not getattr(q, "_template_compiled", False)
    assert proxy.template_engine().program_count() == 0


def test_explain_renders_template_compiled_route(tri_proxy, monkeypatch):
    """EXPLAIN / EXPLAIN ANALYZE (satellite b): the route line says
    ``template-compiled`` and the per-step device table carries the
    whole-plan compiled row."""
    from wukong_tpu.obs.device import get_device_obs

    monkeypatch.setattr(Global, "enable_device_obs", True)
    get_device_obs().reset()
    proxy, text = tri_proxy
    Global.join_strategy = "walk"
    Global.template_device = "device"
    proxy.serve_query(text)  # settles the capacity classes (a retry is a
    get_device_obs().reset()  # dispatch of its own)
    rep = proxy.explain_query(text, analyze=True)
    assert rep["route"] == "template-compiled"
    assert "route: template-compiled" in rep["rendered"]
    steps = [r for r in rep.get("device_steps", [])
             if r.get("site") == "template.plan"]
    assert len(steps) == 1  # the whole plan is ONE dispatch
    assert steps[0]["live"] == 715


# ---------------------------------------------------------------------------
# program cache: residency budget eviction
# ---------------------------------------------------------------------------

def test_budget_eviction_under_template_budget_mb(monkeypatch):
    """Two oversized programs cannot co-reside under a 1 MB budget: the
    LRU victim is evicted with its bytes charged on the residency
    ledger (kind ``template``)."""
    from wukong_tpu.obs.device import get_device_obs, read_device_input

    monkeypatch.setattr(Global, "enable_device_obs", True)
    monkeypatch.setattr(Global, "template_budget_mb", 1)
    get_device_obs().reset()
    triples, g, _meta = _tri_world()
    a = int(triples[triples[:, 1] == 2][0, 0])
    eng = TemplateCompiledEngine(g)
    # what stays on the device with a program is its start list: both
    # templates settled, as if by an earlier store, at 2^18 rows (1 MB);
    # the index-origin one expands at the class of its total, where a
    # program that no draw can change stays (ISSUE 33)
    qw = handq([(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2)], [-1, -2])
    CPUEngine(g).execute(qw)
    for pats, cap in (
            ([(a, 2, OUT, -1), (-1, 3, OUT, -2)], 1 << 10),
            ([(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2)],
             capacity_class(qw.result.nrows, floor=1))):
        spec = extract_template(handq(pats, [-1, -2]))[0]
        eng._good_caps[(spec, eng._version())] = (1 << 18, cap)
    q1 = handq([(a, 2, OUT, -1), (-1, 3, OUT, -2)], [-1, -2])
    assert eng.try_execute(q1)
    assert eng.program_count() == 1
    t1 = read_device_input("resident_bytes").get("template", 0)
    q2 = handq([(2, PREDICATE_ID, IN, -1), (-1, 2, OUT, -2)], [-1, -2])
    assert eng.try_execute(q2)
    assert eng.program_count() == 1  # the first program was evicted
    cached = sum(p.nbytes for p in eng._programs.values())
    t2 = read_device_input("resident_bytes").get("template", 0)
    assert t2 == cached  # the victim's bytes were charged back (evict)
    assert t2 < t1 + cached  # ... not accumulated alongside the fill
    # the evicted template re-executes correctly (cache miss, restage)
    q3 = handq([(a, 2, OUT, -1), (-1, 3, OUT, -2)], [-1, -2])
    qh = handq([(a, 2, OUT, -1), (-1, 3, OUT, -2)], [-1, -2])
    CPUEngine(g).execute(qh)
    assert eng.try_execute(q3)
    assert_identical(qh, q3)


def test_program_key_includes_route_knobs():
    """A runtime knob flip can never serve a program chosen under
    different routing rules: the knob set joins the cache key."""
    from wukong_tpu.engine.template_compile import _program_key

    Global.template_device = "auto"
    k1 = _program_key(("t",), 0, (1024,))
    Global.template_device = "device"
    k2 = _program_key(("t",), 0, (1024,))
    assert k1 != k2
    assert _program_key(("t",), 1, (1024,)) != k2  # version joins too


# ---------------------------------------------------------------------------
# consumers: stream-epoch + view-maintenance device frontier
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lubm_world():
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm

    triples, _lay = generate_lubm(1, seed=42)
    ss = VirtualLubmStrings(1, seed=42)
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(triples))
    return triples, ss, perm


PREFIX = """
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
"""
Q_CHAIN = PREFIX + """SELECT ?X ?Y ?Z WHERE {
    ?X ub:memberOf ?Y .
    ?Y ub:subOrganizationOf ?Z .
}"""
Q_ONEHOP = PREFIX + "SELECT ?X ?Y WHERE { ?X ub:advisor ?Y . }"


def _stream_result(triples, ss, perm, text, knob):
    from wukong_tpu.stream import ReplaySource, StreamContext

    Global.template_device = knob
    base = triples[perm[:len(triples) // 2]]
    live = triples[perm[len(triples) // 2:]]
    ctx = StreamContext([build_partition(base, 0, 1)], ss)
    qid = ctx.register(text)
    ctx.feed_source(ReplaySource(live, batch_size=4096))
    return ctx.result_set(qid)


@pytest.mark.stream
def test_stream_epoch_device_frontier_matches_host_oracle(lubm_world,
                                                          monkeypatch):
    """The fully device-evaluated stream frontier (``template_device
    device`` forces the fused seed extraction for every epoch) converges
    to the byte-identical standing result of the host path."""
    from wukong_tpu.obs.device import get_device_obs, read_device_input

    monkeypatch.setattr(Global, "enable_device_obs", True)
    get_device_obs().reset()
    triples, ss, perm = lubm_world
    host = _stream_result(triples, ss, perm, Q_CHAIN, "host")
    dev = _stream_result(triples, ss, perm, Q_CHAIN, "device")
    assert host.shape == dev.shape
    assert np.array_equal(host, dev)
    counts = read_device_input("dispatches", "stream.seed_extract")
    assert int(counts["count"]) > 0  # the device frontier actually ran


@pytest.mark.stream
def test_device_seed_extract_gating_and_parity(lubm_world):
    """The fused extraction is knob-gated (host -> None, auto under the
    amortization floor -> None) and byte-identical to ``match_delta``
    per term when it runs."""
    from wukong_tpu.stream.continuous import (device_seed_extract,
                                              match_delta)

    triples, ss, _perm = lubm_world
    from wukong_tpu.sparql.parser import Parser

    q = Parser(ss).parse(Q_CHAIN)
    pats = list(q.pattern_group.patterns)
    batch = triples[:4096]

    Global.template_device = "host"
    assert device_seed_extract(pats, batch) is None
    Global.template_device = "auto"
    Global.join_device_min_candidates = 1 << 60
    assert device_seed_extract(pats, batch) is None

    Global.template_device = "device"
    seeds = device_seed_extract(pats, batch)
    assert seeds is not None and len(seeds) == len(pats)
    for (vars_d, seed_d), pat in zip(seeds, pats):
        vars_h, seed_h = match_delta(pat, batch)
        assert vars_d == vars_h
        assert np.array_equal(seed_d, seed_h)


@pytest.mark.serve
def test_view_maintenance_device_union_matches_host(lubm_world,
                                                    monkeypatch):
    """Consumer 3: an epoch's per-view semi-naive term unions batch into
    one fused device frontier — survivor decisions and the standing
    seen-set stay byte-identical to the host path."""
    from wukong_tpu.serve.views import ViewRegistry

    monkeypatch.setattr(Global, "enable_views", True)
    monkeypatch.setattr(Global, "enable_device_obs", True)
    triples, ss, perm = lubm_world
    base = triples[perm[:len(triples) // 2]]
    batches = [triples[perm[len(triples) // 2:len(triples) // 2 + 2048]],
               triples[perm[len(triples) // 2 + 2048:
                            len(triples) // 2 + 4096]]]

    def drive(knob):
        Global.template_device = knob
        g = build_partition(base, 0, 1)
        vr = ViewRegistry()
        vr.attach(g, ss)
        assert vr.promote(("m-chain",), Q_CHAIN)
        assert vr.promote(("m-onehop",), Q_ONEHOP)
        out = []
        for i, batch in enumerate(batches):
            out.append(vr.on_mutation(batch, version=i + 1))
        seen = {m: sorted(vr._ce.queries[v.qid].seen)
                for m, v in vr._views.items()}
        return out, seen

    host_surv, host_seen = drive("host")
    dev_surv, dev_seen = drive("device")
    assert host_surv == dev_surv
    assert host_seen == dev_seen


# ---------------------------------------------------------------------------
# consumer: device-side slice settlement in the distributed join
# ---------------------------------------------------------------------------

def test_dist_settle_device_concat_matches_host(monkeypatch):
    """Consumer 1: the gather thread's slice settlement concatenates
    padded per-slice tables on device — byte-identical (row order
    included) to ``np.concatenate`` over the same slices."""
    from wukong_tpu.join.dist import DistributedWCOJExecutor

    rng = np.random.default_rng(3)
    slices = [rng.integers(0, 1 << 20, size=(n, 3)).astype(np.int64)
              for n in (17, 1, 63, 9)]
    host = np.concatenate(slices, axis=0)

    dj = DistributedWCOJExecutor.__new__(DistributedWCOJExecutor)
    dj._settle_broken = False
    monkeypatch.setattr(Global, "template_device", "device")
    out = dj._settle(list(slices), 3)
    assert out.dtype == np.int64
    assert np.array_equal(out, host)
    monkeypatch.setattr(Global, "template_device", "host")
    out_h = dj._settle(list(slices), 3)
    assert np.array_equal(out_h, host)


# ---------------------------------------------------------------------------
# the small end of the rule: light templates over the device walk (ISSUE 29)
# ---------------------------------------------------------------------------

UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
Q4 = PREFIX + """SELECT ?X ?Y1 ?Y2 ?Y3 WHERE {
    ?X ub:worksFor %s . ?X rdf:type ub:FullProfessor .
    ?X ub:name ?Y1 . ?X ub:emailAddress ?Y2 . ?X ub:telephone ?Y3 . }"""
Q5 = PREFIX + """SELECT ?X WHERE {
    ?X ub:subOrganizationOf %s . ?X rdf:type ub:ResearchGroup . }"""
Q6 = PREFIX + """SELECT ?X ?Y WHERE {
    ?Y ub:subOrganizationOf %s . ?Y rdf:type ub:Department .
    ?X ub:worksFor ?Y . ?X rdf:type ub:FullProfessor . }"""
LIGHTS = {"q4": (Q4, "Department"), "q5": (Q5, "Department"),
          "q6": (Q6, "University")}


def _device_walk_proxy(n_univ: int):
    """A proxy built as the console builds it: the walk is the device
    engine (here on the CPU backend), one jitted call a step."""
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
    from wukong_tpu.planner.optimizer import make_planner

    triples, _lay = generate_lubm(n_univ, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(n_univ, seed=42)
    proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    proxy.planner = make_planner(triples, None)
    proxy.tpu.stats = proxy.planner.stats
    return proxy


@pytest.fixture(scope="module")
def light_proxy():
    return _device_walk_proxy(1)


@pytest.fixture(scope="module")
def light_proxy20():
    return _device_walk_proxy(20)


def _instances(proxy, cls: str) -> list[str]:
    tid = proxy.str_server.str2id(f"<{UB}{cls}>")
    return [proxy.str_server.id2str(int(v))
            for v in proxy.g.get_index(tid, IN)]


def _fresh(proxy) -> None:
    """Module-scoped proxies start each test with no program and no memo."""
    proxy.template_engine().clear()
    proxy._plan_cache.clear()


def test_small_classes_route_device_and_keep_their_program(light_proxy):
    """Every class of q5's program is under ``template_min_rows``: it takes
    its program from the first draw, and a reply of some ten rows does not
    latch it to the walk."""
    proxy = light_proxy
    _fresh(proxy)
    depts = _instances(proxy, "Department")
    for k, dept in enumerate(depts[:4]):
        q = proxy.serve_query(Q5 % dept, blind=False)
        assert q.template_route == "device" and q._template_compiled, k
        assert 0 < q.result.nrows < 100
        assert max(q._template_caps) < Global.template_min_rows
        assert route_why(q._template_est_rows, q._template_plan_caps) \
            == "small_classes"
        assert demotion_report() == {}
    assert proxy.template_engine().program_count() == 1


def test_large_class_small_reply_is_latched_as_before(light_proxy):
    """The middle of the rule: q4's estimated peak (some 36 rows) reaches a
    ``template_min_rows`` of 16, so it takes its program by the estimate;
    the program's classes (1,024 rows) are over it and the reply under it:
    demoted to the walk, and its program goes."""
    proxy = light_proxy
    _fresh(proxy)
    Global.template_min_rows = 16
    depts = _instances(proxy, "Department")
    q = proxy.serve_query(Q4 % depts[0], blind=False)
    assert q.template_route == "device" and q._template_compiled
    assert route_why(q._template_est_rows, q._template_plan_caps) \
        == "estimate"
    assert q.result.nrows < 16 <= min(q._template_caps)
    assert list(demotion_report().values()) == ["small_measured"]
    assert proxy.template_engine().program_count() == 0
    q2 = proxy.serve_query(Q4 % depts[1], blind=False)
    assert q2.template_route == "latched_host"
    assert not getattr(q2, "_template_compiled", False)


def test_estimate_over_threshold_routes_device_as_before(light_proxy):
    """The large end: an index-origin chain estimated over
    ``template_min_rows`` takes its program and, with a reply over it too,
    keeps it."""
    proxy = light_proxy
    _fresh(proxy)
    q = proxy.serve_query(Q_CHAIN, blind=False)
    assert q.template_route == "device" and q._template_compiled
    assert q._template_est_rows >= Global.template_min_rows
    assert route_why(q._template_est_rows, q._template_plan_caps) \
        == "estimate"
    assert q.result.nrows >= Global.template_min_rows
    assert demotion_report() == {}
    assert proxy.serve_query(Q_CHAIN, blind=False).template_route == "device"


def test_small_program_grown_past_the_threshold_is_judged_large(
        light_proxy, monkeypatch):
    """A small program whose retry grows a class to ``template_min_rows`` or
    over is from then on a large one: the reply that grew it is judged
    against the grown classes (and, small, latches the template), and the
    next plan is routed by them."""
    proxy = light_proxy
    _fresh(proxy)
    monkeypatch.setattr(Global, "table_capacity_min", 8)
    Global.template_min_rows = 512
    text = Q6 % _instances(proxy, "University")[0]
    eng = proxy.template_engine()
    q0 = proxy._prepare(text, None, False, None, "default")
    start = q0._template_plan_caps[0]
    assert start < 512  # a university's departments
    # settled, as if by lighter draws, at an expansion class of 8 rows
    with eng._lock:
        eng._good_caps[(q0._tsig, eng._version())] = (start, 8)
    q = proxy.serve_query(text, blind=False)
    assert q.template_route == "device" and q._template_compiled
    assert q._template_plan_caps == (start, 8)
    assert route_why(q._template_est_rows, q._template_plan_caps) \
        == "small_classes"
    assert q._template_attempts >= 2  # overflowed, regrown, run again
    assert max(q._template_caps) >= 512 > q.result.nrows > 0
    assert eng._good_caps[(q._tsig, eng._version())] == q._template_caps
    assert list(demotion_report().values()) == ["small_measured"]
    assert proxy.serve_query(text, blind=False).template_route \
        == "latched_host"
    # re-armed, the template is routed by the classes it grew to
    reset_demotions()
    q3 = proxy._prepare(text, None, False, None, "default")
    assert q3._template_plan_caps == q._template_caps
    assert route_why(q3._template_est_rows, q3._template_plan_caps) \
        != "small_classes"


@pytest.mark.parametrize("name", sorted(LIGHTS))
def test_light_program_equals_the_walk_over_drawn_constants(light_proxy20,
                                                            name):
    """q4, q5 and q6 through their programs equal the host walk row for
    row over 20 drawn constants each, and one program, traced once, serves
    every draw: constants are bound query by query."""
    proxy = light_proxy20
    text, cls = LIGHTS[name]
    pool = _instances(proxy, cls)
    rng = np.random.default_rng(29)
    draws = [pool[i] for i in rng.choice(len(pool), size=20, replace=False)]
    eng = proxy.template_engine()
    fns, rows = set(), 0
    for k, iri in enumerate(draws):
        qc = proxy.serve_query(text % iri, blind=False)
        assert qc.template_route == "device" and qc._template_compiled, k
        assert qc._template_attempts == 1, k
        qh = proxy.serve_query(text % iri, blind=False, device="cpu")
        assert not getattr(qh, "_template_compiled", False)
        assert_identical(qh, qc)
        rows += qc.result.nrows
        with eng._lock:
            fns |= {id(p.fn) for key, p in eng._programs.items()
                    if key[0] == qc._tsig}
            (prog,) = [p for key, p in eng._programs.items()
                       if key[0] == qc._tsig]
        assert len(fns) == 1 and prog.fn._cache_size() == 1, k
    assert rows > 0
    assert demotion_report() == {}


def test_member_lists_of_any_length_run_one_program(light_proxy):
    """A constant's member list is padded to the class of the longest list
    its segment holds, as a start list is: students taking 1 to 4 courses
    (classes of 1, 2 and 4 rows, were each padded alone) run one trace."""
    proxy = light_proxy
    g, ss = proxy.g, proxy.str_server
    takes = ss.str2id(f"<{UB}takesCourse>")
    teacher = ss.str2id(f"<{UB}teacherOf>")
    course = ss.str2id(f"<{UB}Course>")
    seg = g.segments[(takes, OUT)]
    by_len: dict[int, int] = {}
    for vid in g.get_index(ss.str2id(f"<{UB}UndergraduateStudent>"), IN):
        by_len.setdefault(len(g.get_triples(int(vid), takes, OUT)), int(vid))
    assert seg.max_degree == 4 and sorted(by_len) == [1, 2, 3, 4]
    old = Global.table_capacity_min
    Global.table_capacity_min = 1  # a class to every power of two
    eng = TemplateCompiledEngine(g)
    try:
        for n, student in sorted(by_len.items()):
            # courses (by the type index) this student takes, and who
            # teaches each
            pats = [(course, TYPE_ID, IN, -1), (student, takes, OUT, -1),
                    (-1, teacher, IN, -2)]

            def build():
                return handq(pats, [-1, -2])

            spec = extract_template(build())[0]
            assert [op[0] for op in spec] == ["index", "filter_member",
                                              "expand"]
            qc = build()
            qc._tsig = "courses-of-a-student"  # as the proxy: no constants
            assert eng.try_execute(qc)
            qh = build()
            CPUEngine(g).execute(qh)
            assert qh.result.nrows == n
            assert_identical(qh, qc)
    finally:
        Global.table_capacity_min = old
    (prog,) = eng._programs.values()
    assert prog.fn._cache_size() == 1


# ---------------------------------------------------------------------------
# capacity classes in eighths of an octave, never above the exact bound
# (ISSUE 31)
# ---------------------------------------------------------------------------

class _Degrees:
    """What ``_initial_caps`` asks of a store: each segment's longest edge
    list, and the version its memo is kept under."""

    version = 0

    def __init__(self, by_pid: dict):
        self.by_pid = by_pid

    def max_degree(self, pid, _d) -> int:
        return self.by_pid[pid]


def _sized_engine(n0: int, degrees: tuple, monkeypatch):
    """(engine, spec) of a chain that is nothing but sizes: an index start
    of ``n0`` rows and one expansion a segment, the k-th over a segment
    whose longest edge list is ``degrees[k]``."""
    _triples, g, _meta = _tri_world()
    eng = TemplateCompiledEngine(g)
    eng.g = _Degrees({10 + k: d for k, d in enumerate(degrees)})
    monkeypatch.setattr(eng, "_start_len", lambda spec: n0)
    return eng, (("index", 1, IN),) + tuple(
        ("expand", 10 + k, OUT, k) for k in range(len(degrees)))


# name -> (start rows, each expansion's longest edge list, the planner's
# estimate a step, the classes before, the classes now): PERF.md section 6
INITIAL_CAPS = {
    "watdiv_c3": (
        400_120, (1, 1, 1, 1, 8, 4000),
        (400_120, 400_120, 200_224, 120_152, 84_135, 94_467, 4_227_401),
        (1 << 19, 1 << 20, 1 << 19, 1 << 18, 1 << 18, 1 << 18, 1 << 24),
        (425_984, 425_984, 425_984, 245_760, 180_224, 196_608, 9_437_184)),
    "lubm640_q7": (
        110_020, (4, 25), (110_020, 330_294, 1_055_938),
        (1 << 17, 1 << 20, 1 << 22), (114_688, 458_752, 2_359_296)),
    "lubm640_q2": (
        698_900, (1,), (698_900, 698_900),
        (1 << 20, 1 << 21), (720_896, 720_896)),
    "lubm160_q7": (
        27_596, (4, 22), (27_596, 82_741, 264_310),
        (1 << 15, 1 << 18, 1 << 20), (28_672, 114_688, 589_824)),
    "lubm160_q2": (
        174_455, (1,), (174_455, 174_455),
        (1 << 18, 1 << 19), (180_224, 180_224)),
    # light plans keep their classes: q6 (24 departments, 45 staff at
    # most), q4 (a department's 36 professors through four columns)
    "lubm640_q6": (24, (45,), (24, 540), (32, 2048), (32, 2048)),
    "lubm640_q4": (36, (1, 1, 1), (36, 36, 36, 36), (64,) * 4, (64,) * 4),
}


@pytest.mark.parametrize("name", sorted(INITIAL_CAPS))
def test_initial_caps_at_the_shapes_of_the_cells(name, monkeypatch):
    """``_initial_caps`` from stubbed estimates at the shapes of the
    benchmark's programs: twice the estimate, rounded up an eighth of an
    octave, and never above the class of the exact bound. C3's last
    expansion (estimated 4,227,401 rows, twice that 0.8 % past 2^23) runs
    at 9 x 2^20 where it ran at 2^24; q2's one expansion over a segment of
    out-degree 1 runs at the class of its 698,900 starts."""
    n0, degrees, est_steps, before, now = INITIAL_CAPS[name]
    eng, spec = _sized_engine(n0, degrees, monkeypatch)
    caps = eng._initial_caps(name, spec, max(est_steps), list(est_steps))
    assert caps == now
    bound = eng._fill_bound(spec, n0)
    for c, b, c0 in zip(caps, bound, before):
        assert c <= capacity_class(b, floor=1) and c <= c0
        assert c == capacity_class(c, floor=1)


def test_initial_caps_without_the_planners_walk_round_the_same_way(
        monkeypatch):
    """No per-step estimates: four times the step before, at least the
    estimated peak, each rounded up an eighth of an octave and held under
    the exact bound."""
    eng, spec = _sized_engine(9000, (3, 50), monkeypatch)
    caps = eng._initial_caps("t", spec, 70_000, None)
    # 9,000; the bound 9,000 x 3 under the peak's 73,728; 4 x 73,728
    assert caps == (9216, 28_672, 294_912)


Q_TAKES = PREFIX + """SELECT ?X ?Y ?Z WHERE {
    ?X ub:takesCourse ?Y . ?Z ub:teacherOf ?Y . }"""


def test_a_program_at_classes_that_are_no_power_of_two_equals_the_walk(
        light_proxy):
    """Index-origin chains at LUBM-1 whose classes are 15 x 1,024 and 10 x
    4,096 rows: served through their programs they equal the CPU engine
    row for row, in one attempt."""
    proxy = light_proxy
    _fresh(proxy)
    texts = [Q_CHAIN, Q_TAKES]
    odd = 0
    for text in texts:
        qc = proxy.serve_query(text, blind=False)
        assert qc.template_route == "device" and qc._template_compiled
        assert qc._template_attempts == 1
        caps = qc._template_caps
        assert all(c == capacity_class(c, floor=1) for c in caps)
        odd += sum(1 for c in caps if c & (c - 1))
        assert qc.result.nrows <= caps[-1] < 2.25 * qc.result.nrows + 1024
        qh = proxy.serve_query(text, blind=False, device="cpu")
        assert not getattr(qh, "_template_compiled", False)
        assert_identical(qh, qc)
    assert odd >= 2
    assert demotion_report() == {}


def test_an_overflow_regrows_to_twice_the_class_at_least(light_proxy,
                                                         monkeypatch):
    """A class that is no power of two, set too small on purpose (9 x
    1,024 rows for a reply of some 20,000): the program overflows, the
    class regrows to a class of at least twice the rows and at least the
    measured total, the reply equals the CPU engine's, and what is
    remembered comes from the run that fitted: the chain binds nothing
    query by query, so each class settles on the total THAT run measured
    (ISSUE 33), the overflowed run's totals are not used."""
    proxy = light_proxy
    _fresh(proxy)
    eng = proxy.template_engine()
    _builds, runs = _spy(eng, monkeypatch)
    q0 = proxy._prepare(Q_TAKES, None, False, None, "default")
    first = q0._template_plan_caps
    small = 9 * 1024
    with eng._lock:
        eng._good_caps[(q0._tsig, eng._version())] = first[:-1] + (small,)
    qc = proxy.serve_query(Q_TAKES, blind=False)
    assert qc._template_compiled and qc._template_attempts == 2
    assert qc.result.nrows > small
    grown = qc._template_caps
    assert grown[:-1] == first[:-1]
    assert grown[-1] >= max(2 * small, qc.result.nrows)
    assert grown[-1] == capacity_class(grown[-1], floor=1)
    (_c0, _t0, ovf0), (c1, totals, ovf1) = runs
    assert any(ovf0) and not any(ovf1) and c1 == grown
    settled = grown[:1] + tuple(
        min(c, t) for c, t in zip(grown[1:], _classes_of(totals)))
    assert eng._good_caps[(qc._tsig, eng._version())] == settled
    assert settled[-1] >= qc.result.nrows > small
    # the overflowed program went; the one that fitted stays until the
    # next request has built the settled one
    assert [k[2] for k in eng._programs] == [grown]
    qh = proxy.serve_query(Q_TAKES, blind=False, device="cpu")
    assert_identical(qh, qc)
    # remembered: the next reply runs once, at the settled classes
    q2 = proxy.serve_query(Q_TAKES, blind=False)
    assert q2._template_attempts == 1 and q2._template_caps == settled
    assert [k[2] for k in eng._programs] == [settled]
    assert_identical(qh, q2)


@pytest.mark.parametrize("caps,total,want", [
    ((9216, 9216), 15_000, (9216, 18_432)),       # twice the class
    ((9216, 9216), 40_000, (9216, 40_960)),       # the total's class
    ((9216, 9216, 10_240), 15_000, (9216, 18_432, 18_432)),  # steps after
    ((1024, 18 << 20), 20 << 20, (1024, 32 << 20)),  # up to the cap
    ((1024, 1024), 0, (1024, 4096)),              # a total that wrapped
])
def test_grow_caps_keeps_its_room(caps, total, want):
    totals = np.zeros(len(caps) - 1, dtype=np.int64)
    ovfs = np.zeros(len(caps) - 1, dtype=bool)
    totals[0], ovfs[0] = total, True  # the first expansion overflowed
    assert TemplateCompiledEngine._grow_caps(caps, totals, ovfs) == want


def test_grow_caps_past_the_cap_is_an_overflow():
    with pytest.raises(TemplateOverflow):
        TemplateCompiledEngine._grow_caps(
            (1024, 1 << 25), np.asarray([0]), np.asarray([True]))


# ---------------------------------------------------------------------------
# a program that no draw can change settles its classes on the totals its
# first sound run measured (ISSUE 33)
# ---------------------------------------------------------------------------

def _spy(eng, monkeypatch):
    """(builds, runs): every program ``eng`` stages from here on (its
    classes, blind or not) and every dispatch's (classes, totals, overflow
    flags), in order."""
    builds, runs = [], []
    stage, dispatch = eng._stage, eng._dispatch

    def staged(tsig, spec, caps, v2c, proj, width, blind=False):
        builds.append((tuple(caps), bool(blind)))
        return stage(tsig, spec, caps, v2c, proj, width, blind)

    def dispatched(prog, args, q, tr):
        res = dispatch(prog, args, q, tr)
        runs.append((tuple(prog.caps), [int(t) for t in res[3]],
                     [bool(o) for o in res[4]]))
        return res

    monkeypatch.setattr(eng, "_stage", staged)
    monkeypatch.setattr(eng, "_dispatch", dispatched)
    return builds, runs


def _settles() -> float:
    from wukong_tpu.obs.metrics import get_registry

    return get_registry().counter("wukong_template_settles_total").value()


def _settle_events(q) -> list[dict]:
    return [a for sp in q.trace.spans for _t, n, a in sp.events
            if n == "capacity.settle"]


def _classes_of(totals) -> tuple:
    return tuple(capacity_class(t, 1, int(Global.table_capacity_max))
                 for t in totals)


Q7 = PREFIX + """SELECT ?X ?Y ?Z WHERE {
    ?Y rdf:type ub:FullProfessor . ?Y ub:teacherOf ?Z .
    ?Z rdf:type ub:Course . ?X ub:advisor ?Y .
    ?X rdf:type ub:UndergraduateStudent . ?X ub:takesCourse ?Z . }"""


@pytest.mark.parametrize("text", [Q_CHAIN, Q_TAKES, Q7],
                         ids=["chain", "takes", "q7"])
@pytest.mark.parametrize("blind", [False, True],
                         ids=["materialising", "blind"])
def test_an_unbound_template_settles_on_its_measured_totals(
        light_proxy, monkeypatch, text, blind):
    """An index-origin chain binds nothing query by query: its first sound
    run leaves remembered, step by step, the class of the total the run
    measured (never higher than it ran at, never above the class of the
    exact bound, the start class as it is). The second reply builds exactly
    one more program, the third none, one program stays resident, and all
    three replies are the CPU walk's, byte for byte; traced, the first
    reply holds one ``capacity.settle`` event a step that came down, and
    ``wukong_template_settles_total`` counts the same. LUBM's q7 filters by
    three types: a type is the template's own constant (its signature
    keeps it), staged with the program, so q7 is such a chain too (its
    estimate at LUBM-1 is under ``template_min_rows``: sent by the knob)."""
    proxy = light_proxy
    _fresh(proxy)
    monkeypatch.setattr(Global, "enable_tracing", True)
    if text is Q7:
        monkeypatch.setattr(Global, "template_device", "device")
    eng = proxy.template_engine()
    builds, runs = _spy(eng, monkeypatch)
    qh = proxy.serve_query(text, blind=blind, device="cpu")
    assert not getattr(qh, "_template_compiled", False)
    n_settled = _settles()
    replies = [proxy.serve_query(text, blind=blind)]
    # the guess's program stays until the settled one takes its place
    assert [k[2] for k in eng._programs] == [replies[0]._template_caps]
    replies += [proxy.serve_query(text, blind=blind) for _ in range(2)]
    for k, qc in enumerate(replies):
        assert qc.template_route == "device" and qc._template_compiled, k
        assert qc._template_attempts == 1, k
        if blind:
            assert qc.result.nrows == qh.result.nrows
        else:
            assert_identical(qh, qc)
    guess = replies[0]._template_caps
    assert builds[:1] == [(guess, blind)]
    (_c, totals, ovfs), = runs[:1]
    assert not any(ovfs)
    settled = guess[:1] + tuple(
        min(c, t) for c, t in zip(guess[1:], _classes_of(totals)))
    assert settled != guess  # twice the estimate is a class over the total
    spec = extract_template(
        proxy._prepare(text, None, blind, None, "default"))[0]
    if text is Q7:
        assert [op[0] for op in spec].count("filter_pair_const") >= 2
        (prog,) = eng._programs.values()
        assert prog.fixed
    bound = eng._fill_bound(spec, eng._start_len(spec))
    for c, g0, b in zip(settled, guess, bound):
        assert 1 <= c <= g0 and c <= capacity_class(b, floor=1)
    key = (replies[0]._tsig, eng._version())
    assert eng._good_caps[key] == settled
    assert [q._template_caps for q in replies] == [guess, settled, settled]
    # one program more, then none; the guess's went when it was built
    assert builds == [(guess, blind), (settled, blind)]
    assert [k[2] for k in eng._programs if k[0] == key[0]] == [settled]
    assert eng.program_count() == 1
    # a program at settled classes measures the same totals: idempotent
    assert [r[1] for r in runs] == [totals] * 3
    came_down = [(k, c0, c1) for k, (c0, c1) in
                 enumerate(zip(guess, settled)) if c0 != c1]
    assert [(e["step"], e["cap_from"], e["cap_to"])
            for e in _settle_events(replies[0])] == came_down
    assert all(e["site"] == "template.plan"
               for e in _settle_events(replies[0]))
    assert not _settle_events(replies[1]) and not _settle_events(replies[2])
    assert _settles() - n_settled == len(came_down)
    # the route rule reads the settled classes
    q4 = proxy._prepare(text, None, blind, None, "default")
    assert q4._template_plan_caps == settled


def test_the_blind_and_the_materialising_program_settle_alike(
        light_proxy, monkeypatch):
    """Both run the same expansions over the same operands: whichever runs
    first settles the template, and the other is built at the settled
    classes from its first request; the guess leaves no program behind."""
    proxy = light_proxy
    _fresh(proxy)
    eng = proxy.template_engine()
    builds, _runs = _spy(eng, monkeypatch)
    first = proxy.serve_query(Q_CHAIN, blind=True)
    settled = eng._good_caps[(first._tsig, eng._version())]
    assert settled != first._template_caps
    q = proxy.serve_query(Q_CHAIN, blind=False)
    assert q._template_caps == settled and q.result.nrows == first.result.nrows
    assert proxy.serve_query(Q_CHAIN, blind=True)._template_caps == settled
    assert builds == [(first._template_caps, True), (settled, False),
                      (settled, True)]
    assert sorted(k[2:4] for k in eng._programs) == [(settled, False),
                                                     (settled, True)]


def _bound_draws(proxy, kind: str):
    """(patterns of a draw, the draws) of a hand-ordered LUBM-1 template
    whose program binds one operand query by query, with totals that
    differ between the draws."""
    g, ss = proxy.g, proxy.str_server

    def pid(name):
        return ss.str2id(f"<{UB}{name}>")

    takes, teacher, works = pid("takesCourse"), pid("teacherOf"), \
        pid("worksFor")
    by_len: dict[int, int] = {}
    for vid in g.get_index(pid("UndergraduateStudent"), IN):
        by_len.setdefault(len(g.get_triples(int(vid), takes, OUT)), int(vid))
    students = [by_len[n] for n in sorted(by_len)]
    depts = [int(v) for v in g.get_index(pid("Department"), IN)][:4]
    if kind == "const_list":  # a student's courses, and who teaches each
        return (lambda c: [(c, takes, OUT, -1), (-1, teacher, IN, -2)],
                students)
    if kind == "filter_member":  # courses this student takes, by the index
        return (lambda c: [(pid("Course"), TYPE_ID, IN, -1),
                           (c, takes, OUT, -1), (-1, teacher, IN, -2)],
                students)
    # full professors of one department, and what each teaches
    return (lambda c: [(pid("FullProfessor"), TYPE_ID, IN, -1),
                       (-1, works, OUT, c), (-1, teacher, OUT, -2)],
            depts)


@pytest.mark.parametrize("kind", ["const_list", "filter_pair_const",
                                  "filter_member"])
def test_a_bound_template_keeps_its_classes_over_the_draws(
        light_proxy, monkeypatch, kind):
    """A constant's start list, a constant object or a constant's member
    list is bound query by query: the totals are the draw's, not the
    store's, so the template runs every draw at the classes of its first
    attempt, in one program, and settles nothing."""
    proxy = light_proxy
    pats_of, draws = _bound_draws(proxy, kind)
    eng = TemplateCompiledEngine(proxy.g)
    builds, runs = _spy(eng, monkeypatch)
    n_settled = _settles()
    spec = extract_template(handq(pats_of(draws[0]), [-1, -2]))[0]
    assert kind in [op[0] for op in spec]
    first = eng._initial_caps(f"a-{kind}-template", spec, None, None)
    for k, c in enumerate(draws):
        qc = handq(pats_of(c), [-1, -2])
        qc._tsig = f"a-{kind}-template"  # as the proxy: no vertex constant
        assert eng.try_execute(qc)
        qh = handq(pats_of(c), [-1, -2])
        CPUEngine(proxy.g).execute(qh)
        assert_identical(qh, qc)
        assert qc._template_caps == first and qc._template_attempts == 1, k
        assert eng._good_caps[(qc._tsig, eng._version())] == first, k
    assert len({tuple(r[1]) for r in runs}) > 1  # the draws' totals differ
    assert any(_classes_of(r[1]) != first[1:] for r in runs)
    assert builds == [(first, False)] and eng.program_count() == 1
    (prog,) = eng._programs.values()
    assert not prog.fixed and prog.fn._cache_size() == 1
    assert _settles() == n_settled


def test_a_store_version_bump_forgets_the_settled_classes(monkeypatch):
    """Settled classes are facts of the store at one version: after a
    dynamic insert the template starts again from its guess, runs sound on
    the mutated store, and settles on what that run measures."""
    from wukong_tpu.store.dynamic import insert_triples
    from wukong_tpu.types import NORMAL_ID_START

    proxy, text = _mk_tri_proxy()
    monkeypatch.setattr(Global, "join_strategy", "walk")
    monkeypatch.setattr(Global, "template_device", "device")
    eng = proxy.template_engine()
    _builds, runs = _spy(eng, monkeypatch)
    q0 = proxy.run_single_query(text, blind=False)
    v0 = eng._version()
    settled0 = eng._good_caps[(q0._tsig, v0)]
    assert settled0 != q0._template_caps
    assert proxy.run_single_query(text, blind=False)._template_caps \
        == settled0
    a, b, c = (NORMAL_ID_START + 7001, NORMAL_ID_START + 7002,
               NORMAL_ID_START + 7003)
    insert_triples(proxy.g, np.asarray(
        [[a, 2, b], [b, 3, c], [a, 4, c]], dtype=np.int64))
    v1 = eng._version()
    assert v1 != v0 and (q0._tsig, v1) not in eng._good_caps
    q1 = proxy.run_single_query(text, blind=False)
    assert q1._template_compiled
    assert q1._template_attempts == q0._template_attempts
    assert q1.result.nrows == q0.result.nrows + 1
    # from the guess again, not from the classes of the store before
    assert q1._template_caps == q0._template_caps
    assert runs[-1][1] != runs[0][1]  # the totals are the new store's
    assert eng._good_caps[(q1._tsig, v1)] == q1._template_caps[:1] + tuple(
        min(c0, t) for c0, t in zip(q1._template_caps[1:],
                                    _classes_of(runs[-1][1])))
    assert all(k[1] == v1 for k in eng._programs)
