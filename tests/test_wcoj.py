"""Tensor-join (WCOJ) execution: oracle identity, routing, chaos, gate.

The acceptance bar (ISSUE 9): the WCOJ path returns byte-identical result
rows to the walk AND to the independent brute-force BGP oracle on triangle,
diamond, and 4-clique worlds; acyclic LUBM reference shapes route ``walk``
under ``join_strategy auto``; and a ``join.materialize`` fault degrades the
query to the walk — never to an error.

ISSUE 15 adds the device plane: the XLA level route is byte-identical to
the host kernels (including padded/bucketed edge cases through the jitted
kernels), any device failure degrades to host, the route chooser is
memoized + feedback-demotable, and the DISTRIBUTED generic join fans a
cyclic query across a >= 4-shard store on the heavy lane with
byte-identical gathered rows, a per-slice ``join.slice`` chaos fallback,
and the whole drill lockdep-checked.
"""

import sys

import numpy as np
import pytest

sys.path.insert(0, "tests")
from bgp_oracle import TripleIndex, eval_bgp  # noqa: E402

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.join import JOIN_STRATEGIES
from wukong_tpu.join.kernels import (
    intersect_many,
    intersect_sorted,
    member_sorted,
    pair_member,
)
from wukong_tpu.join.qgraph import analyze
from wukong_tpu.join.wcoj import WCOJExecutor, store_vertex_bound
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
from wukong_tpu.types import IN, OUT
from wukong_tpu.utils.errors import ErrorCode

pytestmark = pytest.mark.wcoj

WORLDS = {
    "triangle": lambda: generate_triangle(m=60, noise=3, seed=1),
    "diamond": lambda: generate_diamond(m=40, noise=2, seed=1),
    "clique4": lambda: generate_clique4(n=120, fan=6, ncliques=8, seed=1),
}


@pytest.fixture(scope="module", params=sorted(WORLDS))
def world(request):
    from wukong_tpu.store.gstore import build_partition

    triples, meta = WORLDS[request.param]()
    g = build_partition(triples, 0, 1)
    stats = Stats.generate(triples)
    return request.param, triples, g, stats, meta


@pytest.fixture(autouse=True)
def _clean_faults_and_knobs():
    faults.clear()
    yield
    faults.clear()
    Global.join_strategy = "auto"
    Global.wcoj_ratio = 4
    Global.wcoj_min_rows = 8192
    Global.join_device = "auto"
    Global.join_device_min_candidates = 65536
    Global.join_dist_parts = 4


def mkq(meta, blind=False) -> SPARQLQuery:
    q = SPARQLQuery()
    q.pattern_group.patterns = [Pattern(s, p, OUT, o)
                                for (s, p, o) in meta["patterns"]]
    q.result.nvars = len(meta["vars"])
    q.result.required_vars = list(meta["vars"])
    q.result.blind = blind
    return q


def rows_of(q) -> set:
    return set(map(tuple, q.result.table.tolist()))


# ---------------------------------------------------------------------------
# oracle identity: wcoj == walk == brute force
# ---------------------------------------------------------------------------

def test_wcoj_matches_walk_and_bruteforce_oracle(world):
    name, triples, g, stats, meta = world
    qw = mkq(meta)
    heuristic_plan(qw)
    CPUEngine(g).execute(qw)
    assert qw.result.status_code == ErrorCode.SUCCESS

    qj = mkq(meta)
    heuristic_plan(qj)
    WCOJExecutor(g, stats=stats).execute(qj)
    assert qj.result.status_code == ErrorCode.SUCCESS

    assert rows_of(qw) == rows_of(qj), name
    oracle = set(eval_bgp(TripleIndex(triples), meta["patterns"],
                          meta["vars"]))
    assert rows_of(qj) == oracle, name


def test_wcoj_blind_counts_match_walk(world):
    name, _triples, g, stats, meta = world
    qw = mkq(meta, blind=True)
    heuristic_plan(qw)
    CPUEngine(g).execute(qw)
    qj = mkq(meta, blind=True)
    heuristic_plan(qj)
    WCOJExecutor(g, stats=stats).execute(qj)
    assert qw.result.nrows == qj.result.nrows, name


def test_wcoj_cost_planned_order_identical(world):
    """The optimizer's plan order (not just the heuristic's) feeds the
    same analyzer and returns the same rows."""
    name, _triples, g, stats, meta = world
    pl = Planner(stats)
    qw, qj = mkq(meta), mkq(meta)
    pl.generate_plan(qw)
    pl.generate_plan(qj)
    CPUEngine(g).execute(qw)
    WCOJExecutor(g, stats=stats).execute(qj)
    assert qw.result.status_code == qj.result.status_code \
        == ErrorCode.SUCCESS
    assert rows_of(qw) == rows_of(qj), name


# ---------------------------------------------------------------------------
# query-graph analyzer
# ---------------------------------------------------------------------------

def test_qgraph_detects_cycles(world):
    name, _t, _g, stats, meta = world
    q = mkq(meta)
    heuristic_plan(q)
    qg = analyze(q.pattern_group.patterns, stats=stats)
    assert qg.supported and qg.cyclic
    # the elimination order covers every variable exactly once
    assert sorted(qg.order) == sorted(qg.vars)


def test_qgraph_acyclic_chain_and_star():
    chain = [Pattern(-1, 2, OUT, -2), Pattern(-2, 3, OUT, -3)]
    star = [Pattern(-1, 2, OUT, -2), Pattern(-1, 3, OUT, -3),
            Pattern(-1, 4, OUT, -4)]
    for pats in (chain, star):
        qg = analyze(pats)
        assert qg.supported and not qg.cyclic


def test_qgraph_parallel_edges_are_cyclic():
    qg = analyze([Pattern(-1, 2, OUT, -2), Pattern(-1, 3, OUT, -2)])
    assert qg.supported and qg.cyclic


def test_qgraph_unsupported_shapes_route_walk():
    # variable predicate / self-loop / meta expansion are not wcoj shapes
    assert not analyze([Pattern(-1, -9, OUT, -2)]).supported
    assert not analyze([Pattern(-1, 2, OUT, -1)]).supported
    assert not analyze([Pattern(-1, 1, OUT, -2)]).supported  # ?x type ?t
    assert not analyze([]).supported


def test_qgraph_engine_form_orientation():
    """IN-direction patterns are read triple-wise: (o, p, s)."""
    # planned form of (?b <-p- ?a): anchor ?b, direction IN
    qg = analyze([Pattern(-2, 2, IN, -1), Pattern(-1, 3, OUT, -2)])
    assert qg.supported and qg.cyclic  # both edges join the same pair


# ---------------------------------------------------------------------------
# sorted-array kernels
# ---------------------------------------------------------------------------

def test_kernels_member_and_intersect():
    a = np.array([1, 3, 5, 7, 9], dtype=np.int64)
    vals = np.array([0, 1, 2, 5, 9, 10], dtype=np.int64)
    assert member_sorted(a, vals).tolist() == \
        [False, True, False, True, True, False]
    b = np.array([3, 4, 5, 9, 11], dtype=np.int64)
    assert intersect_sorted(a, b).tolist() == [3, 5, 9]
    assert intersect_many([a, b, np.array([5, 9], dtype=np.int64)]) \
        .tolist() == [5, 9]
    assert member_sorted(np.empty(0, dtype=np.int64), vals).sum() == 0


def test_kernels_pair_member_matches_segment_probe():
    from wukong_tpu.store.segment import CSRSegment

    rng = np.random.default_rng(3)
    k = rng.integers(0, 50, 400)
    v = rng.integers(0, 50, 400)
    seg = CSRSegment.from_pairs(k, v)
    anchors = rng.integers(0, 60, 300)
    vals = rng.integers(0, 60, 300)
    got = pair_member(seg.keys, seg.offsets, seg.edges, anchors, vals)
    want = seg.contains_pair(anchors, vals)
    assert np.array_equal(got, want)


def test_kernels_jit_compile_parity():
    """The same kernel source traces under XLA and agrees with NumPy."""
    from wukong_tpu.join.kernels import jit_kernels
    from wukong_tpu.store.segment import CSRSegment

    member, pair = jit_kernels()
    rng = np.random.default_rng(5)
    s = np.unique(rng.integers(0, 100, 60))
    vals = rng.integers(0, 110, 80)
    assert np.array_equal(np.asarray(member(s, vals)),
                          member_sorted(s, vals))
    seg = CSRSegment.from_pairs(rng.integers(0, 30, 200),
                                rng.integers(0, 30, 200))
    anchors = rng.integers(0, 40, 100)
    pvals = rng.integers(0, 40, 100)
    assert np.array_equal(
        np.asarray(pair(seg.keys, seg.offsets, seg.edges, anchors, pvals)),
        pair_member(seg.keys, seg.offsets, seg.edges, anchors, pvals))


# ---------------------------------------------------------------------------
# strategy selection
# ---------------------------------------------------------------------------

def test_choose_strategy_knob_and_ratio(world):
    name, _t, _g, stats, meta = world
    pl = Planner(stats)
    q = mkq(meta)
    pl.generate_plan(q)
    pats = q.pattern_group.patterns
    Global.join_strategy = "walk"
    assert pl.choose_strategy(pats) == "walk"
    Global.join_strategy = "wcoj"
    assert pl.choose_strategy(pats) == "wcoj"
    Global.join_strategy = "auto"
    out = pl.choose_strategy(pats)
    assert out in JOIN_STRATEGIES
    # with the floors dropped, a cyclic blowup shape must route wcoj
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    assert pl.choose_strategy(pats) == "wcoj", name


def test_choose_strategy_acyclic_always_walks(world):
    _name, _t, _g, stats, meta = world
    pl = Planner(stats)
    pid = next(iter(meta["P"].values()))
    chain = [Pattern(-1, pid, OUT, -2), Pattern(-2, pid, OUT, -3)]
    q = SPARQLQuery()
    q.pattern_group.patterns = chain
    q.result.nvars = 3
    q.result.required_vars = [-1, -2, -3]
    heuristic_plan(q)
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    assert pl.choose_strategy(q.pattern_group.patterns) == "walk"


LUBM_PREFIX = """
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
"""
#: the reference LUBM basic-suite shapes (wukong lubm_q1..q7) — q1/q2 are
#: the cyclic LUBM Q2/Q9 triangles, the rest are acyclic
LUBM_REFERENCE_SHAPES = {
    "lubm_q1": LUBM_PREFIX + """SELECT ?X ?Y ?Z WHERE {
        ?X rdf:type ub:GraduateStudent . ?Y rdf:type ub:University .
        ?Z rdf:type ub:Department . ?X ub:memberOf ?Z .
        ?Z ub:subOrganizationOf ?Y . ?X ub:undergraduateDegreeFrom ?Y . }""",
    "lubm_q2": LUBM_PREFIX + """SELECT ?X ?Y ?Z WHERE {
        ?X rdf:type ub:UndergraduateStudent . ?Y rdf:type ub:FullProfessor .
        ?Z rdf:type ub:Course . ?X ub:advisor ?Y . ?Y ub:teacherOf ?Z .
        ?X ub:takesCourse ?Z . }""",
    "lubm_q3": LUBM_PREFIX + """SELECT ?X WHERE {
        ?X rdf:type ub:GraduateStudent .
        ?X ub:takesCourse
        <http://www.Department0.University0.edu/GraduateCourse0> . }""",
    "lubm_q4": LUBM_PREFIX + """SELECT ?X ?Y1 ?Y2 WHERE {
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X rdf:type ub:FullProfessor . ?X ub:name ?Y1 .
        ?X ub:emailAddress ?Y2 . }""",
    "lubm_q5": LUBM_PREFIX + """SELECT ?X WHERE {
        ?X ub:memberOf <http://www.Department0.University0.edu> . }""",
    "lubm_q6": LUBM_PREFIX + """SELECT ?X WHERE {
        ?X rdf:type ub:GraduateStudent . }""",
    "lubm_q7": LUBM_PREFIX + """SELECT ?X ?Y WHERE {
        ?X rdf:type ub:UndergraduateStudent . ?Y rdf:type ub:Course .
        <http://www.Department0.University0.edu/AssociateProfessor0>
        ub:teacherOf ?Y . ?X ub:takesCourse ?Y . }""",
}


@pytest.fixture(scope="module")
def lubm_world():
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
    from wukong_tpu.store.gstore import build_partition

    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    return g, VirtualLubmStrings(1, seed=42), Stats.generate(triples)


def test_lubm_reference_queries_route_walk_under_auto(lubm_world):
    """The acceptance guard: every LUBM reference shape — including the
    two cyclic triangles, whose walk intermediates stay small — routes
    ``walk`` under the default auto knobs, so the serving headline path
    is untouched by the new strategy."""
    from wukong_tpu.sparql.parser import Parser

    g, ss, stats = lubm_world
    pl = Planner(stats)
    for name, text in LUBM_REFERENCE_SHAPES.items():
        q = Parser(ss).parse(text)
        pl.generate_plan(q)
        assert pl.choose_strategy(q.pattern_group.patterns) == "walk", name


def test_lubm_acyclic_wcoj_forced_still_identical(lubm_world):
    """Forcing wcoj on a supported acyclic LUBM shape stays
    byte-identical to the walk (strategy changes plans, never answers)."""
    from wukong_tpu.sparql.parser import Parser

    g, ss, stats = lubm_world
    text = LUBM_REFERENCE_SHAPES["lubm_q5"]
    qw = Parser(ss).parse(text)
    heuristic_plan(qw)
    CPUEngine(g, ss).execute(qw)
    qj = Parser(ss).parse(text)
    heuristic_plan(qj)
    WCOJExecutor(g, ss, stats=stats).execute(qj)
    assert qj.result.status_code == ErrorCode.SUCCESS
    assert rows_of(qw) == rows_of(qj)


# ---------------------------------------------------------------------------
# proxy routing, degradation, chaos
# ---------------------------------------------------------------------------

@pytest.fixture()
def tri_proxy():
    from wukong_tpu.store.gstore import build_partition

    triples, meta = generate_triangle(m=60, noise=3, seed=1)
    g = build_partition(triples, 0, 1)
    ss = CyclicStrings(meta)
    stats = Stats.generate(triples)
    proxy = Proxy(g, ss, cpu_engine=CPUEngine(g, ss), planner=Planner(stats))
    return proxy, cyclic_query_text(meta)


def test_proxy_auto_routes_wcoj_and_matches_walk(tri_proxy):
    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    q = proxy.run_single_query(text, blind=False)
    assert q.join_strategy == "wcoj"
    assert q.result.status_code == ErrorCode.SUCCESS
    Global.join_strategy = "walk"
    qw = proxy.run_single_query(text, blind=False)
    assert qw.join_strategy == "walk"
    assert rows_of(q) == rows_of(qw)


def test_proxy_strategy_memoized_and_knob_responsive(tri_proxy):
    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    assert proxy.run_single_query(text).join_strategy == "wcoj"
    # memoized decision must NOT outlive a knob flip (knobs join the key)
    Global.join_strategy = "walk"
    assert proxy.run_single_query(text).join_strategy == "walk"
    Global.join_strategy = "auto"
    assert proxy.run_single_query(text).join_strategy == "wcoj"


@pytest.mark.chaos
def test_join_materialize_fault_degrades_to_walk(tri_proxy):
    """An injected ``join.materialize`` transient fires before any result
    state is touched; the proxy re-dispatches the SAME query to the walk:
    reply SUCCESS, rows byte-identical, fallback counted — never an
    error."""
    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    qw = proxy.run_single_query(text, blind=False)  # wcoj baseline
    assert qw.join_strategy == "wcoj"
    proxy.wcoj().tables.clear()
    before = _fallbacks(proxy)
    faults.install(FaultPlan(
        [FaultSpec(site="join.materialize", kind="transient")], seed=7))
    q = proxy.run_single_query(text, blind=False)
    faults.clear()
    assert q.result.status_code == ErrorCode.SUCCESS
    assert q.result.complete
    assert rows_of(q) == rows_of(qw)
    assert _fallbacks(proxy) == before + 1


def _fallbacks(proxy) -> float:
    total = 0.0
    for s in proxy.metrics.snapshot().get(
            "wukong_join_fallback_total", {}).get("series", []):
        total += s["value"]
    return total


def test_wcoj_budget_expiry_is_structured_partial(tri_proxy):
    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    Global.query_budget_rows = 10
    try:
        q = proxy.run_single_query(text, blind=False)
    finally:
        Global.query_budget_rows = 0
    assert q.join_strategy == "wcoj"
    assert q.result.status_code == ErrorCode.BUDGET_EXCEEDED
    assert not q.result.complete
    assert q.result.dropped_patterns  # the unexecuted patterns are named


def test_explain_renders_strategy_and_levels(tri_proxy):
    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    rep = proxy.explain_query(text)
    assert rep["strategy"] == "wcoj"
    assert "strategy: wcoj" in rep["rendered"]
    rep2 = proxy.explain_query(text, analyze=True)
    assert rep2["strategy"] == "wcoj"
    levels = rep2["wcoj_levels"]
    assert len(levels) == 3  # one per variable
    assert all({"var", "candidates", "rows_out", "probes"} <= set(lv)
               for lv in levels)
    assert "candidates" in rep2["rendered"]


def test_table_cache_invalidates_on_store_version_bump(tri_proxy):
    """A dynamic insert bumps the store version; the WCOJ sorted-table
    cache is version-keyed, so the next query sees the new edge without
    any explicit invalidation."""
    from wukong_tpu.store.dynamic import insert_triples

    proxy, text = tri_proxy
    Global.join_strategy = "wcoj"
    base = proxy.run_single_query(text, blind=False)
    g = proxy.g
    meta_p = {2: "p1", 3: "p2", 4: "p3"}
    assert set(meta_p) == {2, 3, 4}
    # close a brand-new triangle on fresh vertices
    from wukong_tpu.types import NORMAL_ID_START

    a, b, c = (NORMAL_ID_START + 7001, NORMAL_ID_START + 7002,
               NORMAL_ID_START + 7003)
    insert_triples(g, np.asarray(
        [[a, 2, b], [b, 3, c], [a, 4, c]], dtype=np.int64))
    q = proxy.run_single_query(text, blind=False)
    assert q.result.status_code == ErrorCode.SUCCESS
    assert (a, b, c) in rows_of(q)
    assert rows_of(q) - rows_of(base) == {(a, b, c)}


def test_vertex_bound_follows_the_store_version_not_a_list(tri_proxy):
    """The bound of the table a candidate list is marked in is the store's
    (one past its largest keyed or indexed vertex), cached per store version
    beside the tables: the same for every list, new after a write that adds
    a larger vertex; a sharded view's is the largest of its shards'."""
    from wukong_tpu.join.dist import ShardedJoinView
    from wukong_tpu.store.dynamic import insert_triples
    from wukong_tpu.types import NORMAL_ID_START

    proxy, _text = tri_proxy
    g, tables = proxy.g, proxy.wcoj().tables
    top = max(int(seg.keys[-1]) for seg in g.segments.values())
    assert tables.vertex_bound() == store_vertex_bound(g) == top + 1
    entries = tables.stats()["entries"]
    assert tables.vertex_bound() == top + 1  # a hit: nothing is added
    assert tables.stats()["entries"] == entries
    a = NORMAL_ID_START + 70_001
    assert a > top
    insert_triples(g, np.asarray([[a, 2, a + 1]], dtype=np.int64))
    assert tables.vertex_bound() == store_vertex_bound(g) == a + 2
    assert ShardedJoinView([g, g]).vertex_bound() == a + 2
    assert ShardedJoinView([]).vertex_bound() == 0


@pytest.mark.parametrize("lst,fits", [
    ([], True),
    ([0, 3, 9], True),
    ([3, 3, 9], True),  # a repeated id marks its flag twice
    ([-1, 3], False),
    ([3, 10], False),  # past the store's bound: it would be dropped
    ([9], True),
])
def test_a_list_the_table_cannot_hold_is_searched(lst, fits):
    class Tables:
        def vertex_bound(self):
            return 10

    ex = WCOJExecutor(None, tables=Tables())
    got = ex._list_bound(np.asarray(lst, dtype=np.int64))
    assert got == (10 if fits else None)


# ---------------------------------------------------------------------------
# the join-strategy analysis gate
# ---------------------------------------------------------------------------

GATE_GOOD = """
JOIN_STRATEGIES = ("walk", "wcoj")
"""
GATE_CHOOSER_OK = """
def choose_strategy(patterns):
    if not patterns:
        return "walk"
    return "wcoj"
"""
GATE_CHOOSER_BAD = """
def choose_strategy(patterns):
    return "wolk"
"""


def _write_tree(root, files):
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return str(root)


def test_join_gate_clean_tree_passes(tmp_path):
    from wukong_tpu.analysis import run_analysis

    pkg = _write_tree(tmp_path / "pkg", {
        "join/__init__.py": GATE_GOOD,
        "planner/opt.py": GATE_CHOOSER_OK,
    })
    assert run_analysis(pkg, plugins=["join-strategy"]) == []


def test_join_gate_flags_undeclared_strategy(tmp_path):
    from wukong_tpu.analysis import run_analysis

    pkg = _write_tree(tmp_path / "pkg", {
        "join/__init__.py": GATE_GOOD,
        "planner/opt.py": GATE_CHOOSER_BAD,
    })
    bad = run_analysis(pkg, plugins=["join-strategy"])
    assert len(bad) == 1 and "wolk" in bad[0].message


def test_join_gate_flags_missing_registry(tmp_path):
    from wukong_tpu.analysis import run_analysis

    pkg = _write_tree(tmp_path / "pkg", {
        "join/__init__.py": "X = 1\n",
    })
    bad = run_analysis(pkg, plugins=["join-strategy"])
    assert len(bad) == 1 and "JOIN_STRATEGIES" in bad[0].message


# ---------------------------------------------------------------------------
# the device route: padded/bucketed kernel parity (ISSUE 15 satellite)
# ---------------------------------------------------------------------------

def _rand_csr(seed=7, nk=60, ne=400, vmax=80):
    from wukong_tpu.store.segment import CSRSegment

    rng = np.random.default_rng(seed)
    return CSRSegment.from_pairs(rng.integers(0, nk, ne),
                                 rng.integers(0, vmax, ne)), rng


def test_kernels_jit_empty_candidate_lists():
    """Zero-length candidate vectors through the jitted kernels match the
    NumPy kernels (both all-empty, no shape errors)."""
    from wukong_tpu.join.kernels import jit_kernels

    member, pair = jit_kernels()
    seg, _ = _rand_csr()
    empty = np.empty(0, dtype=np.int64)
    assert np.asarray(member(np.array([1, 3, 5]), empty)).shape == (0,)
    got = np.asarray(pair(seg.keys, seg.offsets, seg.edges, empty, empty))
    assert got.shape == (0,)
    assert np.array_equal(got, pair_member(seg.keys, seg.offsets,
                                           seg.edges, empty, empty))


def _group_oracle(choice, starts, degs, window, glob, segs, gen, cap):
    """What one call of the level program keeps, one candidate at a time:
    the group's candidates in the host's order, ``cap`` of them from the
    window's base on, those every other constraint passes."""
    lo, hi, base = window
    cand = []
    for r in range(lo, hi):
        if choice[r] == gen:
            run = glob if gen == len(segs) else \
                segs[gen].edges[starts[gen][r]:starts[gen][r] + degs[gen][r]]
            cand += [(r, int(x)) for x in run]
    keep = []
    for r, x in cand[base:base + cap]:
        ok = gen == len(segs) or x in glob
        for i, seg in enumerate(segs):
            if i != gen:
                ok = ok and x in seg.edges[starts[i][r]:starts[i][r]
                                           + degs[i][r]]
        if ok:
            keep.append((r, x))
    return keep


def test_kernels_level_probe_windows_singletons_and_a_base():
    """The level program against its NumPy twin and a one-candidate-at-a-
    time oracle: each generator (two adjacencies, one of degree-1 runs, and
    the list), a window of no row of its generator (count 0), a window of
    some rows, and a window that starts inside a row's run (``base``) and
    stops inside a later one (``out_cap``), each over the level's rows and
    over a run's own (``rows_cap`` from ``row0``), survivor for survivor.
    """
    from wukong_tpu.join.kernels import (
        jit_level_probe,
        level_probe,
        level_ranges,
        pad_pow2,
        to_device_i32,
    )
    from wukong_tpu.store.segment import CSRSegment

    seg, rng = _rand_csr(seed=11)
    seg1 = CSRSegment.from_pairs(np.arange(50), rng.integers(0, 80, 50))
    glob = np.unique(rng.integers(0, 80, 30))
    n = 64
    anchors = np.stack([rng.integers(0, 70, n), rng.integers(0, 60, n)])
    starts, degs, _choice, _mins = level_ranges(
        anchors, [(seg.keys, seg.offsets), (seg1.keys, seg1.offsets)],
        (0, 1), len(glob))
    choice = rng.integers(0, 3, n).astype(np.int8)  # any rows to any group
    dev = [to_device_i32(a) for a in (starts, degs, glob, seg.edges,
                                      seg1.edges)]
    seen = set()
    for gen, rows_cap in [(g, r) for g in (0, 1, 2) for r in (n, 32)]:
        for window, cap in (((0, 0, 0), 16), ((0, n, 0), None),
                            ((10, 40, 0), None), ((10, 40, 3), 8)):
            if window[1] - window[0] > rows_cap:
                continue
            sizes = degs[gen] if gen < 2 else np.full(n, len(glob))
            total = int(sizes[window[0]:window[1]][
                choice[window[0]:window[1]] == gen].sum())
            cap = cap or pad_pow2(total, floor=16)
            win = np.array((min(window[0], n - rows_cap),) + window,
                           dtype=np.int32)
            want = level_probe(choice, starts, degs, win, glob,
                               [seg.edges, seg1.edges], gen, (8, 2),
                               gen != 2, cap, rows_cap=rows_cap)
            fn = jit_level_probe(gen, (8, 2), gen != 2, None, cap, rows_cap)
            got = fn(np.asarray(choice), dev[0], dev[1], win, dev[2],
                     dev[3], dev[4])
            count = int(got[2])
            assert count == int(want[2]), (gen, window)
            pairs = list(zip(np.asarray(got[0])[:count].tolist(),
                             np.asarray(got[1])[:count].tolist()))
            assert pairs == list(zip(want[0][:count].tolist(),
                                     want[1][:count].tolist()))
            assert pairs == _group_oracle(choice, starts, degs, window, glob,
                                          [seg, seg1], gen, cap)
            seen.add((window == (0, 0, 0), count > 0, rows_cap))
    assert {(True, False, 32), (False, True, 32), (False, True, n)} <= seen
    # an empty window keeps nothing
    assert not any(empty and kept for empty, kept, _r in seen)


# the device-made level against the host's, through ``_level``: the worlds
# level by level, and the triangle's last level on prefixes made to hold
# what the worlds do not (a small list, rows of degree 0, no candidate, a
# level in runs, an id past int32)
LEVEL_CASES = ("triangle", "diamond", "clique4", "list_generated",
               "degree_zero_rows", "empty_level", "level_in_runs",
               "int32_degrades")


@pytest.mark.parametrize("case", LEVEL_CASES)
def test_a_level_made_on_the_device_equals_the_host_level(case, monkeypatch):
    """A level of the device route makes its candidates on the chip and
    keeps the survivors the host path keeps, in its order: the new prefix
    is the host level's array for array, and so are the candidates. An id
    past int32 in the prefix degrades the level to the host, latched for
    the query and counted as ``int32_range``."""
    from wukong_tpu.join import kernels
    from wukong_tpu.obs.metrics import get_registry
    from wukong_tpu.store.gstore import build_partition

    def fallbacks():
        snap = get_registry().snapshot()
        return sum(x["value"] for x in (snap.get(
            "wukong_join_device_fallback_total") or {}).get("series", [])
            if x["labels"].get("reason") == "int32_range")

    triples, meta = WORLDS[case if case in WORLDS else "triangle"]()
    g = build_partition(triples, 0, 1)
    ex = WCOJExecutor(g)
    q0 = mkq(meta)
    heuristic_plan(q0)
    qg, unary = ex._analyze_and_warm(q0)
    Global.join_device = "device"  # the device floor: one candidate
    prefixes = [np.empty((1, 0), dtype=np.int64)]
    for k, v in enumerate(qg.order):
        cols = {qg.order[i]: i for i in range(k)}
        prefixes.append(ex._level(qg, v, k, prefixes[-1], cols, unary[v],
                                  "host")[0])
    calls = []
    real = WCOJExecutor._probe_start

    def spy(self, lvl, groups, lo, hi, whole, tr=None):
        calls.append((lvl["generators"], [j for j, _c in groups], whole))
        return real(self, lvl, groups, lo, hi, whole, tr)

    monkeypatch.setattr(WCOJExecutor, "_probe_start", spy)

    def both(k, prefix, lst):
        v, cols = qg.order[k], {qg.order[i]: i for i in range(k)}
        host = ex._level(qg, v, k, prefix, cols, lst, "host")
        q = SPARQLQuery()
        made = ex._level(qg, v, k, prefix, cols, lst, "device", q)
        assert np.array_equal(made[0], host[0]), (case, k)
        assert made[1]["candidates"] == host[1]["candidates"]
        assert host[1]["enumerated"] == "host"
        return made[1], host[1], q

    if case in WORLDS:
        for k in range(1, len(qg.order)):
            rec, _host, _q = both(k, prefixes[k], unary[qg.order[k]])
            assert (rec["route"], rec["enumerated"]) == ("device", "device")
            assert rec["candidates"] > 0
        return
    k = len(qg.order) - 1
    prefix, lst = prefixes[k].copy(), list(unary[qg.order[k]])
    nobody = store_vertex_bound(g) + 7  # no adjacency's key
    if case == "list_generated":
        ends = np.unique(prefixes[k + 1][:, -1])
        lst.append(np.sort(np.random.default_rng(3).choice(ends, 3,
                                                           replace=False)))
    elif case == "degree_zero_rows":
        prefix[::2] = nobody
    elif case == "empty_level":
        prefix[:] = nobody
    elif case == "level_in_runs":
        monkeypatch.setattr(kernels, "LEVEL_SLICE", 16)
    elif case == "int32_degrades":
        prefix[0] = 1 << 31
    was = fallbacks()
    rec, host, q = both(k, prefix, lst)
    if case == "int32_degrades":
        assert q._join_device_broken and fallbacks() == was + 1
        assert (rec["route"], rec["enumerated"]) == ("host", "host")
        return
    assert (rec["route"], rec["enumerated"]) == ("device", "device")
    if case == "list_generated":  # some rows took the list as generator
        assert any(gens - 1 in gen for gens, gen, _w in calls)
    elif case == "degree_zero_rows":
        assert 0 < rec["candidates"] < both(k, prefixes[k], lst)[0][
            "candidates"]
    elif case == "empty_level":
        assert rec["candidates"] == 0 and not calls
    elif case == "level_in_runs":
        assert calls and not any(whole for _g, _j, whole in calls)
        assert len(calls) > 1


# the list's membership on the device: (the sorted list, the candidates, the
# id bound, whether ``direct_lookup_wins`` gives the table at these shapes)
_RNG = np.random.default_rng(35)
_LIST = np.unique(_RNG.integers(0, 200, 60))
MEMBER_CASES = {
    "empty_list": (np.empty(0, np.int64), _RNG.integers(0, 200, 64), 200,
                   None),
    "one_id": (np.array([17]), np.array([16, 17, 18, 17, 0, 199]), 200, True),
    "ids_at_0_and_at_the_bound": (
        np.array([0, 5, 199]), np.array([0, 1, 5, 198, 199, 200, 201, -1]),
        200, True),
    "candidates_outside_the_range": (
        _LIST, _RNG.integers(-50, 300, 256), 200, True),
    "negative_candidates": (_LIST, -_RNG.integers(1, 1 << 30, 64), 200, True),
    "all_padding": (_LIST, np.zeros(128, np.int64), 200, True),
    "list_longer_than_the_candidates": (
        np.arange(0, 300, 2), np.array([0, 1, 298, 299]), 300, False),
    "no_bound": (_LIST, _RNG.integers(0, 200, 64), None, False),
    "a_repeated_id": (np.array([3, 3, 9, 9, 9, 150]),
                      _RNG.integers(0, 200, 64), 200, True),
}


@pytest.mark.parametrize("case", sorted(MEMBER_CASES))
def test_kernels_member_on_the_device_equals_member_sorted(case):
    """``member_sorted_device``: a table over the id range where the rule
    gives it (one scatter in the traced program), the search where it does
    not (none); either equals ``member_sorted`` slot for slot."""
    import jax

    from wukong_tpu.join.kernels import (
        direct_lookup_wins,
        member_sorted_device,
        to_device_i32,
    )

    lst, cand, bound, direct = MEMBER_CASES[case]
    want = member_sorted(lst.astype(np.int64), cand.astype(np.int64))
    if direct is not None and bound is not None:
        assert direct_lookup_wins(len(cand), len(lst), bound) == direct

    def fn(a, v):
        return member_sorted_device(a, v, bound)

    args = to_device_i32(lst), to_device_i32(cand)
    got = np.asarray(jax.jit(fn)(*args))
    assert got.dtype == bool and np.array_equal(got, want)
    assert str(jax.make_jaxpr(fn)(*args)).count(" scatter[") == bool(direct)
    if case in ("ids_at_0_and_at_the_bound", "one_id"):
        assert want.any() and not want.all()
    if case in ("negative_candidates", "empty_list"):
        assert not want.any()


def test_kernels_depth_bounded_pair_member_parity():
    """The device path's log2(max_degree)+1 iteration bound converges to
    the same mask as the generic log2(len(edges))+1 bound."""
    seg, rng = _rand_csr(seed=13, nk=40, ne=800, vmax=100)
    anchors = rng.integers(0, 50, 500)
    vals = rng.integers(0, 100, 500)
    max_deg = int(np.diff(seg.offsets).max())
    depth = max(max_deg, 1).bit_length() + 1
    assert np.array_equal(
        pair_member(seg.keys, seg.offsets, seg.edges, anchors, vals),
        pair_member(seg.keys, seg.offsets, seg.edges, anchors, vals,
                    depth=depth))


def test_kernels_jit_values_past_int31_under_x64():
    """>2^31-safe ids/offsets through the jitted kernels: under
    ``jax.enable_x64`` the SAME kernel source runs int64 and
    matches NumPy on values past int32 range. (The default x64-off device
    path never sees such values — ``to_device_i32`` REFUSES them and the
    executor degrades to host, tested below.)"""
    from jax import enable_x64

    from wukong_tpu.join.kernels import jit_kernels

    big = np.int64(1) << 32
    keys = np.array([2, 5, 9], dtype=np.int64)
    edges = np.array([big + 1, big + 7, big + 3, big + 9, big + 5],
                     dtype=np.int64)
    offsets = np.array([0, 2, 4, 5], dtype=np.int64)
    anchors = np.array([2, 2, 5, 9, 7], dtype=np.int64)
    vals = np.array([big + 1, big + 3, big + 9, big + 5, big + 1],
                    dtype=np.int64)
    want_pair = pair_member(keys, offsets, edges, anchors, vals)
    want_member = member_sorted(np.sort(edges), vals)
    with enable_x64():
        member, pair = jit_kernels()
        got_pair = np.asarray(pair(keys, offsets, edges, anchors, vals))
        got_member = np.asarray(member(np.sort(edges), vals))
    assert np.array_equal(got_pair, want_pair)
    assert np.array_equal(got_member, want_member)


def test_to_device_i32_refuses_out_of_range():
    """Offsets/ids past int32 must refuse (DeviceRangeError -> host
    fallback), never silently truncate."""
    from wukong_tpu.join.kernels import DeviceRangeError, to_device_i32

    with pytest.raises(DeviceRangeError):
        to_device_i32(np.array([0, 1, 1 << 31], dtype=np.int64))
    ok = to_device_i32(np.array([0, (1 << 31) - 1], dtype=np.int64))
    assert np.asarray(ok).tolist() == [0, (1 << 31) - 1]


def test_stream_seed_masks_device_parity():
    """The stream subsystem's device-batched frontier seeding: one fused
    call's per-term masks reproduce match_delta's host seeds exactly
    (const endpoints, wildcards, repeated-var equality)."""
    from wukong_tpu.stream.continuous import device_seed_masks, match_delta

    rng = np.random.default_rng(3)
    triples = np.stack([rng.integers(100, 130, 400),
                        rng.integers(2, 6, 400),
                        rng.integers(100, 130, 400)], axis=1).astype(np.int64)
    pats = [Pattern(-1, 3, OUT, -2),          # both ends free
            Pattern(112, 4, OUT, -2),         # const subject
            Pattern(-1, 2, OUT, 105),         # const object
            Pattern(-1, 5, OUT, -1),          # repeated var: s == o
            Pattern(-2, 3, IN, -1)]           # engine-form IN orientation
    Global.join_device = "device"  # force past the amortization floor
    masks = device_seed_masks(pats, triples)
    assert masks is not None and masks.shape == (len(pats), len(triples))
    for i, pat in enumerate(pats):
        vh, sh = match_delta(pat, triples)
        vd, sd = match_delta(pat, triples, row_mask=masks[i])
        assert vh == vd
        assert np.array_equal(sh, sd), i
    Global.join_device = "host"  # pinned host: no device masks
    assert device_seed_masks(pats, triples) is None


# ---------------------------------------------------------------------------
# the device route: executor identity, fallback, chooser, feedback
# ---------------------------------------------------------------------------

def test_wcoj_device_route_byte_identical(world):
    """Forced ``join_device device``: every level probes on the XLA path
    and the result TABLE (rows AND order) is byte-identical to the host
    route — same candidate enumeration, same mask semantics."""
    name, _t, g, stats, meta = world
    qh, qd = mkq(meta), mkq(meta)
    heuristic_plan(qh)
    heuristic_plan(qd)
    WCOJExecutor(g, stats=stats).execute(qh)
    Global.join_device = "device"
    WCOJExecutor(g, stats=stats).execute(qd)
    assert qd.result.status_code == ErrorCode.SUCCESS
    assert np.array_equal(qh.result.table, qd.result.table), name
    assert all(lv["route"] == "device" for lv in qd.join_stats), name
    assert all(lv["route"] == "host" for lv in qh.join_stats), name


@pytest.mark.parametrize("half", ["_device_ranges", "_probe_start",
                                  "_probe_finish"])
def test_wcoj_device_failure_degrades_to_host(world, monkeypatch, half):
    """Any device-path failure, where the ranges are looked up, where a
    group's candidates are dispatched or where the survivors are fetched,
    degrades the level (and latches the rest of the query) to the host
    kernels — the same table, row order included, never an error. The
    first level has no bound adjacency and never reaches the device."""
    name, _t, g, stats, meta = world
    Global.join_device = "device"
    wc = WCOJExecutor(g, stats=stats)
    monkeypatch.setattr(
        WCOJExecutor, half,
        lambda self, *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    q = mkq(meta)
    heuristic_plan(q)
    wc.execute(q)
    assert q.result.status_code == ErrorCode.SUCCESS
    assert q._join_device_broken
    assert all(lv["route"] == "host" for lv in q.join_stats[1:])
    assert all(lv["enumerated"] == "host" for lv in q.join_stats)
    qh = mkq(meta)
    heuristic_plan(qh)
    Global.join_device = "host"
    WCOJExecutor(g, stats=stats).execute(qh)
    assert np.array_equal(q.result.table, qh.result.table), name


def test_choose_join_route_knob_and_threshold(world):
    from wukong_tpu.join import JOIN_ROUTES

    _name, _t, _g, stats, meta = world
    pl = Planner(stats)
    q = mkq(meta)
    pl.generate_plan(q)
    pats = q.pattern_group.patterns
    Global.join_device = "host"
    assert pl.choose_join_route(pats) == "host"
    Global.join_device = "device"
    assert pl.choose_join_route(pats) == "device"
    Global.join_device = "auto"
    assert pl.choose_join_route(pats) in JOIN_ROUTES
    # the dispatch-amortization threshold: floor of 1 routes any
    # estimable chain device, an absurd floor routes host
    Global.join_device_min_candidates = 1
    assert pl.choose_join_route(pats) == "device"
    Global.join_device_min_candidates = 1 << 60
    assert pl.choose_join_route(pats) == "host"


def test_proxy_route_memoized_and_demoted(tri_proxy, monkeypatch):
    """The route decision is memoized through the plan cache and the
    measured-candidate feedback demotes an over-predicted device route
    back to host for the next same-template query (the PR 10 pattern)."""
    from wukong_tpu.planner.optimizer import Planner as _P

    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    monkeypatch.setattr(_P, "choose_join_route",
                        lambda self, pats: "device")
    q = proxy.run_single_query(text, blind=False)
    assert q.join_strategy == "wcoj" and q.join_route == "device"
    # the tiny triangle world's measured candidates sit far under the
    # (default) threshold -> the feedback demotes the memoized route
    q2 = proxy.run_single_query(text, blind=False)
    assert q2.join_route == "host"
    # a knob flip re-arms the estimate-driven decision (new memo key)
    Global.join_device_min_candidates = 1
    q3 = proxy.run_single_query(text, blind=False)
    assert q3.join_route == "device"


@pytest.mark.parametrize("floor", [1, 1 << 40])
def test_a_proxy_request_on_the_device_route_answers_as_the_host(
        tri_proxy, monkeypatch, floor):
    """A request the proxy sends down the join's device route answers the
    table the host route answers: every level with a bound adjacency made
    on the chip (a floor of one candidate), or none (a floor no level
    reaches); the demoted request that follows, on the host route,
    answers the same table."""
    from wukong_tpu.planner.optimizer import Planner as _P

    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    monkeypatch.setattr(_P, "choose_join_route",
                        lambda self, pats: "device")
    monkeypatch.setattr(WCOJExecutor, "_device_floor",
                        staticmethod(lambda: floor))
    q = proxy.run_single_query(text, blind=False)
    assert q.join_route == "device"
    made = [lv["enumerated"] for lv in q.join_stats]
    assert ("device" in made) == (floor == 1)
    assert bool(getattr(q, "_join_programs", None)) == (floor == 1)
    # the triangle's measured candidates sit under the threshold: demoted
    qh = proxy.run_single_query(text, blind=False)
    assert qh.join_route == "host"
    assert np.array_equal(q.result.table, qh.result.table)


@pytest.mark.parametrize("shared", [False, True])
def test_a_demoted_template_lets_go_the_programs_only_it_ran(
        tri_proxy, monkeypatch, shared):
    """The level programs a request ran belong to its template; a demotion
    off the join's device route lets go each one no other template ran
    (their text is resident while cached), and keeps one another template
    ran too; the next request of the template takes the host route."""
    from wukong_tpu.join import kernels
    from wukong_tpu.planner.optimizer import Planner as _P

    def cached(keys):
        return {k for k in keys if k in (kernels._LEVEL_RANGES_CACHE
                                         if k[0] == "ranges"
                                         else kernels._LEVEL_PROBE_CACHE)}

    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    monkeypatch.setattr(_P, "choose_join_route",
                        lambda self, pats: "device")
    # every level of the route on the device, where the measured volume
    # (under join_device_min_candidates) demotes the template after
    monkeypatch.setattr(WCOJExecutor, "_device_floor", staticmethod(lambda: 1))
    if shared:  # another template's request ran the same programs
        real = WCOJExecutor._device_level

        def spy(self, adj, G, prefix, q, k, tr):
            got = real(self, adj, G, prefix, q, k, tr)
            kernels.own_level_programs(q._join_programs, "another")
            return got

        monkeypatch.setattr(WCOJExecutor, "_device_level", spy)
    q = proxy.run_single_query(text, blind=False)
    assert q.join_route == "device"
    used = q._join_programs
    assert {k[0] for k in used} == {"ranges", "probe"}
    # the triangle's measured candidates sit under the threshold: demoted
    assert cached(used) == (used if shared else set())
    assert proxy.run_single_query(text, blind=False).join_route == "host"
    kernels.disown_level_programs("another")
    assert not cached(used)


def test_proxy_route_demoted_after_device_failure(tri_proxy, monkeypatch):
    """A device path that failed mid-query (latched host) demotes the
    template's memoized route — a deterministic failure is paid once,
    not re-attempted per query."""
    from wukong_tpu.planner.optimizer import Planner as _P

    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    Global.join_device_min_candidates = 1  # measured volume never demotes
    monkeypatch.setattr(_P, "choose_join_route",
                        lambda self, pats: "device")
    monkeypatch.setattr(
        WCOJExecutor, "_probe_start",
        lambda self, *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    q = proxy.run_single_query(text, blind=False)
    assert q.result.status_code == ErrorCode.SUCCESS
    assert q.join_route == "device"  # routed device, degraded internally
    q2 = proxy.run_single_query(text, blind=False)
    assert q2.join_route == "host"  # the failure latched the memo


def test_explain_renders_route_line(tri_proxy):
    proxy, text = tri_proxy
    Global.wcoj_min_rows = 1
    Global.wcoj_ratio = 1
    Global.join_device = "device"
    rep = proxy.explain_query(text, analyze=True)
    assert rep["strategy"] == "wcoj"
    assert rep["route"] == "device"
    assert "route: device" in rep["rendered"]
    assert all(lv["route"] == "device" for lv in rep["wcoj_levels"])


# ---------------------------------------------------------------------------
# the distributed generic join: heavy-lane fan-out over a 4-shard store
# ---------------------------------------------------------------------------

@pytest.fixture()
def lockdep_checked():
    """The distributed-join drill runs fully lockdep-checked: every lock
    the pool/slices create is a Debug wrapper feeding the
    acquisition-order graph; teardown asserts zero order cycles and zero
    declared-leaf inversions."""
    from wukong_tpu.analysis import lockdep

    lockdep.install(True)
    yield
    try:
        assert lockdep.cycles() == [], lockdep.cycles()
        assert lockdep.leaf_violations() == [], lockdep.leaf_violations()
    finally:
        lockdep.install(False)


@pytest.fixture()
def dist_world(lockdep_checked):
    """A 4-shard triangle world + a started host engine pool (locks built
    under the lockdep fixture so the whole drill is order-checked)."""
    from wukong_tpu.runtime.scheduler import EnginePool
    from wukong_tpu.store.gstore import build_partition

    triples, meta = generate_triangle(m=80, noise=4, seed=2)
    g1 = build_partition(triples, 0, 1)
    parts = [build_partition(triples, k, 4) for k in range(4)]
    stats = Stats.generate(triples)
    pool = EnginePool(num_engines=4,
                      make_engine=lambda tid: CPUEngine(g1))
    pool.start()
    yield g1, parts, stats, meta, pool
    pool.stop()


def _heavy_submitted(pool) -> float:
    from wukong_tpu.obs.metrics import get_registry

    for s in get_registry().snapshot().get(
            "wukong_pool_submitted_total", {}).get("series", []):
        if s["labels"].get("lane") == "heavy":
            return s["value"]
    return 0.0


def test_dist_join_fans_out_and_gathers_identical(dist_world):
    """The drill: a cyclic query over a 4-shard store fans out on the
    heavy lane (pool submissions counted), and the gathered rows are
    byte-identical (sorted) to the single-engine WCOJ and the walk."""
    from wukong_tpu.join.dist import DistributedWCOJExecutor

    g1, parts, stats, meta, pool = dist_world
    qw = mkq(meta)
    heuristic_plan(qw)
    CPUEngine(g1).execute(qw)
    q1 = mkq(meta)
    heuristic_plan(q1)
    WCOJExecutor(g1, stats=stats).execute(q1)
    before = _heavy_submitted(pool)
    qd = mkq(meta)
    heuristic_plan(qd)
    dx = DistributedWCOJExecutor(parts, stats=stats, pool=pool)
    dx.execute(qd)
    assert qd.result.status_code == ErrorCode.SUCCESS
    assert qd.join_dist == {"slices": 4}
    assert _heavy_submitted(pool) >= before + 3  # slices 1..3 fanned out
    assert rows_of(qd) == rows_of(q1) == rows_of(qw)
    a = np.asarray(sorted(rows_of(q1)), dtype=np.int64)
    b = np.asarray(sorted(rows_of(qd)), dtype=np.int64)
    assert np.array_equal(a, b)  # byte-identical gathered rows
    # merged per-level stats cover every level with slice attribution
    assert all(lv.get("slices") == 4 for lv in qd.join_stats)


@pytest.mark.chaos
def test_dist_join_slice_fault_degrades_per_slice(dist_world):
    """An injected ``join.slice`` transient fails ONE slice; the gather
    barrier re-runs it inline (per-slice fallback) and the query still
    succeeds with byte-identical rows — never a per-query failure."""
    from wukong_tpu.join.dist import DistributedWCOJExecutor

    g1, parts, stats, meta, pool = dist_world
    q1 = mkq(meta)
    heuristic_plan(q1)
    WCOJExecutor(g1, stats=stats).execute(q1)
    faults.install(FaultPlan(
        [FaultSpec(site="join.slice", kind="transient", count=1)], seed=5))
    qd = mkq(meta)
    heuristic_plan(qd)
    dx = DistributedWCOJExecutor(parts, stats=stats, pool=pool)
    dx.execute(qd)
    faults.clear()
    assert qd.result.status_code == ErrorCode.SUCCESS
    assert rows_of(qd) == rows_of(q1)
    assert _dist_fallbacks("slice_retry") >= 1


def _dist_fallbacks(reason: str) -> float:
    from wukong_tpu.obs.metrics import get_registry

    for s in get_registry().snapshot().get(
            "wukong_join_dist_fallback_total", {}).get("series", []):
        if s["labels"].get("reason") == reason:
            return s["value"]
    return 0.0


@pytest.mark.chaos
def test_dist_join_double_slice_failure_degrades_to_walk(dist_world):
    """A slice that fails its inline retry too degrades the WHOLE query
    to the (distributed) walk through the proxy's strategy router — the
    wcoj->walk posture, reply SUCCESS, rows intact."""
    from wukong_tpu.join.dist import DistributedWCOJExecutor
    from wukong_tpu.runtime.proxy import Proxy

    g1, parts, stats, meta, pool = dist_world

    class _FakeDist:
        """Stands in for the DistEngine in the strategy router: carries
        the sharded store's partitions and walks on the host engine."""

        class _SS:
            pass

        def __init__(self):
            self.sstore = self._SS()
            self.sstore.stores = parts

        def execute(self, q, from_proxy=True):
            return CPUEngine(g1).execute(q, from_proxy)

    proxy = Proxy(g1, None, cpu_engine=CPUEngine(g1),
                  planner=Planner(stats))
    proxy.dist = _FakeDist()
    proxy._pool = pool
    qw = mkq(meta)
    heuristic_plan(qw)
    CPUEngine(g1).execute(qw)
    faults.install(FaultPlan(
        [FaultSpec(site="join.slice", kind="transient", count=2,
                   shard=1)], seed=9))
    q = mkq(meta)
    heuristic_plan(q)
    q.join_strategy = "wcoj"
    proxy._serve_execute(q, proxy.dist)
    faults.clear()
    assert q.result.status_code == ErrorCode.SUCCESS
    assert rows_of(q) == rows_of(qw)
    assert _fallbacks(proxy) >= 1  # counted as a wcoj->walk degradation


def test_dist_join_no_pool_runs_single(dist_world):
    """Without live engines the fan-out degrades to the single federated
    join (mode=single), not to an error."""
    from wukong_tpu.join.dist import DistributedWCOJExecutor

    g1, parts, stats, meta, _pool = dist_world
    q1 = mkq(meta)
    heuristic_plan(q1)
    WCOJExecutor(g1, stats=stats).execute(q1)
    qd = mkq(meta)
    heuristic_plan(qd)
    dx = DistributedWCOJExecutor(parts, stats=stats, pool=None)
    dx.execute(qd)
    assert qd.result.status_code == ErrorCode.SUCCESS
    assert rows_of(qd) == rows_of(q1)
    assert getattr(qd, "join_dist", None) is None  # no fan-out happened


def test_sharded_join_view_version_tracks_all_shards(dist_world):
    """Any shard's mutation bumps the federated view's version, AND a
    wholesale shard-slot replacement (migration cutover / recovery
    rebuild assigns ``stores[i] = new_store`` in place) changes it too —
    the shared table cache must never serve a retired shard's data."""
    from wukong_tpu.join.dist import ShardedJoinView
    from wukong_tpu.store.dynamic import insert_triples
    from wukong_tpu.store.gstore import build_partition
    from wukong_tpu.types import NORMAL_ID_START

    _g1, parts, _stats, meta, _pool = dist_world
    live = list(parts)  # stands in for sstore.stores (held by reference)
    view = ShardedJoinView(live)
    v0 = view.version
    a = NORMAL_ID_START + 9001
    insert_triples(live[2], np.asarray([[a, 2, a + 1]], dtype=np.int64))
    v1 = view.version
    assert v1 != v0
    # slot replacement: a fresh store object in the SAME list slot (the
    # PR 12 cutover shape) must change the key even at equal versions
    triples2, _ = generate_triangle(m=20, noise=1, seed=8)
    live[1] = build_partition(triples2, 1, 4)
    assert view.version != v1
    assert view.stores[1] is live[1]  # reads resolve the live source


def test_dist_join_budget_expiry_commits_completed_slices(dist_world):
    """Structured budget expiry mid-fan-out: the completed slices' rows
    commit as the partial result (complete=False, structured status) —
    the base executor's 'expiry commits the prefix built so far'
    posture, never a silently empty partial."""
    from wukong_tpu.join.dist import DistributedWCOJExecutor

    g1, parts, stats, meta, pool = dist_world
    Global.query_budget_rows = 200  # each slice charges the shared budget
    try:
        qd = mkq(meta)
        heuristic_plan(qd)
        dx = DistributedWCOJExecutor(parts, stats=stats, pool=pool)
        from wukong_tpu.runtime.resilience import Deadline

        qd.deadline = Deadline.from_config()
        dx.execute(qd)
    finally:
        Global.query_budget_rows = 0
    assert qd.result.status_code == ErrorCode.BUDGET_EXCEEDED
    assert not qd.result.complete


def test_join_gate_requires_readme_knob_row(tmp_path):
    from wukong_tpu.analysis import run_analysis

    pkg = _write_tree(tmp_path / "pkg", {
        "join/__init__.py": GATE_GOOD,
    })
    readme = tmp_path / "README.md"
    readme.write_text("| knob | default |\n|---|---|\n| `other` | x |\n")
    bad = run_analysis(pkg, plugins=["join-strategy"],
                       readme_path=str(readme))
    assert len(bad) == 1 and "join_strategy" in bad[0].message
    readme.write_text(
        "| knob | default |\n|---|---|\n| `join_strategy` | auto |\n")
    assert run_analysis(pkg, plugins=["join-strategy"],
                        readme_path=str(readme)) == []


GATE_ROUTES = GATE_GOOD + '\nJOIN_ROUTES = ("host", "device")\n'
GATE_ROUTE_CHOOSER_OK = """
def choose_join_route(patterns):
    if not patterns:
        return "host"
    return "device"
"""
GATE_ROUTE_CHOOSER_BAD = """
def classify_join_route(q):
    return "gpu"
"""


def test_join_gate_route_chooser_needs_registry(tmp_path):
    """A route chooser without a literal JOIN_ROUTES registry is a
    violation — the closed set must exist before anything returns from
    it."""
    from wukong_tpu.analysis import run_analysis

    pkg = _write_tree(tmp_path / "pkg", {
        "join/__init__.py": GATE_GOOD,  # strategies only, no routes
        "planner/opt.py": GATE_ROUTE_CHOOSER_OK,
    })
    bad = run_analysis(pkg, plugins=["join-strategy"])
    assert len(bad) == 1 and "JOIN_ROUTES" in bad[0].message


def test_join_gate_flags_undeclared_route(tmp_path):
    from wukong_tpu.analysis import run_analysis

    pkg = _write_tree(tmp_path / "pkg", {
        "join/__init__.py": GATE_ROUTES,
        "planner/opt.py": GATE_ROUTE_CHOOSER_BAD,
    })
    bad = run_analysis(pkg, plugins=["join-strategy"])
    assert len(bad) == 1 and "gpu" in bad[0].message
    pkg2 = _write_tree(tmp_path / "pkg2", {
        "join/__init__.py": GATE_ROUTES,
        "planner/opt.py": GATE_ROUTE_CHOOSER_OK,
    })
    assert run_analysis(pkg2, plugins=["join-strategy"]) == []


def test_join_gate_requires_join_device_knob_row(tmp_path):
    """Config-readme coverage both ways: with routes declared, the
    README knob table must carry the `join_device` row next to
    `join_strategy` (and is clean once both exist)."""
    from wukong_tpu.analysis import run_analysis

    pkg = _write_tree(tmp_path / "pkg", {
        "join/__init__.py": GATE_ROUTES,
    })
    readme = tmp_path / "README.md"
    readme.write_text(
        "| knob | default |\n|---|---|\n| `join_strategy` | auto |\n")
    bad = run_analysis(pkg, plugins=["join-strategy"],
                       readme_path=str(readme))
    assert len(bad) == 1 and "join_device" in bad[0].message
    readme.write_text(
        "| knob | default |\n|---|---|\n| `join_strategy` | auto |\n"
        "| `join_device` | auto |\n")
    assert run_analysis(pkg, plugins=["join-strategy"],
                        readme_path=str(readme)) == []
