"""TPU engine vs CPU oracle on the LUBM basic suite (virtual CPU devices)."""

import glob
import os

import numpy as np
import pytest

from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.tpu import TPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.planner.plan_file import set_plan
from wukong_tpu.sparql.parser import Parser
from wukong_tpu.store.gstore import build_partition

from wukong_tpu.utils.paths import LUBM_BASIC as BASIC


@pytest.fixture(scope="module")
def world():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    return g, ss


@pytest.fixture(scope="module")
def engines(world):
    g, ss = world
    return CPUEngine(g, ss), TPUEngine(g, ss)


def _both(engines, ss, text, plan=None):
    cpu, tpu = engines
    rows = {}
    for name, eng in (("cpu", cpu), ("tpu", tpu)):
        q = Parser(ss).parse(text)
        if plan:
            assert set_plan(q.pattern_group, open(plan).read())
        else:
            heuristic_plan(q)
        eng.execute(q)
        assert q.result.status_code == 0, (name, q.result.status_code)
        rows[name] = sorted(map(tuple, q.result.table.tolist()))
    return rows["cpu"], rows["tpu"]


QUERIES = [f for f in sorted(glob.glob(f"{BASIC}/lubm_q*")) if os.path.isfile(f)]


@pytest.mark.parametrize("qfile", QUERIES, ids=[os.path.basename(f) for f in QUERIES])
def test_tpu_matches_cpu_basic_suite(engines, world, qfile):
    _, ss = world
    cpu_rows, tpu_rows = _both(engines, ss, open(qfile).read())
    assert cpu_rows == tpu_rows, (
        f"{os.path.basename(qfile)}: cpu {len(cpu_rows)} rows "
        f"vs tpu {len(tpu_rows)} rows")


OSDI_PLANS = sorted(glob.glob(f"{BASIC}/osdi16_plan/lubm_q*.fmt"))


@pytest.mark.parametrize("pfile", OSDI_PLANS,
                         ids=[os.path.basename(f) for f in OSDI_PLANS])
def test_tpu_matches_cpu_osdi_plans(engines, world, pfile):
    _, ss = world
    qname = os.path.basename(pfile)[:-4]
    cpu_rows, tpu_rows = _both(engines, ss, open(f"{BASIC}/{qname}").read(), pfile)
    assert cpu_rows == tpu_rows


def test_capacity_overflow_retry(world):
    """Force a tiny starting capacity so expansion must regrow mid-query."""
    from wukong_tpu.config import Global

    g, ss = world
    old = Global.table_capacity_min
    Global.table_capacity_min = 16
    try:
        tpu = TPUEngine(g, ss)
        tpu.cap_min = 16
        cpu = CPUEngine(g, ss)
        text = open(f"{BASIC}/lubm_q2").read()
        qc = Parser(ss).parse(text)
        heuristic_plan(qc)
        cpu.execute(qc)
        qt = Parser(ss).parse(text)
        heuristic_plan(qt)
        tpu.execute(qt)
        assert qt.result.nrows == qc.result.nrows
        assert sorted(map(tuple, qt.result.table.tolist())) == \
            sorted(map(tuple, qc.result.table.tolist()))
    finally:
        Global.table_capacity_min = old


def test_segment_cache_reuse_and_eviction(world):
    g, ss = world
    tpu = TPUEngine(g, ss, budget_bytes=1 << 20)
    text = open(f"{BASIC}/lubm_q4").read()
    for _ in range(2):
        q = Parser(ss).parse(text)
        heuristic_plan(q)
        tpu.execute(q)
        assert q.result.status_code == 0
    assert tpu.dstore.bytes_used <= (1 << 20) + 4 * (1 << 16)  # budget + slack


def test_stats_capacity_estimation_reduces_retries(world):
    """With planner stats, q2-style expansions should need no capacity retry."""
    from wukong_tpu.engine import tpu_kernels as K
    from wukong_tpu.loader.lubm import generate_lubm
    from wukong_tpu.planner.stats import Stats

    g, ss = world
    triples, _ = generate_lubm(1, seed=42)
    stats = Stats.generate(triples)
    calls = []
    orig = K.wk_walk_expand

    def counting_expand(*a, **k):
        calls.append(k.get("cap_out"))
        return orig(*a, **k)

    text = open(f"{BASIC}/lubm_q2").read()
    try:
        K.wk_walk_expand = counting_expand
        tpu = TPUEngine(g, ss, stats=stats)
        q = Parser(ss).parse(text)
        heuristic_plan(q)
        q.result.blind = True
        tpu.execute(q)
        with_stats = len(calls)
        calls.clear()
        tpu2 = TPUEngine(g, ss)  # no stats
        q2 = Parser(ss).parse(text)
        heuristic_plan(q2)
        q2.result.blind = True
        tpu2.execute(q2)
        without = len(calls)
    finally:
        K.wk_walk_expand = orig
    assert q.result.nrows == q2.result.nrows
    assert with_stats <= without  # stats never add retries


HEAVIES = [f"{BASIC}/lubm_q{k}" for k in (1, 2, 3, 7)]


@pytest.mark.parametrize("qfile", HEAVIES,
                         ids=[os.path.basename(f) for f in HEAVIES])
def test_batch_index_replicate_and_slice(engines, world, qfile):
    """Batched index-origin (heavy) execution: every replicated instance
    reproduces the single-query count; slices partition it."""
    cpu, tpu = engines
    _, ss = world
    text = open(qfile).read()

    q = Parser(ss).parse(text)
    heuristic_plan(q)
    cpu.execute(q)
    assert q.result.status_code == 0
    want = q.result.nrows

    B = 4
    qb = Parser(ss).parse(text)
    heuristic_plan(qb)
    qb.result.blind = True
    counts = tpu.execute_batch_index(qb, B)
    assert counts.shape == (B,)
    assert counts.tolist() == [want] * B

    qs = Parser(ss).parse(text)
    heuristic_plan(qs)
    qs.result.blind = True
    counts = tpu.execute_batch_index(qs, B, slice_mode=True)
    assert int(counts.sum()) == want


def test_suggest_index_batch(engines, world):
    _, tpu = engines
    _, ss = world
    q = Parser(ss).parse(open(f"{BASIC}/lubm_q2").read())
    heuristic_plan(q)
    b = tpu.suggest_index_batch(q)
    assert 1 <= b <= 1024


def test_prefetch_pipelining_stages_chain_segments(engines, world, monkeypatch):
    """gpu_enable_pipeline stages every chain segment before dispatch."""
    from wukong_tpu.config import Global
    from wukong_tpu.engine.tpu import TPUEngine

    g, ss = world
    monkeypatch.setattr(Global, "gpu_enable_pipeline", True)
    tpu = TPUEngine(g, ss)
    staged = []
    orig = tpu.dstore.prefetch
    monkeypatch.setattr(tpu.dstore, "prefetch",
                        lambda pats: (staged.append(1), orig(pats))[1])
    q = Parser(ss).parse(open(f"{BASIC}/lubm_q4").read())
    heuristic_plan(q)
    tpu.execute(q)
    assert q.result.status_code == 0 and staged

    monkeypatch.setattr(Global, "gpu_enable_pipeline", False)
    staged.clear()
    q = Parser(ss).parse(open(f"{BASIC}/lubm_q4").read())
    heuristic_plan(q)
    tpu.execute(q)
    assert q.result.status_code == 0 and not staged


def test_versatile_kuu_on_device(world):
    """VERSATILE known_unknown_unknown (?x ?p ?y, x bound) runs on the
    device chain via the combined-adjacency segment + expand2 — beyond the
    reference, whose GPU engine refuses every versatile shape
    (gpu_engine.hpp:267-333). Results must match the CPU kernels exactly."""
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.sparql.parser import Parser

    g, ss = world
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine

    cpu = CPUEngine(g, ss)
    tpu = TPUEngine(g, ss)
    text = """
    PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X ?P ?Y WHERE {
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X ?P ?Y .
    }"""

    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    assert qc.result.status_code == 0 and qc.result.nrows > 0

    qt = Parser(ss).parse(text)
    heuristic_plan(qt)
    tpu.execute(qt)
    assert qt.result.status_code == 0
    import numpy as np

    def rows(q):
        cols = [q.result.var2col(v) for v in q.result.required_vars]
        return sorted(map(tuple, np.asarray(q.result.table)[:, cols].tolist()))

    assert rows(qt) == rows(qc)
    # and the chain actually used the device path: the versatile combined
    # segment must be staged
    assert ("vpv", 1) in tpu.dstore._cache  # OUT direction

    # continuation after the versatile step (filter on the new value col)
    text2 = """
    PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
    PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X ?P ?Y WHERE {
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X ?P ?Y .
        ?Y rdf:type ub:Course .
    }"""
    qc2 = Parser(ss).parse(text2)
    heuristic_plan(qc2)
    cpu.execute(qc2)
    qt2 = Parser(ss).parse(text2)
    heuristic_plan(qt2)
    tpu.execute(qt2)
    assert qt2.result.status_code == 0
    assert rows(qt2) == rows(qc2)
    assert qc2.result.nrows > 0


def test_versatile_const_shapes_on_device(world):
    """The remaining VERSATILE shapes run on the device chain too:
    const_unknown_unknown / const_unknown_const start via a host CSR init
    (sparql.hpp:246-290), known_unknown_const mid-chain via expand2 + an
    equality fold on the value row (sparql.hpp:651-699). The reference GPU
    engine refuses all of these; ours must match the CPU kernels exactly."""
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import IN, OUT, TYPE_ID

    g, ss = world
    cpu = CPUEngine(g, ss)
    tpu = TPUEngine(g, ss)
    dept0 = ss.str2id("<http://www.Department0.University0.edu>")
    univ0 = ss.str2id("<http://www.University0.edu>")
    fp = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#FullProfessor>")

    def run(eng, pats, req):
        q = SPARQLQuery()
        q.result.nvars = len(req)
        q.pattern_group.patterns = [Pattern(*p) for p in pats]
        q.result.required_vars = list(req)
        eng.execute(q, from_proxy=False)
        assert q.result.status_code == 0, q.result.status_code
        cols = [q.result.var2col(v) for v in req]
        return sorted(map(tuple, np.asarray(q.result.table)[:, cols].tolist()))

    def cmp(pats, req, name):
        a = run(cpu, pats, req)
        b = run(tpu, pats, req)
        assert a == b, (name, len(a), len(b))
        assert len(a) > 0, (name, "vacuous: empty result")
        return a

    # const_unknown_unknown start: Dept0 ?P ?Y (full combined adjacency)
    cmp([(dept0, -9, OUT, -1)], [-9, -1], "c_u_u")
    # const_unknown_const: Dept0 ?P Univ0 (= subOrganizationOf)
    got = cmp([(dept0, -9, OUT, univ0)], [-9], "c_u_c")
    sub = ss.str2id(
        "<http://swat.cse.lehigh.edu/onto/univ-bench.owl#subOrganizationOf>")
    assert (sub,) in got
    # known_unknown_const mid-chain: FullProfessors with any edge to Univ0
    # (degreeFrom flavors) — type-index start keeps the k_u_c mid-chain
    cmp([(fp, TYPE_ID, IN, -1), (-1, -9, OUT, univ0)], [-1, -9], "k_u_c")
    # and a continuation AFTER the fold (normal expand on the filtered rows)
    works = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#worksFor>")
    cmp([(fp, TYPE_ID, IN, -1), (-1, -9, OUT, univ0),
         (-1, works, OUT, -2)], [-1, -9, -2], "k_u_c_then_expand")


def test_union_children_ride_device_chain(world):
    """Seeded UNION branches route back through the TPU engine: the branch
    plans anchor on inherited bindings (no whole-graph index start), the
    parent table uploads once, and the branch segments stage on device."""
    from wukong_tpu.planner.heuristic import heuristic_plan

    g, ss = world
    cpu = CPUEngine(g, ss)
    tpu = TPUEngine(g, ss)
    text = """PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X ?Y ?Z WHERE {
        ?X ub:memberOf ?Y .
        { ?X ub:undergraduateDegreeFrom ?Z . }
        UNION { ?X ub:mastersDegreeFrom ?Z . }
    }"""
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    qt = Parser(ss).parse(text)
    heuristic_plan(qt)
    # anchored branches plan as one k2u each, no index start prepended
    assert all(len(u.patterns) == 1 and u.patterns[0].subject == -1
               for u in qt.pattern_group.unions)
    tpu.execute(qt)
    assert qt.result.status_code == 0
    a = sorted(map(tuple, np.asarray(qc.result.table).tolist()))
    b = sorted(map(tuple, np.asarray(qt.result.table).tolist()))
    assert a == b and len(a) > 0
    ug = ss.str2id(
        "<http://swat.cse.lehigh.edu/onto/univ-bench.owl#undergraduateDegreeFrom>")
    ms = ss.str2id(
        "<http://swat.cse.lehigh.edu/onto/univ-bench.owl#mastersDegreeFrom>")
    staged = {k[:2] for k in tpu.dstore._cache if isinstance(k, tuple)}
    assert any(k[0] == ug for k in staged)  # branch BGPs ran on device
    assert any(k[0] == ms for k in staged)


def test_optional_leftjoin_on_device(world):
    """OPTIONAL groups sharing a bound var run as dedup-seeded device
    children + host left join (the shared formulation); the full reference
    optional suite, including the promoted-base q5, matches CPU."""
    import glob

    from wukong_tpu.planner.heuristic import heuristic_plan

    g, ss = world
    cpu = CPUEngine(g, ss)
    tpu = TPUEngine(g, ss)
    for qf in sorted(
            glob.glob("/root/reference/scripts/sparql_query/lubm/optional/q*")):
        if "fmt" in qf or "manual" in qf:
            continue
        text = open(qf).read()
        qc = Parser(ss).parse(text)
        heuristic_plan(qc)
        cpu.execute(qc)
        assert qc.result.status_code == 0, qf
        qt = Parser(ss).parse(text)
        heuristic_plan(qt)
        tpu.execute(qt)
        assert qt.result.status_code == 0, qf
        a = sorted(map(tuple, np.asarray(qc.result.table).tolist()))
        b = sorted(map(tuple, np.asarray(qt.result.table).tolist()))
        assert a == b and len(a) > 0, qf
    # the seeded child must actually stage its segment on device
    text = """PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?S ?UG WHERE {
        ?S ub:memberOf ?D .
        OPTIONAL { ?S ub:undergraduateDegreeFrom ?UG }
    }"""
    tpu2 = TPUEngine(g, ss)
    qt = Parser(ss).parse(text)
    heuristic_plan(qt)
    tpu2.execute(qt)
    assert qt.result.status_code == 0
    ug = ss.str2id(
        "<http://swat.cse.lehigh.edu/onto/univ-bench.owl#undergraduateDegreeFrom>")
    staged = {k[:2] for k in tpu2.dstore._cache if isinstance(k, tuple)}
    assert any(k[0] == ug for k in staged)
