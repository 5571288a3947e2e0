"""CPU oracle engine vs independent BGP evaluation on LUBM-1.

Runs every basic LUBM query (the reference's acceptance suite,
scripts/sparql_query/lubm/basic) through parse -> plan -> execute and compares
the projected result multiset against the naive BGP oracle.
"""

import glob
import os

import numpy as np
import pytest

from bgp_oracle import TripleIndex, eval_bgp
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.planner.plan_file import set_plan
from wukong_tpu.sparql.parser import Parser
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.types import BLANK_ID

from wukong_tpu.utils.paths import LUBM_BASIC as BASIC


@pytest.fixture(scope="module")
def world():
    triples, lay = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    idx = TripleIndex(triples)
    return triples, g, ss, idx


def _run(world, text, plan_file=None):
    _, g, ss, idx = world
    q = Parser(ss).parse(text)
    raw_patterns = [(p.subject, p.predicate, p.object)
                    for p in q.pattern_group.patterns]
    if plan_file:
        assert set_plan(q.pattern_group, open(plan_file).read())
    else:
        heuristic_plan(q)
    eng = CPUEngine(g, ss)
    eng.execute(q)
    assert q.result.status_code == 0, q.result.status_code
    got = sorted(map(tuple, q.result.table.tolist()))
    want = sorted(eval_bgp(idx, raw_patterns, q.result.required_vars))
    return q, got, want


QUERIES = sorted(glob.glob(f"{BASIC}/lubm_q*"))
QUERIES = [f for f in QUERIES if os.path.isfile(f)]


@pytest.mark.parametrize("qfile", QUERIES, ids=[os.path.basename(f) for f in QUERIES])
def test_basic_suite_heuristic_plan(world, qfile):
    q, got, want = _run(world, open(qfile).read())
    assert got == want, f"{qfile}: {len(got)} vs {len(want)} rows"
    # q3 is empty even on real LUBM (docs/performance/S1C24-LUBM2560-20181203.md
    # Q3 #R=0); q10/q11 probe tiny constants that may not exist at LUBM-1
    name = os.path.basename(qfile)
    if name not in ("lubm_q3", "lubm_q10", "lubm_q11"):
        assert len(got) > 0, f"{name} unexpectedly empty"


OSDI_PLANS = sorted(glob.glob(f"{BASIC}/osdi16_plan/lubm_q*.fmt"))


@pytest.mark.parametrize("pfile", OSDI_PLANS,
                         ids=[os.path.basename(f) for f in OSDI_PLANS])
def test_basic_suite_osdi16_plans(world, pfile):
    qname = os.path.basename(pfile)[:-4]
    q, got, want = _run(world, open(f"{BASIC}/{qname}").read(), plan_file=pfile)
    assert got == want, f"{qname}: {len(got)} vs {len(want)} rows"


MANUAL_PLANS = [f for f in sorted(glob.glob(f"{BASIC}/manual_plan/lubm_q*.fmt"))
                if "q1_2" not in f]


@pytest.mark.parametrize("pfile", MANUAL_PLANS,
                         ids=[os.path.basename(f) for f in MANUAL_PLANS])
def test_basic_suite_manual_plans(world, pfile):
    qname = os.path.basename(pfile)[:-4]
    q, got, want = _run(world, open(f"{BASIC}/{qname}").read(), plan_file=pfile)
    assert got == want, f"{qname}: {len(got)} vs {len(want)} rows"


def test_union(world):
    triples, g, ss, idx = world
    text = """
    PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
    PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X WHERE {
        { ?X rdf:type ub:FullProfessor . } UNION { ?X rdf:type ub:Lecturer . }
    }"""
    q = Parser(ss).parse(text)
    for u in q.pattern_group.unions:
        pass
    heuristic_plan(q)
    eng = CPUEngine(g, ss)
    eng.execute(q)
    assert q.result.status_code == 0
    got = sorted(x[0] for x in q.result.table.tolist())
    fp = eval_bgp(idx, [(-1, 1, _t(ss, "FullProfessor"))], [-1])
    lec = eval_bgp(idx, [(-1, 1, _t(ss, "Lecturer"))], [-1])
    want = sorted([x[0] for x in fp] + [x[0] for x in lec])
    assert got == want


def _t(ss, name):
    return ss.str2id(f"<http://swat.cse.lehigh.edu/onto/univ-bench.owl#{name}>")


def _p(ss, name):
    return ss.str2id(f"<http://swat.cse.lehigh.edu/onto/univ-bench.owl#{name}>")


def test_optional(world):
    triples, g, ss, idx = world
    # every FullProfessor in Department0, optionally the department they head
    text = """
    PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
    PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X ?D WHERE {
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X rdf:type ub:FullProfessor .
        OPTIONAL { ?X ub:headOf ?D . }
    }"""
    q = Parser(ss).parse(text)
    heuristic_plan(q)
    eng = CPUEngine(g, ss)
    eng.execute(q)
    assert q.result.status_code == 0
    rows = q.result.table.tolist()
    # all FullProfessors of dept0 present exactly once (head count = 1)
    d0 = ss.str2id("<http://www.Department0.University0.edu>")
    profs = eval_bgp(idx, [(-1, _p(ss, "worksFor"), d0),
                           (-1, 1, _t(ss, "FullProfessor"))], [-1])
    assert len(rows) == len(profs)
    heads = [r for r in rows if r[1] != BLANK_ID]
    assert len(heads) == 1 and heads[0][1] == d0
    # non-heads carry BLANK_ID
    assert all(r[1] == BLANK_ID for r in rows if r[0] != heads[0][0])


def test_filter_regex_and_distinct(world):
    triples, g, ss, idx = world
    text = """
    PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
    PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT DISTINCT ?Y1 WHERE {
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X rdf:type ub:FullProfessor .
        ?X ub:name ?Y1 .
        FILTER regex(?Y1, "FullProfessor[0-3]")
    }"""
    q = Parser(ss).parse(text)
    heuristic_plan(q)
    eng = CPUEngine(g, ss)
    eng.execute(q)
    assert q.result.status_code == 0
    names = sorted(ss.id2str(int(r[0])) for r in q.result.table)
    assert names == ['"FullProfessor0"', '"FullProfessor1"',
                     '"FullProfessor2"', '"FullProfessor3"']


def test_order_limit_offset(world):
    triples, g, ss, idx = world
    text = """
    PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
    PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X ?N WHERE {
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X rdf:type ub:FullProfessor .
        ?X ub:name ?N .
    } ORDER BY ?N LIMIT 3 OFFSET 1"""
    q = Parser(ss).parse(text)
    heuristic_plan(q)
    eng = CPUEngine(g, ss)
    eng.execute(q)
    names = [ss.id2str(int(r[1])) for r in q.result.table]
    assert len(names) == 3
    assert names == sorted(names)
    assert names[0] == '"FullProfessor1"'  # offset skipped FullProfessor0


WRONG = "/root/reference/scripts/sparql_query/lubm/wrong"


@pytest.mark.skipif(not os.path.isdir(WRONG),
                    reason="S1: the reference's suite is not in the tree")
def test_wrong_suite_engine_errors(world):
    """Reference 'wrong' suite: q2 without a plan must fail with a plan error."""
    from wukong_tpu.utils.errors import ErrorCode, WukongError

    triples, g, ss, idx = world
    text = open(f"{WRONG}/q2").read()
    q = Parser(ss).parse(text)
    with pytest.raises(WukongError):
        heuristic_plan(q)


def test_corun(world):
    """CORUN: same kept rows as plain execution for a filter window, and
    EXISTS semantics for an expansion window (distinct main rows kept)."""
    from wukong_tpu.config import Global
    from wukong_tpu.sparql.ir import Pattern
    from wukong_tpu.types import IN

    triples, g, ss, idx = world
    eng = CPUEngine(g, ss)
    d0 = ss.str2id("<http://www.Department0.University0.edu>")
    memberOf = _p(ss, "memberOf")
    takes = _p(ss, "takesCourse")
    ug = _t(ss, "UndergraduateStudent")

    def run(pats, corun=None):
        from wukong_tpu.sparql.ir import SPARQLQuery

        q = SPARQLQuery()
        q.pattern_group.patterns = list(pats)
        q.result.nvars = 2
        q.result.required_vars = [-1]
        if corun:
            q.corun_enabled = True
            q.corun_step, q.fetch_step = corun
        old = Global.enable_corun
        Global.enable_corun = True
        try:
            eng.execute(q)
        finally:
            Global.enable_corun = old
        assert q.result.status_code == 0, q.result.status_code
        return sorted(map(tuple, q.result.table.tolist()))

    base = [Pattern(d0, memberOf, IN, -1), Pattern(-1, 1, 1, ug)]
    # filter-only window: identical rows
    assert run(base, corun=(1, 2)) == run(base)
    assert len(run(base)) > 0
    # expansion window: corun keeps each main row once (EXISTS semantics)
    pats2 = [Pattern(d0, memberOf, IN, -1), Pattern(-1, takes, 1, -2)]
    plain_distinct = sorted({r[0] for r in run(pats2)})
    corun_rows = run(pats2, corun=(1, 2))
    assert sorted(r[0] for r in corun_rows) == plain_distinct
