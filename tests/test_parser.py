import glob
import os

import pytest

from wukong_tpu.loader.lubm import P, T, VirtualLubmStrings
from wukong_tpu.sparql.ir import FilterType
from wukong_tpu.sparql.parser import Parser, SPARQLSyntaxError
from wukong_tpu.types import OUT, PREDICATE_ID, TYPE_ID
from wukong_tpu.utils.errors import ErrorCode, WukongError
from wukong_tpu.utils.paths import LUBM_BASIC

WRONG = "/root/reference/scripts/sparql_query/lubm/wrong"

LUBM_Q4 = """
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
SELECT ?X ?Y1 ?Y2 ?Y3 WHERE {
    ?X  ub:worksFor  <http://www.Department0.University0.edu>  .
    ?X  rdf:type  ub:FullProfessor  .
    ?X  ub:name  ?Y1  .
    ?X  ub:emailAddress  ?Y2  .
    ?X  ub:telephone  ?Y3  .
}
"""


@pytest.fixture(scope="module")
def ss():
    return VirtualLubmStrings(1, seed=42)


@pytest.fixture(scope="module")
def parser(ss):
    return Parser(ss)


def test_parse_q4(parser, ss):
    q = parser.parse(LUBM_Q4)
    pats = q.pattern_group.patterns
    assert len(pats) == 5
    d0 = ss.str2id("<http://www.Department0.University0.edu>")
    assert pats[0].subject == -1 and pats[0].predicate == P["worksFor"]
    assert pats[0].object == d0 and pats[0].direction == OUT
    assert pats[1].predicate == TYPE_ID and pats[1].object == T["FullProfessor"]
    assert q.result.required_vars == [-1, -2, -3, -4]
    assert q.result.nvars == 4


def test_parse_all_reference_lubm_queries(ss):
    """Every basic LUBM query in the tree parses."""
    files = sorted(glob.glob(f"{LUBM_BASIC}/lubm_q*"))
    files = [f for f in files if "plan" not in f]
    assert files
    for f in files:
        p = Parser(ss)
        q = p.parse(open(f).read())
        assert q.pattern_group.patterns


def test_variable_predicate(parser):
    q = Parser(parser.str_server).parse(
        "SELECT ?X ?P WHERE { ?X ?P <http://www.Department0.University0.edu> . }")
    pat = q.pattern_group.patterns[0]
    assert pat.predicate < 0  # variable predicate


def test_predicate_keyword(ss):
    q = Parser(ss).parse(
        "PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>\n"
        "SELECT ?X WHERE { ?X __PREDICATE__ ub:subOrganizationOf . }")
    pat = q.pattern_group.patterns[0]
    assert pat.predicate == PREDICATE_ID
    assert pat.object == P["subOrganizationOf"]


def test_union_optional_filter(ss):
    q = Parser(ss).parse("""
        PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
        PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
        SELECT DISTINCT ?X WHERE {
            { ?X rdf:type ub:Course . } UNION { ?X rdf:type ub:GraduateCourse . }
            OPTIONAL { ?X ub:name ?N . }
            FILTER ( bound(?N) && ?X != ?N )
        } ORDER BY DESC(?X) LIMIT 10 OFFSET 2
        """)
    assert len(q.pattern_group.unions) == 2
    assert len(q.pattern_group.optional) == 1
    assert len(q.pattern_group.filters) == 1
    f = q.pattern_group.filters[0]
    assert f.type == FilterType.And
    assert f.arg1.type == FilterType.Builtin_bound
    assert q.distinct and q.limit == 10 and q.offset == 2
    assert q.orders[0].descending


def test_template_placeholder(ss):
    tmpl = Parser(ss).parse_template("""
        PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
        PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
        SELECT ?X WHERE {
            ?X ub:takesCourse %ub:GraduateCourse .
            ?X rdf:type ub:GraduateStudent .
        }""")
    assert tmpl.ptypes == [T["GraduateCourse"]]
    assert tmpl.pos == [(0, "object")]
    import numpy as np

    tmpl.candidates = [np.array([12345, 67890])]
    q = tmpl.instantiate(np.random.default_rng(0))
    assert q.pattern_group.patterns[0].object in (12345, 67890)


def test_syntax_errors(ss):
    with pytest.raises(SPARQLSyntaxError):
        Parser(ss).parse("SELECT WHERE { }")
    with pytest.raises(SPARQLSyntaxError):
        Parser(ss).parse("SELECT ?X WHERE { ?X }")
    with pytest.raises(WukongError) as e:
        Parser(ss).parse("SELECT ?X WHERE { ?X <http://unknown.pred> ?Y . }")
    assert e.value.code == ErrorCode.UNKNOWN_SUB


@pytest.mark.skipif(not os.path.isdir(WRONG),
                    reason="S1: the reference's suite is not in the tree")
def test_wrong_suite_parse_behavior(ss):
    """The reference 'wrong' suite: only `syntax` fails at parse time; q1-q4
    parse fine and fail later at plan/execution (wrong/README.md)."""
    with pytest.raises(SPARQLSyntaxError):
        Parser(ss).parse(open(f"{WRONG}/syntax").read())
    for name in ("q1", "q2", "q3", "q4"):
        q = Parser(ss).parse(open(f"{WRONG}/{name}").read())
        assert q.pattern_group.patterns
