"""Robustness sweep: every query in the reference's LUBM sub-suites.

The reference validates these suites manually against its console
(scripts/sparql_query/lubm/{union,optional,filter,order,dedup,attr,batch}).
Here every file must either execute cleanly (status SUCCESS) on our LUBM-1
world or fail with a *clean* WukongError (e.g. UNKNOWN_SUB for constants our
synthesized data doesn't contain) — never crash.
"""

import glob
import os

import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.sparql.parser import Parser
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.utils.errors import ErrorCode, WukongError

SUITES = "/root/reference/scripts/sparql_query/lubm"

FILES = sorted(
    f for suite in ("union", "optional", "filter", "order", "dedup", "attr")
    for f in glob.glob(f"{SUITES}/{suite}/*")
    if os.path.isfile(f) and not f.endswith(".md") and "README" not in f)


@pytest.fixture(scope="module")
def world():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    return g, ss


@pytest.mark.parametrize(
    "qfile", FILES,
    ids=[f"{os.path.basename(os.path.dirname(f))}-{os.path.basename(f)}"
         for f in FILES])
def test_suite_query_executes_or_fails_cleanly(world, qfile, monkeypatch):
    g, ss = world
    monkeypatch.setattr(Global, "enable_vattr", True)
    text = open(qfile).read()
    try:
        q = Parser(ss).parse(text)
    except WukongError as e:
        # constants absent from synthesized data / parser-rejected shapes
        assert e.code in (ErrorCode.UNKNOWN_SUB, ErrorCode.SYNTAX_ERROR), qfile
        return
    try:
        heuristic_plan(q)
    except WukongError as e:
        assert e.code == ErrorCode.UNKNOWN_PLAN, qfile
        return
    eng = CPUEngine(g, ss)
    eng.execute(q)
    # engine failures must be clean status codes, never raised exceptions
    assert isinstance(q.result.status_code, ErrorCode), qfile


@pytest.mark.skipif(not os.path.isdir(f"{SUITES}/union"),
                    reason="S1: the reference's suite is not in the tree")
def test_union_suite_counts(world):
    """union/q1: |Course ∪ University names| == |Course names| + |Univ names|."""
    g, ss = world
    text = open(f"{SUITES}/union/q1").read()
    q = Parser(ss).parse(text)
    heuristic_plan(q)
    CPUEngine(g, ss).execute(q)
    assert q.result.status_code == 0
    from wukong_tpu.loader.lubm import P, T
    from wukong_tpu.types import IN

    n_course = len(g.get_index(T["Course"], IN))
    n_univ_named = len(g.get_index(T["University"], IN))  # all have names
    assert q.result.nrows == n_course + n_univ_named
