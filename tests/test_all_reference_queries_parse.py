"""Grammar coverage: EVERY query file the reference ships must parse.

The reference's acceptance surface is its scripts/sparql_query tree (lubm,
watdiv, dbpsb, yago — SURVEY §4). The LUBM suites are executed elsewhere
against real data; the other datasets are external, so the contract this
file pins is the FRONTEND's: lexer + parser + IR translation accept every
query shape the reference accepts (chains with `;`/`,`, language-tagged
literals, %templates, full-IRI predicates, corun/mt extensions), with the
`wrong` suite staying rejected."""

import glob
import os

import pytest

from wukong_tpu.sparql.parser import Parser
from wukong_tpu.types import NORMAL_ID_START
from wukong_tpu.utils.errors import WukongError

ROOT = "/root/reference/scripts/sparql_query"

QUERY_FILES = sorted(
    f for pat in ("lubm/basic/lubm_q*", "lubm/union/q*", "lubm/optional/q*",
                  "lubm/filter/q*", "lubm/order/q*", "lubm/dedup/q*",
                  "lubm/attr/lubm_attr_q*", "lubm/batch/*",
                  "lubm/emulator/q*", "lubm/corun/q*",
                  "watdiv/watdiv_*", "watdiv/emulator/q*",
                  "dbpsb/dbpsb_q*", "yago/yago_q*")
    for f in glob.glob(f"{ROOT}/{pat}")
    if not f.endswith((".md", ".fmt")) and "plan" not in f)


class PermissiveStrings:
    """String server stub: every IRI/literal resolves (fresh ids), so parse
    coverage is about GRAMMAR, not about which dataset is loaded."""

    def __init__(self):
        self._ids: dict[str, int] = {}
        self.pid2type: dict[int, int] = {}  # no attr predicates

    def str2id(self, s: str) -> int:
        if s not in self._ids:
            # treat everything as a normal entity; type positions accept
            # normal ids in the translator
            self._ids[s] = NORMAL_ID_START + 10_000 + len(self._ids)
        return self._ids[s]

    def exist(self, s: str) -> bool:
        return True

    def exist_id(self, i: int) -> bool:
        return False

    def id2str(self, i: int) -> str:
        return f"<id{i}>"


def _is_query_text(text: str) -> bool:
    up = text.upper()
    return "SELECT" in up and "WHERE" in up


@pytest.mark.parametrize("qfile", QUERY_FILES,
                         ids=[f[len(ROOT) + 1:] for f in QUERY_FILES])
def test_reference_query_parses(qfile):
    text = open(qfile, errors="replace").read()
    if not _is_query_text(text):
        pytest.skip("not a SPARQL file (batch list / config)")
    ss = PermissiveStrings()
    p = Parser(ss)
    if "%" in text:
        t = p.parse_template(text)
        assert t.pos and t.query.pattern_group.patterns
    else:
        q = p.parse(text)
        assert (q.pattern_group.patterns or q.pattern_group.unions
                or q.pattern_group.optional)


@pytest.mark.skipif(not os.path.isdir(f"{ROOT}/lubm/wrong"),
                    reason="S1: the reference's suite is not in the tree")
def test_wrong_suite_still_rejected():
    """The `wrong` suite: q1-q4 are RUNTIME-wrong (unbound SELECT vars,
    bad regex, ...) and must parse; only `syntax` is a parse error — it
    must raise a clean WukongError, never crash or half-parse."""
    for qfile in sorted(glob.glob(f"{ROOT}/lubm/wrong/q*")):
        Parser(PermissiveStrings()).parse(
            open(qfile, errors="replace").read())
    with pytest.raises(WukongError):
        Parser(PermissiveStrings()).parse(
            open(f"{ROOT}/lubm/wrong/syntax", errors="replace").read())


def test_arrow_terminator_vs_negative_filter_literal():
    """'<-' is a pattern terminator ONLY at terminator position; inside a
    FILTER, '?y<-1' must still lex as '<' '-1' (a real regression once)."""
    ss = PermissiveStrings()
    q = Parser(ss).parse(
        "SELECT ?x ?y WHERE { ?x <http://p> ?y . FILTER(?y<-1) }")
    assert len(q.pattern_group.filters) == 1
    # and the terminators still parse (reference emulator q9 shape)
    q2 = Parser(ss).parse("""SELECT ?x ?y WHERE {
        ?y <http://p> ?x <-
        ?y <http://q> ?x ->
        ?y <http://r> ?x .
    }""")
    assert len(q2.pattern_group.patterns) == 3
