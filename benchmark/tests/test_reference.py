"""The plain reference against the row counts pinned at LUBM-40, seed 0
(``tests/test_golden_counts.py``'s GOLDEN_LUBM40, recorded from the CPU
oracle), and the bytes model on the same data."""
import os

import pytest

from benchmark.bytes_model import query_bytes
from benchmark.reference import Reference, ReferenceError_, parse_bgp

GOLDEN_LUBM40 = {"lubm_q1": 2587, "lubm_q2": 43172, "lubm_q4": 8,
                 "lubm_q5": 15, "lubm_q6": 208, "lubm_q7": 1217}
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BASIC = os.path.join(ROOT, "queries", "lubm", "basic")
CONSTANTS = ("<http://www.Department0.University0.edu>",
             "<http://www.University0.edu>")


@pytest.fixture(scope="module")
def ref40():
    from wukong_tpu.loader.lubm import (VirtualLubmStrings, generate_lubm,
                                        index_strings)

    triples, _ = generate_lubm(40, seed=0)
    names = VirtualLubmStrings(40, seed=0)  # the data's string table
    ref = Reference(triples, index_strings())
    ref.ids.update({iri: names.str2id(iri) for iri in CONSTANTS})
    return ref


@pytest.mark.parametrize("qn", sorted(GOLDEN_LUBM40))
def test_reference_counts_at_lubm40(ref40, qn):
    with open(os.path.join(BASIC, qn)) as f:
        text = f.read()
    rows = ref40.evaluate(text)
    assert len(rows) == GOLDEN_LUBM40[qn]
    assert rows.shape[1] == len(parse_bgp(text)[0])
    if qn == "lubm_q2":  # courses' names: 8 bytes an edge, 4 a course, rows
        names = ref40.edge_count(ref40.resolve(
            "<http://swat.cse.lehigh.edu/onto/univ-bench.owl#name>"))
        assert query_bytes(ref40, text, len(rows)) == \
            8 * names + 4 * len(rows) + 2 * len(rows) * 2 * 4


def test_reference_refuses_what_it_does_not_read(ref40):
    with pytest.raises(ReferenceError_):
        ref40.evaluate("SELECT ?X WHERE { ?X <http://nowhere/p> ?Y . }")
    with pytest.raises(ReferenceError_):
        parse_bgp("SELECT ?X WHERE { ?X ?P ?Y . OPTIONAL { ?X ?Q ?Z } }")
