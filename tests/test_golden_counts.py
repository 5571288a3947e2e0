"""Pinned golden result counts at LUBM-40 (docs/performance `#R` methodology).

The reference's per-commit perf reports record expected result counts per
query (e.g. docs/performance/S1C24-LUBM2560-20181203.md `#R` columns) — the
de-facto regression harness. These counts were recorded ONCE from the CPU
oracle at LUBM-40 (synthesizer DATASET_VERSION=2, seed=0) and pinned, so an
engine regression surfaces even where the nested-loop-join oracle (used at
LUBM-1) would be too slow to run.
"""

import pytest

from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.tpu import TPUEngine
from wukong_tpu.loader.lubm import DATASET_VERSION, VirtualLubmStrings, generate_lubm
from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.sparql.parser import Parser
from wukong_tpu.store.gstore import build_partition

from wukong_tpu.utils.paths import LUBM_BASIC as BASIC

# (query, rows) at LUBM-40 seed=0 — recorded from the CPU oracle, v2 dataset
GOLDEN_LUBM40 = {
    "lubm_q1": 2587,
    "lubm_q2": 43172,
    "lubm_q3": 0,
    "lubm_q4": 8,
    "lubm_q5": 15,
    "lubm_q6": 208,
    "lubm_q7": 1217,
}


@pytest.fixture(scope="module")
def world40():
    assert DATASET_VERSION == 2, "re-record GOLDEN_LUBM40 for the new dataset"
    triples, _ = generate_lubm(40, seed=0)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(40, seed=0)
    return g, ss


@pytest.mark.parametrize("qn", sorted(GOLDEN_LUBM40))
def test_golden_counts_cpu(world40, qn):
    g, ss = world40
    q = Parser(ss).parse(open(f"{BASIC}/{qn}").read())
    heuristic_plan(q)
    q.result.blind = True
    CPUEngine(g, ss).execute(q)
    assert q.result.status_code == 0
    assert q.result.nrows == GOLDEN_LUBM40[qn]


@pytest.fixture(scope="module")
def tpu40(world40):
    g, ss = world40
    return TPUEngine(g, ss)


@pytest.mark.parametrize("qn", sorted(GOLDEN_LUBM40))
def test_golden_counts_tpu(world40, tpu40, qn):
    g, ss = world40
    q = Parser(ss).parse(open(f"{BASIC}/{qn}").read())
    heuristic_plan(q)
    q.result.blind = True
    tpu40.execute(q)
    assert q.result.status_code == 0
    assert q.result.nrows == GOLDEN_LUBM40[qn]


def test_golden_counts_batched_heavy(world40, tpu40):
    """The batched index chain reproduces the pinned count per instance."""
    g, ss = world40
    q = Parser(ss).parse(open(f"{BASIC}/lubm_q7").read())
    heuristic_plan(q)
    q.result.blind = True
    counts = tpu40.execute_batch_index(q, 2)
    assert counts.tolist() == [GOLDEN_LUBM40["lubm_q7"]] * 2
