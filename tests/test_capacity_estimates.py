"""Planner-driven chain capacity estimation (ROADMAP lever 2).

estimate_chain walks the joint-type-table model over an ALREADY-ORDERED plan
(the engine's execution order) and must track true intermediate sizes closely
enough that capacity classes stop over-provisioning (each 2x of slack doubles
every kernel's cost). The oracle here is the CPU engine's actual row counts.
"""

import numpy as np
import pytest

from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.planner.optimizer import Planner
from wukong_tpu.planner.stats import Stats
from wukong_tpu.sparql.parser import Parser

from wukong_tpu.utils.paths import LUBM_BASIC as BASIC


@pytest.fixture(scope="module")
def world():
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
    from wukong_tpu.store.gstore import build_partition

    triples, _ = generate_lubm(1, seed=0)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=0)
    stats = Stats.generate(triples)
    return g, ss, stats


def _true_step_rows(g, ss, q):
    """Actual row count after each pattern step, from the CPU oracle."""
    from wukong_tpu.engine.cpu import CPUEngine

    eng = CPUEngine(g, ss)
    rows = []
    while not q.done_patterns():
        eng._execute_one_pattern(q)
        rows.append(q.result.nrows)
    return rows


@pytest.mark.parametrize("qn", ["lubm_q1", "lubm_q2", "lubm_q4", "lubm_q7"])
def test_estimate_chain_tracks_true_rows(world, qn):
    g, ss, stats = world
    q = Parser(ss).parse(open(f"{BASIC}/{qn}").read())
    heuristic_plan(q)
    est = Planner(stats).estimate_chain(q.pattern_group.patterns)
    assert est is not None and len(est) == len(q.pattern_group.patterns)
    true_rows = _true_step_rows(g, ss, q)
    # each step's estimate must be within 8x of truth in both directions
    # (one capacity class of slack is 2x; 8x still saves >=2 classes vs the
    # old compounding-fanout estimates that overshot by 30x+)
    for k, (e, t) in enumerate(zip(est, true_rows)):
        if t == 0:
            continue  # empty intermediates: any small estimate is fine
        # over-provisioning is the perf-critical direction (capacity = cost);
        # underestimates only cost one overflow retry, so the lower bound is
        # a loose sanity check (LUBM-1's fine_type shares are noisy)
        assert e <= max(8 * t, 64), f"{qn} step {k}: est {e} >> true {t}"
        assert e >= t / 64, f"{qn} step {k}: est {e} << true {t}"


def test_estimate_chain_none_without_walkable_start(world):
    _, ss, stats = world
    q = Parser(ss).parse(open(f"{BASIC}/lubm_q4").read())
    heuristic_plan(q)
    pats = list(q.pattern_group.patterns)
    # drop the start pattern: the remaining chain anchors on an unbound var
    assert Planner(stats).estimate_chain(pats[1:]) is None
    assert Planner(stats).estimate_chain([]) is None


def test_tpu_engine_uses_estimates_and_stays_correct(world):
    """With estimates wired in, capacities shrink but results must not change
    (the overflow-retry net catches underestimates)."""
    jax = pytest.importorskip("jax")
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine

    g, ss, stats = world
    eng = TPUEngine(g, ss, stats=stats)
    ref = CPUEngine(g, ss)
    for qn in ["lubm_q1", "lubm_q4", "lubm_q7"]:
        q1 = Parser(ss).parse(open(f"{BASIC}/{qn}").read())
        heuristic_plan(q1)
        eng.execute(q1, from_proxy=False)
        q2 = Parser(ss).parse(open(f"{BASIC}/{qn}").read())
        heuristic_plan(q2)
        ref.execute(q2, from_proxy=False)
        assert q1.result.nrows == q2.result.nrows, qn
        a = np.asarray(q1.result.table)
        b = np.asarray(q2.result.table)
        assert a.shape == b.shape
        ra = set(map(tuple, a.tolist()))
        rb = set(map(tuple, b.tolist()))
        assert ra == rb, qn


def test_underestimate_triggers_retry_not_row_loss(world):
    """Force tiny estimates: compact_to/expand must overflow, retry, and
    still produce the full result set."""
    pytest.importorskip("jax")
    from wukong_tpu.engine.tpu import TPUEngine

    g, ss, stats = world
    eng = TPUEngine(g, ss, stats=stats)
    orig = eng._chain_estimates
    eng._chain_estimates = lambda pats: {k: 1.0 for k in range(len(pats))}
    try:
        q = Parser(ss).parse(open(f"{BASIC}/lubm_q1").read())
        heuristic_plan(q)
        eng.execute(q, from_proxy=False)
        assert q.result.status_code == 0
        n_forced = q.result.nrows
    finally:
        eng._chain_estimates = orig
    q2 = Parser(ss).parse(open(f"{BASIC}/lubm_q1").read())
    heuristic_plan(q2)
    eng2 = TPUEngine(g, ss, stats=stats)
    eng2.execute(q2, from_proxy=False)
    assert n_forced == q2.result.nrows


def test_suggest_index_batch_scales_with_estimates(world):
    """Accurate estimates must allow a reasonable heavy-query batch size."""
    pytest.importorskip("jax")
    from wukong_tpu.engine.tpu import TPUEngine

    g, ss, stats = world
    eng = TPUEngine(g, ss, stats=stats)
    q = Parser(ss).parse(open(f"{BASIC}/lubm_q1").read())
    heuristic_plan(q)
    b_est = eng.suggest_index_batch(q)
    assert b_est >= 1
    eng_nostats = TPUEngine(g, ss)
    q2 = Parser(ss).parse(open(f"{BASIC}/lubm_q1").read())
    heuristic_plan(q2)
    assert eng_nostats.suggest_index_batch(q2) >= 1
