"""Sort-merge batch executor (engine/tpu_merge.py) vs the v1 probe path and
the CPU oracle.

The merge path answers the same batched queries with gather-free kernels
(tpu_kernels.py merge_*); these tests pin exact per-instance counts across
all three executors on LUBM-1, plus the edge cases that differ structurally
from v1: deferred filter masks, capacity memoization, estimate-driven
compaction, and missing segments.
"""

import glob
import os

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.tpu import TPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.sparql.parser import Parser
from wukong_tpu.store.gstore import build_partition

from wukong_tpu.utils.paths import LUBM_BASIC as BASIC
# the benchmark set; q8+ (versatile / attr shapes) are host-path queries
QUERIES = [f"{BASIC}/lubm_q{k}" for k in range(1, 8)]


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


def _parse(ss, qfile):
    q = Parser(ss).parse(open(qfile).read())
    heuristic_plan(q)
    q.result.blind = True
    return q


@pytest.fixture
def merge_flag():
    old = Global.enable_merge_join
    yield
    Global.enable_merge_join = old


@pytest.mark.parametrize("qfile", QUERIES,
                         ids=[os.path.basename(f) for f in QUERIES])
def test_merge_matches_v1_and_oracle(engines, world, qfile, merge_flag):
    cpu, tpu = engines
    g, ss = world
    oracle = _parse(ss, qfile)
    oracle.result.blind = False
    cpu.execute(oracle)
    assert oracle.result.status_code == 0
    want = oracle.result.nrows

    q = _parse(ss, qfile)
    index_start = q.start_from_index()
    B = 3
    per_mode = {}
    for flag in (True, False):
        Global.enable_merge_join = flag
        qx = _parse(ss, qfile)
        if index_start:
            counts = tpu.execute_batch_index(qx, B)
        else:
            const = qx.pattern_group.patterns[0].subject
            counts = tpu.execute_batch(
                qx, np.full(B, const, dtype=np.int64))
        per_mode[flag] = counts.tolist()
    assert per_mode[True] == per_mode[False] == [want] * B

    if index_start:  # slice mode partitions the same total
        Global.enable_merge_join = True
        qs = _parse(ss, qfile)
        counts = tpu.execute_batch_index(qs, B, slice_mode=True)
        assert int(counts.sum()) == want


def test_capacity_memo_learns_and_reuses(engines, world):
    """Second run of the same (query, B) starts from learned exact caps —
    no overflow retry, same counts."""
    _, tpu = engines
    _, ss = world
    q = _parse(ss, f"{BASIC}/lubm_q7")
    c1 = tpu.execute_batch_index(q, 2)
    key = tpu.merge._key(q.pattern_group.patterns, 2, "rep")
    assert key in tpu.merge._cap_memo
    memo = dict(tpu.merge._cap_memo[key])
    q2 = _parse(ss, f"{BASIC}/lubm_q7")
    c2 = tpu.execute_batch_index(q2, 2)
    assert c1.tolist() == c2.tolist()
    assert tpu.merge._cap_memo[key] == memo


def test_merge_missing_segment_yields_zero(engines, world):
    """An expansion over a predicate with no segment produces 0 rows per
    instance (not an error)."""
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import IN, OUT, TYPE_ID

    _, tpu = engines
    g, _ = world
    # University members exist; predicate id 999 has no segment
    q = SPARQLQuery()
    q.pattern_group.patterns = [Pattern(17, TYPE_ID, IN, -1),
                                Pattern(-1, 999, OUT, -2)]
    q.result.nvars = 2
    q.result.required_vars = [-1, -2]
    q.result.blind = True
    counts = tpu.execute_batch_index(q, 2)
    assert counts.tolist() == [0, 0]


def test_run_batch_const_many_pipelines(engines, world):
    """K in-flight const batches, one sync: counts match the sequential
    path, including when a batch in the window overflows (slow-path redo)."""
    _, tpu = engines
    g, ss = world
    q = _parse(ss, f"{BASIC}/lubm_q4")
    const = q.pattern_group.patterns[0].subject
    consts = np.full(5, const, dtype=np.int64)
    want = tpu.execute_batch(q, consts).tolist()
    many = tpu.merge.run_batch_const_many(q, [consts] * 3)
    assert [m.tolist() for m in many] == [want] * 3

    # cold memo: the window must still return exact counts via the redo path
    tpu.merge._cap_memo.clear()
    many = tpu.merge.run_batch_const_many(q, [consts] * 2)
    assert [m.tolist() for m in many] == [want] * 2


def test_const_list_matches_contains_many_all_routes(world):
    """const_list (the k2c merge relation) must agree with the CPU oracle's
    _contains_many on every routing branch — type OUT/IN, versatile
    PREDICATE_ID both directions, and normal segments both directions."""
    from wukong_tpu.types import IN, OUT, PREDICATE_ID, TYPE_ID

    g, ss = world
    cpu = CPUEngine(g, ss)
    tpu = TPUEngine(g, ss)
    ids = np.unique(np.concatenate(
        [s.keys[:50] for s in list(g.segments.values())[:6]]))
    cases = [(TYPE_ID, OUT, 17), (TYPE_ID, IN, int(ids[0])),
             (PREDICATE_ID, OUT, 7), (PREDICATE_ID, IN, 7),
             (7, OUT, int(g.segments[(7, IN)].keys[0])),
             (7, IN, int(g.segments[(7, OUT)].keys[0]))]
    for pid, d, const in cases:
        oracle = cpu._contains_many(
            ids, pid, d, np.full(len(ids), const, dtype=np.int64))
        lst, real = tpu.dstore.const_list(pid, d, const)
        got = np.isin(ids, np.asarray(lst)[:real])
        assert got.tolist() == oracle.tolist(), (pid, d, const)


def test_merge_forced_compaction_matches(engines, world, monkeypatch):
    """Filter steps that trigger the estimate-driven compact branch keep
    exact counts (root-level and mid-chain rebasing)."""
    _, tpu = engines
    _, ss = world
    q = _parse(ss, f"{BASIC}/lubm_q1")
    want = tpu.execute_batch_index(q, 2).tolist()
    # force every membership step to compact into a tiny class, then let the
    # overflow-retry loop discover the exact capacities
    monkeypatch.setattr(
        TPUEngine, "_chain_estimates",
        lambda self, pats: {k: 1.0 for k in range(len(pats))})
    tpu.merge._cap_memo.clear()
    q2 = _parse(ss, f"{BASIC}/lubm_q1")
    got = tpu.execute_batch_index(q2, 2).tolist()
    assert got == want


@pytest.mark.parametrize("qfile", QUERIES,
                         ids=[os.path.basename(f) for f in QUERIES])
def test_stream_expand_in_executor(engines, world, qfile, monkeypatch):
    """Force the Pallas streaming expand (interpret mode) through the whole
    merge executor: counts must match the oracle for every benchmark query.
    Slice mode keeps step-1 anchors distinct (pure stream arm); replicate
    mode duplicates them uniformly B times (B <= MDUP exercises the m-hot
    arm, beyond it the in-cond XLA fallback)."""
    from wukong_tpu.engine import tpu_stream

    cpu, tpu = engines
    g, ss = world
    monkeypatch.setattr(tpu_stream, "FORCE_INTERPRET", True)
    # density gate off so even sparse expands take the kernel
    monkeypatch.setattr(tpu_stream, "want_stream",
                        lambda est, ne, cap: cap % tpu_stream.TILE == 0)

    oracle = _parse(ss, qfile)
    oracle.result.blind = False
    cpu.execute(oracle)
    want = oracle.result.nrows

    q = _parse(ss, qfile)
    Global.enable_merge_join = True
    if q.start_from_index():
        counts = tpu.execute_batch_index(q, 2, slice_mode=True)
        assert int(counts.sum()) == want
        from wukong_tpu.engine.tpu_stream import MDUP

        q2 = _parse(ss, qfile)
        counts = tpu.execute_batch_index(q2, MDUP)  # m-hot at the exact cap
        assert counts.tolist() == [want] * MDUP
        q3 = _parse(ss, qfile)
        counts = tpu.execute_batch_index(q3, MDUP + 2)  # beyond: XLA arm
        assert counts.tolist() == [want] * (MDUP + 2)
    else:
        const = q.pattern_group.patterns[0].subject
        counts = tpu.execute_batch(q, np.full(2, const, dtype=np.int64))
        assert counts.tolist() == [want] * 2


def test_run_batch_index_many_matches_single(engines, world):
    """K windowed replicate heavy batches == K independent run_batch_index."""
    g, ss = world
    cpu, tpu = engines
    q = Parser(ss).parse(open(f"{BASIC}/lubm_q7").read())
    heuristic_plan(q)
    single = tpu.merge.run_batch_index(q, 4, False)
    many = tpu.execute_batch_index_many(q, 4, 3)
    assert len(many) == 3
    for counts in many:
        assert np.array_equal(np.asarray(counts), np.asarray(single))


def test_bytes_model_roofline(engines, world, monkeypatch):
    """The host-side HBM-traffic model (bench roofline fields): after a run,
    bytes_model reports the staged segment sizes actually in the device
    cache plus a capacity-driven table-state term, and scales its table term
    with B (capacity classes are per-batch). The lookup dispatch is pinned
    to the merge arm so the segment term's B-invariance assertion holds
    (the backend-aware factor can legitimately flip arms between capacity
    classes, changing what the model counts as streamed)."""
    from wukong_tpu.engine.tpu_merge import MergeExecutor

    monkeypatch.setattr(MergeExecutor, "PROBE_LOOKUP_FACTOR", 1 << 60)
    _, tpu = engines
    _, ss = world
    tpu.merge._cap_memo.clear()  # memoized caps were learned on other arms
    q = _parse(ss, f"{BASIC}/lubm_q7")
    tpu.execute_batch_index(q, 2)
    bm = tpu.merge.bytes_model(q, 2, "rep")
    assert bm is not None
    assert bm["total_bytes"] == bm["segment_bytes"] + bm["table_bytes"]
    assert bm["segment_bytes"] > 0 and bm["table_bytes"] > 0
    # segment term counts what the kernels READ (expand skips ekey, k2k
    # skips the key arrays), so it is bounded above by the staged bytes of
    # the chain's pinned segments — all still cache-resident after the run
    folds = tpu.merge._plan_folds(q.pattern_group.patterns, index_mode=True)
    staged = 0
    for key in tpu.merge._chain_pins(q.pattern_group.patterns, folds,
                                     index_mode=True):
        seg = tpu.dstore._cache.get(key)
        if seg is not None:
            staged += seg.nbytes
        ent = tpu.dstore._index_cache.get(key)
        if ent is not None:
            staged += int(ent[0].size) * 4
    # + the init index list (idx key, not a chain pin)
    p0 = q.pattern_group.patterns[0]
    ent = tpu.dstore._index_cache.get(
        ("idx", int(p0.subject), int(p0.direction)))
    if ent is not None:
        staged += int(ent[0].size) * 4
    assert 0 < bm["segment_bytes"] <= staged
    # B-scaling: the table term grows with the batch, segments do not
    q2 = _parse(ss, f"{BASIC}/lubm_q7")
    tpu.execute_batch_index(q2, 4)
    bm4 = tpu.merge.bytes_model(q2, 4, "rep")
    assert bm4["table_bytes"] > bm["table_bytes"]
    assert bm4["segment_bytes"] == bm["segment_bytes"]
    # out-of-scope chains (versatile predicates) return None
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import OUT

    qv = SPARQLQuery()
    qv.pattern_group.patterns = [Pattern(17, -3, OUT, -1)]
    qv.result.nvars = 1
    assert tpu.merge.bytes_model(qv, 2, "rep") is None


@pytest.mark.parametrize("qfile", QUERIES,
                         ids=[os.path.basename(f) for f in QUERIES])
def test_probe_lookup_path_matches(world, qfile, monkeypatch):
    """Force the probe-lookup arm for EVERY expand (factor 0: any segment
    'wins') and pin count equality with the CPU oracle — the sort-vs-probe
    dispatch must be invisible to results. A fresh engine avoids cap-memo
    crosstalk with the suite's shared engine; pins are checked to stage the
    BUCKET forms the probe path actually reads."""
    from wukong_tpu.engine.tpu_merge import MergeExecutor

    g, ss = world
    cpu = CPUEngine(g, ss)
    tpu = TPUEngine(g, ss)
    monkeypatch.setattr(MergeExecutor, "PROBE_LOOKUP_FACTOR", 0)

    oracle = _parse(ss, qfile)
    oracle.result.blind = False
    cpu.execute(oracle)
    want = oracle.result.nrows

    q = _parse(ss, qfile)
    B = 3
    if q.start_from_index():
        counts = tpu.execute_batch_index(q, B)
        mode = "rep"
    else:
        counts = tpu.execute_batch(
            q, np.full(B, q.pattern_group.patterns[0].subject,
                       dtype=np.int64))
        mode = "const"
    assert counts.tolist() == [want] * B
    # pins include the bucket forms ((pid, d) / ("segf", ...)) for every
    # expand; with probing forced, exactly those are what the run staged
    pats = q.pattern_group.patterns
    index_mode = mode == "rep"
    folds = tpu.merge._plan_folds(pats, index_mode=index_mode)
    pins = tpu.merge._chain_pins(pats, folds, index_mode=index_mode)
    expand_pins = [k for k in pins
                   if not (isinstance(k[0], str)
                           and k[0] in ("mrg", "mrgf", "rev"))]
    assert expand_pins, "no bucket-form pins for a chain with expands"
    for k in expand_pins:
        assert k in tpu.dstore._cache, f"pin {k} not staged by the run"
    # and the traffic model prices the probe path (no full-segment stream)
    bm = tpu.merge.bytes_model(q, B, mode)
    assert bm is not None and bm["total_bytes"] > 0


def test_run_batch_const_mixed_cross_class(engines, world):
    """ONE flight spanning DIFFERENT templates (the emulator's cross-class
    window): counts must match the per-class sequential path, including
    when a job in the flight overflows (slow-path redo) and when a
    planner-empty or merge-unsupported job is mixed in via the engine
    wrapper."""
    _, tpu = engines
    g, ss = world
    jobs = []
    want = []
    for qn in ("lubm_q4", "lubm_q5", "lubm_q6"):
        q = _parse(ss, f"{BASIC}/{qn}")
        const = q.pattern_group.patterns[0].subject
        consts = np.full(4, const, dtype=np.int64)
        want.append(tpu.execute_batch(q, consts).tolist())  # learns caps
        jobs.append((q, consts))
    got = tpu.merge.run_batch_const_mixed(jobs)
    assert [r.tolist() for r in got] == want
    # cold-memo flight: redo path must still produce exact counts
    tpu.merge._cap_memo.clear()
    got = tpu.merge.run_batch_const_mixed(jobs)
    assert [r.tolist() for r in got] == want
    # engine wrapper: same jobs through execute_batch_mixed
    got = tpu.execute_batch_mixed(jobs)
    assert [r.tolist() for r in got] == want


@pytest.mark.parametrize("seed", range(8))
def test_probe_vs_merge_arm_fuzz(seed, monkeypatch):
    """Differential fuzz of the lookup-dispatch arms on random worlds:
    the SAME random chain through (a) every expand/member forced onto the
    probe/binary-search arms and (b) every step forced onto the sort-merge
    arms must agree with each other AND with the independent BGP oracle.
    Random shapes cover expand-expand, expand-k2c, and k2k back-edges
    (LUBM's fixed shapes never vary the dispatch boundary)."""
    from tests.bgp_oracle import TripleIndex, eval_bgp
    from wukong_tpu.engine.tpu_merge import MergeExecutor
    from wukong_tpu.loader.generic_rdf import generate_generic
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import IN, OUT, TYPE_ID

    rng = np.random.default_rng(4200 + seed)
    triples, meta = generate_generic(4000, n_preds=6, n_types=3,
                                     seed=100 + seed)
    g = build_partition(triples, 0, 1)
    pids = [int(p) for p in np.unique(triples[:, 1]) if p != TYPE_ID]
    types = sorted(g.type_ids)
    tid = int(rng.choice(types))
    p1, p2 = (int(x) for x in rng.choice(pids, 2, replace=False))
    d1, d2 = int(rng.integers(2)), int(rng.integers(2))
    shape = int(rng.integers(3))
    pats = [Pattern(tid, TYPE_ID, IN, -1), Pattern(-1, p1, d1, -2)]
    if shape == 0:
        pats.append(Pattern(-2, p2, d2, -3))
        nv = 3
    elif shape == 1:  # k2c on the root var: a real const filter
        seg = g.segments.get((p2, OUT))
        const = (int(np.asarray(seg.edges)[rng.integers(seg.num_edges)])
                 if seg is not None and seg.num_edges else int(types[0]))
        pats.append(Pattern(-1, p2, OUT, const))
        nv = 2
    else:  # k2k back-edge
        pats.append(Pattern(-2, p2, d2, -1))
        nv = 2

    def mk():
        q = SPARQLQuery()
        q.pattern_group.patterns = [Pattern(p.subject, p.predicate,
                                            p.direction, p.object)
                                    for p in pats]
        q.result.nvars = nv
        q.result.required_vars = [-(i + 1) for i in range(nv)]
        q.result.blind = True
        return q

    B = 3
    got = {}
    for name, factor in (("probe", 0), ("merge", 1 << 60)):
        monkeypatch.setattr(MergeExecutor, "PROBE_LOOKUP_FACTOR", factor)
        eng = TPUEngine(g, None)
        got[name] = eng.execute_batch_index(mk(), B).tolist()
    assert got["probe"] == got["merge"], (seed, shape, got)

    # ground truth: the independent nested-loop oracle over raw triples
    def raw(p):
        if p.predicate == TYPE_ID and int(p.direction) == IN:
            return (p.object, TYPE_ID, p.subject)
        if int(p.direction) == OUT:
            return (p.subject, p.predicate, p.object)
        return (p.object, p.predicate, p.subject)

    idx = TripleIndex(triples)
    want = len(eval_bgp(idx, [raw(p) for p in pats],
                        [-(i + 1) for i in range(nv)]))
    assert got["probe"] == [want] * B, (seed, shape, want, got["probe"])
