"""Distributed engine vs CPU oracle on an 8-way partitioned LUBM-1 (CPU mesh)."""

import glob
import os

import numpy as np
import pytest

from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.parallel.dist_engine import DistEngine
from wukong_tpu.parallel.mesh import make_mesh
from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.sparql.parser import Parser
from wukong_tpu.store.gstore import build_all_partitions, build_partition

from wukong_tpu.utils.paths import LUBM_BASIC as BASIC

# BGP-only, const-predicate queries (the distributed v1 support matrix —
# same scope as the reference's GPU engine)
DIST_QUERIES = ["lubm_q1", "lubm_q2", "lubm_q3", "lubm_q4", "lubm_q5",
                "lubm_q6", "lubm_q7", "lubm_q12"]

OPTIONAL_DIR = "/root/reference/scripts/sparql_query/lubm/optional"
UNION_DIR = "/root/reference/scripts/sparql_query/lubm/union"
ATTR_DIR = "/root/reference/scripts/sparql_query/lubm/attr"


def _needs(path):
    """Skip where a query file or suite of the reference is not in the tree."""
    return pytest.mark.skipif(
        not os.path.exists(path),
        reason="S1: the reference's suite is not in the tree")


@pytest.fixture(autouse=True)
def _pin_collective_route():
    """At LUBM-1 every const-start chain is light, so the default in-place
    routing would answer most of this module without touching the
    collective machinery it validates. Pin the sharded route; the
    test_inplace_* cases flip the flag back on explicitly."""
    from wukong_tpu.config import Global

    old = Global.enable_dist_inplace
    Global.enable_dist_inplace = False
    yield
    Global.enable_dist_inplace = old


@pytest.fixture(scope="module")
def world(eight_cpu_devices):
    triples, _ = generate_lubm(1, seed=42)
    ss = VirtualLubmStrings(1, seed=42)
    g1 = build_partition(triples, 0, 1)
    stores = build_all_partitions(triples, 8)
    mesh = make_mesh(8)
    dist = DistEngine(stores, ss, mesh)
    cpu = CPUEngine(g1, ss)
    return ss, cpu, dist


@pytest.mark.parametrize(
    "qn", [pytest.param(qn, marks=_needs(f"{BASIC}/{qn}"))
           for qn in DIST_QUERIES])
def test_dist_matches_cpu(world, qn):
    ss, cpu, dist = world
    text = open(f"{BASIC}/{qn}").read()
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    dist.execute(qd)
    assert qd.result.status_code == 0, (qn, qd.result.status_code)
    # compare row multisets over the shared bound variables (the dist engine
    # now projects via the host final phase; the raw-variable comparison below
    # still validates the full binding set)
    qc2 = Parser(ss).parse(text)
    heuristic_plan(qc2)
    cpu.execute(qc2, from_proxy=False)
    cols_c2 = [qc2.result.v2c_map[v] for v in sorted(qd.result.v2c_map)]
    want = sorted(map(tuple, qc2.result.table[:, cols_c2].tolist()))
    cols_d = [qd.result.v2c_map[v] for v in sorted(qd.result.v2c_map)]
    got = sorted(map(tuple, qd.result.table[:, cols_d].tolist()))
    assert got == want, f"{qn}: dist {len(got)} vs cpu {len(want)} rows"


def test_dist_blind_counts(world):
    ss, cpu, dist = world
    text = open(f"{BASIC}/lubm_q2").read()
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc, from_proxy=False)
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    qd.result.blind = True
    dist.execute(qd)
    assert qd.result.status_code == 0
    assert qd.result.nrows == qc.result.nrows


def test_dist_versatile_const_start(world):
    """?X ?P <const> flips to a versatile const start (owner-partition CSR
    walk) and must match the CPU engine; bound-object versatile stays
    rejected (CPU parity — no such reference kernel)."""
    ss, cpu, dist = world
    text = ("SELECT ?X ?P WHERE "
            "{ ?X ?P <http://www.Department0.University0.edu> . }")
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    assert qc.result.status_code == 0 and qc.result.nrows > 0
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    dist.execute(qd)
    assert qd.result.status_code == 0
    assert _rows_of(qd.result) == _rows_of(qc.result)

    # bound-object versatile (?x ?p ?y, BOTH bound): unsupported everywhere
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import IN, OUT, PREDICATE_ID

    works = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#worksFor>")
    q = SPARQLQuery()
    q.result.nvars = 3
    q.pattern_group.patterns = [
        Pattern(works, PREDICATE_ID, IN, -1),
        Pattern(-1, works, OUT, -2),
        Pattern(-1, -3, OUT, -2),
    ]
    q.result.required_vars = [-1, -2, -3]
    dist.execute(q)
    assert q.result.status_code != 0


def test_dist_capacity_retry(world, monkeypatch):
    """Tiny capacity classes force exchange + expansion overflow retries."""
    from wukong_tpu.config import Global

    ss, cpu, dist = world
    monkeypatch.setattr(dist, "cap_min", 32)
    dist._fn_cache.clear()
    text = open(f"{BASIC}/lubm_q2").read()
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc, from_proxy=False)
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    qd.result.blind = True
    dist.execute(qd)
    assert qd.result.status_code == 0
    assert qd.result.nrows == qc.result.nrows


def test_dist_larger_scale_deep_chain(world, eight_cpu_devices):
    """LUBM-2 across 8 shards: deeper chains, multiple exchanges, real skew."""
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
    from wukong_tpu.store.gstore import build_all_partitions, build_partition

    triples, _ = generate_lubm(2, seed=9)
    ss2 = VirtualLubmStrings(2, seed=9)
    stores = build_all_partitions(triples, 8)
    dist = DistEngine(stores, ss2, make_mesh(8))
    cpu = CPUEngine(build_partition(triples, 0, 1), ss2)
    for qn in ("lubm_q1", "lubm_q7"):
        text = open(f"{BASIC}/{qn}").read()
        qc = Parser(ss2).parse(text)
        heuristic_plan(qc)
        cpu.execute(qc, from_proxy=False)
        qd = Parser(ss2).parse(text)
        heuristic_plan(qd)
        qd.result.blind = True
        dist.execute(qd)
        assert qd.result.status_code == 0, (qn, qd.result.status_code)
        assert qd.result.nrows == qc.result.nrows, qn


def test_dist_filter_and_projection(world):
    """FILTER + DISTINCT/projection run host-side after the distributed BGP."""
    ss, cpu, dist = world
    text = """
    PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
    PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT DISTINCT ?Y1 WHERE {
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X rdf:type ub:FullProfessor .
        ?X ub:name ?Y1 .
        FILTER regex(?Y1, "FullProfessor[0-2]")
    }"""
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    dist.execute(qd)
    assert qd.result.status_code == 0
    got = sorted(map(tuple, qd.result.table.tolist()))
    want = sorted(map(tuple, qc.result.table.tolist()))
    assert got == want and len(got) == 3


@_needs(UNION_DIR)
def test_dist_top_level_union(world):
    """union/q1: each branch runs distributed, results merge host-side."""
    ss, cpu, dist = world
    text = open(f"{UNION_DIR}/q1").read()
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    assert qc.result.status_code == 0
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    dist.execute(qd)
    assert qd.result.status_code == 0
    got = sorted(map(tuple, qd.result.table.tolist()))
    want = sorted(map(tuple, qc.result.table.tolist()))
    assert got == want and len(got) > 0


def test_dist_union_branch_filters(world):
    """Branch-level FILTERs inside a distributed UNION must be applied."""
    ss, cpu, dist = world
    text = """
    PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
    PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X ?Y WHERE {
        { ?X rdf:type ub:Course . ?X ub:name ?Y .
          FILTER regex(?Y, "Course1.*") }
        UNION
        { ?X rdf:type ub:GraduateCourse . ?X ub:name ?Y . }
    }"""
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    dist.execute(qd)
    assert qd.result.status_code == 0
    got = sorted(map(tuple, qd.result.table.tolist()))
    want = sorted(map(tuple, qc.result.table.tolist()))
    assert got == want and 0 < len(got)


# ---------------------------------------------------------------------------
# distributed v2: OPTIONAL / nested UNION / attributes (round-2 VERDICT #3)
# ---------------------------------------------------------------------------

UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
RDF = "http://www.w3.org/1999/02/22-rdf-syntax-ns#"


def _rows_of(res):
    return sorted(map(tuple, np.asarray(res.table).tolist()))


def _compare(world, text):
    ss, cpu, dist = world
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    dist.execute(qd)
    assert qc.result.status_code == 0, f"cpu failed: {qc.result.status_code}"
    assert qd.result.status_code == 0, f"dist failed: {qd.result.status_code}"
    assert _rows_of(qc.result) == _rows_of(qd.result), (
        f"cpu {qc.result.nrows} rows vs dist {qd.result.nrows}")
    return qd


@_needs(OPTIONAL_DIR)
@pytest.mark.parametrize("qn", ["q1", "q1s0", "q1s1", "q2", "q2s1", "q3",
                                "q4", "q5"])
def test_dist_optional_suite(world, qn):
    # q5 has no required patterns: the parser promotes the leading OPTIONAL
    # to the base (reference planner behavior), so it runs everywhere
    _compare(world, open(f"{OPTIONAL_DIR}/{qn}").read())


@_needs(UNION_DIR)
@pytest.mark.parametrize("qn", ["q1", "q2"])
def test_dist_union_suite(world, qn):
    _compare(world, open(f"{UNION_DIR}/{qn}").read())


def test_dist_union_seeded_by_patterns(world):
    """UNION branches seeded by a preceding BGP (inherit_union semantics)."""
    text = f"""PREFIX ub: <{UB}>
    SELECT ?X ?Y ?Z WHERE {{
        ?X ub:memberOf ?Y .
        {{ ?X ub:undergraduateDegreeFrom ?Z . }}
        UNION {{ ?X ub:mastersDegreeFrom ?Z . }}
    }}"""
    q = _compare(world, text)
    assert q.result.nrows > 0


def test_dist_optional_with_blanks_then_filter(world):
    """OPTIONAL + bound() FILTER over the BLANK-filled column."""
    text = f"""PREFIX ub: <{UB}>
    SELECT ?S ?UG ?DOC WHERE {{
        ?S ub:undergraduateDegreeFrom ?UG .
        OPTIONAL {{ ?S ub:doctoralDegreeFrom ?DOC }} .
        FILTER (!bound(?DOC))
    }}"""
    _compare(world, text)


@pytest.fixture(scope="module")
def attr_world(eight_cpu_devices):
    from wukong_tpu.loader.lubm import generate_lubm_attrs

    triples, _ = generate_lubm(1, seed=42)
    attrs = generate_lubm_attrs(1, seed=42)
    ss = VirtualLubmStrings(1, seed=42)
    g1 = build_partition(triples, 0, 1, attr_triples=attrs)
    stores = build_all_partitions(triples, 8, attr_triples=attrs)
    dist = DistEngine(stores, ss, make_mesh(8))
    cpu = CPUEngine(g1, ss)
    return ss, cpu, dist


@_needs(ATTR_DIR)
@pytest.mark.parametrize("qn", ["lubm_attr_q1", "lubm_attr_q2", "lubm_attr_q3"])
def test_dist_attr_suite(attr_world, qn, monkeypatch):
    from wukong_tpu.config import Global

    monkeypatch.setattr(Global, "enable_vattr", True)
    ss, cpu, dist = attr_world
    text = open(f"{ATTR_DIR}/{qn}").read()
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    dist.execute(qd)
    assert qc.result.status_code == 0
    assert qd.result.status_code == 0
    assert _rows_of(qc.result) == _rows_of(qd.result)
    assert np.allclose(np.sort(np.asarray(qc.result.attr_table), axis=0),
                       np.sort(np.asarray(qd.result.attr_table), axis=0))


def test_dist_blind_optional_union_silent_parity(world):
    """Reference silent mode works for ANY shape (it executes and just never
    ships the table, query.hpp:619-630): blind + OPTIONAL must return the
    true row count with an empty table, matching the non-blind row count."""
    ss, cpu, dist = world
    text = f"""PREFIX ub: <{UB}>
    SELECT ?S ?UG ?DOC WHERE {{
        ?S ub:undergraduateDegreeFrom ?UG .
        OPTIONAL {{ ?S ub:doctoralDegreeFrom ?DOC }} .
    }}"""
    qfull = Parser(ss).parse(text)
    heuristic_plan(qfull)
    dist.execute(qfull)
    assert qfull.result.status_code == 0

    q = Parser(ss).parse(text)
    heuristic_plan(q)
    q.result.blind = True
    dist.execute(q)
    assert q.result.status_code == 0
    assert q.result.nrows == qfull.result.nrows > 0
    assert q.result.table.size == 0  # the table itself is never shipped


def test_dist_optional_filter_on_parent_var(world):
    """OPTIONAL group whose FILTER references a var bound only by the parent."""
    text = f"""PREFIX ub: <{UB}>
    SELECT ?S ?UG ?DOC WHERE {{
        ?S ub:undergraduateDegreeFrom ?UG .
        OPTIONAL {{ ?S ub:doctoralDegreeFrom ?DOC . FILTER(?UG != ?DOC) }} .
    }}"""
    _compare(world, text)


def test_dist_skew_aware_exchange_no_retry(eight_cpu_devices):
    """Hub-skewed exchanges: the multiplicity-bound capacity estimate must
    absorb a University0-style hot destination on the FIRST attempt (the
    reference absorbs skew via work stealing, engine.hpp:186-207)."""
    from wukong_tpu.loader.generic_rdf import generate_generic
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery

    triples, meta = generate_generic(20_000, n_preds=8, n_types=4, seed=5)
    g1 = build_partition(triples, 0, 1)
    stores = build_all_partitions(triples, 8)
    dist = DistEngine(stores, None, make_mesh(8))
    # two-hop through the hub-attracting object column: the exchange keys on
    # a column whose values concentrate into hubs
    from wukong_tpu.types import TYPE_ID

    pids = np.unique(triples[:, 1])
    pids = [int(p) for p in pids if p != TYPE_ID][:2]

    def mk():
        q = SPARQLQuery()
        q.pattern_group.patterns = [
            Pattern(pids[0], 0, 0, -1),  # __PREDICATE__ index start
            Pattern(-1, pids[0], 1, -2),  # expand: objects (hub-skewed)
            Pattern(-2, pids[1], 1, -3),  # exchange on the hub column
        ]
        q.result.nvars = 3
        q.result.required_vars = [-1, -2, -3]
        return q

    builds = []
    orig = dist._build_plan

    def spy(q, cap_override, n_steps=None, seed=None):
        builds.append(1)
        return orig(q, cap_override, n_steps, seed)

    dist._build_plan = spy
    qd = mk()
    dist.execute(qd, from_proxy=False)
    assert qd.result.status_code == 0
    assert len(builds) == 1, f"capacity retries happened: {len(builds) - 1}"

    # the multiplicity bound must cover the true hot-destination load even
    # where the naive est//D*4 slack would not (it matters at pod-scale D,
    # where 4/D of the inflated estimate undershoots a dominant hub)
    plan = orig(mk(), {}, n_steps=3)
    exch_step = plan.steps[2]
    assert exch_step.exch_cap > 0
    hub_edges = triples[triples[:, 1] == pids[0]][:, 2]
    hot_mult = int(np.bincount(hub_edges - hub_edges.min()).max())
    assert exch_step.exch_cap >= hot_mult

    cpu = CPUEngine(g1, None)
    qc = mk()
    cpu.execute(qc, from_proxy=False)
    got = sorted(map(tuple, qd.result.table.tolist()))
    want = sorted(map(tuple, qc.result.table.tolist()))
    assert got == want


def test_preshard_multihost_load_matches_global(tmp_path, eight_cpu_devices):
    """Per-host loader sharding: 2 hosts x 4 shards, each host builds its
    partitions from ITS file only; the assembled cluster is segment-identical
    to a global build and answers queries on the 8-way mesh."""
    from wukong_tpu.loader.base import load_host_partitions, preshard_dataset
    from wukong_tpu.loader.lubm import write_dataset

    src = tmp_path / "ds"
    shard_dir = tmp_path / "sharded"
    write_dataset(str(src), 1, seed=9)
    meta = preshard_dataset(str(src), str(shard_dir), num_hosts=2,
                            shards_per_host=4)
    assert meta["num_hosts"] == 2

    stores = []
    for h in range(2):  # each host loads independently
        stores.extend(load_host_partitions(str(shard_dir), h))
    assert [g.sid for g in stores] == list(range(8))
    # attribute triples must survive presharding (subject-owner placement)
    assert any(g.attrs for g in stores)

    from wukong_tpu.loader.base import load_triples

    triples = load_triples(str(src))
    want = build_all_partitions(triples, 8)
    for g, w in zip(stores, want):
        assert set(g.segments) == set(w.segments), g.sid
        for k in w.segments:
            assert np.array_equal(g.segments[k].keys, w.segments[k].keys)
            assert np.array_equal(g.segments[k].edges, w.segments[k].edges)
        for k in w.index:
            assert np.array_equal(np.sort(g.index[k]), np.sort(w.index[k]))

    ss = VirtualLubmStrings(1, seed=9)
    dist = DistEngine(stores, ss, make_mesh(8))
    cpu = CPUEngine(build_partition(triples, 0, 1), ss)
    text = open(f"{BASIC}/lubm_q4").read()
    qd = Parser(ss).parse(text)
    heuristic_plan(qd)
    dist.execute(qd)
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    assert qd.result.status_code == 0
    assert _rows_of(qd.result) == _rows_of(qc.result)


def test_dist_versatile_kuu(world):
    """Distributed VERSATILE ?x ?p ?y (x bound): each shard expands its
    combined adjacency inside the compiled chain — beyond the reference,
    whose accelerator refuses every versatile shape. Exact row parity with
    the single-host CPU kernels, including a continuation step."""
    _compare(world, f"""PREFIX ub: <{UB}>
    SELECT ?X ?P ?Y WHERE {{
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X ?P ?Y .
    }}""")
    # continuation anchored on the versatile VALUE column
    _compare(world, f"""PREFIX rdf: <{RDF}>
    PREFIX ub: <{UB}>
    SELECT ?X ?P ?Y WHERE {{
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        ?X ?P ?Y .
        ?Y rdf:type ub:Course .
    }}""")


def test_dist_versatile_probe_bound(eight_cpu_devices, monkeypatch):
    """The compiled versatile step must bake the COMBINED segment's probe
    bound, not a missing segment(pid=0)'s default of 1 — on this world,
    with each shard's keys hashed whole (no key shift: a shard's keys then
    share their low bits and crowd a few home buckets), the versatile hash
    table needs 3 probe rounds, so a baked max_probe=1 silently drops every
    key outside its home bucket (a real bug once)."""
    from wukong_tpu.loader.generic_rdf import generate_generic
    from wukong_tpu.parallel import sharded_store
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import OUT, TYPE_ID

    monkeypatch.setattr(sharded_store, "_key_shift", lambda _keys: 0)
    triples, meta = generate_generic(20_000, n_preds=8, n_types=4, seed=5)
    stores = build_all_partitions(triples, 8)
    dist = DistEngine(stores, None, make_mesh(8))
    assert dist.sstore.versatile_segment(int(OUT)).max_probe > 1

    pids = [int(p) for p in np.unique(triples[:, 1]) if p != TYPE_ID][:1]

    def mk():
        q = SPARQLQuery()
        q.pattern_group.patterns = [
            Pattern(pids[0], 0, 0, -1),   # __PREDICATE__ index start
            Pattern(-1, -2, OUT, -3)]     # versatile ?x ?p ?y
        q.result.nvars = 3
        q.result.required_vars = [-1, -2, -3]
        return q

    qd = mk()
    dist.execute(qd, from_proxy=False)
    assert qd.result.status_code == 0
    cpu = CPUEngine(build_partition(triples, 0, 1), None)
    qc = mk()
    cpu.execute(qc, from_proxy=False)
    assert _rows_of(qd.result) == _rows_of(qc.result)
    assert qc.result.nrows > 0


def test_dist_c2k_mid_chain(world):
    """const_to_known mid-chain (sparql.hpp:138-163's c2k): a const-subject
    pattern whose object is already bound runs as a reverse-segment member
    step inside the compiled chain (patterns built in index form so the
    c2k stays mid-chain — heuristic_plan would hoist the const start)."""
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import IN, OUT, TYPE_ID

    ss, cpu, dist = world
    fp = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#FullProfessor>")
    works = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#worksFor>")
    fp0 = ss.str2id("<http://www.Department0.University0.edu/FullProfessor0>")

    def mk():
        q = SPARQLQuery()
        q.pattern_group.patterns = [
            Pattern(fp, TYPE_ID, IN, -1),    # type-index start -> ?X
            Pattern(-1, works, OUT, -2),     # ?X worksFor ?D
            Pattern(fp0, works, OUT, -2),    # c2k: FP0 worksFor ?D (bound)
        ]
        q.result.nvars = 2
        q.result.required_vars = [-1, -2]
        return q

    qc, qd = mk(), mk()
    cpu.execute(qc, from_proxy=False)
    dist.execute(qd, from_proxy=False)
    assert qd.result.status_code == 0
    assert _rows_of(qd.result) == _rows_of(qc.result)
    assert qc.result.nrows > 0  # FullProfessors of Department0.University0


def test_dist_seeded_union_c2k_branch(world):
    """UNION branches whose FIRST pattern is const-subject/bound-object run
    distributed off the seeded parent rows (widened seed-anchor resolution)."""
    from wukong_tpu.sparql.ir import Pattern, PatternGroup, SPARQLQuery
    from wukong_tpu.types import IN, OUT, TYPE_ID

    ss, cpu, dist = world
    ap = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#AssociateProfessor>")
    works = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#worksFor>")
    fp0 = ss.str2id("<http://www.Department0.University0.edu/FullProfessor0>")
    fp1 = ss.str2id("<http://www.Department1.University0.edu/FullProfessor0>")

    def mk():
        q = SPARQLQuery()
        q.pattern_group.patterns = [
            Pattern(ap, TYPE_ID, IN, -1),
            Pattern(-1, works, OUT, -2),
        ]
        for c in (fp0, fp1):
            u = PatternGroup()
            u.patterns = [Pattern(c, works, OUT, -2)]  # seeded c2k branch
            q.pattern_group.unions.append(u)
        q.result.nvars = 2
        q.result.required_vars = [-1, -2]
        return q

    qc, qd = mk(), mk()
    cpu.execute(qc, from_proxy=False)
    dist.execute(qd, from_proxy=False)
    assert qd.result.status_code == 0
    assert _rows_of(qd.result) == _rows_of(qc.result)
    assert qc.result.nrows > 0


def test_dist_versatile_const_shapes(world):
    """Distributed const_unknown_const and known_unknown_const: owner-shard
    CSR start / expand2 + equality fold inside the compiled chain."""
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import IN, OUT, TYPE_ID

    ss, cpu, dist = world
    dept0 = ss.str2id("<http://www.Department0.University0.edu>")
    univ0 = ss.str2id("<http://www.University0.edu>")
    fp = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#FullProfessor>")
    works = ss.str2id("<http://swat.cse.lehigh.edu/onto/univ-bench.owl#worksFor>")

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
        b = run(dist, pats, req)
        assert a == b, (name, len(a), len(b))
        assert len(a) > 0, (name, "vacuous: empty result")

    # const_unknown_const start: Dept0 ?P Univ0
    cmp([(dept0, -9, OUT, univ0)], [-9], "c_u_c")
    # versatile const start continuing into a distributed chain: everyone
    # with an edge INTO Dept0, then where they work
    cmp([(dept0, -9, IN, -1), (-1, works, OUT, -2)], [-9, -1, -2],
        "c_u_u_then_chain")
    # known_unknown_const mid-chain inside the compiled shard_map chain
    cmp([(fp, TYPE_ID, IN, -1), (-1, -9, OUT, univ0)], [-1, -9], "k_u_c")
    # continuation after the fold
    cmp([(fp, TYPE_ID, IN, -1), (-1, -9, OUT, univ0), (-1, works, OUT, -2)],
        [-1, -9, -2], "k_u_c_then_expand")


def test_learned_caps_tighten_steady_state(world):
    """Successful chains record EXACT capacity classes per pattern key: the
    second run of an exchange-bearing query compiles at capacities no
    larger than (usually far below) the estimate-driven first run, with
    identical results; an injected undersized class still self-corrects
    through the overflow retry."""
    ss, cpu, dist = world
    dist._learned_caps.clear()
    text = open(f"{BASIC}/lubm_q7").read()

    def run():
        q = Parser(ss).parse(text)
        heuristic_plan(q)
        q.result.blind = True
        dist.execute(q, from_proxy=False)
        assert q.result.status_code == 0
        return q.result.nrows, dist.last_chain_stats

    rows1, st1 = run()
    assert dist._learned_caps  # learning happened
    rows2, st2 = run()
    assert rows2 == rows1
    caps1 = [s["cap"] for s in st1["steps"]]
    caps2 = [s["cap"] for s in st2["steps"]]
    assert all(c2 <= c1 for c1, c2 in zip(caps1, caps2))
    ex1 = [s["exch_cap"] for s in st1["steps"] if "exch_cap" in s]
    ex2 = [s["exch_cap"] for s in st2["steps"] if "exch_cap" in s]
    assert ex1 and all(c2 <= c1 for c1, c2 in zip(ex1, ex2))
    # run 2's classes are exact: every load fits its (tight) class
    for s in st2["steps"]:
        assert s["rows_peak_shard"] <= s["cap"]
        if "exch_cap" in s:
            assert s["exch_peak_dest"] <= s["exch_cap"]
    # undersized injection on a LEARNED chain: retry restores correctness
    dist.force_cap_override = {("cap", 1): 2}
    rows3, st3 = run()
    assert rows3 == rows1 and st3["retries"] >= 1


# ----------------------------------------------------------------------
# round-5 in-place owner-routed fast path (reference need_fork_join,
# sparql.hpp:802-814; proxy owner routing, proxy.hpp:201-219)
# ----------------------------------------------------------------------
def _rows_over_shared_vars(q):
    cols = [q.result.v2c_map[v] for v in sorted(q.result.v2c_map)]
    return sorted(map(tuple, np.asarray(q.result.table)[:, cols].tolist()))


def test_inplace_routes_agree_with_collective(world):
    """Light const-start chains route in place (zero collectives) and must
    produce identical rows to the sharded chain — the both-routes
    verification the round-4 verdict asked the suite to carry."""
    from wukong_tpu.config import Global
    from wukong_tpu.types import NORMAL_ID_START

    ss, cpu, dist = world
    for qn in ("lubm_q4", "lubm_q5", "lubm_q6"):
        text = open(f"{BASIC}/{qn}").read()
        q1 = Parser(ss).parse(text)
        heuristic_plan(q1)
        first = q1.pattern_group.patterns[0]
        Global.enable_dist_inplace = True
        try:
            dist.execute(q1)
        finally:
            Global.enable_dist_inplace = False
        st = dist.last_chain_stats or {}
        assert q1.result.status_code == 0, qn
        if first.subject >= NORMAL_ID_START and first.predicate > 0:
            assert st.get("mode") == "inplace", (qn, st)
        q2 = Parser(ss).parse(text)
        heuristic_plan(q2)
        dist.execute(q2)  # collective (autouse fixture pinned the flag off)
        assert q2.result.status_code == 0, qn
        assert _rows_over_shared_vars(q1) == _rows_over_shared_vars(q2), qn


def test_inplace_overflow_falls_back_to_collective(world):
    """A chain whose live table outgrows dist_inplace_rows mid-walk aborts
    the in-place route and re-runs through the collective path with
    identical results (the fork-join analogue of need_fork_join)."""
    from wukong_tpu.config import Global

    ss, cpu, dist = world
    text = """PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X ?Y WHERE {
        ?X ub:subOrganizationOf <http://www.University0.edu> .
        ?Y ub:memberOf ?X .
    }"""
    q0 = Parser(ss).parse(text)
    heuristic_plan(q0)
    first = q0.pattern_group.patterns[0]
    fan = len(cpu.g.get_triples(first.subject, first.predicate,
                                first.direction))
    assert fan > 0
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc, from_proxy=False)
    assert qc.result.nrows > fan  # the expansion that must trip the abort

    Global.enable_dist_inplace = True
    old_thr = Global.dist_inplace_rows
    Global.dist_inplace_rows = fan  # entry passes; first expansion overflows
    try:
        qd = Parser(ss).parse(text)
        heuristic_plan(qd)
        dist.execute(qd, from_proxy=False)
    finally:
        Global.dist_inplace_rows = old_thr
        Global.enable_dist_inplace = False
    assert qd.result.status_code == 0
    st = dist.last_chain_stats or {}
    assert st.get("mode") != "inplace", st  # retreated to the sharded chain
    assert qd.result.nrows == qc.result.nrows


def test_inplace_seeded_union_child(world):
    """Seeded (UNION) children with small parent tables also ride the
    in-place route; merged rows must match the collective run."""
    from wukong_tpu.config import Global

    ss, cpu, dist = world
    text = """PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
    SELECT ?X ?Y WHERE {
        ?X ub:worksFor <http://www.Department0.University0.edu> .
        { ?X ub:teacherOf ?Y . } UNION { ?Y ub:advisor ?X . }
    }"""
    Global.enable_dist_inplace = True
    try:
        q1 = Parser(ss).parse(text)
        heuristic_plan(q1)
        dist.execute(q1)
    finally:
        Global.enable_dist_inplace = False
    assert q1.result.status_code == 0
    q2 = Parser(ss).parse(text)
    heuristic_plan(q2)
    dist.execute(q2)
    assert q2.result.status_code == 0
    assert q1.result.nrows > 0
    assert _rows_over_shared_vars(q1) == _rows_over_shared_vars(q2)


def test_inplace_attr_tail_and_blind(world):
    """In-place prefix + owner-routed attr tail + blind count parity."""
    from wukong_tpu.config import Global

    ss, cpu, dist = world
    text = open(f"{BASIC}/lubm_q4").read()
    Global.enable_dist_inplace = True
    try:
        qb = Parser(ss).parse(text)
        heuristic_plan(qb)
        qb.result.blind = True
        dist.execute(qb)
    finally:
        Global.enable_dist_inplace = False
    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc, from_proxy=False)
    assert qb.result.status_code == 0
    assert qb.result.nrows == qc.result.nrows
    assert qb.result.table.shape[0] == 0  # blind: the table never ships


def test_dist_cap_memo_roundtrip(world, tmp_path):
    """Learned capacity classes persist across engines/processes: a fresh
    engine loading the memo starts at the exact classes (round-5 cold-start
    fix); in-process learning wins over a stale memo (setdefault)."""
    ss, cpu, dist = world
    text = open(f"{BASIC}/lubm_q7").read()
    q = Parser(ss).parse(text)
    heuristic_plan(q)
    q.result.blind = True
    dist.execute(q, from_proxy=False)
    assert q.result.status_code == 0 and dist._learned_caps
    path = str(tmp_path / "caps.json")
    dist.save_cap_memo(path)

    fresh = DistEngine(dist.sstore.stores, ss, dist.mesh)
    fresh.load_cap_memo(path)
    assert fresh._learned_caps == dist._learned_caps
    # in-process learning is not clobbered by a later load
    key = next(iter(fresh._learned_caps))
    fresh._learned_caps[key] = {("cap", 0): 1024}
    fresh.load_cap_memo(path)
    assert fresh._learned_caps[key] == {("cap", 0): 1024}


# ---------------------------------------------------------------------------
# a sharded deployment served through Proxy.serve_query (the benchmark's
# lubm_bundle_sharded loader: shards booted once, no whole store beside them)
# ---------------------------------------------------------------------------

def _route_attrs(q):
    return [a for sp in q.trace.spans for (_t, n, a) in sp.events
            if n == "proxy.route"]


@pytest.fixture(scope="module")
def sharded_world(tmp_path_factory, eight_cpu_devices):
    """LUBM-1 hash-sharded over 4 of the 8 devices, as the loader builds it,
    and a single-partition CPU oracle over the same triples."""
    from benchmark.loaders import lubm_bundle_sharded

    d = str(tmp_path_factory.mktemp("sharded") / "c")
    world = lubm_bundle_sharded.load(
        {"universities": 1, "partitions": 4, "data_seed": 42}, 0, d)
    triples = np.asarray(world.triples, dtype=np.int64)
    g1 = build_partition(triples, 0, 1)
    return world, triples, CPUEngine(g1, world.proxy.str_server)


@pytest.mark.parametrize("qn", [f"lubm_q{k}" for k in range(1, 8)])
def test_sharded_proxy_serves_lubm(sharded_world, qn, monkeypatch):
    """Every LUBM query a sharded proxy serves unpinned is the sharded
    engine's, with no fallback, and its rows are the plain reference's and
    the CPU oracle's, as sorted multisets."""
    import re

    from benchmark.reference import Reference, sorted_rows
    from wukong_tpu.config import Global

    world, _triples, cpu = sharded_world
    text = open(f"{BASIC}/{qn}").read()
    monkeypatch.setattr(Global, "enable_tracing", True)
    q = world.proxy.serve_query(text, blind=False)
    assert q.result.status_code == 0 and q.result.complete, qn
    spans = {sp.name for sp in q.trace.spans}
    assert "dist.execute" in spans, qn
    assert not spans & {"cpu.execute", "tpu.execute"}, qn
    assert "proxy.fallback" not in q.trace.event_names(), qn
    assert [a["route"] for a in _route_attrs(q)] == ["dist"], qn
    cols = [q.result.v2c_map[v] for v in q.result.required_vars]
    got = sorted_rows(np.asarray(q.result.table)[:, cols])

    ref = Reference(world.triples, world.index_rows)
    ss = world.proxy.str_server
    for iri in re.findall(r"<http://www\.[^>]*\.edu>", text):
        ref.ids[iri] = int(ss.str2id(iri))
    assert np.array_equal(got, ref.evaluate(text)), qn

    qc = Parser(ss).parse(text)
    heuristic_plan(qc)
    cpu.execute(qc)
    want = sorted_rows(np.asarray(qc.result.table)[
        :, [qc.result.v2c_map[v] for v in qc.result.required_vars]])
    assert np.array_equal(got, want), qn
    assert len(got) or qn == "lubm_q3"  # q3 is empty by design


UNION_TEXT = """PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
SELECT ?X WHERE {
  ?X rdf:type ub:FullProfessor .
  { ?X ub:worksFor <http://www.Department0.University0.edu> . }
  UNION
  { ?X ub:headOf <http://www.Department0.University0.edu> . }
}"""


def test_sharded_proxy_serves_a_union_and_degrades_to_the_shards_in_place(
        sharded_world, monkeypatch):
    """A UNION is the sharded engine's, with no fallback. A shape the
    sharded engine refuses falls back, with its ``proxy.fallback`` event, to
    the host engine that walks every shard in place, not to the device
    engine, which stages no shard: both answers are the oracle's. The
    engine pool's engines and a template's constants read every shard, not
    the proxy's own partition alone."""
    from wukong_tpu.config import Global
    from wukong_tpu.parallel.inplace import InplaceEngine
    from wukong_tpu.types import IN
    from wukong_tpu.utils.errors import ErrorCode
    from wukong_tpu.utils.paths import LUBM_EMULATOR as EMU

    world, _triples, cpu = sharded_world
    proxy, ss = world.proxy, world.proxy.str_server
    qc = Parser(ss).parse(UNION_TEXT)
    heuristic_plan(qc)
    cpu.execute(qc)
    want = sorted(np.asarray(qc.result.table)[:, 0].tolist())
    assert len(want) > 0
    monkeypatch.setattr(Global, "enable_tracing", True)

    def served():
        q = proxy.serve_query(UNION_TEXT, blind=False)
        assert q.result.status_code == 0 and q.result.complete
        col = q.result.v2c_map[q.result.required_vars[0]]
        # an event outside every span is a span of its own name
        return ({sp.name for sp in q.trace.spans}
                | set(q.trace.event_names()),
                sorted(np.asarray(q.result.table)[:, col].tolist()))

    names, got = served()
    assert "dist.execute" in names and got == want
    assert "proxy.fallback" not in names

    def refuse(q, *a, **kw):
        q.result.status_code = ErrorCode.UNSUPPORTED_SHAPE

    monkeypatch.setattr(proxy.dist, "execute", refuse)
    names, got = served()
    assert "proxy.fallback" in names
    assert "cpu.execute" in names and "tpu.execute" not in names
    assert got == want

    eng = proxy._new_host_engine()
    assert isinstance(eng, InplaceEngine)
    q4 = Parser(ss).parse(open(f"{BASIC}/lubm_q4").read())
    heuristic_plan(q4)
    eng.execute(q4)
    assert q4.result.status_code == 0 and q4.result.nrows > 0

    tmpl = Parser(ss).parse_template(open(f"{EMU}/q1").read())
    proxy.fill_template(tmpl)
    whole = sorted(cpu.g.get_index(tmpl.ptypes[0], IN).tolist())
    assert sorted(tmpl.candidates[0].tolist()) == whole
    assert len(whole) > len(proxy.g.get_index(tmpl.ptypes[0], IN))


def test_shards_partition_the_whole(sharded_world):
    """The four shards hold the single store's edges and no other, each edge
    on the shard that owns its key (``hash(vid) % 4``): OUT edges with their
    subject, IN edges with their object; each index member with its
    vertex's owner."""
    world, triples, cpu = sharded_world
    stores = world.proxy.dist.sstore.stores
    g1 = cpu.g
    assert len(stores) == 4 and world.proxy.g is stores[0]

    def pairs(seg):
        return np.stack([np.repeat(seg.keys, np.diff(seg.offsets)),
                         seg.edges], axis=1)

    for key, seg in g1.segments.items():
        parts = []
        for k, st in enumerate(stores):
            mine = st.segments.get(key)
            if mine is None:
                continue
            assert np.all(mine.keys % 4 == k), key
            parts.append(pairs(mine))
        got = np.concatenate(parts)
        assert np.array_equal(got[np.lexsort(got.T[::-1])],
                              pairs(seg)[np.lexsort(pairs(seg).T[::-1])]), key
    for key, members in g1.index.items():
        got = np.sort(np.concatenate(
            [st.index.get(key, np.empty(0, np.int64)) for st in stores]))
        assert np.array_equal(got, np.sort(members)), key
    assert sum(int(np.sum(st.v_set % 4 == k))
               for k, st in enumerate(stores)) == sum(
        len(st.v_set) for st in stores)


def _counter_total(name):
    from wukong_tpu.obs.metrics import get_registry

    series = (get_registry().snapshot().get(name) or {}).get("series", [])
    return sum(float(s.get("value", 0)) for s in series)


def _hub_graph():
    """A type T of 2,000 members, each with a p edge to one hub H (all but
    a few) and H with a q edge to each of 3,000 vertices: H's shard holds
    most of the q edges and every row of the last expansion."""
    from wukong_tpu.types import NORMAL_ID_START, TYPE_ID

    T, P, Q = 20, 21, 22
    base = NORMAL_ID_START
    xs = np.arange(base + 8, base + 8 + 2000)
    hub, other = base + 1, base + 2
    zs = np.arange(base + 100_000, base + 103_000)
    obj = np.where(np.arange(len(xs)) % 50 == 0, other, hub)
    rows = [np.stack([xs, np.full_like(xs, TYPE_ID), np.full_like(xs, T)], 1),
            np.stack([xs, np.full_like(xs, P), obj], 1),
            np.stack([np.full_like(zs, hub), np.full_like(zs, Q), zs], 1),
            np.array([[other, Q, zs[0]]])]
    return np.concatenate(rows).astype(np.int64), (T, P, Q)


def _hub_query(T, P, Q):
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import IN, OUT, TYPE_ID

    q = SPARQLQuery()
    q.pattern_group.patterns = [Pattern(T, TYPE_ID, IN, -1),
                                Pattern(-1, P, OUT, -2),
                                Pattern(-2, Q, OUT, -3)]
    q.result.nvars = 3
    q.result.required_vars = [-1, -2, -3]
    return q


def test_hot_vertex_answers_exactly_through_the_retry(eight_cpu_devices):
    """One hot vertex owns most edges: its shard holds nearly every row of
    the last expansion, and every shard is padded to it. Started at a class
    that cannot hold its exchange, the chain runs again at the exact class,
    answers exactly, and the retry counter and a ``capacity.retry`` event
    count it; run again, it starts at the class it measured."""
    from wukong_tpu.obs.trace import QueryTrace

    triples, (T, P, Q) = _hub_graph()
    dist = DistEngine(build_all_partitions(triples, 4), None,
                      make_mesh(4, eight_cpu_devices[:4]))
    want_q = _hub_query(T, P, Q)
    CPUEngine(build_partition(triples, 0, 1), None).execute(
        want_q, from_proxy=False)
    want = sorted(map(tuple, want_q.result.table.tolist()))
    assert len(want) == 1960 * 3000 + 40  # the hub's rows and the rest

    before = _counter_total("wukong_dist_capacity_retries_total")
    # each shard sends the hub's owner its 490 rows: a class of 256 a
    # destination cannot hold them
    dist.force_cap_override = {("exch", 2): 256}
    q = _hub_query(T, P, Q)
    q.trace = QueryTrace(kind="query")
    dist.execute(q, from_proxy=False)
    assert q.result.status_code == 0
    assert sorted(map(tuple, q.result.table.tolist())) == want
    retries = dist.last_chain_stats["retries"]
    assert retries >= 1
    assert _counter_total("wukong_dist_capacity_retries_total") - before \
        == retries
    assert q.trace.event_names().count("capacity.retry") >= retries
    stats = dist.last_chain_stats
    assert stats["rows_max_shard"] == 1960 * 3000
    assert stats["rows_mean_shard"] == len(want) / 4

    q2 = _hub_query(T, P, Q)
    dist.execute(q2, from_proxy=False)
    assert dist.last_chain_stats["retries"] == 0  # the learned classes
    assert sorted(map(tuple, q2.result.table.tolist())) == want


def test_exchange_counters_read_a_hand_checked_query(eight_cpu_devices):
    """Four rows, one a shard, each exchanged to the next shard: the chain
    counts four live rows off their chip, ``(D - 1)`` slots a shard of the
    destination class, 4 x 2 ids of bytes, and its span says so."""
    from wukong_tpu.obs.trace import QueryTrace
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import IN, NORMAL_ID_START, OUT, TYPE_ID

    T, P, Q = 20, 21, 22
    base = NORMAL_ID_START  # a multiple of 4: vertex base + k is on shard k
    xs = base + np.arange(4)
    ys = base + 100 + (np.arange(4) + 1) % 4  # the next shard's vertex
    zs = base + 200 + np.arange(4)
    triples = np.concatenate([
        np.stack([xs, np.full(4, TYPE_ID), np.full(4, T)], 1),
        np.stack([xs, np.full(4, P), ys], 1),
        np.stack([ys, np.full(4, Q), zs], 1)]).astype(np.int64)
    dist = DistEngine(build_all_partitions(triples, 4), None,
                      make_mesh(4, eight_cpu_devices[:4]))
    q = SPARQLQuery()
    q.pattern_group.patterns = [Pattern(T, TYPE_ID, IN, -1),
                                Pattern(-1, P, OUT, -2),
                                Pattern(-2, Q, OUT, -3)]
    q.result.nvars = 3
    q.result.required_vars = [-1, -2, -3]
    q.trace = QueryTrace(kind="query")
    names = ("wukong_dist_exchange_rows_total",
             "wukong_dist_exchange_slots_total",
             "wukong_dist_exchange_bytes_total")
    before = [_counter_total(n) for n in names]
    dist.execute(q, from_proxy=False)
    assert q.result.status_code == 0 and q.result.nrows == 4
    exch = [s for s in dist.last_chain_stats["steps"] if "exch_cap" in s]
    assert len(exch) == 1  # the third step anchors on y: y's owner
    slots = 4 * 3 * exch[0]["exch_cap"]
    got = [_counter_total(n) - b for n, b in zip(names, before)]
    assert got == [4, slots, 4 * 2 * 4]
    chain = next(sp for sp in q.trace.spans if sp.name == "dist.chain")
    assert chain.attrs["exchange_rows"] == 4
    assert chain.attrs["exchange_slots"] == slots
    assert chain.attrs["rows_max_shard"] == 1
    assert chain.attrs["rows_mean_shard"] == 1.0


def test_shard_tables_hash_the_bits_above_the_shard(sharded_world):
    """A shard's vertex-keyed table holds only its own residue mod 4, so it
    homes a key by the bits above (``key_shift`` 2) and probes one round
    where the same keys hashed whole need two or three; the type index,
    keyed by type ids on every shard, is hashed whole. A constant probed on
    a shard that does not own it is found nowhere there."""
    from wukong_tpu.engine.device_store import _next_pow2
    from wukong_tpu.parallel.sharded_store import _shard_tables
    from wukong_tpu.types import IN, OUT, TYPE_ID

    world, _triples, _cpu = sharded_world
    sstore = world.proxy.dist.sstore
    typed = sstore.segment(TYPE_ID, OUT)
    assert typed.key_shift == 2 and typed.max_probe == 1
    assert sstore.segment(TYPE_ID, IN).key_shift == 0
    shards = [(st.segments[(TYPE_ID, OUT)].keys,
               st.segments[(TYPE_ID, OUT)].offsets,
               st.segments[(TYPE_ID, OUT)].edges) for st in sstore.stores]
    nb = max(_next_pow2((max(len(k) for k, _o, _e in shards) + 3) // 4), 2)
    ep = _next_pow2(max(len(e) for _k, _o, e in shards))
    assert _shard_tables(shards, nb, ep, 0)["max_probe"] >= 2

    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery

    dist = world.proxy.dist
    for vid in (int(shards[1][0][0]), int(shards[2][0][-1])):
        q = SPARQLQuery()  # a const start: every shard probes the constant
        q.pattern_group.patterns = [Pattern(vid, TYPE_ID, OUT, -1)]
        q.result.nvars = 1
        q.result.required_vars = [-1]
        dist.execute(q, from_proxy=False)
        owner = dist.sstore.stores[vid % 4]
        assert sorted(q.result.table[:, 0].tolist()) == sorted(
            owner.get_triples(vid, TYPE_ID, OUT).tolist())
