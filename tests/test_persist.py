"""GStore persistence round-trip (bench depends on the store cache)."""

import numpy as np

from wukong_tpu.loader.lubm import generate_lubm, generate_lubm_attrs
from wukong_tpu.store.checker import check_partition
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.store.persist import load_gstore, save_gstore


def test_gstore_roundtrip(tmp_path):
    triples, _ = generate_lubm(1, seed=13)
    attrs = generate_lubm_attrs(1, seed=13)
    g = build_partition(triples, 0, 2, attr_triples=attrs)
    path = str(tmp_path / "p0")
    save_gstore(g, path)
    g2 = load_gstore(path)
    assert g2.sid == g.sid and g2.num_workers == g.num_workers
    assert set(g2.segments) == set(g.segments)
    for k in g.segments:
        assert np.array_equal(g2.segments[k].keys, g.segments[k].keys)
        assert np.array_equal(g2.segments[k].offsets, g.segments[k].offsets)
        assert np.array_equal(g2.segments[k].edges, g.segments[k].edges)
    assert set(g2.index) == set(g.index)
    for k in g.index:
        assert np.array_equal(g2.index[k], g.index[k])
    assert g2.type_ids == g.type_ids
    assert set(g2.vp) == set(g.vp)
    for d in g.vp:
        assert np.array_equal(g2.vp[d].keys, g.vp[d].keys)
        assert np.array_equal(g2.vp[d].edges, g.vp[d].edges)
    assert np.array_equal(g2.v_set, g.v_set)
    assert set(g2.attrs) == set(g.attrs)
    for a in g.attrs:
        assert np.array_equal(g2.attrs[a].keys, g.attrs[a].keys)
        assert np.array_equal(g2.attrs[a].values, g.attrs[a].values)
        assert g2.attrs[a].type == g.attrs[a].type
    assert check_partition(g2) == []


def test_loaded_store_queries_identically(tmp_path):
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.loader.lubm import VirtualLubmStrings
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.sparql.parser import Parser
    from wukong_tpu.utils.paths import LUBM_BASIC

    triples, _ = generate_lubm(1, seed=13)
    g = build_partition(triples, 0, 1)
    path = str(tmp_path / "p0")
    save_gstore(g, path)
    g2 = load_gstore(path)
    ss = VirtualLubmStrings(1, seed=13)
    text = open(f"{LUBM_BASIC}/lubm_q4").read()
    rows = []
    for store in (g, g2):
        q = Parser(ss).parse(text)
        heuristic_plan(q)
        CPUEngine(store, ss).execute(q)
        assert q.result.status_code == 0
        rows.append(sorted(map(tuple, q.result.table.tolist())))
    assert rows[0] == rows[1]
