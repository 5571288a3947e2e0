"""Stats.generate scale fast paths (round 5): the per-vertex frozenset
loops OOM-killed the LUBM-10240 run (220 M typed vertices -> ~44 GB of
Python sets), so single-typed worlds and large untyped-with-out-edges
populations take vectorized paths. These tests pin the vectorized
signature grouping against an independent brute-force implementation."""

import numpy as np
import pytest

from wukong_tpu.planner.stats import Stats
from wukong_tpu.types import NORMAL_ID_START, TYPE_ID


def _world_with_big_untyped(n_untyped=250_000, seed=0):
    """A few typed vertices + a large untyped population with out-edges —
    drives the vectorized signature branch (> 200k out-edged untyped)."""
    rng = np.random.default_rng(seed)
    base = NORMAL_ID_START
    typed = base + np.arange(50)
    t_id = 40
    untyped = base + 50 + np.arange(n_untyped)
    preds = 2 + np.arange(5)
    rows = [np.stack([typed, np.full(50, TYPE_ID), np.full(50, t_id)], 1)]
    # each untyped subject: 1-3 distinct predicates toward typed targets
    k = rng.integers(1, 4, n_untyped)
    subs = np.repeat(untyped, k)
    # distinct preds per subject via offset trick
    b0 = rng.integers(0, 5, n_untyped)
    step = rng.integers(1, 3, n_untyped)
    j = np.concatenate([np.arange(x) for x in k])
    psel = preds[(np.repeat(b0, k) + j * np.repeat(step, k)) % 5]
    objs = typed[rng.integers(0, 50, len(subs))]
    rows.append(np.stack([subs, psel, objs], 1))
    # plus literals that are objects only (no out-edges at all)
    lits = base + 50 + n_untyped + np.arange(1000)
    rows.append(np.stack([typed[rng.integers(0, 50, 1000)],
                          np.full(1000, int(preds[0])), lits], 1))
    return np.unique(np.concatenate(rows), axis=0)


def test_vectorized_untyped_signature_matches_bruteforce():
    triples = _world_with_big_untyped()
    st = Stats.generate(triples)

    # brute force: group untyped subjects by their out-predicate SET
    s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]
    typed_set = set(s[p == TYPE_ID].tolist())
    psets: dict[int, frozenset] = {}
    for si, pi in zip(s.tolist(), p.tolist()):
        if pi != TYPE_ID and si not in typed_set:
            psets.setdefault(si, set())
    for si, pi in zip(s.tolist(), p.tolist()):
        if pi != TYPE_ID and si not in typed_set:
            psets[si].add(pi)
    all_vs = set(s.tolist()) | {x for x in o.tolist()
                                if x >= NORMAL_ID_START}
    no_out = all_vs - typed_set - set(psets)
    groups: dict[frozenset, set] = {}
    for v, ps in psets.items():
        groups.setdefault(frozenset(ps), set()).add(v)
    if no_out:
        groups.setdefault(frozenset(), set()).update(no_out)

    # same partition: vertices share a Stats class iff they share a pset
    cls_of = {int(v): st.type_of(int(v))
              for v in (set(psets) | no_out)}
    assert all(c < 0 for c in cls_of.values())  # complex ids
    seen = {}
    for key, members in groups.items():
        cids = {cls_of[v] for v in members}
        assert len(cids) == 1, f"group {key} split across classes"
        cid = cids.pop()
        assert cid not in seen, f"classes {key} and {seen[cid]} merged"
        seen[cid] = key
        assert st.tyscount[cid] == len(members)


def test_single_typed_fast_path_counts():
    from wukong_tpu.loader.lubm import generate_lubm

    triples, _ = generate_lubm(1, seed=0)
    st = Stats.generate(triples)
    s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]
    want = dict(zip(*np.unique(o[p == TYPE_ID], return_counts=True)))
    for t, c in want.items():
        assert st.tyscount[int(t)] == int(c)
    # one shared class for the literal pools (objects with no out-edges)
    neg = [t for t in st.tyscount if t < 0]
    assert len(neg) == 1
    typed_n = len(np.unique(s[p == TYPE_ID]))
    assert len(st.vtype_ids) == typed_n + st.tyscount[neg[0]]


def _worlds():
    from wukong_tpu.loader.lubm import generate_lubm
    from wukong_tpu.loader.watdiv import generate_watdiv

    return {"lubm1": lambda: generate_lubm(1, seed=0)[0],
            "watdiv1": lambda: generate_watdiv(1, seed=0)[0],
            "big_untyped": _world_with_big_untyped}


def _fields(st: Stats) -> dict:
    return {"vtype_ids": st.vtype_ids.tolist(), "vtype": st.vtype.tolist(),
            "tyscount": st.tyscount, "complex_members": st.complex_members,
            "pred_edges": st.pred_edges, "pstype": st.pstype,
            "potype": st.potype, "distinct_subj": st.distinct_subj,
            "distinct_obj": st.distinct_obj, "fine_type": st.fine_type}


@pytest.mark.parametrize("world", sorted(_worlds()))
def test_tables_by_id_and_sorting_give_the_same_statistics(world,
                                                           monkeypatch):
    """``Stats.generate`` has two ways to the same numbers: tables indexed
    by id where ids are dense (``DENSE_ROOM``), sorting where they are not.
    Every field, to the count, on a typed graph (LUBM), a mostly untyped
    one whose classes are themselves typed (WatDiv) and the large untyped
    population of the signature path."""
    from wukong_tpu.planner import stats

    triples = _worlds()[world]()
    monkeypatch.setattr(stats, "DENSE_ROOM", float("inf"))
    by_id = _fields(Stats.generate(triples))
    monkeypatch.setattr(stats, "DENSE_ROOM", 0.0)
    sorting = _fields(Stats.generate(triples))
    for name in by_id:
        assert by_id[name] == sorting[name], name
    # the rule itself: these generators' ids are dense, a handful of
    # triples over the same id range is not
    monkeypatch.undo()
    room = stats.DENSE_ROOM * triples.nbytes
    assert 8 * (int(triples.max()) + 1) <= room
    assert not 8 * (int(triples.max()) + 1) <= stats.DENSE_ROOM * triples[:100].nbytes
