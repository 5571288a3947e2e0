"""LDBC SNB as LSQB reads it (loader/snb.py): the generator is deterministic,
``knows`` is symmetric and correlated by place, every edge keeps its domain
and range, every node is typed and a message twice, the string backend
round-trips, and a bundle written and booted serves the same rows."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from wukong_tpu.loader import snb  # noqa: E402
from wukong_tpu.loader.snb import (  # noqa: E402
    CLASSES,
    EDGES,
    FIXED,
    MESSAGE,
    QUERIES,
    P,
    T,
    SnbLayout,
    VirtualSnbStrings,
    generate_snb,
)
from wukong_tpu.types import NORMAL_ID_START, TYPE_ID  # noqa: E402

SF, SEED = 0.03, 1


@pytest.fixture(scope="module")
def made():
    return generate_snb(SF, SEED)


def _edges(triples, name):
    rows = triples[triples[:, 1] == P[name]]
    return rows[:, 0], rows[:, 2]


def test_schema_marker_and_source_sizes():
    assert snb.SCHEMA == "ldbc-snb-lsqb-1"
    assert [snb.persons_at(sf) for sf in (1, 3, 10)] == \
        [11_000, 27_000, 73_000]
    assert (FIXED["Country"], FIXED["City"], FIXED["Continent"],
            FIXED["Tag"], FIXED["TagClass"]) == (111, 1_343, 6, 16_080, 71)
    assert len(CLASSES) == 11 and len(EDGES) == 15
    assert snb.ASSUMED["knows_shares"] == (0.45, 0.45, 0.10)
    # the degree grows with the scale, as Datagen's does
    assert snb.mean_degree(11_000) < snb.mean_degree(27_000) < \
        snb.mean_degree(73_000)
    lay = SnbLayout(10)  # the sizes of the cell's scale, without making it
    assert 29.5e6 < sum(lay.n.values()) < 30.5e6
    assert abs(lay.n["Post"] - 7.4e6) < 1e5 and \
        abs(lay.n["Comment"] - 21.9e6) < 2e5


@pytest.mark.parametrize("other,same", [((SF, SEED), True),
                                        ((SF, SEED + 1), False),
                                        ((0.02, SEED), False)])
def test_deterministic_in_scale_factor_and_seed(made, other, same):
    again = generate_snb(*other)[0]
    assert (again.shape == made[0].shape
            and np.array_equal(again, made[0])) is same


def test_meta_counts_what_was_made(made):
    triples, meta = made
    assert meta["schema"] == snb.SCHEMA
    assert meta["num_triples"] == len(triples)
    assert meta["num_nodes"] == sum(meta["nodes"].values())
    assert meta["num_edges"] == len(triples) - meta["edges"]["rdf:type"]
    for name, pid in P.items():
        assert meta["edges"][name] == int((triples[:, 1] == pid).sum()) > 0
    assert triples.dtype == np.int64 and int(triples.max()) < 2 ** 31 - 1
    assert int(triples[:, [0, 2]].min()) > 1


def test_knows_is_symmetric_simple_and_heavy_tailed(made):
    s, o = _edges(made[0], "knows")
    n = made[1]["layout"].n["Person"]
    fwd = np.sort(s * (1 << 32) + o)
    assert np.array_equal(fwd, np.sort(o * (1 << 32) + s))  # both directions
    assert len(np.unique(fwd)) == len(fwd) and not np.any(s == o)
    deg = np.bincount(s - NORMAL_ID_START, minlength=n)
    assert 0.6 * snb.mean_degree(n) < deg.mean() < 1.4 * snb.mean_degree(n)
    assert deg.max() > 2.0 * deg.mean()


def test_friends_share_a_country_far_more_often_than_chance(made):
    triples, meta = made
    lay = meta["layout"]
    ps, pc = _edges(triples, "isLocatedIn")
    person = ps < lay.base["City"]
    city_of = dict(zip(ps[person].tolist(), pc[person].tolist()))
    cs, cc = _edges(triples, "isPartOf")
    country_of = dict(zip(cs.tolist(), cc.tolist()))
    home = np.array([country_of[city_of[p]] for p in lay.ids("Person")])
    s, o = _edges(triples, "knows")
    same = (home[s - lay.base["Person"]] == home[o - lay.base["Person"]])
    share = np.bincount(home - lay.base["Country"]) / len(home)
    assert same.mean() > 3 * float(share @ share)


@pytest.mark.parametrize("name", sorted(EDGES))
def test_every_edge_keeps_its_domain_and_range(made, name):
    lay = made[1]["layout"]
    s, o = _edges(made[0], name)
    domain, rng = EDGES[name]

    def inside(ids, classes):
        ok = np.zeros(len(ids), dtype=bool)
        for c in classes:
            ok |= (ids >= lay.base[c]) & (ids < lay.base[c] + lay.n[c])
        return ok

    assert inside(s, domain).all() and inside(o, rng).all()
    if name == "isLocatedIn":  # Person -> City, Message -> Country
        person = inside(s, ("Person",))
        assert inside(o[person], ("City",)).all()
        assert inside(o[~person], ("Country",)).all()
    if name == "isPartOf":  # City -> Country -> Continent
        city = inside(s, ("City",))
        assert inside(o[city], ("Country",)).all()
        assert inside(o[~city], ("Continent",)).all()
    if name == "replyOf":  # one parent a comment, an earlier message
        assert len(np.unique(s)) == len(s) == lay.n["Comment"]
        to_comment = inside(o, ("Comment",))
        assert (o[to_comment] < s[to_comment]).all()
        assert 0.3 < (~to_comment).mean() < 0.8


def test_every_node_is_typed_and_a_message_twice(made):
    triples, meta = made
    lay = meta["layout"]
    typed = triples[triples[:, 1] == TYPE_ID]
    for cls in CLASSES:
        mine = np.sort(typed[typed[:, 2] == T[cls], 0])
        assert np.array_equal(mine, lay.ids(cls)), cls
    messages = np.sort(typed[typed[:, 2] == T["Message"], 0])
    assert np.array_equal(messages, np.concatenate(
        [lay.ids(c) for c in MESSAGE]))
    assert len(typed) == meta["num_nodes"] + len(messages)
    # one creator, one container or parent, one country a message
    for name, cls in (("hasCreator", "Post"), ("hasCreator", "Comment"),
                      ("isLocatedIn", "Post"), ("isLocatedIn", "Comment")):
        s, _o = _edges(triples, name)
        mine = s[(s >= lay.base[cls]) & (s < lay.base[cls] + lay.n[cls])]
        assert np.array_equal(np.sort(mine), lay.ids(cls))
    _f, posts = _edges(triples, "containerOf")
    assert np.array_equal(np.sort(posts), lay.ids("Post"))


def test_comments_are_mostly_by_friends_of_the_posts_author(made):
    triples, meta = made
    lay = meta["layout"]
    creator = np.zeros(lay.id_end, dtype=np.int64)
    ms, mp = _edges(triples, "hasCreator")
    creator[ms] = mp
    cs, co = _edges(triples, "replyOf")
    direct = co < lay.base["Comment"]
    ks, ko = _edges(triples, "knows")
    friends = np.sort(ks * (1 << 32) + ko)
    key = creator[cs[direct]] * (1 << 32) + creator[co[direct]]
    at = np.minimum(np.searchsorted(friends, key), len(friends) - 1)
    assert (friends[at] == key).mean() > 0.6


def test_virtual_strings_round_trip(tmp_path):
    from wukong_tpu.store.string_server import StringServer

    snb.write_string_tables(str(tmp_path), SF, SEED)
    ss = StringServer(str(tmp_path))
    vs = VirtualSnbStrings(SF, SEED)
    lay = vs.lay
    for cls in CLASSES:
        for vid in (lay.base[cls], lay.base[cls] + lay.n[cls] - 1):
            s = ss.id2str(vid)
            assert s == f"<{snb.DATA}{cls}{vid - lay.base[cls]}>"
            assert ss.str2id(s) == vid and ss.exist(s) and ss.exist_id(vid)
    assert ss.str2id(f"<{snb.SNB}knows>") == P["knows"]
    assert ss.str2id(f"<{snb.SNB}Message>") == T["Message"]
    assert ss.id2str(TYPE_ID) == snb.RDF_TYPE_STR
    assert not ss.exist(f"<{snb.DATA}Person{lay.n['Person']}>")
    assert not ss.exist_id(lay.id_end)


def test_query_texts_hold_the_sources_patterns():
    assert set(QUERIES) == {"q2", "q3"}
    assert QUERIES["q2"].count(" .\n") == 8 and \
        QUERIES["q3"].count(" .\n") == 16
    for label in ("snb:Comment", "snb:Post", "snb:Person", "snb:knows",
                  "snb:replyOf", "snb:hasCreator"):
        assert label in QUERIES["q2"]
    assert QUERIES["q3"].count("snb:knows") == 3
    assert QUERIES["q3"].count("snb:isPartOf ?country") == 3


def test_a_bundle_written_and_booted_gives_the_same_rows(tmp_path):
    """The cold start over the generator: the first boot builds and saves,
    the second loads, and both serve q2 and q3 with the same rows."""
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.runtime.boot import boot_store, snb_source
    from wukong_tpu.runtime.proxy import Proxy
    from wukong_tpu.store.persist import gstore_digest

    def served(booted):
        g, ss = booted.store, booted.str_server
        proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
        proxy.planner = booted.planner
        proxy.tpu.stats = proxy.planner.stats
        out = {}
        for name, text in QUERIES.items():
            res = proxy.serve_query(text, blind=False).result
            cols = [res.v2c_map[v] for v in res.required_vars]
            rows = np.asarray(res.table)[:, cols]
            out[name] = rows[np.lexsort(rows.T[::-1])]
        return out

    d = str(tmp_path / "snb")
    first = boot_store(snb_source(0.02, SEED, d), d)
    second = boot_store(snb_source(0.02, SEED, d), d)
    assert not first.from_bundle and second.from_bundle
    assert {"boot.build", "boot.save"} <= set(first.phases)
    assert set(second.phases) == {"boot.bundle_load", "boot.stats_load"}
    assert gstore_digest(first.store) == gstore_digest(second.store)
    a, b = served(first), served(second)
    for name in QUERIES:
        assert len(a[name]) > 0 and np.array_equal(a[name], b[name]), name
    # another seed is another bundle, beside the first
    third = boot_store(snb_source(0.02, SEED + 1, d), d)
    assert not third.from_bundle and third.bundle_path != first.bundle_path
