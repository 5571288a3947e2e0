"""WatDiv (loader/watdiv.py): the data model, the string backend, and the 20
basic-testing templates served through ``Proxy.serve_query`` (TPUEngine on
the CPU backend), each compared row for row with the benchmark's plain
reference."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from wukong_tpu.config import Global  # noqa: E402
from wukong_tpu.loader import watdiv  # noqa: E402
from wukong_tpu.loader.watdiv import (  # noqa: E402
    FIXED,
    MEMBER_CLASSES,
    NS,
    SCALING,
    TEMPLATES,
    P,
    T,
    VirtualWatdivStrings,
    WatdivLayout,
    generate_watdiv,
    write_dataset,
)
from wukong_tpu.types import IN, TYPE_ID  # noqa: E402

SCALE, SEED = 2, 1
NAMED = [f"<{watdiv.WSDBM}{v}>"
         for v in ("Product0", "Country1", "Country5", "Language0")]


@pytest.fixture(scope="module")
def triples():
    return generate_watdiv(SCALE, SEED)[0]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The world as the benchmark's loader builds it, the reference over the
    same triples, and one seeded draw of every template."""
    from benchmark.loaders import watdiv as loader
    from benchmark.reference import Reference
    from benchmark.traffic import Traffic

    world = loader.load(
        {"scale_factor": SCALE, "data_seed": SEED, "named_vertices": NAMED},
        0, str(tmp_path_factory.mktemp("watdiv") / "data"))
    ref = Reference(world.triples, world.index_rows)
    mix = {"loop": "closed", "clients": 1, "warm_draws": 2,
           "classes": [{"name": n, "kind": n[0], "file": f"watdiv/basic/{n}"}
                       for n in TEMPLATES]}
    traffic = Traffic(mix, 2 ** 31 + 5, ref.instances, world.id2str)
    requests = {}
    for req in traffic.warm_pass(0):
        requests.setdefault(req.cls, []).append(req)
    ref.ids.update(traffic.constants())
    return world, ref, requests


# ---------------------------------------------------------------------------
# the data model
# ---------------------------------------------------------------------------

def test_class_counts_and_ratios(triples):
    lay = WatdivLayout(SCALE, SEED)
    for cls, per_unit in SCALING.items():
        assert lay.n[cls] == per_unit * SCALE
    for cls, n in FIXED.items():
        assert lay.n[cls] == n
    assert lay.n["User"] == 4 * lay.n["Product"]
    assert lay.n["Review"] == lay.n["Purchase"] == 6 * lay.n["Product"]
    # about 109 k triples a unit of scale factor, most of them social
    assert 100_000 * SCALE < len(triples) < 120_000 * SCALE
    social = np.isin(triples[:, 1], [P["wsdbm:friendOf"], P["wsdbm:follows"]])
    assert 0.6 < social.mean() < 0.8
    # every instance of a class a placeholder draws from is its member
    for cls in MEMBER_CLASSES:
        members = triples[(triples[:, 1] == TYPE_ID)
                          & (triples[:, 2] == T[cls]), 0]
        assert np.array_equal(np.sort(members), lay.ids(cls))
    cats = np.unique(triples[(triples[:, 1] == TYPE_ID) & np.isin(
        triples[:, 0], lay.ids("Product")), 2])
    assert cats.tolist() == [T[f"ProductCategory{k}"] for k in range(15)]


def test_every_template_predicate_present(triples):
    present = set(np.unique(triples[:, 1]).tolist())
    used = set()
    for text in TEMPLATES.values():
        body = text[text.index("{") + 1:text.index("}")]
        used.update(line.split()[1] for line in body.strip().split("\n"))
    assert len(used) == 47 and "rdf:type" in used, sorted(used)
    for q in used - {"rdf:type"}:
        assert q.split(":")[0] in NS and q in P and P[q] in present, q
    assert len(watdiv.PRED_NAMES) >= 84
    assert len({i for q, i in P.items() if ":" in q}) == len(watdiv.PRED_NAMES)


def test_in_degrees_are_skewed(triples):
    likes = triples[triples[:, 1] == P["wsdbm:likes"], 2]
    deg = np.bincount(likes - likes.min())
    deg = deg[deg > 0]
    # a uniform draw of as many likes over as many products stays within a
    # few times its mean; the Zipfian one does not
    uniform = np.bincount(np.random.default_rng(0).integers(
        0, 250 * SCALE, len(likes)))
    assert deg.max() / deg.mean() > 3 * uniform.max() / uniform.mean()
    lay = WatdivLayout(SCALE, SEED)
    assert int(np.argmax(np.bincount(likes - lay.base["Product"]))) == 0


def test_deterministic_in_scale_and_seed(triples):
    again = generate_watdiv(SCALE, SEED)[0]
    assert np.array_equal(triples, again)
    other = generate_watdiv(SCALE, SEED + 1)[0]
    assert other.shape != triples.shape or not np.array_equal(other, triples)
    assert len(generate_watdiv(1, SEED)[0]) < len(triples)


def test_no_duplicate_triple(triples):
    assert len(np.unique(triples, axis=0)) == len(triples)
    assert triples.min() >= 0 and triples.max() < 2 ** 31 - 1


def test_strings_round_trip_every_class(triples):
    ss = VirtualWatdivStrings(SCALE, SEED)
    lay = ss.lay
    for name in lay.n:
        for k in {0, lay.n[name] // 2, lay.n[name] - 1}:
            vid = lay.base[name] + k
            text = ss.id2str(vid)
            assert text[0] == ('"' if name in lay.literal else "<")
            assert ss.str2id(text) == vid and ss.exist(text)
    for text, i in watdiv.index_strings():
        assert ss.str2id(text) == i and ss.id2str(i) == text
    assert not ss.exist(f"<{watdiv.WSDBM}Product{lay.n['Product']}>")
    assert not ss.exist_id(lay.id_end)
    # every id the data uses has a string
    for vid in np.random.default_rng(0).choice(
            np.unique(triples[:, [0, 2]]), 200):
        assert ss.str2id(ss.id2str(int(vid))) == int(vid)


def test_write_dataset(tmp_path):
    meta = write_dataset(str(tmp_path), 1, seed=2)
    assert (tmp_path / "id_triples.npy").exists()
    assert (tmp_path / "queries" / "S1").exists()
    assert meta["num_triples"] > 100_000
    from wukong_tpu.store.string_server import StringServer

    ss = StringServer(str(tmp_path))
    assert ss.id2str(ss.str2id(f"<{watdiv.WSDBM}Retailer3>")) \
        == f"<{watdiv.WSDBM}Retailer3>"


def test_benchmark_query_files_are_the_templates():
    qdir = os.path.join(ROOT, "benchmark", "queries", "watdiv", "basic")
    assert sorted(os.listdir(qdir)) == sorted(TEMPLATES)
    for name, text in TEMPLATES.items():
        with open(os.path.join(qdir, name)) as f:
            assert f.read() == text, name


# ---------------------------------------------------------------------------
# the templates, served
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(TEMPLATES))
def test_template_served_equals_reference(served, name):
    from benchmark.driver import serve
    from benchmark.reference import sorted_rows

    world, ref, requests = served
    host_steps = []
    cpu_runs = []
    tpu_cpu = world.proxy.tpu.cpu
    step, run = tpu_cpu._execute_one_pattern, world.proxy.cpu.execute
    tpu_cpu._execute_one_pattern = lambda *a, **k: (
        host_steps.append(1), step(*a, **k))[1]
    world.proxy.cpu.execute = lambda *a, **k: (
        cpu_runs.append(1), run(*a, **k))[1]
    try:
        for req in requests[name]:
            r = serve(world.proxy, req)
            assert r.ok, r.status
            want = ref.evaluate(req.text)
            got = sorted_rows(r.rows())
            assert got.shape == want.shape and np.array_equal(got, want)
            assert want.shape[1] == req.text.split("WHERE")[0].count("?")
    finally:
        tpu_cpu._execute_one_pattern, world.proxy.cpu.execute = step, run
    # answered by the device engine alone
    assert not host_steps and not cpu_runs


def test_template_program_binds_each_draws_constants(served):
    """The whole-plan program is cached under a signature that leaves vertex
    constants out: a second draw must not be answered with the first's."""
    from benchmark.driver import serve
    from benchmark.reference import sorted_rows
    from wukong_tpu.engine.template_compile import reset_demotions

    world, ref, requests = served
    reset_demotions()
    Global.template_device = "device"
    try:
        for name in ("L1", "L2", "S2", "F5"):
            for req in requests[name]:
                r = serve(world.proxy, req)
                assert r.ok and r.route == "template.plan", (name, r.route)
                assert np.array_equal(sorted_rows(r.rows()),
                                      ref.evaluate(req.text)), name
    finally:
        Global.template_device = "auto"
        reset_demotions()


def test_traced_reply_names_its_route_and_counts_retries(served):
    world, _ref, requests = served
    Global.enable_tracing = True
    try:
        q = world.proxy.serve_query(requests["S1"][0].text, blind=False)
    finally:
        Global.enable_tracing = False
    events = q.trace.event_names()
    assert events.count("proxy.route") == 1
    assert "device.dispatch" in events
    assert "capacity.retry" not in events  # sized for the heaviest retailer


# ---------------------------------------------------------------------------
# a drawn type (S3, S5: one of the 15 product categories)
# ---------------------------------------------------------------------------

def _category_texts(world, name):
    slot = "%wsdbm:ProductCategory"
    return {k: TEMPLATES[name].replace(
        slot, f"<{watdiv.WSDBM}ProductCategory{k}>")
        for k in range(watdiv.CATEGORIES)}


def test_categories_are_peers_and_the_heaviest_sizes_them(served):
    g = served[0].proxy.g
    cats = [T[f"ProductCategory{k}"] for k in range(watdiv.CATEGORIES)]
    sizes = {c: len(g.get_index(c, IN)) for c in cats}
    assert min(sizes.values()) > 0 and len(set(sizes.values())) > 1
    heaviest = max(cats, key=lambda c: (sizes[c], -c))
    assert {g.heaviest_peer_type(c) for c in cats} == {heaviest}
    # a type that is no instance of a class is its own only peer
    assert g.heaviest_peer_type(T["Role2"]) == T["Role2"]
    assert g.heaviest_peer_type(T["ProductCategory"]) == T["ProductCategory"]
    dstore = served[0].proxy.tpu.dstore
    pads = {int(dstore.index_list(c, IN)[0].shape[0]) for c in cats}
    assert len(pads) == 1 and pads.pop() >= max(sizes.values())


@pytest.mark.parametrize("name", ["S3", "S5"])
def test_a_category_met_later_compiles_nothing(served, name):
    """Every draw of the category runs the walk at the capacity classes of
    the heaviest one: after one category, the other fourteen find every
    kernel compiled (and equal the reference)."""
    from benchmark.reference import sorted_rows
    from wukong_tpu.engine import tpu_kernels as K

    world, ref, _requests = served
    texts = _category_texts(world, name)
    ref.ids.update({f"<{watdiv.WSDBM}ProductCategory{k}>":
                    T[f"ProductCategory{k}"] for k in texts})
    kernels = [K.wk_walk_init_from_list, K.wk_walk_expand,
               K.wk_walk_member_mask_known, K.wk_walk_compact,
               K.wk_walk_compact_to]
    world.proxy.serve_query(texts[0], blind=False)
    before = [k._cache_size() for k in kernels]
    for k in range(1, watdiv.CATEGORIES):
        q = world.proxy.serve_query(texts[k], blind=False)
        rows = np.asarray(q.result.table)[
            :, [q.result.v2c_map[v] for v in q.result.required_vars]]
        assert np.array_equal(sorted_rows(rows.astype(np.int64)),
                              ref.evaluate(texts[k])), k
    assert [k._cache_size() for k in kernels] == before


def test_a_drawn_type_is_demoted_once_for_all_its_peers(served):
    """The compiled route and its latch go by the template's family: the
    first category's small reply demotes S3 for all fifteen, and a category
    met afterwards builds no program."""
    from wukong_tpu.engine.template_compile import (
        demotion_report,
        reset_demotions,
    )

    world, _ref, _requests = served
    texts = _category_texts(world, "S3")
    reset_demotions()
    old = Global.template_min_rows
    Global.template_min_rows = 16  # under a category's list, over a reply
    try:
        q = world.proxy.serve_query(texts[1], blind=False)
        assert q.template_route == "device" and q._template_compiled, \
            (q._template_est_steps, q.result.nrows)
        assert list(demotion_report().values()) == ["small_measured"]
        programs = world.proxy.template_engine().program_count()
        for k in (0, 7, 14):
            q = world.proxy.serve_query(texts[k], blind=False)
            assert q.template_route == "latched_host", k
            assert not getattr(q, "_template_compiled", False)
        assert world.proxy.template_engine().program_count() == programs
        assert len(demotion_report()) == 1
    finally:
        Global.template_min_rows = old
        reset_demotions()


def test_a_type_named_twice_keeps_its_own_plan(served):
    """Peers of a type share a plan recipe, which reads the type from the
    query it is replayed onto; a type named in two places cannot be told
    apart by place, so such a query stays out of the family."""
    from benchmark.reference import sorted_rows

    world, ref, _requests = served
    body = ("?v0 rdf:type CAT . ?v1 rdf:type CAT . "
            "?v0 wsdbm:hasGenre ?v2 . ?v1 wsdbm:hasGenre ?v2")
    for k in (3, 5, 0):
        iri = f"<{watdiv.WSDBM}ProductCategory{k}>"
        text = watdiv._template(body).replace("CAT", iri)
        ref.ids[iri] = T[f"ProductCategory{k}"]
        q = world.proxy.serve_query(text, blind=False)
        rows = np.asarray(q.result.table)[
            :, [q.result.v2c_map[v] for v in q.result.required_vars]]
        want = ref.evaluate(text)
        assert len(want) and np.array_equal(
            sorted_rows(rows.astype(np.int64)), want), k


def test_expanding_an_index_start_by_its_own_predicate_keeps_the_rows(
        triples):
    """``?x p ?y`` from the index of ``p``: every ``?x`` has the predicate,
    so the sizing estimate is the rows times the mean of those that have
    it, not of all vertices of the type (S3's plan: the publisher index,
    the category, then the publisher; read 20 times too low, the heaviest
    category overflowed its class the first time it came)."""
    from wukong_tpu.planner.optimizer import Planner
    from wukong_tpu.planner.stats import Stats
    from wukong_tpu.sparql.ir import Pattern
    from wukong_tpu.types import OUT, PREDICATE_ID

    pub, cat = P["sorg:publisher"], T["ProductCategory0"]
    est = Planner(Stats.generate(triples)).estimate_chain([
        Pattern(pub, PREDICATE_ID, IN, -1), Pattern(-1, TYPE_ID, OUT, cat),
        Pattern(-1, pub, OUT, -2)])
    s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]
    have = np.intersect1d(s[p == pub], s[(p == TYPE_ID) & (o == cat)])
    assert est[0] == len(np.unique(s[p == pub]))
    assert est[1] == pytest.approx(len(have)) and len(have) > 0
    assert est[2] == pytest.approx(len(have))  # one publisher a product
