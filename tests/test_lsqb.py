"""LSQB's q2 and q3 through ``Proxy.serve_query`` at some 900 persons, each
route compared row for row, as sorted multisets, with the benchmark's plain
reference: the walk, the worst-case-optimal join with host and with device
levels, a whole-plan template program, and the program's own choice; a level
forced through more than one slice and more than one run of prefix rows; the
spans, the ``join.level`` event and the counters of a traced reply; and the
faults the scale-factor-10 deployment found, one test each; the lookups by
a table over the id range in every level, both sides of
``direct_lookup_wins`` reached and counted, one program for any list; and
the two programs of a level made on the device, each call beside its NumPy
twin on the patterns' own levels, the levels counted where they are made."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from wukong_tpu.config import Global  # noqa: E402
from wukong_tpu.join import kernels  # noqa: E402
from wukong_tpu.join import wcoj as wcoj_mod  # noqa: E402
from wukong_tpu.loader import snb  # noqa: E402
from wukong_tpu.obs.metrics import get_registry  # noqa: E402
from wukong_tpu.types import IN  # noqa: E402

SF, SEED = 0.05, 1
KNOBS = ("join_strategy", "join_device", "template_device",
         "join_device_min_candidates", "wcoj_ratio", "wcoj_min_rows",
         "enable_tracing")
# route -> (join_strategy, join_device, template_device)
ROUTES = {
    "walk": ("walk", "auto", "host"),
    "wcoj-host": ("wcoj", "host", "host"),
    "wcoj-device": ("wcoj", "device", "host"),
    "template": ("walk", "auto", "device"),
    "auto": ("auto", "auto", "auto"),
}


@pytest.fixture(autouse=True)
def knobs():
    saved = {k: getattr(Global, k) for k in KNOBS}
    yield
    for k, v in saved.items():
        setattr(Global, k, v)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """The served system as ``runtime/console.py`` builds it, and what the
    plain reference owes each text."""
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.planner.optimizer import make_planner
    from wukong_tpu.runtime.proxy import Proxy
    from wukong_tpu.store.gstore import build_partition
    from wukong_tpu.store.string_server import StringServer

    from benchmark.reference import Reference

    triples, _meta = snb.generate_snb(SF, SEED)
    d = str(tmp_path_factory.mktemp("snb"))
    snb.write_string_tables(d, SF, SEED)
    ss = StringServer(d)
    g = build_partition(triples, 0, 1)
    proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    proxy.planner = make_planner(triples, None)
    proxy.tpu.stats = proxy.planner.stats
    ref = Reference(triples, snb.index_strings())
    owed = {name: ref.evaluate(text) for name, text in snb.QUERIES.items()}
    return proxy, owed


def rows_of(q):
    from benchmark.reference import sorted_rows

    res = q.result
    assert int(res.status_code) == 0 and res.complete, res.status_code
    cols = [res.v2c_map[v] for v in res.required_vars]
    return sorted_rows(np.asarray(res.table)[:, cols])


def serve(proxy, name, route):
    Global.join_strategy, Global.join_device, Global.template_device = \
        ROUTES[route]
    return proxy.serve_query(snb.QUERIES[name], blind=False)


def test_the_reference_owes_rows(world):
    _proxy, owed = world
    assert owed["q2"].shape[1] == 4 and len(owed["q2"]) > 10_000
    assert owed["q3"].shape[1] == 3 and len(owed["q3"]) > 1_000
    assert len(owed["q3"]) % 6 == 0  # six ordered triples a triangle


def test_the_walk_outlasts_eight_attempts_on_q3(world):
    """q3 is 16 steps whose later estimates are under one row: the chain
    needs nine attempts here, and the limit was eight whatever the
    length (the reply was UNKNOWN_PATTERN, 'capacity retry limit
    exceeded')."""
    proxy, owed = world
    Global.enable_tracing = True
    q = serve(proxy, "q3", "walk")
    chain = [sp for sp in q.trace.spans if sp.name == "tpu.chain"]
    assert len(chain) == 1 and np.array_equal(rows_of(q), owed["q3"])
    # the module's first walk of q3: no class is remembered yet
    assert 8 < chain[0].attrs["attempts"] <= 16 + 2


@pytest.mark.parametrize("name", ["q2", "q3"])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_every_route_equals_the_reference(world, route, name):
    from benchmark.driver import route_of

    proxy, owed = world
    q = serve(proxy, name, route)
    got = rows_of(q)
    assert got.shape == owed[name].shape and np.array_equal(got, owed[name])
    took = route_of(q)
    if route == "walk":
        assert took == "walk"
    elif route == "template":
        assert took == "template.plan"  # the plan is accepted
    elif route.startswith("wcoj"):
        assert took == "wcoj:" + route.split("-")[1]
        levels = q.join_stats
        assert len(levels) == (4 if name == "q2" else 7)
        assert levels[-1]["rows_out"] == len(owed[name])
    else:  # the program's own choice: both patterns are cyclic
        assert q.join_strategy == "wcoj" and took.startswith("wcoj")
    assert not getattr(q, "_join_device_broken", False)


@pytest.mark.parametrize("name", ["q2", "q3"])
def test_a_level_in_slices_and_runs_of_rows(world, name, monkeypatch):
    """``LEVEL_SLICE`` at 2,048: every level over 2,048 candidates is probed
    in slices of that one size, every level over 8,192 a run of prefix rows
    at a time, and the rows are those of the level taken whole."""
    proxy, owed = world
    Global.enable_tracing = True
    whole = serve(proxy, name, "wcoj-device")
    monkeypatch.setattr(kernels, "LEVEL_SLICE", 2048)
    cut = serve(proxy, name, "wcoj-device")
    assert np.array_equal(rows_of(cut), owed[name])
    for a, b in zip(whole.join_stats, cut.join_stats):
        assert (a["candidates"], a["rows_out"], a["route"]) == \
            (b["candidates"], b["rows_out"], b["route"])
    widest = max(cut.join_stats, key=lambda lv: lv["candidates"])
    assert widest["candidates"] > 4 * 2048 and widest["route"] == "device"
    # its slots: whole slices and one class of what is left a group and run
    assert widest["candidates"] <= widest["slots"] < \
        widest["candidates"] + 2048 * 2 * (widest["candidates"] // 8192 + 2)

    def dispatches(q):
        return sum(1 for n in q.trace.event_names() if n == "device.dispatch")

    assert dispatches(cut) >= widest["candidates"] // 2048
    assert dispatches(cut) > dispatches(whole)
    names = [sp.name for sp in cut.trace.spans]
    assert names.count("wcoj.probe.sync") > names.count("wcoj.level")


def test_a_level_of_one_run_makes_one_call_a_group(world, monkeypatch):
    """Up to ``LEVEL_CHUNK_SLICES`` slices of candidates a level is one call
    of ``wk_level_probe`` a generator group at the ``pad_pow2`` class of its
    candidates, the calls dispatched together and their survivors fetched
    after; a level in runs cuts a group into slices and fetches a run
    later. Either looks its
    ranges up once, passing every adjacency's cached ``id_bound``, and the
    store's vertex bound for its list: the form of a lookup follows the
    call's shapes (``direct_lookup_wins``), not the level's size."""
    proxy, owed = world
    probes, ranges = [], []
    real_probe, real_ranges = wcoj_mod.jit_level_probe, \
        wcoj_mod.jit_level_ranges

    def spy_probe(gen, depths, has_list, list_bound, out_cap, rows_cap,
                  used=None):
        probes.append((gen, depths, has_list, list_bound, out_cap))
        return real_probe(gen, depths, has_list, list_bound, out_cap,
                          rows_cap, used)

    def spy_ranges(id_bounds, anchor_of, has_list, used=None):
        ranges.append((id_bounds, anchor_of, has_list))
        return real_ranges(id_bounds, anchor_of, has_list, used)

    monkeypatch.setattr(wcoj_mod, "jit_level_probe", spy_probe)
    monkeypatch.setattr(wcoj_mod, "jit_level_ranges", spy_ranges)
    Global.enable_tracing = True
    vbound = wcoj_mod.store_vertex_bound(proxy.g)

    def passes_its_bounds():
        assert probes and ranges and not getattr(q, "_join_device_broken",
                                                 False)
        for bounds, anchor_of, _has_list in ranges:
            assert isinstance(bounds, tuple) and len(bounds) == len(anchor_of)
            assert all(isinstance(b, int) and 0 < b <= vbound for b in bounds)
        for _gen, _depths, has_list, list_bound, _cap in probes:
            assert list_bound in (None, vbound)
            assert list_bound == vbound or not has_list

    q = serve(proxy, "q3", "wcoj-device")
    passes_its_bounds()
    made = [lv for lv in q.join_stats if lv["enumerated"] == "device"]
    assert len(ranges) == len(made) > 0
    calls = [d for d in q.device_steps if d.get("site") == "wcoj.probe"]
    assert calls and all(d["capacity"] == kernels.pad_pow2(d["live"])
                         for d in calls)
    names = [sp.name for sp in q.trace.spans]
    at = [i for i, n in enumerate(names) if n == "wcoj.probe.dispatch"]
    # a run's calls are dispatched together, then fetched in order
    assert at and all(names[i + 1] in ("wcoj.probe.dispatch",
                                       "wcoj.probe.sync") for i in at)
    whole = {p[:4] for p in probes}
    del probes[:]
    monkeypatch.setattr(kernels, "LEVEL_SLICE", 2048)
    q = serve(proxy, "q3", "wcoj-device")
    assert np.array_equal(rows_of(q), owed["q3"])
    passes_its_bounds()
    assert {p[:4] for p in probes} == whole  # the same groups, other classes


@pytest.mark.parametrize("n,want", [
    (0, [(0, 0, 1024)]),
    (5, [(0, 5, 1024)]),
    (1 << 22, [(0, 1 << 22, 1 << 22)]),
    ((1 << 22) + 1, [(0, 1 << 22, 1 << 22), (1 << 22, (1 << 22) + 1, 1024)]),
    (3 * (1 << 22) + 70_000,
     [(k << 22, (k + 1) << 22, 1 << 22) for k in range(3)]
     + [(3 << 22, (3 << 22) + 70_000, 1 << 17)]),
    (87_000_000, None),
])
def test_level_slices(n, want):
    got = kernels.level_slices(n)
    if want is not None:
        assert got == want
    # LSQB q3's widest level at scale factor 10: twenty slices of 2^22 and
    # what is left, never one tensor of 2^27 slots
    assert sum(hi - lo for lo, hi, _cp in got) == n
    assert all(hi - lo <= cp <= kernels.LEVEL_SLICE for lo, hi, cp in got)
    assert all(a[1] == b[0] for a, b in zip(got, got[1:]))
    if n == 87_000_000:
        assert len(got) == 21 and sum(cp for *_x, cp in got) < 1.1 * n


@pytest.mark.parametrize("counts,limit_slices,want", [
    ([], 4, [(0, 0)]),
    ([3, 3, 3], 4, [(0, 3)]),
    ([5000] * 4, 1, [(0, 1), (1, 2), (2, 3), (3, 4)]),
    ([4000, 4000, 4000, 100, 9000, 1], 4, [(0, 2), (2, 4), (4, 5), (5, 6)]),
    ([0, 0, 20000, 0], 4, [(0, 2), (2, 3), (3, 4)]),
])
def test_row_chunks(counts, limit_slices, want, monkeypatch):
    monkeypatch.setattr(kernels, "LEVEL_SLICE", 2048)
    monkeypatch.setattr(wcoj_mod, "LEVEL_CHUNK_SLICES", limit_slices)
    got = wcoj_mod._row_chunks(np.asarray(counts, dtype=np.int64))
    assert got == want
    assert got[0][0] == 0 and got[-1][1] == len(counts)


def _family(name, label="route"):
    snap = get_registry().snapshot()
    return {s["labels"][label]: s["value"]
            for s in (snap.get(name) or {}).get("series", [])}


@pytest.mark.parametrize("name", ["q2", "q3"])
def test_spans_event_and_counters_of_a_traced_reply(world, name):
    proxy, owed = world
    before = {m: _family(m) for m in ("wukong_join_level_candidates_total",
                                      "wukong_join_level_slots_total")}
    Global.enable_tracing = True
    Global.join_device_min_candidates = 4096  # small levels stay on the host
    q = serve(proxy, name, "auto")
    assert q.join_route == "device"
    spans = q.trace.spans
    names = [sp.name for sp in spans]
    levels = [sp for sp in spans if sp.name == "wcoj.level"]
    assert names.count("wcoj.execute") == 1
    assert len(levels) == len(q.join_stats)
    assert [sp.attrs["level"] for sp in levels] == list(range(len(levels)))
    execute = next(sp for sp in spans if sp.name == "wcoj.execute")
    assert all(sp.parent == execute.index for sp in levels)
    for sp in spans:  # a level's parts lie inside a level
        if sp.name in ("wcoj.enumerate", "wcoj.probe.stage",
                       "wcoj.probe.dispatch", "wcoj.probe.sync",
                       "wcoj.compact", "wcoj.probe.host"):
            assert spans[sp.parent].name == "wcoj.level", sp.name
    events = [(n, a) for sp in spans for _t, n, a in sp.events
              if n == "join.level"]
    assert len(events) == len(levels)
    routes = set()
    for (_n, a), lv, sp in zip(events, q.join_stats, levels):
        assert set(a) == {"var", "candidates", "slots", "rows_out", "route",
                          "direct", "searched", "enumerated"}
        assert (a["var"], a["candidates"], a["slots"], a["rows_out"],
                a["route"], a["direct"], a["searched"], a["enumerated"]) == (
                    lv["var"], lv["candidates"], lv["slots"], lv["rows_out"],
                    lv["route"], lv["direct"], lv["searched"],
                    lv["enumerated"])
        if a["route"] == "host":
            assert a["direct"] == a["searched"] == 0
        # a device-route level with a bound adjacency (every level but the
        # first here) makes its candidates on the chip
        assert (a["enumerated"] == "device") == (
            a["route"] == "device" and lv["level"] > 0)
        inside = [s.name for s in spans if s.parent == sp.index]
        # the host enumerates first, or the device level ships its anchors
        assert inside[0] in ("wcoj.enumerate", "wcoj.probe.stage")
        assert "wcoj.enumerate" in inside
        # a device level stages; it dispatches and syncs unless its one
        # constraint is its own generator (q2's first level)
        assert ("wcoj.probe.stage" in inside) == (a["route"] == "device")
        assert ("wcoj.probe.dispatch" in inside) == \
            ("wcoj.probe.sync" in inside)
        if a["route"] == "host":
            assert "wcoj.probe.dispatch" not in inside
        assert a["slots"] >= a["candidates"]
        routes.add(a["route"])
    assert events[-1][1]["rows_out"] == len(owed[name])
    assert routes == {"host", "device"} if name == "q3" else "device" in routes
    for metric, key in (("wukong_join_level_candidates_total", "candidates"),
                        ("wukong_join_level_slots_total", "slots")):
        now = _family(metric)
        for route in ("host", "device"):
            rose = now.get(route, 0) - before[metric].get(route, 0)
            assert rose == sum(lv[key] for lv in q.join_stats
                               if lv["route"] == route), (metric, route)
    assert "wk:" not in "".join(names)  # the annotation is the profile's


@pytest.mark.parametrize("route", ["wcoj-host", "wcoj-device"])
@pytest.mark.parametrize("name", ["q2", "q3"])
def test_the_host_compaction_has_its_span(world, name, route, monkeypatch):
    """A level's host work is covered by its children: ``wcoj.compact``
    once a run of rows with candidates (its survivors kept) and once for
    the level (the concatenation into the next prefix), ``wcoj.probe.host``
    once a run a host-route level probes. ``LEVEL_SLICE`` at 2,048 takes
    the wider levels in runs."""
    proxy, owed = world
    Global.enable_tracing = True
    monkeypatch.setattr(kernels, "LEVEL_SLICE", 2048)
    q = serve(proxy, name, route)
    assert np.array_equal(rows_of(q), owed[name])
    spans = q.trace.spans
    levels = [sp for sp in spans if sp.name == "wcoj.level"]
    assert len(levels) == len(q.join_stats)
    most_runs = 0
    for sp, lv in zip(levels, q.join_stats):
        inside = [s.name for s in spans if s.parent == sp.index]
        runs = inside.count("wcoj.enumerate") - 1  # less the choice
        with_candidates = runs if lv["candidates"] else 0
        assert inside.count("wcoj.compact") == with_candidates + 1
        assert inside.count("wcoj.probe.host") == (
            with_candidates if lv["route"] == "host" else 0)
        assert inside[-1] == "wcoj.compact"  # the concatenation, last
        most_runs = max(most_runs, runs)
    assert most_runs > 1
    for sp in spans:
        if sp.name in ("wcoj.compact", "wcoj.probe.host"):
            assert spans[sp.parent].name == "wcoj.level"


def test_the_level_programs_have_stable_names():
    import jax.numpy as jnp

    fn = kernels.jit_level_ranges((4,), (0,), False)
    assert fn.__wrapped__.__name__ == "wk_level_ranges"
    assert fn is kernels.jit_level_ranges((4,), (0,), False)
    anchors = jnp.zeros((1, 8), dtype=jnp.int32)
    keys, offsets = jnp.arange(4, dtype=jnp.int32), \
        jnp.arange(5, dtype=jnp.int32)
    assert "wk_level_ranges" in fn.lower(anchors, 0, keys, offsets).as_text(
        debug_info=True)
    fn = kernels.jit_level_probe(0, (3,), False, None, 8, 8)
    assert fn.__wrapped__.__name__ == "wk_level_probe"
    assert fn is kernels.jit_level_probe(0, (3,), False, 7, 8, 8)  # no list
    args = [jnp.zeros(8, dtype=jnp.int8), jnp.zeros((1, 8), dtype=jnp.int32),
            jnp.ones((1, 8), dtype=jnp.int32), jnp.array([0, 0, 8, 0]),
            jnp.zeros(1, dtype=jnp.int32), jnp.arange(8, dtype=jnp.int32)]
    assert "wk_level_probe" in fn.lower(*args).as_text(debug_info=True)


def _adjacency(rng, nkeys, id_bound, max_deg=9, values=16):
    """A CSR adjacency of ``nkeys`` keys under ``id_bound``, each with a
    sorted run of up to ``max_deg - 1`` distinct values under ``values``."""
    keys = np.sort(rng.choice(id_bound, nkeys, replace=False))
    deg = rng.integers(0, max_deg, nkeys)
    offsets = np.concatenate([[0], np.cumsum(deg)])
    edges = np.concatenate([np.sort(rng.choice(values, d, replace=False))
                            for d in deg]).astype(np.int64)
    return keys, offsets, edges


def _i32(*arrays):
    import jax.numpy as jnp

    return [jnp.asarray(np.asarray(a).astype(np.int32)) for a in arrays]


@pytest.mark.parametrize("rows", [64, 1 << 18])
def test_ranges_that_address_their_keys_equal_ranges_that_search(rows):
    """``wk_level_ranges``: at 2^18 prefix rows over 3,000 keys an
    adjacency's key lookup addresses a table over the id range
    (``direct_lookup_wins``), at 64 it searches; both equal the NumPy
    ranges, the generator choice and the minimum degree included, with an
    anchor that is no key, and -1 (a padding row), of degree 0."""
    rng = np.random.default_rng(rows)
    nkeys, id_bound = 3_000, 5_000
    first = _adjacency(rng, nkeys, id_bound)
    second = _adjacency(rng, nkeys, id_bound)
    anchors = rng.integers(-1, id_bound + 50, (2, rows))
    tables = [first[:2], second[:2]]
    want = kernels.level_ranges(anchors, tables, (1, 0), 4)
    assert kernels.direct_lookup_wins(rows, nkeys, id_bound) == (rows > 64)
    fn = kernels.jit_level_ranges((id_bound, id_bound), (1, 0), True)
    got = fn(*_i32(anchors), 4, *_i32(*first[:2], *second[:2]))
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(g), w)
    assert np.asarray(got[2]).dtype == np.int8
    assert (want[3] == 0).any() and (want[3] > 0).any()
    if rows > 64:  # every generator is somebody's: both, and the list
        assert set(np.unique(want[2])) == {0, 1, 2}


def _lookup_forms():
    got = _family("wukong_join_probe_lookups_total", "form")
    return got.get("direct", 0), got.get("search", 0)


def _scatters(fn, *args):
    """The tables a program builds: its scatters that set at sorted
    indices (the compaction sets at unsorted ones, the spread adds)."""
    import re

    import jax

    return sum("indices_are_sorted=True" in p for p in re.findall(
        r" scatter\[([^\]]*)\]", str(jax.make_jaxpr(fn)(*args))))


@pytest.mark.parametrize("name", ["q2", "q3"])
def test_the_level_programs_equal_their_numpy_twins_level_by_level(
        world, name, monkeypatch):
    """Every call of the two level programs that a pattern's levels make
    equals the NumPy twin (``kernels.level_ranges``, ``level_probe``) on
    the same operands, survivor for survivor; the forms the executor
    counts are the forms the programs were traced with: one sorted scatter
    a table."""
    proxy, owed = world
    real_probe, real_ranges = wcoj_mod.jit_level_probe, \
        wcoj_mod.jit_level_ranges
    lookups, scatters = [], []

    def host(a):
        return np.asarray(a).astype(np.int64)

    def spy_ranges(id_bounds, anchor_of, has_list, used=None):
        fn = real_ranges(id_bounds, anchor_of, has_list, used)

        def ranges(anchors, list_len, *tables):
            got = fn(anchors, list_len, *tables)
            t = [host(a) for a in tables]
            want = kernels.level_ranges(host(anchors),
                                        list(zip(t[::2], t[1::2])), anchor_of,
                                        list_len if has_list else None)
            for w, g in zip(want, got):
                assert np.array_equal(np.asarray(g), w)
            lookups.append(len(id_bounds))
            scatters.append(_scatters(fn, anchors, list_len, *tables))
            return got

        return ranges

    def spy_probe(gen, depths, has_list, list_bound, out_cap, rows_cap,
                  used=None):
        fn = real_probe(gen, depths, has_list, list_bound, out_cap, rows_cap,
                        used)

        def probe(choice, starts, degs, window, glob, *edges):
            got = fn(choice, starts, degs, window, glob, *edges)
            want = kernels.level_probe(
                np.asarray(choice), host(starts), host(degs), host(window),
                host(glob), [host(e) for e in edges], gen, depths, has_list,
                out_cap, rows_cap=rows_cap)
            count = int(got[2])
            assert count == int(want[2]) and count > 0
            for w, g in zip(want[:2], got[:2]):
                assert np.array_equal(np.asarray(g)[:count], w[:count])
            lookups.append(int(has_list))
            scatters.append(_scatters(fn, choice, starts, degs, window, glob,
                                      *edges))
            return got

        return probe

    monkeypatch.setattr(wcoj_mod, "jit_level_probe", spy_probe)
    monkeypatch.setattr(wcoj_mod, "jit_level_ranges", spy_ranges)
    before = _lookup_forms()
    q = serve(proxy, name, "wcoj-device")
    assert np.array_equal(rows_of(q), owed[name])
    assert not getattr(q, "_join_device_broken", False)
    direct = sum(lv["direct"] for lv in q.join_stats)
    searched = sum(lv["searched"] for lv in q.join_stats)
    assert (direct + searched, direct) == (sum(lookups), sum(scatters))
    now = _lookup_forms()
    assert (now[0] - before[0], now[1] - before[1]) == (direct, searched)
    # at these sizes every lookup of every level addresses a table
    assert direct > 0 and searched == 0


def test_both_sides_of_the_rule_are_reached_and_counted(world, monkeypatch):
    """q2 taken whole addresses a table in every lookup; cut into slices of
    2,048 candidates, a slice over the list of some 1.6 x 10^5 comments
    searches it (the scatter of the list would cost more than 18 rounds over
    2,048 slots) while the 900 keys of ``knows`` stay a table: both forms
    in one reply, the rows the same."""
    proxy, owed = world
    comments = len(proxy.g.get_index(snb.T["Comment"], IN))
    vbound = wcoj_mod.store_vertex_bound(proxy.g)
    assert kernels.direct_lookup_wins(1 << 18, comments, vbound)
    assert not kernels.direct_lookup_wins(2048, comments, vbound)
    before = _lookup_forms()
    whole = serve(proxy, "q2", "wcoj-device")
    mid = _lookup_forms()
    assert mid[0] > before[0] and mid[1] == before[1]
    monkeypatch.setattr(kernels, "LEVEL_SLICE", 2048)
    cut = serve(proxy, "q2", "wcoj-device")
    assert np.array_equal(rows_of(cut), rows_of(whole))
    after = _lookup_forms()
    direct = sum(lv["direct"] for lv in cut.join_stats)
    searched = sum(lv["searched"] for lv in cut.join_stats)
    assert direct > 0 and searched > 0
    assert (after[0] - mid[0], after[1] - mid[1]) == (direct, searched)


def test_one_probe_program_for_any_list_of_a_length():
    """The table's bound is the store's, not the list's: two lists of one
    length and different last ids run one compiled program (the length
    specialises it, as it always did; the values must not)."""
    bound, rows = 5_000, 4096
    rng = np.random.default_rng(35)
    low = np.sort(rng.choice(1_000, 300, replace=False))
    high = np.sort(rng.choice(np.arange(2_000, bound), 300, replace=False))
    assert low[-1] != high[-1]
    assert kernels.direct_lookup_wins(rows, 300, bound)
    keys, offsets, edges = _adjacency(rng, 600, 1_000, max_deg=12,
                                      values=bound)
    anchors = rng.integers(0, 1_000, (1, 512))
    starts, degs, choice, mins = kernels.level_ranges(
        anchors, [(keys, offsets)], (0,), None)
    assert 0 < int(mins.sum()) <= rows
    fn = kernels.jit_level_probe(0, (5,), True, bound, rows, 512)
    assert fn is kernels.jit_level_probe(0, (5,), True, bound, rows, 512)
    was = fn._cache_size()
    window = np.array([0, 0, 512, 0], dtype=np.int32)
    for lst in (low, high):
        got = fn(*_i32(choice), *_i32(starts, degs, window, lst, edges))
        want = kernels.level_probe(choice, starts, degs, window, lst,
                                   [edges], 0, (5,), True, rows)
        count = int(got[2])
        assert count == int(want[2]) > 0
        assert np.array_equal(np.asarray(got[1])[:count], want[1][:count])
        assert np.isin(want[1][:count], lst).all()
    assert fn._cache_size() == was + 1


@pytest.mark.parametrize("name", ["q2", "q3"])
def test_levels_made_on_the_device_are_counted(world, name):
    """``wukong_join_level_enumerations_total{where}`` counts a level a
    reply by where its candidates were made: on the chip every level of
    the device route after the first (the first binds no adjacency: its
    candidates are the list's, made on the host), on the host the rest;
    the ``join.level`` event says the same in ``enumerated``."""
    proxy, _owed = world
    Global.enable_tracing = True
    Global.join_device_min_candidates = 4096  # small levels stay on the host
    before = _family("wukong_join_level_enumerations_total", "where")
    q = serve(proxy, name, "auto")
    assert q.join_route == "device"
    now = _family("wukong_join_level_enumerations_total", "where")
    rose = {w: now.get(w, 0) - before.get(w, 0) for w in ("device", "host")}
    events = [a for sp in q.trace.spans for _t, n, a in sp.events
              if n == "join.level"]
    on_device = [lv["level"] for lv in q.join_stats
                 if lv["route"] == "device"]
    made = [a["var"] for a in events if a["enumerated"] == "device"]
    assert rose == {"device": len(made),
                    "host": len(q.join_stats) - len(made)}
    assert [lv["level"] for lv in q.join_stats
            if lv["enumerated"] == "device"] == \
        [k for k in on_device if k > 0] and made
