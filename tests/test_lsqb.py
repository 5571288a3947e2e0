"""LSQB's q2 and q3 through ``Proxy.serve_query`` at some 900 persons, each
route compared row for row, as sorted multisets, with the benchmark's plain
reference: the walk, the worst-case-optimal join with host and with device
levels, a whole-plan template program, and the program's own choice; a level
forced through more than one slice and more than one run of prefix rows; the
spans, the ``join.level`` event and the counters of a traced reply; and the
faults the scale-factor-10 deployment found, one test each."""

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


def test_a_level_of_one_run_probes_as_levels_always_did(world, monkeypatch):
    """Up to ``LEVEL_CHUNK_SLICES`` slices of candidates a level is one call
    a generator group at the ``pad_pow2`` class of its candidates, by the
    program that searches its keys (no ``id_bounds``), each mask fetched
    before the next group is staged: what a LUBM heavy's first request
    allocates on the device, and when, is what it was before LSQB (on the
    chip q1 of ``lubm640-heavy`` read 2,166 ms for 1,924 with the sliced,
    table-addressing, deferred form here). A level in runs takes that
    form."""
    proxy, owed = world
    seen = []
    real = wcoj_mod.jit_level_probe

    def spy(depths, has_glob, id_bounds=None):
        seen.append(id_bounds)
        return real(depths, has_glob, id_bounds)

    monkeypatch.setattr(wcoj_mod, "jit_level_probe", spy)
    Global.enable_tracing = True
    q = serve(proxy, "q3", "wcoj-device")
    assert seen and all(b is None for b in seen)
    calls = [d for d in q.device_steps if d.get("site") == "wcoj.probe"]
    assert calls and all(d["capacity"] == kernels.pad_pow2(d["live"])
                         for d in calls)
    names = [sp.name for sp in q.trace.spans]
    at = [i for i, n in enumerate(names) if n == "wcoj.probe.dispatch"]
    assert at and all(names[i + 1] == "wcoj.probe.sync" for i in at)
    del seen[:]
    monkeypatch.setattr(kernels, "LEVEL_SLICE", 2048)
    q = serve(proxy, "q3", "wcoj-device")
    assert np.array_equal(rows_of(q), owed["q3"])
    assert any(isinstance(b, tuple) and None not in b for b in seen)
    assert any(b is None for b in seen)  # its small levels still whole


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


def _family(name):
    snap = get_registry().snapshot()
    return {s["labels"]["route"]: s["value"]
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
                       "wcoj.probe.dispatch", "wcoj.probe.sync"):
            assert spans[sp.parent].name == "wcoj.level", sp.name
    events = [(n, a) for sp in spans for _t, n, a in sp.events
              if n == "join.level"]
    assert len(events) == len(levels)
    routes = set()
    for (_n, a), lv, sp in zip(events, q.join_stats, levels):
        assert set(a) == {"var", "candidates", "slots", "rows_out", "route"}
        assert (a["var"], a["candidates"], a["slots"], a["rows_out"],
                a["route"]) == (lv["var"], lv["candidates"], lv["slots"],
                                lv["rows_out"], lv["route"])
        inside = [s.name for s in spans if s.parent == sp.index]
        assert inside[0] == "wcoj.enumerate"
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


def test_the_level_probe_has_a_stable_name():
    import jax.numpy as jnp

    fn = kernels.jit_level_probe((3,), False)
    assert fn.__wrapped__.__name__ == "wk_level_probe"
    assert fn is kernels.jit_level_probe((3,), False, (None,))
    args = [jnp.ones(8, dtype=bool), jnp.arange(8, dtype=jnp.int32),
            jnp.zeros(1, dtype=jnp.int32), jnp.arange(4, dtype=jnp.int32),
            jnp.arange(5, dtype=jnp.int32) * 2,
            jnp.arange(8, dtype=jnp.int32), jnp.zeros(8, dtype=jnp.int32)]
    assert "wk_level_probe" in fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("rows", [64, 1 << 18])
def test_a_probe_that_addresses_its_keys_equals_one_that_searches(rows):
    """``id_bounds``: at 2^18 candidates over 3,000 keys the anchors' key
    lookup addresses a table over the id range (``direct_lookup_wins``), at
    64 it searches; both equal the NumPy probe."""
    import jax.numpy as jnp

    rng = np.random.default_rng(rows)
    nkeys, id_bound = 3_000, 5_000
    keys = np.sort(rng.choice(id_bound, nkeys, replace=False))
    deg = rng.integers(0, 9, nkeys)
    offsets = np.concatenate([[0], np.cumsum(deg)])
    edges = np.concatenate([np.sort(rng.choice(16, d, replace=False))
                            for d in deg]).astype(np.int64)
    anchors = rng.integers(0, id_bound + 50, rows)
    cand = rng.integers(0, 16, rows)
    valid = rng.random(rows) < 0.9
    want = kernels.level_probe_host(valid, cand, None, keys, offsets, edges,
                                    anchors)
    assert kernels.direct_lookup_wins(rows, nkeys, id_bound) == (rows > 64)
    dev = [jnp.asarray(a.astype(np.int32)) for a in
           (cand, np.zeros(1), keys, offsets, edges, anchors)]
    for bounds in (None, (id_bound,)):
        fn = kernels.jit_level_probe((5,), False, bounds)
        got = np.asarray(fn(jnp.asarray(valid), *dev))
        assert np.array_equal(got, want), bounds
    assert want.any() and not want.all()
