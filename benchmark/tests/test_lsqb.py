"""The LSQB cell's pieces at some 900 persons on the CPU: the real entries
are well formed and state every size the generator assumes; the mix yields
q2 then q3; the loader builds once and loads once on one cache directory and
gives equal worlds; it exits at once on a program without the data model;
the tiny cell is correct and the worst-case-optimal join answers it; every
reader the cell brings gives a number on a traced run and nothing on a run
without spans; and the controls come out not correct."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "data", "BENCHMARK.lsqb.json")
CELL = "lsqb-tiny"
SEED = 2 ** 31 + 34
NEW = 13  # per-layer metrics the cell brings


def _device():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def test_real_entries_are_well_formed():
    from wukong_tpu.loader import snb

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["lsqb-cyclic"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lsqb-1chip", "lsqb_cyclic", 1)
    # appended behind the five cells there were, nothing moved (by place,
    # not "the last": a later cell comes behind this one)
    assert bench["workloads"][5] is cell
    cfg = {c["name"]: c for c in bench["configs"]}["lsqb-1chip"]
    assert bench["configs"][4] is cfg
    for entry in (cell, cfg):
        assert all(len(str(v)) <= 200 for v in entry.values())
    with open(os.path.join(ROOT, cfg["file"])) as f:
        conf = json.load(f)
    assert conf["generator"] == "snb_bundle" and conf["query_suite"] == "lsqb"
    # the source's scale factor 10, cut to its 3 by a run's time limit
    assert (conf["source_scale_factor"], conf["scale_factor"]) == (10, 3)
    assert conf["data_seed"] == 0 and conf["chips"] == conf["partitions"] == 1
    assert conf["reduced"] == cfg["reduced"] == ["queries", "scale_factor"]
    assert "360 s" in conf["why_reduced"]  # with the seconds that forced it
    assert conf["source"] == cfg["source"]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lubm640-1chip.json")) as f:
        assert conf["guarantees"] == json.load(f)["guarantees"]
    # every size the generator sets in the source's place, by name and value
    assumed = " ".join(conf["assumed"])
    for name, value in snb.ASSUMED.items():
        assert f"{name} {value}" in assumed, name
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".lsqb")]
    assert len(mine) == NEW and bench["per_layer"][42:42 + NEW] == mine
    for m in mine:
        assert m["workloads"] == ["lsqb-cyclic"] and m["moves"] == "qps"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    with open(BENCH) as f:  # the test's copy names the same readers
        assert [m["name"] for m in json.load(f)["per_layer"][:NEW]] == \
            [m["name"] for m in mine]


def test_query_files_are_the_programs_texts_and_in_the_subset():
    from wukong_tpu.loader import snb

    from benchmark.reference import parse_bgp
    from benchmark.spec import query_text

    for name, patterns in (("q2", 8), ("q3", 16)):
        text = query_text(f"lsqb/{name}")
        assert text == snb.QUERIES[name]
        select, pats = parse_bgp(text)
        assert len(pats) == patterns and "%" not in text
        assert set(select) <= {t for p in pats for t in p if t[0] == "?"}


def test_mix_yields_q2_then_q3():
    from benchmark.spec import Cell
    from benchmark.traffic import Traffic

    mix = Cell(os.path.join(ROOT, "BENCHMARK.json"), "lsqb-cyclic").mix
    assert (mix["clients"], mix["order"], mix["close"]) == (1, "replay",
                                                            "cycle")
    assert mix["check_sample"] == 400 and mix["trace_window_s"] is None
    t = Traffic(mix, SEED, lambda _iri: np.empty(0), str)
    got = [t.take().cls for _ in range(4)]
    assert got == ["lsqb_q2", "lsqb_q3", "lsqb_q2", "lsqb_q3"]
    assert t.take(closing=True) is None  # a block's start: the cycle is whole
    assert [r.cls for r in t.warm_pass(0)] == ["lsqb_q2", "lsqb_q3"]


def test_loader_builds_once_then_loads(tmp_path):
    from wukong_tpu.loader.snb import generate_snb
    from wukong_tpu.store.persist import gstore_digest

    from benchmark.loaders import snb_bundle

    conf = {"scale_factor": 0.02, "data_seed": 3}
    first = snb_bundle.load(conf, SEED, str(tmp_path / "c"))
    second = snb_bundle.load(conf, SEED, str(tmp_path / "c"))
    assert not first.facts["from_bundle"] and second.facts["from_bundle"]
    assert {"generate_snb", "build", "save", "triples_load"} <= \
        set(first.seconds)
    assert {"bundle_load", "stats_load", "triples_load"} == \
        set(second.seconds)
    assert first.facts["bundle_bytes"] == second.facts["bundle_bytes"] > 0
    assert gstore_digest(first.proxy.g) == gstore_digest(second.proxy.g)
    assert np.array_equal(first.triples, second.triples)
    tr = first.triples  # int32 on disk, int64 rows for the reference
    assert tr.dtype == np.int32 and tr[tr[:, 1] == 1].dtype == np.int64
    made, meta = generate_snb(0.02, 3)
    assert np.array_equal(np.asarray(tr), made)
    assert first.index_rows == second.index_rows
    for name in ("triples", "stored_edges", "scale_factor", "data_seed",
                 "nodes", "edges", "num_nodes", "num_edges"):
        assert first.facts[name] == second.facts[name]
    assert first.facts["num_edges"] == meta["num_edges"]


def test_loader_exits_at_once_without_the_data_model(monkeypatch):
    from benchmark.loaders import snb_bundle

    for broken in (None, type(sys)("snb_without_marker")):
        monkeypatch.setitem(sys.modules, "wukong_tpu.loader.snb", broken)
        import wukong_tpu.loader
        monkeypatch.setattr(wukong_tpu.loader, "snb", broken, raising=False)
        t0 = time.perf_counter()
        with pytest.raises(SystemExit) as e:
            snb_bundle.load({"scale_factor": 10}, 1, "/nonexistent")
        assert time.perf_counter() - t0 < 1.0  # before any data is made
        assert "no wukong_tpu/loader/snb.py" in str(e.value)
        assert e.value.code != 0


def test_loader_says_so_where_there_is_no_room(monkeypatch, tmp_path):
    import shutil
    from collections import namedtuple

    from benchmark.loaders import snb_bundle

    usage = namedtuple("usage", "total used free")
    monkeypatch.setattr(shutil, "disk_usage", lambda _p: usage(10, 9, 1))
    with pytest.raises(SystemExit) as e:
        snb_bundle.load({"scale_factor": 10}, 1, str(tmp_path / "c"))
    assert "no room for the bundle" in str(e.value)
    assert "17.8 GB" in str(e.value)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """One untraced run of the tiny cell from an empty cache directory; the
    ``Run`` and the world it drove are kept, and traced replies added."""
    from wukong_tpu.config import Global

    from benchmark import run as runmod
    from benchmark.driver import serve
    from benchmark.spec import Cell

    kept = {}

    class KeptRun(runmod.Run):
        def __init__(self, *a):
            super().__init__(*a)
            kept["run"] = self

    saved = runmod.Run, runmod.CACHE
    runmod.Run = KeptRun
    runmod.CACHE = str(tmp_path_factory.mktemp("cache"))
    try:
        res = runmod.run_cell(
            Cell(BENCH, CELL), SEED, 1.0, False, _device(),
            break_program=lambda world: kept.setdefault("world", world))
        cache = runmod.CACHE
    finally:
        runmod.Run, runmod.CACHE = saved
    run = kept["run"]
    Global.enable_tracing = True
    try:
        traced = [serve(kept["world"].proxy, r.req) for r in run.replies[:4]]
    finally:
        Global.enable_tracing = False
    return res, run, traced, cache


def test_tiny_cell_is_correct_and_the_join_answers_it(tiny):
    res, run, traced, cache = tiny
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert res["attempted"] % 2 == 0  # the window closes at a whole cycle
    assert set(res["metrics"]) == {"qps", "setup_s"}
    assert [r.req.cls for r in run.replies[:2]] == ["lsqb_q2", "lsqb_q3"]
    assert all(len(r.table) > 0 for r in run.replies)
    # q2 stays with the join; at this size q3's measured prefix is 4.3 times
    # its reply, so ``_record_wcoj_feedback`` hands it to a template program
    assert {r.route for r in run.replies if r.req.cls == "lsqb_q2"} == \
        {"wcoj:device"}
    assert all("join.level" in r.events for r in traced
               if r.req.cls == "lsqb_q2")
    assert any(n.startswith("store-") and n.endswith(".npz")
               for n in os.listdir(os.path.join(cache, "lsqb-test_d0")))
    json.dumps(res)


def test_every_new_reader_gives_a_number(tiny):
    from benchmark import spec

    _res, run, traced, _cache = tiny
    run.replies = traced + run.replies
    run.memory_peak_bytes = 3 << 30
    run.peaks = {"hbm_bytes_per_s": 819e9}
    serves = [(r.req.cls, 10 + 100 * k, 90 + 100 * k)
              for k, r in enumerate(traced)]
    run.trace = {"serves": serves, "window_ns": (0, 100 * len(traced) + 100),
                 "busy_intervals_ns": [[10 + 100 * k, 20 + 100 * k]
                                       for k in range(len(traced))]}
    values = {m["name"]: spec.layer_reader(m["name"])(run)
              for m in spec.Cell(BENCH, CELL).per_layer
              if m["name"].endswith(".lsqb")}
    assert len(values) == NEW
    assert all(v is not None for v in values.values()), values
    assert values["hbm_peak_gib.lsqb"] == 3.0
    whole = values["bytes_roofline_pct.lsqb"]
    assert whole > 0  # made-up nanoseconds of device time: no share of a peak
    # the request that fills the window begins before it (the profiler is
    # still starting): it counts with the share of it that lies inside,
    # and one that lies half outside does not count
    reader = spec.layer_reader("bytes_roofline_pct.lsqb")
    hi = 100 * len(traced) + 100
    run.trace["window_ns"] = (15, hi)  # 75 of its 80 ns inside: it counts
    assert reader(run) > 0
    run.trace["serves"] = serves[:1]
    assert reader(run) > 0
    run.trace["window_ns"] = (50, hi)  # half outside: it does not
    assert reader(run) is None
    run.trace["serves"] = serves
    rest = reader(run)
    run.trace["serves"] = serves[1:]
    assert rest == reader(run) > 0
    run.trace["serves"], run.trace["window_ns"] = serves, (0, hi)
    assert 50.0 <= values["wcoj_route_pct.lsqb"] <= 100.0
    assert 0 < values["device_levels_pct.lsqb"] <= 100.0
    assert values["candidates_per_reply.lsqb"] > 1000
    assert values["padding_pct.lsqb"] >= 0
    for name in ("enumerate_ms_per_reply", "probe_dispatch_ms_per_reply",
                 "probe_sync_ms_per_reply", "p50_ms.q2", "p50_ms.q3"):
        assert values[name + ".lsqb"] > 0, name
    for name in ("stages_per_reply", "capacity_retries_per_reply"):
        assert values[name + ".lsqb"] == 0  # warm tables, settled classes


def test_level_routes_reads_a_canned_reply():
    from benchmark.wcoj_levels import level_routes

    class Canned:
        spans = [("proxy.execute", 0, 0, 90), ("wcoj.execute", 1, 0, 80),
                 ("wcoj.level", 2, 0, 10), ("wcoj.enumerate", 3, 0, 5),
                 ("wcoj.level", 2, 10, 30), ("wcoj.enumerate", 3, 10, 5),
                 ("wcoj.probe.stage", 3, 15, 5),
                 ("wcoj.probe.stage", 3, 16, 1),
                 ("wcoj.probe.dispatch", 3, 20, 5),
                 ("wcoj.enumerate", 3, 25, 5),
                 ("wcoj.probe.stage", 3, 30, 2),
                 ("wcoj.probe.sync", 3, 32, 8),
                 ("proxy.reply", 1, 85, 5), ("wcoj.probe.stage", 2, 0, 0)]

    assert level_routes(Canned) == (2, 1)


def test_readers_return_nothing_without_spans_or_trace(tiny):
    from benchmark import spec

    _res, run, _traced, _cache = tiny
    saved = run.replies, run.trace
    run.replies = [r for r in run.replies if not r.spans]
    run.trace = None
    try:
        for name in ("wcoj_route_pct", "device_levels_pct",
                     "enumerate_ms_per_reply", "probe_dispatch_ms_per_reply",
                     "probe_sync_ms_per_reply", "stages_per_reply",
                     "capacity_retries_per_reply", "bytes_roofline_pct"):
            assert spec.layer_reader(name + ".lsqb")(run) is None, name
    finally:
        run.replies, run.trace = saved


@pytest.mark.parametrize("control,failing", [
    ("alter", {"wrong_replies"}),
    ("partial", {"failed_replies"}),
])
def test_controls_come_out_not_correct(control, failing, tiny, monkeypatch):
    from wukong_tpu.config import Global

    from benchmark import run as runmod
    from benchmark.spec import Cell

    monkeypatch.setattr(runmod, "CACHE", tiny[3])  # from the bundle
    try:
        res = runmod.run_cell(Cell(BENCH, CELL), SEED + 1, 1.0, False,
                              _device(), control=control)
    finally:
        Global.query_budget_rows = 0
    assert res["correct"] is False
    over = {n for n, c in res["checks"].items()
            if (c["value"] < c["limit"] if c["rule"] == ">="
                else c["value"] > c["limit"])}
    assert failing <= over, res["checks"]
