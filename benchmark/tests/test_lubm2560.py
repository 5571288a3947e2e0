"""The LUBM-2560 cell's pieces at LUBM-1 on the CPU: the real entries are
well formed; the loader builds once and loads once on one cache directory
and gives equal worlds; it exits at once on a program without the cold start
from a bundle; every reader the cell brings gives a number on a traced run;
and the controls come out not correct."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "data", "BENCHMARK.lubm_bundle.json")
CELL = "lubm1-bundle-tiny"
SEED = 2 ** 31 + 32


def _device():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def test_real_entries_are_well_formed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["lubm2560-heavy"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lubm2560-1chip", "heavy", 1)
    assert bench["workloads"][-1] is cell  # appended, nothing moved
    cfg = {c["name"]: c for c in bench["configs"]}["lubm2560-1chip"]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        conf = json.load(f)
    assert conf["generator"] == "lubm_bundle"
    assert conf["universities"] == conf["source_universities"] == 2560
    assert conf["reduced"] == cfg["reduced"] == []
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lubm640-1chip.json")) as f:
        assert conf["guarantees"] == json.load(f)["guarantees"]
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".lubm2560")]
    assert len(mine) == 10
    for m in mine:
        assert m["workloads"] == ["lubm2560-heavy"] and m["moves"] == "qps"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    with open(BENCH) as f:  # the test's copy names the same ten
        assert [m["name"] for m in json.load(f)["per_layer"][:10]] == \
            [m["name"] for m in mine]


def test_loader_builds_once_then_loads(tmp_path):
    from wukong_tpu.store.persist import gstore_digest

    from benchmark.loaders import lubm_bundle

    conf = {"universities": 1, "data_seed": 0}
    first = lubm_bundle.load(conf, SEED, str(tmp_path / "c"))
    second = lubm_bundle.load(conf, SEED, str(tmp_path / "c"))
    assert not first.facts["from_bundle"] and second.facts["from_bundle"]
    assert {"generate_lubm", "build", "save", "triples_load"} <= \
        set(first.seconds)
    assert {"bundle_load", "stats_load", "triples_load"} == \
        set(second.seconds)
    assert first.facts["bundle_bytes"] == second.facts["bundle_bytes"] > 0
    assert gstore_digest(first.proxy.g) == \
        gstore_digest(second.proxy.g)
    assert np.array_equal(first.triples, second.triples)
    # int32 on disk; the rows the reference selects come out int64, since
    # it packs a pair as (s << 32) | o
    tr = first.triples
    assert tr.dtype == np.int32 and tr[:, 1].dtype == np.int32
    rows = tr[tr[:, 1] == 1]
    assert rows.dtype == np.int64 and type(rows) is np.ndarray and len(rows)
    assert tr[(tr[:, 2] == 20) & (tr[:, 1] == 1), 0].dtype == np.int64
    from wukong_tpu.loader.lubm import generate_lubm
    assert np.array_equal(np.asarray(tr), generate_lubm(1, 0)[0])
    assert first.index_rows == second.index_rows
    for name in ("triples", "stored_edges", "universities", "data_seed"):
        assert first.facts[name] == second.facts[name]


def test_loader_exits_at_once_without_the_boot_function(monkeypatch):
    from benchmark.loaders import lubm_bundle

    monkeypatch.setitem(sys.modules, "wukong_tpu.runtime.boot", None)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        lubm_bundle.load({"universities": 2560}, 1, "/nonexistent")
    assert time.perf_counter() - t0 < 1.0  # before any data is made
    assert "no wukong_tpu.runtime.boot" in str(e.value)
    assert e.value.code != 0


def test_loader_says_so_where_there_is_no_room(monkeypatch, tmp_path):
    import shutil
    from collections import namedtuple

    from benchmark.loaders import lubm_bundle

    usage = namedtuple("usage", "total used free")
    monkeypatch.setattr(shutil, "disk_usage", lambda _p: usage(10, 9, 1))
    with pytest.raises(SystemExit) as e:
        lubm_bundle.load({"universities": 2560}, 1, str(tmp_path / "c"))
    assert "no room for the bundle" in str(e.value) and "23.8 GB" in str(e.value)


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
        traced = [serve(kept["world"].proxy, r.req) for r in run.replies[:6]]
    finally:
        Global.enable_tracing = False
    return res, run, traced, cache


def test_tiny_cell_is_correct(tiny):
    res, run, _traced, cache = tiny
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert res["attempted"] % 3 == 0  # the window closes at a whole cycle
    assert set(res["metrics"]) == {"qps", "setup_s"}
    assert [r.req.cls for r in run.replies[:3]] == \
        ["lubm_q1", "lubm_q2", "lubm_q7"]
    assert any(n.startswith("store-") and n.endswith(".npz")
               for n in os.listdir(os.path.join(cache, "lubm1-bundle-test_d0")))
    json.dumps(res)


def test_every_new_reader_gives_a_number(tiny):
    from benchmark import spec

    _res, run, traced, _cache = tiny
    run.replies = traced + run.replies
    run.memory_peak_bytes = 7 << 30
    run.peaks = {"hbm_bytes_per_s": 819e9}
    serves = [(r.req.cls, 10 + 100 * k, 90 + 100 * k)
              for k, r in enumerate(traced)]
    run.trace = {"serves": serves, "window_ns": (0, 100 * len(traced) + 100),
                 "busy_intervals_ns": [[10 + 100 * k, 20 + 100 * k]
                                       for k in range(len(traced))]}
    values = {m["name"]: spec.layer_reader(m["name"])(run)
              for m in spec.Cell(BENCH, CELL).per_layer
              if m["name"].endswith(".lubm2560")}
    assert len(values) == 10 and all(v is not None for v in values.values())
    assert values["hbm_peak_gib.lubm2560"] == 7.0
    assert values["bytes_roofline_pct.lubm2560"] > 0
    assert values["sync_wait_ms.lubm2560"] > 0
    assert values["stage_ms_per_reply.lubm2560"] >= 0
    for name in ("stages_per_reply", "evictions_per_reply",
                 "capacity_retries_per_reply"):
        assert values[name + ".lubm2560"] == 0  # a warm store that fits
    for q in (1, 2, 7):
        assert values[f"p50_ms.q{q}.lubm2560"] > 0


def test_span_readers_return_nothing_without_spans_or_trace(tiny):
    from benchmark import spec

    _res, run, _traced, _cache = tiny
    saved = run.replies, run.trace
    run.replies = [r for r in run.replies if not r.spans]
    run.trace = None
    try:
        for name in ("sync_wait_ms", "stage_ms_per_reply", "stages_per_reply",
                     "evictions_per_reply", "capacity_retries_per_reply",
                     "bytes_roofline_pct"):
            assert spec.layer_reader(name + ".lubm2560")(run) is None, name
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
