"""The WatDiv cell's pieces at scale factor 1 on the CPU: the configuration,
the ``watdiv_basic`` mix, the loader and every reader the cell brings load
and give a number; the loader refuses a program with another generator at
once; and the controls come out not correct."""
import json
import os
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "data", "BENCHMARK.watdiv.json")
SEED = 2 ** 31 + 91


def _device():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def test_real_entries_are_well_formed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["watdiv100m-basic"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("watdiv100m-1chip", "watdiv_basic", 1)
    cfg = {c["name"]: c for c in bench["configs"]}["watdiv100m-1chip"]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        conf = json.load(f)
    assert conf["generator"] == "watdiv" and conf["scale_factor"] == 1000
    assert conf["reduced"] == cfg["reduced"] == []
    assert set(conf["guarantees"]) == {"store", "reply", "path"}
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "watdiv_basic.json")) as f:
        mix = json.load(f)
    assert [c["name"] for c in mix["classes"]] == [
        f"{k}{i}" for k, n in (("L", 5), ("S", 7), ("F", 5), ("C", 3))
        for i in range(1, n + 1)]
    assert all(c["kind"] == c["name"][0] and c["per_block"] == 1
               for c in mix["classes"])
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".watdiv")]
    assert len(mine) == 9  # no median of the L class: it is bimodal (PERF.md)
    for m in mine:
        assert m["workloads"] == ["watdiv100m-basic"] and m["moves"] == "qps"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))


def test_loader_refuses_another_generator_at_once(monkeypatch):
    from wukong_tpu.loader import watdiv

    from benchmark.loaders import watdiv as loader

    monkeypatch.delattr(watdiv, "SCHEMA")
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        loader.load({"scale_factor": 1000}, 1, "/nonexistent")
    assert time.perf_counter() - t0 < 1.0  # before any data is made
    assert "not the WatDiv data model" in str(e.value)
    assert e.value.code != 0


@pytest.fixture(scope="module")
def tiny():
    """One untraced run of the tiny cell; the ``Run`` and the world it drove
    are kept, and a few traced replies are added for the readers."""
    from wukong_tpu.config import Global

    from benchmark import run as runmod
    from benchmark.driver import serve
    from benchmark.spec import Cell

    kept = {}

    class KeptRun(runmod.Run):
        def __init__(self, *a):
            super().__init__(*a)
            kept["run"] = self

    saved, runmod.Run = runmod.Run, KeptRun
    try:
        res = runmod.run_cell(
            Cell(BENCH, "watdiv1-tiny"), SEED, 2.0, False, _device(),
            break_program=lambda world: kept.setdefault("world", world))
    finally:
        runmod.Run = saved
    run = kept["run"]
    Global.enable_tracing = True
    try:
        traced = [serve(kept["world"].proxy, r.req) for r in run.replies[:40]]
    finally:
        Global.enable_tracing = False
    return res, run, traced


def test_tiny_cell_is_correct_with_all_twenty(tiny):
    res, run, _traced = tiny
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 40
    assert set(res["metrics"]) == {"qps", "setup_s"}
    assert {r.req.cls for r in run.replies} >= {
        "L1", "L5", "S1", "S7", "F1", "F5", "C1", "C2", "C3"}
    assert {r.req.kind for r in run.replies} == set("LSFC")
    assert all(c["value"] <= c["limit"] for n, c in res["checks"].items()
               if c["rule"] == "<=")
    json.dumps(res)


def test_every_new_reader_gives_a_number(tiny):
    from benchmark import spec

    _res, run, traced = tiny
    run.replies = traced + run.replies
    run.memory_peak_bytes = 3 << 30
    run.peaks = {"hbm_bytes_per_s": 819e9}
    # a made-up profile: every traced request inside the window, the device
    # busy for a tenth of it
    serves = [(r.req.cls, 10 + 100 * k, 90 + 100 * k)
              for k, r in enumerate(traced)]
    run.trace = {"serves": serves, "window_ns": (0, 100 * len(traced) + 100),
                 "busy_intervals_ns": [[10 + 100 * k, 20 + 100 * k]
                                       for k in range(len(traced))]}
    values = {}
    for m in spec.Cell(BENCH, "watdiv1-tiny").per_layer:
        if m["name"].endswith(".watdiv"):
            values[m["name"]] = spec.layer_reader(m["name"])(run)
    assert len(values) == 9 and all(v is not None for v in values.values())
    assert values["hbm_peak_gib.watdiv"] == 3.0
    assert values["dispatches_per_reply.watdiv"] >= 1
    assert 0 <= values["template_route_pct.watdiv"] <= 100
    assert values["capacity_retries_per_reply.watdiv"] >= 0
    assert values["stages_per_reply.watdiv"] == 0  # all staged in warm-up
    assert values["bytes_roofline_pct.watdiv"] > 0
    for k in "SFC":
        assert values[f"p50_ms.{k}.watdiv"] > 0


def test_readers_return_nothing_without_spans_or_trace(tiny):
    """The parent's program under these files: no ``proxy.execute`` span, no
    profile. Nothing is read and nothing raises."""
    from benchmark import spec

    _res, run, _traced = tiny
    saved = run.replies, run.trace
    run.replies = [r for r in run.replies if not r.spans]
    run.trace = None
    try:
        for name in ("dispatches_per_reply.watdiv", "template_route_pct.watdiv",
                     "capacity_retries_per_reply.watdiv",
                     "stages_per_reply.watdiv", "bytes_roofline_pct.watdiv"):
            assert spec.layer_reader(name)(run) is None, name
    finally:
        run.replies, run.trace = saved


@pytest.mark.parametrize("control,failing", [
    ("alter", {"wrong_replies"}),
    ("partial", {"failed_replies"}),
])
def test_controls_come_out_not_correct(control, failing):
    from wukong_tpu.config import Global

    from benchmark import run as runmod
    from benchmark.spec import Cell

    try:
        res = runmod.run_cell(Cell(BENCH, "watdiv1-tiny"), SEED + 1, 1.0,
                              False, _device(), control=control)
    finally:
        Global.query_budget_rows = 0
    assert res["correct"] is False
    over = {n for n, c in res["checks"].items()
            if (c["value"] < c["limit"] if c["rule"] == ">="
                else c["value"] > c["limit"])}
    assert failing <= over, res["checks"]
