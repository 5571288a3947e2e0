"""The sharded LUBM-2560 cell's pieces on the CPU: the real entries are well
formed; the loader exits at once on a program without the sharded cold start;
at LUBM-1 on four virtual devices (in a process of its own, which asks the CPU
backend for them) the cell comes out correct, builds once and loads once, its
controls come out not correct and every reader it brings gives a number; and
the collective reader agrees with the trace reducer on a recorded trace."""
import gzip
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "lubm2560-4chip-heavy"
MINE = ["p50_ms.q1", "p50_ms.q2", "p50_ms.q7", "dist_route_pct",
        "exchange_rows_per_reply", "exchange_padding_pct",
        "capacity_retries_per_reply", "shard_skew_pct", "collective_pct",
        "bytes_roofline_pct", "ici_roofline_pct", "hbm_peak_gib"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_real_entries_are_well_formed():
    bench = _bench()
    cell = bench["workloads"][-1]  # appended, nothing moved
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        (CELL, "lubm2560-4chip", "heavy", 4)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 2)
    cfg = bench["configs"][-1]
    assert cfg["name"] == "lubm2560-4chip" and cfg["reduced"] == \
        ["universities"]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lubm2560-1chip.json")) as f:
        one_chip = json.load(f)
    assert conf["generator"] == "lubm_bundle_sharded"
    assert conf["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert (conf["universities"], conf["source_universities"]) == \
        (1280, 10240)
    assert conf["reduced"] == cfg["reduced"]
    assert conf["partitions"] == conf["chips"] == 4
    assert conf["data_seed"] == one_chip["data_seed"]  # one generator seed
    assert set(conf["guarantees"]) == set(one_chip["guarantees"])
    assert "DistEngine" in conf["engines"]
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".lubm2560x4")]
    assert [m["name"] for m in mine] == [f"{n}.lubm2560x4" for n in MINE]
    assert bench["per_layer"][-12:] == mine
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "qps"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    for name in ("compiles_in_window", "device_idle_pct", "named_busy_pct"):
        m = {m["name"]: m for m in bench["per_layer"]}[name]
        assert "workloads" not in m  # every cell, the sharded one too
    e2e = [m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])]
    assert e2e == ["qps", "setup_s"]


def test_loader_exits_at_once_without_the_sharded_boot(monkeypatch):
    from benchmark.loaders import lubm_bundle_sharded

    monkeypatch.setitem(sys.modules, "wukong_tpu.runtime.boot", None)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        lubm_bundle_sharded.load({"universities": 2560, "partitions": 4}, 1,
                                 "/nonexistent")
    assert time.perf_counter() - t0 < 1.0  # before any data is made
    assert "boot_shards" in str(e.value) and e.value.code != 0


# the tiny cell in a process of its own: four virtual CPU devices
DRIVE = r'''
import json, os, sys
sys.path.insert(0, sys.argv[1])
from benchmark import dist_chain, run as runmod, spec
from benchmark.driver import serve
from benchmark.spec import Cell
from wukong_tpu.config import Global
import jax

runmod.CACHE = sys.argv[2]
BENCH = os.path.join(sys.argv[1], "benchmark", "tests", "data",
                     "BENCHMARK.lubm_sharded.json")
CELL = "lubm1-sharded-tiny"
devs = jax.devices()
dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
       "count": len(devs)}
kept = {}

class KeptRun(runmod.Run):
    def __init__(self, *a):
        super().__init__(*a)
        kept["run"] = self

runmod.Run = KeptRun
out = {}
seed = 2 ** 31 + 39
res = runmod.run_cell(Cell(BENCH, CELL), seed, 1.0, False, dev,
                      break_program=lambda w: kept.setdefault("world", w))
out["first"] = res
run, world = kept["run"], kept["world"]
out["first_facts"] = {"stored_edges_by_shard":
                      world.facts["stored_edges_by_shard"]}
Global.enable_tracing = True
traced = [serve(world.proxy, r.req) for r in run.replies[:6]]
Global.enable_tracing = False
run.replies = traced + run.replies
run.peaks = {"hbm_bytes_per_s": 819e9}
serves = [(r.req.cls, 10 + 100 * k, 90 + 100 * k)
          for k, r in enumerate(traced)]
run.trace = {"serves": serves, "window_ns": (0, 100 * len(traced) + 100),
             "window_s": 100 * len(traced) + 100,
             "busy_s": 50 * len(traced), "busy_intervals_ns": [],
             "per_device_busy_s": {"/device:0": 2.0, "/device:1": 1.0,
                                   "/device:2": 1.0, "/device:3": 1.0}}
busy = tuple([[10 + 100 * k, 60 + 100 * k] for k in range(len(traced))])
coll = tuple([[20 + 100 * k, 30 + 100 * k] for k in range(len(traced))])
dist_chain.device_intervals = lambda _run: ((busy, coll),) * 4
dist_chain.device_peak_bytes = lambda _run: [3 << 30, 2 << 30, 2 << 30, 2 << 30]
out["values"] = {m["name"]: spec.layer_reader(m["name"])(run)
                 for m in Cell(BENCH, CELL).per_layer}
out["second"] = runmod.run_cell(Cell(BENCH, CELL), seed + 1, 1.0, False, dev)
out["controls"] = {}
for control in ("partial", "alter"):
    r = runmod.run_cell(Cell(BENCH, CELL), seed + 2, 1.0, False, dev,
                        control=control)
    Global.query_budget_rows = 0
    out["controls"][control] = r
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cache = str(tmp_path_factory.mktemp("cache"))
    p = subprocess.run([sys.executable, "-c", DRIVE, ROOT, cache], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_tiny_cell_is_correct_and_loads_its_bundle(tiny):
    for run in ("first", "second"):
        res = tiny[run]
        assert res["correct"] is True, res["checks"]
        assert res["failed"] == 0 and res["attempted"] % 3 == 0
        assert set(res["metrics"]) == {"qps", "setup_s"}
    assert len(tiny["first_facts"]["stored_edges_by_shard"]) == 4


@pytest.mark.parametrize("control,failing", [
    ("alter", {"wrong_replies"}),
    ("partial", {"failed_replies"}),
])
def test_controls_come_out_not_correct(tiny, control, failing):
    res = tiny["controls"][control]
    assert res["correct"] is False
    over = {n for n, c in res["checks"].items()
            if (c["value"] < c["limit"] if c["rule"] == ">="
                else c["value"] > c["limit"])}
    assert failing <= over, res["checks"]


def test_every_new_reader_gives_a_number(tiny):
    v = tiny["values"]
    assert len(v) == 12 and all(x is not None for x in v.values()), v
    assert v["dist_route_pct.lubm2560x4"] == 100.0
    assert v["capacity_retries_per_reply.lubm2560x4"] == 0
    assert v["exchange_rows_per_reply.lubm2560x4"] > 0
    assert 0 <= v["exchange_padding_pct.lubm2560x4"] < 100
    assert v["shard_skew_pct.lubm2560x4"] == pytest.approx(60.0)
    assert v["collective_pct.lubm2560x4"] == pytest.approx(20.0)
    assert v["hbm_peak_gib.lubm2560x4"] == 3.0
    for name in ("bytes_roofline_pct", "ici_roofline_pct"):
        assert v[name + ".lubm2560x4"] > 0
    for q in (1, 2, 7):
        assert v[f"p50_ms.q{q}.lubm2560x4"] > 0


def test_collective_intervals_of_the_recorded_trace(tmp_path):
    """On a one-chip trace with no collective: the busy time is the
    reducer's, and no collective is found."""
    from benchmark import dist_chain, xplane

    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data", "tiny_tpu.xplane.pb.gz")) as src, \
            open(d / "tiny.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    path = xplane.find_trace(str(tmp_path))
    devs = dist_chain._intervals(path, 0.0)
    assert len(devs) == 1
    busy, coll = devs[0]
    assert abs(xplane.total(busy) / 1e9 - xplane.reduce(path)["busy_s"]) < 1e-9
    assert coll == []
    assert dist_chain.COLLECTIVE.search(
        "%all-to-all.3 = s32[4,3,1024]{2,1,0} all-to-all(s32[4,3,1024] %x)")
    assert dist_chain.COLLECTIVE.search("%ag = s32[4] all-gather-start(%y)")
    assert not dist_chain.COLLECTIVE.search(
        "%fusion.2 = s32[8] fusion(s32[8] %all-to-all.3), kind=kLoop")
