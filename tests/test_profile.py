"""Introspection plane (ISSUE 7): EXPLAIN / EXPLAIN ANALYZE, per-shard
heat telemetry, /top, latency attribution + regression sentinel.

Acceptance surface: EXPLAIN renders the planner's per-step cost/cardinality
estimates (golden-pinned); ANALYZE joins actual per-step rows/wall-time
against them on chain/const/index shapes and its latency decomposition
covers >=90% of end-to-end wall time; batched members are attributed via
their FusedGroup's dispatch span; heat counters account primary/failover/
degraded fetch outcomes (chaos-marked); the Zipfian hot-spot scenario
ranks the hot shard first with load-rate CDFs separating hot from cold;
/top scrapes; and the regression sentinel trips and auto-dumps through
the flight recorder.
"""

import json

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.loader.lubm import UB, VirtualLubmStrings, generate_lubm
from wukong_tpu.obs import QueryTrace, get_recorder, get_registry
from wukong_tpu.obs.heat import get_heat, payload_size
from wukong_tpu.obs.profile import (
    LatencyAttributor,
    decompose,
    get_attributor,
    render_top,
)
from wukong_tpu.runtime import faults
from wukong_tpu.runtime.faults import FaultPlan, FaultSpec
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.utils.errors import ErrorCode

pytestmark = pytest.mark.obs

PREFIX = """
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
"""
Q_CHAIN = PREFIX + """SELECT ?X ?Y WHERE {
    ?X ub:memberOf ?Y .
    ?Y ub:subOrganizationOf ?Z .
}"""
Q_TYPE = PREFIX + """SELECT ?X WHERE {
    ?X rdf:type ub:FullProfessor .
    ?X ub:worksFor ?D .
}"""


@pytest.fixture(scope="module")
def world():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    return {"g": g, "ss": ss, "triples": triples}


@pytest.fixture(scope="module")
def proxy(world):
    from wukong_tpu.planner.optimizer import make_planner

    p = Proxy(world["g"], world["ss"],
              CPUEngine(world["g"], world["ss"]))
    p.planner = make_planner(world["triples"])
    return p


@pytest.fixture(autouse=True)
def _hygiene(monkeypatch):
    """Tracing knobs at defaults; recorder/attributor/heat state clean;
    no fault plan leaks across tests."""
    monkeypatch.setattr(Global, "enable_tracing", False)
    monkeypatch.setattr(Global, "trace_sample_every", 1)
    monkeypatch.setattr(Global, "trace_dump_dir", "")
    monkeypatch.setattr(Global, "enable_attribution", False)
    get_recorder().clear()
    get_attributor().reset()
    get_heat().reset()
    faults.clear()
    yield
    faults.clear()


def _const_texts(world, n=2):
    """Same-template const-start chain texts (the batchable shape)."""
    from wukong_tpu.types import OUT

    ss, g = world["ss"], world["g"]
    pid = ss.str2id(f"<{UB}memberOf>")
    depts = np.asarray(g.get_index(pid, OUT))[:n]
    return [
        f"SELECT ?s ?c WHERE {{ ?s <{UB}memberOf> {ss.id2str(int(d))} . "
        f"?s <{UB}takesCourse> ?c . }}" for d in depts]


# ---------------------------------------------------------------------------
# EXPLAIN: golden output + estimate parity with the planner
# ---------------------------------------------------------------------------

EXPLAIN_GOLDEN = """\
EXPLAIN
step  pattern                                    est_rows   est_cost
   0  (11 0 IN -2)                                  275.0      614.0
   1  (-2 11 OUT -3)                                275.0      889.0
   2  (-2 7 IN -1)                                7,473.0   15,285.0
planner: cost-based, strategy: walk, est total cost 16,788.0"""


def test_explain_golden(proxy):
    r = proxy.explain_query(Q_CHAIN)
    assert r["mode"] == "EXPLAIN"
    assert r["rendered"] == EXPLAIN_GOLDEN


def test_explain_estimates_match_planner(proxy):
    """The EXPLAIN surface and the capacity-sizing estimate_chain must
    come from one cardinality model (the refactor's contract)."""
    r = proxy.explain_query(Q_CHAIN)
    q = proxy._parse_text(Q_CHAIN)
    proxy._plan_prepared(q, True, None)
    ests = proxy.planner.estimate_chain(q.pattern_group.patterns)
    assert [s["est_rows"] for s in r["steps"]] == pytest.approx(ests)


def test_explain_without_planner_renders_dashes(world):
    p2 = Proxy(world["g"], world["ss"],
               CPUEngine(world["g"], world["ss"]))  # no planner
    r = p2.explain_query(Q_CHAIN)
    assert r["planner"] == "heuristic/none"
    assert all("est_rows" not in s for s in r["steps"])
    assert "-" in r["rendered"]


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE: estimate-vs-actual join on chain / const / index shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", ["chain", "const", "index"])
def test_analyze_joins_estimates_and_actuals(proxy, world, shape):
    text = {"chain": Q_CHAIN, "index": Q_TYPE,
            "const": _const_texts(world, 1)[0]}[shape]
    r = proxy.explain_query(text, analyze=True, device="cpu")
    assert r["mode"] == "EXPLAIN ANALYZE"
    assert r["status"] == "SUCCESS"
    # every step joined: estimates AND actuals keyed on step index
    for k, s in enumerate(r["steps"]):
        assert s["step"] == k
        assert s["est_rows"] > 0
        assert s["rows_out"] is not None and s["time_us"] is not None
    assert r["steps"][-1]["rows_out"] == r["rows"]
    # the forced trace reached the flight recorder
    assert get_recorder().find(r["trace_id"]) is not None


def test_analyze_decomposition_covers_90pct(proxy):
    """Acceptance: `console analyze` on a LUBM chain query shows per-step
    estimated vs actual cardinalities and a latency decomposition whose
    components sum to >=90% of end-to-end wall time."""
    r = proxy.explain_query(Q_CHAIN, analyze=True, device="cpu")
    d = r["decomposition"]
    assert d["covered_frac"] >= 0.90
    comp = d["components"]
    assert comp["execute"] > 0 and comp["parse"] >= 0 and comp["plan"] >= 0
    assert sum(comp.values()) + d["other_us"] <= d["total_us"] * 1.01
    assert "est_rows" in r["steps"][0] and r["steps"][0]["rows_out"] >= 0
    assert "latency:" in r["rendered"]


def test_console_analyze_and_top_verbs(proxy, tmp_path, capsys):
    from wukong_tpu.runtime.console import Console

    qf = tmp_path / "q.sparql"
    qf.write_text(Q_CHAIN)
    con = Console(proxy)
    con.run_command(f"analyze -f {qf} -d cpu")
    out = capsys.readouterr().out
    assert "EXPLAIN ANALYZE" in out and "latency:" in out
    con.run_command("explain -f " + str(qf))
    assert "EXPLAIN" in capsys.readouterr().out
    con.run_command("top -k 4")
    out = capsys.readouterr().out
    assert "SHARDS" in out and "TEMPLATES" in out and "LANES" in out


# ---------------------------------------------------------------------------
# batched-member attribution (via the FusedGroup dispatch span)
# ---------------------------------------------------------------------------

def test_batched_member_attribution(proxy, world):
    from wukong_tpu.runtime.batcher import FusedGroup, QueryBatcher, _Pending

    texts = _const_texts(world, 2)
    members = []
    for t in texts:
        q = proxy._parse_text(t)
        proxy._plan_prepared(q, True, None)
        q.deadline = None
        q.trace = QueryTrace(kind="query", text=t)
        members.append(_Pending(q))
    b = QueryBatcher(proxy.cpu)
    try:
        FusedGroup(members, b, engine=None).run(proxy.cpu)
    finally:
        b.close()
    for m in members:
        assert m.q.result.status_code == ErrorCode.SUCCESS
        m.trace.finish("SUCCESS")
        evs = [(sp.name, sp.attrs) for sp in m.trace.spans]
        settled = [a for (n, a) in evs if n == "batch.settled"]
        assert settled and settled[0]["dispatch_us"] > 0
        d = decompose(m.trace)
        # no execute span of its own: the FusedGroup's dispatch span
        # duration becomes the member's execute component
        assert d["components"]["execute"] == settled[0]["dispatch_us"]


# ---------------------------------------------------------------------------
# per-shard heat: counters, failover kinds (chaos), hot-spot scenario
# ---------------------------------------------------------------------------

class _Mesh4:
    devices = np.empty(4, dtype=object)


def _sstore(world, n=4):
    from wukong_tpu.parallel.sharded_store import ShardedDeviceStore

    stores = [build_partition(world["triples"], i, n) for i in range(n)]
    return ShardedDeviceStore(stores, _Mesh4(), replication_factor=1)


def test_heat_charges_primary_fetches(world):
    sstore = _sstore(world)
    for i in (0, 0, 0, 1):
        sstore._fetch_shard(i, lambda g: np.arange(64), "t")
    rep = get_heat().report()
    assert rep["ranked"][0]["shard"] == 0
    assert rep["shards"][0]["fetches"] == 3
    assert rep["shards"][0]["by_kind"]["primary"] == 3
    assert rep["shards"][1]["rows"] == 64
    assert rep["shards"][1]["bytes"] == np.arange(64).nbytes
    # the wukong_shard_heat_* metrics carry the same numbers
    m = get_registry().counter("wukong_shard_heat_fetches_total",
                               labels=("shard", "kind"))
    assert m.value(shard="0", kind="primary") >= 3


def test_heat_off_knob_skips_charging(world, monkeypatch):
    monkeypatch.setattr(Global, "enable_heat", False)
    sstore = _sstore(world)
    sstore._fetch_shard(2, lambda g: np.arange(8), "t")
    assert get_heat().report()["ranked"] == []


@pytest.mark.chaos
def test_heat_counters_under_failover(world, monkeypatch):
    """A downed primary served by a replica charges kind=failover; with no
    replica it charges kind=degraded — the heat plane sees the outage the
    way placement must (a hot shard in failover is the migration signal)."""
    from wukong_tpu.store.persist import clone_gstore

    monkeypatch.setattr(Global, "retry_base_ms", 1)
    monkeypatch.setattr(Global, "retry_max_ms", 2)
    sstore = _sstore(world)
    sstore.replicas = {0: [(1, clone_gstore(sstore.stores[0]))]}
    faults.install(FaultPlan([FaultSpec("dist.shard_fetch", "shard_down",
                                        shard=0)], seed=0))
    out, ok = sstore._fetch_shard(0, lambda g: np.arange(4), "t")
    assert ok and len(out) == 4
    faults.install(FaultPlan([FaultSpec("dist.shard_fetch", "shard_down",
                                        shard=3)], seed=0))
    out, ok = sstore._fetch_shard(3, lambda g: np.arange(4), "t")
    assert not ok
    rep = get_heat().report()
    assert rep["shards"][0]["by_kind"]["failover"] == 1
    assert rep["shards"][3]["by_kind"]["degraded"] == 1
    assert rep["shards"][3]["rows"] == 0  # empty substitution has no rows


def test_hotspot_scenario_ranks_hot_shard_first(world, proxy):
    """Acceptance + ROADMAP item 3 fixture: the Zipfian skewed-workload
    run must rank the hot shard first, and the per-shard load-rate CDFs
    must separate hot from cold."""
    from wukong_tpu.runtime.emulator import Emulator

    sstore = _sstore(world)
    emu = Emulator(proxy)
    rep = emu.run_hotspot(n_ops=600, zipf_a=1.6, seed=7, sstore=sstore)
    assert rep["ranked"][0] == rep["hot"]
    assert rep["separation"] > 1.5
    shards = rep["report"]["shards"]
    hot_p50 = shards[rep["hot"]]["load_rate_cdf"][0.5]
    for s, d in shards.items():
        if s != rep["hot"] and d["load_rate_cdf"]:
            assert hot_p50 > d["load_rate_cdf"][0.5]
    # the hot shard carries the load share a Zipf(1.6) head implies
    assert shards[rep["hot"]]["share"] > 0.5


def test_top_endpoint_scrape(world):
    """GET /top (plain text) and /top.json (structured) serve the heat
    report through the metrics endpoint."""
    import socket
    import urllib.request

    from wukong_tpu.obs import maybe_start_metrics_http, stop_metrics_http

    sstore = _sstore(world)
    for i in (1, 1, 2):
        sstore._fetch_shard(i, lambda g: np.arange(16), "t")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    assert maybe_start_metrics_http(port=port) is not None
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/top", timeout=5).read().decode()
        assert "SHARDS" in body and "TEMPLATES" in body and "LANES" in body
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/top.json?k=2", timeout=5).read())
        assert js["shards"]["ranked"][0]["shard"] == 1
        assert len(js["shards"]["ranked"]) <= 2
    finally:
        stop_metrics_http()


# ---------------------------------------------------------------------------
# latency attribution + regression sentinel
# ---------------------------------------------------------------------------

def _fake_trace(total_us, parse_us, execute_us):
    tr = QueryTrace(kind="query")
    sp = tr.start_span("proxy.parse")
    tr.end_span(sp)
    sp.t1_us = sp.t0_us + parse_us
    sp2 = tr.start_span("cpu.execute")
    tr.end_span(sp2)
    sp2.t1_us = sp2.t0_us + execute_us
    tr.finish("SUCCESS")
    tr.t1_us = tr.t0_us + total_us
    return tr


def test_regression_sentinel_p95_trip_dumps_trace(monkeypatch):
    monkeypatch.setattr(Global, "attribution_min_samples", 8)
    monkeypatch.setattr(Global, "attribution_p95_drift_pct", 100)
    att = LatencyAttributor(window=64)
    for _ in range(10):
        assert att.observe(_fake_trace(1000, 100, 850), "T") is None
    slow = _fake_trace(5000, 120, 4800)
    v = att.observe(slow, "T")
    assert v is not None and v["reason"] == "P95_DRIFT"
    assert ("LATENCY_REGRESSION", slow) in list(get_recorder().dumps)
    assert get_registry().counter(
        "wukong_latency_regressions_total",
        labels=("template",)).value(template="T") >= 1


def test_regression_sentinel_first_trip_on_a_fresh_clock(monkeypatch):
    """A host up for less than the cooldown still sees its first
    regression."""
    import wukong_tpu.obs.profile as profile_mod

    monkeypatch.setattr(profile_mod, "get_usec", lambda: 2_000_000)
    monkeypatch.setattr(Global, "attribution_cooldown_s", 3600)
    monkeypatch.setattr(Global, "attribution_min_samples", 8)
    monkeypatch.setattr(Global, "attribution_p95_drift_pct", 100)
    att = LatencyAttributor(window=64)
    for _ in range(10):
        assert att.observe(_fake_trace(1000, 100, 850), "T") is None
    v = att.observe(_fake_trace(5000, 120, 4800), "T")
    assert v is not None and v["reason"] == "P95_DRIFT"


def test_regression_sentinel_component_shift(monkeypatch):
    monkeypatch.setattr(Global, "attribution_min_samples", 8)
    monkeypatch.setattr(Global, "attribution_share_drift_pct", 25)
    monkeypatch.setattr(Global, "attribution_p95_drift_pct", 10_000)
    att = LatencyAttributor(window=64)
    for _ in range(10):
        att.observe(_fake_trace(1000, 100, 850), "T")
    # same total (p95 quiet) but parse's share jumped 10% -> 60%
    v = att.observe(_fake_trace(1000, 600, 350), "T")
    assert v is not None and v["reason"] == "COMPONENT_SHIFT"
    assert v["component"] == "parse" and v["share_drift_pts"] > 25


def test_attribution_via_proxy_feeds_top(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_tracing", True)
    monkeypatch.setattr(Global, "enable_attribution", True)
    for _ in range(3):
        q = proxy.run_single_query(Q_CHAIN, device="cpu", blind=True)
        assert q.result.status_code == ErrorCode.SUCCESS
    rep = get_attributor().report()
    assert rep and rep[0]["count"] == 3
    assert rep[0]["top_component"] == "execute"
    text, js = render_top()
    assert js["templates"][0]["count"] == 3
    assert "sig:" in text  # the template key reached the rendered table


def test_attribution_off_is_untouched(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_tracing", True)
    proxy.run_single_query(Q_CHAIN, device="cpu", blind=True)
    assert get_attributor().report() == []


# ---------------------------------------------------------------------------
# satellites: payload sizing, heat-telemetry gate
# ---------------------------------------------------------------------------

def test_payload_size_shapes():
    a = np.arange(10, dtype=np.int64)
    assert payload_size((a, a[:3])) == (10, a.nbytes + a[:3].nbytes)
    assert payload_size(a) == (10, a.nbytes)
    assert payload_size(None) == (0, 0)
    assert payload_size((None, "x")) == (0, 0)


def test_heat_telemetry_gate_fixtures(tmp_path):
    """The new analysis gate: an unregistered placement-input metric and
    an unannotated shared structure are violations; the clean shape is
    not."""
    from wukong_tpu.analysis import run_analysis

    def write(tree: dict) -> str:
        root = tmp_path / "pkg"
        for rel, src in tree.items():
            p = root / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(src)
        return str(root)

    bad = write({"obs/heat.py": (
        "PLACEMENT_INPUTS = {'fetches': 'wukong_nope_total'}\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self.shards = {}\n"
        "        self.lock = make_lock('heat.x')\n")})
    out = run_analysis(bad, plugins=["heat-telemetry"])
    msgs = "\n".join(str(v) for v in out)
    assert "wukong_nope_total" in msgs  # unregistered placement input
    assert "A.shards" in msgs  # unannotated shared structure
    assert "heat.x" in msgs  # undeclared leaf lock

    good = write({"obs/heat.py": (
        "PLACEMENT_INPUTS = {'fetches': 'wukong_ok_total'}\n"
        "declare_leaf('heat.x')\n"
        "reg.counter('wukong_ok_total')\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self.shards = {}  # guarded by: _lock\n"
        "        self.lock = make_lock('heat.x')\n")})
    assert run_analysis(good, plugins=["heat-telemetry"]) == []


def test_monitor_heat_lines(world):
    from wukong_tpu.runtime.monitor import Monitor

    mon = Monitor()
    assert mon.heat_lines() == []  # quiet with nothing charged
    sstore = _sstore(world)
    sstore._fetch_shard(2, lambda g: np.arange(4), "t")
    lines = mon.heat_lines(k=2)
    assert len(lines) == 1 and "2:1" in lines[0]
    assert 2 in mon.shard_load_cdfs()
