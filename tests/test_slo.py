"""Tenant-aware SLO plane (ISSUE 10): per-tenant accounting, error
budgets, burn-rate sentinels, and the overload signal bus.

Acceptance surface: a tenant identity threads proxy -> pool -> reply
(stamped on the query, the trace, and every reply-side metric, bounded
to ``max_tenants`` label values with an ``__overflow__`` bucket);
``SLOTracker`` computes compliance / remaining error budget /
multi-window burn rates against config- or runtime-registered specs; the
burn sentinel counts ``wukong_slo_burn_alerts_total{tenant,window}`` and
dumps exactly one attributable trace per cooldown window; every
``ADMISSION_INPUTS`` entry is backed by a registered metric;
``Emulator.run_tenants`` (3 conflicting tenant classes, chaos variant)
is ROADMAP item 4's acceptance fixture; the off knob degrades every hook
to one check; and the ``slo-telemetry`` analysis gate holds the surface
statically. Satellite: the WCOJ measured-blowup feedback loop demotes
over-predicted templates to the walk.
"""

import json
import time

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.loader.lubm import UB, VirtualLubmStrings, generate_lubm
from wukong_tpu.obs import QueryTrace, get_recorder, get_registry
from wukong_tpu.obs.metrics import MetricsRegistry
from wukong_tpu.obs.slo import (
    ADMISSION_INPUTS,
    OVERFLOW_TENANT,
    SLOSpec,
    SLOTracker,
    get_overload,
    get_slo,
    parse_specs,
    render_slo,
    reset_labels,
    tenant_label,
)
from wukong_tpu.runtime import faults
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.runtime.resilience import Deadline
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.utils.errors import ErrorCode

pytestmark = pytest.mark.slo

PREFIX = """
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
"""
Q_CHAIN = PREFIX + """SELECT ?X ?Y WHERE {
    ?X ub:memberOf ?Y .
    ?Y ub:subOrganizationOf ?Z .
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
    """Accounting knobs at defaults; tracker/signals/labels/recorder
    clean; no fault plan leaks across tests."""
    monkeypatch.setattr(Global, "enable_tracing", False)
    monkeypatch.setattr(Global, "trace_sample_every", 1)
    monkeypatch.setattr(Global, "enable_tenant_accounting", True)
    monkeypatch.setattr(Global, "max_tenants", 64)
    monkeypatch.setattr(Global, "slo_specs", "")
    get_slo().reset()
    get_overload().reset()
    reset_labels()
    get_recorder().clear()
    faults.clear()
    yield
    get_slo().reset()
    get_overload().reset()
    reset_labels()
    faults.clear()


# ---------------------------------------------------------------------------
# tenant identity threading: proxy -> query -> trace -> metrics
# ---------------------------------------------------------------------------

def test_tenant_threads_query_trace_and_metrics(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_tracing", True)
    m = get_registry().counter("wukong_queries_total",
                               labels=("status", "tenant"))
    before = m.value(status="SUCCESS", tenant="gold")
    q = proxy.serve_query(Q_CHAIN, blind=True, tenant="gold")
    assert q.result.status_code == ErrorCode.SUCCESS
    assert q.tenant == "gold"
    [tr] = get_recorder().last(1)
    assert tr.tenant == "gold"
    assert tr.to_dict()["tenant"] == "gold"
    assert m.value(status="SUCCESS", tenant="gold") == before + 1
    # the reply landed on the tenant latency histogram + the SLO tracker
    c = get_slo().compliance("gold")
    assert c is not None and c["samples"] == 1


def test_default_tenant_path_unchanged(proxy):
    q = proxy.run_single_query(Q_CHAIN, device="cpu", blind=True)
    assert q.result.status_code == ErrorCode.SUCCESS
    assert q.tenant == "default"
    assert get_slo().compliance("default")["samples"] >= 1


def test_parse_error_still_reaches_tenant_accounting(proxy):
    from wukong_tpu.utils.errors import WukongError

    with pytest.raises(WukongError):
        proxy.serve_query("SELECT ?x WHERE { broken", tenant="gold")
    c = get_slo().compliance("gold")
    assert c is not None and c["errors"] == 1
    # the in-flight slot was released on the error path too
    assert get_overload().report()["tenants"]["gold"]["inflight"] == 0


# ---------------------------------------------------------------------------
# bounded label cardinality
# ---------------------------------------------------------------------------

def test_overflow_bucket_bounds_cardinality(monkeypatch):
    monkeypatch.setattr(Global, "max_tenants", 2)
    assert tenant_label("a") == "a"
    assert tenant_label("b") == "b"
    assert tenant_label("c") == OVERFLOW_TENANT
    assert tenant_label("a") == "a"  # seen tenants keep their label
    assert tenant_label(None) == OVERFLOW_TENANT  # "default" past the cap


def test_prometheus_golden_with_tenant_labels_and_overflow():
    reg = MetricsRegistry()
    c = reg.counter("wukong_queries_total",
                    "Proxy queries by reply status and tenant",
                    labels=("status", "tenant"))
    c.labels(status="SUCCESS", tenant="gold").inc(3)
    c.labels(status="SUCCESS", tenant=OVERFLOW_TENANT).inc()
    golden = (
        "# HELP wukong_queries_total Proxy queries by reply status and tenant\n"
        "# TYPE wukong_queries_total counter\n"
        'wukong_queries_total{status="SUCCESS",tenant="__overflow__"} 1\n'
        'wukong_queries_total{status="SUCCESS",tenant="gold"} 3\n')
    assert reg.render_prometheus() == golden


# ---------------------------------------------------------------------------
# SLO specs, compliance, error budget, burn rates
# ---------------------------------------------------------------------------

def test_parse_specs_forms():
    specs = parse_specs("gold:95:50:0.999; bulk:99:0:0.9")
    assert specs[0] == SLOSpec("gold", 0.95, 50.0, 0.999)
    assert specs[1].percentile == 0.99 and specs[1].latency_ms == 0.0
    with pytest.raises(ValueError):
        parse_specs("gold:95:50")  # missing availability


def test_config_declared_specs_apply(monkeypatch):
    monkeypatch.setattr(Global, "slo_specs", "cfg:95:100:0.99")
    t = SLOTracker(window=64)
    t.observe("cfg", 1000, ok=True)
    c = t.compliance("cfg")
    assert c["spec"] == {"percentile": 0.95, "latency_ms": 100.0,
                         "availability": 0.99}


def test_compliance_budget_and_burn_math():
    t = SLOTracker(window=128)
    t.register(SLOSpec("a", percentile=0.95, latency_ms=0.0,
                       availability=0.9))
    for i in range(20):
        t.observe("a", 1000, ok=(i % 2 == 0))  # 50% bad, budget 10%
    c = t.compliance("a")
    assert c["compliance"] == 0.5
    # burn = bad_frac / budget = 0.5 / 0.1 = 5 on both windows
    assert c["burn"]["fast"] == pytest.approx(5.0)
    assert c["burn"]["slow"] == pytest.approx(5.0)
    # budget remaining = 1 - 0.5/0.1 = -4 (overdrawn 4x)
    assert c["error_budget_remaining"] == pytest.approx(-4.0)


def test_latency_target_counts_as_bad():
    t = SLOTracker(window=64)
    t.register(SLOSpec("a", percentile=0.95, latency_ms=1.0,
                       availability=0.5))
    t.observe("a", 500, ok=True)     # under 1ms: good
    t.observe("a", 5000, ok=True)    # over 1ms: bad despite SUCCESS
    c = t.compliance("a")
    assert c["compliance"] == 0.5


def test_parse_specs_percent_availability_normalized():
    """'99.9' availability must mean three nines, not a 1e-9 budget that
    pages on every blip; junk availability is a config error."""
    [sp] = parse_specs("gold:95:50:99.9")
    assert sp.availability == pytest.approx(0.999)
    with pytest.raises(ValueError):
        parse_specs("gold:95:50:0")
    with pytest.raises(ValueError):
        parse_specs("gold:95:50:150")


def test_burn_windows_see_different_history():
    """The fast and slow windows must diverge: a 5-minute all-bad burst
    after an hour of clean traffic is a fast-window cliff but a diluted
    slow-window burn. (A raw sample deque capped at slo_window made both
    windows read the same recent samples at any real qps — the bucketed
    ring is the fix.)"""
    from wukong_tpu.obs.slo import _TenantSLO

    st = _TenantSLO(window=64)
    now = 10_000_000_000_000  # synthetic clock, us
    for t in range(now - 3_600_000_000, now - 300_000_000, 10_000_000):
        st.buckets.append((t, 10, 0))    # clean hour
    for t in range(now - 300_000_000, now, 10_000_000):
        st.buckets.append((t, 10, 10))   # all-bad 5-minute tail
    fast, n_fast = SLOTracker._burn(st, now, 300, 0.1)
    slow, n_slow = SLOTracker._burn(st, now, 3600, 0.1)
    assert fast == pytest.approx(10.0, rel=0.15)  # 100% bad / 10% budget
    assert slow < fast / 5  # diluted by the clean hour
    assert n_slow > n_fast


def test_repeats_validation_does_not_leak_inflight(proxy):
    from wukong_tpu.utils.errors import WukongError

    with pytest.raises(WukongError):
        proxy.run_single_query(Q_CHAIN, repeats=0, tenant="leaky")
    assert "leaky" not in get_overload().report()["tenants"]


def test_no_spec_no_burn_no_alert():
    t = SLOTracker(window=64)
    for _ in range(30):
        assert t.observe("anon", 1000, ok=False) is None
    c = t.compliance("anon")
    assert c["spec"] is None and "burn" not in c


# ---------------------------------------------------------------------------
# the burn-rate sentinel
# ---------------------------------------------------------------------------

def test_burn_sentinel_trips_counts_and_dumps(monkeypatch):
    monkeypatch.setattr(Global, "slo_dump_cooldown_s", 3600)
    t = SLOTracker(window=128)
    t.register(SLOSpec("gold", 0.95, 0.0, 0.999))
    tr = QueryTrace(kind="query", tenant="gold")
    tr.finish("ERROR")
    verdicts = [t.observe("gold", 1000, ok=False,
                          trace=tr) for _ in range(40)]
    trips = [v for v in verdicts if v is not None]
    # one trip for the whole burst (cooldown holds), both windows counted
    assert len(trips) == 1
    assert trips[0]["windows"] == ("fast", "slow")
    assert trips[0]["fast_burn"] >= Global.slo_burn_fast_x
    m = get_registry().counter("wukong_slo_burn_alerts_total",
                               labels=("tenant", "window"))
    assert m.value(tenant="gold", window="fast") >= 1
    assert m.value(tenant="gold", window="slow") >= 1
    # exactly ONE attributable dump per cooldown window
    dumps = [(r, d) for (r, d) in get_recorder().dumps if r == "SLO_BURN"]
    assert len(dumps) == 1 and dumps[0][1].tenant == "gold"


def test_burn_sentinel_first_trip_on_a_fresh_clock(monkeypatch):
    """``get_usec`` counts from the machine's boot: a host up for less than
    the cooldown still pages on its first burn."""
    import wukong_tpu.obs.slo as slo_mod

    monkeypatch.setattr(Global, "slo_dump_cooldown_s", 3600)
    monkeypatch.setattr(slo_mod, "get_usec", lambda: 5_000_000)
    t = SLOTracker(window=128)
    t.register(SLOSpec("gold", 0.95, 0.0, 0.999))
    trips = [v for v in (t.observe("gold", 1000, ok=False)
                         for _ in range(40)) if v is not None]
    assert len(trips) == 1


def test_burn_sentinel_min_samples_floor():
    t = SLOTracker(window=64)
    t.register(SLOSpec("a", 0.95, 0.0, 0.999))
    # a handful of bad replies must not page (BURN_MIN_SAMPLES floor)
    for _ in range(8):
        assert t.observe("a", 1000, ok=False) is None


def test_burn_sentinel_cooldown_rearms(monkeypatch):
    monkeypatch.setattr(Global, "slo_dump_cooldown_s", 0)
    t = SLOTracker(window=128)
    t.register(SLOSpec("a", 0.95, 0.0, 0.999))
    verdicts = [t.observe("a", 1000, ok=False) for _ in range(40)]
    # with no cooldown, every observe past the sample floor re-trips
    assert len([v for v in verdicts if v is not None]) > 1


def test_burn_sentinel_budget_absorbs_fault_rate():
    """The conflicting-SLO property: the same bad-reply rate trips a
    three-nines tenant and leaves a one-nine tenant quiet."""
    t = SLOTracker(window=256)
    t.register(SLOSpec("strict", 0.95, 0.0, 0.999))
    t.register(SLOSpec("loose", 0.95, 0.0, 0.5))
    strict = loose = 0
    for i in range(100):
        bad = i % 4 == 0  # 25% bad
        if t.observe("strict", 1000, ok=not bad) is not None:
            strict += 1
        if t.observe("loose", 1000, ok=not bad) is not None:
            loose += 1
    assert strict >= 1 and loose == 0


# ---------------------------------------------------------------------------
# the overload signal bus
# ---------------------------------------------------------------------------

def test_admission_inputs_backed_by_registered_metrics(proxy):
    """Runtime parity of the ADMISSION_INPUTS contract: every named
    metric exists in the live registry (the slo-telemetry gate holds the
    same statically)."""
    import wukong_tpu.runtime.scheduler  # noqa: F401 (registers gauges)

    snap = get_registry().snapshot()
    for signal, metric in ADMISSION_INPUTS.items():
        assert metric in snap, (signal, metric)


def test_overload_inflight_and_arrival_ewma():
    sig = get_overload()
    sig.note_admit("t1")
    sig.note_admit("t1")
    assert sig.report()["tenants"]["t1"]["inflight"] == 2
    assert sig.inflight_series()[("t1",)] == 2
    sig.note_done("t1")
    assert sig.report()["tenants"]["t1"]["inflight"] == 1
    # two arrivals = one gap = a live arrival-rate EWMA
    assert sig.report()["tenants"]["t1"]["arrival_qps"] > 0


def test_pool_queue_delay_and_utilization(world):
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.runtime.scheduler import EnginePool, _pool_utilization
    from wukong_tpu.sparql.parser import Parser

    g, ss = world["g"], world["ss"]
    get_overload().reset()
    pool = EnginePool(num_engines=2,
                      make_engine=lambda tid: CPUEngine(g, ss))
    pool.start()
    try:
        q = Parser(ss).parse(Q_CHAIN)
        heuristic_plan(q)
        q.result.blind = True
        out = pool.wait(pool.submit(q), timeout=30)
        assert out.result.status_code == ErrorCode.SUCCESS
        lanes = get_overload().lane_delay_series()
        assert ("default",) in lanes and lanes[("default",)] > 0
        assert 0.0 <= _pool_utilization() <= 1.0
    finally:
        pool.stop()


def test_pool_shed_counts_cause_and_tenant(world):
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.runtime.scheduler import EnginePool
    from wukong_tpu.sparql.parser import Parser
    from wukong_tpu.utils.errors import QueryTimeout

    g, ss = world["g"], world["ss"]
    m = get_registry().counter("wukong_shed_total",
                               labels=("cause", "tenant"))
    before = m.value(cause="queue_deadline", tenant="gold")
    pool = EnginePool(num_engines=1,
                      make_engine=lambda tid: CPUEngine(g, ss))
    pool.start()
    try:
        q = Parser(ss).parse(Q_CHAIN)
        heuristic_plan(q)
        q.result.blind = True
        q.tenant = "gold"
        q.deadline = Deadline(timeout_ms=1)
        time.sleep(0.02)  # expire in the queue
        out = pool.wait(pool.submit(q), timeout=30)
        assert isinstance(out, QueryTimeout)
        assert m.value(cause="queue_deadline", tenant="gold") == before + 1
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# the off knob: zero-cost accounting bypass
# ---------------------------------------------------------------------------

def test_off_knob_touches_nothing(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_tenant_accounting", False)
    q = proxy.serve_query(Q_CHAIN, blind=True, tenant="ghost")
    assert q.result.status_code == ErrorCode.SUCCESS
    assert q.tenant == "ghost"  # the identity still rides the query
    assert get_slo().compliance("ghost") is None
    assert "ghost" not in get_overload().report()["tenants"]
    lanes = get_overload().lane_delay_series()
    assert lanes == {}


# ---------------------------------------------------------------------------
# surfaces: /slo endpoint, console verb, Monitor line
# ---------------------------------------------------------------------------

def test_slo_endpoint_scrape(proxy):
    import socket
    import urllib.request

    from wukong_tpu.obs import maybe_start_metrics_http, stop_metrics_http

    get_slo().register(SLOSpec("gold", 0.95, 50.0, 0.99))
    proxy.serve_query(Q_CHAIN, blind=True, tenant="gold")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    assert maybe_start_metrics_http(port=port) is not None
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/slo", timeout=5).read().decode()
        assert "wukong-slo" in body and "gold" in body
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/slo.json", timeout=5).read())
        rows = {r["tenant"]: r for r in js["tenants"]}
        assert rows["gold"]["spec"]["availability"] == 0.99
        assert "error_budget_remaining" in rows["gold"]
        assert "burn" in rows["gold"]
        assert js["signals"]["inputs"] == ADMISSION_INPUTS
    finally:
        stop_metrics_http()


def test_console_slo_verb_and_tenant_flag(proxy, tmp_path, capsys):
    from wukong_tpu.runtime.console import Console

    qf = tmp_path / "q.sparql"
    qf.write_text(Q_CHAIN)
    con = Console(proxy)
    con.run_command(f"sparql -f {qf} -d cpu -t acme")
    assert get_slo().compliance("acme")["samples"] == 1
    con.run_command("slo -k 4")
    out = capsys.readouterr().out
    assert "wukong-slo" in out and "acme" in out


def test_monitor_slo_lines():
    from wukong_tpu.runtime.monitor import Monitor

    mon = Monitor()
    assert mon.slo_lines() == []  # quiet with no spec'd tenants
    get_slo().register(SLOSpec("gold", 0.95, 0.0, 0.99))
    for i in range(10):
        get_slo().observe("gold", 1000, ok=i % 2 == 0)
    lines = mon.slo_lines()
    assert len(lines) == 1
    assert lines[0].startswith("SLO[") and "gold" in lines[0]
    assert "burn" in lines[0]


def test_render_slo_empty_state():
    text, js = render_slo()
    assert "no tenant replies observed" in text
    assert js["tenants"] == []
    assert js["signals"]["inputs"] == ADMISSION_INPUTS


# ---------------------------------------------------------------------------
# Emulator.run_tenants — item 4's acceptance fixture
# ---------------------------------------------------------------------------

def _serving_texts(world, n=6):
    from wukong_tpu.types import OUT

    ss, g = world["ss"], world["g"]
    pid = ss.str2id(f"<{UB}advisor>")
    anchors = np.asarray(g.get_index(pid, OUT))[:n]
    return [f"SELECT ?s WHERE {{ ?s <{UB}advisor> "
            f"{ss.id2str(int(a))} . }}" for a in anchors]


def test_run_tenants_conflicting_slos(proxy, world):
    """Acceptance: 3 conflicting tenant classes produce per-tenant
    compliance / error budget / burn rates in the scenario result and
    /slo.json."""
    from wukong_tpu.runtime.emulator import Emulator

    out = Emulator(proxy).run_tenants(
        _serving_texts(world), duration_s=0.8, warmup_s=0.1, seed=3)
    assert set(out["tenants"]) == {"gold", "silver", "bulk"}
    for name, d in out["tenants"].items():
        assert d["served"] > 0, name
        slo = d["slo"]
        assert slo["spec"] is not None
        assert slo["compliance"] is not None
        assert "error_budget_remaining" in slo
        assert set(slo["burn"]) == {"fast", "slow"}
    # the same numbers are in the /slo.json body the scrape serves
    rows = {r["tenant"]: r for r in out["slo_json"]["tenants"]}
    assert set(rows) >= {"gold", "silver", "bulk"}
    assert out["qps"] > 0 and out["chaos"] is False


@pytest.mark.chaos
def test_run_tenants_chaos_trips_sentinel_with_one_dump(proxy, world):
    """Acceptance: the chaos variant (transient faults at proxy.serve,
    the same rate for every tenant) trips the burn sentinel only for
    tenants whose budget cannot absorb it, and dumps exactly one
    attributable trace per tenant per cooldown window."""
    from wukong_tpu.runtime.emulator import Emulator

    out = Emulator(proxy).run_tenants(
        _serving_texts(world), duration_s=1.2, warmup_s=0.1,
        chaos=True, chaos_p=0.3, seed=3)
    assert out["alerts"]["gold"] >= 1      # budget 0.001: burn ~300x
    assert out["alerts"]["bulk"] == 0      # budget 0.1: burn ~3x < slow_x
    assert out["burn_dumps"], "chaos must dump at least one trace"
    per_tenant: dict = {}
    for d in out["burn_dumps"]:
        assert d["tenant"] in ("gold", "silver")
        per_tenant[d["tenant"]] = per_tenant.get(d["tenant"], 0) + 1
    # one dump per tenant per cooldown window (cooldown >> run duration)
    assert all(n == 1 for n in per_tenant.values()), per_tenant
    # the injected faults also burned availability in the tracker
    assert out["tenants"]["gold"]["slo"]["compliance"] < 1.0


# ---------------------------------------------------------------------------
# satellites: dump attribution, wcoj feedback, the slo-telemetry gate
# ---------------------------------------------------------------------------

def test_flight_recorder_dump_carries_tenant():
    tr = QueryTrace(kind="query", tenant="acme")
    tr.finish("SUCCESS")
    get_recorder().dump(tr, "SLO_BURN")
    [(reason, dumped)] = list(get_recorder().dumps)
    assert reason == "SLO_BURN" and dumped.tenant == "acme"
    assert dumped.to_dict()["tenant"] == "acme"


def test_regression_sentinel_verdict_carries_tenant(monkeypatch):
    from wukong_tpu.obs.profile import LatencyAttributor

    monkeypatch.setattr(Global, "attribution_min_samples", 4)

    def fake(total_us):
        tr = QueryTrace(kind="query", tenant="acme")
        sp = tr.start_span("cpu.execute")
        tr.end_span(sp)
        sp.t1_us = sp.t0_us + int(total_us * 0.9)
        tr.finish("SUCCESS")
        tr.t1_us = tr.t0_us + total_us
        return tr

    att = LatencyAttributor(window=32)
    for _ in range(6):
        att.observe(fake(1000), "T")
    v = att.observe(fake(50_000), "T")
    assert v is not None and v["tenant"] == "acme"


def test_wcoj_measured_feedback_demotes_to_walk(monkeypatch):
    """Satellite: a template auto-routed wcoj on the over-predicted
    estimate is demoted to the walk once the measured prefix blowup
    shows wcoj did not keep intermediates near the fragment."""
    from wukong_tpu.loader.datagen import generate_triangle
    from wukong_tpu.planner.optimizer import Planner
    from wukong_tpu.planner.stats import Stats
    from wukong_tpu.sparql.ir import Pattern, SPARQLQuery
    from wukong_tpu.types import OUT

    monkeypatch.setattr(Global, "wcoj_min_rows", 1)
    triples, spec = generate_triangle(m=200, noise=4, seed=0)
    g = build_partition(triples, 0, 1)
    stats = Stats.generate(triples)
    p = Proxy(g, None, CPUEngine(g))
    p.planner = Planner(stats)

    def planned():
        q = SPARQLQuery()
        q.pattern_group.patterns = [Pattern(s, pr, OUT, o)
                                    for (s, pr, o) in spec["patterns"]]
        q.result.nvars = len(spec["vars"])
        q.result.required_vars = list(spec["vars"])
        q.result.blind = True
        p.planner.generate_plan(q)
        return q

    q = planned()
    q.join_strategy = p.classify_join_strategy(q)
    assert q.join_strategy == "wcoj"  # the estimate routes wcoj
    p._serve_execute(q, p.cpu)
    assert q.result.status_code == ErrorCode.SUCCESS
    # the REAL triangle keeps its prefix near the fragment: no demotion
    assert p.classify_join_strategy(planned()) == "wcoj"
    # a measured prefix blowup past wcoj_ratio demotes the template
    q2 = planned()
    q2.join_stats = [
        {"level": 0, "var": -1, "rows_in": 1, "rows_out": 5000,
         "candidates": 5000, "probes": 1, "time_us": 10},
        {"level": 1, "var": -2, "rows_in": 5000, "rows_out": 100,
         "candidates": 5100, "probes": 2, "time_us": 10}]
    q2.result.status_code = ErrorCode.SUCCESS
    before = get_registry().counter("wukong_join_demotions_total").value()
    p._record_wcoj_feedback(q2)
    assert p.classify_join_strategy(planned()) == "walk"
    assert get_registry().counter(
        "wukong_join_demotions_total").value() == before + 1
    # the measurement itself is introspectable through the plan cache
    key = (*p._plan_version(), "auto", int(Global.wcoj_ratio),
           int(Global.wcoj_min_rows))
    from wukong_tpu.runtime.batcher import template_signature

    assert p._plan_cache.aux(
        "wcoj_measured", template_signature(q2), key,
        lambda: None) == 50.0


def test_proxy_serve_fault_site_is_injectable(proxy):
    """The chaos scenario's injection point: a transient fault at
    proxy.serve surfaces as a client-visible error reply that reaches
    tenant accounting."""
    from wukong_tpu.runtime.faults import FaultPlan, FaultSpec, TransientFault

    faults.install(FaultPlan(
        [FaultSpec("proxy.serve", "transient", p=1.0, count=1)], seed=0))
    with pytest.raises(TransientFault):
        proxy.serve_query(Q_CHAIN, blind=True, tenant="gold")
    c = get_slo().compliance("gold")
    assert c["errors"] == 1
    # the plan is exhausted (count=1): the next query serves normally
    q = proxy.serve_query(Q_CHAIN, blind=True, tenant="gold")
    assert q.result.status_code == ErrorCode.SUCCESS


def test_slo_telemetry_gate_fixtures(tmp_path):
    """The new analysis gate: an unregistered admission-input metric, an
    unannotated shared structure, and an undeclared leaf lock are
    violations; the clean shape is not."""
    from wukong_tpu.analysis import run_analysis

    def write(tree: dict) -> str:
        root = tmp_path / "pkg"
        for rel, src in tree.items():
            p = root / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(src)
        return str(root)

    bad = write({"obs/slo.py": (
        "ADMISSION_INPUTS = {'shed': 'wukong_nope_total'}\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self.tenants = {}\n"
        "        self.lock = make_lock('slo.x')\n")})
    out = run_analysis(bad, plugins=["slo-telemetry"])
    msgs = "\n".join(str(v) for v in out)
    assert "wukong_nope_total" in msgs  # unregistered admission input
    assert "A.tenants" in msgs  # unannotated shared structure
    assert "slo.x" in msgs  # undeclared leaf lock

    good = write({"obs/slo.py": (
        "ADMISSION_INPUTS = {'shed': 'wukong_ok_total'}\n"
        "declare_leaf('slo.x')\n"
        "reg.counter('wukong_ok_total')\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self.tenants = {}  # guarded by: _lock\n"
        "        self.lock = make_lock('slo.x')\n")})
    assert run_analysis(good, plugins=["slo-telemetry"]) == []

    # a tree without an SLO plane is not checked (partial fixtures)
    empty = write({"other.py": "x = 1\n"})
    assert run_analysis(empty, plugins=["slo-telemetry"]) == []
