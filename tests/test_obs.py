"""Observability subsystem: tracing, metrics registry, flight recorder.

Covers the ISSUE 3 acceptance surface: a traced query carries proxy /
queue / per-BGP-step (rows in/out) / shard-fetch spans; under an installed
FaultPlan the retry attempts and breaker events appear as span events
(chaos-marked); a deadline-expired query auto-dumps its trace through the
flight recorder; and MetricsRegistry.render_prometheus round-trips the
golden exposition format.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.obs import (
    MetricsRegistry,
    QueryTrace,
    activate,
    chrome_trace_events,
    get_recorder,
    get_registry,
    maybe_start_trace,
)
from wukong_tpu.obs.recorder import FlightRecorder
from wukong_tpu.runtime import faults
from wukong_tpu.runtime.faults import FaultPlan, FaultSpec, TransientFault
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.runtime.resilience import CircuitBreaker, Deadline, retry_call
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


@pytest.fixture(scope="module")
def world():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    return g, ss


@pytest.fixture()
def proxy(world):
    g, ss = world
    return Proxy(g, ss, CPUEngine(g, ss))


@pytest.fixture(autouse=True)
def _tracing_hygiene(monkeypatch):
    """Each test opts into tracing explicitly; the recorder starts empty
    and no fault plan leaks across tests."""
    monkeypatch.setattr(Global, "enable_tracing", False)
    monkeypatch.setattr(Global, "trace_sample_every", 1)
    monkeypatch.setattr(Global, "trace_dump_dir", "")
    get_recorder().clear()
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# metrics registry + golden Prometheus exposition format
# ---------------------------------------------------------------------------

GOLDEN = """\
# HELP q_latency_us Latency
# TYPE q_latency_us histogram
q_latency_us_bucket{le="10"} 2
q_latency_us_bucket{le="100"} 3
q_latency_us_bucket{le="+Inf"} 4
q_latency_us_sum 1157.5
q_latency_us_count 4
# HELP queries_total Queries served
# TYPE queries_total counter
queries_total{status="SUCCESS"} 3
queries_total{status="TIMEOUT"} 1
# HELP queue_depth Waiting queries
# TYPE queue_depth gauge
queue_depth 7
"""


def test_prometheus_golden_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("queries_total", "Queries served", labels=("status",))
    c.labels(status="SUCCESS").inc()
    c.labels(status="SUCCESS").inc(2)
    c.labels(status="TIMEOUT").inc()
    reg.gauge("queue_depth", "Waiting queries").set(7)
    h = reg.histogram("q_latency_us", "Latency", buckets=(10, 100))
    h.observe(3)
    h.observe(4.5)
    h.observe(50)
    h.observe(1100)
    assert reg.render_prometheus() == GOLDEN


def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x")
    b = reg.counter("x_total")
    assert a is b  # same family: cached handles and lookups converge
    a.inc(5)
    snap = reg.snapshot()
    assert snap["x_total"]["series"][0]["value"] == 5
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # kind mismatch is a programming error
    with pytest.raises(ValueError):
        reg.counter("bad name!")
    with pytest.raises(ValueError):
        a.inc(-1)  # counters only go up


def test_registry_reset_keeps_cached_handles():
    """reset() zeroes in place: module-level cached handles and fresh
    lookups must keep converging on the same (zeroed) series."""
    reg = MetricsRegistry()
    c = reg.counter("t_total")
    h = reg.histogram("t_lat", buckets=(10,))
    c.inc(3)
    h.observe(5)
    reg.reset()
    assert reg.counter("t_total") is c  # same family object survives
    assert c.value() == 0
    assert reg.snapshot()["t_lat"]["series"][0]["count"] == 0
    c.inc()  # the old handle still feeds the exported series
    assert reg.snapshot()["t_total"]["series"][0]["value"] == 1


def test_gauge_callback_and_labeled_callback():
    reg = MetricsRegistry()
    reg.gauge("depth").set_function(lambda: 42)
    reg.gauge("open_keys", labels=("name",)).set_function(
        lambda: {("dist.shard",): 3})
    text = reg.render_prometheus()
    assert "depth 42" in text
    assert 'open_keys{name="dist.shard"} 3' in text


def test_labeled_gauge_callback_drops_absent_series():
    """The callback's return IS the series set: a dead breaker/pool must
    disappear from the export, not linger at its last value."""
    reg = MetricsRegistry()
    g = reg.gauge("open_keys", labels=("name",))
    state = {("a",): 1}
    g.set_function(lambda: dict(state))
    assert 'open_keys{name="a"} 1' in reg.render_prometheus()
    state.clear()
    assert 'name="a"' not in reg.render_prometheus()


def test_histogram_bulk_observe():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(10,))
    h.observe(5, count=100)  # one call per device batch, not per query
    snap = reg.snapshot()["lat"]["series"][0]
    assert snap["count"] == 100 and snap["sum"] == 500


# ---------------------------------------------------------------------------
# trace context basics
# ---------------------------------------------------------------------------

def test_trace_spans_nest_and_export():
    tr = QueryTrace(kind="query")
    with tr.span("a"):
        with tr.span("b", step=1):
            tr.event("ev", k=2)
    assert [s.name for s in tr.spans] == ["a", "b"]
    assert tr.spans[0].depth == 0 and tr.spans[1].depth == 1
    assert tr.spans[1].events[0][1] == "ev"
    evs = chrome_trace_events([tr])
    assert any(e["ph"] == "X" and e["name"] == "a" for e in evs)
    assert any(e["ph"] == "i" and e["name"] == "ev" for e in evs)


def test_maybe_start_trace_respects_knobs(monkeypatch):
    assert maybe_start_trace() is None  # default off: zero-overhead path
    monkeypatch.setattr(Global, "enable_tracing", True)
    assert maybe_start_trace() is not None
    monkeypatch.setattr(Global, "trace_sample_every", 4)
    got = sum(maybe_start_trace() is not None for _ in range(16))
    assert got == 4  # 1 in N sampling


# ---------------------------------------------------------------------------
# end-to-end: traced query through the proxy (acceptance span set)
# ---------------------------------------------------------------------------

def test_traced_query_has_proxy_and_step_spans(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_tracing", True)
    q = proxy.run_single_query(Q_CHAIN, device="cpu", blind=True)
    assert q.result.status_code == ErrorCode.SUCCESS
    tr = get_recorder().last(1)[0]
    assert tr.status == "SUCCESS"
    names = [s.name for s in tr.spans]
    assert "proxy.parse" in names and "proxy.plan" in names
    assert "cpu.execute" in names
    steps = [s for s in tr.spans if s.name == "cpu.step"]
    assert len(steps) == 3  # one span per BGP step
    for sp in steps:  # rows in/out recorded at step granularity
        assert "rows_in" in sp.attrs and "rows_out" in sp.attrs
    assert steps[0].attrs["rows_in"] == 0
    assert steps[-1].attrs["rows_out"] == q.result.nrows
    # reply status reached the registry
    assert get_registry().counter(
        "wukong_queries_total", labels=("status", "tenant")).value(
            status="SUCCESS", tenant="default") >= 1


def test_traced_query_through_engine_pool_has_queue_span(world, monkeypatch):
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.runtime.scheduler import EnginePool
    from wukong_tpu.sparql.parser import Parser

    g, ss = world
    monkeypatch.setattr(Global, "enable_tracing", True)
    pool = EnginePool(num_engines=2,
                      make_engine=lambda tid: CPUEngine(g, ss))
    pool.start()
    try:
        q = Parser(ss).parse(Q_CHAIN)
        heuristic_plan(q)
        q.result.blind = True
        q.trace = maybe_start_trace(kind="query")
        out = pool.wait(pool.submit(q), timeout=30)
        assert out.result.status_code == ErrorCode.SUCCESS
        names = [s.name for s in q.trace.spans]
        assert "pool.queue" in names  # queue wait is its own span
        qs = next(s for s in q.trace.spans if s.name == "pool.queue")
        assert "engine" in qs.attrs  # closed by the popping engine thread
        assert "cpu.execute" in names
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# chaos: retry attempts / breaker events / fault sites land on the trace
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_retry_and_fault_events_appear_in_trace(monkeypatch):
    monkeypatch.setattr(Global, "retry_base_ms", 1)
    monkeypatch.setattr(Global, "retry_max_ms", 2)
    faults.install(FaultPlan([FaultSpec("dist.shard_fetch", "transient",
                                        count=2)], seed=0))
    tr = QueryTrace(kind="query")

    def attempt():
        faults.site("dist.shard_fetch", shard=3)
        return "ok"

    with activate(tr), tr.span("shard.fetch", shard=3):
        out = retry_call(attempt, site="dist.shard_fetch[3]",
                         retry_on=(TransientFault,))
    assert out == "ok"
    evs = tr.event_names()
    assert evs.count("fault.injected") == 2  # both injected transients
    assert evs.count("retry") == 2  # ...and both retry attempts
    sp = tr.spans[0]
    assert {n for (_t, n, _a) in sp.events} == {"fault.injected", "retry"}


@pytest.mark.chaos
def test_env_fault_plan_events_appear_in_trace(proxy, monkeypatch):
    """The WUKONG_FAULT_PLAN env form (acceptance wording): a traced query
    through the proxy while the pool.execute site faults carries the
    injected-fault and retry evidence on its trace."""
    monkeypatch.setattr(Global, "enable_tracing", True)
    monkeypatch.setattr(Global, "retry_base_ms", 1)
    monkeypatch.setattr(Global, "retry_max_ms", 2)
    monkeypatch.setenv("WUKONG_FAULT_PLAN",
                       "seed=3;stream.ingest:transient,count=1")
    monkeypatch.setitem(faults._state, "plan", None)
    monkeypatch.setitem(faults._state, "env_checked", False)
    from wukong_tpu.stream import StreamContext

    g, _ss = proxy.g, proxy.str_server
    ctx = StreamContext([g], proxy.str_server)
    ctx.feed(np.asarray([[131072, 2, 131073]], dtype=np.int64))
    tr = next(t for t in reversed(get_recorder().last())
              if t.kind == "stream")
    evs = tr.event_names()
    assert "fault.injected" in evs and "retry" in evs


@pytest.mark.chaos
def test_breaker_trip_and_close_events_appear_in_trace():
    clock = [0.0]
    br = CircuitBreaker(threshold=2, cooldown_ms=1000,
                        clock=lambda: clock[0])
    tr = QueryTrace(kind="query")
    with activate(tr), tr.span("shard.fetch", shard=0):
        br.record_failure(0)
        br.record_failure(0)  # trips
        clock[0] = 2.0  # past cooldown: half-open probe allowed
        assert br.allow(0)
        br.record_success(0)  # closes
    evs = tr.event_names()
    assert "breaker.trip" in evs and "breaker.close" in evs
    assert get_registry().counter(
        "wukong_breaker_trips_total", labels=("key",)).value(key="0") >= 1


@pytest.mark.chaos
def test_chaos_sharded_fetch_spans_in_dist_trace(world, monkeypatch):
    """Integration: a traced query over the sharded store under an
    installed FaultPlan carries shard.fetch spans whose events show the
    injected faults and retries."""
    from wukong_tpu.parallel.sharded_store import ShardedDeviceStore

    class _Mesh:  # only .devices.size is consulted by the store
        devices = np.empty(1, dtype=object)

    g, ss = world
    monkeypatch.setattr(Global, "retry_base_ms", 1)
    monkeypatch.setattr(Global, "retry_max_ms", 2)
    store = ShardedDeviceStore.__new__(ShardedDeviceStore)
    store.stores = [g]
    store.breaker = CircuitBreaker()
    store.degraded_shards = set()
    store.failover_shards = set()
    store.replicas = {}
    store.rotation = {}
    store._rotation_rr = {}
    store._event_noted = {}
    faults.install(FaultPlan([FaultSpec("dist.shard_fetch", "transient",
                                        count=1)], seed=0))
    tr = QueryTrace(kind="query")
    with activate(tr):
        out, ok = store._fetch_shard(0, lambda g: "csr", "segment(7,0)")
    assert (out, ok) == ("csr", True)
    [sp] = [s for s in tr.spans if s.name == "shard.fetch"]
    assert sp.attrs["shard"] == 0 and sp.attrs["ok"] is True
    evs = [n for (_t, n, _a) in sp.events]
    assert "fault.injected" in evs and "retry" in evs


# ---------------------------------------------------------------------------
# flight recorder: ring, dump-on-timeout, slow-query threshold
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_is_bounded_and_searchable():
    rec = FlightRecorder(capacity=4)
    for i in range(8):
        rec.on_complete(QueryTrace(kind="query", qid=100 + i))
    assert len(rec.last()) == 4  # bounded ring
    assert rec.find(107) is not None  # by qid
    assert rec.find(rec.last(1)[0].trace_id) is not None  # by trace id
    assert rec.find(100) is None  # evicted


def test_flight_recorder_dumps_on_timeout(proxy, monkeypatch, tmp_path):
    """A deadline-expired query auto-dumps its trace: in-memory AND as a
    JSON file when trace_dump_dir is set (ISSUE 3 acceptance)."""
    import wukong_tpu.runtime.proxy as proxy_mod

    monkeypatch.setattr(Global, "enable_tracing", True)
    monkeypatch.setattr(Global, "trace_dump_dir", str(tmp_path))

    class _Clock:  # expires after the first engine-side check
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            self.t += 0.6
            return self.t

    monkeypatch.setattr(
        proxy_mod.Deadline, "from_config",
        classmethod(lambda cls: Deadline(timeout_ms=1, clock=_Clock())))
    q = proxy.run_single_query(Q_CHAIN, device="cpu", blind=True)
    assert q.result.status_code == ErrorCode.QUERY_TIMEOUT
    rec = get_recorder()
    reasons = [r for r, _t in rec.dumps]
    assert "QUERY_TIMEOUT" in reasons
    files = os.listdir(tmp_path)
    assert len(files) == 1 and files[0].startswith("trace_")
    import json

    dump = json.load(open(tmp_path / files[0]))
    assert dump["reason"] == "QUERY_TIMEOUT"
    assert any(s["name"] == "cpu.execute" for s in dump["spans"])


def test_flight_recorder_slow_query_threshold(monkeypatch):
    monkeypatch.setattr(Global, "trace_slow_ms", 0)  # threshold off
    rec = FlightRecorder(capacity=8)
    tr = QueryTrace(kind="query")
    rec.on_complete(tr, ErrorCode.SUCCESS)
    assert not rec.dumps
    monkeypatch.setattr(Global, "trace_slow_ms", 1)
    slow = QueryTrace(kind="query")
    slow.t0_us -= 5_000  # pretend it ran 5ms
    rec.on_complete(slow, ErrorCode.SUCCESS)
    assert [r for r, _t in rec.dumps] == ["SLOW_QUERY"]


# ---------------------------------------------------------------------------
# stream epochs are traced too
# ---------------------------------------------------------------------------

def test_stream_epoch_traced(world, monkeypatch):
    from wukong_tpu.stream import StreamContext

    g, ss = world
    monkeypatch.setattr(Global, "enable_tracing", True)
    triples, _ = generate_lubm(1, seed=42)
    ctx = StreamContext([build_partition(triples[:100], 0, 1)], ss)
    ctx.register(PREFIX + "SELECT ?X ?Y WHERE { ?X ub:memberOf ?Y . }")
    ctx.feed(triples[100:200])
    tr = next(t for t in reversed(get_recorder().last())
              if t.kind == "stream")
    names = [s.name for s in tr.spans]
    assert "stream.ingest" in names and "stream.eval" in names
    assert "stream.eval_query" in names  # per-standing-query span


# ---------------------------------------------------------------------------
# tooling satellites: lint gate + overhead guard
# ---------------------------------------------------------------------------

def test_lint_obs_gate():
    """No bare print() in library code outside report paths — run the
    actual gate script the way CI would."""
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "lint_obs.py")
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_parse_failure_still_reaches_reply_observability(proxy, monkeypatch):
    """A query that dies in parse/plan (no reply object exists) must still
    land on the flight recorder and the status counter — a syntax-error
    storm is an operational signal, not a silent gap."""
    from wukong_tpu.utils.errors import WukongError

    monkeypatch.setattr(Global, "enable_tracing", True)
    with pytest.raises(WukongError):
        proxy.run_single_query("SELECT ?x WHERE { broken", device="cpu")
    [tr] = get_recorder().last(1)
    assert tr.status == "SYNTAX_ERROR"
    assert get_registry().counter(
        "wukong_queries_total", labels=("status", "tenant")).value(
            status="SYNTAX_ERROR", tenant="default") >= 1


def test_tracing_off_leaves_query_untouched(proxy):
    """Default path: no trace object reaches the query, no recorder entry
    (the zero-overhead contract the bench guard quantifies)."""
    q = proxy.run_single_query(Q_CHAIN, device="cpu", blind=True)
    assert q.result.status_code == ErrorCode.SUCCESS
    assert getattr(q, "trace", None) is None
    assert get_recorder().last() == []


# ---------------------------------------------------------------------------
# ROADMAP follow-up (e): HTTP scrape endpoint + periodic snapshot-to-file
# ---------------------------------------------------------------------------

def test_metrics_http_endpoint(monkeypatch):
    """GET /metrics serves the Prometheus exposition, /metrics.json the
    snapshot; metrics_port=0 (the default) starts nothing."""
    import json as _json
    import socket
    import urllib.request

    from wukong_tpu.obs import maybe_start_metrics_http, stop_metrics_http

    assert maybe_start_metrics_http(port=0) is None  # default: off
    with socket.socket() as s:  # find a free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = maybe_start_metrics_http(port=port)
    assert srv is not None
    try:
        get_registry().counter("wukong_obs_http_probe_total", "probe").inc()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "# TYPE wukong_obs_http_probe_total counter" in body
        js = _json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=5).read())
        assert js["wukong_obs_http_probe_total"]["kind"] == "counter"
        # idempotent: a second start reuses the running server
        assert maybe_start_metrics_http(port=port) is srv
    finally:
        stop_metrics_http()


def test_metrics_snapshotter_writes_file(tmp_path):
    import json as _json

    from wukong_tpu.obs import MetricsSnapshotter

    path = tmp_path / "soak_metrics.json"
    snap = MetricsSnapshotter(str(path), interval_s=0.1)
    get_registry().counter("wukong_obs_snap_probe_total", "probe").inc(3)
    snap.start()
    deadline = time.time() + 5
    while not path.exists() and time.time() < deadline:
        time.sleep(0.05)
    snap.stop()
    data = _json.loads(path.read_text())
    assert data["wukong_obs_snap_probe_total"]["series"][0]["value"] == 3.0
