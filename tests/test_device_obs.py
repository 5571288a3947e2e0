"""Device-observatory tests: padding efficiency hand-computed across
pad_pow2 capacity classes (including the all-padding and empty edge
cases), residency byte accounting across a store-version invalidation,
the variant-storm sentinel's once-per-cooldown contract, DEVICE_INPUTS
<-> registry parity, the /device scrape + console verb + Monitor line
surfaces, the EXPLAIN ANALYZE device table on a device-routed cyclic
query, and the off-knob zero-touch guarantee. The whole module runs
fully lockdep-checked (the observatory-suite posture)."""

import json
import socket
import time
import urllib.request

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.join.kernels import pad_pow2
from wukong_tpu.join.wcoj import JoinTableCache
from wukong_tpu.loader.datagen import (
    CyclicStrings,
    cyclic_query_text,
    generate_triangle,
)
from wukong_tpu.obs.device import (
    DEVICE_INPUTS,
    CompileLedger,
    get_device_obs,
    maybe_device_dispatch,
    maybe_device_resident,
    note_feedback,
    read_device_input,
    render_device,
)
from wukong_tpu.obs.events import get_journal
from wukong_tpu.obs.metrics import get_registry, snapshot_labeled_value
from wukong_tpu.obs.tsdb import get_tsdb
from wukong_tpu.planner.optimizer import Planner
from wukong_tpu.planner.stats import Stats
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.store.gstore import build_partition

pytestmark = pytest.mark.device


@pytest.fixture(autouse=True, scope="module")
def _lockdep_checked():
    """Ledger charges fire from engine sync points — the suite runs with
    the lock-order checker live and teardown asserts zero cycles and
    zero declared-leaf inversions."""
    from wukong_tpu.analysis import lockdep

    lockdep.install(True)
    yield
    try:
        assert lockdep.cycles() == [], lockdep.cycles()
        assert lockdep.leaf_violations() == [], lockdep.leaf_violations()
    finally:
        lockdep.install(False)


@pytest.fixture(autouse=True)
def _hygiene(monkeypatch):
    """Device knobs at defaults, the process-wide observatory + journal
    + tsdb clean before and after every test."""
    monkeypatch.setattr(Global, "enable_device_obs", True)
    monkeypatch.setattr(Global, "enable_events", True)
    get_device_obs().reset()
    get_journal().clear()
    get_tsdb().reset()
    yield
    get_device_obs().reset()


# ---------------------------------------------------------------------------
# padding efficiency: hand-computed across pad_pow2 capacity classes
# ---------------------------------------------------------------------------

def test_padding_efficiency_hand_computed():
    """Charge live-row counts straight out of the engine's pad_pow2
    buckets and check live/padded to the digit, per site and overall."""
    lives = [1, 700, 1024, 1025, 5000]
    caps = [pad_pow2(n) for n in lives]
    assert caps == [1024, 1024, 1024, 2048, 8192]
    for n, c in zip(lives, caps):
        rec = maybe_device_dispatch("t.probe", template="p1",
                                    live=n, capacity=c, wall_us=10)
        assert rec["padding_efficiency"] == round(n / c, 4)
    want = sum(lives) / sum(caps)
    got = read_device_input("padding_efficiency", site="t.probe")
    assert got == pytest.approx(want)
    assert read_device_input("padding_efficiency") == pytest.approx(want)


def test_padding_efficiency_edge_cases():
    """All-padding dispatches (0 live rows against a full class) drive
    efficiency to 0.0; capacity-free dispatches (no padded tensor) leave
    it undefined rather than polluting the ratio."""
    assert read_device_input("padding_efficiency") is None  # nothing yet
    maybe_device_dispatch("t.empty", template="e", live=0, capacity=0)
    assert read_device_input("padding_efficiency") is None  # still no class
    rec = maybe_device_dispatch("t.allpad", template="a",
                                live=0, capacity=1024)
    assert rec["padding_efficiency"] == 0.0
    assert read_device_input("padding_efficiency", site="t.allpad") == 0.0
    # the capacity-free site stays absent from the per-site gauge map
    assert "t.empty" not in \
        get_device_obs().dispatch_ledger.site_efficiencies()


def test_dispatch_cold_warm_and_report_rows():
    """Cold = a (site, template, capacity) variant's first call; repeats
    of the same variant are warm, a new capacity class is cold again."""
    for _ in range(3):
        maybe_device_dispatch("t.chain", template="d2", live=500,
                              capacity=1024, wall_us=100)
    maybe_device_dispatch("t.chain", template="d2", live=1500,
                          capacity=2048, wall_us=100)
    counts = read_device_input("dispatches", site="t.chain")
    assert counts == {"count": 4, "cold": 2, "warm": 2, "wall_us": 400}
    rows = {(r["template"], r["capacity"]): r
            for r in get_device_obs().dispatch_ledger.report(10)}
    assert rows[("d2", 1024)]["dispatches"] == 3
    assert rows[("d2", 1024)]["cold"] == 1
    assert rows[("d2", 1024)]["warm"] == 2
    assert rows[("d2", 2048)]["cold"] == 1
    assert read_device_input("variants", site="t.chain") == 2


# ---------------------------------------------------------------------------
# residency: byte accounting across a store-version invalidation
# ---------------------------------------------------------------------------

class _FakeStore:
    version = 7


def test_residency_bytes_across_version_invalidation(monkeypatch):
    """JoinTableCache dseg fills charge their exact device bytes; a
    store-version bump reaps the stale tables as ONE invalidate edge
    carrying their summed bytes; the high-water survives the drop."""
    monkeypatch.setattr(Global, "join_table_cache", 64)
    g = _FakeStore()
    cache = JoinTableCache(g)
    a = np.zeros(100, dtype=np.int32)   # 400 B each
    t1 = (a, a, a, 2)                   # dseg tuple: 1200 B device-side
    t2 = (a, a, a, 3)
    cache._put((7, "dseg", 11, 0), t1)
    cache._put((7, "dseg", 12, 0), t2)
    res = get_device_obs().residency
    assert res.totals() == {"join_table": 2400}
    assert read_device_input("residency_high_water") == 2400
    snap0 = get_registry().snapshot()

    g.version = 8  # store mutation: the old tables are unreachable
    cache._put((8, "dseg", 11, 0), t1)
    assert res.totals() == {"join_table": 2400 - 2400 + 1200}
    assert read_device_input("resident_bytes") == {"join_table": 1200}
    assert read_device_input("residency_high_water") == 2400
    snap1 = get_registry().snapshot()
    inv = (snapshot_labeled_value(snap1, "wukong_device_residency_total",
                                  kind="join_table", event="invalidate")
           - snapshot_labeled_value(snap0, "wukong_device_residency_total",
                                    kind="join_table", event="invalidate"))
    assert inv == 1  # one edge, not one per reaped entry

    # same-version edge dedup: a second invalidate on version 8 still
    # drops bytes but does not mint a second edge
    assert res.invalidate("join_table", 1200, version=8) is False
    assert res.totals()["join_table"] == 0
    snap2 = get_registry().snapshot()
    assert snapshot_labeled_value(
        snap2, "wukong_device_residency_total",
        kind="join_table", event="invalidate") == snapshot_labeled_value(
        snap1, "wukong_device_residency_total",
        kind="join_table", event="invalidate")


def test_residency_lru_evict_charges_bytes(monkeypatch):
    """LRU pressure on the join-table cache surfaces as evict edges and
    the byte total returns to the survivors' sum."""
    monkeypatch.setattr(Global, "join_table_cache", 2)
    cache = JoinTableCache(_FakeStore())
    a = np.zeros(64, dtype=np.int32)  # 256 B
    for i in range(3):
        cache._put((7, "dseg", i, 0), (a, a, a, 2))
    res = get_device_obs().residency
    assert res.totals()["join_table"] == 2 * 768  # one entry evicted
    snap = get_registry().snapshot()
    assert snapshot_labeled_value(snap, "wukong_device_residency_total",
                                  kind="join_table", event="evict") >= 1


def test_residency_budget_flag(monkeypatch):
    monkeypatch.setattr(Global, "device_budget_mb", 1)
    maybe_device_resident("fill", "segment", 2 << 20)
    st = get_device_obs().residency.stats()
    assert st["over_budget"] is True
    assert "OVER BUDGET" in render_device()[0]


# ---------------------------------------------------------------------------
# variant-storm sentinel: trips once per cooldown
# ---------------------------------------------------------------------------

def test_storm_trips_once_per_cooldown():
    led = CompileLedger(limit=3, cooldown_s=0.05)
    storms = []
    for i in range(8):  # 8 distinct variants minted back-to-back
        _cold, storm = led.note("s", f"t{i}", 1024)
        if storm is not None:
            storms.append((i, storm))
    assert len(storms) == 1  # trips when the window crosses the limit...
    assert storms[0][0] == 3 and storms[0][1] == 4
    time.sleep(0.06)  # ...and not again until the cooldown elapses
    for i in range(8, 13):
        _cold, storm = led.note("s", f"t{i}", 1024)
        if storm is not None:
            storms.append((i, storm))
    assert len(storms) == 2
    # warm re-dispatches never count as mints
    assert led.note("s", "t0", 1024) == (False, None)


def test_storm_first_trip_on_a_fresh_clock(monkeypatch):
    """A host up for less than the cooldown still sees its first storm."""
    import wukong_tpu.obs.device as device_mod

    monkeypatch.setattr(device_mod, "get_usec", lambda: 1_000)
    led = CompileLedger(limit=3, cooldown_s=60.0)
    storms = [led.note("s", f"t{i}", 1024)[1] for i in range(5)]
    assert [s for s in storms if s is not None] == [4]


def test_storm_journals_event_once(monkeypatch):
    """Through the facade: a storm journals ONE device.variant_storm
    ClusterEvent (and survives an empty FlightRecorder ring)."""
    monkeypatch.setattr(Global, "device_variant_limit", 2)
    monkeypatch.setattr(Global, "device_storm_cooldown_s", 60.0)
    for i in range(6):
        maybe_device_dispatch("t.storm", template=f"v{i}", live=1,
                              capacity=1024)
    evs = get_journal().last(kind="device.variant_storm")
    assert len(evs) == 1
    assert evs[0].attrs["site"] == "t.storm"
    assert evs[0].attrs["minted_in_window"] == 3
    assert evs[0].attrs["limit"] == 2
    snap = get_registry().snapshot()
    assert snapshot_labeled_value(snap, "wukong_device_variant_storms_total",
                                  site="t.storm") == 1


# ---------------------------------------------------------------------------
# DEVICE_INPUTS <-> registry parity and the read contract
# ---------------------------------------------------------------------------

def test_device_inputs_all_registered():
    registered = set(get_registry().snapshot())
    for signal, metric in DEVICE_INPUTS.items():
        assert metric in registered, (signal, metric)


def test_read_device_input_contract():
    with pytest.raises(KeyError):
        read_device_input("no_such_signal")
    with pytest.raises(KeyError):
        # declared, but metric-backed only: the reader must say so
        read_device_input("bytes_moved")
    assert read_device_input("dispatches")["count"] == 0
    assert read_device_input("resident_bytes") == {}


def test_trend_reads_through_tsdb():
    from wukong_tpu.obs.device import device_trend

    assert device_trend() == {}  # cold start: no samples, no rates
    for _ in range(4):
        maybe_device_dispatch("t.trend", template="d1", live=10,
                              capacity=1024)
        get_tsdb().sample_once()
        time.sleep(0.01)
    tr = device_trend()
    assert tr and tr["dispatches_per_s"] > 0


# ---------------------------------------------------------------------------
# surfaces: /device scrape, console verb, Monitor line, EXPLAIN table
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5).read().decode()


def test_device_scrape_endpoint(monkeypatch):
    from wukong_tpu.obs import maybe_start_metrics_http, stop_metrics_http

    port = _free_port()
    monkeypatch.setattr(Global, "metrics_host", "127.0.0.1")
    assert maybe_start_metrics_http(port=port) is not None
    try:
        maybe_device_dispatch("t.http", template="d1", live=512,
                              capacity=1024, wall_us=250)
        maybe_device_resident("fill", "segment", 4096)
        body = _get(port, "/device")
        assert "wukong-device" in body and "DISPATCH" in body
        assert "RESIDENT" in body
        js = json.loads(_get(port, "/device.json"))
        assert js["dispatches"]["count"] == 1
        assert js["by_site_efficiency"]["t.http"] == 0.5
        assert js["residency"]["by_kind"]["segment"] == 4096
        assert js["inputs"] == DEVICE_INPUTS
    finally:
        stop_metrics_http()


@pytest.fixture()
def tri_proxy():
    triples, meta = generate_triangle(m=60, noise=3, seed=1)
    g = build_partition(triples, 0, 1)
    ss = CyclicStrings(meta)
    stats = Stats.generate(triples)
    proxy = Proxy(g, ss, cpu_engine=CPUEngine(g, ss),
                  planner=Planner(stats))
    return proxy, cyclic_query_text(meta)


def _force_device_wcoj(monkeypatch):
    monkeypatch.setattr(Global, "wcoj_min_rows", 1)
    monkeypatch.setattr(Global, "wcoj_ratio", 1)
    monkeypatch.setattr(Global, "join_device", "device")


def test_console_device_verb(tri_proxy, monkeypatch, capsys):
    from wukong_tpu.runtime.console import Console

    proxy, text = tri_proxy
    _force_device_wcoj(monkeypatch)
    proxy.serve_query(text, blind=True)
    con = Console(proxy)
    assert con.run_command("device") is True
    out = capsys.readouterr().out
    assert "wukong-device" in out and "wcoj.probe" in out
    assert con.run_command("device -j -k 2") is True
    js = json.loads(capsys.readouterr().out)
    assert js["dispatches"]["count"] >= 1
    assert js["residency"]["by_kind"].get("join_table", 0) > 0


def test_monitor_device_line(tri_proxy, monkeypatch):
    from wukong_tpu.runtime.monitor import Monitor

    mon = Monitor()
    assert mon.device_lines() == []  # quiet before any dispatch
    proxy, text = tri_proxy
    _force_device_wcoj(monkeypatch)
    proxy.serve_query(text, blind=True)
    lines = mon.device_lines()
    assert len(lines) == 1 and lines[0].startswith("Device[")
    assert "pad_eff" in lines[0] and "resident" in lines[0]


def test_explain_analyze_device_table(tri_proxy, monkeypatch):
    """EXPLAIN ANALYZE on a device-routed cyclic query renders the
    per-step device table: every WCOJ probe level shows up with its
    capacity class, live rows, and cold/warm temperature."""
    proxy, text = tri_proxy
    _force_device_wcoj(monkeypatch)
    rep = proxy.explain_query(text, analyze=True)
    assert rep["route"] == "device"
    steps = rep["device_steps"]
    assert steps and all(s["site"] == "wcoj.probe" for s in steps)
    assert all(s["capacity"] >= s["live"] > 0 for s in steps)
    assert all(s["temp"] in ("cold", "warm") for s in steps)
    rendered = rep["rendered"]
    assert "device:" in rendered and "wcoj.probe" in rendered
    # the observatory's ledger saw the same dispatches the table shows
    counts = read_device_input("dispatches", site="wcoj.probe")
    assert counts["count"] >= len(steps)


# ---------------------------------------------------------------------------
# off knob: zero-touch
# ---------------------------------------------------------------------------

def test_off_knob_is_zero_touch(tri_proxy, monkeypatch):
    """enable_device_obs=False: the seams return None / no-op, the
    ledgers stay empty across a full device-routed query, and the
    feedback counter holds still."""
    monkeypatch.setattr(Global, "enable_device_obs", False)
    snap0 = get_registry().snapshot()
    assert maybe_device_dispatch("t.off", template="x", live=1,
                                 capacity=1024) is None
    maybe_device_resident("fill", "segment", 1 << 20)
    note_feedback("join_route", "demote_host")
    proxy, text = tri_proxy
    _force_device_wcoj(monkeypatch)
    monkeypatch.setattr(Global, "enable_device_obs", False)
    proxy.serve_query(text, blind=True)
    obs = get_device_obs()
    assert obs.dispatch_ledger.report(10) == []
    assert obs.residency.totals() == {}
    assert obs.compile_ledger.variant_counts() == {}
    snap1 = get_registry().snapshot()
    for metric in DEVICE_INPUTS.values():
        assert (snap1.get(metric) or {}).get("series", []) == \
            (snap0.get(metric) or {}).get("series", []), metric
    text_out, js = render_device()
    assert "enable_device_obs is OFF" in text_out
    assert js["enabled"] is False
