"""The segment store under pressure: a ``DeviceStore`` whose byte budget
holds the segments of any one of LUBM q1, q2, q7 but not of the three.

One client replays the three for three cycles through ``Proxy.serve_query``,
as the ``heavy`` mix does: every reply is the CPU oracle's, no fallback
counter moves, from the second cycle on every reply evicts what the last one
staged and stages its own again (``device.evict`` beside ``device.stage``),
no segment goes while a chain has it pinned, and the store is inside its
budget whenever no pin is held.
"""

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.tpu import TPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.planner.optimizer import make_planner
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.utils.paths import QUERIES

pytestmark = pytest.mark.obs

BUDGET = 4_000_000  # q1 3.0 MB, q2 2.0, q7 3.3; the three share 6.3 MB
FALLBACKS = ("wukong_join_fallback_total", "wukong_template_fallback_total")


def _text(k):
    with open(f"{QUERIES}/lubm/basic/lubm_q{k}") as f:
        return f.read()


def _rows(table):
    t = np.asarray(table, dtype=np.int64)
    return t[np.lexsort(t.T[::-1])] if len(t) else t


def _fallbacks(proxy):
    snap = proxy.metrics.snapshot()
    return {n: sum(s.get("value", 0) for s in
                   (snap.get(n) or {}).get("series", [])) for n in FALLBACKS}


@pytest.fixture(scope="module")
def replay():
    triples, _ = generate_lubm(2, seed=0)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(2, seed=0)
    proxy = Proxy(g, ss, CPUEngine(g, ss),
                  TPUEngine(g, ss, budget_bytes=BUDGET))
    proxy.planner = make_planner(triples, None)
    proxy.tpu.stats = proxy.planner.stats
    ds = proxy.tpu.dstore
    seen = {"evicted_pinned": [], "over_budget": [], "peak_unpinned": 0}
    evict, unpin = ds._evict, ds.unpin

    def watched_evict(key, why):
        if key in ds._pinned:
            seen["evicted_pinned"].append(key)
        return evict(key, why)

    def watched_unpin(keys):
        unpin(keys)
        if not ds._pinned:
            seen["peak_unpinned"] = max(seen["peak_unpinned"], ds.bytes_used)
            if ds.bytes_used > ds.budget:
                seen["over_budget"].append(ds.bytes_used)

    ds._evict, ds.unpin = watched_evict, watched_unpin
    before = _fallbacks(proxy)
    oracle = {k: _rows(proxy.run_single_query(
        _text(k), device="cpu", blind=False).result.table) for k in (1, 2, 7)}
    saved = Global.enable_tracing
    Global.enable_tracing = True
    try:
        replies = [(cycle, k, proxy.serve_query(_text(k), blind=False))
                   for cycle in range(3) for k in (1, 2, 7)]
    finally:
        Global.enable_tracing = saved
    return proxy, oracle, replies, seen, before


def test_every_reply_is_the_oracles_and_nothing_fell_back(replay):
    proxy, oracle, replies, _seen, before = replay
    for cycle, k, q in replies:
        assert int(q.result.status_code) == 0 and q.result.complete
        assert len(oracle[k]) > 0
        assert np.array_equal(_rows(q.result.table), oracle[k]), (cycle, k)
    assert _fallbacks(proxy) == before


def test_the_lru_goes_round_from_the_second_cycle_on(replay):
    _proxy, _oracle, replies, _seen, _before = replay
    for cycle, k, q in replies:
        events = [(n, a) for sp in q.trace.spans for _t, n, a in sp.events]
        names = [n for n, _a in events]
        if cycle == 0:
            assert "device.stage" in names, (cycle, k)
            continue
        assert "device.stage" in names and "device.evict" in names, (cycle, k)
        for n, a in events:
            if n == "device.evict":
                assert a["why"] in ("budget", "unpin") and a["bytes"] > 0
                assert isinstance(a["segment"], str)
        # an eviction makes room for a staging: never more out than fits
        assert sum(a["bytes"] for n, a in events if n == "device.evict") \
            <= BUDGET


def test_pins_hold_and_the_budget_holds_once_they_are_released(replay):
    proxy, _oracle, _replies, seen, _before = replay
    ds = proxy.tpu.dstore
    assert seen["evicted_pinned"] == []
    assert seen["over_budget"] == []
    assert 0 < seen["peak_unpinned"] <= BUDGET
    assert not ds._pinned and ds.bytes_used <= BUDGET
    # the books balance: what the store says it holds is what it holds
    assert ds.bytes_used == sum(s.nbytes for s in ds._cache.values()) + sum(
        dev.size * 4 for dev, _n in ds._index_cache.values())


def test_without_a_trace_an_eviction_costs_no_event(replay):
    proxy, oracle, _replies, _seen, _before = replay
    assert not Global.enable_tracing
    q = proxy.serve_query(_text(2), blind=False)  # evicts q7's, untraced
    assert getattr(q, "trace", None) is None
    assert np.array_equal(_rows(q.result.table), oracle[2])
