"""Percentiles, rates, the traffic queue and the interval arithmetic of the
trace reducer, on made-up inputs."""
import collections

import numpy as np
import pytest

from benchmark import xplane
from benchmark.stats import ReplyLog, percentile
from benchmark.traffic import Traffic


def test_percentile_of_a_short_list_is_not_its_maximum():
    xs = list(range(1, 21))  # 1..20
    assert percentile(xs, 50) == 10.5
    assert percentile(xs, 95) == 19.05  # index int(20*0.95) would say 20
    assert percentile([7.0], 95) == 7.0
    assert percentile([], 50) is None


def test_rate_and_tail_count_a_stall():
    """40 replies of 10 ms, then the client stalls 2 s on one reply: the rate
    divides by the time that really passed, the failed reply is in no
    latency, and the tail shows the stall."""
    log = ReplyLog(t_open=100.0)
    t = 100.0
    for i in range(40):
        log.add("q4", "light", t, t + 0.010, ok=i != 5)
        t += 0.010
    log.add("q1", "heavy", t, t + 2.0, ok=True)
    assert log.attempted == 41 and log.failed == 1
    assert abs(log.elapsed_s - 2.4) < 1e-9
    assert abs(log.rate() - 40 / 2.4) < 1e-9
    assert abs(log.rate(wrong=2) - 38 / 2.4) < 1e-9
    light = log.latencies_ms(kind="light")
    assert len(light) == 39 and abs(percentile(light, 95) - 10.0) < 1e-6
    assert abs(max(log.latencies_ms()) - 2000.0) < 1e-6


def _mix(order="shuffled", close="reply"):
    return {"loop": "closed", "clients": 2, "order": order, "close": close,
            "classes": [
                {"name": "lubm_q5", "kind": "light",
                 "file": "lubm/light/lubm_q5", "per_block": 9,
                 "draw": {"dist": "uniform"}},
                {"name": "lubm_q2", "kind": "heavy",
                 "file": "lubm/heavy/lubm_q2", "per_block": 1}]}


def _traffic(seed, **kw):
    pool = np.arange(1000, 1200)
    return Traffic(_mix(**kw), seed, lambda iri: pool, lambda i: f"<d{i}>")


def test_queue_same_work_every_seed_in_another_order():
    a = [_traffic(7).take() for _ in range(1)]  # determinism, below
    qa, qb, qc = _traffic(7), _traffic(7), _traffic(2 ** 31 + 9)
    ta = [qa.take() for _ in range(50)]
    tb = [qb.take() for _ in range(50)]
    tc = [qc.take() for _ in range(50)]
    assert [r.text for r in ta] == [r.text for r in tb] and a[0].text == ta[0].text
    assert [r.cls for r in ta] != [r.cls for r in tc]
    for reqs in (ta, tc):  # the heavy share is exact in every block
        for k in range(0, 50, 10):
            kinds = collections.Counter(r.kind for r in reqs[k:k + 10])
            assert kinds == {"light": 9, "heavy": 1}
    assert all("%ub:" not in r.text for r in ta)
    # uniform over 200: no constant takes much more than its 1/200
    big = _traffic(3)
    texts = [big.take().text for _ in range(4000)]
    top = collections.Counter(t for t in texts if "<d" in t).most_common(1)[0][1]
    assert top / 3600 < 0.02
    assert set(big.constants().values()) <= set(range(1000, 1200))


def test_window_close_reply_and_cycle():
    q = _traffic(1, order="replay", close="cycle")
    got = [q.take(closing=False) for _ in range(13)]
    assert [r.cls for r in got[:10]] == ["lubm_q5"] * 9 + ["lubm_q2"]
    rest = []
    while (r := q.take(closing=True)) is not None:
        rest.append(r)
    assert len(rest) == 7  # the block in hand is given out whole
    assert _traffic(1).take(closing=True) is None  # close: reply stops at once


def test_interval_arithmetic():
    merged = xplane.union([(5, 9), (0, 3), (2, 4), (8, 12), (20, 21)])
    assert merged == [[0, 4], [5, 12], [20, 21]]
    assert xplane.total(xplane.clip(merged, 3, 10)) == 1 + 5
    assert xplane.gaps(merged, 0, 30) == [[4, 5], [12, 20], [21, 30]]
    red = {"busy_intervals_ns": merged}
    assert xplane.busy_within(red, [(0, 6), (4, 10)]) == (4 + 5) / 1e9


def test_a_mix_the_generator_cannot_make_is_refused():
    for kw in ({"order": "spaced"}, {"close": "never"}):
        with pytest.raises(SystemExit):
            _traffic(1, **kw)
    mix = _mix()
    mix["classes"][0]["draw"] = {"dist": "zipf", "a": 1.0}
    with pytest.raises(SystemExit):
        Traffic(mix, 1, lambda iri: np.arange(5), str)
    with pytest.raises(SystemExit):
        Traffic(dict(_mix(), loop="open"), 1, lambda iri: np.arange(5), str)
