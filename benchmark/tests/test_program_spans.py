"""The readers of the program's own tracing on a made-up ``Run``: spans as
the 4-tuples ``driver.serve`` keeps, events, and a profile recorded here, on
the CPU, with the program's ``wk:`` annotations in it."""
import os
import time

import pytest

from benchmark import program_spans as ps
from benchmark import spec
from benchmark.driver import Reply
from benchmark.run import Run

US = 1000  # ns in a us


class Req:
    def __init__(self, cls, kind):
        self.cls, self.kind = cls, kind


class Cell:
    name = "made-up"


def walk(t0, parse, plan, stage, attempts, finalize, reply):
    """One reply's spans as the walk leaves them: ``attempts`` is a list of
    (dispatch_us, sync_us)."""
    spans, at = [], t0

    def add(name, depth, dur):
        nonlocal at
        spans.append((name, depth, at, dur))

    add("proxy.parse", 0, parse)
    at += parse
    add("proxy.plan", 0, plan)
    at += plan + 3  # a gap no span covers
    chain = sum(d + s for d, s in attempts) + 10  # 10 us of its own
    execute = stage + chain + finalize + 7
    add("proxy.execute", 0, execute + 5)
    add("tpu.execute", 1, execute)
    add("tpu.stage", 2, stage)
    at += stage
    add("tpu.chain", 2, chain)
    for d, s in attempts:
        add("tpu.dispatch", 3, d)
        at += d
        add("tpu.sync", 3, s)
        at += s
    at += 10
    add("tpu.finalize", 2, finalize)
    at += finalize + 12
    add("proxy.reply", 0, reply)
    return spans


def template(t0):
    spans = [("proxy.parse", 0, t0, 100), ("proxy.plan", 0, t0 + 100, 50),
             ("proxy.execute", 0, t0 + 150, 5000),
             ("template.execute", 1, t0 + 160, 4900),
             ("template.stage", 2, t0 + 170, 30),
             ("template.dispatch", 2, t0 + 200, 400),
             ("template.sync", 2, t0 + 600, 4000),
             ("template.commit", 2, t0 + 4600, 300),
             ("proxy.reply", 0, t0 + 5150, 60)]
    return spans


def reply(cls, kind, spans, events=(), ok=True):
    r = Reply(Req(cls, kind))
    r.ok, r.spans, r.events = ok, spans, tuple(events)
    r.t_send, r.t_done = 0.0, 1.0
    return r


@pytest.fixture()
def run():
    run = Run(Cell(), 1.0)
    d = "device.dispatch"
    run.replies = [
        reply("lubm_q4", "light",
              walk(0, 300, 200, 40, [(900, 3000)], 60, 75),
              ["retry", d, d, d]),
        reply("lubm_q5", "light",
              walk(9000, 100, 100, 20, [(500, 1000), (700, 2000)], 40, 45),
              [d, d, d, d, d]),
        reply("lubm_q7", "heavy", template(20000), [d]),
        # not counted: failed, spans of a program without serve_query's
        # spans, no spans at all
        reply("lubm_q6", "light", walk(0, 1, 1, 1, [(1, 1)], 1, 1), ok=False),
        reply("lubm_q6", "light", [("tpu.execute", 0, 0, 999),
                                   ("tpu.chain", 1, 0, 900)]),
        reply("lubm_q6", "light", None),
    ]
    return run


def read(name, run):
    return spec.layer_reader(name)(run)


@pytest.mark.parametrize("metric,want", [
    ("front_span_ms.light", (0.5 + 0.2) / 2),
    ("execute_span_ms.light",
     ((40 + 3910 + 60 + 7 + 5) + (20 + 4210 + 40 + 7 + 5)) / 2e3),
    ("host_dispatch_ms.light", ((40 + 900) + (20 + 500 + 700)) / 2e3),
    ("sync_wait_ms.light", (3000 + 3000) / 2e3),
    ("reply_side_ms.light", ((60 + 75) + (40 + 45)) / 2e3),
    ("syncs_per_reply.light", 1.5),
    ("dispatches_per_reply.light", 4.0),
    ("light_sync_wait_ms.mixed", 3.0),
    ("light_syncs_per_reply.mixed", 1.5),
    ("execute_span_ms.heavy", 5.0),
    ("sync_wait_ms.heavy", 4.0),
])
def test_span_reader_on_made_up_replies(run, metric, want):
    assert read(metric, run) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "front_span_ms.light", "execute_span_ms.light", "host_dispatch_ms.light",
    "sync_wait_ms.light", "reply_side_ms.light", "syncs_per_reply.light",
    "dispatches_per_reply.light", "light_sync_wait_ms.mixed",
    "light_syncs_per_reply.mixed", "execute_span_ms.heavy",
    "sync_wait_ms.heavy", "idle_while_sync_pct", "idle_while_dispatch_pct",
    "idle_outside_engine_pct"])
def test_reader_returns_nothing_for_a_program_without_the_spans(run, metric):
    """A parent commit: replies carry the engine's older spans only, the
    profile no ``wk:`` annotation. Nothing is read and nothing raises."""
    run.replies = [r for r in run.replies
                   if r.spans and r.spans[0][0] == "tpu.execute"]
    run.trace = {"window_ns": (0, 10), "busy_intervals_ns": [[2, 3]]}
    assert read(metric, run) is None


def test_self_time_is_duration_less_children(run):
    own = ps.self_ms(run.replies[0].spans)
    assert own["tpu.chain"] == pytest.approx(0.010)
    assert own["tpu.execute"] == pytest.approx(0.007)
    assert own["proxy.execute"] == pytest.approx(0.005)
    assert own["tpu.sync"] == pytest.approx(3.0)
    assert sum(own.values()) == pytest.approx(sum(
        s[3] for s in run.replies[0].spans if s[1] == 0) / 1e3)


def test_intersect():
    a = [[0, 10], [20, 30], [40, 50]]
    b = [[5, 25], [28, 45]]
    assert ps.intersect(a, b) == [[5, 10], [20, 25], [28, 30], [40, 45]]
    assert ps.intersect(a, []) == [] and ps.intersect([], b) == []


IDLE = ("idle_while_sync_pct", "idle_while_dispatch_pct",
        "idle_outside_engine_pct")


def test_idle_shares_on_made_up_annotations(run, monkeypatch):
    """Window 0-1000, busy 100-200 and 600-700: 800 idle. A sync covers
    150-500 (300 of it idle), a dispatch 400-650 (of the idle rest, 500-600),
    another thread's stage 900-950."""
    notes = (("tpu.sync", 150, 500), ("tpu.dispatch", 400, 650),
             ("template.stage", 900, 950), ("proxy.parse", 0, 1000))
    monkeypatch.setattr(ps, "annotations", lambda _run: notes)
    run.trace = {"window_ns": (0, 1000),
                 "busy_intervals_ns": [[100, 200], [600, 700]]}
    got = [read(m, run) for m in IDLE]
    assert got == pytest.approx([100 * 300 / 800, 100 * 150 / 800,
                                 100 * 350 / 800])
    assert sum(got) == pytest.approx(100.0)


def test_idle_shares_on_a_recorded_profile(run, monkeypatch, tmp_path):
    """The program's ``span`` helper under a profile recorded here: the
    ``wk:`` annotations come back by name, on one clock, and the shares of
    a made-up busy pattern over them sum to 100."""
    import jax.profiler

    from wukong_tpu.obs.trace import QueryTrace, span

    monkeypatch.setattr(ps, "OUT", str(tmp_path))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tr = QueryTrace()
    jax.profiler.start_trace(ps.trace_dir(run), profiler_options=opts)
    try:
        for _ in range(3):
            with span(tr, "proxy.execute"), span(tr, "tpu.execute"):
                with span(tr, "tpu.stage"):
                    time.sleep(0.002)
                with span(tr, "tpu.dispatch"):
                    time.sleep(0.004)
                with span(tr, "tpu.sync"):
                    time.sleep(0.008)
            time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    notes = ps.annotations(run)
    names = [n for n, _a, _b in notes]
    assert names.count("tpu.sync") == 3 and names.count("proxy.execute") == 3
    by = {n: [(a, b) for m, a, b in notes if m == n] for n in set(names)}
    for (a, b), sp in zip(sorted(by["tpu.sync"]),
                          [s for s in tr.spans if s.name == "tpu.sync"]):
        assert 7e6 < b - a < 50e6  # the 8 ms sleep, in ns
        assert abs((b - a) / 1e3 - sp.dur_us) < 2000  # the span beside it
    lo = min(a for _n, a, _b in notes)
    hi = max(b for _n, _a, b in notes)
    # busy during the first half of every sync: the rest of each sync is
    # idle-while-sync, stage and dispatch wholly idle, gaps outside
    busy = [[a, (a + b) // 2] for a, b in sorted(by["tpu.sync"])]
    run.trace = {"window_ns": (lo, hi), "busy_intervals_ns": busy}
    sync, dispatch, outside = (read(m, run) for m in IDLE)
    assert sync + dispatch + outside == pytest.approx(100.0, abs=1e-9)
    idle = (hi - lo) - sum(b - a for a, b in busy)
    assert sync == pytest.approx(
        100 * sum(b - (a + b) // 2 for a, b in by["tpu.sync"]) / idle)
    assert dispatch == pytest.approx(100 * sum(
        b - a for n in ("tpu.stage", "tpu.dispatch") for a, b in by[n]) / idle)
    assert outside > 0


def test_no_profile_no_annotations(run, monkeypatch, tmp_path):
    monkeypatch.setattr(ps, "OUT", str(tmp_path))
    assert ps.annotations(run) == ()
    run.trace = {"window_ns": (0, 10), "busy_intervals_ns": []}
    assert ps.idle_shares(run) is None


def test_every_new_metric_is_declared_with_a_reader():
    """``BENCHMARK.json`` names each metric of this file's readers, with the
    cells whose replies or profile give it something to read."""
    import json

    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    ends = {m["name"] for m in bench["end_to_end"]}
    for name in IDLE + ("front_span_ms.light", "syncs_per_reply.light",
                        "light_syncs_per_reply.mixed", "sync_wait_ms.heavy"):
        m = declared[name]
        assert callable(spec.layer_reader(name))
        assert m["moves"] in ends and m["workloads"]
        assert m["source"] in ("program_span", "program_counter",
                               "device_trace")
