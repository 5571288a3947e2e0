"""Runtime layer: proxy, console commands, monitor, emulator (CPU mesh)."""

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.tpu import TPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.runtime.console import Console
from wukong_tpu.runtime.emulator import Emulator, load_mix_config
from wukong_tpu.runtime.monitor import Monitor
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.store.gstore import build_partition

from wukong_tpu.utils.paths import LUBM_BASIC as BASIC
from wukong_tpu.utils.paths import LUBM_EMULATOR as EMU


@pytest.fixture(scope="module")
def proxy():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    return Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))


def test_run_single_query(proxy):
    q = proxy.run_single_query(open(f"{BASIC}/lubm_q4").read(), repeats=2,
                               device="cpu", blind=False)
    assert q.result.status_code == 0
    assert q.result.nrows > 0


def test_run_single_query_with_plan(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_planner", False)
    q = proxy.run_single_query(
        open(f"{BASIC}/lubm_q2").read(),
        plan_text=open(f"{BASIC}/osdi16_plan/lubm_q2.fmt").read(),
        device="cpu")
    assert q.result.status_code == 0


def test_gsck_via_proxy(proxy):
    assert proxy.gstore_check() == 0


def test_console_commands(proxy, capsys):
    c = Console(proxy)
    assert c.run_command("help")
    assert c.run_command("config -v")
    assert c.run_command(f"sparql -f {BASIC}/lubm_q5 -d cpu -n 2")
    assert c.run_command("gsck -i -n")
    assert c.run_command("logger 2")
    assert c.run_command("bogus-command")  # unknown -> error, not crash
    assert not c.run_command("quit")
    out = capsys.readouterr().out
    assert "help" in out or "config" in out or True


def test_monitor_cdf():
    m = Monitor()
    for i in range(100):
        m.add_latency(float(i), qtype=0)
    cdf = m.cdf(0)
    assert cdf[0.5] == pytest.approx(50, abs=2)
    assert cdf[1.0] == 99


def test_mix_config_and_template_fill(proxy):
    mix = load_mix_config(f"{EMU}/mix_config", proxy.str_server)
    assert len(mix.templates) == 6 and len(mix.heavies) == 0
    for tmpl in mix.templates:
        proxy.fill_template(tmpl)
        assert all(len(c) > 0 for c in tmpl.candidates)


def test_emulator_cpu_path(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_tpu", False)
    mix = load_mix_config(f"{EMU}/mix_config", proxy.str_server)
    out = Emulator(proxy).run(mix, duration_s=0.5, warmup_s=0.1)
    assert out["thpt_qps"] > 0


def test_emulator_tpu_batch_path(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_tpu", True)
    mix = load_mix_config(f"{EMU}/mix_config", proxy.str_server)
    out = Emulator(proxy).run(mix, duration_s=1.0, warmup_s=0.2, batch=64)
    assert out["thpt_qps"] > 0


def test_batch_counts_match_single(proxy):
    """execute_batch per-query counts == per-instance single execution."""
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.sparql.parser import Parser

    tmpl = Parser(proxy.str_server).parse_template(open(f"{EMU}/q1").read())
    proxy.fill_template(tmpl)
    rng = np.random.default_rng(7)
    consts = tmpl.candidates[0][rng.integers(0, len(tmpl.candidates[0]), 32)]
    q0 = tmpl.instantiate(rng)
    heuristic_plan(q0)
    counts = proxy.tpu.execute_batch(q0, np.asarray(consts, dtype=np.int64))
    for i, c in enumerate(consts):
        qi = tmpl.instantiate(rng)
        # patch with OUR const and replan
        qi.pattern_group.patterns[tmpl.pos[0][0]].object = int(c)
        heuristic_plan(qi)
        qi.result.blind = True
        proxy.cpu.execute(qi, from_proxy=False)
        assert counts[i] == qi.result.nrows, (i, int(c))


def test_engine_pool_executes_and_steals(proxy):
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.runtime.scheduler import EnginePool
    from wukong_tpu.sparql.parser import Parser

    pool = EnginePool(num_engines=4,
                      make_engine=lambda tid: CPUEngine(proxy.g, proxy.str_server))
    pool.start()
    try:
        qids = []
        for i in range(16):
            q = Parser(proxy.str_server).parse(open(f"{BASIC}/lubm_q5").read())
            heuristic_plan(q)
            q.result.blind = True
            # pile everything onto engine 0 so neighbors must steal
            qids.append(pool.submit(q, tid=0))
        outs = [pool.wait(qid, timeout=30) for qid in qids]
        assert all(o is not None and o.result.status_code == 0 for o in outs)
        assert all(o.result.nrows == outs[0].result.nrows for o in outs)
    finally:
        pool.stop()


def test_emulator_heavy_mix(proxy, monkeypatch):
    monkeypatch.setattr(Global, "enable_tpu", False)
    mix = load_mix_config(f"{EMU}/mix_config_heavy", proxy.str_server)
    assert len(mix.heavies) == 4 and len(mix.templates) == 0
    out = Emulator(proxy).run(mix, duration_s=0.5, warmup_s=0.1)
    assert out["thpt_qps"] > 0


def test_dist_fallback_on_unsupported_shape():
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
    from wukong_tpu.parallel.dist_engine import DistEngine
    from wukong_tpu.parallel.mesh import make_mesh
    from wukong_tpu.store.gstore import build_all_partitions, build_partition

    triples, _ = generate_lubm(1, seed=42)
    ss = VirtualLubmStrings(1, seed=42)
    g = build_partition(triples, 0, 1)
    stores = build_all_partitions(triples, 8)
    dist = DistEngine(stores, ss, make_mesh(8))
    p = Proxy(g, ss, CPUEngine(g, ss), None, dist)
    # versatile query: dist rejects, proxy must fall back to the host engine
    q = p.run_single_query(
        "SELECT ?X ?P WHERE { ?X ?P <http://www.Department0.University0.edu> . }",
        device="dist", blind=False)
    assert q.result.status_code == 0
    assert q.result.nrows > 0


def test_sharded_proxy_routes_unpinned_requests_to_the_sharded_engine(
        monkeypatch):
    """A proxy that holds the sharded engine serves an unpinned request
    through it (``proxy.route`` says ``dist``); a pinned ``device=`` is
    honoured; a proxy without one routes as before."""
    import jax

    from wukong_tpu.parallel.dist_engine import DistEngine
    from wukong_tpu.parallel.mesh import make_mesh
    from wukong_tpu.store.gstore import build_all_partitions

    triples, _ = generate_lubm(1, seed=42)
    ss = VirtualLubmStrings(1, seed=42)
    g = build_partition(triples, 0, 1)
    dist = DistEngine(build_all_partitions(triples, 4), ss,
                      make_mesh(4, jax.devices()[:4]))
    sharded = Proxy(g, ss, CPUEngine(g, ss), None, dist)
    single = Proxy(g, ss, CPUEngine(g, ss), None)
    monkeypatch.setattr(Global, "enable_tracing", True)
    text = open(f"{BASIC}/lubm_q2").read()

    def served(p, device=None):
        q = p.serve_query(text, blind=False, device=device)
        assert q.result.status_code == 0
        spans = {sp.name for sp in q.trace.spans}
        routes = [a.get("route") for sp in q.trace.spans
                  for (_t, n, a) in sp.events if n == "proxy.route"]
        return spans, routes, q.result.nrows

    spans, routes, n = served(sharded)
    assert "dist.execute" in spans and "cpu.execute" not in spans
    assert routes == ["dist"]
    spans, routes, n_cpu = served(sharded, "cpu")
    assert "cpu.execute" in spans and "dist.execute" not in spans
    assert routes == ["walk"] and n_cpu == n
    spans, _routes, _n = served(sharded, "dist")
    assert "dist.execute" in spans
    spans, routes, n_single = served(single)
    assert "dist.execute" not in spans and "cpu.execute" in spans
    assert routes == ["walk"] and n_single == n


def test_sparql_batch_mode(proxy, tmp_path):
    c = Console(proxy)
    batch = tmp_path / "batch"
    batch.write_text(
        f"sparql -f {BASIC}/lubm_q5 -d cpu\n"
        f"# comment line\n"
        f"sparql -f {BASIC}/lubm_q4 -d cpu -n 2\n")
    assert c.run_command(f"sparql -b {batch}")
    # exclusive flags rejected cleanly (error logged, nothing executed)
    import wukong_tpu.runtime.console as con

    errors = []
    orig = con.log_error
    con.log_error = lambda msg: errors.append(msg)
    try:
        assert c.run_command(f"sparql -f {BASIC}/lubm_q5 -b {batch}")
        assert c.run_command("sparql")
        assert c.run_command("sparql -b /no/such/file")
        nested = batch.parent / "nested"
        nested.write_text(f"sparql -b {nested}\n")
        assert c.run_command(f"sparql -b {nested}")
    finally:
        con.log_error = orig
    assert len(errors) == 4
    assert "exclusive" in errors[0] and "exclusive" in errors[1]
    assert "cannot read" in errors[2] and "nested" in errors[3]


def test_mt_factor_never_truncates_results(proxy):
    """-m must not silently slice the index scan on single-driver engines."""
    full = proxy.run_single_query(open(f"{BASIC}/lubm_q2").read(),
                                  device="cpu", blind=True)
    sliced = proxy.run_single_query(open(f"{BASIC}/lubm_q2").read(),
                                    device="cpu", blind=True, mt_factor=8)
    assert sliced.result.nrows == full.result.nrows


def test_emulator_open_loop_pool(proxy, monkeypatch):
    """Host path keeps -p queries in flight across the engine pool; every
    submitted query completes and is recorded."""
    monkeypatch.setattr(Global, "enable_tpu", False)
    mix = load_mix_config(f"{EMU}/mix_config", proxy.str_server)
    emu = Emulator(proxy)
    out = emu.run(mix, duration_s=0.5, warmup_s=0.1, parallel=4)
    assert out["thpt_qps"] > 0
    # all latency records drained (no stranded in-flight queries)
    assert proxy.engine_pool().poll() == []


def test_emulator_heavy_batched_device(proxy, monkeypatch):
    """Heavy index-origin emulator classes go through execute_batch_index."""
    monkeypatch.setattr(Global, "enable_tpu", True)
    calls = []
    orig = proxy.tpu.execute_batch_index

    def spy(q, B, slice_mode=False):
        calls.append(B)
        return orig(q, B, slice_mode)

    monkeypatch.setattr(proxy.tpu, "execute_batch_index", spy)
    import os
    import tempfile

    from wukong_tpu.utils.paths import LUBM_BASIC as basic
    d = tempfile.mkdtemp()
    with open(os.path.join(d, "mix"), "w") as f:
        f.write(f"0 1\n{basic}/lubm_q2 1\n")
    mix = load_mix_config(os.path.join(d, "mix"), proxy.str_server)
    out = Emulator(proxy).run(mix, duration_s=0.5, warmup_s=0.1)
    assert out["thpt_qps"] > 0
    assert calls and all(b >= 1 for b in calls)


def test_emulator_templates_q7_to_q12(proxy):
    """The reference's extended emulator templates: direction terminators
    (`<-`) and %<fromPredicate> placeholders (proxy.hpp:76-99) must fill
    and execute. Instantiated constants must come from the right side of
    the predicate index."""
    import numpy as np

    from wukong_tpu.sparql.parser import Parser

    rng = np.random.default_rng(0)
    for qn in ("q7", "q8", "q9", "q10", "q11", "q12"):
        text = open(f"{EMU}/{qn}").read()
        t = Parser(proxy.str_server).parse_template(text)
        proxy.fill_template(t)
        q = t.instantiate(rng)
        from wukong_tpu.planner.heuristic import heuristic_plan

        heuristic_plan(q)
        proxy.cpu.execute(q)
        assert q.result.status_code == 0, qn
        assert q.result.nrows > 0, qn

    # %<fromPredicate> in an OBJECT slot draws the predicate's objects
    tq11 = Parser(proxy.str_server).parse_template(
        open(f"{EMU}/q11").read())
    proxy.fill_template(tq11)
    (pi, fld), = tq11.pos
    pat = tq11.query.pattern_group.patterns[pi]
    from wukong_tpu.types import OUT

    objs = set(int(x) for x in proxy.g.get_index(pat.predicate, OUT))
    assert fld == "object"
    assert set(int(c) for c in tq11.candidates[0]) <= objs


def test_engine_pool_failure_detection_and_respawn():
    """Beyond the reference (wukong.cpp:252 TODO: no supervision at all):
    an engine THREAD death fails its in-flight query (no stranded waiter),
    the tid respawns with a fresh engine, and queued work still completes.
    Past MAX_RESPAWNS the engine is declared dead and routed around."""
    import threading
    import time as _time

    from wukong_tpu.runtime.scheduler import EnginePool

    class Bomb:
        """Engine whose execute kills the whole THREAD on 'die' queries."""

        def __init__(self, tid):
            self.tid = tid

        def execute(self, q):
            if q == "die":
                raise SystemExit(13)  # escapes the per-query except Exception
            return ("ok", self.tid, q)

    pool = EnginePool(num_engines=2, make_engine=Bomb)
    pool._neighbors = lambda tid: []  # no stealing: deterministic victim
    pool.start()
    try:
        # normal operation
        assert pool.wait(pool.submit("a"), timeout=10)[0] == "ok"

        # thread death: the in-flight query FAILS (waiter not stranded)...
        qid = pool.submit("die", tid=0)
        out = pool.wait(qid, timeout=10)
        assert isinstance(out, RuntimeError)
        # ...and the tid respawned: work routed to it still completes
        deadline = _time.time() + 10
        while pool.health()[0]["respawns"] != 1:
            assert _time.time() < deadline
            _time.sleep(0.01)
        assert pool.wait(pool.submit("b", tid=0), timeout=10)[0] == "ok"
        h = pool.health()
        # a served query resets the crash budget (decay): isolated poison
        # queries over time must never accumulate into declare-dead
        assert h[0]["alive"] and h[0]["respawns"] == 0

        # crash loop: exceed MAX_RESPAWNS -> dead, submissions route around
        for _ in range(EnginePool.MAX_RESPAWNS + 1):
            out = pool.wait(pool.submit("die", tid=0), timeout=10)
            assert isinstance(out, RuntimeError)
        deadline = _time.time() + 10
        while pool.health()[0]["alive"]:
            assert _time.time() < deadline
            _time.sleep(0.01)
        # dead engine: new work still completes (on the survivor)
        for _ in range(4):
            assert pool.wait(pool.submit("c", tid=0), timeout=10)[0] == "ok"
        assert pool.health()[1]["alive"]
        assert threading.active_count() >= 1
    finally:
        pool.stop()


def test_emulator_inflight_window(proxy, monkeypatch):
    """After a class's first device batch learns capacities, subsequent
    draws ride the CROSS-CLASS flight (run_batch_const_mixed): W=parallel
    batches dispatch back-to-back and sync once (the device path's
    honoring of -p). With one class in the mix, every drawn job is that
    class."""
    monkeypatch.setattr(Global, "enable_tpu", True)
    mix = load_mix_config(f"{EMU}/mix_config", proxy.str_server)
    mix.templates = mix.templates[:1]  # one class => deterministic warm-up
    mix.heavies = []
    mix.weights = mix.weights[:1]
    calls = []
    orig = proxy.tpu.merge.run_batch_const_mixed

    def spy(jobs):
        calls.append(len(jobs))
        return orig(jobs)

    monkeypatch.setattr(proxy.tpu.merge, "run_batch_const_mixed", spy)
    out = Emulator(proxy).run(mix, duration_s=8.0, warmup_s=0.5, batch=64,
                              parallel=4)
    assert out["thpt_qps"] > 0
    assert calls and all(w == 4 for w in calls), calls
