"""Device-busy seconds by program and the readers of PR 36's table, on two
small recorded traces: ``lubm1-tiny`` on a TPU v5e (the modules a parent
commit leaves unnamed: my chip run, PR 25) and a profile recorded here, on
the CPU, of programs named as the program names them, under the
``bench.window`` and ``serve:`` annotations the harness writes."""
import gzip
import os
import shutil
import time

import pytest

from benchmark import device_programs as dp
from benchmark import program_spans as ps
from benchmark import spec, xplane
from benchmark.driver import Reply
from benchmark.run import Run

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("named_busy_pct", "walk_busy_pct", "template_busy_pct",
       "probe_busy_ms_per_reply.lsqb", "compact_ms_per_reply.lsqb",
       "idle_while_host_join_pct.lsqb")


class Cell:
    name = "made-up"


class Req:
    def __init__(self, cls, kind):
        self.cls, self.kind = cls, kind


def read(name, run):
    return spec.layer_reader(name)(run)


@pytest.fixture()
def tpu_run(monkeypatch, tmp_path):
    """The recorded chip trace where ``run.py`` keeps a cell's profile."""
    monkeypatch.setattr(ps, "OUT", str(tmp_path))
    run = Run(Cell(), 1.0)
    d = os.path.join(ps.trace_dir(run), "plugins", "profile", "t")
    os.makedirs(d)
    with gzip.open(os.path.join(HERE, "data", "tiny_tpu.xplane.pb.gz")) as src, \
            open(os.path.join(d, "tiny.xplane.pb"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    run.trace = xplane.reduce(xplane.find_trace(ps.trace_dir(run)))
    return run


def test_modules_of_the_recorded_chip_trace(tpu_run):
    by = dp.busy_s(tpu_run)
    # the walk's kernels under the names a parent gave them, and JAX's own
    assert {"jit_expand", "jit_member_mask_known",
            "jit_broadcast_in_dim"} <= set(by)
    assert all(s > 0 for s in by.values())
    # modules of one device do not overlap: they sum to its busy time
    assert sum(by.values()) == pytest.approx(tpu_run.trace["busy_s"],
                                             abs=1e-9)
    assert max(by, key=by.get) in ("jit_expand", "jit_member_mask_known",
                                   "jit__compact_impl")


@pytest.mark.parametrize("metric", NEW)
def test_a_parent_reads_nothing(tpu_run, metric):
    """A program that names nothing, spans no compaction and runs no level
    probe: every new reader returns None, and nothing raises."""
    tpu_run.replies = []
    assert read(metric, tpu_run) is None


def wk_walk_step(x):
    return (x * 3 + 1) % 1_000_003


def wk_template_t0123abcd(x):
    return (x * 5 + 2) % 1_000_003


def wk_level_probe(x):
    return x % 7 == 0


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A window of three replies, each a walk step, a template program, a
    level probe and one eager operation, under ``serve:`` annotations and
    the program's spans; one more request half inside the window."""
    import jax
    import jax.numpy as jnp
    import jax.profiler

    from wukong_tpu.obs.trace import QueryTrace, span

    out = str(tmp_path_factory.mktemp("out"))
    fns = [jax.jit(f) for f in (wk_walk_step, wk_template_t0123abcd,
                                wk_level_probe)]
    x = jnp.arange(1 << 16, dtype=jnp.int32)
    for f in fns:
        jax.block_until_ready(f(x))
    jax.block_until_ready(x + 1)
    d = os.path.join(out, "trace", Cell.name)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tr = QueryTrace()
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("serve:q_half"):
            time.sleep(0.004)
            with jax.profiler.TraceAnnotation(xplane.WINDOW):
                time.sleep(0.004)
                for _ in range(3):
                    with jax.profiler.TraceAnnotation("serve:q"):
                        for f in fns:
                            jax.block_until_ready(f(x))
                        jax.block_until_ready(x + 1)  # eager, JAX's name
                        with span(tr, "wcoj.enumerate"):
                            time.sleep(0.003)
                        with span(tr, "wcoj.compact"):
                            time.sleep(0.002)
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    return out


@pytest.fixture()
def cpu_run(cpu_profile, monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(ps, "OUT", cpu_profile)
    run = Run(Cell(), 1.0)
    path = xplane.find_trace(ps.trace_dir(run))
    window, serves = None, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if e.name == xplane.WINDOW:
                    window = (e.start_ns, end)
                elif e.name.startswith(xplane.SERVE):
                    serves.append((e.name[len(xplane.SERVE):], e.start_ns,
                                   end))
    lo, hi = window
    run.trace = {"window_ns": window, "serves": serves,
                 "busy_intervals_ns": [[lo + (hi - lo) // 4,
                                        lo + (hi - lo) // 2]]}
    return run


def test_modules_of_a_recorded_cpu_profile(cpu_run):
    by = dp.busy_s(cpu_run)
    assert {"jit_wk_walk_step", "jit_wk_template_t0123abcd",
            "jit_wk_level_probe"} <= set(by)
    eager = [m for m in by if not m.startswith(dp.NAMED)]
    assert eager and all(by[m] > 0 for m in by)


def test_the_shares_of_a_recorded_cpu_profile(cpu_run):
    by = dp.busy_s(cpu_run)
    total = sum(by.values())
    named = read("named_busy_pct", cpu_run)
    walk = read("walk_busy_pct", cpu_run)
    template = read("template_busy_pct", cpu_run)
    assert walk == pytest.approx(100 * by["jit_wk_walk_step"] / total)
    assert template == pytest.approx(
        100 * by["jit_wk_template_t0123abcd"] / total)
    assert named == pytest.approx(100 * sum(
        s for m, s in by.items() if m.startswith("jit_wk_")) / total)
    assert 0 < walk < named < 100 and walk + template < named


def test_probe_ms_per_reply_of_a_recorded_cpu_profile(cpu_run):
    """Three replies inside the window, and the request that began before
    it counted by the share of it inside: three replies' worth and a
    part."""
    lo, hi = cpu_run.trace["window_ns"]
    half = next((a, b) for c, a, b in cpu_run.trace["serves"]
                if c == "q_half")
    part = (min(half[1], hi) - max(half[0], lo)) / (half[1] - half[0])
    assert 0 < part < 1
    assert dp.window_replies(cpu_run) == pytest.approx(3 + part)
    got = read("probe_busy_ms_per_reply.lsqb", cpu_run)
    assert got == pytest.approx(
        1e3 * dp.busy_s(cpu_run)["jit_wk_level_probe"] / (3 + part))


def test_idle_while_host_join_of_a_recorded_cpu_profile(cpu_run):
    """Busy in the window's second quarter only: the enumerations and
    compactions there are busy, the rest of them idle time they cover."""
    notes = ps.annotations(cpu_run)
    lo, hi = cpu_run.trace["window_ns"]
    (a, b), = cpu_run.trace["busy_intervals_ns"]
    idle = [[lo, a], [b, hi]]
    host = xplane.union((s, e) for n, s, e in notes
                        if n in ("wcoj.enumerate", "wcoj.compact"))
    want = 100 * xplane.total(ps.intersect(idle, host)) / (hi - lo - (b - a))
    got = read("idle_while_host_join_pct.lsqb", cpu_run)
    assert 0 < got < 100 and got == pytest.approx(want)


def test_compact_ms_per_reply_on_made_up_replies(cpu_run):
    def reply(spans, ok=True):
        r = Reply(Req("lsqb_q2", "heavy"))
        r.ok, r.spans, r.events = ok, spans, ()
        return r

    level = [("proxy.execute", 0, 0, 9000), ("wcoj.level", 1, 0, 8000),
             ("wcoj.enumerate", 2, 0, 3000), ("wcoj.compact", 2, 3000, 1500),
             ("wcoj.compact", 2, 4500, 500)]
    cpu_run.replies = [reply(level), reply(level[:3]),
                       reply(level, ok=False)]
    assert read("compact_ms_per_reply.lsqb", cpu_run) == pytest.approx(1.0)
    cpu_run.replies = [reply(level[:3])]
    assert read("compact_ms_per_reply.lsqb", cpu_run) is None


def test_the_table_is_declared_with_its_readers():
    import json

    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "workloads" not in declared["named_busy_pct"]
    for name in NEW:
        m = declared[name]
        assert callable(spec.layer_reader(name)) and m["moves"] == "qps"
        assert set(m.get("workloads", cells)) <= cells
    assert list(declared)[-len(NEW):] == list(NEW)  # appended, in order
