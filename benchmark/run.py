#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It looks for the chips the cell asks for and exits non-zero
without them; makes the data (from the configuration's data seed) and the
traffic (from ``--seed``) and builds the served system as
``runtime/console.py`` builds it; warms the cell's own query classes until a
pass compiles nothing; drives ``Proxy.serve_query`` from the cell's closed
loop for ``--seconds``; then compares a sample of the replies with the plain
reference and prints one JSON line: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared beside its limit. Earlier lines on standard
output carry phase seconds and the per-class table; the checks are also the
last lines on standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()  # set-up counts from here: before jax is imported

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT  # run as a script: import from the checkout's root
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PLATFORM = "tpu"
CACHE = os.path.join(HERE, ".cache")
OUT = os.path.join(HERE, "out")


def say(line: str, **fields) -> None:
    print(json.dumps({"line": line, **fields}, default=str), flush=True)


class Compiles:
    """Programs handed to the backend compiler (persistent-cache lookups
    included), counted through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Degradations:
    """What the program does instead of its device path, counted without
    its tracing: executions of the proxy's CPU engine (capacity and shape
    fallbacks), pattern steps the device engine ran on the host, and the
    proxy's own fallback counters."""

    def __init__(self, proxy):
        self.proxy = proxy
        self.cpu_engine_executions = 0
        self.host_steps = 0
        cpu_execute = proxy.cpu.execute
        host_step = proxy.tpu.cpu._execute_one_pattern

        def counted_execute(*a, **kw):
            self.cpu_engine_executions += 1
            return cpu_execute(*a, **kw)

        def counted_step(*a, **kw):
            self.host_steps += 1
            return host_step(*a, **kw)

        proxy.cpu.execute = counted_execute
        proxy.tpu.cpu._execute_one_pattern = counted_step

    def counts(self, replies, native_loader: bool) -> dict[str, int]:
        snap = self.proxy.metrics.snapshot()

        def family(name):
            return int(sum(s.get("value", 0) for s in
                           (snap.get(name) or {}).get("series", [])))

        return {
            "cpu_engine_executions": self.cpu_engine_executions,
            "host_steps": self.host_steps,
            "join_fallbacks": family("wukong_join_fallback_total"),
            "template_fallbacks": family("wukong_template_fallback_total"),
            "device_probe_broken": sum(1 for r in replies if r.device_broken),
            "fallback_events": sum(1 for r in replies for e in r.events
                                   if e.endswith(".fallback")),
            "numpy_loader": 0 if native_loader else 1,
        }


class Run:
    """What the metric readers read."""

    def __init__(self, cell, seconds):
        self.cell = cell
        self.seconds = seconds
        self.setup_s = None
        self.log = None  # stats.ReplyLog of the window
        self.replies = []  # driver.Reply, in queue order
        self.wrong = 0  # replies the check found wrong
        self.memory_peak_bytes = None
        self.compiles_in_window = None
        self.trace = None  # xplane.reduce(...) of the traced run
        self.ref = None  # the plain reference (counts for the bytes model)
        self.peaks = None  # benchmark/peaks.json entry of this device


def alter_an_answer(proxy) -> None:
    """A fault planted where the reply is produced: the last row overwritten
    with the first. The reply still says SUCCESS and complete, with as many
    rows; only the row-for-row comparison can tell."""
    import numpy as np

    inner = proxy._serve_execute

    def altered(q, eng, pinned=False):
        q = inner(q, eng, pinned=pinned)
        t = np.array(q.result.table)
        if len(t) > 1:
            t[-1] = t[0]
            q.result.set_table(t)
        return q

    proxy._serve_execute = altered


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != PLATFORM:
        raise SystemExit(f"benchmark: JAX found no {PLATFORM} (platform is "
                         f"{dev['platform']}): not run, no number printed")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX "
                         f"reports {len(devs)}")
    return dev


def warm_up(world, traffic, compiles, annotate: bool) -> list[dict]:
    """Pass after pass over the cell's own classes until one pass hands
    nothing to the backend compiler; every warm reply has to succeed."""
    from benchmark.driver import serve

    passes = []
    for k in range(traffic.warm_passes_max):
        n0, t0 = compiles.n, time.perf_counter()
        for req in traffic.warm_pass(k):
            r = serve(world.proxy, req, annotate)
            if not r.ok:
                raise SystemExit(f"benchmark: warm-up request {req.cls} "
                                 f"failed: {r.status}")
        passes.append({"seconds": round(time.perf_counter() - t0, 2),
                       "compiles": compiles.n - n0})
        if compiles.n == n0:
            break
    return passes


def profile_window(trace_dir: str, seconds: float):
    """-> ``on_open`` for ``run_window``: trace the first ``seconds`` of the
    window under the ``bench.window`` annotation, on the main thread."""
    import jax.profiler

    from benchmark.xplane import WINDOW

    def on_open(_t_open: float) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # the annotations, not every allocation
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()

    return on_open


def class_table(log, replies) -> dict:
    from benchmark.stats import percentile

    table = {}
    for cls in sorted({r.req.cls for r in replies}):
        lat = log.latencies_ms(cls=cls)
        routes: dict[str, int] = {}
        for r in replies:
            if r.req.cls == cls and r.route:
                routes[r.route] = routes.get(r.route, 0) + 1
        table[cls] = {"replies": len(lat), "p50_ms": percentile(lat, 50),
                      "p95_ms": percentile(lat, 95),
                      "max_ms": max(lat, default=None), "routes": routes}
    return table


def run_cell(cell, seed: int, seconds: float, trace: bool, dev: dict,
             control: str | None = None, break_program=None) -> dict:
    """Everything after the look for a chip. ``break_program(world)`` lets a
    test plant a fault under the timed path; ``control`` breaks a guarantee
    after the warm-up: ``partial`` switches on the program's own
    partial-answer path (``query_budget_rows``), ``alter`` alters an id of
    every reply that still says SUCCESS."""
    from wukong_tpu.config import Global
    from wukong_tpu.utils.compilecache import setup_persistent_cache

    import jax

    from benchmark import check, spec, xplane
    from benchmark.driver import FrontSpans, run_window
    from benchmark.reference import Reference
    from benchmark.traffic import Traffic

    run = Run(cell, seconds)
    run.peaks = spec.peaks(dev["kind"]) if dev["platform"] == PLATFORM else None
    cache_dir = setup_persistent_cache()
    # the program keeps only programs that took the compiler a second or
    # more; a run here is a new process every time, so keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = Compiles()
    say("device", device=dev, compile_cache_dir=cache_dir)

    data_dir = os.path.join(CACHE, cell.config_name)  # + _d<data seed>
    world = spec.loader(cell.config["generator"]).load(cell.config, seed,
                                                       data_dir)
    say("load", seed=seed, **world.facts, seconds=world.seconds)
    t0 = time.perf_counter()
    ref = run.ref = Reference(world.triples, world.index_rows)
    traffic = Traffic(cell.mix, seed, ref.instances, world.id2str)
    degr = Degradations(world.proxy)
    if break_program is not None:
        break_program(world)
    Global.enable_tracing = bool(trace)
    if trace:
        FrontSpans(world.proxy)
    t_traffic = time.perf_counter() - t0
    passes = warm_up(world, traffic, compiles, annotate=trace)
    say("warm_up", passes=passes, traffic_s=round(t_traffic, 2),
        compiles=compiles.n, compile_s=round(compiles.seconds, 2),
        cache_hits=compiles.cache_hits)

    if control == "partial":  # the program's own partial-answer path
        Global.query_budget_rows = 3
    elif control == "alter":
        alter_an_answer(world.proxy)
    elif control:
        raise SystemExit(f"benchmark: unknown control {control!r}")
    on_open = None
    trace_dir = os.path.join(OUT, "trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)  # the newest only
        on_open = profile_window(trace_dir, min(
            float(cell.mix.get("trace_window_s") or seconds), seconds))
    n_compiles = compiles.n
    run.setup_s = time.perf_counter() - T_START
    run.log, run.replies = run_window(world.proxy, traffic, seconds,
                                      annotate=trace, on_open=on_open)
    run.compiles_in_window = compiles.n - n_compiles
    Global.enable_tracing = False
    stats = world.proxy.tpu.dstore.device.memory_stats() or {}
    run.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0)) or None
    say("window", seconds=run.log.elapsed_s, attempted=run.log.attempted,
        compiles_in_window=run.compiles_in_window,
        classes=class_table(run.log, run.replies))

    # the check: after the window and after the memory reading
    t0 = time.perf_counter()
    ref.ids.update(traffic.constants())
    verdict = check.decide(
        ref, run.replies, seed, int(cell.mix.get("check_sample", 300)),
        degr.counts(run.replies, world.facts["native_loader"]))
    run.wrong = verdict["wrong"]
    check_s = time.perf_counter() - t0
    for note in verdict["notes"]:  # which replies, for a run that fails
        print(f"note: {note}", file=sys.stderr)
    if verdict["degraded"]:
        print(f"note: degraded {verdict['degraded']}", file=sys.stderr)

    device = dict(dev, memory_peak_bytes=run.memory_peak_bytes)
    breakdown = None
    t0 = time.perf_counter()
    if trace:
        run.trace = xplane.reduce(xplane.find_trace(trace_dir))
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        read = spec.layer_reader(m["name"]) if trace else \
            spec.end_to_end_reader(m["name"])
        value = read(run)
        if value is not None:  # nothing to read: the metric is left out
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    say("after", check_s=round(check_s, 2),
        reduce_s=round(time.perf_counter() - t0, 2),
        total_s=round(time.perf_counter() - T_START, 2),
        notes=verdict["notes"], degraded=verdict["degraded"],
        trace_events=run.trace["n_device_events"] if run.trace else None)

    result = {"correct": verdict["correct"], "attempted": run.log.attempted,
              "failed": run.log.failed + run.wrong, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench-file", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another BENCHMARK.json (tests and trials)")
    ap.add_argument("--control", default=None, choices=("partial", "alter"),
                    help="break a guarantee after the warm-up (the program's "
                         "partial-answer path on, or an id of every reply "
                         "altered): the result has to come out not correct")
    args = ap.parse_args(argv)

    try:
        import wukong_tpu
    except ImportError:
        raise SystemExit("benchmark: the program (wukong_tpu/) is not in "
                         f"{ROOT}: nothing to measure") from None
    if not os.path.abspath(wukong_tpu.__file__).startswith(ROOT + os.sep):
        raise SystemExit("benchmark: wukong_tpu was imported from "
                         f"{wukong_tpu.__file__}, not from this checkout")
    # one compile cache for every run of this checkout, at a fixed path
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(CACHE, "xla"))
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)

    from benchmark.spec import Cell

    cell = Cell(args.bench_file, args.workload)
    dev = device_check(cell.chips)  # before any work
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), dev,
                      control=args.control)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
