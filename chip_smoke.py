#!/usr/bin/env python3
"""The served LUBM path, once, on the attached TPU — the quickest proof that
the system still starts on the chip.

    python3 chip_smoke.py [--scale N] [--seed S] [--heavy-batches] [--chips 4]

One process, no child that touches JAX, nothing read outside the checkout.
It loads LUBM(N) through the calls ``runtime/console.py`` makes, answers the
LUBM basic queries q1-q7 through ``Proxy.run_single_query`` (what the
console's ``sparql -f`` calls) and through the batch entry points the
emulator uses, and compares every answer with the CPU oracle
(``engine/cpu.py``) on the same store. The system degrades by design —
capacity and shape fallbacks to the CPU engine, host steps inside the device
engine, a NumPy loader where no C++ compiler is found, an XLA emit where the
Pallas kernel fails its probe — and every such degradation on the smoke's
own queries counts as failure here.

One JSON line per phase on stdout; logs go to stderr. The last line is
``{"ok": true, "device": {...}}`` and the exit code 0 only if every check
of every phase held. Without an accelerator it exits non-zero before any
work: no CPU run goes out under this script's name. ``--chips 4`` runs the
sharded path (``DistEngine`` over a 4-device mesh) against the same oracle,
and nothing else.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

QUERIES = tuple(f"lubm_q{k}" for k in range(1, 8))
BATCH = 1024  # const-start instances per batch (the emulator's batch)
# The two cyclic heavies: batched, each compiles 22-26 merge-chain programs,
# and the chip's compiler takes 10-30 s over every program that holds a
# sort (311 s and 181 s at LUBM-40, PERF.md PR 22). With them a cold run
# does not fit the 1200 s a smoke is given, so they wait for the option.
HEAVY_BATCHES = ("lubm_q1", "lubm_q7")
PLATFORM = "tpu"  # the platform this script measures; anything else fails


class Smoke:
    """Phase lines, the failures they found, and JAX's compile events."""

    def __init__(self):
        import jax.monitoring as mon

        self.failures: list[str] = []
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def compile_mark(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits

    def compile_since(self, mark: tuple) -> dict:
        """Programs handed to the backend compiler since ``mark`` (the
        seconds include persistent-cache lookups) and how many of them the
        persistent cache answered."""
        return {"compiles": self.compiles - mark[0],
                "compile_s": round(self.compile_s - mark[1], 2),
                "cache_hits": self.cache_hits - mark[2]}

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            print(f"FAIL: {what}", file=sys.stderr, flush=True)
        return bool(ok)

    @staticmethod
    def emit(phase: str, **fields) -> None:
        print(json.dumps({"phase": phase, **fields}, default=str),
              flush=True)


def device_phase(smoke: Smoke, chips: int) -> dict:
    import jax

    from wukong_tpu.utils.compilecache import setup_persistent_cache

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != PLATFORM:
        raise SystemExit(f"chip_smoke: JAX found no {PLATFORM} "
                         f"(platform is {dev['platform']}); not run")
    if chips > 1 and len(devs) != chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX reports {len(devs)}")
    smoke.emit("device", **dev, compile_cache_dir=setup_persistent_cache(),
               cache_dir_from_env=bool(
                   os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    return dev


def load_phase(smoke: Smoke, scale: int, seed: int, parts: int = 1):
    """The calls ``console.main`` makes, in its order. Returns the host
    store, the string server, the planner and, for ``parts`` > 1, the
    partitions."""
    from wukong_tpu import native
    from wukong_tpu.loader.base import load_attr_triples, load_triples
    from wukong_tpu.loader.lubm import write_dataset
    from wukong_tpu.planner.optimizer import make_planner
    from wukong_tpu.store.gstore import build_all_partitions, build_partition
    from wukong_tpu.store.string_server import StringServer
    from wukong_tpu.utils.paths import REPO

    secs = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        secs[name] = round(time.perf_counter() - t0, 2)
        return out

    smoke.check(native.get_lib() is not None,
                "native C++ loader library unavailable (NumPy fallback)")
    data = os.path.join(REPO, ".cache", "chip_smoke", f"lubm{scale}_s{seed}")
    done = os.path.join(data, "str_normal_virtual")  # written last
    reused = os.path.exists(done)
    if not reused:
        timed("write_dataset", lambda: write_dataset(data, scale, seed))
    ss = timed("string_server", lambda: StringServer(data))
    triples = timed("load_triples", lambda: load_triples(data))
    attrs = timed("load_attr_triples", lambda: load_attr_triples(data))
    # device arrays are int32 and x64 is off: staging would narrow wider
    # ids without a word
    smoke.check(0 <= int(triples.min())
                and int(triples.max()) < np.iinfo(np.int32).max,
                "vertex ids do not fit int32 at this scale")
    g = timed("build_partition",
              lambda: build_partition(triples, 0, 1, attrs))
    stores = None
    if parts > 1:
        stores = timed("build_all_partitions", lambda: build_all_partitions(
            triples, parts, attr_triples=attrs))
    statfile = os.path.join(data, "statfile")
    planner = timed("make_planner", lambda: make_planner(
        None if os.path.exists(statfile + ".npz") else triples, statfile))
    smoke.emit("load", scale=scale, seed=seed, dataset_reused=reused,
               triples=int(len(triples)),
               stored_edges=int(sum(s.num_edges
                                    for s in g.segments.values())),
               partitions=parts, native_lib=native.get_lib() is not None,
               seconds=secs)
    return g, ss, planner, stores


def _text(qn: str) -> str:
    from wukong_tpu.utils.paths import LUBM_BASIC

    with open(os.path.join(LUBM_BASIC, qn)) as f:
        return f.read()


def _planned(qn: str, ss, planner, blind: bool):
    """Parse + plan exactly as ``Proxy._plan`` does."""
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.sparql.parser import Parser

    q = Parser(ss).parse(_text(qn))
    if not planner.generate_plan(q):
        heuristic_plan(q)
    q.result.blind = blind
    return q


def result_rows(q) -> np.ndarray:
    """The reply's table over its projected variables, rows sorted: two
    replies are equal as multisets of rows iff these arrays are equal."""
    res = q.result
    cols = [res.v2c_map[v] for v in res.required_vars]
    rows = np.asarray(res.table)[:, cols].astype(np.int64)
    return rows[np.lexsort(rows.T[::-1])] if len(rows) else rows


def oracle_rows(qn: str, g, ss, planner) -> np.ndarray:
    """The plain reference: the CPU engine on the same store."""
    from wukong_tpu.engine.cpu import CPUEngine

    q = _planned(qn, ss, planner, blind=False)
    CPUEngine(g, ss).execute(q)
    if q.result.status_code != 0:
        raise RuntimeError(f"CPU oracle failed on {qn}: "
                           f"{q.result.status_code!r}")
    return result_rows(q)


def oracle_phase(smoke: Smoke, g, ss, planner) -> dict:
    t0 = time.perf_counter()
    want = {qn: oracle_rows(qn, g, ss, planner) for qn in QUERIES}
    smoke.emit("oracle", engine="CPUEngine",
               rows={qn: len(r) for qn, r in want.items()},
               seconds=round(time.perf_counter() - t0, 2))
    return want


# routes on which the device did the work; "planner-empty" proves nothing
# about the device, and on "wcoj:host", "dist.inplace" and "host" the host
# answered
DEVICE_ROUTES = ("tpu.chain", "template.plan", "wcoj:device", "dist.chain")


def _route(q, spans: set, dist) -> str:
    """Which of the proxy's routes answered (``Proxy._serve_execute``)."""
    if q.planner_empty:
        return "planner-empty"
    if getattr(q, "_template_compiled", False):
        return "template.plan"
    levels = getattr(q, "join_stats", None)
    if levels:  # the tensor-join executor committed this reply
        on_dev = any(lv.get("route") == "device" for lv in levels)
        return "wcoj:device" if on_dev else "wcoj:host"
    if "dist.execute" in spans:  # owner-routed host chain, or shard_map
        inplace = (dist.last_chain_stats or {}).get("mode") == "inplace"
        return "dist.inplace" if inplace else "dist.chain"
    return "tpu.chain" if "tpu.chain" in spans else "host"


def _served(smoke: Smoke, label: str, q, want: np.ndarray, dist) -> dict:
    """The checks every served reply has to pass; returns its fields."""
    failed_before = len(smoke.failures)
    spans = [sp.name for sp in q.trace.spans]
    fallbacks = [n for n in q.trace.event_names() if n.endswith(".fallback")]
    if smoke.check(q.result.status_code == 0,
                   f"{label}: status {q.result.status_code!r}"):
        got = result_rows(q)
        smoke.check(got.shape == want.shape and np.array_equal(got, want),
                    f"{label}: {len(got)} rows differ from the CPU "
                    f"oracle's {len(want)}")
    smoke.check(not fallbacks, f"{label}: fallback events {fallbacks}")
    smoke.check("tpu.host_step" not in spans,
                f"{label}: {spans.count('tpu.host_step')} pattern steps ran "
                "on the host inside the device engine")
    smoke.check(not getattr(q, "_join_device_broken", False),
                f"{label}: a wcoj device probe failed and the host probe "
                "stood in")
    return {"rows": int(q.result.nrows),
            "route": _route(q, set(spans), dist),
            "passed": len(smoke.failures) == failed_before,
            "fallback_events": fallbacks}


def serve_phase(smoke: Smoke, proxy, want: dict, device: str | None) -> None:
    """q1-q7 non-blind through ``Proxy.run_single_query``: a first call
    (compiles included) and a warm one, each timed to the reply's host
    table. ``device=None`` is the route the proxy picks by itself. Where
    the system's own choice keeps a query on the host — a small tensor
    join, an in-place distributed chain — it is served once more with the
    device path forced: pinned as ``sparql -d tpu`` pins it, or with
    ``enable_dist_inplace`` off."""
    from wukong_tpu.config import Global

    Global.enable_tracing = True

    def serve(qn: str, pin: str | None, label: str) -> dict:
        mark = smoke.compile_mark()
        t0 = time.perf_counter()
        q = proxy.run_single_query(_text(qn), device=pin, blind=False)
        first_s = time.perf_counter() - t0
        comp = smoke.compile_since(mark)
        attempts = next((sp.attrs.get("attempts") for sp in q.trace.spans
                         if sp.name == "tpu.chain"), None)
        fields = _served(smoke, f"{label} {qn}", q, want[qn], proxy.dist)
        mark = smoke.compile_mark()
        t0 = time.perf_counter()
        q2 = proxy.run_single_query(_text(qn), device=pin, blind=False)
        warm_s = time.perf_counter() - t0
        _served(smoke, f"{label} {qn} (warm)", q2, want[qn], proxy.dist)
        if fields["route"] == "tpu.chain":
            fields["chain_attempts"] = attempts
        return {**fields, "first_s": round(first_s, 3), **comp,
                "warm_s": round(warm_s, 4),
                "warm_compiles": smoke.compile_since(mark)["compiles"]}

    for qn in QUERIES:
        line = serve(qn, device, "serve")
        if line["route"] not in DEVICE_ROUTES + ("planner-empty",):
            inplace = Global.enable_dist_inplace
            Global.enable_dist_inplace = False
            forced = serve(qn, device or "tpu", "serve (device forced)")
            Global.enable_dist_inplace = inplace
            smoke.check(forced["route"] in DEVICE_ROUTES,
                        f"serve (device forced) {qn}: answered by "
                        f"{forced['route']}")
            line["device_forced"] = forced
        smoke.emit("serve", query=qn, **line)


def console_phase(smoke: Smoke, proxy, want: np.ndarray) -> None:
    """q4 once through the CLI verb itself."""
    from wukong_tpu.runtime.console import Console
    from wukong_tpu.utils.paths import LUBM_BASIC

    before = len(proxy.recorder.last())
    Console(proxy).run_command(f"sparql -f {LUBM_BASIC}/lubm_q4 -v 5")
    traces = proxy.recorder.last()
    ran = smoke.check(len(traces) == before + 1,
                      "console: `sparql -f` recorded no trace")
    status = traces[-1].status if ran else None
    # q4 is a light: since PR 29 its whole-plan program answers it, not the
    # walk; either route's execute span ends with the reply's rows
    rows = [sp.attrs.get("rows") for sp in traces[-1].spans
            if sp.name in ("tpu.execute", "template.execute")] if ran else []
    smoke.check(status == "SUCCESS" and rows == [len(want)],
                f"console: sparql -f lubm_q4 ended {status} with rows "
                f"{rows}, oracle has {len(want)}")
    smoke.emit("console", command="sparql -f queries/lubm/basic/lubm_q4 -v 5",
               status=status, rows=rows)


def batch_phase(smoke: Smoke, eng, ss, planner, want: dict,
                heavy: bool) -> None:
    """The path the emulator and the bench use: ``execute_batch`` x1024 for
    the constant-start queries, ``execute_batch_index`` at
    ``suggest_index_batch`` for the index-origin ones. Every instance's
    count has to equal the single query's. One call each: a second one
    starts at the tight capacity classes the first learned, which is a new
    set of programs to compile."""
    from wukong_tpu.types import NORMAL_ID_START

    for qn in QUERIES:
        if qn in HEAVY_BATCHES and not heavy:
            smoke.emit("batch", query=qn, skipped="needs --heavy-batches")
            continue
        q = _planned(qn, ss, planner, blind=True)
        if q.planner_empty:
            smoke.emit("batch", query=qn, route="planner-empty")
            continue
        start = q.pattern_group.patterns[0].subject
        const_start = start >= NORMAL_ID_START
        B = BATCH if const_start else eng.suggest_index_batch(q, cap=BATCH)

        mark = smoke.compile_mark()
        t0 = time.perf_counter()
        try:
            if const_start:
                counts = eng.execute_batch(
                    q, np.full(B, start, dtype=np.int64))
            else:
                counts = eng.execute_batch_index(q, B)
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            # the bench halves the batch and goes on; the smoke reports
            smoke.check(False, f"batch {qn}: out of device memory at "
                        f"batch={B}: {str(e)[:300]}")
            continue
        n = len(want[qn])
        ok = smoke.check(
            len(counts) == B and bool((np.asarray(counts) == n).all()),
            f"batch {qn}: per-instance counts {np.unique(counts)[:8]} != "
            f"the single query's {n}")
        smoke.emit("batch", query=qn,
                   entry="execute_batch" if const_start
                   else "execute_batch_index", batch=int(B),
                   count_each=n, all_equal=ok,
                   first_s=round(time.perf_counter() - t0, 3),
                   **smoke.compile_since(mark))


def site_counts() -> dict:
    """obs.device dispatch counts per site: {site: [dispatches, cold]}."""
    from wukong_tpu.obs.device import get_device_obs

    out: dict = {}
    for r in get_device_obs().dispatch_ledger.report(k=1 << 30):
        c = out.setdefault(r["site"], [0, 0])
        c[0] += r["dispatches"]
        c[1] += r["cold"]
    return out


def kernels_phase(smoke: Smoke, eng, g, on_tpu: bool) -> None:
    """Which Pallas variant the probe selected, whether any query sent work
    through it, and ``stream_expand`` run directly on the staged
    ``takesCourse`` segment with every course as frontier, against
    ``merge_expand`` on the same inputs (bit-identical for distinct
    anchors). Off the TPU — under test only — the kernel is interpreted."""
    import jax
    import jax.numpy as jnp

    from wukong_tpu.engine import tpu_kernels as K
    from wukong_tpu.engine import tpu_stream
    from wukong_tpu.loader.lubm import P
    from wukong_tpu.types import IN

    tpu_stream.stream_available()
    rep = tpu_stream.stream_report()
    if on_tpu:
        smoke.check(rep["live"], "stream_expand is not live on the TPU: "
                    + rep["reason"])
        smoke.check(rep["variant"] == tpu_stream.FIRST_CHOICE,
                    f"stream_expand runs as {rep['variant']}, not its "
                    f"first choice {tpu_stream.FIRST_CHOICE}: "
                    + rep["reason"])
    emit = dict(eng.merge.emit_counts)

    host = g.segments[(P["takesCourse"], IN)]
    seg = eng.dstore.merge_segment(P["takesCourse"], IN)
    n = len(host.keys)
    total = int(host.num_edges)
    cap_in = K.next_capacity(n, eng.cap_min, eng.cap_max)
    cap_out = K.next_capacity(total, eng.cap_min, eng.cap_max)
    smoke.check(total <= cap_out, f"takesCourse has {total} edges, past "
                f"table_capacity_max {eng.cap_max}")
    cur = np.full(cap_in, np.iinfo(np.int32).max, dtype=np.int32)
    cur[:n] = host.keys  # distinct, sorted
    args = (seg.skey, seg.sstart, seg.sdeg, seg.edges, jnp.asarray(cur),
            jnp.int32(n), jnp.ones(cap_in, bool))
    mark = smoke.compile_mark()
    t0 = time.perf_counter()
    got = jax.device_get(tpu_stream.wk_walk_merge_stream_expand(
        *args, cap_out=cap_out, interpret=not on_tpu,
        mhot=tpu_stream.mhot_enabled(), mdup=tpu_stream.stream_mdup()))
    stream_s = time.perf_counter() - t0
    want = jax.device_get(K.wk_walk_merge_expand(*args, cap_out=cap_out))
    same = smoke.check(
        int(got[3]) == total and all(np.array_equal(a, b)
                                     for a, b in zip(got, want)),
        f"stream_expand differs from merge_expand on takesCourse "
        f"(totals {int(got[3])} / {int(want[3])}, host {total})")
    smoke.emit("kernels", **rep, first_choice=tpu_stream.FIRST_CHOICE,
               emit_counts_from_queries=emit,
               stream_dispatched_by_a_query=emit["stream"] > 0,
               direct={"segment": "takesCourse IN", "frontier": n,
                       "S": int(seg.skey.shape[0]),
                       "E": int(seg.edges.shape[0]), "C": cap_in,
                       "cap_out": cap_out, "edges_out": total,
                       "equals_merge_expand": same,
                       "first_s": round(stream_s, 3),
                       **smoke.compile_since(mark)},
               dispatch_sites=site_counts())


def memory_phase(smoke: Smoke, devices: list, resident: dict) -> None:
    per_dev = []
    for d in devices:
        st = d.memory_stats() or {}
        per_dev.append({k: int(st[k]) for k in
                        ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
                        if k in st})
    peaks = [p.get("peak_bytes_in_use") for p in per_dev]
    if len(per_dev) > 1 and smoke.check(
            all(peaks), f"memory_stats() gave no peak: {per_dev}"):
        smoke.check(max(peaks) <= 2 * min(peaks),
                    f"device peaks more than 2x apart: {peaks}")
    smoke.emit("memory", devices=per_dev, **resident)


def run_one_chip(smoke: Smoke, scale: int, seed: int, dev: dict,
                 heavy_batches: bool) -> None:
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.runtime.proxy import Proxy

    g, ss, planner, _ = load_phase(smoke, scale, seed)
    proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    proxy.planner = planner
    proxy.tpu.stats = planner.stats  # capacity estimation, as the console
    want = oracle_phase(smoke, g, ss, planner)
    serve_phase(smoke, proxy, want, None)
    console_phase(smoke, proxy, want["lubm_q4"])
    batch_phase(smoke, proxy.tpu, ss, planner, want, heavy_batches)
    kernels_phase(smoke, proxy.tpu, g, dev["platform"] == "tpu")
    from wukong_tpu.obs.device import get_device_obs

    memory_phase(smoke, [proxy.tpu.dstore.device], {
        "device_store_bytes": int(proxy.tpu.dstore.bytes_used),
        "resident_by_kind": get_device_obs().residency.totals()})


def run_sharded(smoke: Smoke, scale: int, seed: int, chips: int) -> None:
    """``console --dist``'s world: one partition per device, q1-q7 pinned to
    the distributed engine, the oracle on the unsharded store."""
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.parallel.dist_engine import DistEngine
    from wukong_tpu.parallel.mesh import make_mesh
    from wukong_tpu.runtime.proxy import Proxy

    g, ss, planner, stores = load_phase(smoke, scale, seed, parts=chips)
    dist = DistEngine(stores, ss, make_mesh(chips))
    proxy = Proxy(g, ss, CPUEngine(g, ss), None, dist)
    proxy.planner = planner
    want = oracle_phase(smoke, g, ss, planner)
    serve_phase(smoke, proxy, want, "dist")
    staged = list(dist.sstore._cache.values()) \
        + list(dist.sstore._index_cache.values())
    device_sets = set()
    for seg in staged:
        for arr in vars(seg).values() if seg is not None else ():
            if hasattr(arr, "sharding"):
                device_sets.add(tuple(sorted(
                    d.id for d in arr.sharding.device_set)))
    smoke.check(device_sets == {tuple(range(chips))},
                f"staged shard arrays live on device sets {device_sets}, "
                f"not on all {chips}")
    memory_phase(smoke, list(dist.mesh.devices.flat),
                 {"staged_bytes": int(dist.sstore.bytes_used),
                  "staged_arrays_device_sets": sorted(device_sets)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=640,
                    help="LUBM universities (default 640, ~81 M triples)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = the sharded path only, on a 4-device mesh")
    ap.add_argument("--heavy-batches", action="store_true",
                    help="also batch the cyclic heavies q1 and q7 "
                         "(minutes of compilation; past a smoke's time)")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    smoke = Smoke()
    dev = device_phase(smoke, args.chips)
    if args.chips > 1:
        run_sharded(smoke, args.scale, args.seed, args.chips)
    else:
        run_one_chip(smoke, args.scale, args.seed, dev, args.heavy_batches)
    smoke.emit("total", seconds=round(time.perf_counter() - t0, 1),
               compiles=smoke.compiles,
               compile_s=round(smoke.compile_s, 1),
               cache_hits=smoke.cache_hits, failures=smoke.failures)
    if smoke.failures:
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
