#!/usr/bin/env python
"""LUBM-10240 on the CPU backend, one process, in RAM (round-4 verdict #3).

The north-star scale (BASELINE.json: reference 5-node CUDA cluster,
S5C24(MEEPO)-LUBM10240-20181212.md:130-152) cannot be cached on this VM's
disk (~68 GB store > free space), so everything happens in one process:
synthesize -> build a single partition (versatile off: no query in L1-L7
needs the combined adjacency, and it saves ~22 GB) -> measure the lights
batched through the merge executor + as many heavies as the time budget
allows -> oracle-verify by sampled per-constant counts against the CPU
engine (lights) / a time-boxed CPU run (heavies).

Writes BENCH_10240_CPU.json (compact) + BENCH_10240_DETAIL.json at the repo
root. Peak RSS is logged per phase; the 125 GB host fits the int64 build
with versatile off (HBM_BUDGET.md "LUBM-10240 exact planning headers").

Usage: detached, one at a time on this 1-core host:
  setsid python scripts/at_scale_10240.py > .cache/at10240.log 2>&1 &
Env: WUKONG_10240_QUERIES (csv, default q4,q5,q6,q3,q2,q7,q1),
     WUKONG_10240_BUDGET_S (wall budget for the query/oracle loop,
     counted from store-build completion — the build pipeline alone is
     hours at this scale; default 7200),
     WUKONG_ORACLE_TIMEOUT (heavy CPU-oracle box, default 3600),
     WUKONG_10240_CACHE_GB (device-segment cache budget, default 32 —
     host RAM plays the device here; lower it on smaller hosts).
"""

import json
import os
import resource
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from wukong_tpu.utils.paths import LUBM_BASIC as BASIC  # noqa: E402

SCALE = int(os.environ.get("WUKONG_10240_SCALE", "10240"))  # override = smoke
BATCH = 1024


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def log(msg: str) -> None:
    print(f"# [{time.strftime('%H:%M:%S')}] {msg} (peak rss {rss_gb():.1f} GB)",
          file=sys.stderr, flush=True)


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from bench import DATASET_NOTES, _emit_final, _geomean
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
    from wukong_tpu.planner.optimizer import Planner
    from wukong_tpu.planner.stats import Stats
    from wukong_tpu.sparql.parser import Parser
    from wukong_tpu.store.gstore import build_partition
    from wukong_tpu.types import NORMAL_ID_START
    from wukong_tpu.utils.compilecache import setup_persistent_cache

    setup_persistent_cache()
    # device-cache budget: the default Global.tpu_mem_cache_gb = 4 models
    # v5e HBM, but this run's "device" IS host RAM — keeping the 4 GB
    # budget just measures LRU re-staging of the ~4 GB start segments
    # (first run: q4 at 139 ms/query, pure restage). The v5e-8 fit
    # question is answered by BUDGET_10240.json (per-chip 1/8 shards),
    # not by throttling this artifact.
    from wukong_tpu.config import Global

    # 32 GB covers the ENTIRE padded store (~28 GB int32) with margin, so
    # nothing ever restages, while capping worst-case RSS at
    # store + cache + stats + chain buffers ≈ 75 GB on this 125 GB host
    Global.tpu_mem_cache_gb = int(
        os.environ.get("WUKONG_10240_CACHE_GB", "32"))
    budget_s = int(os.environ.get("WUKONG_10240_BUDGET_S", "7200"))
    qnames = [f"lubm_{q}" if not q.startswith("lubm") else q
              for q in os.environ.get(
                  "WUKONG_10240_QUERIES",
                  "q4,q5,q6,q3,q2,q7,q1").split(",")]

    # disk-space-gated caches: generation + stats are ~75 min of 1-core
    # work per attempt; a crash or budget misjudgment must not pay them
    # twice. The int32 triples npy is ~15 GB, the stats npz ~5 GB — both
    # skipped when free disk is short (the in-RAM path still works).
    tri_cache = os.path.join(REPO, ".cache", f"lubm{SCALE}_i32_triples.npy")
    stats_cache = os.path.join(REPO, ".cache", f"lubm{SCALE}_stats.npz")

    def _free_gb(path=REPO) -> float:
        st = os.statvfs(path)
        return st.f_bavail * st.f_frsize / 2**30

    triples = None
    if os.path.exists(tri_cache):
        log(f"loading cached triples {tri_cache}")
        try:
            triples = np.load(tri_cache)
        except Exception as e:  # truncated/corrupt cache: regenerate
            log(f"triples cache unreadable ({e}); regenerating")
            os.unlink(tri_cache)
    if triples is None:
        log("synthesizing LUBM-10240")
        triples, _lay = generate_lubm(SCALE, seed=0)
        log(f"{len(triples):,} triples")
        # ids < 2^31 by the store contract (gstore.check_vid_range) —
        # asserted HERE because Stats.generate consumes the narrowed array
        # long before build_partition would catch a silent wrap. int32
        # halves every downstream sort/copy — the int64 run OOMed at 130 GB
        assert int(triples.max()) < 2**31 - 1, "ids overflow int32"
        triples = triples.astype(np.int32)
        log("narrowed to int32")
        need = triples.nbytes / 2**30 + 2
        if _free_gb() > need + 10:
            try:  # tmp + rename: a crash/ENOSPC mid-save must never leave
                # a truncated cache that aborts every later run at startup
                np.save(tri_cache + ".tmp.npy", triples)
                os.replace(tri_cache + ".tmp.npy", tri_cache)
                log(f"triples cached ({triples.nbytes / 2**30:.1f} GB)")
            except Exception as e:
                log(f"triples cache save failed: {e}")
        else:
            log(f"triples cache skipped (free {_free_gb():.0f} GB)")
    stats = None
    if os.path.exists(stats_cache):
        try:
            stats = Stats.load(stats_cache)
            log("stats loaded from cache")
        except Exception as e:
            log(f"stats cache unreadable ({e}); regenerating")
            os.unlink(stats_cache)
    if stats is None:
        stats = Stats.generate(triples)
        log("stats done")
        if _free_gb() > 20:
            try:
                stats.save(stats_cache + ".tmp")
                os.replace(stats_cache + ".tmp.npz", stats_cache)
                log("stats cached")
            except Exception as e:
                log(f"stats cache save failed: {e}")
    g = build_partition(triples, 0, 1, versatile=False)
    log(f"store built: {g.stats_str()}")
    del triples
    # the query/oracle budget starts NOW: at this scale the build pipeline
    # alone exceeds the old from-process-start budget, which would have
    # skipped every query and emitted an empty artifact
    t0 = time.time()

    ss = VirtualLubmStrings(SCALE, seed=0)
    eng = TPUEngine(g, ss, stats=stats)
    cpu = CPUEngine(g, ss)
    planner = Planner(stats)
    rng = np.random.default_rng(0)
    details, failed = {}, []

    for qn in qnames:
        if time.time() - t0 > budget_s:
            print(f"# {qn}: skipped (budget {budget_s}s)", file=sys.stderr)
            continue
        try:
            text = open(f"{BASIC}/{qn}").read()
            q = Parser(ss).parse(text)
            planner.generate_plan(q)
            q.result.blind = True
            if q.planner_empty:
                details[qn] = {"us": 0.1, "rows": 0, "planner_empty": True}
                log(f"{qn}: planner-proved empty")
                continue
            const_start = q.pattern_group.patterns[0].subject >= NORMAL_ID_START
            if const_start:
                bq = BATCH
                consts = np.full(
                    bq, q.pattern_group.patterns[0].subject, dtype=np.int64)
                best, rows = None, 0
                for trial in range(3):
                    qt = Parser(ss).parse(text)
                    planner.generate_plan(qt)
                    qt.result.blind = True
                    t = time.perf_counter()
                    counts = eng.execute_batch(qt, consts)
                    dt = (time.perf_counter() - t) * 1e6 / bq
                    rows = int(counts[0])
                    best = dt if best is None else min(best, dt)
                d = {"us": round(best, 1), "rows": rows, "batch": bq}
                # oracle: 8 sampled distinct constants through the SAME
                # planned chain vs single-instance CPU runs
                seg = g.segments.get(
                    (int(q.pattern_group.patterns[0].predicate),
                     int(q.pattern_group.patterns[0].direction)))
                ver = {"ok": True, "sampled": 0}
                if seg is not None and len(seg.keys):
                    picks = np.unique(seg.keys[rng.integers(
                        0, len(seg.keys), 8)])
                    qv = Parser(ss).parse(text)
                    planner.generate_plan(qv)
                    qv.result.blind = True
                    batch_counts = eng.execute_batch(
                        qv, np.asarray(picks, dtype=np.int64))
                    for i, c0 in enumerate(picks):
                        qc = Parser(ss).parse(text)
                        planner.generate_plan(qc)
                        qc.pattern_group.patterns[0].subject = int(c0)
                        qc.result.blind = True
                        cpu.execute(qc, from_proxy=False)
                        if qc.result.nrows != int(batch_counts[i]):
                            ver = {"ok": False, "const": int(c0),
                                   "merge": int(batch_counts[i]),
                                   "cpu": int(qc.result.nrows)}
                            break
                        ver["sampled"] = i + 1
                d["oracle"] = ver
            else:
                bq = eng.suggest_index_batch(q)
                best, rows = None, 0
                for trial in range(2):
                    qt = Parser(ss).parse(text)
                    planner.generate_plan(qt)
                    qt.result.blind = True
                    t = time.perf_counter()
                    counts = eng.execute_batch_index(qt, bq)
                    dt = (time.perf_counter() - t) * 1e6 / bq
                    rows = int(counts[0])
                    best = dt if best is None else min(best, dt)
                d = {"us": round(best, 1), "rows": rows, "batch": bq}
                # heavy oracle: time-boxed CPU run compares total rows
                box = int(os.environ.get("WUKONG_ORACLE_TIMEOUT", "3600"))
                if time.time() - t0 + box < budget_s * 1.5:
                    import signal

                    def bail(_s, _f):
                        raise TimeoutError()

                    qc = Parser(ss).parse(text)
                    planner.generate_plan(qc)
                    qc.result.blind = True
                    old = signal.signal(signal.SIGALRM, bail)
                    signal.alarm(box)
                    try:
                        cpu.execute(qc, from_proxy=False)
                        d["oracle"] = {"ok": qc.result.nrows == rows,
                                       "cpu": int(qc.result.nrows)}
                    except TimeoutError:
                        d["oracle"] = {"ok": None,
                                       "note": f"cpu oracle > {box}s"}
                    finally:
                        signal.alarm(0)
                        signal.signal(signal.SIGALRM, old)
            details[qn] = d
            log(f"{qn}: {d['us']:,.1f} us/query (rows={d['rows']}, "
                f"oracle={d.get('oracle')})")
        except Exception as e:
            failed.append(qn)
            details[qn] = {"error": str(e)[:300]}
            log(f"{qn}: FAILED {e!r:.200}")

    us = [d["us"] for d in details.values()
          if d.get("us") and not d.get("planner_empty")]
    bad = [qn for qn, d in details.items()
           if isinstance(d.get("oracle"), dict)
           and d["oracle"].get("ok") is False]
    os.chdir(REPO)
    obj = {
        "metric": f"LUBM-{SCALE} at-scale: {','.join(details)} on the CPU "
                  f"backend (single 1-core host, in-RAM build, no disk "
                  f"cache), oracle-sampled"
                  + (f"; FAILED: {','.join(failed)}" if failed else "")
                  + (f"; VERIFY-FAILED: {','.join(bad)}" if bad else ""),
        "value": round(_geomean(us), 1) if us else None,
        "unit": "us",
        "vs_baseline": None,
        "backend": "cpu",
        "scale": SCALE,
        "dataset": DATASET_NOTES["lubm"],
        "detail": details,
    }
    _emit_final(obj, "BENCH_10240_DETAIL.json")
    with open("BENCH_10240_CPU.json", "w") as f:
        json.dump(obj, f, indent=1, sort_keys=True)
        f.write("\n")
    log("done")


if __name__ == "__main__":
    main()
