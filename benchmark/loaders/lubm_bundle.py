"""Loader for configurations whose ``generator`` is ``lubm_bundle``: LUBM at
a scale whose store cannot be generated and built in every run.

The store, the string server and the planner come from the program's own
cold start (``wukong_tpu/runtime/boot.py``: ``boot_store``, the call
``runtime/console.py`` makes): the first run of a tree generates
LUBM(universities) from the configuration's data seed, builds and saves a
bundle under the cache directory; every later run loads it and touches no
triple. The proxy is built as the console builds it — one partition,
``CPUEngine`` + ``TPUEngine``, the planner's statistics handed to the device
engine. The triples the plain reference needs are the generator's own, kept
beside the bundle as an int32 ``.npy`` by this loader (ids are below 2^31)
and read back by every run, the one that made them too; they never come out
of the store. They stay int32 and on disk (``mmap_mode="r"``): in memory as
int64 they are 7.7 GB at LUBM-2560, which beside the store's 18 GB and the
reference's own arrays is more than a 40 GiB host has."""

from __future__ import annotations

import dataclasses
import os
import resource
import shutil
import time

import numpy as np

from benchmark.loaders.lubm import World

# what a run keeps on disk, per generated triple: the bundle 57 bytes (every
# id is an int64 and stored twice, with the indexes), the statistics 5, the
# reference's triples 12; and LUBM's triples a university
DISK_BYTES_PER_TRIPLE = 74
TRIPLES_PER_UNIVERSITY = 125_800


class _WideRows(np.ndarray):
    """int32 triples whose selected rows come out int64: the plain reference
    packs a pair as ``(s << 32) | o``. A plain slice (a column) stays a view
    of the int32 file."""

    def __getitem__(self, key):
        out = super().__getitem__(key)
        keys = key if isinstance(key, tuple) else (key,)
        if any(isinstance(k, np.ndarray) for k in keys):
            return np.asarray(out, dtype=np.int64)
        return out


def _mem_total() -> int | None:
    try:
        with open("/proc/meminfo") as f:
            return int(f.readline().split()[1]) * 1024  # MemTotal, in kB
    except (OSError, ValueError, IndexError):
        return None


def load(config: dict, seed: int, data_dir: str) -> World:
    try:  # first, and before any data is made: the parent has no such call
        from wukong_tpu.runtime.boot import (boot_store, bundle_key,
                                             bundle_stem, lubm_source)
    except ImportError:
        raise SystemExit(
            "benchmark: this program has no wukong_tpu.runtime.boot "
            "(a store booted from a saved bundle): a configuration whose "
            "generator is 'lubm_bundle' cannot be run on it") from None
    from wukong_tpu import native
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.runtime.proxy import Proxy

    from benchmark.reference import read_index_rows

    n = int(config["universities"])
    seed = int(config.get("data_seed", seed))  # the data's, not the traffic's
    data_dir = f"{data_dir}_d{seed}"
    secs: dict[str, float] = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        secs[name] = round(time.perf_counter() - t0, 2)
        return out

    source = lubm_source(n, seed, data_dir)
    stem = bundle_stem(bundle_key(source.key))
    kept = os.path.join(data_dir, f"triples-{stem}.npy")

    def generate():
        """The generator's triples, kept for the reference on their way to
        the store build."""
        triples, attrs = timed("generate_lubm", source.load)
        if int(triples.min()) < 0 or \
                int(triples.max()) >= np.iinfo(np.int32).max:
            raise SystemExit("benchmark: vertex ids do not fit the device's "
                             "int32")
        out = np.lib.format.open_memmap(kept + ".tmp", mode="w+",
                                        dtype=np.int32, shape=triples.shape)
        out[:] = triples
        out.flush()
        del out
        os.replace(kept + ".tmp", kept)
        return triples, attrs

    if not os.path.exists(os.path.join(data_dir, stem + ".npz")):
        need = n * TRIPLES_PER_UNIVERSITY * DISK_BYTES_PER_TRIPLE
        free = shutil.disk_usage(data_dir).free
        if free < need:
            raise SystemExit(
                f"benchmark: no room for the bundle: LUBM-{n} keeps about "
                f"{need / 1e9:.1f} GB under {data_dir} (store bundle, "
                f"statistics, the reference's triples) and {free / 1e9:.1f} "
                "GB are free")
    booted = boot_store(dataclasses.replace(source, load=generate), data_dir)
    for name, (s, _nbytes) in booted.phases.items():
        secs[name.removeprefix("boot.")] = round(s, 2)
    if "build" in secs:  # statistics and partition: less the generator's
        secs["build"] = round(secs["build"] - secs["generate_lubm"], 2)
    if not os.path.exists(kept):  # a bundle without its triples
        generate()
    triples = timed("triples_load", lambda: np.load(
        kept, mmap_mode="r").view(_WideRows))

    g, ss = booted.store, booted.str_server
    proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    proxy.planner = booted.planner
    proxy.tpu.stats = proxy.planner.stats  # capacity estimation, as the console
    facts = {"universities": n, "data_seed": seed,
             "triples": int(len(triples)),
             "stored_edges": int(sum(s.num_edges for s in g.segments.values())),
             "native_loader": native.get_lib() is not None,
             "from_bundle": booted.from_bundle,
             "bundle_bytes": os.path.getsize(booted.bundle_path)
             if os.path.exists(booted.bundle_path) else 0,
             "triples_bytes": os.path.getsize(kept),
             "host_peak_rss_bytes":
                 resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024,
             "host_mem_total_bytes": _mem_total()}
    return World(proxy, triples, read_index_rows(
        os.path.join(data_dir, "str_index")), ss.id2str, secs, facts)
