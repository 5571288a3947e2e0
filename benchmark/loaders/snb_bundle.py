"""Loader for configurations whose ``generator`` is ``snb_bundle``: LDBC
SNB's social graph as LSQB reads it, at a scale whose store is booted from
a saved bundle.

As ``lubm_bundle``: the store, the string server and the planner come from
the program's own cold start (``wukong_tpu/runtime/boot.py``: ``boot_store``
over ``snb_source``): the first run of a tree generates the graph at the
configuration's ``scale_factor`` from its data seed
(``wukong_tpu/loader/snb.py``), builds and saves a bundle under the cache
directory; every later run loads it and touches no triple. The proxy is
built as the console builds it — one partition, ``CPUEngine`` +
``TPUEngine``, the planner's statistics handed to the device engine. The
triples the plain reference needs are the generator's own, kept beside the
bundle as an int32 ``.npy`` (ids are below 2^31) and mapped by every run,
the one that made them too; they never come out of the store. The
generator's counts a class and a predicate are kept beside them, so that a
run from the bundle states them too.

A program without that data model is refused at once, before any data is
made and with a non-zero exit: it has no ``wukong_tpu/loader/snb.py``, or
one without the ``SCHEMA`` marker this cell is written against."""

from __future__ import annotations

import dataclasses
import json
import os
import resource
import shutil
import time

import numpy as np

from benchmark.loaders.lubm import World
from benchmark.loaders.lubm_bundle import (DISK_BYTES_PER_TRIPLE, _mem_total,
                                           _WideRows)

SCHEMA = "ldbc-snb-lsqb-1"
# triples a person, for the room a first run needs on disk (86 M at
# scale factor 3's 27,000 persons, 235 M at 10's 73,000)
TRIPLES_PER_PERSON = 3_300


def load(config: dict, seed: int, data_dir: str) -> World:
    try:  # first, and before any data is made
        from wukong_tpu.loader import snb
    except ImportError:
        snb = None
    if getattr(snb, "SCHEMA", None) != SCHEMA:
        raise SystemExit(
            "benchmark: this program has no wukong_tpu/loader/snb.py with "
            f"the data model this cell is written against (SCHEMA "
            f"{SCHEMA!r}): a configuration whose generator is 'snb_bundle' "
            "cannot be run on it")
    from wukong_tpu import native
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.runtime.boot import (boot_store, bundle_key, bundle_stem,
                                         snb_source)
    from wukong_tpu.runtime.proxy import Proxy

    from benchmark.reference import read_index_rows

    scale = config["scale_factor"]
    seed = int(config.get("data_seed", seed))  # the data's, not the traffic's
    data_dir = f"{data_dir}_d{seed}"
    secs: dict[str, float] = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        secs[name] = round(time.perf_counter() - t0, 2)
        return out

    source = snb_source(scale, seed, data_dir)
    stem = bundle_stem(bundle_key(source.key))
    kept = os.path.join(data_dir, f"triples-{stem}.npy")
    counts = os.path.join(data_dir, f"counts-{stem}.json")

    def generate():
        """The generator's triples, kept for the reference on their way to
        the store build."""
        triples, meta = timed("generate_snb",
                              lambda: snb.generate_snb(scale, seed))
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
        with open(counts, "w") as f:
            json.dump({k: meta[k] for k in ("nodes", "edges", "num_nodes",
                                            "num_edges", "num_triples")}, f)
        return triples, None

    if not os.path.exists(os.path.join(data_dir, stem + ".npz")):
        need = snb.persons_at(scale) * TRIPLES_PER_PERSON \
            * DISK_BYTES_PER_TRIPLE
        free = shutil.disk_usage(data_dir).free
        if free < need:
            raise SystemExit(
                f"benchmark: no room for the bundle: scale factor {scale} "
                f"keeps about {need / 1e9:.1f} GB under {data_dir} (store "
                f"bundle, statistics, the reference's triples) and "
                f"{free / 1e9:.1f} GB are free")
    booted = boot_store(dataclasses.replace(source, load=generate), data_dir)
    for name, (s, _nbytes) in booted.phases.items():
        secs[name.removeprefix("boot.")] = round(s, 2)
    if "build" in secs:  # statistics and partition: less the generator's
        secs["build"] = round(secs["build"] - secs["generate_snb"], 2)
    if not (os.path.exists(kept) and os.path.exists(counts)):
        generate()  # a bundle without its triples
    triples = timed("triples_load", lambda: np.load(
        kept, mmap_mode="r").view(_WideRows))
    with open(counts) as f:
        made = json.load(f)

    g, ss = booted.store, booted.str_server
    proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    proxy.planner = booted.planner
    proxy.tpu.stats = proxy.planner.stats  # capacity estimates, as the console
    facts = {"scale_factor": scale, "data_seed": seed,
             "triples": int(len(triples)), **made,
             "stored_edges": int(sum(s.num_edges
                                     for s in g.segments.values())),
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
