"""Loader for configurations whose ``generator`` is ``lubm_bundle_sharded``:
LUBM hash-partitioned over the chips of one host and served by the sharded
engine.

The shards, the string server and the planner come from the program's own
cold start (``wukong_tpu/runtime/boot.py``: ``boot_shards``): the first run
of a tree generates LUBM(universities) from the configuration's data seed,
assigns every triple to its owners once (``hash(vid) % partitions``, the
subject's for its OUT edge and the object's for its IN edge), builds the
``partitions`` shards side by side and saves them as one bundle of shard
files under the cache directory; every later run loads them and touches no
triple. No whole store is built beside the shards. The proxy is the one
``boot.sharded_proxy`` makes: ``DistEngine`` over one chip a shard serves
every request. The triples the plain reference needs are the generator's
own, kept beside the bundle as an int32 ``.npy``, as ``lubm_bundle`` keeps
them."""

from __future__ import annotations

import dataclasses
import os
import resource
import shutil
import time

import numpy as np

from benchmark.loaders.lubm import World
from benchmark.loaders.lubm_bundle import (DISK_BYTES_PER_TRIPLE,
                                          TRIPLES_PER_UNIVERSITY, _mem_total,
                                          _WideRows)


def load(config: dict, seed: int, data_dir: str) -> World:
    try:  # first, and before any data is made: the parent has no such call
        from wukong_tpu.runtime.boot import (boot_shards, bundle_key,
                                             bundle_stem, lubm_source,
                                             sharded_proxy)
    except ImportError:
        raise SystemExit(
            "benchmark: this program has no wukong_tpu.runtime.boot."
            "boot_shards (a store hash-partitioned over the chips, booted "
            "from a saved bundle): a configuration whose generator is "
            "'lubm_bundle_sharded' cannot be run on it") from None
    import jax

    from wukong_tpu import native

    from benchmark.reference import read_index_rows

    n = int(config["universities"])
    parts = int(config["partitions"])
    seed = int(config.get("data_seed", seed))  # the data's, not the traffic's
    data_dir = f"{data_dir}_d{seed}"
    secs: dict[str, float] = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        secs[name] = round(time.perf_counter() - t0, 2)
        return out

    devices = jax.devices()[:parts]
    if len(devices) < parts:
        raise SystemExit(f"benchmark: {parts} partitions need {parts} "
                         f"devices, JAX reports {len(jax.devices())}")
    source = lubm_source(n, seed, data_dir)
    kept = os.path.join(data_dir,
                        f"triples-{bundle_stem(bundle_key(source.key))}.npy")

    def generate():
        """The generator's triples, kept for the reference on their way to
        the shards."""
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

    shard0 = os.path.join(data_dir, bundle_stem(
        {**bundle_key(source.key), "partitions": parts}) + "-shard0.npz")
    if not os.path.exists(shard0):
        need = n * TRIPLES_PER_UNIVERSITY * DISK_BYTES_PER_TRIPLE
        free = shutil.disk_usage(data_dir).free
        if free < need:
            raise SystemExit(
                f"benchmark: no room for the bundle: LUBM-{n} keeps about "
                f"{need / 1e9:.1f} GB under {data_dir} (shard bundles, "
                f"statistics, the reference's triples) and {free / 1e9:.1f} "
                "GB are free")
    booted = boot_shards(dataclasses.replace(source, load=generate), data_dir,
                         parts)
    for name, (s, _nbytes) in booted.phases.items():
        secs[name.removeprefix("boot.")] = round(s, 2)
    if "build" in secs:  # shards and statistics: less the generator's
        secs["build"] = round(secs["build"] - secs["generate_lubm"], 2)
    if not os.path.exists(kept):  # a bundle without its triples
        generate()
    triples = timed("triples_load", lambda: np.load(
        kept, mmap_mode="r").view(_WideRows))

    proxy = sharded_proxy(booted, devices)
    edges = [int(sum(s.num_edges for s in g.segments.values()))
             for g in booted.stores]
    facts = {"universities": n, "data_seed": seed, "partitions": parts,
             "triples": int(len(triples)),
             "stored_edges": sum(edges), "stored_edges_by_shard": edges,
             "native_loader": native.get_lib() is not None,
             "from_bundle": booted.from_bundle,
             "bundle_bytes": sum(os.path.getsize(p)
                                 for p in booted.bundle_paths
                                 if os.path.exists(p)),
             "triples_bytes": os.path.getsize(kept),
             "host_peak_rss_bytes":
                 resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024,
             "host_mem_total_bytes": _mem_total()}
    return World(proxy, triples, read_index_rows(
        os.path.join(data_dir, "str_index")), booted.str_server.id2str, secs,
        facts)
