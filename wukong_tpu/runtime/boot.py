"""Cold start: the served store from a saved bundle, or built and saved.

The reference re-ingests its ID-triple files at every boot. Here a built
partition round-trips through one bundle (``store/persist.py``) and the
planner's statistics through their statfile, so a second start over the same
data reads both and touches no triple: at LUBM-2560 that is half a minute
instead of some six of generating, sorting and counting.

A bundle is found by its key, which is in the file's name and in its
``_meta``: what names the data (a generator's parameters, or the files of a
dataset directory), ``persist.FORMAT_VERSION``, and a digest of the sources
that decide the store's bytes. A tree that changes the layout therefore
never reads an older tree's bundle; a bundle that fails its checksums is
rebuilt, loudly, and never served.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
import zipfile
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from wukong_tpu.obs.metrics import get_registry
from wukong_tpu.store import persist
from wukong_tpu.utils.errors import CheckpointCorrupt
from wukong_tpu.utils.logger import log_error, log_info

# the sources that decide a bundle's bytes, relative to the package
LAYOUT_SOURCES = ("loader/lubm.py", "store/gstore.py", "store/segment.py",
                  "store/persist.py")

_M_SECONDS = get_registry().gauge(
    "wukong_boot_seconds", "Seconds of each cold-start phase "
    "(boot.bundle_load, boot.stats_load, boot.build, boot.save)",
    labels=("phase",))
_M_BYTES = get_registry().gauge(
    "wukong_boot_bytes", "Bytes each cold-start phase read, built or wrote",
    labels=("phase",))


@dataclass(frozen=True)
class TripleSource:
    """Where a dataset's triples come from, should the store have to be
    built. ``load`` is called then and only then."""

    key: dict  # what names the data: generator, universities, seed, ...
    strings_dir: str  # the directory ``StringServer`` reads
    load: Callable[[], tuple]  # -> ([M, 3] id triples, attribute triples)


@dataclass
class Booted:
    store: object
    str_server: object
    planner: object
    from_bundle: bool
    bundle_path: str
    phases: dict = field(default_factory=dict)  # phase -> (seconds, bytes)


@dataclass
class BootedShards:
    """A store hash-partitioned over ``len(stores)`` workers: shard ``k``
    holds what ``build_partition(triples, k, n)`` gives, from the bundle
    file ``bundle_paths[k]``."""

    stores: list
    str_server: object
    planner: object
    from_bundle: bool
    bundle_paths: list
    phases: dict = field(default_factory=dict)  # phase -> (seconds, bytes)


def dataset_source(dataset_dir: str) -> TripleSource:
    """The id-format dataset directory the console is started on. A
    generated dataset is named by its generator's parameters
    (``str_normal_virtual``), any other by its files' names and sizes."""
    from wukong_tpu.loader.base import load_attr_triples, load_triples

    virt = os.path.join(dataset_dir, "str_normal_virtual")
    if os.path.exists(virt):
        with open(virt) as f:
            key = {k: v for k, v in json.load(f).items()
                   if not k.startswith("num_")}
    else:
        names = sorted(n for n in os.listdir(dataset_dir)
                       if n.startswith(("id_", "attr_")))
        key = {"generator": "files", "files": hashlib.sha256(repr(
            [(n, os.path.getsize(os.path.join(dataset_dir, n)))
             for n in names]).encode()).hexdigest()[:12]}
    return TripleSource(key, dataset_dir, lambda: (
        load_triples(dataset_dir), load_attr_triples(dataset_dir)))


def lubm_source(n_univ: int, seed: int, strings_dir: str) -> TripleSource:
    """LUBM(n_univ) synthesized in memory from ``seed``; of a dataset
    directory only the string tables (a few KB) are written, here, because
    ``StringServer`` reads them from ``strings_dir``."""
    from wukong_tpu.loader import lubm

    lubm.write_string_tables(strings_dir, n_univ, seed)
    return TripleSource(
        {"generator": "lubm", "n_univ": n_univ, "seed": seed}, strings_dir,
        lambda: (lubm.generate_lubm(n_univ, seed)[0],
                 lubm.lubm_attr_columns(n_univ, seed)))


def snb_source(scale_factor: float, seed: int,
               strings_dir: str) -> TripleSource:
    """LDBC SNB as LSQB reads it (``loader/snb.py``), synthesized in memory
    from ``seed``; the key holds a digest of the generator, because the
    data are whatever that file makes of its parameters."""
    from wukong_tpu.loader import snb

    snb.write_string_tables(strings_dir, scale_factor, seed)
    with open(snb.__file__, "rb") as f:
        made_by = hashlib.sha256(f.read()).hexdigest()[:12]
    return TripleSource(
        {"generator": "snb", "scale_factor": scale_factor, "seed": seed,
         "made_by": made_by}, strings_dir,
        lambda: (snb.generate_snb(scale_factor, seed)[0], None))


def layout_digest() -> str:
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for rel in LAYOUT_SOURCES:
        with open(os.path.join(pkg, rel), "rb") as f:
            h.update(rel.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()[:12]


def bundle_key(source_key: dict) -> dict:
    return {**source_key, "format": list(persist.FORMAT_VERSION),
            "layout": layout_digest()}


def bundle_stem(key: dict) -> str:
    """The bundle's file name without its extension: the key, readable."""
    words = [f"{k}={'.'.join(map(str, v)) if isinstance(v, list) else v}"
             for k, v in sorted(key.items())]
    return ("store-" + "-".join(words)).replace(os.sep, "_")


def _narrowed(triples: np.ndarray) -> np.ndarray:
    """int32 triples where every id fits (the store's own contract, checked
    by ``build_partition``): the sorts of a build work on copies, and at
    LUBM-2560 the int64 copies put the build past a 40 GiB host."""
    if triples.dtype == np.int64 and len(triples) \
            and 0 <= int(triples.min()) and int(triples.max()) < 2**31 - 1:
        return triples.astype(np.int32)
    return triples


def _widen(g) -> None:
    """Every array of a store built from int32 triples as the int64 the
    store holds everywhere else: the same bytes as a build from int64."""
    def wide(a):
        return a.astype(np.int64) if a.dtype == np.int32 else a

    for seg in list(g.segments.values()) + list(g.vp.values()):
        seg.keys, seg.edges = wide(seg.keys), wide(seg.edges)
    for k in g.index:
        g.index[k] = wide(g.index[k])
    g.v_set, g.t_set, g.p_set = wide(g.v_set), wide(g.t_set), wide(g.p_set)


def _build(source: TripleSource):
    from wukong_tpu.planner.stats import Stats
    from wukong_tpu.store.gstore import build_partition

    triples, attrs = source.load()
    stats = Stats.generate(triples)  # before the store: one at a time
    narrow = _narrowed(triples)
    del triples
    g = build_partition(narrow, 0, 1, attrs)
    del narrow, attrs
    _widen(g)
    return [g], stats


def _build_shards(source: TripleSource, n: int):
    """The ``n`` partitions from one assignment of the triples to their
    owners (``build_all_partitions``), while the planner's statistics, which
    need the triples and no store, are gathered on a thread beside them."""
    import threading

    from wukong_tpu.planner.stats import Stats
    from wukong_tpu.store.gstore import build_all_partitions

    triples, attrs = source.load()
    box: dict = {}

    def gather():
        try:
            box["stats"] = Stats.generate(triples)
        except BaseException as e:  # re-raised on the calling thread
            box["error"] = e

    th = threading.Thread(target=gather, name="boot-stats")
    th.start()
    try:
        stores = build_all_partitions(_narrowed(triples), n, attrs)
    finally:
        th.join()
    if "error" in box:
        raise box["error"]
    for g in stores:
        _widen(g)
    return stores, box["stats"]


def _save(stores: list, stats, keys: list, paths: list,
          statfile: str) -> int:
    """Every file under a temporary name first, the stores side by side: a
    start that is killed while it writes leaves no bundle, not part of
    one."""
    from concurrent.futures import ThreadPoolExecutor

    tmps, stat_tmp = [p + ".tmp.npz" for p in paths], statfile + ".tmp"
    try:
        with ThreadPoolExecutor(max_workers=len(stores)) as ex:
            list(ex.map(lambda a: persist.save_gstore(a[0], a[1], key=a[2]),
                        zip(stores, tmps, keys)))
        stats.save(stat_tmp)
        os.replace(stat_tmp + ".npz", statfile + ".npz")
        for tmp, path in zip(tmps, paths):  # the bundle last: a start
            os.replace(tmp, path)           # looks for it
    finally:
        for p in tmps + [stat_tmp + ".npz"]:  # what a full disk left behind
            if os.path.exists(p):
                os.remove(p)
    return sum(os.path.getsize(p) for p in paths) \
        + os.path.getsize(statfile + ".npz")


def _load(keys: list, paths: list, statfile: str, phases: dict):
    """-> (stores, planner) from the bundle, or None where there is none
    for these keys. A bundle that cannot be trusted is removed."""
    from concurrent.futures import ThreadPoolExecutor

    from wukong_tpu.planner.optimizer import make_planner

    if not all(os.path.exists(p) for p in paths + [statfile + ".npz"]):
        return None
    try:
        for key, path in zip(keys, paths):
            if persist.bundle_key(path) != key:
                raise CheckpointCorrupt("the key in its _meta is not the "
                                        "key in its name", path=path)
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=len(paths)) as ex:
            stores = list(ex.map(persist.load_gstore, paths))
        phases["boot.bundle_load"] = (time.perf_counter() - t0, sum(
            os.path.getsize(p) for p in paths))
        t0 = time.perf_counter()
        try:
            planner = make_planner(None, statfile)
        except (zipfile.BadZipFile, KeyError, OSError, ValueError) as e:
            raise CheckpointCorrupt(f"unreadable statistics: {e}",
                                    path=statfile + ".npz") from None
        phases["boot.stats_load"] = (time.perf_counter() - t0,
                                     os.path.getsize(statfile + ".npz"))
    except CheckpointCorrupt as e:
        log_error(f"boot: {e}: the bundle is NOT served; rebuilding "
                  "from the triples")
        phases.clear()
        for p in paths + [statfile + ".npz"]:
            if os.path.exists(p):
                os.remove(p)
        return None
    return stores, planner


def boot_store(source: TripleSource, bundle_dir: str) -> Booted:
    """The store, the string server and the planner of ``source``'s data:
    loaded from the bundle under ``bundle_dir`` whose key matches, else
    built as every start used to and saved there for the next one."""
    key = bundle_key(source.key)
    stem = os.path.join(bundle_dir, bundle_stem(key))
    b = _boot(source, bundle_dir, [key], [stem + ".npz"], stem + ".stat",
              lambda: _build(source))
    return Booted(b.stores[0], b.str_server, b.planner, b.from_bundle,
                  b.bundle_paths[0], b.phases)


def boot_shards(source: TripleSource, bundle_dir: str,
                partitions: int) -> BootedShards:
    """``source``'s data hash-partitioned over ``partitions`` workers, as
    ``boot_store`` boots one: loaded from the bundle's shard files, else
    built from one assignment of the triples to their owners and saved. No
    whole store is built beside the shards: the planner's statistics are
    gathered from the triples."""
    key = {**bundle_key(source.key), "partitions": int(partitions)}
    stem = os.path.join(bundle_dir, bundle_stem(key))
    keys = [{**key, "shard": k} for k in range(partitions)]
    paths = [f"{stem}-shard{k}.npz" for k in range(partitions)]
    return _boot(source, bundle_dir, keys, paths, stem + ".stat",
                 lambda: _build_shards(source, partitions))


def sharded_proxy(booted: BootedShards, devices=None):
    """The proxy of a sharded deployment: the sharded engine over the
    booted shards, one device a shard (``devices``, else the first of
    ``jax.devices()``), serves every request that pins no engine. No whole
    store stands beside the shards: the host engine walks them in place,
    each lookup on its vertex's owner (``InplaceEngine``), and answers what
    the sharded engine refuses; the device engine holds no segment of its
    own (a federated view of the shards, which it cannot stage) and answers
    only where it is pinned; shard 0 is the proxy's host partition."""
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.parallel.dist_engine import DistEngine
    from wukong_tpu.parallel.inplace import FederatedGraph, InplaceEngine
    from wukong_tpu.parallel.mesh import make_mesh
    from wukong_tpu.runtime.proxy import Proxy

    stores, ss = booted.stores, booted.str_server
    dist = DistEngine(stores, ss, make_mesh(len(stores), devices))
    proxy = Proxy(stores[0], ss, InplaceEngine(stores, ss),
                  TPUEngine(FederatedGraph(stores), ss), dist,
                  planner=booted.planner)
    proxy.tpu.stats = booted.planner.stats
    return proxy


def _boot(source: TripleSource, bundle_dir: str, keys: list, paths: list,
          statfile: str, build) -> BootedShards:
    from wukong_tpu.planner.optimizer import Planner
    from wukong_tpu.store.string_server import StringServer

    phases: dict = {}
    loaded = _load(keys, paths, statfile, phases)
    if loaded is not None:
        stores, planner = loaded
    else:
        t0 = time.perf_counter()
        stores, stats = build()
        phases["boot.build"] = (time.perf_counter() - t0,
                                sum(g.memory_bytes() for g in stores))
        planner = Planner(stats)
        t0 = time.perf_counter()
        os.makedirs(bundle_dir, exist_ok=True)
        try:
            nbytes = _save(stores, stats, keys, paths, statfile)
        except OSError as e:  # a start must not fail for want of a cache
            log_error(f"boot: bundle not saved ({e}); the next start "
                      "builds again")
            nbytes = 0
        phases["boot.save"] = (time.perf_counter() - t0, nbytes)
    for name, (secs, nbytes) in phases.items():
        _M_SECONDS.labels(phase=name).set(secs)
        _M_BYTES.labels(phase=name).set(nbytes)
        log_info(f"{name}: {secs:.2f} s, {nbytes:,} bytes")
    return BootedShards(stores, StringServer(source.strings_dir), planner,
                        loaded is not None, paths, phases)
