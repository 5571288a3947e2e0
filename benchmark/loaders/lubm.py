"""Loader for configurations whose ``generator`` is ``lubm``.

Makes LUBM(universities) from the seed in memory, builds the store, the
planner and the proxy by the calls ``runtime/console.py`` makes — one
partition, ``CPUEngine`` + ``TPUEngine``, the planner's statistics handed to
the device engine — and returns them with the triples for the plain
reference. Nothing of the data goes to disk: of a dataset directory only the
string tables (a few KB) are written under the cache directory, because
``StringServer`` reads them from there."""

from __future__ import annotations

import json
import os
import threading
import time


class World:
    """What a run drives and what the check compares against."""

    def __init__(self, proxy, triples, index_rows, id2str, seconds, facts):
        self.proxy = proxy
        self.triples = triples
        self.index_rows = index_rows
        self.id2str = id2str
        self.seconds = seconds  # phase -> host seconds
        self.facts = facts  # counts and flags for the log line


def _write_string_tables(data_dir: str, universities: int, seed: int,
                         n_triples: int, n_attrs: int) -> None:
    """The small files of a dataset directory (``loader/lubm.write_dataset``
    writes the same rows); ``str_normal_virtual`` is written last."""
    from wukong_tpu.loader import lubm

    os.makedirs(data_dir, exist_ok=True)
    with open(os.path.join(data_dir, "str_index"), "w") as f:
        for s, i in lubm.index_strings():
            f.write(f"{s}\t{i}\n")
    with open(os.path.join(data_dir, "str_attr_index"), "w") as f:
        for s, i, t in lubm.attr_index_strings():
            f.write(f"{s}\t{i}\t{t}\n")
    meta = {"generator": "lubm", "n_univ": universities, "seed": seed,
            "num_triples": n_triples, "num_attrs": n_attrs}
    with open(os.path.join(data_dir, "str_normal_virtual"), "w") as f:
        json.dump(meta, f)


def load(config: dict, seed: int, data_dir: str) -> World:
    import numpy as np

    from wukong_tpu import native
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.loader.lubm import generate_lubm, generate_lubm_attrs
    from wukong_tpu.planner.optimizer import make_planner
    from wukong_tpu.runtime.proxy import Proxy
    from wukong_tpu.store.gstore import build_partition
    from wukong_tpu.store.string_server import StringServer

    from benchmark.reference import read_index_rows

    n = int(config["universities"])
    # the generator ties every size to its seed, and sizes decide capacity
    # classes and program shapes: a configuration states its data seed, so
    # that every --seed sends its traffic to the same store
    seed = int(config.get("data_seed", seed))
    data_dir = f"{data_dir}_d{seed}"
    secs: dict[str, float] = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        secs[name] = round(time.perf_counter() - t0, 2)
        return out

    triples, _layout = timed("generate_lubm", lambda: generate_lubm(n, seed))
    attrs = timed("generate_lubm_attrs", lambda: generate_lubm_attrs(n, seed))
    if int(triples.min()) < 0 or int(triples.max()) >= np.iinfo(np.int32).max:
        raise SystemExit("benchmark: vertex ids do not fit the device's int32")
    _write_string_tables(data_dir, n, seed, len(triples), len(attrs))
    ss = StringServer(data_dir)

    # the statistics need only the triples: they are gathered beside the
    # store build, on a thread of their own (NumPy sorts release the GIL),
    # and end before it does. So no statistics file is kept: it would be
    # 320 MB a seed at LUBM-640 and save no second of set-up.
    box: dict = {}

    def plan():
        t0 = time.perf_counter()
        try:
            box["planner"] = make_planner(triples, None)
        except BaseException as e:  # re-raised on the main thread
            box["error"] = e
        secs["make_planner"] = round(time.perf_counter() - t0, 2)

    th = threading.Thread(target=plan, name="bench-planner")
    th.start()
    g = timed("build_partition", lambda: build_partition(triples, 0, 1, attrs))
    t0 = time.perf_counter()
    th.join()
    secs["planner_wait"] = round(time.perf_counter() - t0, 2)
    if "error" in box:
        raise box["error"]
    del attrs

    proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    proxy.planner = box["planner"]
    proxy.tpu.stats = proxy.planner.stats  # capacity estimation, as the console
    facts = {"universities": n, "data_seed": seed,
             "triples": int(len(triples)),
             "stored_edges": int(sum(s.num_edges for s in g.segments.values())),
             "native_loader": native.get_lib() is not None}
    return World(proxy, triples, read_index_rows(
        os.path.join(data_dir, "str_index")), ss.id2str, secs, facts)
