"""Loader for configurations whose ``generator`` is ``watdiv``.

Makes WatDiv at the configuration's ``scale_factor`` in memory
(``wukong_tpu/loader/watdiv.py``), builds the store, the planner and the
proxy by the calls ``runtime/console.py`` makes, one partition,
``CPUEngine`` + ``TPUEngine``, the planner's statistics gathered on a thread
beside the store build and handed to the device engine, and returns them
with the triples for the plain reference. Of a dataset directory only the
string tables are written, because ``StringServer`` reads them from there.

A program whose generator is not WatDiv's data model (the parent of the PR
that brought this file has a sketch under the same name: 18 predicates, no
offers' ``gr:includes``, invented templates) is refused at once, before any
data is made: it carries no ``SCHEMA`` marker."""

from __future__ import annotations

import json
import os
import threading
import time

from benchmark.loaders.lubm import World

SCHEMA = "watdiv-wsdbm-1"


def load(config: dict, seed: int, data_dir: str) -> World:
    from wukong_tpu.loader import watdiv

    if getattr(watdiv, "SCHEMA", None) != SCHEMA:
        raise SystemExit(
            "benchmark: wukong_tpu/loader/watdiv.py is not the WatDiv data "
            f"model this cell is written against (SCHEMA {SCHEMA!r}): the "
            "program cannot run this configuration")

    import numpy as np

    from wukong_tpu import native
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.planner.optimizer import make_planner
    from wukong_tpu.runtime.proxy import Proxy
    from wukong_tpu.store.gstore import build_partition
    from wukong_tpu.store.string_server import StringServer

    from benchmark.reference import read_index_rows

    scale = int(config["scale_factor"])
    # as the LUBM loader: the data seed is the configuration's, so that
    # every --seed sends its traffic to the same store (sizes decide
    # capacity classes and program shapes)
    seed = int(config.get("data_seed", seed))
    data_dir = f"{data_dir}_d{seed}"
    secs: dict[str, float] = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        secs[name] = round(time.perf_counter() - t0, 2)
        return out

    triples, _layout = timed("generate_watdiv",
                             lambda: watdiv.generate_watdiv(scale, seed))
    if int(triples.min()) < 0 or int(triples.max()) >= np.iinfo(np.int32).max:
        raise SystemExit("benchmark: vertex ids do not fit the device's int32")
    os.makedirs(data_dir, exist_ok=True)
    with open(os.path.join(data_dir, "str_index"), "w") as f:
        for s, i in watdiv.index_strings():
            f.write(f"{s}\t{i}\n")
    with open(os.path.join(data_dir, "str_normal_virtual"), "w") as f:
        json.dump({"generator": "watdiv", "scale": scale, "seed": seed,
                   "num_triples": int(len(triples))}, f)
    ss = StringServer(data_dir)

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
    g = timed("build_partition", lambda: build_partition(triples, 0, 1))
    t0 = time.perf_counter()
    th.join()
    secs["planner_wait"] = round(time.perf_counter() - t0, 2)
    if "error" in box:
        raise box["error"]

    proxy = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    proxy.planner = box["planner"]
    proxy.tpu.stats = proxy.planner.stats  # capacity estimation, as the console
    facts = {"scale_factor": scale, "data_seed": seed,
             "triples": int(len(triples)),
             "stored_edges": int(sum(s.num_edges for s in g.segments.values())),
             "native_loader": native.get_lib() is not None}
    # the vertices the query files name outright (wsdbm:Product0, ...) are
    # no rows of str_index: the reference gets their ids with the table's
    rows = read_index_rows(os.path.join(data_dir, "str_index"))
    rows += [(iri, ss.str2id(iri)) for iri in config.get("named_vertices", [])]
    return World(proxy, triples, rows, ss.id2str, secs, facts)
