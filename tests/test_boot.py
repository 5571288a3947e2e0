"""Cold start from a saved bundle (``runtime/boot.py``).

A world booted from a bundle is the world that was built: the same store
bytes (``gstore_digest``), the same plans for LUBM q1-q7, the same rows as
the CPU engine gives on the built world. A bundle under another key is not
read; one that fails its checksums is rebuilt, loudly, never served; and the
console started twice on one directory builds once.
"""

import os

import numpy as np
import pytest

from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.loader.lubm import (generate_lubm, generate_lubm_attrs,
                                    lubm_attr_columns, write_dataset)
from wukong_tpu.planner.optimizer import make_planner
from wukong_tpu.runtime import boot
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.store import persist
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.utils.paths import QUERIES

BASIC = os.path.join(QUERIES, "lubm", "basic")
N, SEED = 2, 11


@pytest.fixture(scope="module")
def built():
    triples, _ = generate_lubm(N, SEED)
    g = build_partition(triples, 0, 1, generate_lubm_attrs(N, SEED))
    return g, make_planner(triples, None)


@pytest.fixture(scope="module")
def booted(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("bundle"))
    first = boot.boot_store(boot.lubm_source(N, SEED, d), d)
    second = boot.boot_store(boot.lubm_source(N, SEED, d), d)
    return d, first, second


def _proxy(g, ss, planner):
    p = Proxy(g, ss, CPUEngine(g, ss), None)
    p.planner = planner
    return p


def test_second_start_reads_the_bundle_and_no_triple(booted):
    d, first, second = booted
    assert not first.from_bundle and second.from_bundle
    assert set(first.phases) == {"boot.build", "boot.save"}
    assert set(second.phases) == {"boot.bundle_load", "boot.stats_load"}
    assert first.bundle_path == second.bundle_path
    name = os.path.basename(first.bundle_path)
    for word in ("generator=lubm", f"n_univ={N}", f"seed={SEED}",
                 "format=" + ".".join(map(str, persist.FORMAT_VERSION)),
                 "layout=" + boot.layout_digest()):
        assert word in name  # the key is in the file's name ...
    assert persist.bundle_key(first.bundle_path) == \
        boot.bundle_key({"generator": "lubm", "n_univ": N, "seed": SEED})
    snap = _proxy(second.store, second.str_server,
                  second.planner).metrics.snapshot()
    phases = {s["labels"]["phase"]: s["value"]
              for s in snap["wukong_boot_seconds"]["series"]}
    assert phases["boot.bundle_load"] > 0 and "boot.stats_load" in phases
    assert {s["labels"]["phase"] for s in
            snap["wukong_boot_bytes"]["series"]} >= set(second.phases)


def test_booted_world_is_the_built_world(booted, built):
    _d, first, second = booted
    g, planner = built
    want = persist.gstore_digest(g)
    # the build narrows the triples to int32 for its sorts and widens the
    # store after: the same bytes as the plain int64 build
    assert persist.gstore_digest(first.store) == want
    assert persist.gstore_digest(second.store) == want
    ref = _proxy(g, second.str_server, planner)
    got = _proxy(second.store, second.str_server, second.planner)
    for k in range(1, 8):
        with open(os.path.join(BASIC, f"lubm_q{k}")) as f:
            text = f.read()
        a = ref.run_single_query(text, device="cpu", blind=False)
        b = got.run_single_query(text, device="cpu", blind=False)
        assert [(p.subject, p.predicate, int(p.direction), p.object)
                for p in a.pattern_group.patterns] == \
            [(p.subject, p.predicate, int(p.direction), p.object)
             for p in b.pattern_group.patterns], f"q{k} planned otherwise"
        assert np.array_equal(np.asarray(a.result.table),
                              np.asarray(b.result.table)), f"q{k}"
        assert len(a.result.table) or k == 3  # q3 is empty by design


def test_attr_columns_are_the_attr_rows():
    triples, _ = generate_lubm(1, SEED)
    rows, cols = generate_lubm_attrs(1, SEED), lubm_attr_columns(1, SEED)
    assert rows == list(zip(cols.subject.tolist(), cols.aid.tolist(),
                            [1] * len(rows), cols.value.tolist()))
    for n, sid in ((1, 0), (3, 2)):
        assert persist.gstore_digest(build_partition(triples, sid, n, rows)) \
            == persist.gstore_digest(build_partition(triples, sid, n, cols))


def _another_universities(monkeypatch, d):
    return boot.lubm_source(N + 1, SEED, d)


def _another_seed(monkeypatch, d):
    return boot.lubm_source(N, SEED + 1, d)


def _another_format(monkeypatch, d):
    monkeypatch.setattr(persist, "FORMAT_VERSION", (2, 99))
    return boot.lubm_source(N, SEED, d)


def _another_layout(monkeypatch, d):
    monkeypatch.setattr(boot, "layout_digest", lambda: "0" * 12)
    return boot.lubm_source(N, SEED, d)


@pytest.mark.parametrize("other", [_another_universities, _another_seed,
                                   _another_format, _another_layout])
def test_a_bundle_under_another_key_is_not_read(other, booted, monkeypatch,
                                                tmp_path):
    d, first, _second = booted
    mine = str(tmp_path / "d")
    os.makedirs(mine)
    for name in os.listdir(d):  # the bundle of (N, SEED), and only it
        if name.startswith("store-"):
            os.link(os.path.join(d, name), os.path.join(mine, name))
    again = boot.boot_store(other(monkeypatch, mine), mine)
    assert not again.from_bundle and "boot.build" in again.phases
    assert again.bundle_path != os.path.join(
        mine, os.path.basename(first.bundle_path))
    assert os.path.exists(again.bundle_path)  # saved beside the other


def _flip_bytes(path):
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        chunk = f.read(64)
        f.seek(-64, os.SEEK_CUR)
        f.write(bytes(b ^ 0xFF for b in chunk))


def _truncate(path):
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 3)


def _swap_in_another_bundle(path):
    d = os.path.dirname(path)
    other = boot.boot_store(boot.lubm_source(1, SEED, d), d)
    os.replace(other.bundle_path, path)  # a sound bundle, of other data


def _break_statistics(path):
    _truncate(path[:-len(".npz")] + ".stat.npz")


@pytest.mark.parametrize("damage", [_flip_bytes, _truncate,
                                    _swap_in_another_bundle,
                                    _break_statistics])
def test_a_corrupt_bundle_is_rebuilt_loudly_never_served(damage, built,
                                                         monkeypatch,
                                                         tmp_path):
    d = str(tmp_path)
    first = boot.boot_store(boot.lubm_source(N, SEED, d), d)
    damage(first.bundle_path)
    said = []
    monkeypatch.setattr(boot, "log_error", said.append)
    again = boot.boot_store(boot.lubm_source(N, SEED, d), d)
    assert not again.from_bundle and set(again.phases) == \
        {"boot.build", "boot.save"}
    assert len(said) == 1 and "NOT served" in said[0]
    assert persist.gstore_digest(again.store) == persist.gstore_digest(built[0])
    third = boot.boot_store(boot.lubm_source(N, SEED, d), d)
    assert third.from_bundle  # the rebuilt bundle is sound


def test_console_started_twice_on_one_directory_builds_once(tmp_path,
                                                            monkeypatch,
                                                            capsys):
    from wukong_tpu.config import Global
    from wukong_tpu.runtime.console import main as console_main
    from wukong_tpu.store import gstore

    data = str(tmp_path / "lubm1")
    write_dataset(data, 1, seed=SEED)
    cfg = tmp_path / "config"
    cfg.write_text("global_enable_tpu 0\n")
    builds = []
    real = gstore.build_partition
    monkeypatch.setattr(gstore, "build_partition",
                        lambda *a, **kw: builds.append(1) or real(*a, **kw))
    prev = Global.enable_tpu
    outs = []
    try:
        for _ in range(2):
            assert console_main([str(cfg), data, "-c",
                                 f"sparql -f {BASIC}/lubm_q4 -v 3"]) == 0
            outs.append(capsys.readouterr())
    finally:
        Global.enable_tpu = prev
    assert len(builds) == 1
    assert "boot.build" in outs[0].err + outs[0].out
    assert "boot.bundle_load" in outs[1].err + outs[1].out
    # the same reply from the built and from the loaded store
    rows = [[ln.split("]")[-1] for ln in (o.out + o.err).splitlines()
             if "Department" in ln] for o in outs]
    assert len(rows[0]) == 3 and rows[0] == rows[1]


def test_shards_boot_once_then_load(tmp_path, built):
    """``boot_shards`` builds every shard from one assignment of the triples
    and saves one file a shard and the statistics; the second start loads
    them and reads no triple. Each shard is ``build_partition``'s, and the
    planner plans as the whole store's does."""
    d = str(tmp_path)
    first = boot.boot_shards(boot.lubm_source(N, SEED, d), d, 3)
    second = boot.boot_shards(boot.lubm_source(N, SEED, d), d, 3)
    assert not first.from_bundle and second.from_bundle
    assert set(first.phases) == {"boot.build", "boot.save"}
    assert set(second.phases) == {"boot.bundle_load", "boot.stats_load"}
    assert first.bundle_paths == second.bundle_paths
    assert len(first.bundle_paths) == 3
    for k, path in enumerate(first.bundle_paths):
        assert "partitions=3" in os.path.basename(path)
        assert persist.bundle_key(path) == {**boot.bundle_key(
            {"generator": "lubm", "n_univ": N, "seed": SEED}),
            "partitions": 3, "shard": k}
    triples, _ = generate_lubm(N, SEED)
    attrs = generate_lubm_attrs(N, SEED)
    for k in range(3):
        want = persist.gstore_digest(build_partition(triples, k, 3, attrs))
        assert persist.gstore_digest(first.stores[k]) == want
        assert persist.gstore_digest(second.stores[k]) == want
    from wukong_tpu.sparql.parser import Parser

    _g, planner = built
    for k in range(1, 8):
        with open(os.path.join(BASIC, f"lubm_q{k}")) as f:
            text = f.read()
        a, b = (Parser(second.str_server).parse(text) for _ in range(2))
        assert planner.generate_plan(a) == second.planner.generate_plan(b)
        assert [(p.subject, p.predicate, int(p.direction), p.object)
                for p in a.pattern_group.patterns] == \
            [(p.subject, p.predicate, int(p.direction), p.object)
             for p in b.pattern_group.patterns], f"q{k} planned otherwise"
    # the one-partition bundle of the same data is another file
    one = boot.boot_store(boot.lubm_source(N, SEED, d), d)
    assert not one.from_bundle and one.bundle_path not in first.bundle_paths
