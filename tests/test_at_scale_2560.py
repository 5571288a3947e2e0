"""LUBM-2560 store-metadata regression (round-4 verdict #2): the bench
chains' pin sets and capacity classes must fit v5e HBM at the scale the
flagship claim is made at — checked from the cached store's npz HEADERS
(zip member headers give every array's shape without touching the 16.9 GB
of data) plus the cached optimizer stats, so the test runs in seconds.

Math mirrors HBM_BUDGET.md:
- staged merge form per (pid, dir): edges + ekey int32 (pow2-padded) and
  skey/sstart/sdeg int32 (pow2-padded) = 8 B/edge + 12 B/key after padding
- chain state per expand level at table_capacity_max: (vals, parent) int32
- variadic-sort workspace ~3x the biggest level

Skipped when the 2560 caches are absent (fresh checkout / other machines).
"""

import os
import zipfile

import json
import numpy as np
import pytest
from numpy.lib import format as npf

from wukong_tpu.types import NORMAL_ID_START

CACHE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache")
STORE = os.path.join(CACHE, "lubm2560_v2_p0.npz")
STATS = os.path.join(CACHE, "lubm2560_v2_stats.npz")
from wukong_tpu.utils.paths import LUBM_BASIC as BASIC

pytestmark = pytest.mark.skipif(
    not (os.path.exists(STORE) and os.path.exists(STATS)
         and os.path.isdir(BASIC)),
    reason="LUBM-2560 caches not built on this machine")

HBM_BYTES = 16 * 2**30  # v5e: 16 GiB HBM per chip


def _pow2(n: int) -> int:
    p = 1
    while p < max(n, 1):
        p *= 2
    return p


@pytest.fixture(scope="module")
def store_meta():
    """{(pid, d): (num_keys, num_edges)} from npz headers + tiny meta blob."""
    shapes = {}
    with zipfile.ZipFile(STORE) as z:
        for name in z.namelist():
            with z.open(name) as f:
                version = npf.read_magic(f)
                shape, _fortran, _dtype = npf._read_array_header(f, version)
                shapes[name.removesuffix(".npy")] = shape
    meta = json.loads(bytes(np.load(STORE)["_meta"]).decode())
    segs = {}
    for i, (pid, d) in enumerate(meta["segments"]):
        segs[(int(pid), int(d))] = (shapes[f"seg{i}_k"][0],
                                    shapes[f"seg{i}_e"][0])
    return segs


def _staged_bytes(nk: int, ne: int) -> int:
    """Bytes of the staged merge form (device_store._stage_merge)."""
    return 12 * _pow2(nk) + 8 * _pow2(ne)


def test_staged_all_matches_hbm_budget_table(store_meta):
    """HBM_BUDGET.md's 'staged-ALL ~10.5 GiB' row stays honest."""
    total = sum(_staged_bytes(nk, ne) for nk, ne in store_meta.values())
    assert 8 * 2**30 < total < 13 * 2**30, f"{total / 2**30:.1f} GiB"
    biggest = max(_staged_bytes(nk, ne) for nk, ne in store_meta.values())
    assert biggest < 2.5 * 2**30  # "~1.4 GiB biggest single segment"


def test_planned_chains_fit_hbm(store_meta):
    """Every bench query's pin set + chain state + sort workspace fits one
    chip at LUBM-2560 — the single-chip feasibility claim behind the bench.
    Pins come from the REAL planned chains (type-centric Planner over the
    cached 2560 stats), sized by the staged-form math above; capacity
    classes are bounded by table_capacity_max exactly as the executor
    clamps them."""
    from wukong_tpu.config import Global
    from wukong_tpu.engine.tpu_merge import MergeExecutor
    from wukong_tpu.loader.lubm import VirtualLubmStrings
    from wukong_tpu.planner.optimizer import Planner
    from wukong_tpu.planner.stats import Stats
    from wukong_tpu.sparql.parser import Parser

    ss = VirtualLubmStrings(2560, seed=0)
    planner = Planner(Stats.load(STATS))
    cap_max = Global.table_capacity_max
    level_bytes = 2 * 4 * cap_max  # (vals, parent) int32 at full class
    for k in range(1, 8):
        q = Parser(ss).parse(open(f"{BASIC}/lubm_q{k}").read())
        planner.generate_plan(q)
        if q.planner_empty:
            continue
        pats = q.pattern_group.patterns
        if any(p.predicate < 0 for p in pats):
            continue  # host-path shape, no device chain to budget
        index_mode = pats[0].subject < NORMAL_ID_START
        folds = MergeExecutor._plan_folds(pats, index_mode=index_mode)
        pins = MergeExecutor._chain_pins(pats, folds, index_mode=index_mode)
        pin_bytes = 0
        for key in pins:
            if key[0] in ("mrg", "mrgf"):
                # expands pin both merge and bucket forms but stage only
                # ONE at runtime; the merge form bounds both (bucket form
                # is 3 flat bucket arrays + edges, same magnitude), so
                # count each expand once here and skip its bucket twin
                nk, ne = store_meta.get((key[1], key[2]), (0, 0))
                pin_bytes += _staged_bytes(nk, ne)  # mrgf <= unfiltered
            elif key[0] == "rev":  # rev list: bounded by the key count
                nk, _ = store_meta.get((key[1], key[2]), (0, 0))
                pin_bytes += 4 * _pow2(nk)
            # bare (pid, d) / ("segf", ...) bucket twins: counted above
        expands = sum(1 for (_s, _p, kind, _f) in MergeExecutor.classify(
            pats, folds, index_mode) if kind == "expand")
        state_bytes = (expands + 1) * level_bytes
        workspace = 3 * level_bytes
        need = pin_bytes + state_bytes + workspace
        assert need <= HBM_BYTES, (
            f"lubm_q{k}: pins {pin_bytes / 2**30:.2f} GiB + state "
            f"{state_bytes / 2**30:.2f} GiB + sort workspace "
            f"{workspace / 2**30:.2f} GiB = {need / 2**30:.2f} GiB > 16 GiB")
