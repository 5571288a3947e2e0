"""Analytic LUBM segment headers + the LUBM-10240 HBM budget (round-4
verdict #3: the north-star scale must at least be PLANNED — capacity
classes and staged-segment footprints derived from exact synthesized
headers, asserted to fit v5e-8 HBM — even though its ~68 GB store cannot
be built on this machine's disk).

Two layers:
1. `lubm_headers` validity: at a scale small enough to build for real,
   every header is an upper bound on the built store's segment (keys,
   edges, max degree), covers every segment the store builds, and stays
   tight (<= 1.5x on edges) — so the 10240 numbers are trustworthy.
2. LUBM-10240 budget walk, mirroring tests/test_at_scale_2560.py's math
   (HBM_BUDGET.md): per-chain staged pins + chain state + sort workspace,
   single-chip and 8-way-sharded, against v5e's 16 GiB/chip.
"""

import numpy as np
import pytest

from wukong_tpu.loader.lubm import generate_lubm, lubm_headers
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.types import IN, NORMAL_ID_START, OUT

from wukong_tpu.utils.paths import LUBM_BASIC as BASIC
HBM_BYTES = 16 * 2**30  # v5e: 16 GiB HBM per chip
MESH_D = 8  # v5e-8


def _pow2(n: int) -> int:
    p = 1
    while p < max(n, 1):
        p *= 2
    return p


def _staged_bytes(nk: int, ne: int) -> int:
    """Staged merge form (device_store._stage_merge): edges+ekey int32
    pow2-padded (8 B/edge) + skey/sstart/sdeg int32 pow2-padded (12 B/key)."""
    return 12 * _pow2(nk) + 8 * _pow2(ne)


@pytest.mark.parametrize("scale", [1, 8])
def test_headers_upper_bound_real_store(scale):
    h = lubm_headers(scale)
    triples, _lay = generate_lubm(scale, seed=0)
    g = build_partition(triples, 0, 1)
    for (pid, d), (nk, ne, md) in h["segs"].items():
        seg = g.segments.get((pid, d))
        if seg is None:
            continue  # header may bound a segment the data didn't produce
        real_k, real_e = len(seg.keys), len(seg.edges)
        real_md = int(np.max(np.diff(seg.offsets))) if real_k else 0
        assert nk >= real_k, (pid, d, nk, real_k)
        assert ne >= real_e, (pid, d, ne, real_e)
        assert md >= real_md, (pid, d, md, real_md)
        assert ne <= max(real_e, 1) * 1.5 + 64, \
            (pid, d, "header too loose", ne, real_e)
    # full coverage: every built segment has a header
    missing = [k for k in g.segments if k not in h["segs"]]
    assert not missing, missing
    # type index counts exact
    for t, n in h["type_index"].items():
        real = len(g.get_index(t, IN))
        assert real <= n <= real * 1.001 + 2, (t, n, real)


@pytest.fixture(scope="module")
def headers_10240():
    return lubm_headers(10240)


def test_10240_magnitudes(headers_10240):
    """Sanity-pin the scale: ~4x LUBM-2560 (582 M stored edges there)."""
    tot = headers_10240["totals"]
    assert 1.1e9 < tot["triples"] < 1.7e9
    assert 1.8e8 < tot["entities"] < 2.6e8


def _plans_10240():
    """L1-L7 plans for the budget walk. heuristic_plan needs no stats file;
    plan SHAPES are scale-invariant in LUBM (all cardinality ratios are
    constants of the generator), so the chains sized here are the chains
    the bench would run."""
    from wukong_tpu.loader.lubm import VirtualLubmStrings
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.sparql.parser import Parser

    ss = VirtualLubmStrings(10240, seed=0)
    out = []
    for k in range(1, 8):
        q = Parser(ss).parse(open(f"{BASIC}/lubm_q{k}").read())
        heuristic_plan(q)
        if any(p.predicate < 0 for p in q.pattern_group.patterns):
            continue  # host-path shape: no device chain to budget
        out.append((f"lubm_q{k}", q))
    return out


def test_10240_planned_chains_fit_v5e8(headers_10240):
    """Every bench chain's pins + state + workspace fit ONE v5e chip when
    the store is sharded 8 ways (the reference's own 10240 numbers are
    from a multi-node cluster: S5C24(MEEPO)-LUBM10240-20181212.md) —
    the v5e-8 deployment plan is feasible."""
    from wukong_tpu.config import Global
    from wukong_tpu.engine.tpu_merge import MergeExecutor

    segs = {k: (nk, ne) for k, (nk, ne, _md) in headers_10240["segs"].items()}
    cap_max = Global.table_capacity_max
    level_bytes = 2 * 4 * cap_max
    report = {}
    for qn, q in _plans_10240():
        pats = q.pattern_group.patterns
        index_mode = pats[0].subject < NORMAL_ID_START
        folds = MergeExecutor._plan_folds(pats, index_mode=index_mode)
        pins = MergeExecutor._chain_pins(pats, folds, index_mode=index_mode)
        pin_bytes = 0
        for key in pins:
            if key[0] in ("mrg", "mrgf"):
                nk, ne = segs.get((key[1], key[2]), (0, 0))
                pin_bytes += _staged_bytes(nk, ne)
            elif key[0] == "rev":
                nk, _ = segs.get((key[1], key[2]), (0, 0))
                pin_bytes += 4 * _pow2(nk)
        expands = sum(1 for (_s, _p, kind, _f) in MergeExecutor.classify(
            pats, folds, index_mode) if kind == "expand")
        state = (expands + 1) * level_bytes
        workspace = 3 * level_bytes
        # 8-way sharding: segment arrays split ~1/D per chip (hash
        # placement; 1.3x slack covers skew + pow2 re-padding), chain
        # state + workspace are per-shard already (per-shard capacity
        # classes cap at table_capacity_max)
        shard_pins = int(pin_bytes / MESH_D * 1.3)
        need = shard_pins + state + workspace
        report[qn] = (pin_bytes, need)
        assert need <= HBM_BYTES, (
            f"{qn}@10240 on v5e-8: shard pins {shard_pins / 2**30:.2f} GiB"
            f" + state {state / 2**30:.2f} + workspace "
            f"{workspace / 2**30:.2f} GiB > 16 GiB")
    # single-chip feasibility is informational: the lights must fit a
    # single chip outright (their pins are the small segments)
    for qn in ("lubm_q4", "lubm_q5", "lubm_q6"):
        if qn in report:
            pin_bytes, _ = report[qn]
            assert pin_bytes + 4 * level_bytes <= HBM_BYTES, \
                f"{qn}@10240 single-chip: {pin_bytes / 2**30:.2f} GiB pins"


def test_10240_staged_all_needs_sharding(headers_10240):
    """Staged-ALL at 10240 exceeds one chip (documents WHY the deployment
    is v5e-8) but fits the 8-chip mesh with margin."""
    total = sum(_staged_bytes(nk, ne)
                for nk, ne, _md in headers_10240["segs"].values())
    assert total > HBM_BYTES  # one chip cannot hold the whole store
    assert total / MESH_D * 1.3 < HBM_BYTES  # v5e-8 holds it sharded
