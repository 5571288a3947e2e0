"""Fingerprint-packed hash probe (tpu_kernels._hash_find_fp).

The fp probe must be bit-identical to the classic 8-lane probe on found/
start/degree for arbitrary key sets — including buckets with duplicate
fingerprints (the fp_dup candidate bound) and probing keys absent from the
table whose fingerprint collides with a present key (verification must
reject them)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from wukong_tpu.engine import tpu_kernels as K  # noqa: E402
from wukong_tpu.engine.device_store import build_hash_table, fp_words  # noqa: E402


def _mk_table(keys, degs):
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum(degs, out=offsets[1:])
    bkey, bstart, bdeg, max_probe = build_hash_table(
        np.asarray(keys, dtype=np.int64), offsets)
    w0, w1, dup = fp_words(bkey)
    return (jnp.asarray(bkey.reshape(-1)), jnp.asarray(bstart.reshape(-1)),
            jnp.asarray(bdeg.reshape(-1)), jnp.asarray(w0), jnp.asarray(w1),
            max_probe, dup)


def _both(bk, bs, bd, w0, w1, mp, dup, cur, n):
    valid = jnp.arange(len(cur), dtype=jnp.int32) < n
    f0, s0, d0 = K._hash_find(bk, bs, bd, cur, valid, mp)
    f1, s1, d1 = K._hash_find_fp(bk, bs, bd, w0, w1, cur, valid, mp, dup)
    return (np.asarray(f0), np.asarray(s0), np.asarray(d0),
            np.asarray(f1), np.asarray(s1), np.asarray(d1))


def test_fp_probe_matches_classic_random():
    rng = np.random.default_rng(0)
    keys = np.unique(rng.integers(1, 1 << 30, 5000))
    degs = rng.integers(0, 50, len(keys))
    bk, bs, bd, w0, w1, mp, dup = _mk_table(keys, degs)
    # probe a mix of present and absent keys
    cur_np = np.concatenate([
        rng.choice(keys, 4000),
        rng.integers(1, 1 << 30, 4192)]).astype(np.int32)
    cur = jnp.asarray(cur_np)
    f0, s0, d0, f1, s1, d1 = _both(bk, bs, bd, w0, w1, mp, dup,
                                   cur, len(cur) - 100)
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(d0, d1)


def test_fp_probe_handles_fp_collisions_in_bucket():
    """Construct keys guaranteed to share fingerprints within a bucket and
    assert fp_dup > 1 is honored (no false negatives)."""
    # find keys with equal (bucket, fingerprint) pairs by brute force
    M = np.uint32(2654435761)
    F = np.uint32(0x9E3779B1)
    NB = 2  # force tiny bucket count: every key lands in bucket 0 or 1
    cand = np.arange(1, 4000, dtype=np.uint32)
    b = (cand * M) & np.uint32(NB - 1)
    fp = ((cand * F) >> 24) & np.uint32(0xFF)
    # pick a (bucket, fp) pair with >= 3 members
    from collections import defaultdict

    groups = defaultdict(list)
    for k, bb, ff in zip(cand, b, fp):
        groups[(int(bb), int(ff))].append(int(k))
    trip = next(v for v in groups.values() if len(v) >= 3)[:3]
    other = [int(k) for k in cand[:20] if int(k) not in trip][:5]
    keys = np.asarray(sorted(trip + other), dtype=np.int64)
    degs = np.arange(1, len(keys) + 1)
    bk, bs, bd, w0, w1, mp, dup = _mk_table(keys, degs)
    assert dup >= 2  # the construction actually exercises the dup path
    cur = jnp.asarray(np.concatenate([keys, [977777]]).astype(np.int32))
    f0, s0, d0, f1, s1, d1 = _both(bk, bs, bd, w0, w1, mp, dup,
                                   cur, len(cur))
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(d0, d1)


def test_fp_probe_absent_key_with_colliding_fp_rejected():
    """An absent probe key whose fingerprint matches a stored key must be
    rejected by the bkey verification gather."""
    M = np.uint32(2654435761)
    F = np.uint32(0x9E3779B1)
    stored = 12345
    NBguess = 2
    sb = (np.uint32(stored) * M) & np.uint32(NBguess - 1)
    sf = ((np.uint32(stored) * F) >> 24) & np.uint32(0xFF)
    imposter = None
    for k in range(1, 200000):
        if k == stored:
            continue
        if ((np.uint32(k) * M) & np.uint32(NBguess - 1)) == sb and \
                (((np.uint32(k) * F) >> 24) & np.uint32(0xFF)) == sf:
            imposter = k
            break
    assert imposter is not None
    keys = np.asarray([stored], dtype=np.int64)
    bk, bs, bd, w0, w1, mp, dup = _mk_table(keys, np.asarray([7]))
    cur = jnp.asarray(np.asarray([stored, imposter], dtype=np.int32))
    f0, s0, d0, f1, s1, d1 = _both(bk, bs, bd, w0, w1, mp, dup, cur, 2)
    np.testing.assert_array_equal(f0, f1)
    assert bool(f1[0]) and not bool(f1[1])


def test_engine_results_identical_with_and_without_fp(tmp_path):
    """Full engine A/B: enable_fp_probe on/off must give identical results."""
    from wukong_tpu.config import Global
    from wukong_tpu.engine.tpu import TPUEngine
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
    from wukong_tpu.planner.heuristic import heuristic_plan
    from wukong_tpu.sparql.parser import Parser
    from wukong_tpu.store.gstore import build_partition
    from wukong_tpu.utils.paths import LUBM_BASIC

    triples, _ = generate_lubm(1, seed=0)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=0)
    text = open(f"{LUBM_BASIC}/lubm_q7").read()
    results = {}
    for flag in (True, False):
        old = Global.enable_fp_probe
        Global.enable_fp_probe = flag
        try:
            eng = TPUEngine(g, ss)
            q = Parser(ss).parse(text)
            heuristic_plan(q)
            eng.execute(q, from_proxy=False)
            assert q.result.status_code == 0
            results[flag] = (q.result.nrows,
                             set(map(tuple,
                                     np.asarray(q.result.table).tolist())))
        finally:
            Global.enable_fp_probe = old
    assert results[True] == results[False]
