"""tpu_stream.stream_expand vs tpu_kernels.merge_expand (interpret mode).

The streaming emitter must be a bit-identical drop-in for the XLA merge
emit: same (val, parent, out_n, total) for distinct-anchor frontiers, same
via its lax.cond fallback when anchors repeat. Segments are random CSRs
shaped like the staged MergeSegment arrays (pow2-padded, INT32_MAX pads).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from wukong_tpu.engine.tpu_kernels import INT32_MAX  # noqa: E402
from wukong_tpu.engine.tpu_kernels import \
    wk_walk_merge_expand as merge_expand  # noqa: E402
from wukong_tpu.engine.tpu_stream import TILE  # noqa: E402
from wukong_tpu.engine.tpu_stream import \
    wk_walk_merge_stream_expand as stream_expand  # noqa: E402


def _mk_segment(rng, nkeys, max_deg):
    """Random CSR segment in staged MergeSegment form (pow2 pads)."""
    keys = np.sort(rng.choice(200_000, size=nkeys, replace=False)).astype(
        np.int32)
    degs = rng.integers(0, max_deg + 1, size=nkeys)
    offs = np.concatenate([[0], np.cumsum(degs)]).astype(np.int64)
    edges = rng.integers(0, 2**31 - 1, size=int(offs[-1]), dtype=np.int64)
    Kp = 1 << max(int(nkeys - 1).bit_length(), 1)
    Ep = 1 << max(int(len(edges) - 1).bit_length(), 8)
    sk = np.full(Kp, INT32_MAX, np.int32)
    sk[:nkeys] = keys
    ss = np.zeros(Kp, np.int32)
    ss[:nkeys] = offs[:-1]
    sd = np.zeros(Kp, np.int32)
    sd[:nkeys] = degs
    e = np.full(Ep, INT32_MAX, np.int32)
    e[:len(edges)] = edges
    return sk, ss, sd, e, keys, offs


def _run_both(sk, ss, sd, e, cur, n, live, cap):
    a = merge_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                     jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                     jnp.asarray(live), cap_out=cap)
    b = stream_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                      jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                      jnp.asarray(live), cap_out=cap, interpret=True)
    return [np.asarray(x) for x in a], [np.asarray(x) for x in b]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_stream_matches_merge_distinct_anchors(seed):
    rng = np.random.default_rng(seed)
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=300, max_deg=9)
    C = 512
    # distinct anchors: a sample of keys + some misses, no repeats
    pool = np.concatenate([keys, np.setdiff1d(
        rng.choice(200_000, 400, replace=False), keys)])
    cur = np.full(C, INT32_MAX, np.int32)
    n = 300
    cur[:n] = rng.choice(pool, size=n, replace=False)
    live = np.ones(C, bool)
    live[rng.integers(0, n, 20)] = False  # folded-filter mask
    (av, ap, an, at), (bv, bp, bn, bt) = _run_both(
        sk, ss, sd, e, cur, n, live, cap=1 << 12)
    assert int(at) == int(bt) and int(an) == int(bn)
    assert np.array_equal(av, bv)
    assert np.array_equal(ap, bp)
    assert int(at) > 0  # the case actually expanded something


def _multiset(v, p, n):
    return sorted(zip(v[:n].tolist(), p[:n].tolist()))


def test_stream_duplicate_anchors_mhot():
    """Multiplicity <= MDUP streams through the m-hot arm: same (val,
    parent) BAG as the XLA emit (edge-repeat vs run-repeat order)."""
    rng = np.random.default_rng(7)
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=64, max_deg=5)
    C = 256
    picks = rng.choice(keys, size=30, replace=False)
    reps = rng.integers(1, 5, size=30)  # multiplicities 1..4
    anchors = np.repeat(picks, reps)
    n = len(anchors)
    cur = np.full(C, INT32_MAX, np.int32)
    cur[:n] = anchors
    live = np.ones(C, bool)
    (av, ap, an, at), (bv, bp, bn, bt) = _run_both(
        sk, ss, sd, e, cur, n, live, cap=1 << 12)
    assert int(at) == int(bt) and int(an) == int(bn)
    assert int(at) > 0
    assert _multiset(av, ap, an) == _multiset(bv, bp, bn)


def test_stream_duplicate_anchors_mhot_off_bitwise():
    """mhot=False restores the XLA fallback: bit-identical on duplicates."""
    from wukong_tpu.engine.tpu_stream import \
        wk_walk_merge_stream_expand as se

    rng = np.random.default_rng(7)
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=64, max_deg=5)
    C = 256
    cur = np.full(C, INT32_MAX, np.int32)
    n = 100
    cur[:n] = rng.choice(keys, size=n, replace=True)  # repeats guaranteed
    cur[1] = cur[0]
    live = np.ones(C, bool)
    a = merge_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                     jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                     jnp.asarray(live), cap_out=1 << 12)
    b = se(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
           jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
           jnp.asarray(live), cap_out=1 << 12, interpret=True, mhot=False)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_stream_high_multiplicity_falls_back_bitwise():
    """Multiplicity > MDUP takes the XLA arm: bit-identical again."""
    from wukong_tpu.engine.tpu_stream import MDUP

    rng = np.random.default_rng(9)
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=64, max_deg=5)
    C = 256
    cur = np.full(C, INT32_MAX, np.int32)
    hot = keys[np.argmax(sd[:64])]
    n = MDUP + 8
    cur[:n] = hot  # one key far beyond the m-hot cap
    live = np.ones(C, bool)
    (av, ap, an, at), (bv, bp, bn, bt) = _run_both(
        sk, ss, sd, e, cur, n, live, cap=1 << 12)
    assert int(at) == int(bt) and int(an) == int(bn)
    assert np.array_equal(av, bv)
    assert np.array_equal(ap, bp)


@pytest.mark.parametrize("seed", range(6))
def test_stream_mhot_fuzz(seed):
    """Randomized duplicate-anchor frontiers (mixed multiplicities 1..MDUP,
    hub degrees, partial live masks, both compaction backends): the m-hot
    bag must equal the XLA emit's bag, totals identical."""
    rng = np.random.default_rng(500 + seed)
    nkeys = int(rng.integers(16, 400))
    max_deg = int(rng.integers(1, 20))
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=nkeys, max_deg=max_deg)
    C = int(rng.choice([256, 1024]))
    npick = int(rng.integers(1, min(C // 4, nkeys) + 1))
    picks = rng.choice(keys, size=npick, replace=False)
    reps = rng.integers(1, 5, size=npick)
    anchors = np.repeat(picks, reps)[: C - 1]
    rng.shuffle(anchors)  # duplicates need not be row-adjacent
    n = len(anchors)
    cur = np.full(C, INT32_MAX, np.int32)
    cur[:n] = anchors
    live = rng.random(C) > rng.random() * 0.4
    mxu = bool(rng.integers(0, 2))
    a = merge_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                     jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                     jnp.asarray(live), cap_out=1 << 13)
    b = stream_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                      jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                      jnp.asarray(live), cap_out=1 << 13, interpret=True,
                      mxu=mxu)
    av, ap, an, at = [np.asarray(x) for x in a]
    bv, bp, bn, bt = [np.asarray(x) for x in b]
    assert int(at) == int(bt) and int(an) == int(bn)
    assert _multiset(av, ap, int(an)) == _multiset(bv, bp, int(bn))


def test_stream_empty_and_all_miss():
    rng = np.random.default_rng(3)
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=50, max_deg=4)
    C = 256
    cur = np.full(C, INT32_MAX, np.int32)
    live = np.ones(C, bool)
    # n = 0
    (_, _, an, at), (_, _, bn, bt) = _run_both(
        sk, ss, sd, e, cur, 0, live, cap=1 << 10)
    assert int(at) == 0 and int(bt) == 0 and int(bn) == 0
    # all misses
    cur[:40] = np.arange(40, dtype=np.int32) + 500_000
    (_, _, an, at), (_, _, bn, bt) = _run_both(
        sk, ss, sd, e, cur, 40, live, cap=1 << 10)
    assert int(at) == 0 and int(bt) == 0


def test_stream_overflow_totals_agree():
    """total > cap_out must be reported identically (the host retry
    signal); emitted values beyond capacity are unused by contract."""
    rng = np.random.default_rng(11)
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=128, max_deg=40)
    C = 256
    cur = np.full(C, INT32_MAX, np.int32)
    cur[:128] = keys
    live = np.ones(C, bool)
    cap = TILE  # tiny capacity to force overflow
    (_, _, an, at), (_, _, bn, bt) = _run_both(
        sk, ss, sd, e, cur, 128, live, cap=cap)
    assert int(at) == int(bt)
    assert int(at) > cap
    assert int(an) == int(bn) == cap


def test_stream_tiny_segment_single_tile():
    """E < TILE pads up to one tile."""
    sk = np.asarray([5, 9, INT32_MAX, INT32_MAX], np.int32)
    ss = np.asarray([0, 3, 0, 0], np.int32)
    sd = np.asarray([3, 2, 0, 0], np.int32)
    e = np.full(8, INT32_MAX, np.int32)
    e[:5] = [10, 11, 12, 20, 21]
    cur = np.full(8, INT32_MAX, np.int32)
    cur[:2] = [9, 5]
    live = np.ones(8, bool)
    (av, ap, an, at), (bv, bp, bn, bt) = _run_both(
        sk, ss, sd, e, cur, 2, live, cap=1 << 10)
    assert int(bt) == 5 and int(bn) == 5
    assert np.array_equal(av, bv) and np.array_equal(ap, bp)
    # key-sorted emission: key 5's run (parent row 1) precedes key 9's
    assert bv[:5].tolist() == [10, 11, 12, 20, 21]
    assert bp[:5].tolist() == [1, 1, 1, 0, 0]


def test_stream_multi_tile_carries():
    """Runs spanning tile boundaries + many tiles exercise the SMEM
    carries and the accumulator flush path."""
    rng = np.random.default_rng(13)
    nkeys = 500
    keys = np.sort(rng.choice(100_000, nkeys, replace=False)).astype(np.int32)
    degs = rng.integers(1, 8, nkeys)
    # one huge run spanning several tiles
    degs[100] = 3 * TILE + 17
    offs = np.concatenate([[0], np.cumsum(degs)])
    E = int(offs[-1])
    edges = rng.integers(0, 2**31 - 1, E, dtype=np.int64).astype(np.int32)
    Kp = 512
    Ep = 1 << int(E - 1).bit_length()
    sk = np.full(Kp, INT32_MAX, np.int32)
    sk[:nkeys] = keys
    ss = np.zeros(Kp, np.int32)
    ss[:nkeys] = offs[:-1]
    sd = np.zeros(Kp, np.int32)
    sd[:nkeys] = degs
    e = np.full(Ep, INT32_MAX, np.int32)
    e[:E] = edges
    C = 1024
    cur = np.full(C, INT32_MAX, np.int32)
    n = 400
    cur[:n] = rng.choice(keys, size=n, replace=False)
    live = np.ones(C, bool)
    (av, ap, an, at), (bv, bp, bn, bt) = _run_both(
        sk, ss, sd, e, cur, n, live, cap=1 << 13)
    assert int(at) == int(bt) and int(an) == int(bn)
    assert np.array_equal(av, bv)
    assert np.array_equal(ap, bp)


def test_mxu_and_vpu_compaction_agree():
    """Both compaction backends (MXU matmul on 16-bit halves vs VPU masked
    reductions) must emit identical results."""
    rng = np.random.default_rng(21)
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=200, max_deg=7)
    C = 512
    cur = np.full(C, INT32_MAX, np.int32)
    n = 180
    cur[:n] = rng.choice(keys, size=n, replace=False)
    live = np.ones(C, bool)
    args = [jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
            jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
            jnp.asarray(live)]
    a = stream_expand(*args, cap_out=1 << 12, interpret=True, mxu=True)
    b = stream_expand(*args, cap_out=1 << 12, interpret=True, mxu=False)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert int(a[3]) > 0


@pytest.mark.parametrize("seed", range(8))
def test_stream_fuzz_random_shapes(seed):
    """Randomized shapes: segment sizes, degree skew (hub runs), frontier
    density, live masks, both compaction backends — all must match the XLA
    emit exactly."""
    rng = np.random.default_rng(100 + seed)
    nkeys = int(rng.integers(8, 600))
    max_deg = int(rng.integers(1, 30))
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=nkeys, max_deg=max_deg)
    C = int(rng.choice([64, 256, 1024]))
    n = int(rng.integers(0, min(C, nkeys) + 1))
    cur = np.full(C, INT32_MAX, np.int32)
    if n:
        cur[:n] = rng.choice(keys, size=n, replace=False)
    live = rng.random(C) > rng.random() * 0.5
    mxu = bool(rng.integers(0, 2))
    a = merge_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                     jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                     jnp.asarray(live), cap_out=1 << 13)
    b = stream_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                      jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                      jnp.asarray(live), cap_out=1 << 13, interpret=True,
                      mxu=mxu)
    assert int(a[3]) == int(b[3]) and int(a[2]) == int(b[2])
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))


@pytest.mark.parametrize("mdup", [2, 8])
def test_stream_mdup_override(mdup):
    """Non-default multiplicity caps: the mdup-scaled accumulator/flush
    logic must stay bag-equal to the XLA emit for multiplicities within
    the cap, and beyond-cap frontiers must still fall back bit-identical."""
    rng = np.random.default_rng(21)
    sk, ss, sd, e, keys, offs = _mk_segment(rng, nkeys=80, max_deg=6)
    C = 512
    picks = rng.choice(keys, size=40, replace=False)
    anchors = np.repeat(picks, mdup)  # multiplicity exactly at the cap
    n = len(anchors)
    cur = np.full(C, INT32_MAX, np.int32)
    cur[:n] = anchors
    live = np.ones(C, bool)
    a = merge_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                     jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                     jnp.asarray(live), cap_out=1 << 13)
    b = stream_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                      jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                      jnp.asarray(live), cap_out=1 << 13, interpret=True,
                      mdup=mdup)
    av, ap, an, at = [np.asarray(x) for x in a]
    bv, bp, bn, bt = [np.asarray(x) for x in b]
    assert int(at) == int(bt) and int(an) == int(bn) and int(at) > 0
    assert _multiset(av, ap, int(an)) == _multiset(bv, bp, int(bn))
    # one past the cap: the XLA arm takes over, bit-identical
    anchors2 = np.repeat(picks[:30], mdup + 1)
    n2 = len(anchors2)
    cur2 = np.full(C, INT32_MAX, np.int32)
    cur2[:n2] = anchors2
    a = merge_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                     jnp.asarray(e), jnp.asarray(cur2), jnp.int32(n2),
                     jnp.asarray(live), cap_out=1 << 13)
    b = stream_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                      jnp.asarray(e), jnp.asarray(cur2), jnp.int32(n2),
                      jnp.asarray(live), cap_out=1 << 13, interpret=True,
                      mdup=mdup)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_stream_mdup_env(monkeypatch):
    from wukong_tpu.engine.tpu_stream import MDUP, stream_mdup

    monkeypatch.delenv("WUKONG_STREAM_MDUP", raising=False)
    assert stream_mdup() == MDUP
    monkeypatch.setenv("WUKONG_STREAM_MDUP", "8")
    assert stream_mdup() == 8
    monkeypatch.setenv("WUKONG_STREAM_MDUP", "bogus")
    assert stream_mdup() == MDUP
    monkeypatch.setenv("WUKONG_STREAM_MDUP", "99")
    assert stream_mdup() == 16  # clamped
