"""Adversarial interpret-mode fuzz for the Pallas streaming kernels
(round-4 verdict #7): the edges HARDWARE will hit, pinned as stream ==
merge_expand equality BEFORE the first real-Mosaic run. Families:

- capacity overflow landing mid-tile / exactly at the flush boundary
- runs straddling tile boundaries (deg == TILE, TILE±1, k*TILE+r)
- duplicate-anchor multiplicity exactly mdup (m-hot arm) and mdup+1
  (in-cond XLA fallback) for every supported cap
- edge/key values adjacent to the INT32_MAX pad sentinel
- empty/degenerate segments and frontiers (0 keys, all-zero degrees,
  n == 0, n == C, all-dead live mask, all-miss anchors)

Every case asserts identical (total, out_n) and bag equality of
(val, parent); distinct-anchor and beyond-mdup cases (XLA arm) assert
bitwise equality too. `_emit_kernel_m`'s nblk multi-flush loop is the
subtlest code in the repo — these are its regression armor.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from wukong_tpu.engine.tpu_kernels import INT32_MAX  # noqa: E402
from wukong_tpu.engine.tpu_kernels import \
    wk_walk_merge_expand as merge_expand  # noqa: E402
from wukong_tpu.engine.tpu_stream import MDUP, TILE  # noqa: E402
from wukong_tpu.engine.tpu_stream import \
    wk_walk_merge_stream_expand as stream_expand  # noqa: E402


def _segment(keys, degs, edge_fn=None, rng=None):
    """Staged MergeSegment arrays from explicit keys/degrees. edge_fn(i)
    gives the i-th edge value (default: random legal ids)."""
    keys = np.asarray(keys, np.int64)
    degs = np.asarray(degs, np.int64)
    offs = np.concatenate([[0], np.cumsum(degs)])
    ne = int(offs[-1])
    if edge_fn is None:
        rng = rng or np.random.default_rng(0)
        edges = rng.integers(0, 2**31 - 2, size=ne, dtype=np.int64)
    else:
        edges = np.asarray([edge_fn(i) for i in range(ne)], np.int64)
    Kp = 1 << max(int(max(len(keys), 1) - 1).bit_length(), 1)
    Ep = 1 << max(int(max(ne, 1) - 1).bit_length(), 3)
    sk = np.full(Kp, INT32_MAX, np.int32)
    sk[: len(keys)] = keys
    ss = np.zeros(Kp, np.int32)
    ss[: len(keys)] = offs[:-1]
    sd = np.zeros(Kp, np.int32)
    sd[: len(keys)] = degs
    e = np.full(Ep, INT32_MAX, np.int32)
    e[:ne] = edges
    return sk, ss, sd, e


def _frontier(anchors, C, live=None):
    anchors = np.asarray(anchors, np.int64)
    n = len(anchors)
    cur = np.full(C, INT32_MAX, np.int32)
    cur[:n] = anchors
    lv = np.ones(C, bool) if live is None else np.asarray(live, bool)
    return cur, n, lv


def _check(sk, ss, sd, e, cur, n, live, cap, mdup=MDUP, mxu=None,
           expect_bitwise=False):
    a = merge_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                     jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                     jnp.asarray(live), cap_out=cap)
    b = stream_expand(jnp.asarray(sk), jnp.asarray(ss), jnp.asarray(sd),
                      jnp.asarray(e), jnp.asarray(cur), jnp.int32(n),
                      jnp.asarray(live), cap_out=cap, interpret=True,
                      mdup=mdup, mxu=mxu)
    av, ap, an, at = [np.asarray(x) for x in a]
    bv, bp, bn, bt = [np.asarray(x) for x in b]
    assert int(at) == int(bt), f"totals {int(at)} != {int(bt)}"
    assert int(an) == int(bn), f"out_n {int(an)} != {int(bn)}"
    k = int(an)
    if expect_bitwise:
        assert np.array_equal(av, bv) and np.array_equal(ap, bp)
    elif int(at) <= cap:
        assert (sorted(zip(av[:k].tolist(), ap[:k].tolist()))
                == sorted(zip(bv[:k].tolist(), bp[:k].tolist())))
    # else: duplicate-anchor OVERFLOW — the m-hot arm (edge-repeat order)
    # and the XLA emit (run-repeat order) truncate DIFFERENT prefixes of
    # the same bag; emitted content beyond-capacity is discarded by
    # contract (the host retries at exact capacity), so only the totals
    # comparison above is meaningful
    return int(at), k


# ---------------------------------------------------------------------------
# A. capacity overflow mid-tile / at the flush boundary
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cap_tiles,deg,extra", [
    (1, 7, 3), (1, TILE - 1, 5), (1, 3 * TILE + 17, 0),
    (2, 13, 9), (2, TILE, 1), (4, TILE // 2 + 1, 2),
    (4, 2 * TILE + 3, 0), (8, 61, 50),
], ids=lambda v: str(v))
def test_overflow_mid_tile(cap_tiles, deg, extra):
    """total > cap with the cutoff landing inside a tile and inside a run:
    totals must agree exactly (the host retry signal) and the first `cap`
    outputs must be the same bag."""
    nkeys = 40
    keys = np.arange(10, 10 + nkeys)
    degs = np.full(nkeys, deg)
    if extra:
        degs[nkeys // 2] += extra  # make the cap boundary land mid-run
    sk, ss, sd, e = _segment(keys, degs)
    cur, n, live = _frontier(keys, C=64)
    cap = cap_tiles * TILE
    total, k = _check(sk, ss, sd, e, cur, n, live, cap,
                      expect_bitwise=True)
    assert total > cap and k == cap  # genuinely overflowed mid-stream


@pytest.mark.parametrize("delta", [-1, 0, 1], ids=["cap-1", "cap", "cap+1"])
def test_total_at_flush_boundary(delta):
    """total exactly at / one off the capacity: the last flush block is
    full, exactly empty, or one element over."""
    cap = 2 * TILE
    want_total = cap + delta
    keys = np.arange(5, 5 + 8)
    degs = np.full(8, want_total // 8)
    degs[-1] += want_total - int(degs.sum())
    sk, ss, sd, e = _segment(keys, degs)
    cur, n, live = _frontier(keys, C=16)
    total, k = _check(sk, ss, sd, e, cur, n, live, cap, expect_bitwise=True)
    assert total == want_total and k == min(cap, want_total)


# ---------------------------------------------------------------------------
# B. runs straddling tile boundaries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("degs", [
    [TILE, TILE, TILE],                     # runs exactly tile-aligned
    [TILE - 1, 2, TILE - 1, 2],             # every run crosses a boundary
    [1, TILE, 1, TILE, 1],                  # alternation re-misaligns
    [3 * TILE + 17, 5],                     # one run spans >3 tiles
    [TILE // 2] * 7,                        # half-tile phase walk
    [2 * TILE, 1, 2 * TILE - 1],            # mixed large spans
], ids=["aligned", "minus1", "alt", "giant", "half", "mixed"])
def test_runs_straddle_tiles(degs):
    keys = np.arange(100, 100 + len(degs))
    sk, ss, sd, e = _segment(keys, degs)
    cur, n, live = _frontier(keys, C=16)
    cap = 1 << max(int(sum(degs) - 1).bit_length(), 9)
    total, _ = _check(sk, ss, sd, e, cur, n, live, cap, expect_bitwise=True)
    assert total == sum(degs)


@pytest.mark.parametrize("seed", range(6))
def test_straddle_fuzz_partial_live(seed):
    """Random tile-hostile degree mixes with dead rows in the frontier."""
    rng = np.random.default_rng(900 + seed)
    nkeys = int(rng.integers(8, 60))
    degs = rng.choice([1, 2, TILE - 1, TILE, TILE + 1, TILE // 2 + 1],
                      size=nkeys)
    keys = np.sort(rng.choice(50_000, nkeys, replace=False))
    sk, ss, sd, e = _segment(keys, degs, rng=rng)
    live = rng.random(128) > 0.3
    cur, n, _ = _frontier(keys[: min(nkeys, 127)], C=128)
    cap = 1 << max(int(max(int(degs.sum()), 1) - 1).bit_length(), 9)
    _check(sk, ss, sd, e, cur, n, live, cap, expect_bitwise=True)


# ---------------------------------------------------------------------------
# C. multiplicity exactly mdup (m-hot) and mdup+1 (in-cond fallback)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mdup", [1, 2, 4, 8])
@pytest.mark.parametrize("off", [0, 1], ids=["at-cap", "over-cap"])
def test_multiplicity_at_mdup_boundary(mdup, off):
    """m = mdup streams through the m-hot plane; m = mdup+1 must take the
    XLA arm (bitwise). Both bags must match merge_expand."""
    rng = np.random.default_rng(42 + mdup)
    nkeys = 24
    keys = np.arange(50, 50 + nkeys)
    degs = rng.integers(1, 9, nkeys)
    sk, ss, sd, e = _segment(keys, degs, rng=rng)
    m = mdup + off
    anchors = np.repeat(keys[:10], m)
    rng.shuffle(anchors)
    cur, n, live = _frontier(anchors, C=256)
    total, _ = _check(sk, ss, sd, e, cur, n, live, cap=1 << 11, mdup=mdup,
                      expect_bitwise=(off == 1))
    assert total == int(degs[:10].sum()) * m


@pytest.mark.parametrize("mdup", [2, 4])
@pytest.mark.parametrize("mxu", [False, True], ids=["vpu", "mxu"])
def test_mixed_multiplicities_under_mdup(mdup, mxu):
    """Multiplicities 1..mdup mixed in one frontier, both compaction
    backends, overflow engaged (cap < total) — the m-hot accumulator's
    multi-block flush under pressure."""
    rng = np.random.default_rng(77 * mdup + int(mxu))
    nkeys = 32
    keys = np.arange(1000, 1000 + nkeys)
    degs = rng.integers(1, 2 * TILE // 8, nkeys)
    sk, ss, sd, e = _segment(keys, degs, rng=rng)
    reps = (np.arange(nkeys) % mdup) + 1
    anchors = np.repeat(keys, reps)
    rng.shuffle(anchors)
    cur, n, live = _frontier(anchors[:255], C=256)
    _check(sk, ss, sd, e, cur, n, live, cap=TILE, mdup=mdup, mxu=mxu)


# ---------------------------------------------------------------------------
# D. values adjacent to the INT32_MAX pad sentinel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("val", [INT32_MAX - 1, INT32_MAX - 2],
                         ids=["max-1", "max-2"])
def test_edge_values_near_sentinel(val):
    """Legal edge values one off the padding sentinel must be emitted, not
    confused with padding."""
    keys = [7, 9]
    sk, ss, sd, e = _segment(keys, [3, 2],
                             edge_fn=lambda i: val - (i % 2))
    cur, n, live = _frontier(keys, C=8)
    total, k = _check(sk, ss, sd, e, cur, n, live, cap=TILE,
                      expect_bitwise=True)
    assert total == 5 and k == 5


def test_key_values_near_sentinel():
    """Segment keys adjacent to INT32_MAX: lookup and run selection must
    not treat them as pad keys."""
    keys = [INT32_MAX - 3, INT32_MAX - 2]
    sk, ss, sd, e = _segment(keys, [4, 3])
    cur, n, live = _frontier([INT32_MAX - 2, INT32_MAX - 3, 5], C=8)
    total, _ = _check(sk, ss, sd, e, cur, n, live, cap=TILE,
                      expect_bitwise=True)
    assert total == 7


def test_anchor_values_near_sentinel_miss():
    """Anchors near the sentinel that MISS the segment must emit nothing
    (no accidental pad-row match)."""
    sk, ss, sd, e = _segment([10, 20], [2, 2])
    cur, n, live = _frontier([INT32_MAX - 1, INT32_MAX - 2], C=8)
    total, k = _check(sk, ss, sd, e, cur, n, live, cap=TILE,
                      expect_bitwise=True)
    assert total == 0 and k == 0


# ---------------------------------------------------------------------------
# E. empty / degenerate segments and frontiers
# ---------------------------------------------------------------------------
def test_zero_key_segment():
    sk, ss, sd, e = _segment([], [])
    cur, n, live = _frontier([1, 2, 3], C=8)
    total, k = _check(sk, ss, sd, e, cur, n, live, cap=TILE,
                      expect_bitwise=True)
    assert total == 0 and k == 0


def test_all_zero_degrees():
    sk, ss, sd, e = _segment([5, 6, 7], [0, 0, 0])
    cur, n, live = _frontier([5, 6, 7], C=8)
    total, k = _check(sk, ss, sd, e, cur, n, live, cap=TILE,
                      expect_bitwise=True)
    assert total == 0 and k == 0


def test_zero_frontier_nonempty_segment():
    sk, ss, sd, e = _segment([5, 6], [3, 3])
    cur, n, live = _frontier([], C=8)
    total, k = _check(sk, ss, sd, e, cur, n, live, cap=TILE,
                      expect_bitwise=True)
    assert total == 0 and k == 0


def test_all_dead_live_mask():
    sk, ss, sd, e = _segment([5, 6], [3, 3])
    cur, n, live = _frontier([5, 6], C=8, live=np.zeros(8, bool))
    total, k = _check(sk, ss, sd, e, cur, n, live, cap=TILE,
                      expect_bitwise=True)
    assert total == 0 and k == 0


def test_full_frontier_no_pad_rows():
    """n == C: no padding rows at all in the frontier."""
    rng = np.random.default_rng(5)
    keys = np.arange(100, 164)
    sk, ss, sd, e = _segment(keys, rng.integers(1, 6, 64), rng=rng)
    cur, n, live = _frontier(keys, C=64)
    assert n == 64
    _check(sk, ss, sd, e, cur, n, live, cap=1 << 9, expect_bitwise=True)


def test_single_row_single_edge():
    sk, ss, sd, e = _segment([5], [1], edge_fn=lambda i: 42)
    cur, n, live = _frontier([5], C=8)
    total, k = _check(sk, ss, sd, e, cur, n, live, cap=TILE,
                      expect_bitwise=True)
    assert total == 1 and k == 1


# ---------------------------------------------------------------------------
# F. randomized adversarial mixes (everything at once)
# ---------------------------------------------------------------------------
def _mix_case(seed: int):
    """One randomized adversarial mix: tile-hostile degrees,
    sentinel-adjacent values, duplicate anchors at random multiplicity,
    partial live, caps at/below total, random mdup, both backends. Shared
    by the fuzzer and the seed-pinned regression tests so a pinned seed
    keeps reproducing ITS scenario even if either test's assertions
    change (the draw sequence lives here and only here)."""
    rng = np.random.default_rng(7000 + seed)
    nkeys = int(rng.integers(4, 80))
    degs = rng.choice([0, 1, 2, TILE - 1, TILE, TILE + 1, 37], size=nkeys,
                      p=[.1, .2, .2, .1, .1, .1, .2])
    keys = np.sort(rng.choice(
        np.concatenate([np.arange(1, 60_000),
                        np.array([INT32_MAX - 2, INT32_MAX - 3])]),
        nkeys, replace=False))
    big = rng.integers(0, 2**31 - 2, size=max(int(degs.sum()), 1),
                       dtype=np.int64)
    big[rng.integers(0, len(big), size=max(len(big) // 10, 1))] = \
        INT32_MAX - 1
    sk, ss, sd, e = _segment(keys, degs, edge_fn=lambda i: int(big[i]))
    mdup = int(rng.choice([1, 2, 4, 8]))
    m = int(rng.integers(1, mdup + 2))
    npick = int(rng.integers(1, max(nkeys // 2, 2)))
    picks = rng.choice(keys, size=npick, replace=False)
    anchors = np.repeat(picks, m)[:255]
    # sprinkle misses (incl. sentinel-adjacent)
    miss = rng.choice([123_456_789, INT32_MAX - 4], size=min(10, 255), )
    anchors = np.concatenate([anchors, miss])[:255]
    rng.shuffle(anchors)
    C = 256
    live = rng.random(C) > rng.random() * 0.5
    cur, n, _ = _frontier(anchors, C=C)
    cap = int(rng.choice([TILE, 2 * TILE, 1 << 12]))
    mxu = bool(rng.integers(0, 2))
    return dict(keys=keys, degs=degs, sk=sk, ss=ss, sd=sd, e=e, cur=cur,
                n=n, live=live, cap=cap, mdup=mdup, m=m, mxu=mxu)


def _expect_bitwise(keys, degs, cur, n, live, mdup) -> bool:
    """Mirror stream_expand's arm dispatch EXACTLY (tpu_stream.py):

    - `dup` fires on any duplicate LIVE FOUND anchor — key present in the
      segment, degree irrelevant (the kernel's adjacency test runs before
      deg filtering);
    - with duplicates, the m-hot arm runs when `mmax` — the max per-key
      multiplicity over LIVE, MATCHED, deg>0 anchors — is <= mdup.

    Bitwise equality with merge_expand is only promised on the
    distinct-anchor stream arm (no live found duplicate) and on the XLA
    fallback (mmax > mdup); the m-hot arm is bag-order (edge-repeat).
    Live-masking can trim a constructed m > mdup frontier back into m-hot
    range — found by the round-5 fresh-seed soak at seed 7218."""
    deg_of = dict(zip(keys.tolist(), np.asarray(degs).tolist()))
    found_cnt: dict = {}  # live anchors on keys PRESENT in the segment
    run_cnt: dict = {}  # live anchors on keys with deg > 0
    for i in range(int(n)):
        if live[i]:
            a = int(cur[i])
            if a in deg_of:
                found_cnt[a] = found_cnt.get(a, 0) + 1
                if deg_of[a] > 0:
                    run_cnt[a] = run_cnt.get(a, 0) + 1
    dup = max(found_cnt.values(), default=0) >= 2
    mmax = max(run_cnt.values(), default=0)
    return (not dup) or mmax > mdup


@pytest.mark.parametrize("seed", range(10))
def test_adversarial_mix_fuzz(seed):
    """Randomized adversarial mixes (everything at once); the bitwise-vs-
    bag expectation mirrors the kernel's actual arm dispatch."""
    c = _mix_case(seed)
    _check(c["sk"], c["ss"], c["sd"], c["e"], c["cur"], c["n"], c["live"],
           c["cap"], mdup=c["mdup"], mxu=c["mxu"],
           expect_bitwise=_expect_bitwise(
               c["keys"], c["degs"], c["cur"], c["n"], c["live"],
               c["mdup"]))


def test_live_masked_multiplicity_takes_mhot_arm():
    """Soak regression (seed 7218): anchors constructed at multiplicity 3
    with mdup=2, but live-masking leaves max TWO live copies per key — the
    kernel takes the m-hot arm (bag semantics), and the old assumption
    that constructed m > mdup implies the bitwise XLA fallback is wrong.
    Overflow additionally makes the two arms truncate different prefixes,
    which only the totals contract covers."""
    c = _mix_case(218)
    assert c["m"] > c["mdup"]  # the trap: constructed mult says fallback..
    bw = _expect_bitwise(c["keys"], c["degs"], c["cur"], c["n"], c["live"],
                         c["mdup"])
    assert not bw  # ...but the effective live multiplicity says m-hot
    total, k = _check(c["sk"], c["ss"], c["sd"], c["e"], c["cur"], c["n"],
                      c["live"], c["cap"], mdup=c["mdup"], mxu=c["mxu"],
                      expect_bitwise=bw)
    assert total > c["cap"]  # the overflow half of the scenario is real
