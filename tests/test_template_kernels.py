"""The whole-plan template program's device kernels against their NumPy
oracles (ISSUE 27): ``expand_padded_device`` finds the row of each slot by
scatter + running maximum, ``lookup_ranges_device`` a key's slot by a table
over the id range where ``direct_lookup_wins`` says so and by the search
elsewhere. Every output slot is compared, padding included: a
validity-compacted table has to stay byte-identical to the host expansion,
and the capacity regrowth reads ``total`` and ``overflow``. Runs on the CPU
backend; the chip's numbers are in PERF.md. And the class those programs
are sized by (ISSUE 31): ``capacity_class``, eighths of an octave from
8,192 rows, with both kernels at classes that are no power of two.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wukong_tpu.engine.template_compile import _build_program
from wukong_tpu.join.kernels import (
    CLASS_FINE_FROM,
    capacity_class,
    direct_lookup_wins,
    expand_padded,
    expand_padded_device,
    lookup_ranges,
    lookup_ranges_device,
    pad_pow2,
    pair_member,
)

pytestmark = pytest.mark.template

EDGES = np.arange(100, 164, dtype=np.int32)  # 64 edge values

# name -> (start, deg, edges, out_cap)
EXPAND_CASES = {
    "plain": ([0, 2, 5, 9], [2, 3, 4, 1], EDGES, 16),
    "zero_rows_at_start": ([0, 0, 3, 7], [0, 0, 4, 2], EDGES, 16),
    "zero_rows_in_the_middle": ([0, 3, 3, 3, 8], [3, 0, 0, 5, 2], EDGES, 16),
    "zero_rows_at_the_end": ([4, 9, 0, 0], [5, 3, 0, 0], EDGES, 16),
    "zero_rows_everywhere": ([0, 1, 0, 6, 0, 0, 20, 0],
                             [0, 1, 0, 4, 0, 0, 3, 0], EDGES, 16),
    "all_rows_masked": ([3, 7, 11, 2], [0, 0, 0, 0], EDGES, 16),
    "total_equals_out_cap": ([0, 10, 30], [8, 4, 4], EDGES, 16),
    "total_equals_out_cap_then_zero_rows": ([0, 10, 0, 0], [8, 8, 0, 0],
                                            EDGES, 16),
    "total_over_out_cap": ([0, 10, 30, 40], [8, 6, 9, 5], EDGES, 16),
    "one_row_fills_the_table": ([5, 0, 0, 0], [16, 0, 0, 0], EDGES, 16),
    "last_row_fills_the_table": ([0, 0, 0, 40], [0, 0, 0, 16], EDGES, 16),
    "one_row_one_slot": ([7], [1], EDGES, 8),
    "more_rows_than_slots": (list(range(32)), [1, 0] * 16, EDGES, 8),
    "empty_edge_array": ([0, 0, 0], [0, 0, 0], np.zeros(0, np.int32), 8),
}


def _i32(x):
    return np.asarray(x, dtype=np.int32)


@pytest.mark.parametrize("name", sorted(EXPAND_CASES))
def test_expand_device_equals_numpy_in_every_slot(name):
    start, deg, edges, cap = EXPAND_CASES[name]
    start, deg = _i32(start), _i32(deg)
    want = expand_padded(start, deg, edges, cap)
    got = jax.jit(expand_padded_device, static_argnums=3)(
        jnp.asarray(start), jnp.asarray(deg), jnp.asarray(edges), cap)
    for w, g, what in zip(want, got, ("row", "values", "valid", "total",
                                      "overflow")):
        assert np.array_equal(np.asarray(w), np.asarray(g)), what
    total = int(np.sum(deg))
    assert int(got[3]) == total and bool(got[4]) == (total > cap)
    # under ``valid`` no slot holds a wrong row: the compacted table is
    # the host's np.repeat expansion, cut at the capacity
    live = np.asarray(got[2])
    assert np.array_equal(np.asarray(got[0])[live],
                          np.repeat(np.arange(len(deg)), deg)[:cap])


@pytest.mark.parametrize("seed", range(6))
def test_expand_device_equals_numpy_on_random_degrees(seed):
    rng = np.random.default_rng(seed)
    n, cap = 256, 1024
    deg = _i32(rng.integers(0, 9, n) * (rng.random(n) < 0.6))
    edges = _i32(rng.integers(0, 1 << 20, 4096))
    start = _i32(rng.integers(0, len(edges) - 8, n))
    want = expand_padded(start, deg, edges, cap)
    got = expand_padded_device(jnp.asarray(start), jnp.asarray(deg),
                               jnp.asarray(edges), cap)
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))


def test_expand_device_flags_a_sum_that_wraps_int32():
    """2^31 and more rows in all: the int32 running sum steps down, the
    scatter is given nothing to mark, and ``overflow`` is up (by the sign
    of the wrapped total or by the float shadow sum)."""
    deg = _i32([1 << 30, 1 << 30, 1 << 30, 5])
    got = expand_padded_device(jnp.zeros(4, jnp.int32), jnp.asarray(deg),
                               jnp.asarray(EDGES), 16)
    assert bool(got[4])
    assert np.asarray(got[0]).shape == (16,)


KEYS = _i32([5, 6, 9, 20, 21, 40])
OFFSETS = _i32([0, 2, 3, 7, 8, 8, 12])  # key 21 has no edge

LOOKUP_CASES = {
    "present": [5, 6, 9, 20, 21, 40],
    "absent_below_the_keys": [0, 1, 4],
    "absent_between_the_keys": [7, 8, 10, 19, 22, 39],
    "absent_above_the_keys": [41, 42, 1000],
    "past_the_tables_range": [41, 1 << 20, (1 << 31) - 1, -1, -(1 << 31)],
    "padded_rows": [0, 0, 0, 0],
    "mixed_and_repeated": [40, 5, 5, 7, 40, 0, 21, 99, 9],
}


def _rows(vids, rows):
    """``vids`` tiled up to ``rows`` rows: the rule reads the row count."""
    return _i32(np.resize(_i32(vids), rows))


@pytest.mark.parametrize("form", ["direct", "search"])
@pytest.mark.parametrize("name", sorted(LOOKUP_CASES))
def test_lookup_device_equals_numpy(name, form):
    # one row does not pay for a table of 4096 slots; 64 rows pay for 41
    rows, bound = (64, int(KEYS[-1]) + 1) if form == "direct" else (1, 4096)
    assert direct_lookup_wins(rows, len(KEYS), bound) == (form == "direct")
    vids = _rows(LOOKUP_CASES[name], rows)
    want = lookup_ranges(KEYS, OFFSETS, vids)
    got = jax.jit(lookup_ranges_device, static_argnums=3)(
        jnp.asarray(KEYS), jnp.asarray(OFFSETS), jnp.asarray(vids), bound)
    assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
    assert np.array_equal(np.asarray(want[1]), np.asarray(got[1]))


@pytest.mark.parametrize("rows", [1, 64])
def test_lookup_device_on_an_empty_segment(rows):
    vids = _rows([0, 5, 99], rows)
    got = lookup_ranges_device(jnp.zeros(0, jnp.int32),
                               jnp.zeros(1, jnp.int32), jnp.asarray(vids), 0)
    assert not np.asarray(got[0]).any() and not np.asarray(got[1]).any()


@pytest.mark.parametrize("seed", range(4))
def test_lookup_and_pair_member_device_on_random_segments(seed):
    """A random CSR, both forms: (start, degree) and the edge probe that
    rides on them (``pair_member`` with the id bound staged)."""
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(100, 5000, 700)).astype(np.int32)
    deg = rng.integers(0, 6, len(keys))
    offsets = _i32(np.concatenate([[0], np.cumsum(deg)]))
    edges = _i32(np.concatenate(
        [np.sort(rng.choice(6000, d, replace=False)) for d in deg]))
    bound = int(keys[-1]) + 1
    for rows in (8, 4096):
        vids = _i32(rng.integers(0, 5200, rows))
        vals = _i32(rng.integers(0, 6000, rows))
        hit = rng.random(rows) < 0.5  # half the probes name a real edge
        at = rng.integers(0, len(edges), rows)
        owner = np.searchsorted(offsets, at, side="right") - 1
        vids[hit], vals[hit] = keys[owner[hit]], edges[at[hit]]
        want = lookup_ranges(keys, offsets, vids)
        got = lookup_ranges_device(jnp.asarray(keys), jnp.asarray(offsets),
                                   jnp.asarray(vids), bound)
        assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
        assert np.array_equal(np.asarray(want[1]), np.asarray(got[1]))
        wantm = pair_member(keys, offsets, edges, vids, vals)
        gotm = pair_member(jnp.asarray(keys), jnp.asarray(offsets),
                           jnp.asarray(edges), jnp.asarray(vids),
                           jnp.asarray(vals), xp=jnp, depth=4,
                           id_bound=bound)
        assert wantm.any() and np.array_equal(wantm, np.asarray(gotm))


@pytest.mark.parametrize("rows,nkeys,bound,direct", [
    (1024, 13_937_249, 14_068_321, False),   # a light frontier: search
    (1024, 1 << 16, 1 << 17, False),
    (1 << 17, 465_905, 14_068_321, True),    # q7's first step at LUBM-640
    (1 << 20, 13_742_830, 14_068_321, True),  # q2
    (1 << 21, 13_937_249, 14_068_321, True),  # q7, the type segment
    (1 << 23, 6_759_416, 14_068_321, True),
    (1 << 12, 1 << 13, 1 << 14, True),
    (1, 6, 4096, False),
    (64, 0, 0, False),                       # an empty segment: no table
])
def test_the_shape_rule(rows, nkeys, bound, direct):
    assert direct_lookup_wins(rows, nkeys, bound) is direct


def _lowered(spec, caps, depths, bounds, nkeys, nedges):
    """StableHLO text of one template program over abstract operands."""
    def i(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = [i(caps[0]), i()]
    for op in spec[1:]:
        args += [i(nkeys), i(nkeys + 1), i(nedges)]
        if op[0] == "filter_pair_const":
            args.append(i())
    fn, forms = _build_program(spec, caps, depths, bounds, None)
    return fn.lower(*args).as_text(), forms


Q7_LIKE = (("index", 19, 0), ("expand", 13, 1, 0),
           ("filter_pair_const", 1, 1, 1, 25), ("filter_pair", 12, 1, 1, 0))


def test_a_heavy_shaped_program_holds_no_loop():
    """Caps (2^12, 2^14) over segments of 2^13 keys: every lookup takes
    the table, the row of each slot comes from scatter + cummax, and the
    program holds no ``while`` (the binary searches were 53 % of the
    heavy cell's device time: ledger, PR 26)."""
    text, forms = _lowered(Q7_LIKE, (1 << 12, 1 << 14), (3, 3),
                           (1 << 14,) * 3, 1 << 13, 1 << 15)
    assert forms == [True, True, True]
    assert "while" not in text
    assert "scatter" in text


def test_a_light_shaped_program_keeps_the_search():
    """1024 rows over 2^16 keys: a table of the id range would cost more
    than the 17 rounds it saves, so the lookups search; the expansion's
    row-of-slot is the scatter all the same."""
    text, forms = _lowered(Q7_LIKE, (1024, 1024), (3, 3),
                           (1 << 17,) * 3, 1 << 16, 1 << 18)
    assert forms == [False, False, False]
    assert "stablehlo.while" in text


# ---------------------------------------------------------------------------
# the template programs' capacity class (ISSUE 31)
# ---------------------------------------------------------------------------

# sizes around every boundary of the rule, the sizes PERF.md names (C3's
# last expansion, q7's, q2's start list), and the cap
CLASS_SIZES = [0, 1, 2, 3, 1000, 1023, 1024, 1025, 4097, 8191, 8192, 8193,
               9215, 9216, 9217, 16383, 16384, 16385, 18432, 18433, 110_020,
               400_120, 698_900, 2_111_876, 8_454_803, (1 << 23) + 1,
               (1 << 24) - 1, (1 << 25) - 1, 1 << 25]


@pytest.mark.parametrize("n", CLASS_SIZES)
def test_capacity_class_holds_n_in_eighths_of_an_octave(n):
    c = capacity_class(n, floor=1)
    assert c >= max(n, 1)
    if n <= CLASS_FINE_FROM:
        assert c == pad_pow2(n, floor=1)  # a power of two, as before
    else:
        assert c % 1024 == 0
        assert c - n < n / 8  # at most 12.5 % over, where it was 100 %
        lower = pad_pow2(n, floor=1) // 2  # 2^k < n <= 2^(k+1)
        assert c % (lower // 8) == 0 and lower < c <= 2 * lower
        assert c <= pad_pow2(n, floor=1)
    assert capacity_class(c, floor=1) == c  # a class is its own class
    assert capacity_class(2 * c, floor=1) == 2 * c  # and so is its double


@pytest.mark.parametrize("k", range(0, 26))
def test_capacity_class_equals_pad_pow2_at_powers_of_two(k):
    assert capacity_class(1 << k, floor=1) == 1 << k == pad_pow2(1 << k, 1)
    assert capacity_class(1 << k) == pad_pow2(1 << k)  # the default floor


@pytest.mark.parametrize("lo,hi", [(1, 3000), (8000, 9300), (16000, 20600),
                                   ((1 << 20) - 40, (1 << 20) + 300_000)])
def test_capacity_class_is_monotone(lo, hi):
    step = max((hi - lo) // 3000, 1)
    classes = [capacity_class(n, floor=1) for n in range(lo, hi, step)]
    assert classes == sorted(classes)
    if hi > CLASS_FINE_FROM:
        assert len(set(classes)) > 2


@pytest.mark.parametrize("n,floor,cap_max,want", [
    (5, 1024, None, 1024),               # the floor, as pad_pow2's
    (5, 64, None, 64),
    (9000, 16384, None, 16384),
    (9000, 10_000, None, 10_240),        # a floor is rounded up as a size is
    (8_454_803, 1024, 1 << 25, 9 << 20),  # C3's last expansion at WatDiv
    (8_454_803, 1024, 9_000_000, 9_000_000),  # never above the cap
    ((1 << 25) + 1, 1024, 1 << 25, 1 << 25),
    ((1 << 25) + 1, 1024, None, 36 << 20),
    (100, 1024, 512, 512),
])
def test_capacity_class_floor_and_cap(n, floor, cap_max, want):
    assert capacity_class(n, floor, cap_max) == want


# (rows in, class out): no power of two, 9 x 1,024 and 11 x 2,048
ODD_CLASSES = [(700, 9 * 1024), (2816, 11 * 2048)]


@pytest.mark.parametrize("fill", ["under", "exact", "over"])
@pytest.mark.parametrize("n,cap", ODD_CLASSES)
def test_expand_device_equals_numpy_at_a_class_that_is_no_power_of_two(
        n, cap, fill):
    """Every slot of all five outputs at an ``out_cap`` of 9,216 and of
    22,528 rows, the total under it, on it and over it: a class is only
    padding, whatever its size."""
    assert capacity_class(cap, floor=1) == cap and cap & (cap - 1)
    rng = np.random.default_rng(cap)
    deg = _i32(rng.integers(0, 2 * (cap // n) - 1, n))
    want_total = {"under": int(deg.sum()), "exact": cap,
                  "over": cap + 17}[fill]
    deg[-1] += want_total - int(deg.sum())
    assert deg[-1] >= 0 and (fill != "under" or want_total < cap)
    edges = _i32(rng.integers(0, 1 << 20, 1 << 16))
    start = _i32(rng.integers(0, len(edges) - int(deg.max()), n))
    want = expand_padded(start, deg, edges, cap)
    got = jax.jit(expand_padded_device, static_argnums=3)(
        jnp.asarray(start), jnp.asarray(deg), jnp.asarray(edges), cap)
    for w, g, what in zip(want, got, ("row", "values", "valid", "total",
                                      "overflow")):
        assert np.array_equal(np.asarray(w), np.asarray(g)), what
    assert np.asarray(got[0]).shape == (cap,)
    assert int(got[3]) == want_total and bool(got[4]) == (fill == "over")


@pytest.mark.parametrize("form", ["direct", "search"])
@pytest.mark.parametrize("rows", [c for _n, c in ODD_CLASSES])
def test_lookup_device_equals_numpy_at_a_class_that_is_no_power_of_two(
        rows, form):
    """(start, degree) of every row of a frontier of 9,216 and of 22,528
    rows, by the table and by the search."""
    rng = np.random.default_rng(rows)
    nkeys = 3000 if form == "direct" else 1 << 21
    keys = np.unique(rng.integers(100, 8 * nkeys, nkeys)).astype(np.int32)
    bound = int(keys[-1]) + 1
    assert direct_lookup_wins(rows, len(keys), bound) == (form == "direct")
    offsets = _i32(np.concatenate(
        [[0], np.cumsum(rng.integers(0, 6, len(keys)))]))
    vids = _i32(rng.integers(0, bound + 100, rows))
    hit = rng.random(rows) < 0.5
    vids[hit] = keys[rng.integers(0, len(keys), int(hit.sum()))]
    want = lookup_ranges(keys, offsets, vids)
    got = jax.jit(lookup_ranges_device, static_argnums=3)(
        jnp.asarray(keys), jnp.asarray(offsets), jnp.asarray(vids), bound)
    assert np.asarray(want[1]).any()
    assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
    assert np.array_equal(np.asarray(want[1]), np.asarray(got[1]))
