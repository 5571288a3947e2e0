"""Sorted-array join primitives for the WCOJ executor.

Every kernel is written against a swappable array module ``xp`` (NumPy by
default): the control flow is branch-free with statically-bounded loops, so
the SAME functions trace and JIT-compile under XLA with ``xp=jax.numpy``
(TrieJax's observation that LFTJ's per-level work is sorted search +
gather). The host path runs them as plain NumPy; the device path wraps them
in ``jax.jit``. That they trace does not make them fast on the chip: a
sorted search is ``log2(N)`` dependent gather rounds a row, and a gather is
the slowest thing a TPU does an element (the readings are beside
``DIRECT_NS``). Where a device program searched a dense integer domain it
addresses the domain instead, by one rule on static shapes
(:func:`direct_lookup_wins`): the whole-plan template programs
(:func:`expand_padded_device`, :func:`lookup_ranges_device`) and, in every
level on the join's device route, its two programs: :func:`jit_level_ranges`
(the prefix rows' anchors looked up by :func:`lookup_ranges_device`, once a
level) and :func:`jit_level_probe` (the candidates expanded, probed and
compacted on the chip, the class list by :func:`member_sorted_device`),
with the NumPy forms as their parity oracle.

Data model: adjacency is the store's CSR triplet (sorted unique ``keys``,
``offsets``, ``edges`` sorted within each key run); candidate sets are
sorted 1-D id arrays. Intersection = membership mask via vectorized binary
search; ragged per-row probes = fixed-iteration branchless lower_bound over
each row's [start, end) edge range.
"""

from __future__ import annotations

import sys

import numpy as np

from wukong_tpu.analysis.lockdep import (
    declare_leaf,
    make_lock,
    register_global_lock,
)


def member_sorted(sorted_arr, vals, xp=np):
    """Boolean mask: is ``vals[i]`` present in ``sorted_arr``?

    One vectorized binary search + one gather. Empty set -> all-False.
    Under jit ``searchsorted`` lowers to its default scan: a ``while`` of
    ``ceil(log2(n + 1))`` rounds, one gather of ``len(vals)`` a round
    (read in the compiled HLO and the chip's trace, PR 27), not to a
    sort-based search.
    """
    n = int(sorted_arr.shape[0])
    if n == 0:
        return xp.zeros(vals.shape[0], dtype=bool)
    idx = xp.searchsorted(sorted_arr, vals)
    idx_c = xp.clip(idx, 0, n - 1)
    return (idx < n) & (sorted_arr[idx_c] == vals)


def intersect_sorted(a, b, xp=np):
    """Sorted intersection of two sorted unique arrays (result stays
    sorted/unique). The smaller side should be ``a`` — the probe cost is
    ``|a| * log |b|``."""
    if a.shape[0] == 0 or b.shape[0] == 0:
        return a[:0]
    return a[member_sorted(b, a, xp=xp)]


def intersect_many(lists, xp=np):
    """Fold-intersect sorted unique arrays, smallest first (leapfrog's
    seek-from-the-shortest-list order). Empty input list -> None."""
    if not lists:
        return None
    out = None
    for arr in sorted(lists, key=lambda t: t.shape[0]):
        out = arr if out is None else intersect_sorted(out, arr, xp=xp)
        if out.shape[0] == 0:
            break
    return out


def lookup_ranges(keys, offsets, vids, xp=np):
    """(start, degree) of each vid's edge range in a CSR (0 when absent)."""
    n = int(keys.shape[0])
    if n == 0:
        z = xp.zeros(vids.shape[0], dtype=np.int64)
        return z, z
    idx = xp.searchsorted(keys, vids)
    idx_c = xp.clip(idx, 0, n - 1)
    found = (idx < n) & (keys[idx_c] == vids)
    start = xp.where(found, offsets[idx_c], 0)
    deg = xp.where(found, offsets[idx_c + 1] - offsets[idx_c], 0)
    return start, deg


def expand_ragged(start: np.ndarray, deg: np.ndarray):
    """(row_idx, flat edge positions) for a ragged per-row expansion.

    deg=[2,0,3] -> row_idx=[0,0,2,2,2], pos=[s0,s0+1,s2,s2+1,s2+2]
    (row indices are ORIGINAL positions — zero-degree rows are skipped,
    never compacted away, so callers may index anchors with row_idx).
    Host-side only (the output length is data-dependent — the device path
    pads to a capacity class instead, like the engine's expand kernels).
    """
    row_idx = np.repeat(np.arange(len(deg)), deg)
    total = int(deg.sum())
    local = np.ones(total, dtype=np.int64)
    if total:
        starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
        nz = deg > 0
        local[starts[nz]] = np.concatenate([[0], 1 - deg[nz][:-1]])
        local = np.cumsum(local)
    return row_idx, start[row_idx] + local


def pair_member(keys, offsets, edges, anchors, vals, xp=np, depth=None,
                id_bound=None):
    """Boolean mask: does edge (anchors[i] -> vals[i]) exist in the CSR?

    Branchless lower_bound over each row's sorted [start, end) edge range,
    iterated a FIXED ``log2(len(edges))+1`` times so the loop unrolls
    statically under XLA tracing (the host pays the same bound — a no-op
    once every row's range has converged). ``depth`` overrides the
    iteration count: each row's range is ONE key's edge run, so
    ``log2(max_degree)+1`` converges every row — the device path passes
    the segment's cached degree bound and cuts the dominant per-iteration
    gather cost by the log(len(edges))/log(max_degree) ratio. ``id_bound``
    (device only: the segment's last key + 1, staged with its tables) lets
    the anchors' key lookup take :func:`lookup_ranges_device`'s direct
    form where its shape rule says so; without it the lookup searches.
    """
    if int(edges.shape[0]) == 0:
        return xp.zeros(anchors.shape[0], dtype=bool)
    if id_bound is None or xp is np:
        start, deg = lookup_ranges(keys, offsets, anchors, xp=xp)
    else:
        start, deg = lookup_ranges_device(keys, offsets, anchors, id_bound)
    return edge_run_member(edges, start, deg, vals, xp=xp, depth=depth)


def edge_run_member(edges, start, deg, vals, xp=np, depth=None,
                    loop: bool = False):
    """Boolean mask: is ``vals[i]`` in the sorted edge run ``[start[i],
    start[i] + deg[i])``? :func:`pair_member` once each row's run is known
    (the join's level program spreads it from the level's ranges).
    ``loop`` (device only) runs the rounds in one ``fori_loop``: the same
    gathers, where each unrolled round adds some 0.35 MB of program text
    at 2^24 slots (compiled for a described v5e)."""
    ne = int(edges.shape[0])
    if ne == 0:
        return xp.zeros(vals.shape[0], dtype=bool)
    # int64 search cursors on the host; under an xp=jnp trace the inputs'
    # own dtype rules (int32 by default, int64 under enable_x64) — an
    # unconditional astype would fight the x64-off config every trace
    lo = start.astype(np.int64) if xp is np else start
    end = (start + deg) if xp is not np else (start + deg).astype(np.int64)
    iters = ne.bit_length() + 1 if depth is None else max(int(depth), 1)

    def halve(_i, run):
        lo, hi = run
        active = lo < hi
        # lo + (hi - lo) // 2, NOT (lo + hi) // 2: the device route runs
        # int32, and lo + hi overflows past 2^30 edges, mis-converging
        # the search (the classic binary-search midpoint bug)
        mid = lo + (hi - lo) // 2
        less = edges[xp.clip(mid, 0, ne - 1)] < vals
        return (xp.where(active & less, mid + 1, lo),
                xp.where(active & ~less, mid, hi))

    if loop and xp is not np:
        import jax

        lo, _hi = jax.lax.fori_loop(0, iters, halve, (lo, end))
    else:
        run = (lo, end)
        for i in range(iters):
            run = halve(i, run)
        lo = run[0]
    inb = lo < end
    return inb & (edges[xp.clip(lo, 0, ne - 1)] == vals)


def jit_kernels():
    """jax.jit-wrapped (member_sorted, pair_member) over jax.numpy — the
    XLA path. Imported lazily so the NumPy fallback never touches jax."""
    import jax
    import jax.numpy as jnp

    def wk_join_member(s, v):
        return member_sorted(s, v, xp=jnp)

    def wk_join_pair(k, o, e, a, v):
        return pair_member(k, o, e, a, v, xp=jnp)

    return jax.jit(wk_join_member), jax.jit(wk_join_pair)


# ---------------------------------------------------------------------------
# the device level path: padded/bucketed candidate tensors through XLA
# ---------------------------------------------------------------------------

#: smallest padded capacity class — tiny dispatches all share one compile
PAD_FLOOR = 1024


def pad_pow2(n: int, floor: int = PAD_FLOOR) -> int:
    """The device path's capacity class: smallest power of two >=
    max(n, floor). Candidate tensors are padded to it so the jitted level
    probe compiles a bounded set of shape variants instead of one per
    level size (the engine's table-capacity-class discipline). Kernels
    that queries share by class keep it; a whole-plan template program,
    whose classes are its own, takes :func:`capacity_class`."""
    c = max(int(n), int(floor), 1)
    return 1 << (c - 1).bit_length()


#: the size from which :func:`capacity_class` steps in eighths of an
#: octave: a step under 1,024 rows would be finer than the chip's tile
CLASS_FINE_FROM = 8192


def capacity_class(n: int, floor: int = PAD_FLOOR,
                   cap_max: int | None = None) -> int:
    """A whole-plan template program's capacity class (the classes of
    ``engine/template_compile.py`` only). Up to 8,192 rows it is
    :func:`pad_pow2`; above, the smallest ``2^k * (1 + j/8)``, j = 1..8,
    that holds ``n``, where ``2^k < n``: a multiple of 1,024 at most 12.5
    % over ``n``, where the power of two is up to 100 % over it and every
    gather and scan of the program is linear in the class. Never above
    ``cap_max`` (``table_capacity_max``) where one is given.

    Finer classes cost a template program no compile: its classes are
    private to it (``_program_key`` holds them). The level probe, the
    walk and the stream kernels are shared between queries by class, and
    there the power of two bounds the set of compiles: they keep
    :func:`pad_pow2`."""
    c = max(int(n), int(floor), 1)
    if c <= CLASS_FINE_FROM:
        c = pad_pow2(c, floor=1)
    else:
        step = (1 << ((c - 1).bit_length() - 1)) >> 3
        c = -(-c // step) * step
    return c if cap_max is None else min(c, int(cap_max))


class DeviceRangeError(ValueError):
    """An array holds values outside int32 — the device path (which runs
    int32 under the default x64-off JAX config) must degrade to host
    rather than silently truncate ids or offsets."""


def to_device_i32(arr):
    """Host int array -> device int32 array, REFUSING (DeviceRangeError)
    any value outside int32 range instead of truncating. Offsets past
    2^31 (a >2G-edge segment) and out-of-range ids therefore degrade the
    query to the host kernels, never to wrong answers; the parity tests
    drive the same kernels in int64 under ``jax.enable_x64``
    to pin >2^31-safe behavior when 64-bit mode is on."""
    import jax.numpy as jnp

    a = np.asarray(arr)
    if len(a) and a.dtype != np.int32:
        # offsets are monotone (last element is the max), id arrays need
        # the real extrema — one pass, paid once per cached table build
        lo, hi = int(a.min()), int(a.max())
        if lo < -(1 << 31) or hi >= (1 << 31):
            raise DeviceRangeError(
                f"values [{lo}, {hi}] exceed int32 — host route required")
    return jnp.asarray(a.astype(np.int32, copy=False))


#: the padded size a level taken in runs of rows is probed at
#: (``join/wcoj.py``: a level of more than ``LEVEL_CHUNK_SLICES`` of these,
#: 2^24 candidates): a generator group is cut into slices of this one size,
#: its last slice at the class of what is left. At LSQB's scale factor 10 a
#: level of the triangle holds 8.0 x 10^7 candidates, which as one
#: ``pad_pow2`` tensor is 2^27 slots and a temporary of that size for each
#: step of the probe, made before the host may take the next run. A smaller
#: level is one call a group at ``pad_pow2`` of its candidates.
LEVEL_SLICE = 1 << 22


def level_slices(n: int) -> list:
    """``[(lo, hi, padded slots)]`` of a generator group of ``n``
    candidates in a level taken in runs: whole slices of ``LEVEL_SLICE``,
    then what is left at its own :func:`pad_pow2` class."""
    size = max(int(LEVEL_SLICE), 1)
    out = [(lo, lo + size, size) for lo in range(0, n - size + 1, size)]
    lo = len(out) * size
    if lo < n or not out:
        out.append((lo, n, pad_pow2(n - lo)))
    return out


def level_ranges(anchors, tables, anchor_of, list_len, xp=np,
                 id_bounds=None):
    """The generator choice of one WCOJ level, a prefix row at a time:
    each adjacency's ``(start, degree)`` (its anchor's key looked up among
    the adjacency's keys), and the argmin and the min over those degrees
    and the list's constant length ``list_len`` (``None``: the level has no
    list). ``anchors`` holds one row of ids a prefix column the level
    anchors on, ``anchor_of[j]`` the row of adjacency j, ``tables`` one
    ``(keys, offsets)`` an adjacency. -> ``(starts [A, n], degs [A, n],
    choice [n] int8, mindeg [n])``. The first minimum wins a tie, as in
    ``np.argmin``: an adjacency before a later one, any before the list.
    On the device (``xp=jax.numpy``) ``id_bounds[j]``, the adjacency's
    last key + 1, gives its lookup the form :func:`direct_lookup_wins`
    picks (:func:`lookup_ranges_device`); an anchor of -1 (a padding row)
    has degree 0."""
    starts, degs = [], []
    for j, (keys, offsets) in enumerate(tables):
        vids = anchors[anchor_of[j]]
        if xp is np:
            start, deg = lookup_ranges(keys, offsets, vids)
        else:
            start, deg = lookup_ranges_device(keys, offsets, vids,
                                              id_bounds[j])
        starts.append(start)
        degs.append(deg)
    cols = degs if list_len is None else \
        degs + [xp.full_like(degs[0], list_len)]
    every = xp.stack(cols)
    return (xp.stack(starts), xp.stack(degs),
            xp.argmin(every, axis=0).astype(np.int8), xp.min(every, axis=0))


def prefix_sum(x, xp=np, block: int = 1024):
    """Inclusive running sum along the last axis. On the device
    (``xp=jax.numpy``) in blocks: each block of ``block`` summed along,
    then the blocks' totals (recursively) added in. Compiled for a
    described v5e, XLA's own scan of 2^19-2^20 int32 took 7.6-16.6 s of
    compiling and 0.7-1.3 MB of program text, the blocked one 0.2-0.4 s
    and 0.6-0.9 MB; its sum wraps in int32 as the scan's does."""
    if xp is np:
        return np.cumsum(x, axis=-1)
    n = int(x.shape[-1])
    if n <= block:
        return xp.cumsum(x, axis=-1)
    lead = tuple(x.shape[:-1])
    pad = -n % block
    if pad:
        x = xp.concatenate([x, xp.zeros(lead + (pad,), x.dtype)], axis=-1)
    y = xp.cumsum(x.reshape(lead + (-1, block)), axis=-1)
    last = y[..., -1]
    y = y + (prefix_sum(last, xp, block) - last)[..., None]
    return y.reshape(lead + (n + pad,))[..., :n]


def spread_rows(at, values, out_cap: int, xp=np):
    """Each of ``out_cap`` slots takes the values of the last row whose
    first slot ``at`` (non-decreasing a row; before 0 is slot 0) is at or
    before it: ``values`` is ``[k, rows]``, the result ``[k, out_cap]``.
    Each row's difference from the row before is added at its first slot
    and the slots are summed along, so a row of no slots, which shares its
    first slot with the next row, cancels out; a slot costs a running sum
    and no gather (in wrapping int32 the sums are exact)."""
    at = xp.maximum(at, 0)
    diffs = values - xp.concatenate(
        [xp.zeros_like(values[:, :1]), values[:, :-1]], axis=1)
    if xp is np:
        z = np.zeros((values.shape[0], out_cap), dtype=values.dtype)
        keep = at < out_cap
        for row, d in zip(z, diffs):
            np.add.at(row, at[keep], d[keep])
    else:
        # one scatter a value: one scatter of [k, rows] into [k, slots]
        # took some 90 ns a row on a v5e
        z = xp.stack([xp.zeros(out_cap, dtype=values.dtype).at[at].add(
            d, mode="drop", indices_are_sorted=True) for d in diffs])
    return prefix_sum(z, xp)


def compact_padded(mask, cols, xp=np):
    """The slots ``mask`` keeps, moved to the front of each of ``cols`` (a
    ``[k, n]`` array) in slot order: -> ``(compacted [k, n], count)``; past
    ``count`` a column holds the slots not kept. A kept slot goes to its
    rank (the exclusive running sum of the mask), a slot not kept to
    ``count`` plus the slots not kept before it: a permutation, so the
    scatter is told its indices are unique, one column at a time. On a
    v5e the two columns' scatters took some 110 ms at 2^23 slots, where
    one scatter by ``max`` at the ranks, every slot not kept landing on
    the rank of the next kept one, took 510."""
    m = mask.astype(np.int32)
    cum = prefix_sum(m, xp)
    n = int(mask.shape[0])
    count = cum[n - 1]
    if xp is np:
        order = np.concatenate([np.flatnonzero(mask), np.flatnonzero(~mask)])
        return cols[:, order], count
    rank = cum - m
    slot = xp.arange(n, dtype=np.int32)
    to = xp.where(mask, rank, count + slot - rank)
    return xp.stack([xp.zeros_like(c).at[to].set(c, unique_indices=True)
                     for c in cols]), count


def level_probe(choice, starts, degs, window, glob, edges, gen: int,
                depths: tuple, has_list: bool, out_cap: int, xp=np,
                list_bound: int | None = None, rows_cap: int | None = None):
    """One call of a WCOJ level's candidates, made, probed and compacted:
    the prefix rows in ``[window[1], window[2])`` whose generator
    (:func:`level_ranges`' ``choice``) is ``gen`` expand to their
    candidates, and ``out_cap`` of them from the group's ``window[3]``-th
    on are kept where every constraint but the generator holds. -> ``(rows,
    values, count)``: the first ``count`` slots hold each survivor's prefix
    row and value, in the order the host enumerates them (rows ascending,
    each row's edge run in order; a list-generated row takes the whole
    list ``glob``). ``rows_cap`` rows from ``window[0]`` are read, where
    it is under the level's (a run of rows of a level in runs); the rest
    of the level's rows cost the call nothing.

    ``gen`` is an adjacency (its run ``starts[gen]``, ``degs[gen]`` out of
    ``edges[gen]``) or ``len(edges)``, the list. A slot learns its row, its
    place in the generator's run and every other adjacency's run from the
    rows by :func:`spread_rows` (no key is looked up here); every other
    adjacency ``i`` is probed by :func:`edge_run_member` over that run, in
    ``depths[i]`` rounds on the device, and where ``has_list`` the list's
    membership (:func:`member_sorted_device` by ``list_bound``); the
    survivors are compacted by :func:`compact_padded`. A slot's gathers are
    its value and the searches'. With ``xp=np`` it is the NumPy twin
    (searches converge without ``depths``)."""
    row0, lo, hi, base = window[0], window[1], window[2], window[3]
    if rows_cap is not None and rows_cap < choice.shape[0]:
        if xp is np:
            at = slice(int(row0), int(row0) + rows_cap)
            choice, starts, degs = choice[at], starts[:, at], degs[:, at]
        else:
            import jax

            def cut(a):
                return jax.lax.dynamic_slice_in_dim(a, row0, rows_cap,
                                                    axis=a.ndim - 1)

            choice, starts, degs = cut(choice), cut(starts), cut(degs)
    else:
        row0 = 0 * row0
    rows = xp.arange(choice.shape[0], dtype=np.int32)
    pick = (choice == gen) & (rows >= lo - row0) & (rows < hi - row0)
    if gen < len(edges):
        start, run = starts[gen], edges[gen]
        deg = xp.where(pick, degs[gen], 0)
    else:
        start, run = xp.zeros_like(starts[0]), glob
        deg = xp.where(pick, glob.shape[0], 0).astype(degs.dtype)
    cum = prefix_sum(deg, xp)
    first = cum - deg
    others = [i for i in range(len(edges)) if i != gen]
    per_row = xp.stack([rows.astype(starts.dtype), start - first]
                       + [starts[i] for i in others]
                       + [degs[i] for i in others])
    got = spread_rows(first - base, per_row, out_cap, xp)
    pos = base + xp.arange(out_cap, dtype=np.int32)
    vals = run[xp.clip(got[1] + pos, 0, run.shape[0] - 1)]
    mask = pos < cum[-1]
    for k, i in enumerate(others):
        mask = mask & edge_run_member(
            edges[i], got[2 + k], got[2 + len(others) + k], vals, xp=xp,
            depth=None if xp is np else depths[i], loop=True)
    if has_list:
        mask = mask & (member_sorted(glob, vals) if xp is np
                       else member_sorted_device(glob, vals, list_bound))
    out, count = compact_padded(mask, xp.stack([got[0] + row0, vals]), xp=xp)
    return out[0], out[1], count


# the jitted level programs keyed on what their tracing reads beside the
# operands' shapes: the candidates come in pad_pow2 classes and slices of
# LEVEL_SLICE, the prefix rows in pad_pow2 classes, so the caches stay small
_LEVEL_RANGES_CACHE: dict = {}
_LEVEL_PROBE_CACHE: dict = {}
# which templates' requests ran each cached level program, by its key
_LEVEL_OWNERS: dict = {}
_LEVEL_OWNERS_LOCK = make_lock("join.level_owners")
declare_leaf("join.level_owners")
register_global_lock(sys.modules[__name__], "_LEVEL_OWNERS_LOCK",
                     "join.level_owners")


def own_level_programs(keys, owner) -> None:
    """``owner`` (a template's signature) ran the level programs ``keys``
    (what ``jit_level_ranges`` and ``jit_level_probe`` put in ``used``)."""
    with _LEVEL_OWNERS_LOCK:
        for key in keys:
            _LEVEL_OWNERS.setdefault(key, set()).add(owner)


def disown_level_programs(owner) -> int:
    """``owner``'s template left the join's device route: let go each
    cached level program no other template ran, whose text is resident on
    the device while it is cached (15.7-19.9 MB a call's program and
    2.4-3.6 a ranges program at LSQB's shapes, compiled for a described
    v5e). A caller that holds one
    keeps it; a later request compiles it anew (from the persistent compile
    cache where there is one). -> how many went."""
    gone = 0
    with _LEVEL_OWNERS_LOCK:
        for key, owners in list(_LEVEL_OWNERS.items()):
            owners.discard(owner)
            if not owners:
                del _LEVEL_OWNERS[key]
                cache = _LEVEL_RANGES_CACHE if key[0] == "ranges" \
                    else _LEVEL_PROBE_CACHE
                gone += cache.pop(key, None) is not None
    return gone


def jit_level_ranges(id_bounds: tuple, anchor_of: tuple, has_list: bool,
                     used: set | None = None):
    """:func:`level_ranges` on the chip, once a level of the join's device
    route: ``fn(anchors, list_len, k0, o0, k1, o1, ...) -> (starts, degs,
    choice, mindeg)``, where ``anchors`` is ``int32 [columns, rows]``
    (padding rows -1), ``list_len`` the list's length (ignored without
    ``has_list``) and each adjacency gives its cached ``(keys, offsets)``.
    The ranges stay on the device for the level's calls of
    :func:`jit_level_probe`; the host fetches ``choice`` and ``mindeg``.
    Named ``wk_level_ranges``. ``used`` gets the program's key
    (:func:`own_level_programs`)."""
    import jax
    import jax.numpy as jnp

    bounds = tuple(int(b) for b in id_bounds)
    anchor_of = tuple(int(a) for a in anchor_of)
    key = ("ranges", bounds, anchor_of, bool(has_list))
    if used is not None:
        used.add(key)
    fn = _LEVEL_RANGES_CACHE.get(key)
    if fn is not None:
        return fn

    def wk_level_ranges(anchors, list_len, *tables):
        with jax.named_scope("wk_level_ranges"):
            pairs = [tables[2 * j: 2 * j + 2] for j in range(len(bounds))]
            return level_ranges(anchors, pairs, anchor_of,
                                list_len if has_list else None, xp=jnp,
                                id_bounds=bounds)

    fn = jax.jit(wk_level_ranges)
    _LEVEL_RANGES_CACHE[key] = fn
    return fn


def jit_level_probe(gen: int, depths: tuple, has_list: bool,
                    list_bound: int | None, out_cap: int, rows_cap: int,
                    used: set | None = None):
    """:func:`level_probe` on the chip, one call a generator group (or a
    slice of one) of a level on the join's device route: ``fn(choice,
    starts, degs, window, glob, e0, e1, ...) -> (rows, values, count)``
    over :func:`jit_level_ranges`' device-resident outputs, ``window`` the
    ``int32 [4]`` of the first row read (``rows_cap`` of them), the prefix
    rows' run and the group's first candidate,
    ``glob`` the level's list (a 1-element dummy without one) and each
    adjacency's cached edges. The candidates never leave the chip; the host
    fetches the compacted survivors and keeps ``[:count]``. ``depths[i]``
    is adjacency ``i``'s search bound (log2(max_degree)+1, cached with its
    table); ``list_bound`` (the store's vertex id bound,
    ``JoinTableCache.vertex_bound``) lets the list's membership mark the
    list in a table over the id range, by :func:`direct_lookup_wins` on the
    call's shapes (``None`` searches). Named ``wk_level_probe`` (the
    function and a ``jax.named_scope``), which the profile's reader of the
    level's device time finds. ``used`` gets the program's key."""
    import jax
    import jax.numpy as jnp

    depths = tuple(int(d) for d in depths)
    list_bound = None if list_bound is None or not has_list \
        else int(list_bound)
    key = ("probe", int(gen), depths, bool(has_list), list_bound,
           int(out_cap), int(rows_cap))
    if used is not None:
        used.add(key)
    fn = _LEVEL_PROBE_CACHE.get(key)
    if fn is not None:
        return fn

    def wk_level_probe(choice, starts, degs, window, glob, *edges):
        with jax.named_scope("wk_level_probe"):
            return level_probe(choice, starts, degs, window, glob, edges,
                               gen, depths, has_list, out_cap, xp=jnp,
                               list_bound=list_bound, rows_cap=rows_cap)

    fn = jax.jit(wk_level_probe)
    _LEVEL_PROBE_CACHE[key] = fn
    return fn


def seed_masks(s, p, o, tp, ts, to, eq, xp=np):
    """Every semi-naive term's frontier row mask over an epoch batch
    (stream/continuous.py), [T, N]: triples [N] columns against per-term
    specs [T] (predicate, subject-const, object-const, repeated-var
    equality; -1 = wildcard endpoint). Written against the swappable
    array module like every kernel here — the SAME function is the host
    parity oracle and the jitted device path, so the twins cannot
    drift."""
    m = p[None, :] == tp[:, None]
    m &= (ts[:, None] < 0) | (s[None, :] == ts[:, None])
    m &= (to[:, None] < 0) | (o[None, :] == to[:, None])
    m &= (~eq[:, None]) | (s[None, :] == o[None, :])
    return m


def seed_masks_host(s, p, o, tp, ts, to, eq) -> np.ndarray:
    """NumPy instance of :func:`seed_masks` (the parity oracle)."""
    return seed_masks(s, p, o, tp, ts, to, eq, xp=np)


# ---------------------------------------------------------------------------
# whole-plan compiled-template kernels (engine/template_compile.py)
# ---------------------------------------------------------------------------

def expand_padded(start, deg, edges, out_cap):
    """Order-preserving ragged expansion to a STATIC output capacity:
    the NumPy form, parity oracle of :func:`expand_padded_device`.

    The padded twin of :func:`expand_ragged`: rows land in source-row
    order with each row's edges contiguous (np.repeat order), so a
    validity-compacted result is byte-identical to the host expansion —
    the whole-plan program chains these without ever compacting on
    device. Rows the caller masked out must arrive with ``deg == 0``
    (their position range is then empty and they contribute nothing).

    Returns ``(row_idx, values, valid, total, overflow)``: the source
    row of each output slot, the gathered edge value, the live-slot
    mask, the true output length, and an overflow flag. ``overflow``
    also trips when the int32 cumulative sum wraps (a float32 shadow sum
    of the degrees catches totals past 2^31 that the wrapped integer
    comparison would miss) — the caller regrows the capacity class or
    degrades to the host walk, never truncates.
    """
    n = int(start.shape[0])
    ne = int(edges.shape[0])
    cum = np.cumsum(deg)
    total = cum[n - 1]
    pos = np.arange(out_cap)
    row = np.searchsorted(cum, pos, side="right")
    rowc = np.clip(row, 0, n - 1)
    prev = np.where(rowc > 0, cum[np.clip(rowc - 1, 0, n - 1)], 0)
    local = pos - prev
    if ne:
        values = edges[np.clip(start[rowc] + local, 0, ne - 1)]
    else:
        values = np.zeros(out_cap, dtype=start.dtype)
    valid = (pos < total) & (total > 0)
    fsum = np.sum(deg.astype(np.float32))
    overflow = (total > out_cap) | (total < 0) | (fsum > float(out_cap))
    return rowc, values, valid, total, overflow


def expand_padded_device(start, deg, edges, out_cap):
    """:func:`expand_padded` for the chip (``jax.numpy`` only): the same
    five outputs, every slot of them equal to the NumPy form's, padding
    included.

    The NumPy form finds each slot's source row by a binary search of
    the slot in the cumulative degrees: ``log2(n)`` gather rounds over
    ``out_cap`` slots, 1820 ms a call at (2^21, 2^23) against 169 ms
    for this form (my chip run, PR 27). Here every row's index is
    scattered at its exclusive start and a running maximum carries it
    over the row's slots (the form of ``tpu_kernels.expand``): one
    scatter of ``n`` and one scan of ``out_cap``. A zero-degree row shares its start with the next
    positive-degree row, which has the larger index and wins the
    ``max``; so the starts go in as they are, non-decreasing, and the
    scatter is told so (left to find out, the chip's compiler sorts
    the ``n`` indices first). Slots at or past ``total`` take row
    ``n - 1``, where the search's clip put them. The edge position is
    one gather of ``start - exclusive start`` a slot, plus the slot:
    in wrapping int32 that is the oracle's ``start[row] + (slot -
    cum[row - 1])`` bit for bit.
    """
    import jax
    import jax.numpy as jnp

    n = int(start.shape[0])
    ne = int(edges.shape[0])
    cum = jnp.cumsum(deg)
    total = cum[n - 1]
    first = cum - deg  # exclusive start: cum[row - 1], 0 for row 0
    pos = jnp.arange(out_cap, dtype=first.dtype)
    rows = jnp.arange(n, dtype=jnp.int32)
    # a sum that wrapped int32 steps down where it did: then every index
    # is parked out of range (sorted still) and nothing is marked; the
    # overflow flag is up and the caller discards the table
    monotone = jnp.all(cum >= first)
    marks = jnp.zeros(out_cap, dtype=jnp.int32).at[
        jnp.where(monotone, first, out_cap)].max(
            rows + 1, mode="drop", indices_are_sorted=True)
    src = jax.lax.cummax(marks) - 1
    rowc = jnp.where(pos < total, jnp.maximum(src, 0), n - 1)
    if ne:
        values = edges[jnp.clip((start - first)[rowc] + pos, 0, ne - 1)]
    else:
        values = jnp.zeros(out_cap, dtype=start.dtype)
    valid = (pos < total) & (total > 0)
    fsum = jnp.sum(deg.astype(np.float32))
    overflow = (total > out_cap) | (total < 0) | (fsum > float(out_cap))
    return rowc, values, valid, total, overflow


#: ns an element on the attached chip (TPU v5 lite; my chip run, PR 27,
#: ``scripts/bench_direct_lookup.py``, 13,937,249 sorted unique keys in an
#: id range of 14.07 M), the constants of :func:`direct_lookup_wins`:
#: ``search_round`` one round of ``searchsorted``'s loop a row (16.3-16.6 at
#: 2^16-2^21 rows, where the two forms cross; 9.8 at 2^23; 19-48 under 2^14,
#: where a round's fixed 30 us shows); ``scatter`` a key into the table
#: (83.1 ms the table, 111.6 without the sorted/unique promise);
#: ``fill`` a slot of the table set to -1 (0.61 ms). The two forms read
#: 52.2 against 86.4 ms at 2^17 rows and 103.7 against 89.5 at 2^18, 1968
#: against 301 at 2^23; the three gathers a row both end in cost 8.7 each.
DIRECT_NS = {"search_round": 16.4, "scatter": 6.0, "fill": 0.044}


def direct_lookup_wins(rows: int, nkeys: int, id_bound: int) -> bool:
    """THE shape rule of :func:`lookup_ranges_device`, from static shapes
    alone: the search costs ``rows`` gathers in each of its
    ``ceil(log2(nkeys + 1))`` rounds; the direct form costs one scatter
    of ``nkeys`` into a table of ``id_bound`` that it fills first. Both
    end in the same three gathers a row. A light-sized frontier over a
    big segment (1024 rows over 13.9 M keys) keeps the search; the
    heavy classes (2^20 rows and more) take the table."""
    rounds = int(nkeys).bit_length()
    search = rows * rounds * DIRECT_NS["search_round"]
    direct = nkeys * DIRECT_NS["scatter"] + id_bound * DIRECT_NS["fill"]
    return direct < search


def lookup_ranges_device(keys, offsets, vids, id_bound: int):
    """:func:`lookup_ranges` for the chip (``jax.numpy`` only): the same
    (start, degree) a row, by the form :func:`direct_lookup_wins` picks
    at trace time.

    Direct form: vertex ids are a dense int32 domain, and ``id_bound``
    (the segment's last key + 1, static, staged with its tables) bounds
    it. ``arange(S)`` is scattered at ``keys`` (sorted, unique) into a
    table of ``id_bound`` slots initialised to -1; a row's slot is one
    gather, an id outside ``[0, id_bound)`` or with slot -1 is absent.
    The table is a temporary of the program, never a staged operand,
    and is not built before the rows it serves exist: tied to nothing
    but ``keys``, the compiler built every table of q7's program (five,
    85 MB each at LUBM-640) at the program's start, and its temporaries
    read 650.6 MiB for the 378.9 they take in program order (compiled
    for a described v5e, PR 27; the search's program took 418.3).
    """
    import jax
    import jax.numpy as jnp

    nkeys = int(keys.shape[0])
    rows = int(vids.shape[0])
    id_bound = int(id_bound)
    if nkeys == 0:
        z = jnp.zeros(rows, dtype=offsets.dtype)
        return z, z
    if not direct_lookup_wins(rows, nkeys, id_bound):
        return lookup_ranges(keys, offsets, vids, xp=jnp)
    keys, vids = jax.lax.optimization_barrier((keys, vids))
    table = jnp.full(id_bound, -1, dtype=jnp.int32).at[keys].set(
        jnp.arange(nkeys, dtype=jnp.int32), mode="drop",
        indices_are_sorted=True, unique_indices=True)
    slot = table[jnp.clip(vids, 0, id_bound - 1)]
    found = (vids >= 0) & (vids < id_bound) & (slot >= 0)
    slot = jnp.maximum(slot, 0)
    start = jnp.where(found, offsets[slot], 0)
    deg = jnp.where(found, offsets[slot + 1] - offsets[slot], 0)
    return start, deg


def member_sorted_device(sorted_arr, vals, id_bound: int | None):
    """:func:`member_sorted` for the chip (``jax.numpy`` only): the same
    mask a slot, by the form :func:`direct_lookup_wins` picks at trace time
    from ``(len(vals), len(sorted_arr), id_bound)``.

    Direct form: ``sorted_arr`` (non-decreasing ids inside ``[0,
    id_bound)``: the caller's to see to, ``WCOJExecutor._list_bound``) is
    marked in a table of ``id_bound`` flags and a value answered with one
    gather; a value outside ``[0, id_bound)`` is absent. The scatter is
    told its indices are sorted and not that they are unique: a store
    written without dedup may repeat an id, and on the chip the promise
    bought nothing (68.7 ms either way for 27,000 ids, 122.0 for 8.1 M,
    at 2^23 values; the search took 978 and 1,746: my chip run, PR 35).
    The flags are ``bool``: as ``int32`` the same calls took 61.1 and 108.8
    ms, for a temporary four times the size.
    ``id_bound`` is static and must not follow the list's values (the
    store's vertex id bound, the same for every list of a store version:
    ``sorted_arr[-1] + 1`` would be a compile a draw); ``None`` searches.
    As in :func:`lookup_ranges_device` the table is a temporary of the
    program, tied to its operands so that it is not built before they
    exist.
    """
    import jax
    import jax.numpy as jnp

    n = int(sorted_arr.shape[0])
    rows = int(vals.shape[0])
    if n == 0:
        return jnp.zeros(rows, dtype=bool)
    if id_bound is None or not direct_lookup_wins(rows, n, int(id_bound)):
        return member_sorted(sorted_arr, vals, xp=jnp)
    id_bound = int(id_bound)
    sorted_arr, vals = jax.lax.optimization_barrier((sorted_arr, vals))
    table = jnp.zeros(id_bound, dtype=bool).at[sorted_arr].set(
        True, mode="drop", indices_are_sorted=True)
    return (vals >= 0) & (vals < id_bound) \
        & table[jnp.clip(vals, 0, id_bound - 1)]


def unique_rows_padded(ca, cb, valid, xp=np):
    """Padded two-column row dedupe matching ``np.unique(axis=0)`` order.

    Live rows are lexsorted (first column primary), adjacent duplicates
    are masked, and the survivors are stably compacted to the front —
    the first ``count`` output rows equal the host oracle's unique rows
    exactly, padding after them. A one-column dedupe passes the same
    array as both columns. All shapes are static, so the same function
    traces under jit and runs as the NumPy parity twin.
    """
    n = int(ca.shape[0])
    order = xp.lexsort((cb, ca, ~valid))
    a, b, v = ca[order], cb[order], valid[order]
    first = xp.concatenate([xp.ones(1, dtype=bool),
                            (a[1:] != a[:-1]) | (b[1:] != b[:-1])])
    uniq = v & first
    count = xp.sum(uniq.astype(np.int32))
    comp = xp.lexsort((xp.arange(n), ~uniq))
    return a[comp], b[comp], count


def seed_extract_term(s, p, o, tp, ts, to, eq, ca, cb, xp=np):
    """One semi-naive term's FUSED frontier eval: the seed_masks row mask
    and the per-term unique seed rows in a single pass over the padded
    epoch batch, replacing the host np.stack/np.unique partition pin
    (stream/continuous.py). ``ca``/``cb`` select the term's seed columns
    out of the stacked (s, p, o) triple columns (``ca == cb`` for a
    one-variable term — the duplicated column dedupes identically to a
    one-column np.unique). Returns ``(col_a, col_b, count)`` with the
    first ``count`` rows live, in np.unique(axis=0) order."""
    m = seed_masks(s, p, o, tp[None], ts[None], to[None], eq[None],
                   xp=xp)[0]
    cols = xp.stack([s, p, o])
    return unique_rows_padded(cols[ca], cols[cb], m, xp=xp)


def seed_extract_host(s, p, o, tp, ts, to, eq, ca, cb):
    """NumPy twin of the fused per-term seed extraction (the parity
    oracle): a Python loop over terms, each through the SAME
    :func:`seed_extract_term` the device path traces."""
    outs = [seed_extract_term(np.asarray(s), np.asarray(p), np.asarray(o),
                              np.asarray(tp)[t], np.asarray(ts)[t],
                              np.asarray(to)[t], np.asarray(eq)[t],
                              int(ca[t]), int(cb[t]))
            for t in range(len(tp))]
    return (np.stack([a for a, _, _ in outs]),
            np.stack([b for _, b, _ in outs]),
            np.asarray([int(c) for _, _, c in outs]))


_SEED_EXTRACT_FN = None


def jit_seed_extract():
    """jax.jit + vmap over terms of :func:`seed_extract_term` — one
    compiled dispatch evaluates every term's frontier mask AND its
    deduped seed rows for a whole epoch batch. N and T are padded to
    capacity classes by the caller (pad_pow2), so large epochs share a
    handful of compiles."""
    global _SEED_EXTRACT_FN
    if _SEED_EXTRACT_FN is not None:
        return _SEED_EXTRACT_FN
    import jax
    import jax.numpy as jnp

    def wk_stream_seed_extract(s, p, o, tp, ts, to, eq, ca, cb):
        return seed_extract_term(s, p, o, tp, ts, to, eq, ca, cb, xp=jnp)

    _SEED_EXTRACT_FN = jax.jit(jax.vmap(
        wk_stream_seed_extract, in_axes=(None, None, None, 0, 0, 0, 0, 0, 0)))
    return _SEED_EXTRACT_FN


def concat_rows_padded(stacked, counts, xp=np):
    """Device-side slice settlement: concatenate S padded row tables
    ``stacked [S, cap, w]`` (each slice's first ``counts[i]`` rows live)
    into one padded table in slice order — byte-identical to the host
    ``np.concatenate`` over the live prefixes (join/dist.py's gather
    barrier, which today settles on one host thread). Returns
    ``(rows [S*cap, w], valid, total)``."""
    S = int(stacked.shape[0])
    cap = int(stacked.shape[1])
    cum = xp.cumsum(counts)
    total = cum[S - 1]
    pos = xp.arange(S * cap)
    sl = xp.searchsorted(cum, pos, side="right")
    slc = xp.clip(sl, 0, S - 1)
    prev = xp.where(slc > 0, cum[xp.clip(slc - 1, 0, S - 1)], 0)
    local = xp.clip(pos - prev, 0, cap - 1)
    rows = stacked[slc, local]
    valid = pos < total
    return rows, valid, total


_CONCAT_ROWS_FN = None


def jit_concat_rows():
    """jax.jit-wrapped :func:`concat_rows_padded` (the settlement
    dispatch). Slice count and capacity are padded by the caller so the
    variant set stays bounded."""
    global _CONCAT_ROWS_FN
    if _CONCAT_ROWS_FN is not None:
        return _CONCAT_ROWS_FN
    import jax
    import jax.numpy as jnp

    def wk_dist_concat_rows(st, c):
        return concat_rows_padded(st, c, xp=jnp)

    _CONCAT_ROWS_FN = jax.jit(wk_dist_concat_rows)
    return _CONCAT_ROWS_FN


_SEED_MASK_FN = None


def jit_seed_masks():
    """jax.jit-wrapped :func:`seed_masks` — the fused device call. N and
    T are padded to capacity classes by the caller (pad_pow2, the level
    probe's padded/bucketed discipline) so large epochs share a handful
    of compiles."""
    global _SEED_MASK_FN
    if _SEED_MASK_FN is not None:
        return _SEED_MASK_FN
    import jax
    import jax.numpy as jnp

    def wk_stream_seed_masks(s, p, o, tp, ts, to, eq):
        return seed_masks(s, p, o, tp, ts, to, eq, xp=jnp)

    _SEED_MASK_FN = jax.jit(wk_stream_seed_masks)
    return _SEED_MASK_FN
