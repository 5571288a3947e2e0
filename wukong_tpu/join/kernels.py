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
level, the join's level probe (:func:`jit_level_probe`: the anchors' keys by
:func:`lookup_ranges_device`, the class list by
:func:`member_sorted_device`), with the NumPy forms as their parity oracle.

Data model: adjacency is the store's CSR triplet (sorted unique ``keys``,
``offsets``, ``edges`` sorted within each key run); candidate sets are
sorted 1-D id arrays. Intersection = membership mask via vectorized binary
search; ragged per-row probes = fixed-iteration branchless lower_bound over
each row's [start, end) edge range.
"""

from __future__ import annotations

import numpy as np


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
    ne = int(edges.shape[0])
    if ne == 0:
        return xp.zeros(anchors.shape[0], dtype=bool)
    if id_bound is None or xp is np:
        start, deg = lookup_ranges(keys, offsets, anchors, xp=xp)
    else:
        start, deg = lookup_ranges_device(keys, offsets, anchors, id_bound)
    # int64 search cursors on the host; under an xp=jnp trace the inputs'
    # own dtype rules (int32 by default, int64 under enable_x64) — an
    # unconditional astype would fight the x64-off config every trace
    lo = start.astype(np.int64) if xp is np else start
    end = (start + deg) if xp is not np else (start + deg).astype(np.int64)
    hi = end
    iters = ne.bit_length() + 1 if depth is None else max(int(depth), 1)
    for _ in range(iters):
        active = lo < hi
        # lo + (hi - lo) // 2, NOT (lo + hi) // 2: the device route runs
        # int32, and lo + hi overflows past 2^30 edges, mis-converging
        # the search (the classic binary-search midpoint bug)
        mid = lo + (hi - lo) // 2
        mv = edges[xp.clip(mid, 0, ne - 1)]
        less = mv < vals
        lo = xp.where(active & less, mid + 1, lo)
        hi = xp.where(active & ~less, mid, hi)
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
#: ``pad_pow2`` tensor is 2^27 slots with their anchors, 2 GiB shipped and
#: probed before the host may enumerate again. A smaller level is one
#: dispatch a group at ``pad_pow2`` of its candidates, as it always was.
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


# jitted level-probe variants keyed on (per-adjacency depths, has_glob,
# per-adjacency id bounds, the list's id bound): the candidate tensor shape
# is handled by pad_pow2 bucketing and LEVEL_SLICE, so the cache stays small
_LEVEL_PROBE_CACHE: dict = {}


def jit_level_probe(adj_depths: tuple, has_glob: bool,
                    id_bounds: tuple | None = None,
                    list_bound: int | None = None):
    """The fused XLA probe for one WCOJ generator group: a padded flat
    candidate tensor is masked by every LISTED constraint in one compiled
    call — global sorted-list membership plus one ragged pair probe per
    adjacency — instead of one NumPy pass per constraint with
    materialized intermediates (where the host path pays its
    per-candidate cost). The caller lists only the constraints the group
    actually needs (a generator's self-probe is true by construction and
    is elided), and ``adj_depths[j]`` is adjacency j's binary-search
    iteration bound (log2(max_degree)+1, cached with its device table).

    Every lookup of the probe takes the form :func:`direct_lookup_wins`
    picks from the static shapes, in every level: ``id_bounds[j]`` (the
    segment's last key + 1, cached beside its tables) lets the anchors'
    key lookup address a table over the id range
    (:func:`lookup_ranges_device`), ``list_bound`` (the store's vertex id
    bound, ``JoinTableCache.vertex_bound``) lets the list's membership
    mark the list in one (:func:`member_sorted_device`). At 2^23
    candidates the key of LSQB's ``knows`` among 27,000 and the persons'
    list of 27,000 were 15 rounds of a gather a candidate each, the
    comments' list of 8.1 M 23; a table is one. Both tables are
    temporaries of the call. Without a bound (``None``) a lookup searches;
    a small level over a big segment searches by the rule.

    Signature of the returned fn:
        fn(valid, cand, glob, k0, o0, e0, a0, k1, o1, e1, a1, ...) -> mask
    where ``valid``/``cand`` are the padded candidate tensor and its
    validity mask, ``glob`` the intersected global candidate list (ignored
    when has_glob is False — pass a 1-element dummy), and each adjacency
    contributes (keys, offsets, edges, anchors). The compiled program is
    named ``wk_level_probe`` (the function and a ``jax.named_scope``), so a
    profile that carries scopes can tell it from the template programs."""
    import jax
    import jax.numpy as jnp

    depths = tuple(int(d) for d in adj_depths)
    bounds = (None,) * len(depths) if id_bounds is None \
        else tuple(None if b is None else int(b) for b in id_bounds)
    list_bound = None if list_bound is None or not has_glob \
        else int(list_bound)
    key = (depths, bool(has_glob), bounds, list_bound)
    fn = _LEVEL_PROBE_CACHE.get(key)
    if fn is not None:
        return fn

    def wk_level_probe(valid, cand, glob, *adj):
        with jax.named_scope("wk_level_probe"):
            mask = valid
            if has_glob:
                mask = mask & member_sorted_device(glob, cand, list_bound)
            for j, depth in enumerate(depths):
                keys, offsets, edges, anchors = adj[4 * j: 4 * j + 4]
                mask = mask & pair_member(keys, offsets, edges, anchors,
                                          cand, xp=jnp, depth=depth,
                                          id_bound=bounds[j])
            return mask

    fn = jax.jit(wk_level_probe)
    _LEVEL_PROBE_CACHE[key] = fn
    return fn


def level_probe_host(valid, cand, glob, *adj):
    """NumPy twin of the jitted level probe (same argument layout) — the
    parity tests compare the two directly on padded tensors, including
    all-padding buckets and empty candidate lists."""
    mask = np.asarray(valid).copy()
    if glob is not None:
        mask &= member_sorted(np.asarray(glob), np.asarray(cand))
    for j in range(len(adj) // 4):
        keys, offsets, edges, anchors = adj[4 * j: 4 * j + 4]
        mask &= pair_member(np.asarray(keys), np.asarray(offsets),
                            np.asarray(edges), np.asarray(anchors),
                            np.asarray(cand))
    return mask


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
