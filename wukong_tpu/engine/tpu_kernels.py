"""Jitted TPU kernels for triple-pattern matching over hashed CSR segments.

The reference's GPU hot path (core/gpu/gpu_hash.cu: generate_key_list ->
get_slot_id_list hash probe -> get_edge_list -> prefix sum -> update_result_buf)
maps onto shape-stable XLA ops:

- key lookup is an 8-way bucketized **hash probe** (`_hash_find`) — binary
  search over sorted keys lowers to a slow ~21-round scan loop on TPU, so the
  table is built for 1-2 probe rounds instead.
- ragged expansion positions come from **scatter + cummax** over the output
  index space instead of a second searchsorted.
- membership (k2k/k2c) is a binary search over each row's sorted edge range
  with a static depth bound (the segment's max degree, recorded at staging).

LAYOUT RULE (v5e): XLA pads a 2-D array's minor dimension to 128 lanes, so any
[rows, small] array wastes up to 16-32x HBM (a 33M x 8 gather output would pad
1 GiB to 17 GiB — measured compile OOM). Therefore:
- binding tables are **transposed**: [width, capacity] with capacity minor;
- bucket tables are stored **flat** [NB*8], probed with flat gathers and
  strided-slice lane reduction — no [C, 8] intermediate ever materializes.

All kernels take padded arrays (see device_store) and static capacities, so the
jit cache is bounded by (log2 sizes x width x probe bound). `n` is the live row
count (device scalar). No kernel ever forces a host sync — overflow totals ride
along as device scalars.

Each jitted kernel is named for the route that calls it: ``wk_walk_<kernel>``
for the walk's chain (``engine/tpu.py``), ``wk_walk_merge_<kernel>`` for its
merge executor (``engine/tpu_merge.py``). The name is the module the profile
shows (``jit_wk_walk_expand``); ``analysis/devicegate.py`` holds it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INT32_MAX = np.iinfo(np.int32).max
_HASH_MULT = np.uint32(2654435761)
BUCKET = 8


# ---------------------------------------------------------------------------
# hashed CSR lookup (flat bucket arrays)
# ---------------------------------------------------------------------------


def _home(cur, bmask, key_shift: int):
    """A key's home bucket: the multiplicative hash of its bits above
    ``key_shift`` (a shard's table whose keys all share their low bits,
    ``ShardedDeviceStore``, hashes the bits that differ)."""
    k = cur.astype(jnp.uint32)
    if key_shift:
        k = k >> np.uint32(key_shift)
    return (k * _HASH_MULT) & bmask


def _hash_find(bkey, bstart, bdeg, cur, valid, max_probe: int,
               key_shift: int = 0):
    """(found, start, degree) per cur[i]; bkey/bstart/bdeg are flat [NB*8].

    Per probe round: three flat gathers of [C*8] (groups of 8 consecutive
    slots) + strided-slice lane reduction. Everything stays 1-D, so nothing
    hits the 128-lane padding blowup.
    """
    NB = bkey.shape[0] // BUCKET
    bmask = np.uint32(NB - 1)
    C = cur.shape[0]
    hb = _home(cur, bmask, key_shift)
    found = jnp.zeros(C, bool)
    start = jnp.zeros_like(cur)
    deg = jnp.zeros_like(cur)
    # flat [C*8] index arithmetic (jnp.repeat/tile would lower through a
    # padded [C, 8] broadcast — the 16x blowup this layout exists to avoid)
    j = jnp.arange(C * BUCKET, dtype=jnp.int32)
    row_of_j = j >> 3
    lane_of_j = j & 7
    cur8 = cur[row_of_j]
    for r in range(max_probe):
        rows = (((hb + np.uint32(r)) & bmask).astype(jnp.int32) * BUCKET)
        idx = rows[row_of_j] + lane_of_j  # [C*8] flat slot ids
        kk = bkey[idx]
        hit_flat = kk == cur8
        ss = bstart[idx]
        dd = bdeg[idx]
        for lane in range(BUCKET):
            h = hit_flat[lane::BUCKET]
            pick = h & (~found)
            start = jnp.where(pick, ss[lane::BUCKET], start)
            deg = jnp.where(pick, dd[lane::BUCKET], deg)
            found = found | pick
    ok = valid & found
    return ok, jnp.where(ok, start, 0), jnp.where(ok, deg, 0)


_FP_MULT = np.uint32(0x9E3779B1)


def _fp_of(cur):
    """8-bit key fingerprint, 1..255 (0 marks an empty slot)."""
    fp = ((cur.astype(jnp.uint32) * _FP_MULT) >> np.uint32(24)) \
        & np.uint32(0xFF)
    return jnp.where(fp == 0, np.uint32(1), fp)


def _hash_find_fp(bkey, bstart, bdeg, fpw0, fpw1, cur, valid,
                  max_probe: int, fp_dup: int, key_shift: int = 0):
    """Fingerprint-packed probe: same contract as _hash_find with ~5 [C]
    gathers per round instead of 24.

    fpw0/fpw1 pack the bucket's 8 slot fingerprints into two int32 words
    (staging computes them host-side). A probe round gathers the two words,
    compares all 8 fingerprints in-registers, then verifies only the
    candidate lanes against bkey. fp_dup (static, from staging) is the exact
    max count of identical fingerprints within any one bucket — the number of
    candidate verifications that guarantees no false negative. Random fused
    gathers cost ~30 ns/elem on v5e, so gathered volume IS the probe cost.
    """
    NB = fpw0.shape[0]
    bmask = np.uint32(NB - 1)
    C = cur.shape[0]
    curfp = _fp_of(cur)
    hb = _home(cur, bmask, key_shift)
    found = jnp.zeros(C, bool)
    start = jnp.zeros_like(cur)
    deg = jnp.zeros_like(cur)
    for r in range(max_probe):
        b = ((hb + np.uint32(r)) & bmask).astype(jnp.int32)
        w0 = fpw0[b].astype(jnp.uint32)
        w1 = fpw1[b].astype(jnp.uint32)
        run = jnp.zeros(C, jnp.int32)
        lane_sel = [jnp.full(C, -1, jnp.int32) for _ in range(fp_dup)]
        for lane in range(BUCKET):
            w = w0 if lane < 4 else w1
            fpl = (w >> np.uint32(8 * (lane & 3))) & np.uint32(0xFF)
            is_m = fpl == curfp
            for v in range(fp_dup):
                lane_sel[v] = jnp.where(is_m & (run == v), lane, lane_sel[v])
            run = run + is_m.astype(jnp.int32)
        hit_any = jnp.zeros(C, bool)
        idx_win = jnp.zeros(C, jnp.int32)
        for v in range(fp_dup):
            has = lane_sel[v] >= 0
            idx = b * BUCKET + jnp.maximum(lane_sel[v], 0)
            kk = bkey[idx]
            hit = has & (kk == cur)
            idx_win = jnp.where(hit & ~hit_any, idx, idx_win)
            hit_any = hit_any | hit
        news = hit_any & (~found)
        start = jnp.where(news, bstart[idx_win], start)
        deg = jnp.where(news, bdeg[idx_win], deg)
        found = found | hit_any
    ok = valid & found
    return ok, jnp.where(ok, start, 0), jnp.where(ok, deg, 0)


def _range_member(edges, lo, hi, vals, depth: int):
    """Is vals[i] in sorted edges[lo[i]:hi[i]]? Binary search, static depth."""
    E = edges.shape[0]

    def body(_, carry):
        lo, hi = carry
        active = lo < hi
        mid = (lo + hi) // 2
        mv = edges[jnp.clip(mid, 0, E - 1)]
        less = mv < vals
        lo = jnp.where(active & less, mid + 1, lo)
        hi = jnp.where(active & ~less, mid, hi)
        return lo, hi

    lo_f, _ = jax.lax.fori_loop(0, depth + 1, body, (lo, hi))
    inb = lo_f < hi
    return inb & (edges[jnp.clip(lo_f, 0, E - 1)] == vals)


# ---------------------------------------------------------------------------
# Pattern kernels — binding table layout [width, capacity]
# ---------------------------------------------------------------------------


def _saturate_total(cum):
    """True expansion total from an int32 degree cumsum, saturated to
    INT32_MAX on wraparound. Each degree is < 2^31, so the first time the
    exact prefix passes 2^31 the wrapped value lands in [-2^31, 0) — some
    prefix is negative iff the exact total exceeded int32 range. Without
    this, a wrapped (possibly positive) total could silently pass the host's
    `total > cap` overflow check and truncate rows; saturation instead
    trips the exceeds-capacity error (total > cap_max) deterministically.
    (x64 is disabled process-wide, so an int64 cumsum is not available.)"""
    wrapped = jnp.any(cum < 0)
    return jnp.where(wrapped, jnp.int32(INT32_MAX), cum[-1])


def _probe(bkey, bstart, bdeg, cur, n, max_probe: int,
           fpw0=None, fpw1=None, fp_dup: int = 0, key_shift: int = 0):
    """Probe dispatch. `fp_dup` is the caller's STATIC decision (see
    DeviceSegment.max_fp_dup); row validity is derived from `n` on every
    path so the two probes can never diverge on masking. fp_dup > 0 selects
    the fingerprint-packed probe. `key_shift` (static) is the table's: the
    low key bits its home buckets ignore (0 but for a shard's table)."""
    valid = jnp.arange(cur.shape[0], dtype=jnp.int32) < n
    if fp_dup > 0 and fpw0 is not None:
        return _hash_find_fp(bkey, bstart, bdeg, fpw0, fpw1, cur, valid,
                             max_probe, fp_dup, key_shift)
    return _hash_find(bkey, bstart, bdeg, cur, valid, max_probe, key_shift)


@partial(jax.jit,
         static_argnames=("col", "cap_out", "max_probe", "fp_dup", "key_shift"))
def wk_walk_expand(table, n, bkey, bstart, bdeg, edges, col, cap_out,
                   max_probe, fpw0=None, fpw1=None, fp_dup=0, key_shift=0):
    """known_to_unknown: expand each live row by its neighbor list.

    table: [W, C]. Returns (out [W+1, cap_out], out_n, total) — total may
    exceed cap_out; the host checks it at the end-of-chain sync and retries at
    an exact capacity class (rows are never silently dropped).
    """
    W, C = table.shape
    rows = jnp.arange(C, dtype=jnp.int32)
    valid = rows < n
    cur = table[col]
    found, start, deg = _probe(bkey, bstart, bdeg, cur, n, max_probe,
                               fpw0, fpw1, fp_dup, key_shift)
    cum = jnp.cumsum(deg)
    total = _saturate_total(cum)
    starts_excl = cum - deg
    # scatter each live row's id at its output start; running max fills gaps
    park = jnp.where(deg > 0, starts_excl, cap_out)
    marks = jnp.zeros(cap_out, dtype=jnp.int32).at[park].max(
        rows + 1, mode="drop")
    src = jax.lax.cummax(marks) - 1
    srcc = jnp.clip(src, 0, C - 1)
    j = jnp.arange(cap_out, dtype=jnp.int32)
    eidx = start[srcc] + (j - starts_excl[srcc])
    E = edges.shape[0]
    val = edges[jnp.clip(eidx, 0, E - 1)]
    out_valid = (j < total) & (src >= 0)
    out = jnp.concatenate([table[:, srcc], val[None, :]], axis=0)
    out = jnp.where(out_valid[None, :], out, 0)
    return out, jnp.minimum(total, cap_out).astype(jnp.int32), total


@partial(jax.jit,
         static_argnames=("col", "cap_out", "max_probe", "fp_dup", "key_shift"))
def wk_walk_expand2(table, n, bkey, bstart, bdeg, edges_pid, edges_val, col,
                    cap_out, max_probe, fpw0=None, fpw1=None, fp_dup=0,
                    key_shift=0):
    """VERSATILE known_unknown_unknown (?x ?p ?y with x bound — the
    reference's sparql.hpp:601-650 kernel; its GPU engine refuses the
    shape): expand each live row by its COMBINED adjacency — every
    (predicate, neighbor) pair — binding TWO new columns. Identical
    machinery to wk_walk_expand(), one extra aligned-edge-array gather.

    Returns (out [W+2, cap_out] with pid then val rows, out_n, total)."""
    W, C = table.shape
    rows = jnp.arange(C, dtype=jnp.int32)
    cur = table[col]
    found, start, deg = _probe(bkey, bstart, bdeg, cur, n, max_probe,
                               fpw0, fpw1, fp_dup, key_shift)
    cum = jnp.cumsum(deg)
    total = _saturate_total(cum)
    starts_excl = cum - deg
    park = jnp.where(deg > 0, starts_excl, cap_out)
    marks = jnp.zeros(cap_out, dtype=jnp.int32).at[park].max(
        rows + 1, mode="drop")
    src = jax.lax.cummax(marks) - 1
    srcc = jnp.clip(src, 0, C - 1)
    j = jnp.arange(cap_out, dtype=jnp.int32)
    eidx = jnp.clip(start[srcc] + (j - starts_excl[srcc]), 0,
                    edges_val.shape[0] - 1)
    pid = edges_pid[eidx]
    val = edges_val[eidx]
    out_valid = (j < total) & (src >= 0)
    out = jnp.concatenate([table[:, srcc], pid[None, :], val[None, :]],
                          axis=0)
    out = jnp.where(out_valid[None, :], out, 0)
    return out, jnp.minimum(total, cap_out).astype(jnp.int32), total


@partial(jax.jit,
         static_argnames=("col", "max_probe", "depth", "fp_dup", "key_shift"))
def wk_walk_member_mask_known(table, n, vals, bkey, bstart, bdeg, edges, col,
                              max_probe, depth, fpw0=None, fpw1=None,
                              fp_dup=0, key_shift=0):
    """known_to_known / known_to_const: per-row membership of vals[i] in
    adj(cur[i]). table: [W, C]; vals: [C]."""
    W, C = table.shape
    rows = jnp.arange(C, dtype=jnp.int32)
    valid = rows < n
    cur = table[col]
    found, start, deg = _probe(bkey, bstart, bdeg, cur, n, max_probe,
                               fpw0, fpw1, fp_dup, key_shift)
    ok = _range_member(edges, start, start + deg, vals, depth)
    return valid & found & ok


@partial(jax.jit, static_argnames=("cap_out",))
def wk_walk_compact_to(table, keep, cap_out):
    """compact into a (possibly smaller) capacity class (estimate-driven
    mid-chain shrink: later kernels pay for capacity, not live rows). Returns
    (out [W, cap_out], n, total) — total is the true surviving count; if it
    exceeds cap_out the end-of-chain overflow check retries the chain with an
    exact capacity, so rows are never silently dropped."""
    W, C = table.shape
    total = keep.sum().astype(jnp.int32)
    idx = jnp.nonzero(keep, size=cap_out, fill_value=C - 1)[0]
    out = table[:, idx]
    live = jnp.arange(cap_out, dtype=jnp.int32) < total
    return jnp.where(live[None, :], out, 0), \
        jnp.minimum(total, cap_out).astype(jnp.int32), total


# the dist engine composes the unjitted body (``__wrapped__``) inside its
# one shard_map program
@jax.jit
def wk_walk_compact(table, keep):
    out, n, _total = wk_walk_compact_to.__wrapped__(table, keep,
                                                    table.shape[1])
    return out, n


@partial(jax.jit, static_argnames=("cap",))
def wk_walk_init_from_list(edge_list, real_len, cap):
    """index/const start: one-row table [1, cap] from an edge list."""
    j = jnp.arange(cap, dtype=jnp.int32)
    E = edge_list.shape[0]
    vals = edge_list[jnp.clip(j, 0, E - 1)]
    valid = j < real_len
    table = jnp.where(valid, vals, 0)[None, :]
    return table, jnp.minimum(real_len, cap).astype(jnp.int32)


@partial(jax.jit, static_argnames=("B", "cap", "slice_mode"))
def wk_walk_init_batch_index(edge_list, real_len, B, cap, slice_mode):
    """Batched index-origin start: [2, cap] table with a qid row.

    replicate mode (slice_mode=False): B full copies of the index list —
    B independent instances of the query (throughput batching; amortizes the
    end-of-chain sync across B queries).
    slice mode (slice_mode=True): the index split into B contiguous slices,
    qid = slice id — the reference's mt_factor index-scan slicing
    (sparql.hpp:98-108) as a batch dimension; per-qid counts sum to the
    full query's total.
    """
    j = jnp.arange(cap, dtype=jnp.int32)
    E = edge_list.shape[0]
    if slice_mode:
        per = jnp.maximum((real_len + B - 1) // B, 1)
        qid = jnp.minimum(j // per, B - 1)
        pos = j
        total = real_len
    else:
        r = jnp.maximum(real_len, 1)
        qid = j // r
        pos = j - qid * r
        total = real_len * B
    vals = edge_list[jnp.clip(pos, 0, E - 1)]
    valid = j < total
    table = jnp.stack([jnp.where(valid, qid, 0), jnp.where(valid, vals, 0)])
    return table, jnp.minimum(total, cap).astype(jnp.int32)


def member_mask_list(table, n, col, sorted_list, real_len):
    """index_to_known / const_to_known: membership of a row in a sorted list
    (a body the dist engine traces into its program; ``col`` is static)."""
    W, C = table.shape
    rows = jnp.arange(C, dtype=jnp.int32)
    valid = rows < n
    vals = table[col]
    L = sorted_list.shape[0]
    depth = max(int(L).bit_length(), 1)
    lo = jnp.zeros(C, dtype=jnp.int32)
    hi = jnp.minimum(jnp.full(C, jnp.int32(min(L, INT32_MAX))), real_len)
    ok = _range_member(sorted_list, lo, hi, vals, depth)
    return valid & ok


# ---------------------------------------------------------------------------
# Sort-merge kernels (gather-free joins; the v2 heavy-query path)
#
# Design premise (figures from an earlier installation): XLA random gather
# ~9.5 ns/elem EVEN for sorted indices, while variadic lax.sort costs 2-3
# ns/elem and cumsum/cummax 1.3-2.5 ns/elem. Read on the attached chip (TPU
# v5 lite, int32, 2^23 elements; my chip run, PR 27): gather from a 14.07 M
# table 8.72 ns/elem with random and 8.69 with sorted indices; scatter of
# 13.9 M sorted unique indices 5.96 ns/elem (8.0 without the promise);
# scatter-max of 2^21 sorted indices into 2^23 9.2; cummax 0.46, cumsum
# 0.28; one round of a searchsorted loop 16.4 a row. lax.sort was not read
# there. The hash-probe kernels above pay ~5 gathers per probe
# round plus a log2(deg) binary search per membership — sort-merge replaces
# all of it with concat + one variadic sort + cummax propagation, and the
# expand emits only (val, parent) so old columns are materialized lazily
# (the eager [W+1, cap] regather was the single largest cost at width >= 3).
# The reference's analogue is gpu_hash.cu's probe pipeline; this is the same
# join, restructured for a machine that sorts faster than it gathers.
# ---------------------------------------------------------------------------

INT32_MIN = np.int32(np.iinfo(np.int32).min)


def _merge_lookup(skey, sstart, sdeg, cur):
    """Join cur[i] against a sorted key array. Returns, in MERGED-SORTED
    order over [S + C]: (keys, tag, found, start, deg, is_seg) where tag < S
    marks segment rows and tag - S is the original query row id.

    Padded segment slots carry key INT32_MAX / deg 0, so a padded query row
    (also INT32_MAX) matching one contributes nothing to an expansion and is
    masked by the caller's validity bound for membership.
    """
    S = skey.shape[0]
    C = cur.shape[0]
    keys = jnp.concatenate([skey, cur])
    tag = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                           jnp.arange(S, S + C, dtype=jnp.int32)])
    ks, ts = jax.lax.sort((keys, tag), num_keys=2, is_stable=False)
    is_seg = ts < S
    # segment slots ascend with their (sorted) keys, so cummax == last slot
    slot = jax.lax.cummax(jnp.where(is_seg, ts, -1))
    kprop = jax.lax.cummax(jnp.where(is_seg, ks, INT32_MIN))
    found = (kprop == ks) & (slot >= 0)
    sl = jnp.clip(slot, 0, S - 1)
    start = jnp.where(found, sstart[sl], 0)  # sorted gather from [S]
    deg = jnp.where(found, sdeg[sl], 0)
    return ks, ts, found, start, deg, is_seg


def _emit_gather(ts, S, start, deg, st_ex, edges, total, cap_out):
    """The scatter+cummax+gather emit over the [cap_out] output grid (shared
    by wk_walk_merge_expand and tpu_stream's duplicate-anchor fallback branch).
    Returns (val, parent), zero-masked outside [0, total)."""
    base = start - st_ex  # eidx = base[src] + j (one gather instead of two)
    M = ts.shape[0]
    mrows = jnp.arange(M, dtype=jnp.int32)
    park = jnp.where(deg > 0, st_ex, cap_out)
    marks = jnp.zeros(cap_out, dtype=jnp.int32).at[park].max(
        mrows + 1, mode="drop")
    src = jax.lax.cummax(marks) - 1
    srcc = jnp.clip(src, 0, M - 1)
    j = jnp.arange(cap_out, dtype=jnp.int32)
    E = edges.shape[0]
    eidx = base[srcc] + j
    val = edges[jnp.clip(eidx, 0, E - 1)]
    parent = ts[srcc] - S
    out_ok = (j < total) & (src >= 0)
    return jnp.where(out_ok, val, 0), jnp.where(out_ok, parent, 0)


@partial(jax.jit, static_argnames=("cap_out", "max_probe", "fp_dup"))
def wk_walk_merge_probe_expand(bkey, bstart, bdeg, edges, cur, n, live,
                               cap_out, max_probe, fpw0=None, fpw1=None,
                               fp_dup=0):
    """known_to_unknown for the merge chain when the frontier is far
    smaller than the segment: O(C) hash-probe run lookup against the v1
    bucket table + the shared scatter-emit, instead of _merge_lookup's
    O((S + C) log) variadic sort. At LUBM-2560 a light query's 1024-row
    frontier joined against a 2^26-key segment pays ~150 ms/step in the
    sort (the whole segment is re-sorted per call); the probe pays
    ~max_probe row-contiguous gathers over the frontier only.

    Same contract as wk_walk_merge_expand — (val [cap_out], parent [cap_out],
    out_n, total), parents are input row ids — except output rows are in
    INPUT row order rather than key-sorted anchor order (downstream is
    order-insensitive: nothing assumes emission order).
    """
    C = cur.shape[0]
    rows = jnp.arange(C, dtype=jnp.int32)
    ok_row = (rows < n) & live
    # bucket pads are -1, so INT32_MAX-masked rows can never match one
    curm = jnp.where(ok_row, cur, INT32_MAX)
    found, start, deg = _probe(bkey, bstart, bdeg, curm, n, max_probe,
                               fpw0, fpw1, fp_dup)
    deg = jnp.where(ok_row & found, deg, 0)
    cum = jnp.cumsum(deg)
    total = _saturate_total(cum)
    st_ex = cum - deg
    val, parent = _emit_gather(rows, 0, start, deg, st_ex, edges, total,
                               cap_out)
    return (val, parent,
            jnp.minimum(total, cap_out).astype(jnp.int32), total)


@partial(jax.jit, static_argnames=("cap_out",))
def wk_walk_merge_expand(skey, sstart, sdeg, edges, cur, n, live, cap_out):
    """known_to_unknown without probes: returns (val [cap_out],
    parent [cap_out] into the input row space, out_n, total).

    `live` is a bool row mask (deferred filters zero degrees here instead of
    paying a compaction). Output rows are grouped by anchor value — order
    differs from the eager kernel, which is fine for blind counting and for
    parent-map materialization (nothing downstream assumes input order).
    """
    C = cur.shape[0]
    rows = jnp.arange(C, dtype=jnp.int32)
    ok_row = (rows < n) & live
    curm = jnp.where(ok_row, cur, INT32_MAX)
    ks, ts, found, start, deg, is_seg = _merge_lookup(skey, sstart, sdeg, curm)
    deg = jnp.where(is_seg, 0, deg)
    cum = jnp.cumsum(deg)
    total = _saturate_total(cum)
    st_ex = cum - deg
    val, parent = _emit_gather(ts, skey.shape[0], start, deg, st_ex, edges,
                               total, cap_out)
    return (val, parent,
            jnp.minimum(total, cap_out).astype(jnp.int32), total)


def _run_head_match(k_all, extra_eq, is_rel):
    """For each merged row: does its equal-key run begin with a relation row?
    (relation rows sort first within a run). extra_eq narrows run equality
    beyond the primary key (pair membership). Gather-free.
    """
    M = k_all.shape[0]
    eq_prev = jnp.concatenate([
        jnp.array([False]),
        (k_all[1:] == k_all[:-1]) & extra_eq])
    run_start = ~eq_prev
    run_id = jnp.cumsum(run_start.astype(jnp.int32))  # 1-based, <= M
    packed = jnp.where(run_start,
                       run_id * 2 + is_rel.astype(jnp.int32), -1)
    prop = jax.lax.cummax(packed)
    return (prop == run_id * 2 + 1)


@jax.jit
def wk_walk_merge_member_list(sorted_list, real_len, cur, n, live):
    """Membership of cur[i] in a sorted list (k2c against a const object,
    type checks, index membership). Returns a bool mask in INPUT row order.
    Gather-free: merge + run-head propagation + sort-back by tag.
    """
    L = sorted_list.shape[0]
    C = cur.shape[0]
    rows = jnp.arange(C, dtype=jnp.int32)
    ok_row = (rows < n) & live
    curm = jnp.where(ok_row, cur, INT32_MAX)
    lkey = jnp.where(jnp.arange(L, dtype=jnp.int32) < real_len,
                     sorted_list, INT32_MAX - 1)  # pad can't match a query pad
    keys = jnp.concatenate([lkey, curm])
    tag = jnp.concatenate([jnp.arange(L, dtype=jnp.int32),
                           jnp.arange(L, L + C, dtype=jnp.int32)])
    ks, ts = jax.lax.sort((keys, tag), num_keys=2, is_stable=False)
    is_rel = ts < L
    hit = _run_head_match(ks, jnp.ones(ks.shape[0] - 1, bool), is_rel)
    hit = hit & (~is_rel)
    # unsort via a second small sort keyed on tag (cheaper than scatter)
    ts2, hit2 = jax.lax.sort(
        (ts, hit.astype(jnp.int32)), num_keys=1, is_stable=False)
    mask = hit2[L:].astype(bool)
    return mask & ok_row


@jax.jit
def wk_walk_merge_member_binsearch(sorted_list, real_len, cur, n, live):
    """k2c membership for SMALL frontiers: binary-search each row in the
    sorted const list (O(C log L) sorted gathers) instead of merge-sorting
    the whole list with the frontier (wk_walk_merge_member_list pays
    O((L + C) log) per call — at LUBM-2560 a 2^22-member type list
    re-sorts for a 16K-row frontier). Returns a bool mask in INPUT row
    order; search depth derives from the list's padded length (static
    shape)."""
    L = sorted_list.shape[0]
    depth = max(int(L - 1).bit_length(), 1)
    C = cur.shape[0]
    rows = jnp.arange(C, dtype=jnp.int32)
    ok_row = (rows < n) & live
    curm = jnp.where(ok_row, cur, INT32_MAX)
    lo = jnp.zeros(C, jnp.int32)
    hi = jnp.broadcast_to(real_len.astype(jnp.int32), (C,))
    ok = _range_member(sorted_list, lo, hi, curm, depth)
    return ok & ok_row


@jax.jit
def wk_walk_merge_member_pairs(ekey, eval_, e_real, cur, vals, n, live):
    """known_to_known: does edge (cur[i] -> vals[i]) exist? ekey/eval_ are the
    segment's per-edge (key, neighbor) pairs, lex-sorted (CSR order). Returns
    a bool mask in INPUT row order. Gather-free (num_keys=3 sort).
    """
    E = ekey.shape[0]
    C = cur.shape[0]
    rows = jnp.arange(C, dtype=jnp.int32)
    ok_row = (rows < n) & live
    curm = jnp.where(ok_row, cur, INT32_MAX)
    valm = jnp.where(ok_row, vals, INT32_MAX)
    epad = jnp.arange(E, dtype=jnp.int32) < e_real
    ek = jnp.where(epad, ekey, INT32_MAX - 1)
    ev = jnp.where(epad, eval_, INT32_MAX - 1)
    keys = jnp.concatenate([ek, curm])
    vv = jnp.concatenate([ev, valm])
    tag = jnp.concatenate([jnp.arange(E, dtype=jnp.int32),
                           jnp.arange(E, E + C, dtype=jnp.int32)])
    ks, vs, ts = jax.lax.sort((keys, vv, tag), num_keys=3, is_stable=False)
    is_rel = ts < E
    hit = _run_head_match(ks, vs[1:] == vs[:-1], is_rel)
    hit = hit & (~is_rel)
    ts2, hit2 = jax.lax.sort(
        (ts, hit.astype(jnp.int32)), num_keys=1, is_stable=False)
    mask = hit2[E:].astype(bool)
    return mask & ok_row


@jax.jit
def wk_walk_merge_gather_col(col, parent):
    """Materialize a column one parent-hop down: col[parent]."""
    L = col.shape[0]
    return col[jnp.clip(parent, 0, L - 1)]


@partial(jax.jit, static_argnames=("cap_out",))
def wk_walk_merge_compact(vals, parent, keep, n, cap_out):
    """Estimate-driven shrink of a (vals, parent) level: keep surviving rows,
    re-based into a smaller capacity class. Returns (vals', parent', n',
    total) — total rides along for the overflow-retry loop."""
    C = vals.shape[0]
    live = keep & (jnp.arange(C, dtype=jnp.int32) < n)
    total = live.sum().astype(jnp.int32)
    idx = jnp.nonzero(live, size=cap_out, fill_value=C - 1)[0]
    ok = jnp.arange(cap_out, dtype=jnp.int32) < total
    return (jnp.where(ok, vals[idx], 0),
            jnp.where(ok, parent[idx], 0),
            jnp.minimum(total, cap_out).astype(jnp.int32), total)


@partial(jax.jit, static_argnames=("B", "r", "slice_mode"))
def wk_walk_merge_qid_counts(pos0, n, live, B, r, slice_mode):
    """Per-qid surviving row counts from composed space-0 positions.

    replicate mode: qid = pos0 // r (r = real index length); slice mode:
    qid = min(pos0 // r, B-1) (r = ceil(len / B)). Blind-mode finish."""
    C = pos0.shape[0]
    ok = (jnp.arange(C, dtype=jnp.int32) < n) & live
    qid = pos0 // jnp.int32(max(r, 1))
    if slice_mode:
        qid = jnp.minimum(qid, B - 1)
    qid = jnp.where(ok, qid, B)
    return jnp.bincount(qid, length=B + 1)[:B]


def next_capacity(total: int, cap_min: int = 1024,
                  cap_max: int | None = None) -> int:
    """Smallest capacity class holding `total` rows (ceiling from config)."""
    if cap_max is None:
        from wukong_tpu.config import Global

        cap_max = Global.table_capacity_max
    c = cap_min
    while c < total and c < cap_max:
        c <<= 1
    return c
