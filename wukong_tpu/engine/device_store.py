"""Device-resident CSR segment store — the GPUCache analogue.

The reference stages gstore segments into GPU HBM with block-mapping tables and
pattern-aware eviction (core/gpu/gpu_cache.hpp). On TPU the natural unit is the
whole CSR segment as dense arrays; XLA needs static shapes, so arrays are padded
to power-of-two length classes (bounding kernel recompiles) and cached by
(pid, dir). A byte budget with LRU eviction plays the role of the reference's
block free lists; queries pin the segments of their remaining patterns
(gpu_cache.hpp conflict-aware eviction) via `pin`/`unpin`.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from wukong_tpu.obs.device import maybe_device_resident
from wukong_tpu.obs.trace import trace_event
from wukong_tpu.types import IN, OUT, PREDICATE_ID, TYPE_ID

INT32_MAX = np.iinfo(np.int32).max


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


BUCKET = 8  # 8-way associative buckets (matching the reference's cluster size,
#             gstore.hpp ASSOCIATIVITY) — one bucket row = one contiguous 32B load


@dataclass
class DeviceSegment:
    """One (pid, dir) CSR segment staged on device, keyed by an 8-way bucketized
    hash table (the reference probes 8-slot cluster-chaining buckets for the
    same locality reason — gstore.hpp:55-120, gpu_hash.cu:149-260; binary
    search over sorted keys lowers to a slow ~21-round scan loop on TPU, and
    random-gather rounds dominate, so the design minimizes probe rounds).

    Bucket arrays are stored FLAT [NB*8]: a [NB, 8] layout would pad the minor
    dim to 128 lanes on TPU (16x HBM waste — see tpu_kernels.py LAYOUT RULE)."""

    bkey: object  # jnp int32 [NB*8] bucket keys; empty = -1
    bstart: object  # jnp int32 [NB*8] edge range start
    bdeg: object  # jnp int32 [NB*8] edge range length
    edges: object  # jnp int32 [E_pad], padded with INT32_MAX
    num_keys: int
    num_edges: int
    max_probe: int  # static probe-round bound — part of the jit key
    max_deg_log2: int  # static binary-search depth for membership tests
    # VERSATILE combined segments carry a second aligned edge array: the
    # per-edge PREDICATE ids (edges = neighbor values) — expand2 gathers both
    edges2: object = None
    fpw0: object = None  # jnp int32 [NB] packed lane-0..3 fingerprints
    fpw1: object = None  # jnp int32 [NB] packed lane-4..7 fingerprints
    max_fp_dup: int = 1  # exact max same-fp count within any bucket (static)

    @property
    def nbytes(self) -> int:
        n = (self.bkey.size + self.bstart.size
             + self.bdeg.size + self.edges.size) * 4
        if self.edges2 is not None:
            n += self.edges2.size * 4
        if self.fpw0 is not None:
            n += (self.fpw0.size + self.fpw1.size) * 4
        return n


_HASH_MULT = np.uint32(2654435761)  # Knuth multiplicative hashing
_FP_MULT = np.uint32(0x9E3779B1)  # fingerprint hash (tpu_kernels._fp_of)


def fp_words(bkey_2d: np.ndarray):
    """Pack per-slot 8-bit key fingerprints into two int32 words per bucket.

    Returns (fpw0 [NB], fpw1 [NB], max_fp_dup). Fingerprints are 1..255 (0 =
    empty slot); max_fp_dup is the EXACT max count of identical fingerprints
    within any single bucket — the static number of candidate verifications
    the fp probe needs for zero false negatives (tpu_kernels._hash_find_fp).
    """
    fp = ((bkey_2d.astype(np.int64).astype(np.uint32) * _FP_MULT) >> 24) \
        & np.uint32(0xFF)
    fp = np.where(fp == 0, 1, fp).astype(np.uint32)
    fp = np.where(bkey_2d < 0, np.uint32(0), fp)
    w0 = fp[:, 0] | (fp[:, 1] << 8) | (fp[:, 2] << 16) | (fp[:, 3] << 24)
    w1 = fp[:, 4] | (fp[:, 5] << 8) | (fp[:, 6] << 16) | (fp[:, 7] << 24)
    srt = np.sort(fp, axis=1)
    same = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] != 0)
    dup = 1
    if same.any():
        cur = np.ones(fp.shape[0], dtype=np.int64)
        maxr = np.ones(fp.shape[0], dtype=np.int64)
        for j in range(same.shape[1]):
            cur = np.where(same[:, j], cur + 1, 1)
            maxr = np.maximum(maxr, cur)
        dup = int(maxr.max())
    return w0.view(np.int32), w1.view(np.int32), dup


def fold_key(filters) -> tuple:
    """Canonical cache-key form of a fold's (pid, dir, const) filter list.
    THE single definition — filtered_merge_segment's cache key, the chain
    pins, and the bench roofline model all look segments up by it; a second
    hand-written copy that drifted would silently miss the cache."""
    return tuple(sorted((int(p), int(dd), int(c)) for (p, dd, c) in filters))


def combined_adjacency(g, d: int):
    """(keys, offsets, vals, pids) of one partition's COMBINED adjacency in
    direction d: every (predicate, neighbor) edge keyed by vid, predicate-
    ordered within each vid (stable sort; per-predicate parts are appended
    pid-ascending). OUT includes rdf:type edges, IN excludes — matching the
    host vp-list semantics (gstore.py). Shared by the single-chip and
    sharded VERSATILE stagings."""
    parts_v, parts_p, parts_w = [], [], []
    for (pid, dd), host in sorted(g.segments.items()):
        if int(dd) != int(d) or len(host.edges) == 0:
            continue
        degs = host.offsets[1:] - host.offsets[:-1]
        parts_v.append(np.repeat(np.asarray(host.keys, np.int64), degs))
        parts_p.append(np.full(len(host.edges), int(pid), np.int64))
        parts_w.append(np.asarray(host.edges, np.int64))
    if not parts_v:
        return (np.empty(0, np.int64), np.zeros(1, np.int64),
                np.empty(0, np.int64), np.empty(0, np.int64))
    v = np.concatenate(parts_v)
    p = np.concatenate(parts_p)
    w = np.concatenate(parts_w)
    order = np.argsort(v, kind="stable")
    v, p, w = v[order], p[order], w[order]
    keys, counts = np.unique(v, return_counts=True)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    return keys, offsets, w, p


def type_index_csr(g):
    """(keys, offsets, edges) of a partition's type index as one CSR keyed by
    type id — shared by the single-chip and sharded stores."""
    pairs = [(t, g.index[(t, IN)]) for t in sorted(g.type_ids)]
    if not pairs:
        return (np.empty(0, np.int64), np.zeros(1, np.int64),
                np.empty(0, np.int64))
    keys = np.asarray([t for t, _ in pairs], dtype=np.int64)
    counts = np.asarray([len(v) for _, v in pairs], dtype=np.int64)
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    edges = np.concatenate([v for _, v in pairs])
    return keys, offsets, edges


def build_hash_table(keys: np.ndarray, offsets: np.ndarray,
                     num_buckets: int | None = None):
    """Host-side bucketized table build (vectorized placement rounds).

    Returns (bkey [NB,8], bstart, bdeg, max_probe). Bucket count is sized for
    <=50% load so nearly all keys land in their home bucket (max_probe 1-2).
    Pass num_buckets to force a shared bucket count across shards (SPMD).
    """
    K = len(keys)
    NB = num_buckets or max(_next_pow2((K + BUCKET // 2 - 1) // (BUCKET // 2)), 2)
    # native fast path (bit-identical placement policy)
    from wukong_tpu.native import build_bucket_table_native

    nat = build_bucket_table_native(np.asarray(keys), np.asarray(offsets), NB)
    if nat is not None:
        return nat
    bmask = np.uint32(NB - 1)
    bkey = np.full((NB, BUCKET), -1, dtype=np.int32)
    bstart = np.zeros((NB, BUCKET), dtype=np.int32)
    bdeg = np.zeros((NB, BUCKET), dtype=np.int32)
    if K == 0:
        return bkey, bstart, bdeg, 1
    starts = offsets[:-1].astype(np.int64)
    degs = (offsets[1:] - offsets[:-1]).astype(np.int64)
    hb = (keys.astype(np.uint32) * _HASH_MULT) & bmask
    used = np.zeros(NB, dtype=np.int64)
    pending = np.arange(K)
    round_ = 0
    while len(pending):
        tb = ((hb[pending] + np.uint32(round_)) & bmask).astype(np.int64)
        order = np.argsort(tb, kind="stable")
        tbs = tb[order]
        # rank within each same-bucket group this round
        idx = np.arange(len(tbs))
        begins = np.flatnonzero(np.concatenate([[True], tbs[1:] != tbs[:-1]]))
        group_id = np.cumsum(np.concatenate([[0], (tbs[1:] != tbs[:-1]).astype(int)]))
        rank = idx - begins[group_id]
        lane = used[tbs] + rank
        ok = lane < BUCKET
        rows = tbs[ok]
        lanes = lane[ok]
        kidx = pending[order[ok]]
        bkey[rows, lanes] = keys[kidx]
        bstart[rows, lanes] = starts[kidx]
        bdeg[rows, lanes] = degs[kidx]
        np.add.at(used, rows, 1)
        placed = np.zeros(len(pending), dtype=bool)
        placed[order[ok]] = True
        pending = pending[~placed]
        round_ += 1
        if round_ > NB:
            raise RuntimeError("bucket hash build failed to converge")
    return bkey, bstart, bdeg, max(round_, 1)


@dataclass
class MergeSegment:
    """One (pid, dir) CSR segment staged for the sort-merge kernels: sorted
    key/start/deg arrays (padded with INT32_MAX / 0) plus the per-edge
    lex-sorted (key, neighbor) pairs for pair-membership joins. The merge
    path needs sorted order, not buckets — this is the gather-free twin of
    DeviceSegment (see tpu_kernels.py sort-merge rationale)."""

    skey: object  # jnp int32 [K_pad] sorted keys, pad INT32_MAX
    sstart: object  # jnp int32 [K_pad] edge range starts, pad 0
    sdeg: object  # jnp int32 [K_pad] edge range lengths, pad 0
    edges: object  # jnp int32 [E_pad]
    ekey: object  # jnp int32 [E_pad] per-edge key (repeat of skey by degree)
    num_keys: int
    num_edges: int

    @property
    def nbytes(self) -> int:
        return (self.skey.size * 3 + self.edges.size + self.ekey.size) * 4


class DeviceStore:
    """Stages host CSR segments into device memory on demand."""

    def __init__(self, gstore, budget_bytes: int | None = None, device=None):
        import jax

        self.g = gstore
        self.device = device or jax.devices()[0]
        self.budget = budget_bytes
        self._cache: dict = {}  # (pid, dir) -> DeviceSegment
        self._index_cache: dict = {}  # ("idx", tpid, dir) -> (jnp arr, real_len)
        #   (the "idx" prefix keeps index keys distinct from segment (pid, dir)
        #    keys in the shared LRU/pin bookkeeping)
        self._lru: list = []
        self._pinned: set = set()
        self.bytes_used = 0
        self.versatile_hits = 0  # times a combined segment was served —
        # an eviction-proof witness that the device versatile arm ran
        # (the staging itself can exceed the cache budget and be evicted
        # right after unpinning, so cache presence is not evidence)

    # ---- segment staging -------------------------------------------------
    def _check_version(self) -> None:
        """Dynamic inserts bump the host store's version; drop stale stagings
        (replaces the reference's lease-based RDMA-cache invalidation,
        dynamic_gstore.hpp:37-102)."""
        v = getattr(self.g, "version", 0)
        if v != getattr(self, "_seen_version", 0):
            seg_bytes = sum(s.nbytes for s in self._cache.values())
            idx_bytes = max(self.bytes_used - seg_bytes, 0)
            self._cache.clear()
            self._index_cache.clear()
            self._lru.clear()
            self.bytes_used = 0
            self.__dict__.pop("_fcsr_memo", None)  # filtered-CSR host memo
            self._seen_version = v
            # ONE residency edge per kind per store-version bump
            if seg_bytes:
                maybe_device_resident("invalidate", "segment", seg_bytes,
                                      version=int(v))
            if idx_bytes:
                maybe_device_resident("invalidate", "index", idx_bytes,
                                      version=int(v))

    def segment(self, pid: int, d: int) -> DeviceSegment | None:
        """Stage (pid, dir) segment; TYPE_ID IN resolves to the type index CSR."""
        self._check_version()
        key = (int(pid), int(d))
        if key in self._cache:
            self._touch(key)
            return self._cache[key]
        if pid == TYPE_ID and int(d) == IN:
            seg = self._build_type_index_csr()
        else:
            host = self.g.segments.get(key)
            if host is None:
                return None
            seg = self._stage(host.keys, host.offsets, host.edges)
        if seg is not None:
            self._insert(key, seg)
        return seg

    def versatile_segment(self, d: int) -> DeviceSegment | None:
        """Stage the COMBINED adjacency of direction d: one CSR keyed by vid
        whose edges are every (predicate, neighbor) pair — the device form of
        the VERSATILE per-vid predicate lists (gstore.hpp:890-903) that the
        reference only ever walks on the CPU (sparql.hpp:601-650; its GPU
        engine refuses the shape). Built from the direction's per-predicate
        segments (vp lists enumerate exactly the predicates with edges);
        expand2 probes it and binds both the predicate and the neighbor."""
        self._check_version()
        key = ("vpv", int(d))
        if key in self._cache:
            self._touch(key)
            self.versatile_hits += 1
            return self._cache[key]
        import jax
        import jax.numpy as jnp

        keys, offsets, w, p = combined_adjacency(self.g, d)
        if len(keys) == 0:
            return None
        self.versatile_hits += 1
        seg = self._stage(keys, offsets, w)
        Ep = seg.edges.shape[0]
        p_pad = np.full(Ep, INT32_MAX, dtype=np.int32)
        p_pad[: len(p)] = p
        seg.edges2 = jax.device_put(jnp.asarray(p_pad), self.device)
        self._insert(key, seg)
        return seg

    def index_list(self, tpid: int, d: int):
        """Index edge list (type members / pred subjects-objects) on device."""
        self._check_version()
        key = ("idx", int(tpid), int(d))
        if key in self._index_cache:
            self._touch(key)
            return self._index_cache[key]
        arr = np.asarray(self.g.get_index(tpid, d), dtype=np.int32)
        room = 0
        if int(d) == IN and int(tpid) in self.g.type_ids:
            # padded as its heaviest peer's list is: the length a kernel is
            # compiled for is then not the drawn type's own
            room = len(self.g.get_index(
                self.g.heaviest_peer_type(tpid), d))
        return self._stage_list(key, arr, room)

    def _stage_list(self, key, arr: np.ndarray, room: int = 0):
        """Pad + device_put a host list and account it in the LRU/budget."""
        import jax.numpy as jnp

        pad = _next_pow2(max(len(arr), room))
        padded = np.full(pad, INT32_MAX, dtype=np.int32)
        padded[: len(arr)] = arr
        dev = jnp.asarray(padded)
        entry = (dev, len(arr))
        self._index_cache[key] = entry
        self._lru.append(key)
        self.bytes_used += dev.size * 4
        maybe_device_resident("fill", "index", dev.size * 4)
        trace_event("device.stage", segment=str(key), bytes=dev.size * 4)
        self._enforce_budget()
        return entry

    def _host_csr(self, pid: int, d: int):
        """(keys, offsets, edges) of a (pid, dir) host CSR, or None;
        TYPE_ID IN resolves to the type index CSR."""
        if int(pid) == TYPE_ID and int(d) == IN:
            keys, offsets, edges = type_index_csr(self.g)
            return (keys, offsets, edges) if len(keys) else None
        host = self.g.segments.get((int(pid), int(d)))
        if host is None:
            return None
        return host.keys, host.offsets, host.edges

    def merge_segment(self, pid: int, d: int) -> MergeSegment | None:
        """Stage (pid, dir) for the sort-merge kernels (sorted arrays +
        per-edge key pairs); TYPE_ID IN resolves to the type index CSR."""
        self._check_version()
        key = ("mrg", int(pid), int(d))
        if key in self._cache:
            self._touch(key)
            return self._cache[key]
        csr = self._host_csr(pid, d)
        if csr is None:
            return None
        seg = self._stage_merge(*csr)
        self._insert(key, seg)
        return seg

    def _stage_merge(self, keys, offsets, edges) -> MergeSegment:
        import jax
        import jax.numpy as jnp

        K, E = len(keys), len(edges)
        Kp, Ep = _next_pow2(K), _next_pow2(E)
        sk = np.full(Kp, INT32_MAX, dtype=np.int32)
        sk[:K] = keys
        ss = np.zeros(Kp, dtype=np.int32)
        ss[:K] = offsets[:-1]
        sd = np.zeros(Kp, dtype=np.int32)
        sd[:K] = offsets[1:] - offsets[:-1]
        e = np.full(Ep, INT32_MAX, dtype=np.int32)
        e[:E] = edges
        ek = np.full(Ep, INT32_MAX, dtype=np.int32)
        ek[:E] = np.repeat(np.asarray(keys, dtype=np.int32),
                           (offsets[1:] - offsets[:-1]).astype(np.int64))
        dev = lambda a: jax.device_put(jnp.asarray(a), self.device)
        return MergeSegment(skey=dev(sk), sstart=dev(ss), sdeg=dev(sd),
                            edges=dev(e), ekey=dev(ek),
                            num_keys=K, num_edges=E)

    def host_num_keys(self, pid: int, d: int) -> int:
        """Key count of a (pid, dir) segment from HOST metadata only — the
        merge chain's sort-vs-probe lookup dispatch reads just this scalar,
        so the decision never stages anything. TYPE_ID IN resolves to the
        type-index CSR, whose key set is exactly the partition's type ids."""
        self._check_version()
        if int(pid) == TYPE_ID and int(d) == IN:
            return len(self.g.type_ids)
        host = self.g.segments.get((int(pid), int(d)))
        return host.num_keys if host is not None else 0

    def host_num_edges(self, pid: int, d: int) -> int:
        """Edge count of a (pid, dir) segment from HOST metadata only (the
        membership sort-vs-probe dispatch: merge_member_pairs sorts the
        whole per-edge pair arrays)."""
        self._check_version()
        if int(pid) == TYPE_ID and int(d) == IN:
            return sum(len(self.g.get_index(t, IN)) for t in self.g.type_ids)
        host = self.g.segments.get((int(pid), int(d)))
        return host.num_edges if host is not None else 0

    def _filtered_host_csr(self, pid: int, d: int, fkey: tuple):
        """Host CSR of (pid, d) with edges restricted to targets satisfying
        every (fpid, fd, fconst) k2c filter — shared by the merge-form and
        bucket-form filtered stagings. O(E log M) searchsorted membership,
        memoized per (pid, d, fkey): a sort-vs-probe flip during capacity
        learning stages BOTH forms, and the scan must not run twice."""
        memo_key = (int(pid), int(d), fkey)
        if not hasattr(self, "_fcsr_memo"):
            self._fcsr_memo = {}
        if memo_key in self._fcsr_memo:
            return self._fcsr_memo[memo_key]
        csr = self._filtered_host_csr_build(pid, d, fkey)
        if len(self._fcsr_memo) > 64:  # bound the HOST-side copies
            self._fcsr_memo.clear()
        self._fcsr_memo[memo_key] = csr
        return csr

    def _filtered_host_csr_build(self, pid: int, d: int, fkey: tuple):
        csr = self._host_csr(pid, d)
        if csr is None:
            return None
        keys, offsets, edges = csr
        edges = np.asarray(edges)
        mask = np.ones(len(edges), dtype=bool)
        for (fp, fd, fc) in fkey:
            allowed = self._const_members(fp, fd, fc)
            if len(allowed) == 0:
                mask[:] = False
                break
            # allowed is sorted: O(E log M) membership, no big re-sort
            pos = np.searchsorted(allowed, edges)
            pos = np.clip(pos, 0, len(allowed) - 1)
            mask &= allowed[pos] == edges
        # per-key surviving counts without a Python loop
        csum = np.concatenate([[0], np.cumsum(mask)])
        new_deg = csum[offsets[1:]] - csum[offsets[:-1]]
        keep_key = new_deg > 0
        fkeys = np.asarray(keys)[keep_key]
        fdeg = new_deg[keep_key]
        foffs = np.zeros(len(fkeys) + 1, dtype=np.int64)
        np.cumsum(fdeg, out=foffs[1:])
        fedges = np.asarray(edges)[mask]
        return fkeys, foffs, fedges

    def filtered_merge_segment(self, pid: int, d: int,
                               filters: list) -> MergeSegment | None:
        """Merge segment of (pid, d) with edges restricted to targets that
        satisfy every (fpid, fd, fconst) k2c filter — the device analogue of
        the reference planner's type-centric pruning (planner.hpp type
        tables): an expand followed by `?v type T` membership becomes ONE
        expand over the pre-intersected segment. Cached per (pid, d,
        filters)."""
        self._check_version()
        fkey = fold_key(filters)
        key = ("mrgf", int(pid), int(d), fkey)
        if key in self._cache:
            self._touch(key)
            return self._cache[key]
        csr = self._filtered_host_csr(pid, d, fkey)
        if csr is None:
            return None
        seg = self._stage_merge(*csr)
        self._insert(key, seg)
        return seg

    def filtered_segment(self, pid: int, d: int,
                         filters: list) -> DeviceSegment | None:
        """Bucket-form twin of filtered_merge_segment, for the probe-lookup
        expand path (small frontier over a filtered fold). Cached per
        (pid, d, filters) under a distinct key."""
        self._check_version()
        fkey = fold_key(filters)
        key = ("segf", int(pid), int(d), fkey)
        if key in self._cache:
            self._touch(key)
            return self._cache[key]
        csr = self._filtered_host_csr(pid, d, fkey)
        if csr is None:
            return None
        seg = self._stage(*csr)
        if seg is not None:
            self._insert(key, seg)
        return seg

    def _const_members(self, pid: int, d: int, const: int) -> np.ndarray:
        """Host-side sorted { x : const ∈ adj(x, pid, d) } (see const_list)."""
        pid, d, const = int(pid), int(d), int(const)
        if pid == TYPE_ID and d == OUT:
            host = self.g.get_index(const, IN)
        elif pid == TYPE_ID and d == IN:
            host = self.g.get_triples(const, TYPE_ID, OUT)
        elif pid == PREDICATE_ID:
            host = self.g.get_index(const, IN if d == OUT else OUT)
        else:
            host = self.g.get_triples(const, pid, IN if d == OUT else OUT)
        return np.sort(np.asarray(host, dtype=np.int64))

    def const_list(self, pid: int, d: int, const: int):
        """Sorted set { x : const ∈ adj(x, pid, d) } staged on device — the
        k2c merge relation, matching the CPU oracle's _contains_many routing
        (type membership lives in the index, not a (TYPE_ID, IN) segment).
        Returns (device array, real_len)."""
        self._check_version()
        key = ("rev", int(pid), int(d), int(const))
        if key in self._index_cache:
            self._touch(key)
            return self._index_cache[key]
        host = self._const_members(pid, d, const)
        return self._stage_list(key, host.astype(np.int32))

    def _build_type_index_csr(self) -> DeviceSegment | None:
        """Type membership as one CSR keyed by type id (subject-side tidx)."""
        keys, offsets, edges = type_index_csr(self.g)
        if len(keys) == 0:
            return None
        return self._stage(keys, offsets, edges)

    def _stage(self, keys, offsets, edges) -> DeviceSegment:
        import jax
        import jax.numpy as jnp

        K, E = len(keys), len(edges)
        Ep = _next_pow2(E)
        e = np.full(Ep, INT32_MAX, dtype=np.int32)
        e[:E] = edges
        bkey, bstart, bdeg, max_probe = build_hash_table(
            np.asarray(keys), np.asarray(offsets))
        max_deg = int((offsets[1:] - offsets[:-1]).max()) if K else 1
        w0, w1, fp_dup = fp_words(bkey)
        seg = DeviceSegment(
            bkey=jax.device_put(jnp.asarray(bkey.reshape(-1)), self.device),
            bstart=jax.device_put(jnp.asarray(bstart.reshape(-1)), self.device),
            bdeg=jax.device_put(jnp.asarray(bdeg.reshape(-1)), self.device),
            edges=jax.device_put(jnp.asarray(e), self.device),
            num_keys=K, num_edges=E, max_probe=max_probe,
            max_deg_log2=max(int(max_deg).bit_length(), 1),
            fpw0=jax.device_put(jnp.asarray(w0), self.device),
            fpw1=jax.device_put(jnp.asarray(w1), self.device),
            max_fp_dup=fp_dup,
        )
        return seg

    # ---- cache management ------------------------------------------------
    def _insert(self, key, seg: DeviceSegment) -> None:
        self._cache[key] = seg
        self._lru.append(key)
        self.bytes_used += seg.nbytes
        maybe_device_resident("fill", "segment", seg.nbytes)
        # traced, inside a request: a segment put on the device for it
        trace_event("device.stage", segment=str(key), bytes=seg.nbytes)
        self._enforce_budget()

    def _enforce_budget(self, why: str = "budget") -> None:
        if self.budget is not None:
            while self.bytes_used > self.budget and self._evictable():
                self._evict(self._evictable()[0], why)

    def _evictable(self):
        return [k for k in self._lru if k not in self._pinned
                and (k in self._cache or k in self._index_cache)]

    def _evict(self, key, why: str) -> None:
        if key in self._cache:
            nb = self._cache.pop(key).nbytes
            kind = "segment"
        else:
            dev, _ = self._index_cache.pop(key)
            nb = dev.size * 4
            kind = "index"
        self.bytes_used -= nb
        maybe_device_resident("evict", kind, nb)
        # traced, inside a request: the staging this one undoes said
        # ``device.stage``; ``why`` is ``budget`` (room for a new staging)
        # or ``unpin`` (a chain let go of what had kept the store over it)
        trace_event("device.evict", segment=str(key), bytes=nb, why=why)
        self._lru.remove(key)

    def _touch(self, key) -> None:
        if key in self._lru:
            self._lru.remove(key)
            self._lru.append(key)

    @staticmethod
    def _pin_key(k):
        # (pid, d) pins the bucketized staging; ("mrg", pid, d) and
        # ("rev", pid, d, c) pin merge/const-list stagings as-is
        return k if isinstance(k[0], str) else (int(k[0]), int(k[1]))

    def pin(self, keys) -> None:
        self._pinned.update(self._pin_key(k) for k in keys)

    def unpin(self, keys) -> None:
        for k in keys:
            self._pinned.discard(self._pin_key(k))
        self._enforce_budget("unpin")  # pins may have deferred evictions

    def prefetch(self, patterns) -> None:
        """Stage the segments of upcoming pattern steps (async via dispatch)."""
        for p in patterns:
            if p.predicate >= 0:
                self.segment(p.predicate, p.direction)
            else:
                # versatile steps use the combined segment — the LARGEST
                # staging in the chain, exactly what prefetch exists for
                self.versatile_segment(p.direction)
