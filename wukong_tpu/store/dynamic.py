"""Dynamic (incremental) store: online bulk insertion — the DynamicGStore role.

The reference's dynamic store (core/store/dynamic_gstore.hpp) swaps the bump
allocator for a real allocator so `load -d <dir>` can insert triples online
(insert_triple_out/in, :537/:603), with lease-based invalidation so remote
RDMA-cached reads stay safe. On TPU the RDMA lease machinery disappears
(SURVEY §7.7): inserts append to per-segment DELTA buffers (O(batch) plus a
membership probe for dedup — never an O(segment) rebuild per batch), and the
merged CSR materializes lazily on first read after a write epoch. Each batch
bumps a store version; device-side caches compare versions and restage lazily.

New predicates/types create new segments/indexes, matching DynamicLoader's
support for unseen predicates (core/loader/dynamic_loader.hpp).
"""

from __future__ import annotations

import numpy as np

from wukong_tpu.store.gstore import GStore, _pred_runs, _triple_argsort
from wukong_tpu.store.segment import CSRSegment
from wukong_tpu.types import IN, NORMAL_ID_START, OUT, TYPE_ID
from wukong_tpu.utils.mathutil import hash_mod


class DeltaCSRSegment:
    """CSR segment with append-only delta buffers (dynamic_gstore.hpp's role,
    redesigned): writes append (key, value) runs; reads materialize the
    merged CSR once per write epoch. Duck-types CSRSegment — every consumer
    (engines, device staging, checker, persistence) sees merged arrays.
    """

    __slots__ = ("_base", "_pending", "_n_pending", "_pending_set")

    def __init__(self, base: CSRSegment | None):
        self._base = base if base is not None else CSRSegment.empty()
        self._pending: list = []
        self._n_pending = 0
        self._pending_set: set = set()  # O(1) dedup probes into the deltas

    # ---- writes ----------------------------------------------------------
    def append(self, ks: np.ndarray, vs: np.ndarray, dedup: bool) -> int:
        """Append a batch; with dedup, pairs already present (in the base,
        the pending deltas, or earlier in the batch) are dropped. O(batch)
        plus a base membership probe — never re-scans prior deltas. Returns
        the number of edges actually appended."""
        if dedup:
            if len(ks):
                pairs = np.stack([ks, vs], axis=1)
                pairs = np.unique(pairs, axis=0)  # in-batch dups
                ks, vs = pairs[:, 0], pairs[:, 1]
            keep = ~self._base.contains_pair(ks, vs)
            if self._pending_set:
                ps = self._pending_set
                keep &= np.fromiter(
                    ((int(k), int(v)) not in ps for k, v in zip(ks, vs)),
                    dtype=bool, count=len(ks))
            ks, vs = ks[keep], vs[keep]
        if len(ks):
            ks = np.asarray(ks, np.int64)
            vs = np.asarray(vs, np.int64)
            self._pending.append((ks, vs))
            self._n_pending += len(ks)
            self._pending_set.update(zip(ks.tolist(), vs.tolist()))
        return int(len(ks))

    # ---- lazy materialization -------------------------------------------
    def _mat(self) -> CSRSegment:
        if self._pending:
            bk = np.repeat(self._base.keys, np.diff(self._base.offsets))
            all_k = np.concatenate([bk] + [p[0] for p in self._pending])
            all_v = np.concatenate([self._base.edges]
                                   + [p[1] for p in self._pending])
            order = np.lexsort((all_v, all_k))
            k, v = all_k[order], all_v[order]
            keys, counts = np.unique(k, return_counts=True)
            offsets = np.zeros(len(keys) + 1, dtype=np.int64)
            np.cumsum(counts, out=offsets[1:])
            # no pair-dedup here: dedup appends were filtered at write time,
            # non-dedup appends legitimately keep duplicates
            self._base = CSRSegment(keys=keys, offsets=offsets, edges=v)
            self._pending.clear()
            self._pending_set.clear()
            self._n_pending = 0
        return self._base

    # ---- CSRSegment interface -------------------------------------------
    @property
    def keys(self):
        return self._mat().keys

    @property
    def offsets(self):
        return self._mat().offsets

    @property
    def edges(self):
        return self._mat().edges

    @property
    def num_keys(self) -> int:
        return self._mat().num_keys

    @property
    def max_degree(self) -> int:
        return self._mat().max_degree

    @property
    def num_edges(self) -> int:  # exact without materializing
        return self._base.num_edges + self._n_pending

    def lookup(self, vid: int):
        return self._mat().lookup(vid)

    def lookup_many(self, vids):
        return self._mat().lookup_many(vids)

    def contains_pair(self, vids, vals):
        return self._mat().contains_pair(vids, vals)

    def memory_bytes(self) -> int:
        return self._base.memory_bytes() + 16 * self._n_pending


def insert_triples(g: GStore, triples: np.ndarray, dedup: bool = True,
                   check_ids: bool = True) -> int:
    """Insert an [N,3] batch into this partition. Returns #edges inserted
    (subject-side copies; the object-side copies are inserted symmetrically).

    Bumps g.version so device caches restage affected segments.
    """
    from wukong_tpu.runtime import faults

    # fault hook BEFORE any mutation: an injected transient leaves the store
    # untouched, so the ingest path's retry replays the batch safely
    faults.site("dynamic.insert", shard=g.sid)
    if check_ids:
        from wukong_tpu.store.gstore import check_vid_range

        check_vid_range(triples)
    s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]
    n = g.num_workers
    mine_out = hash_mod(s, n) == g.sid
    mine_in = (hash_mod(o, n) == g.sid) & (o >= NORMAL_ID_START)

    so, po, oo = s[mine_out], p[mine_out], o[mine_out]
    si, pi, oi = s[mine_in], p[mine_in], o[mine_in]

    order = _triple_argsort(po, so, oo)
    so, po, oo = so[order], po[order], oo[order]
    inserted = 0
    for pid, ks, vs in _pred_runs(po, so, oo):
        inserted += _merge_into(g, (pid, OUT), ks, vs, dedup)
        if pid == TYPE_ID:
            for t in np.unique(vs):
                members = np.unique(ks[vs == t])
                old = g.index.get((int(t), IN), np.empty(0, dtype=np.int64))
                g.index[(int(t), IN)] = np.union1d(old, members)
                g.type_ids.add(int(t))
        else:
            old = g.index.get((pid, IN), np.empty(0, dtype=np.int64))
            g.index[(pid, IN)] = np.union1d(old, np.unique(ks))

    order = _triple_argsort(pi, oi, si)
    si, pi, oi = si[order], pi[order], oi[order]
    for pid, ks, vs in _pred_runs(pi, oi, si):
        _merge_into(g, (pid, IN), ks, vs, dedup)
        old = g.index.get((pid, OUT), np.empty(0, dtype=np.int64))
        g.index[(pid, OUT)] = np.union1d(old, np.unique(ks))

    # versatile structures
    if g.vp:
        g.vp[OUT] = _merge_seg(g.vp.get(OUT), s[mine_out], p[mine_out], True)
        g.vp[IN] = _merge_seg(g.vp.get(IN), oi, pi, True)
        g.v_set = np.union1d(g.v_set, np.concatenate([s[mine_out], oi]))
        tmask = p[mine_out] == TYPE_ID
        g.t_set = np.union1d(g.t_set, o[mine_out][tmask])
        g.p_set = np.union1d(
            g.p_set, np.unique(np.concatenate([p[mine_out][~tmask], pi])))

    g.version = getattr(g, "version", 0) + 1
    return int(inserted)


def _merge_into(g: GStore, key, ks, vs, dedup: bool) -> int:
    seg = g.segments.get(key)
    if not isinstance(seg, DeltaCSRSegment):
        seg = DeltaCSRSegment(seg)
        g.segments[key] = seg
    return seg.append(np.asarray(ks, np.int64), np.asarray(vs, np.int64),
                      dedup)


def _merge_seg(seg, ks, vs, dedup: bool) -> DeltaCSRSegment:
    if not isinstance(seg, DeltaCSRSegment):
        seg = DeltaCSRSegment(seg)
    seg.append(np.asarray(ks, np.int64), np.asarray(vs, np.int64), dedup)
    return seg


# ---------------------------------------------------------------------------
# migration dual-write sinks (runtime/migration.py)
# ---------------------------------------------------------------------------
# In-flight shard-migration recipients that must observe every committed
# mutation between catch-up and cutover. Enroll/deroll run under the WAL
# mutation lock (the migration executor's catch-up/cutover critical
# sections), and every consulting write path — insert_batch_into below,
# StreamIngestor.commit_epoch — reads the dict INSIDE the same lock, so an
# enrolled recipient can never miss, or double-observe, a committed batch.
_MIGRATION_SINKS: dict = {}  # guarded by: mutation_lock()


def enroll_migration_sink(key, store) -> None:  # caller holds: mutation_lock()
    _MIGRATION_SINKS[key] = store


def deroll_migration_sink(key) -> None:  # caller holds: mutation_lock()
    _MIGRATION_SINKS.pop(key, None)


def migration_sinks() -> list:  # caller holds: mutation_lock()
    """The current dual-write targets (empty list when no migration is in
    flight — the common case pays one dict check per batch)."""
    return list(_MIGRATION_SINKS.values())


def load_dir_into(stores: list[GStore], dirname: str, dedup: bool = True) -> int:
    """`load -d <dir>`: read id-triple files and insert into every partition
    (the RDFEngine::execute_load_data path, core/engine/rdf.hpp)."""
    from wukong_tpu.loader.base import load_triples

    from wukong_tpu.store.gstore import check_vid_range

    triples = load_triples(dirname)
    check_vid_range(triples)  # once, not per store
    return insert_batch_into(stores, triples, dedup)


def insert_batch_into(stores: list[GStore], triples: np.ndarray,
                      dedup: bool = True) -> int:
    """One durable batch insert into every partition: the WAL append hook
    fires BEFORE any store mutates, so an acknowledged batch is always
    replayable and a WAL failure leaves the stores untouched. The mutation
    lock keeps the append + fan-out atomic w.r.t. checkpoint
    serialization (runtime/recovery.py)."""
    from wukong_tpu.obs.reuse import maybe_note_invalidation
    from wukong_tpu.serve import notify_mutation
    from wukong_tpu.store.wal import maybe_wal_append, mutation_lock

    with mutation_lock():
        maybe_wal_append("insert", triples, dedup)
        total = 0
        for g in stores:
            total += insert_triples(g, triples, dedup, check_ids=False)
        # dual-write: an in-flight migration's recipient mirrors the batch
        # (each sink hashes out its own shard's rows). Excluded from the
        # returned total: the count answers "how many new edges landed",
        # and the sink is a transient mirror of a store already counted
        for g in migration_sinks():
            insert_triples(g, triples, dedup, check_ids=False)
        # the serving plane's actuator edge (wukong_tpu/serve/): INSIDE
        # the mutation lock, so view maintenance re-keys surviving cache
        # entries atomically with the version bump — a view is never
        # visible at a version it doesn't match. One knob check when the
        # result cache is off.
        if stores:
            notify_mutation("insert",
                            version=getattr(stores[0], "version", 0),
                            triples=triples)
    # cache-coherence telemetry (obs/reuse.py): the batch's version edge
    # kills the stale shadow keys and lands one cache.invalidate event.
    # Outside the mutation lock — the journal emit is pure observability
    # and must never extend the write stall
    if stores:
        maybe_note_invalidation(
            "insert", version=getattr(stores[0], "version", 0),
            n_triples=int(len(triples)))
    return total
