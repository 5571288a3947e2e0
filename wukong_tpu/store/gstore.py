"""Partitioned in-memory RDF graph store over CSR segments.

Capability-equivalent to the reference's GStore/StaticGStore + DGraph facade
(core/store/gstore.hpp, static_gstore.hpp, core/dgraph.hpp) with the storage
format redesigned for TPU staging (see segment.py). Semantics preserved:

- Partitioning: triple (s, p, o) lives on worker hash(s)%n as an OUT edge and on
  worker hash(o)%n as an IN edge (base_loader.hpp:172-173) — every triple is
  stored twice cluster-wide.
- Type triples (p == TYPE_ID) have index-id objects; they produce the per-vertex
  type list (v, TYPE_ID, OUT) on the subject owner and the *type index*
  tidx[t] -> members on the subject owner (gstore.hpp:875-882 collect_idx_info —
  built from OUT keys, hence subject-side). No (·, TYPE_ID, IN) normal segment
  exists (static_gstore.hpp:127-130 skips type triples on the pos side).
- Predicate indexes: pidx_in[p] = local subjects having p (from OUT keys),
  pidx_out[p] = local objects under p (from IN keys) (gstore.hpp:858-888).
- VERSATILE: per-vertex predicate lists (v, PREDICATE_ID, OUT/IN) — OUT includes
  TYPE_ID (type triples are part of the pso walk, static_gstore.hpp:295-330),
  IN excludes type triples (static_gstore.hpp:331-369); plus v/t/p sets
  (all local entities / types / predicates, static_gstore.hpp:267-279).
- Attributes: per-attr sorted (subject -> typed value) maps (gstore.hpp asv path).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from wukong_tpu.store.segment import CSRSegment
from wukong_tpu.types import IN, NORMAL_ID_START, OUT, PREDICATE_ID, TYPE_ID
from wukong_tpu.utils.mathutil import hash_mod


@dataclass
class AttrSegment:
    keys: np.ndarray  # sorted subject ids
    values: np.ndarray  # typed values (int64 or float64)
    type: int  # AttrType tag

    def lookup(self, vid: int):
        i = np.searchsorted(self.keys, vid)
        if i < len(self.keys) and self.keys[i] == vid:
            return self.values[i], True
        return None, False


class AttrColumns(NamedTuple):
    """Attribute triples as parallel columns, for a generator that makes
    them so: tens of millions of (s, aid, type, value) rows are GBs of
    tuples and a minute of Python in ``build_partition``."""

    subject: np.ndarray
    aid: np.ndarray
    value: np.ndarray
    types: dict  # attribute id -> AttrType tag of its values


@dataclass
class GStore:
    """One worker's partition of the graph."""

    sid: int
    num_workers: int
    # normal segments: (pid, dir) -> CSR; includes (TYPE_ID, OUT) = per-vertex types
    segments: dict = field(default_factory=dict)
    # index lists: (tpid, dir) -> sorted vid array
    #   (pid, IN) = local subjects having pid; (pid, OUT) = local objects under pid
    #   (tid, IN) = local members of type tid
    index: dict = field(default_factory=dict)
    # VERSATILE per-vertex predicate lists: dir -> CSR (key = vid, edges = pids)
    vp: dict = field(default_factory=dict)
    # VERSATILE singleton sets
    v_set: np.ndarray = field(default_factory=lambda: np.empty(0, dtype=np.int64))
    t_set: np.ndarray = field(default_factory=lambda: np.empty(0, dtype=np.int64))
    p_set: np.ndarray = field(default_factory=lambda: np.empty(0, dtype=np.int64))
    # attribute segments: aid -> AttrSegment
    attrs: dict = field(default_factory=dict)
    # which index ids are type ids (objects of rdf:type) vs predicates
    type_ids: set = field(default_factory=set)

    # ---- lookup API (mirrors core/dgraph.hpp:106-145) --------------------
    def get_triples(self, vid: int, pid: int, d: int) -> np.ndarray:
        """Neighbor list of a *local* vertex under a predicate.

        pid == PREDICATE_ID returns the VERSATILE per-vertex predicate list
        (gstore.hpp VERSATILE keys); pid == TYPE_ID with d == OUT returns the
        vertex's types. (TYPE_ID, IN) is not a normal segment — engines must use
        get_index for type membership (sparql.hpp:336-340).
        """
        if pid == PREDICATE_ID:
            seg = self.vp.get(int(d))
            return seg.lookup(vid) if seg is not None else np.empty(0, dtype=np.int64)
        seg = self.segments.get((int(pid), int(d)))
        return seg.lookup(vid) if seg is not None else np.empty(0, dtype=np.int64)

    def get_index(self, tpid: int, d: int) -> np.ndarray:
        """Index lookup: members of a type (d=IN) or subjects/objects of a predicate."""
        if tpid == TYPE_ID and int(d) == IN:
            return self.v_set  # all local entities (VERSATILE v_set)
        if tpid == TYPE_ID and int(d) == OUT:
            return self.t_set
        if tpid == PREDICATE_ID and int(d) == OUT:
            return self.p_set
        return self.index.get((int(tpid), int(d)), np.empty(0, dtype=np.int64))

    def max_degree(self, pid: int, d: int) -> int:
        """The longest edge list of any key of the (pid, dir) segment: what
        the heaviest constant a template could draw expands to."""
        seg = self.segments.get((int(pid), int(d)))
        return seg.max_degree if seg is not None else 0

    def heaviest_peer_type(self, tid: int) -> int:
        """Of ``tid`` and its peers, the type with the most members. Peers
        are the types that are instances of a class ``tid`` is an instance
        of (a class of classes: WatDiv's 15 product categories are the
        instances of ``wsdbm:ProductCategory``); a type with no class is
        its own only peer. A template that draws its type from such a class
        is sized for this one, as a start from a constant is sized for the
        heaviest constant of its segment."""
        best, most = int(tid), len(self.get_index(tid, IN))
        for cls in self.get_triples(tid, TYPE_ID, OUT):
            members = self.get_index(int(cls), IN)
            for peer in members[members < NORMAL_ID_START].tolist():
                n = len(self.index.get((peer, IN), ()))
                if peer in self.type_ids and (n, -peer) > (most, -best):
                    best, most = peer, n
        return best

    def get_attr(self, vid: int, aid: int, d: int = OUT):
        seg = self.attrs.get(int(aid))
        if seg is None:
            return None, False
        return seg.lookup(vid)

    # ---- introspection ---------------------------------------------------
    def memory_bytes(self) -> int:
        n = sum(s.memory_bytes() for s in self.segments.values())
        n += sum(a.nbytes for a in (self.v_set, self.t_set, self.p_set))
        n += sum(s.memory_bytes() for s in self.vp.values())
        n += sum(v.nbytes for v in self.index.values())
        n += sum(a.keys.nbytes + a.values.nbytes for a in self.attrs.values())
        return n

    def stats_str(self) -> str:
        ne = sum(s.num_edges for s in self.segments.values())
        return (f"worker {self.sid}/{self.num_workers}: "
                f"{len(self.segments)} segments, {ne} edges, "
                f"{len(self.index)} index lists, {self.memory_bytes() / 2**20:.1f} MiB")


def owner_of_subject(s: np.ndarray, n: int) -> np.ndarray:
    return hash_mod(s, n)


def check_vid_range(triples: np.ndarray) -> None:
    """Device staging narrows ids to int32 (types.py documents the <2^31
    assumption), and INT32_MAX itself is the device-side padding/dead-row
    sentinel — so ids must stay strictly below 2^31 - 1 or they wrap/collide
    silently into wrong query results. The minimum matters too: the native
    radix sort (wukong_native.cpp) extracts unsigned digits and relies on
    non-negative ids, so a negative id mis-sorts on the native path while
    the np.lexsort fallback orders it correctly — a toolchain-dependent
    store divergence unless rejected here."""
    if len(triples) and int(triples.max()) >= 2**31 - 1:
        from wukong_tpu.utils.errors import ErrorCode, WukongError

        raise WukongError(
            ErrorCode.UNKNOWN_PATTERN,
            f"vertex id {int(triples.max())} >= 2^31 - 1: ids no longer fit "
            "the int32 device representation (see types.py)")
    if len(triples) and int(triples.min()) < 0:
        from wukong_tpu.utils.errors import ErrorCode, WukongError

        raise WukongError(
            ErrorCode.UNKNOWN_PATTERN,
            f"vertex id {int(triples.min())} < 0: ids must be non-negative "
            "(the native radix sort's unsigned-digit contract)")


def _triple_argsort(primary, secondary, tertiary) -> np.ndarray:
    """argsort by (primary, secondary, tertiary) — native radix when available
    (the loader's sorted-run preparation, base_loader.hpp sorts)."""
    from wukong_tpu.native import sort_triples_perm

    perm = sort_triples_perm(primary, secondary, tertiary)
    if perm is not None:
        return perm
    return np.lexsort((tertiary, secondary, primary))


def _pred_runs(p_sorted: np.ndarray, k_sorted: np.ndarray, v_sorted: np.ndarray):
    """Yield (pid, keys, values) slices per predicate run of presorted arrays."""
    if len(p_sorted) == 0:
        return
    upids, starts = np.unique(p_sorted, return_index=True)
    bounds = np.append(starts, len(p_sorted))
    for i, pid in enumerate(upids):
        sl = slice(bounds[i], bounds[i + 1])
        yield int(pid), k_sorted[sl], v_sorted[sl]


def build_partition(triples: np.ndarray, sid: int, num_workers: int,
                    attr_triples=None, versatile: bool = True,
                    check_ids: bool = True) -> GStore:
    """Build worker `sid`'s GStore from the full [M,3] triple array and the
    attribute triples, (s, aid, type, value) rows or ``AttrColumns``.

    The reference reaches the same state via the loader's RDMA shuffle + sorted
    insert (base_loader.hpp:165-219, static_gstore.hpp:383-454); here partition
    selection + CSR building are vectorized numpy over the shared array.
    """
    if check_ids:
        check_vid_range(triples)
    s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]

    def out_side():  # the subject owner's copy
        mine = hash_mod(s, num_workers) == sid
        return s[mine], p[mine], o[mine]

    def in_side():
        # the object side never stores type triples as normal edges (the
        # NORMAL_ID_START test folds into the owner mask: one copy, not two)
        mine = (hash_mod(o, num_workers) == sid) & (o >= NORMAL_ID_START)
        return s[mine], p[mine], o[mine]

    return _assemble(GStore(sid=sid, num_workers=num_workers), out_side,
                     in_side, attr_triples, versatile)


def _owner_runs(owner: np.ndarray, n: int) -> list[np.ndarray]:
    """Row numbers of each owner ``0..n-1``, in row order: one stable sort
    of the owner column (a radix sort on its narrow copy). Rows whose owner
    is ``n`` or more belong to none."""
    narrow = owner.astype(np.uint8 if n < 255 else np.int64)
    order = np.argsort(narrow, kind="stable")
    bounds = np.searchsorted(narrow[order], np.arange(n + 1))
    return [order[bounds[k]:bounds[k + 1]] for k in range(n)]


def build_all_partitions(triples: np.ndarray, num_workers: int,
                         attr_triples=None, versatile: bool = True,
                         threads: int | None = None) -> list[GStore]:
    """Every worker's GStore from one assignment of the triples to their
    owners: each triple goes to its subject's owner as an OUT edge and to
    its object's owner as an IN edge, as ``build_partition`` selects them,
    and the partitions are then built side by side on ``threads`` threads
    (default one a partition; the sorts run outside the GIL). The stores
    are the bytes ``build_partition`` gives, shard by shard: each side's
    rows keep their order."""
    from concurrent.futures import ThreadPoolExecutor

    from wukong_tpu.native import get_lib

    check_vid_range(triples)
    get_lib()  # a first use builds the library: once, not on every thread
    n = num_workers
    s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]
    out_rows = _owner_runs(hash_mod(s, n), n)
    in_rows = _owner_runs(np.where(o >= NORMAL_ID_START, hash_mod(o, n), n),
                          n)

    def build(k: int) -> GStore:
        def side(rows):
            return lambda: (s[rows[k]], p[rows[k]], o[rows[k]])

        g = _assemble(GStore(sid=k, num_workers=n), side(out_rows),
                      side(in_rows), attr_triples, versatile)
        out_rows[k] = in_rows[k] = None  # this shard's row lists are spent
        return g

    with ThreadPoolExecutor(max_workers=threads or n,
                            thread_name_prefix="build-shard") as ex:
        return list(ex.map(build, range(n)))


def _assemble(g: GStore, out_side, in_side, attr_triples,
              versatile: bool) -> GStore:
    """Fill ``g`` from its two sides: ``out_side()`` and ``in_side()`` give
    the (s, p, o) columns of the triples it keeps as OUT and as IN edges."""
    sid, num_workers = g.sid, g.num_workers

    # ---- normal segments + predicate indexes (one sort per side) ---------
    # One direction END-TO-END at a time (slice -> sort -> segments ->
    # free), never both directions' copies plus sort workspace at once:
    # at LUBM-10240 (1.27B triples, int32) the old both-sides-up-front
    # layout peaked past this host's 125 GB and the build OOM-killed.
    # pso order: (p, s, o) — each predicate run becomes one OUT segment
    so, po, oo = out_side()
    order = _triple_argsort(po, so, oo)
    so, po, oo = so[order], po[order], oo[order]
    del order
    for pid, ks, vs in _pred_runs(po, so, oo):
        g.segments[(pid, OUT)] = CSRSegment.from_sorted_pairs(ks, vs)
        if pid != TYPE_ID:
            g.index[(pid, IN)] = g.segments[(pid, OUT)].keys.copy()
    if versatile:  # subject-side versatile pieces, before freeing the copies
        vp_out = CSRSegment.from_pairs(so, po)  # includes TYPE_ID edges
        v_sub = np.unique(so)
        p_out = np.unique(po[po != TYPE_ID])
    del so, po, oo

    # pos order: (p, o, s) — each predicate run becomes one IN segment
    si, pi, oi = in_side()
    order = _triple_argsort(pi, oi, si)
    si, pi, oi = si[order], pi[order], oi[order]
    del order
    for pid, ks, vs in _pred_runs(pi, oi, si):
        g.segments[(pid, IN)] = CSRSegment.from_sorted_pairs(ks, vs)
        g.index[(pid, OUT)] = g.segments[(pid, IN)].keys.copy()

    # ---- type index: t -> local members (subject-side) -------------------
    tseg = g.segments.get((TYPE_ID, OUT))
    if tseg is not None:
        ts = np.repeat(tseg.keys, np.diff(tseg.offsets))
        to = tseg.edges
        order = np.argsort(to, kind="stable")
        ts, to = ts[order], to[order]
        for t, ks, vs in _pred_runs(to, ts, ts):
            g.index[(t, IN)] = np.unique(ks)
            g.type_ids.add(t)

    # ---- VERSATILE -------------------------------------------------------
    if versatile:
        g.vp[OUT] = vp_out
        g.vp[IN] = CSRSegment.from_pairs(oi, pi)
        g.v_set = np.union1d(v_sub, oi)
        g.t_set = (np.unique(tseg.edges) if tseg is not None
                   else np.empty(0, dtype=np.int64))
        g.p_set = np.union1d(p_out, pi)

    # ---- attributes ------------------------------------------------------
    if isinstance(attr_triples, AttrColumns):
        mine = hash_mod(attr_triples.subject, num_workers) == sid
        ks, aids, vs = (attr_triples.subject[mine], attr_triples.aid[mine],
                        attr_triples.value[mine])
        del mine
        order = np.lexsort((vs, ks, aids))
        ks, aids, vs = ks[order], aids[order], vs[order]
        del order
        for aid, k, v in _pred_runs(aids, ks, vs):
            at = attr_triples.types[aid]
            g.attrs[aid] = AttrSegment(
                keys=k.astype(np.int64),  # copies: a run is a view of all
                values=v.astype(np.float64 if at in (2, 3) else np.int64),
                type=at)
    elif attr_triples:
        by_aid: dict[int, list] = {}
        for (asub, aid, at, av) in attr_triples:
            if hash_mod(asub, num_workers) == sid:
                by_aid.setdefault(int(aid), []).append((asub, at, av))
        for aid, rows in by_aid.items():
            rows.sort()
            keys = np.asarray([r[0] for r in rows], dtype=np.int64)
            at = rows[0][1]
            dtype = np.float64 if at in (2, 3) else np.int64
            vals = np.asarray([r[2] for r in rows], dtype=dtype)
            g.attrs[aid] = AttrSegment(keys=keys, values=vals, type=at)

    return g
