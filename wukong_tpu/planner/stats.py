"""Type-centric statistics for the cost-based optimizer.

Mirrors the reference's Stats (core/stats.hpp): per-type entity counts
(`tyscount`), predicate -> subject-type / object-type histograms
(`pstype`/`potype`), and the fine-grained (type, pred, dir) -> neighbor-type
histogram (`fine_type`) — stats.hpp:658-869 walks gstore buckets; here the
whole computation is vectorized over the triple array.

Vertices with multiple types or no type get *complex types* synthesized from
their type-set / predicate-set composition (stats.hpp:46-75 type_t,
get_simple_type 642-655): complex ids are negative to stay clear of real type
ids, and `members_of` exposes the base types a complex type contains (so a
type filter can keep matching complex types).

Persisted to a stat file like the reference's `<input>/statfile`
(stats.hpp:585-640) — ours is an npz bundle.
"""

from __future__ import annotations

import json
from collections import defaultdict

import numpy as np

from wukong_tpu.types import IN, NORMAL_ID_START, OUT, TYPE_ID

# Tables indexed by id (a type a vertex: 8 bytes an id; a flag a predicate
# and id: one byte) replace sorting and searching where each takes no more
# room than the triples the caller already holds, times this: ids as dense
# as the generators and the id-format loaders make them (WatDiv at scale
# factor 1000: 0.11 and 1.2 GB beside 2.6 GB of triples; LUBM-640: 0.17 and
# 0.38 beside 1.9). Sparse ids, or a handful of triples, sort.
DENSE_ROOM = 1.0


class Stats:
    def __init__(self):
        self.tyscount: dict[int, int] = {}  # type id -> #entities
        self.pstype: dict[int, dict[int, int]] = {}  # pid -> {stype: count}
        self.potype: dict[int, dict[int, int]] = {}  # pid -> {otype: count}
        # (type, pid, dir) -> {neighbor_type: edge count}
        self.fine_type: dict[tuple, dict[int, int]] = {}
        self.pred_edges: dict[int, int] = {}  # pid -> total triples
        self.distinct_subj: dict[int, int] = {}  # pid -> #distinct subjects
        self.distinct_obj: dict[int, int] = {}  # pid -> #distinct objects
        # complex type composition: complex id (<0) -> frozenset(base type ids)
        self.complex_members: dict[int, frozenset] = {}
        self.vtype: np.ndarray | None = None  # entity -> (simple|complex) type
        self.vtype_ids: np.ndarray | None = None  # sorted entity ids for vtype

    # ------------------------------------------------------------------
    def type_of(self, vid: int) -> int:
        i = np.searchsorted(self.vtype_ids, vid)
        if i < len(self.vtype_ids) and self.vtype_ids[i] == vid:
            return int(self.vtype[i])
        return 0

    def types_containing(self, base_type: int) -> list[int]:
        """All (simple + complex) type ids whose members include base_type."""
        out = [base_type] if base_type in self.tyscount else []
        for cid, members in self.complex_members.items():
            if base_type in members:
                out.append(cid)
        return out

    def count_containing(self, base_type: int) -> int:
        return sum(self.tyscount.get(t, 0) for t in self.types_containing(base_type))

    # ------------------------------------------------------------------
    @staticmethod
    def generate(triples: np.ndarray) -> "Stats":
        """Build statistics from the full [M,3] id-triple array."""
        st = Stats()
        s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]
        is_type = p == TYPE_ID

        # ---- per-vertex simple/complex type ------------------------------
        max_id = int(max(s.max(), o.max())) if len(triples) else 0
        # ids dense enough for tables indexed by id: a flag or a type a
        # vertex, instead of sorting and searching 10^8 edge endpoints
        room = DENSE_ROOM * triples.nbytes
        dense = 8 * (max_id + 1) <= room
        ts, to = s[is_type], o[is_type]
        order = np.lexsort((to, ts))
        ts, to = ts[order], to[order]
        if len(ts):  # a repeated (vertex, type) pair is one type
            first = np.ones(len(ts), dtype=bool)
            first[1:] = (ts[1:] != ts[:-1]) | (to[1:] != to[:-1])
            ts, to = ts[first], to[first]
        uniq_v, starts = np.unique(ts, return_index=True)
        bounds = np.append(starts, len(ts))
        complex_ids: dict[frozenset, int] = {}
        next_complex = -1
        simple_counts: dict[int, int] = defaultdict(int)
        # single-typed vertices take their type; the others a complex type a
        # distinct type SET, numbered in the order the sets first appear
        # over the vertices by id. No Python object a vertex: at LUBM-10240
        # (220 M typed vertices) the per-vertex frozenset loop OOM-killed
        # the host, and WatDiv types a million users twice (role and class).
        typed_types = to[starts].astype(np.int64) if len(ts) else \
            np.empty(0, dtype=np.int64)
        multi = np.flatnonzero(np.diff(bounds) > 1)
        if len(multi):
            from wukong_tpu.utils.mathutil import hash_u64

            utypes = np.unique(to)
            hmap = np.asarray([hash_u64(int(x)) for x in utypes],
                              dtype=np.uint64)
            mixed = hmap[np.searchsorted(utypes, to)]
            sig = np.add.reduceat(mixed, starts)[multi] \
                + np.diff(bounds)[multi].astype(np.uint64)
            _u, first_at, inv = np.unique(sig, return_index=True,
                                          return_inverse=True)
            by_first = np.argsort(first_at)
            rank = np.empty(len(first_at), dtype=np.int64)
            rank[by_first] = np.arange(len(first_at))
            for k in by_first:
                i = multi[first_at[k]]
                complex_ids[frozenset(
                    int(x) for x in to[bounds[i]:bounds[i + 1]])] = \
                    next_complex - int(rank[k])
            typed_types[multi] = next_complex - rank[inv]
            next_complex -= len(first_at)
        for t, c in zip(*np.unique(typed_types, return_counts=True)):
            simple_counts[int(t)] += int(c)
        # untyped vertices: complex type from their out-predicate set
        if dense:
            seen = np.zeros(max_id + 1, dtype=bool)
            seen[o] = True
            seen[:NORMAL_ID_START] = False
            seen[s] = True
            seen[uniq_v] = False
            untyped = np.flatnonzero(seen)
        else:
            all_vs = np.unique(np.concatenate(
                [s, o[o >= NORMAL_ID_START]]))
            untyped = np.setdiff1d(all_vs, uniq_v)
        untyped_types = np.empty(0, dtype=np.int64)
        if len(untyped):
            # untyped subjects actually carrying out-edges (in LUBM-shaped
            # data the untyped set is literal pools with NO out-edges, so
            # this mask is empty and the whole branch is one shared class).
            # ONE membership pass serves both the branch decision and the
            # vectorized path below — each isin sorts the full edge list.
            # (An untyped subject has no rdf:type edge to leave out.)
            keep = seen[s] if dense else np.isin(s, untyped)
            so_, po_ = s[keep], p[keep]
            del keep
            n_out_subj = len(np.unique(so_)) if len(so_) else 0
            if n_out_subj > 200_000:
                # vectorized signature path: group by out-predicate SET
                # via a commutative 64-bit mix over unique (s, p) pairs —
                # the per-vertex frozenset loop at this cardinality is
                # Python-object OOM territory
                from wukong_tpu.utils.mathutil import hash_u64

                # pack (s, p) into one int64: pred ids < 2^17 (NORMAL_ID_
                # START) by construction, subject ids < 2^31 -> 48 bits
                code = np.unique((so_.astype(np.int64) << 17)
                                 | po_.astype(np.int64))
                cs_, cp_ = code >> 17, code & ((1 << 17) - 1)
                upids = np.unique(cp_)
                hmap = np.asarray([hash_u64(int(x)) for x in upids],
                                  dtype=np.uint64)
                mixed = hmap[np.searchsorted(upids, cp_)]
                uv2, ustarts2 = np.unique(cs_, return_index=True)
                sig = np.add.reduceat(mixed, ustarts2)  # commutative mix
                sgu, sinv = np.unique(sig, return_inverse=True)
                sig_cids = np.arange(next_complex,
                                     next_complex - len(sgu), -1,
                                     dtype=np.int64)
                for k in range(len(sgu)):
                    # representative member set is informational only —
                    # the loop path also strips ("p", x) tuples to {}
                    complex_ids[frozenset({("sig", int(sgu[k]))})] = \
                        int(sig_cids[k])
                next_complex -= len(sgu)
                cid_by_subject = sig_cids[sinv]  # aligned with uv2
                pos2 = np.searchsorted(uv2, untyped)
                pos2c = np.clip(pos2, 0, max(len(uv2) - 1, 0))
                found2 = ((pos2 < len(uv2)) & (len(uv2) > 0)
                          & (uv2[pos2c] == untyped))
                empty_cid = 0
                if not found2.all():
                    # no-out-edge literals: one shared class, minted only
                    # when such vertices exist (the loop path allocates on
                    # first use; a phantom zero-member class would leak
                    # into complex_members/statfiles)
                    key = frozenset()
                    if key not in complex_ids:
                        complex_ids[key] = next_complex
                        next_complex -= 1
                    empty_cid = complex_ids[key]
                untyped_types = np.where(
                    found2, cid_by_subject[pos2c] if len(uv2) else 0,
                    empty_cid).astype(np.int64)
                for t, c in zip(*np.unique(untyped_types,
                                           return_counts=True)):
                    simple_counts[int(t)] += int(c)
            elif n_out_subj == 0:
                # all-literal untyped set: one shared empty-pset class
                key = frozenset()
                if key not in complex_ids:
                    complex_ids[key] = next_complex
                    next_complex -= 1
                untyped_types = np.full(len(untyped), complex_ids[key],
                                        dtype=np.int64)
                simple_counts[complex_ids[key]] += len(untyped)
            else:
                order2 = np.argsort(so_, kind="stable")
                so2, po2 = so_[order2], po_[order2]
                uv, ustarts = np.unique(so2, return_index=True)
                ubounds = np.append(ustarts, len(so2))
                pos = np.searchsorted(uv, untyped)
                uvt: list[int] = []
                for v, j in zip(untyped, pos):
                    if j < len(uv) and uv[j] == v:
                        pset = frozenset(
                            int(x) for x in po2[ubounds[j]:ubounds[j + 1]])
                    else:
                        pset = frozenset()
                    key = frozenset({("p", x) for x in pset})
                    if key not in complex_ids:
                        complex_ids[key] = next_complex
                        next_complex -= 1
                    uvt.append(complex_ids[key])
                    simple_counts[complex_ids[key]] += 1
                untyped_types = np.asarray(uvt, dtype=np.int64)
        st.vtype_ids = np.concatenate([uniq_v, untyped]).astype(np.int64)
        st.vtype = np.concatenate([typed_types, untyped_types])
        order3 = np.argsort(st.vtype_ids)
        st.vtype_ids = st.vtype_ids[order3]
        st.vtype = st.vtype[order3]
        st.tyscount = dict(simple_counts)
        st.complex_members = {
            cid: frozenset(x for x in key if not isinstance(x, tuple))
            for key, cid in complex_ids.items()}

        # ---- predicate histograms ----------------------------------------
        tvals = np.unique(np.append(st.vtype, 0))  # 0: no type known
        nt = len(tvals)
        counts = np.bincount(p, minlength=1) if len(p) else \
            np.zeros(1, dtype=np.int64)
        upids = np.flatnonzero(counts)
        rank_of = np.zeros(len(counts), dtype=np.int64)
        rank_of[upids] = np.arange(len(upids))
        if dense and len(upids) * (max_id + 1) <= room:
            # one pass each, nothing sorted and no edge moved: a count a
            # (predicate, subject type, object type) and a flag a
            # (predicate, vertex), both indexed by id
            table = st._dense_types(tvals, max_id)
            r = rank_of[p]
            cube = np.bincount((r * nt + table[s]) * nt + table[o],
                               minlength=len(upids) * nt * nt
                               ).reshape(len(upids), nt, nt)
            flags = np.zeros((len(upids), max_id + 1), dtype=bool)
            flags[r, s] = True
            n_subj = flags.sum(axis=1)
            flags[r, s] = False
            flags[r, o] = True
            n_obj = flags.sum(axis=1)
            del flags, r
        else:
            # sparse ids: a predicate at a time, by sorting
            cube = np.zeros((len(upids), nt, nt), dtype=np.int64)
            n_subj = np.zeros(len(upids), dtype=np.int64)
            n_obj = np.zeros(len(upids), dtype=np.int64)
            for k, pid in enumerate(upids):
                m = p == pid
                sm, om = s[m], o[m]
                n_subj[k], n_obj[k] = len(np.unique(sm)), len(np.unique(om))
                np.add.at(cube[k], (
                    np.searchsorted(tvals, st._lookup_types(sm)),
                    np.searchsorted(tvals, st._lookup_types(om))), 1)
        for k, pid in enumerate(upids.tolist()):
            pairs = cube[k]
            st.pred_edges[pid] = int(counts[pid])
            st.pstype[pid] = _hist_of(tvals, pairs.sum(axis=1))
            if pid == TYPE_ID:
                # rdf:type participates as a predicate too (k2c type
                # filters), with its edge count and its subjects' types only
                continue
            st.distinct_subj[pid] = int(n_subj[k])
            st.distinct_obj[pid] = int(n_obj[k])
            st.potype[pid] = _hist_of(tvals, pairs.sum(axis=0))
            for a, b in zip(*np.nonzero(pairs)):
                ta, tb, c = int(tvals[a]), int(tvals[b]), int(pairs[a, b])
                st.fine_type.setdefault((ta, pid, OUT), {})[tb] = c
                st.fine_type.setdefault((tb, pid, IN), {})[ta] = c
        if TYPE_ID not in st.pred_edges:
            st.pred_edges[int(TYPE_ID)] = 0
            st.pstype[int(TYPE_ID)] = {}
        return st

    def _dense_types(self, tvals: np.ndarray, max_id: int) -> np.ndarray:
        """Id -> index of its vertex's type in ``tvals`` (of 0 where the id
        is no vertex with a type), for ids that are dense."""
        table = np.full(max_id + 1, np.searchsorted(tvals, 0), dtype=np.int64)
        table[self.vtype_ids] = np.searchsorted(tvals, self.vtype)
        return table

    def _lookup_types(self, vids: np.ndarray) -> np.ndarray:
        idx = np.searchsorted(self.vtype_ids, vids)
        idx = np.clip(idx, 0, max(len(self.vtype_ids) - 1, 0))
        found = self.vtype_ids[idx] == vids
        return np.where(found, self.vtype[idx], 0)

    # ------------------------------------------------------------------
    def save(self, path: str) -> None:
        meta = {
            "tyscount": {str(k): v for k, v in self.tyscount.items()},
            "pstype": {str(k): {str(a): b for a, b in v.items()}
                       for k, v in self.pstype.items()},
            "potype": {str(k): {str(a): b for a, b in v.items()}
                       for k, v in self.potype.items()},
            "fine_type": [[list(k), {str(a): b for a, b in v.items()}]
                          for k, v in self.fine_type.items()],
            "pred_edges": {str(k): v for k, v in self.pred_edges.items()},
            "distinct_subj": {str(k): v for k, v in self.distinct_subj.items()},
            "distinct_obj": {str(k): v for k, v in self.distinct_obj.items()},
            "complex_members": {str(k): sorted(v) for k, v in
                                self.complex_members.items()},
        }
        np.savez(path, _meta=np.frombuffer(json.dumps(meta).encode(), np.uint8),
                 vtype=self.vtype, vtype_ids=self.vtype_ids)

    @staticmethod
    def load(path: str) -> "Stats":
        z = np.load(path if path.endswith(".npz") else path + ".npz")
        meta = json.loads(bytes(z["_meta"]).decode())
        st = Stats()
        st.tyscount = {int(k): v for k, v in meta["tyscount"].items()}
        st.pstype = {int(k): {int(a): b for a, b in v.items()}
                     for k, v in meta["pstype"].items()}
        st.potype = {int(k): {int(a): b for a, b in v.items()}
                     for k, v in meta["potype"].items()}
        st.fine_type = {tuple(k): {int(a): b for a, b in v.items()}
                        for k, v in meta["fine_type"]}
        st.pred_edges = {int(k): v for k, v in meta["pred_edges"].items()}
        st.distinct_subj = {int(k): v for k, v in
                            meta.get("distinct_subj", {}).items()}
        st.distinct_obj = {int(k): v for k, v in
                           meta.get("distinct_obj", {}).items()}
        st.complex_members = {int(k): frozenset(v) for k, v in
                              meta["complex_members"].items()}
        st.vtype = z["vtype"]
        st.vtype_ids = z["vtype_ids"]
        return st


def _hist_of(tvals: np.ndarray, counts: np.ndarray) -> dict[int, int]:
    return {int(tvals[i]): int(counts[i]) for i in np.flatnonzero(counts)}
