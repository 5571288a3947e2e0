"""Leapfrog-Triejoin-style worst-case-optimal executor (the ``wcoj`` strategy).

Executes a planned BGP level-at-a-time in the query graph's variable
elimination order (qgraph.py): each level materializes ONE variable, with
every incident pattern constraining the candidate set *at that level* —
per-row adjacency expansion from the cheapest bound anchor, sorted-set
intersection of the global candidate lists (type/predicate indexes, const
neighbor lists), and ragged binary-search probes for the remaining bound
edges. Intermediates are therefore bounded by the join's fragment size, not
by the walk's wedge blowup (EmptyHeaded/TrieJax, PAPERS.md).

Edge tables are the store's own CSR segments, verified-sorted once and
cached per store version (:class:`JoinTableCache`, the plan-cache pattern:
a dynamic insert / stream commit bumps the version and stale entries become
unreachable). Materialization is a ``join.materialize`` fault site — an
injected failure surfaces BEFORE the query result is touched, so the proxy
degrades the query to the walk, never to an error.

Resilience parity with the walk: the per-query deadline is checked and the
row budget charged at every level; expiry commits the prefix built so far
as a structured partial result (``result.complete = False``).

Level routes (``join_device`` knob, ROADMAP item 6i): each level's probe
phase — the per-candidate intersection cost TrieJax moves on-accelerator —
runs either on the NumPy host kernels or as ONE fused XLA dispatch over a
padded/bucketed flat candidate tensor (``kernels.jit_level_probe``), with
device-resident int32 copies of the sorted tables cached per store version
next to their host twins. The two routes are byte-identical by
construction (same candidate enumeration, same mask semantics); any
device-path failure (missing jax, int32 range overflow, a bug) degrades
the level to the host kernels and latches host for the rest of the query —
the same degrade-don't-error posture as the wcoj->walk fallback.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from wukong_tpu.analysis.lockdep import declare_leaf, make_lock
from wukong_tpu.config import Global
from wukong_tpu.join import kernels
from wukong_tpu.join.kernels import (
    DeviceRangeError,
    direct_lookup_wins,
    expand_ragged,
    intersect_many,
    jit_level_probe,
    level_slices,
    lookup_ranges,
    member_sorted,
    pad_pow2,
    pair_member,
    to_device_i32,
)
from wukong_tpu.join.qgraph import U_CONST, U_PINDEX, U_TYPE, analyze
from wukong_tpu.obs.device import maybe_device_dispatch, maybe_device_resident
from wukong_tpu.obs.metrics import get_registry
from wukong_tpu.obs.trace import span, trace_event, traced_execute
from wukong_tpu.runtime import faults
from wukong_tpu.runtime.resilience import (
    charge_query,
    check_query,
    mark_partial,
)
from wukong_tpu.store.segment import CSRSegment
from wukong_tpu.types import IN, OUT
from wukong_tpu.utils.errors import (
    BudgetExceeded,
    ErrorCode,
    QueryTimeout,
    WukongError,
)
from wukong_tpu.utils.timer import get_usec

_M_MATERIALIZE = get_registry().counter(
    "wukong_join_materialize_total",
    "WCOJ sorted-edge-table cache requests", labels=("outcome",))
# device-route observability (README metrics table): which route each
# level's probe phase actually took, why device levels degraded to host,
# and the per-dispatch candidate volume (the dispatch-amortization
# feedback loop behind join_device_min_candidates)
_M_DEVICE_LEVELS = get_registry().counter(
    "wukong_join_device_levels_total",
    "WCOJ level probe phases by executed route", labels=("route",))
_M_DEVICE_FALLBACK = get_registry().counter(
    "wukong_join_device_fallback_total",
    "Device-route levels degraded to the host kernels", labels=("reason",))
_M_DEVICE_CAND = get_registry().histogram(
    "wukong_join_device_candidates",
    "Candidates per device-probed level",
    buckets=(1 << 10, 1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 20,
             1 << 22, 1 << 24))

# what a level cost whichever route probed it: the candidates it enumerated
# and the slots it probed (a device level pads its candidates to capacity
# classes; a host level probes them as they are)
_M_LEVEL_CAND = get_registry().counter(
    "wukong_join_level_candidates_total",
    "Candidates enumerated by WCOJ levels", labels=("route",))
_M_LEVEL_SLOTS = get_registry().counter(
    "wukong_join_level_slots_total",
    "Slots probed by WCOJ levels (padded on the device route)",
    labels=("route",))

# which form each lookup of a device level's probe calls took (a call looks
# up the keys of each adjacency it probes and the list, where it has one):
# a table over the id range or the sorted search, by
# ``kernels.direct_lookup_wins`` on the shapes the program was traced at
_M_PROBE_LOOKUPS = get_registry().counter(
    "wukong_join_probe_lookups_total",
    "Lookups of the WCOJ level probe's calls by form", labels=("form",))

#: a level of more candidates than this many slices of ``LEVEL_SLICE`` is
#: enumerated and probed a run of prefix rows at a time
LEVEL_CHUNK_SLICES = 4


def _row_chunks(counts: np.ndarray) -> list:
    """``[(lo, hi)]`` runs of prefix rows whose candidates (``counts`` a
    row) stay within ``LEVEL_CHUNK_SLICES`` slices each; a row is never
    split, so one with more is a run of its own."""
    limit = max(int(kernels.LEVEL_SLICE), 1) * LEVEL_CHUNK_SLICES
    n = len(counts)
    cum = np.cumsum(counts)
    if n == 0 or int(cum[-1]) <= limit:
        return [(0, n)]
    out, lo, base = [], 0, 0
    while lo < n:
        hi = int(np.searchsorted(cum, base + limit, side="right"))
        hi = min(max(hi, lo + 1), n)
        out.append((lo, hi))
        lo, base = hi, int(cum[hi - 1])
    return out


# the cache lock guards pure dict moves (materialization happens outside
# it); nothing is ever acquired under it
declare_leaf("join.tables")


def _verify_sorted_segment(seg: CSRSegment) -> CSRSegment:
    """Return ``seg`` with edges guaranteed sorted within each key run.

    CSR builders emit this invariant already; a defensive verify keeps the
    probe kernels' binary-search contract independent of future store
    writers. O(E) check, re-sort only on violation.
    """
    e, off = seg.edges, seg.offsets
    if len(e) > 1:
        inc = e[1:] >= e[:-1]
        inc[off[1:-1] - 1] = True  # run boundaries may descend
        if not bool(inc.all()):
            keys = np.repeat(seg.keys, np.diff(off))
            order = np.lexsort((e, keys))
            return CSRSegment.from_sorted_pairs(keys[order], e[order])
    return seg


def _sorted_index(arr) -> np.ndarray:
    a = np.asarray(arr, dtype=np.int64)
    if len(a) > 1 and not bool((a[1:] >= a[:-1]).all()):
        a = np.unique(a)
    return a


def store_vertex_bound(g) -> int:
    """One past the largest vertex id that is a key of a segment or a member
    of an index list of the partition ``g``: every subject, and every object
    that is not a type. It moves only with a write. (Here and not a method
    of ``GStore``: ``store/gstore.py`` is a layout source of the key of
    every saved store bundle, ``runtime/boot.py``.)"""
    last = [int(a[-1]) for a in (
        *(seg.keys for seg in g.segments.values()), *g.index.values(),
        g.v_set) if len(a)]
    return max(last, default=-1) + 1


class JoinTableCache:
    """Per-store cache of verified-sorted edge tables and index lists.

    Keys carry the store version, so mutations (dynamic inserts, stream
    commits) make stale entries unreachable — the plan-cache invalidation
    pattern. Bounded LRU of ``join_table_cache`` entries. Materialization
    (the verify/re-sort pass) runs OUTSIDE the lock behind the
    ``join.materialize`` fault site; a duplicate concurrent build is
    idempotent and the second writer simply refreshes the entry.
    """

    def __init__(self, gstore):
        self.g = gstore
        self._tables: OrderedDict = OrderedDict()  # guarded by: _lock
        self._lock = make_lock("join.tables")

    def _version(self) -> int:
        return int(getattr(self.g, "version", 0))

    def _get(self, key):
        with self._lock:
            v = self._tables.get(key)
            if v is not None:
                self._tables.move_to_end(key)
            return v

    @staticmethod
    def _dev_nbytes(key, value) -> int:
        """Device-resident bytes of one cache entry (0 for host-side
        segments/indexes — only ``dseg`` tuples live in HBM)."""
        if key[1] != "dseg":
            return 0
        return sum(int(getattr(a, "nbytes", 0)) for a in value[:3])

    def _put(self, key, value):
        evicted = []
        stale = []
        with self._lock:
            version = key[0]
            if key[1] == "dseg":
                # reap device tables a store-version bump orphaned: their
                # keys can never hit again, but their HBM bytes would
                # otherwise linger until LRU churn found them
                stale = [k for k in self._tables
                         if k[1] == "dseg" and k[0] != version]
                stale_bytes = sum(self._dev_nbytes(k, self._tables.pop(k))
                                  for k in stale)
            self._tables[key] = value
            self._tables.move_to_end(key)
            cap = max(int(Global.join_table_cache), 1)
            while len(self._tables) > cap:
                evicted.append(self._tables.popitem(last=False))
        # residency charges OUTSIDE the cache lock (both are leaves)
        if stale:
            maybe_device_resident("invalidate", "join_table", stale_bytes,
                                  version=int(version))
        fill = self._dev_nbytes(key, value)
        if fill:
            maybe_device_resident("fill", "join_table", fill)
        for k, v in evicted:
            ev = self._dev_nbytes(k, v)
            if ev:
                maybe_device_resident("evict", "join_table", ev)
        return value

    def segment(self, pid: int, d: int) -> CSRSegment:
        """The (pid, dir) adjacency as a verified-sorted CSR segment."""
        key = (self._version(), "seg", int(pid), int(d))
        hit = self._get(key)
        if hit is not None:
            _M_MATERIALIZE.labels(outcome="hit").inc()
            return hit
        _M_MATERIALIZE.labels(outcome="miss").inc()
        faults.site("join.materialize")
        seg = self.g.segments.get((int(pid), int(d)))
        seg = (CSRSegment.empty() if seg is None
               else _verify_sorted_segment(seg))
        return self._put(key, seg)

    def index_list(self, tpid: int, d: int) -> np.ndarray:
        """A type/predicate index as a sorted unique id array."""
        key = (self._version(), "idx", int(tpid), int(d))
        hit = self._get(key)
        if hit is not None:
            _M_MATERIALIZE.labels(outcome="hit").inc()
            return hit
        _M_MATERIALIZE.labels(outcome="miss").inc()
        faults.site("join.materialize")
        return self._put(key, _sorted_index(self.g.get_index(tpid, d)))

    def neighbor_list(self, const: int, pid: int, d: int) -> np.ndarray:
        """One constant's neighbor list (sorted — a CSR edge run)."""
        # uncached: the segment lookup is already one binary search, and
        # per-const keys would churn the bounded cache under template mixes
        return np.asarray(self.segment(pid, d).lookup(const), dtype=np.int64)

    def device_tables(self, pid: int, d: int):
        """The (pid, dir) adjacency as device-resident int32 arrays
        (keys, offsets, edges, depth, id_bound) for the XLA level probe
        and the whole-plan template programs — built from
        the verified-sorted host segment and cached per store version like
        every other entry, so mutations self-invalidate and steady-state
        device levels never re-ship tables. ``depth`` is the segment's
        binary-search iteration bound (log2(max_degree)+1 — a probe range
        is one key's edge run, never the whole edge array); ``id_bound``
        is the last key + 1 (0 for an empty segment), the static size of
        the table ``kernels.lookup_ranges_device`` addresses. Raises
        :class:`DeviceRangeError` (caller degrades to host) when any
        value exceeds int32 under the default x64-off JAX config."""
        key = (self._version(), "dseg", int(pid), int(d))
        hit = self._get(key)
        if hit is not None:
            _M_MATERIALIZE.labels(outcome="hit").inc()
            return hit
        _M_MATERIALIZE.labels(outcome="miss").inc()
        seg = self.segment(pid, d)  # host twin first (verify + fault site)
        max_deg = (int(np.diff(seg.offsets).max())
                   if len(seg.offsets) > 1 else 0)
        trace_event("device.stage", segment=f"dseg:{(int(pid), int(d))}",
                    bytes=4 * (len(seg.keys) + len(seg.offsets)
                               + len(seg.edges)))
        return self._put(key, (to_device_i32(seg.keys),
                               to_device_i32(seg.offsets),
                               to_device_i32(seg.edges),
                               max(max_deg, 1).bit_length() + 1,
                               int(seg.keys[-1]) + 1 if len(seg.keys) else 0))

    def vertex_bound(self) -> int:
        """The store's vertex id bound (:func:`store_vertex_bound`; a
        sharded view answers for its shards), cached beside the tables per
        store version: the static size of the table
        ``kernels.member_sorted_device`` marks a candidate list in. The
        same for every list, so a probe program specialises on it once (a
        list's own last id would be a compile a draw)."""
        key = (self._version(), "vbound")
        hit = self._get(key)
        if hit is not None:
            return hit
        of_view = getattr(self.g, "vertex_bound", None)
        return self._put(key, int(of_view() if of_view is not None
                                  else store_vertex_bound(self.g)))

    def clear(self) -> None:
        with self._lock:
            dev = sum(self._dev_nbytes(k, v)
                      for k, v in self._tables.items())
            self._tables.clear()
        if dev:
            maybe_device_resident("invalidate", "join_table", dev)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._tables)}


class WCOJExecutor:
    """Worst-case-optimal BGP execution over one (host) partition.

    ``stats`` (the optimizer's type-centric statistics) refines the
    variable elimination order; without it the analyzer falls back to
    structural heuristics. FILTER evaluation and final processing are
    delegated to the CPU engine's stages so string/DISTINCT/ORDER semantics
    can never drift between strategies.
    """

    def __init__(self, gstore, str_server=None, stats=None, tables=None,
                 part=None):
        self.g = gstore
        self.str_server = str_server
        self.stats = stats
        # ``tables`` lets the distributed executor share ONE materialized
        # cache across its per-partition slices (join/dist.py)
        self.tables = tables if tables is not None else JoinTableCache(gstore)
        # ``part`` = (S, k): keep only level-0 candidates whose hash lands
        # in partition k of S — the distributed generic join's split of
        # the first eliminated variable. Later levels are untouched, so
        # the union over k of the S partitioned runs is exactly the
        # unpartitioned result (level-0 values partition the rows).
        self.part = part

    # ------------------------------------------------------------------
    def execute(self, q, from_proxy: bool = True):
        """Engine-contract execution: failures land as reply status codes,
        never as raised WukongErrors (CPUEngine parity)."""
        try:
            return self.try_execute(q, from_proxy)
        except WukongError as e:
            q.result.status_code = e.code
            return q

    def try_execute(self, q, from_proxy: bool = True):
        """Degradable execution: a failure in the join phase RAISES with
        ``q`` untouched, so the caller (the proxy's strategy router) can
        re-dispatch the same query to the walk. Structured deadline/budget
        expiry still commits a partial result, and a FILTER/FINAL-stage
        failure after the join committed sets the reply status (those are
        query-semantic — the walk would fail them identically)."""
        return traced_execute(
            q, "wcoj.execute", lambda: self._try_impl(q, from_proxy),
            lambda: {"rows": q.result.nrows,
                     "status": q.result.status_code.name})

    def _try_impl(self, q, from_proxy: bool):
        try:
            self.run_bgp(q)
        except (QueryTimeout, BudgetExceeded) as e:
            mark_partial(q, e)
            return q
        try:
            if q.pattern_group.filters:
                self._cpu()._execute_filters(q)
            if from_proxy:
                self._cpu()._final_process(q)
        except (QueryTimeout, BudgetExceeded) as e:
            mark_partial(q, e)
        except WukongError as e:
            q.result.status_code = e.code
        return q

    def _cpu(self):
        from wukong_tpu.engine.cpu import CPUEngine

        return CPUEngine(self.g, self.str_server)

    # ------------------------------------------------------------------
    def run_bgp(self, q) -> None:
        """Generic join over the BGP. Commits into ``q.result`` only on
        success or on a structured deadline/budget expiry (partial prefix);
        any other failure leaves ``q`` untouched so the caller can degrade
        to the walk."""
        qg, unary_lists = self._analyze_and_warm(q)
        self._run_levels(q, qg, unary_lists)

    def _analyze_and_warm(self, q):
        """Shape checks + up-front materialization of every backing array.
        The ``join.materialize`` fault site fires here, before ``q`` is
        touched — and before the distributed executor fans slices out, so
        a materialization failure degrades the whole query to the walk
        instead of failing mid-gather."""
        pg = q.pattern_group
        if pg.unions or pg.optional:
            raise WukongError(ErrorCode.UNSUPPORTED_SHAPE,
                              "wcoj executes plain BGPs (UNION/OPTIONAL "
                              "route walk)")
        qg = analyze(pg.patterns, stats=self.stats)
        if not qg.supported:
            raise WukongError(ErrorCode.UNSUPPORTED_SHAPE,
                              f"wcoj: {qg.reason}")

        unary_lists: dict[int, list] = {v: [] for v in qg.order}
        for u in qg.unaries:
            if u.kind == U_TYPE:
                arr = self.tables.index_list(u.payload, IN)
            elif u.kind == U_PINDEX:
                arr = self.tables.index_list(*u.payload)
            else:  # U_CONST
                arr = self.tables.neighbor_list(*u.payload)
            unary_lists[u.var].append(arr)
        # each edge is consumed exactly once as an adjacency (anchored on
        # the endpoint materialized FIRST, expanding/probing the later
        # one) and once as the earlier endpoint's index list — warm only
        # those, so _level's lazy fetches are guaranteed cache hits and
        # no fault can fire past this point
        pos = {v: i for i, v in enumerate(qg.order)}
        for e in qg.edges:
            later_is_o = pos[e.o] > pos[e.s]
            self.tables.segment(e.pid, OUT if later_is_o else IN)
            earlier = e.s if later_is_o else e.o
            self.tables.index_list(e.pid, IN if earlier == e.s else OUT)
        return qg, unary_lists

    def _run_levels(self, q, qg, unary_lists) -> None:
        """The level loop over an analyzed, warmed query graph."""
        route = self._route_for(q)
        prefix = np.empty((1, 0), dtype=np.int64)
        cols: dict[int, int] = {}
        levels: list[dict] = []
        try:
            for k, v in enumerate(qg.order):
                check_query(q, f"wcoj.level {k}")
                t0 = get_usec()
                rows_in = len(prefix)
                with span(getattr(q, "trace", None), "wcoj.level",
                          level=k, var=int(v)):
                    prefix, rec = self._level(qg, v, k, prefix, cols,
                                              unary_lists[v], route, q)
                cols[v] = k
                rec.update(level=k, var=v, rows_in=rows_in,
                           rows_out=len(prefix),
                           time_us=get_usec() - t0)
                levels.append(rec)
                charge_query(q, len(prefix), f"wcoj.level {k}")
        except (QueryTimeout, BudgetExceeded):
            # structured degradation: commit the prefix built so far as a
            # partial result (mark_partial lists every pattern dropped)
            self._commit(q, prefix, cols, levels, partial=True)
            raise
        self._commit(q, prefix, cols, levels, partial=False)

    # ------------------------------------------------------------------
    # level routing (join_device knob; JOIN_ROUTES registry)
    # ------------------------------------------------------------------
    @staticmethod
    def _route_for(q) -> str:
        """The query's level route: the proxy's plan-time classification
        (``q.join_route``) when present, else the forced knob — a bare
        executor under ``auto`` stays on host (it has no cost model to
        amortize the dispatch against)."""
        r = getattr(q, "join_route", None)
        if r is not None:
            return r
        knob = str(Global.join_device).strip().lower()
        return "device" if knob == "device" else "host"

    @staticmethod
    def _device_floor() -> int:
        """Per-level candidate floor for the device probe. A forced
        ``join_device device`` probes every level (deterministic tests);
        under auto-routing, levels below the dispatch-amortization
        threshold keep the host kernels."""
        if str(Global.join_device).strip().lower() == "device":
            return 1
        return max(int(Global.join_device_min_candidates), 1)

    # ------------------------------------------------------------------
    def _level(self, qg, v: int, k: int, prefix: np.ndarray,
               cols: dict, unary: list, route: str = "host", q=None):
        """Materialize variable ``v`` against the bound prefix.

        Generator choice is PER ROW: each prefix row expands from its
        smallest incident candidate list (the cheapest bound adjacency, or
        the intersected global list) — the leapfrog property that bounds
        total candidates by the sum of per-row minimum degrees, which a
        single per-level generator would lose on skewed (hub) data. Every
        constraint then filters all candidates (the generating list's
        self-probe is redundant but always true). Returns the new prefix
        and the level's intersection stats.

        A level of more than ``LEVEL_CHUNK_SLICES`` slices of candidates
        (2^24) is taken a run of prefix rows at a time (``_row_chunks``):
        the host holds one run's candidates, not the level's, and on the
        device route it enumerates the next run while the chip probes this
        one (the probe is dispatched, the mask fetched a run later). A
        smaller level is one run, probed as ``_probe_start`` says.
        """
        tr = getattr(q, "trace", None)
        adj = []  # (anchor col, pid, dir, segment) — other endpoint bound
        glob = list(unary)  # global sorted candidate lists
        for e in qg.edges_of(v):
            v_is_o = e.o == v
            other = e.s if v_is_o else e.o
            if other in cols:
                d = OUT if v_is_o else IN
                seg = self.tables.segment(e.pid, d)
                adj.append((cols[other], e.pid, d, seg))
            else:
                glob.append(self.tables.index_list(
                    e.pid, IN if e.s == v else OUT))
        G = intersect_many(glob)
        n = len(prefix)
        if not adj and G is None:
            raise WukongError(ErrorCode.UNSUPPORTED_SHAPE,
                              f"wcoj: variable {v} has no constraint to "
                              "generate candidates from")

        with span(tr, "wcoj.enumerate"):
            # per-row generator: argmin over each adjacency's degree and
            # the global list's (constant) length
            ranges = [lookup_ranges(seg.keys, seg.offsets, prefix[:, c])
                      for c, _pid, _d, seg in adj]
            deg_stack = [d for (_s, d) in ranges]
            if G is not None:
                deg_stack.append(np.full(n, len(G), dtype=np.int64))
            degs = np.stack(deg_stack) if n else \
                np.empty((len(deg_stack), 0), dtype=np.int64)
            choice = np.argmin(degs, axis=0) if n else \
                np.empty(0, dtype=np.int64)
            chunks = _row_chunks(degs.min(axis=0)) if n else [(0, 0)]
            del degs, deg_stack

        device = route == "device"
        probes = len(adj) + (1 if G is not None else 0)
        state = {"candidates": 0, "slots": 0, "route": "host",
                 "direct": 0, "searched": 0}
        kept_rows, kept_vals = [], []

        def host_mask(row_idx, newcol):
            mask = np.ones(len(newcol), dtype=bool)
            if G is not None:
                mask &= member_sorted(G, newcol)
            for c, _pid, _d, seg in adj:
                mask &= pair_member(seg.keys, seg.offsets, seg.edges,
                                    prefix[row_idx, c], newcol)
            return mask

        def device_failed(e):
            # degrade THIS query's remaining levels to host (the
            # wcoj->walk posture, one layer down); the host probe
            # serves this chunk
            reason = (type(e).__name__ if not isinstance(
                e, DeviceRangeError) else "int32_range")
            _M_DEVICE_FALLBACK.labels(reason=reason).inc()
            if q is not None:
                q._join_device_broken = True

        def finish(pending):
            """The mask of a chunk whose probe was dispatched (or not),
            and its surviving candidates kept, in chunk order."""
            row_idx, newcol, job = pending
            mask = None
            if job is not None:
                try:
                    mask = self._probe_finish(job, len(newcol), q, k)
                    state["slots"] += job["slots"]
                    state["direct"] += job["direct"]
                    state["searched"] += job["searched"]
                    state["route"] = "device"
                except Exception as e:
                    device_failed(e)
            if mask is None:
                with span(tr, "wcoj.probe.host"):
                    mask = host_mask(row_idx, newcol)
                state["slots"] += len(newcol)
            with span(tr, "wcoj.compact"):
                kept_rows.append(row_idx[mask])
                kept_vals.append(newcol[mask])

        pending = None
        for lo, hi in chunks:
            with span(tr, "wcoj.enumerate"):
                row_idx, newcol, gid = self._enumerate(
                    adj, G, ranges, choice, lo, hi, k, device)
            state["candidates"] += len(newcol)
            job = None
            if len(newcol) and device \
                    and (len(chunks) > 1
                         or len(newcol) >= self._device_floor()) \
                    and not (q is not None
                             and getattr(q, "_join_device_broken", False)):
                try:
                    job = self._probe_start(G, adj, prefix, row_idx,
                                            newcol, gid, len(chunks) == 1,
                                            tr)
                except Exception as e:
                    device_failed(e)
            if pending is not None:
                finish(pending)
            pending = (row_idx, newcol, job) if len(newcol) else None
        if pending is not None:
            finish(pending)

        lvl_route = state["route"]
        _M_DEVICE_LEVELS.labels(route=lvl_route).inc()
        _M_LEVEL_CAND.labels(route=lvl_route).inc(state["candidates"])
        _M_LEVEL_SLOTS.labels(route=lvl_route).inc(state["slots"])
        if lvl_route == "device":
            _M_PROBE_LOOKUPS.labels(form="direct").inc(state["direct"])
            _M_PROBE_LOOKUPS.labels(form="search").inc(state["searched"])
        with span(tr, "wcoj.compact"):
            row_idx = np.concatenate(kept_rows) if kept_rows else \
                np.empty(0, dtype=np.int64)
            newcol = np.concatenate(kept_vals) if kept_vals else \
                np.empty(0, dtype=np.int64)
            new_prefix = np.column_stack(
                [prefix[row_idx], newcol]).astype(np.int64, copy=False)
        if tr is not None:
            tr.event("join.level", var=int(v),
                     candidates=state["candidates"], slots=state["slots"],
                     rows_out=len(new_prefix), route=lvl_route,
                     direct=state["direct"], searched=state["searched"])
        return new_prefix, {"candidates": state["candidates"],
                            "slots": state["slots"], "probes": probes,
                            "route": lvl_route, "direct": state["direct"],
                            "searched": state["searched"]}

    def _enumerate(self, adj, G, ranges, choice, lo: int, hi: int, k: int,
                   want_gid: bool):
        """The candidates of prefix rows ``[lo, hi)``: (row index into the
        whole prefix, candidate value, generator id or None), generator
        group by generator group."""
        ch = choice[lo:hi]
        parts = []  # (generator id, row_idx, newcol) per generator group
        for j, (start, deg) in enumerate(ranges):
            rows = np.nonzero(ch == j)[0] + lo
            if len(rows) == 0:
                continue
            row_idx, pos = expand_ragged(start[rows], deg[rows])
            parts.append((j, rows[row_idx], adj[j][3].edges[pos]))
        if G is not None:
            rows = np.nonzero(ch == len(ranges))[0] + lo
            if len(rows):
                parts.append((len(adj), np.repeat(rows, len(G)),
                              np.tile(G, len(rows))))
        if parts:
            row_idx = np.concatenate([p[1] for p in parts])
            newcol = np.concatenate([p[2] for p in parts]).astype(
                np.int64, copy=False)
            # which generator produced each candidate (non-decreasing by
            # construction — groups are appended in generator order), so
            # the device path can elide each group's always-true
            # self-probe and slice groups as contiguous ranges. Only the
            # device route consumes it — the host route skips the alloc
            gid = (np.concatenate([np.full(len(p[1]), p[0],
                                           dtype=np.int16) for p in parts])
                   if want_gid else None)
        else:
            row_idx = np.empty(0, dtype=np.int64)
            newcol = np.empty(0, dtype=np.int64)
            gid = np.empty(0, dtype=np.int16) if want_gid else None

        if self.part is not None and k == 0 and len(newcol):
            # distributed generic join: this slice keeps only its hash
            # partition of the first eliminated variable's candidates —
            # BEFORE the probes, so the fan-out divides the probe work
            S, kk = self.part
            from wukong_tpu.utils.mathutil import hash_mod

            pm = hash_mod(newcol.astype(np.int32), S) == kk
            row_idx, newcol = row_idx[pm], newcol[pm]
            if gid is not None:
                gid = gid[pm]
        return row_idx, newcol, gid

    # ------------------------------------------------------------------
    def _probe_start(self, G, adj, prefix: np.ndarray, row_idx: np.ndarray,
                     newcol: np.ndarray, gid: np.ndarray, whole: bool,
                     tr=None) -> dict:
        """Dispatch the probe phase of a level, or of one run of its rows:
        one fused XLA call per generator group, masking each padded flat
        candidate tensor by every constraint EXCEPT its own generator
        (whose self-probe is true by construction — candidates were drawn
        from that list); the adjacencies ship as cached device-resident
        tables with their binary-search depth bounds, the global list
        ships per call of this (it is an intersection result, not a
        cacheable table). -> the job ``_probe_finish`` takes.

        ``whole`` (the level is one run: up to ``LEVEL_CHUNK_SLICES``
        slices of candidates, 2^24, the largest level any cell ran before
        LSQB's) probes as levels always were: a group is ONE call at the
        ``pad_pow2`` class of its candidates, and its mask is fetched
        before the next group's tensors are built. A level in runs cuts a
        group into slices of ``LEVEL_SLICE`` (``kernels.level_slices``) and
        fetches nothing here, so the host may enumerate the next run
        meanwhile. In either, every lookup of a call (an anchor's key, the
        list) takes the form ``kernels.direct_lookup_wins`` picks from the
        call's shapes: the cached ``id_bound`` of each adjacency and the
        list's bound (``_list_bound``) go to ``jit_level_probe`` whatever
        the level's size, and the job counts the forms (``direct``,
        ``searched``) by the same rule on the same shapes.
        """
        import jax.numpy as jnp

        with span(tr, "wcoj.probe.stage"):
            _M_DEVICE_CAND.observe(len(newcol))
            dev = [self.tables.device_tables(pid, d)
                   for (_c, pid, d, _s) in adj]
            # a level in runs ships the list when its first group needs it
            glob_dev = list_bound = None
            if whole and G is not None:
                glob_dev, list_bound = to_device_i32(G), self._list_bound(G)
            dummy = jnp.zeros(1, dtype=jnp.int32)
            # gid is non-decreasing by construction: one diff pass finds
            # the group boundaries (no sort over millions of candidates)
            bounds = np.flatnonzero(np.diff(gid)) + 1
            starts = np.concatenate([[0], bounds]).tolist()
            ends = np.concatenate([bounds, [len(gid)]]).tolist()
        calls = []  # one a call: its place, its slots, what it gave
        direct = lookups = 0  # the calls' lookups, and those by a table
        passes = []  # (lo, hi): only the self-constraint, all pass
        for glo, ghi in zip(starts, ends):
            g = int(gid[glo])
            use_glob = G is not None and g != len(adj)
            adj_ids = [j for j in range(len(adj)) if j != g]
            if not adj_ids and not use_glob:
                passes.append((glo, ghi))
                continue
            if use_glob and glob_dev is None:
                glob_dev, list_bound = to_device_i32(G), self._list_bound(G)
            depths = tuple(dev[j][3] for j in adj_ids)
            fn = jit_level_probe(depths, use_glob,
                                 tuple(dev[j][4] for j in adj_ids),
                                 list_bound)
            n = ghi - glo
            for slo, shi, Cp in ([(0, n, pad_pow2(n))] if whole
                                 else level_slices(n)):
                lo, hi = glo + slo, glo + shi
                C = hi - lo
                with span(tr, "wcoj.probe.stage"):
                    valid = np.zeros(Cp, dtype=bool)
                    valid[:C] = True
                    cand = np.zeros(Cp, dtype=np.int32)
                    cand[:C] = newcol[lo:hi]  # ids < 2^31 (range-checked)
                    args = [jnp.asarray(valid), jnp.asarray(cand),
                            glob_dev if use_glob else dummy]
                    for j in adj_ids:
                        keys, offsets, edges, _depth, _id_bound = dev[j]
                        avals = prefix[row_idx[lo:hi], adj[j][0]]
                        if len(avals):
                            # anchors come from the PREFIX, which
                            # host-route levels may have bound from
                            # never-range-checked host tables — an
                            # unchecked int32 fill would silently wrap
                            # ids past 2^31 and alias real keys (the
                            # degrade-don't-truncate contract, like the
                            # tables)
                            alo, ahi = int(avals.min()), int(avals.max())
                            if alo < -(1 << 31) or ahi >= (1 << 31):
                                raise DeviceRangeError(
                                    f"anchor values [{alo}, {ahi}] exceed "
                                    "int32 — host route required")
                        anchors = np.zeros(Cp, dtype=np.int32)
                        anchors[:C] = avals
                        args.extend([keys, offsets, edges,
                                     jnp.asarray(anchors)])
                t0 = get_usec()
                with span(tr, "wcoj.probe.dispatch"):
                    if tr is not None:
                        tr.event("device.dispatch", kernel="wk_level_probe")
                    mask = fn(*args)
                if whole:
                    with span(tr, "wcoj.probe.sync"):
                        mask = np.asarray(mask)  # blocking D2H sync
                del args
                # the forms the program took, by the rule it was traced by
                lookups += len(adj_ids) + use_glob
                direct += sum(direct_lookup_wins(Cp, int(dev[j][0].shape[0]),
                                                 dev[j][4]) for j in adj_ids)
                if use_glob and list_bound is not None:
                    direct += direct_lookup_wins(Cp, len(G), list_bound)
                # candidate/anchor uploads + the mask back (device tables
                # are cached residents and don't re-ship)
                calls.append({
                    "lo": lo, "hi": hi, "slots": Cp, "mask": mask, "t0": t0,
                    "wall_us": get_usec() - t0,
                    "template": "p" + "".join(map(str, depths))
                    + ("g" if use_glob else ""),
                    "nbytes": Cp * (1 + 4 + 4 * len(adj_ids)) + C
                    + (int(G.nbytes) if use_glob else 0)})
        return {"calls": calls, "passes": passes, "whole": whole, "tr": tr,
                "direct": int(direct), "searched": int(lookups - direct),
                "slots": sum(c["slots"] for c in calls)
                + sum(hi - lo for lo, hi in passes)}

    def _list_bound(self, G: np.ndarray) -> int | None:
        """The id bound under which the probe may mark the sorted candidate
        list ``G`` in a table over the id range
        (``kernels.member_sorted_device``): the store's vertex bound, where
        ``G`` lies inside ``[0, bound)``; else ``None``, and the probe
        searches the list as the host does (an edge to a vertex that is
        nobody's key here would be dropped from the table)."""
        bound = self.tables.vertex_bound()
        if len(G) and (int(G[0]) < 0 or int(G[-1]) >= bound):
            return None
        return bound

    def _probe_finish(self, job: dict, n: int, q=None,
                      level: int = 0) -> np.ndarray:
        """The host boolean mask over the ``n`` unpadded candidates of what
        ``_probe_start`` dispatched, its masks fetched where they were not
        yet — identical semantics to the host probes."""
        mask = np.zeros(n, dtype=bool)
        for lo, hi in job["passes"]:
            mask[lo:hi] = True
        with span(None if job["whole"] else job["tr"], "wcoj.probe.sync"):
            for c in job["calls"]:
                C = c["hi"] - c["lo"]
                mask[c["lo"]:c["hi"]] = np.asarray(c["mask"])[:C]
                rec = maybe_device_dispatch(
                    "wcoj.probe", template=c["template"], live=C,
                    capacity=c["slots"], nbytes=c["nbytes"],
                    wall_us=c["wall_us"] if job["whole"]
                    else get_usec() - c["t0"])
                if rec is not None and q is not None:
                    rec["step"] = int(level)
                    dsteps = getattr(q, "device_steps", None)
                    if dsteps is None:
                        dsteps = q.device_steps = []
                    dsteps.append(rec)
        return mask

    # ------------------------------------------------------------------
    def _commit(self, q, prefix: np.ndarray, cols: dict, levels: list,
                partial: bool) -> None:
        res = q.result
        res.set_table(prefix)
        res.col_num = prefix.shape[1]
        for v, c in cols.items():
            res.add_var2col(v, c)
        q.join_stats = levels
        if not partial:
            q.pattern_step = len(q.pattern_group.patterns)
