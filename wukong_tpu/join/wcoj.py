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

Level routes (``join_device`` knob, ROADMAP item 6i): a level is either
enumerated and probed by the NumPy host kernels, or made on the chip: the
prefix rows' ranges looked up once (``kernels.jit_level_ranges``), then one
fused XLA dispatch a generator group that expands the candidates, probes
them and compacts the survivors (``kernels.jit_level_probe``), with
device-resident int32 copies of the sorted tables cached per store version
next to their host twins; only each row's generator and the survivors come
back. The two routes are byte-identical by construction (same candidates in
the same order, same mask semantics); any device-path failure (missing jax,
int32 range overflow, a bug) degrades the level to the host kernels and
latches host for the rest of the query — the same degrade-don't-error
posture as the wcoj->walk fallback.
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
    jit_level_ranges,
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

# which form each lookup of a device level's programs took (the keys of
# each adjacency once a level, in ``wk_level_ranges``, and the list once a
# call of ``wk_level_probe``, where it has one): a table over the id range
# or the sorted search, by ``kernels.direct_lookup_wins`` on the shapes the
# program was traced at
_M_PROBE_LOOKUPS = get_registry().counter(
    "wukong_join_probe_lookups_total",
    "Lookups of the WCOJ level probe's calls by form", labels=("form",))

# where each level's candidates were made: on the chip from the prefix rows
# (a level of the device route with a bound adjacency, ``_device_level``)
# or by the host's NumPy enumeration
_M_LEVEL_ENUM = get_registry().counter(
    "wukong_join_level_enumerations_total",
    "WCOJ levels by where their candidates were enumerated",
    labels=("where",))

#: a level of more candidates than this many slices of ``LEVEL_SLICE`` is
#: enumerated and probed a run of prefix rows at a time
LEVEL_CHUNK_SLICES = 4


def _concat(parts: list, dtype) -> np.ndarray:
    return np.concatenate(parts).astype(dtype, copy=False) if parts \
        else np.empty(0, dtype=dtype)


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

        On the device route a level with a bound adjacency and at least
        ``_device_floor`` prefix rows or candidates makes its candidates on
        the chip (``_device_level``): the host ships the anchor columns and
        reads back each row's generator and minimum degree, and the
        survivors. Any other level, and a device level that fails, is
        enumerated and probed on the host, a run of prefix rows at a time
        (``_host_level``); the two are byte-identical, row order included.
        The host keeps the widening of the prefix by the new column.
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
        if not adj and G is None:
            raise WukongError(ErrorCode.UNSUPPORTED_SHAPE,
                              f"wcoj: variable {v} has no constraint to "
                              "generate candidates from")

        device = route == "device" and not (
            q is not None and getattr(q, "_join_device_broken", False))
        floor = self._device_floor()
        choice = None
        made = None
        if device and adj:
            if len(prefix) < floor:
                # a short prefix is looked up on the host: its candidates
                # decide, as they always did, whether the level is worth
                # the device
                with span(tr, "wcoj.enumerate"):
                    choice = self._host_choice(adj, G, prefix)
                mins = choice[2]
                device = int(mins.sum()) >= floor or \
                    len(_row_chunks(mins)) > 1
            if device:
                try:
                    made = self._device_level(adj, G, prefix, q, k, tr)
                except Exception as e:
                    self._device_failed(e, q)
        if made is None:
            made = self._host_level(adj, G, prefix, k, choice, tr,
                                    device and not adj, floor)
        kept_rows, kept_vals, state = made

        lvl_route, where = state["route"], state["enumerated"]
        _M_DEVICE_LEVELS.labels(route=lvl_route).inc()
        _M_LEVEL_CAND.labels(route=lvl_route).inc(state["candidates"])
        _M_LEVEL_SLOTS.labels(route=lvl_route).inc(state["slots"])
        _M_LEVEL_ENUM.labels(where=where).inc()
        if lvl_route == "device":
            _M_PROBE_LOOKUPS.labels(form="direct").inc(state["direct"])
            _M_PROBE_LOOKUPS.labels(form="search").inc(state["searched"])
        with span(tr, "wcoj.compact"):
            row_idx = _concat(kept_rows, np.int64)
            new_prefix = np.column_stack(
                [prefix[row_idx], _concat(kept_vals, np.int64)]).astype(
                    np.int64, copy=False)
        if tr is not None:
            tr.event("join.level", var=int(v),
                     candidates=state["candidates"], slots=state["slots"],
                     rows_out=len(new_prefix), route=lvl_route,
                     direct=state["direct"], searched=state["searched"],
                     enumerated=where)
        return new_prefix, {"candidates": state["candidates"],
                            "slots": state["slots"],
                            "probes": len(adj) + (G is not None),
                            "route": lvl_route, "direct": state["direct"],
                            "searched": state["searched"],
                            "enumerated": where}

    @staticmethod
    def _level_state(route: str) -> dict:
        return {"candidates": 0, "slots": 0, "route": route,
                "enumerated": route, "direct": 0, "searched": 0}

    @staticmethod
    def _device_failed(e: Exception, q) -> None:
        """Degrade THIS query's remaining levels to the host (the
        wcoj->walk posture, one layer down); the host serves the level."""
        reason = (type(e).__name__ if not isinstance(
            e, DeviceRangeError) else "int32_range")
        _M_DEVICE_FALLBACK.labels(reason=reason).inc()
        if q is not None:
            q._join_device_broken = True

    @staticmethod
    def _host_choice(adj, G, prefix: np.ndarray):
        """Each prefix row's ranges in every adjacency, its generator (the
        argmin over their degrees and the list's constant length) and its
        minimum degree, by NumPy searches: ``(ranges, choice, mins)``."""
        n = len(prefix)
        ranges = [lookup_ranges(seg.keys, seg.offsets, prefix[:, c])
                  for c, _pid, _d, seg in adj]
        degs = [d for (_s, d) in ranges]
        if G is not None:
            degs.append(np.full(n, len(G), dtype=np.int64))
        degs = np.stack(degs)
        return ranges, np.argmin(degs, axis=0), degs.min(axis=0)

    def _host_level(self, adj, G, prefix: np.ndarray, k: int, choice, tr,
                    passes: bool, floor: int):
        """The level enumerated and probed in NumPy, a run of prefix rows
        at a time (``_row_chunks``), so the host holds one run's
        candidates. ``passes``: a level of the device route whose one
        constraint is its generator (no bound adjacency): its candidates
        all pass, and a run at the device floor is the route's, though
        nothing is shipped. -> ``(kept_rows, kept_vals, state)``: the
        survivors' prefix rows and values, a list a run."""
        with span(tr, "wcoj.enumerate"):
            if choice is None:
                choice = self._host_choice(adj, G, prefix)
            ranges, ch, mins = choice
            chunks = _row_chunks(mins)
        state = self._level_state("host")
        kept_rows, kept_vals = [], []
        for lo, hi in chunks:
            with span(tr, "wcoj.enumerate"):
                row_idx, newcol = self._enumerate(adj, G, ranges, ch, lo,
                                                  hi, k)
            state["candidates"] += len(newcol)
            if not len(newcol):
                continue
            mask = None
            if passes and (len(chunks) > 1 or len(newcol) >= floor):
                with span(tr, "wcoj.probe.stage"):
                    _M_DEVICE_CAND.observe(len(newcol))
                state["route"] = "device"
            else:
                with span(tr, "wcoj.probe.host"):
                    mask = np.ones(len(newcol), dtype=bool)
                    if G is not None:
                        mask &= member_sorted(G, newcol)
                    for c, _pid, _d, seg in adj:
                        mask &= pair_member(seg.keys, seg.offsets, seg.edges,
                                            prefix[row_idx, c], newcol)
            state["slots"] += len(newcol)
            with span(tr, "wcoj.compact"):
                kept_rows.append(row_idx if mask is None else row_idx[mask])
                kept_vals.append(newcol if mask is None else newcol[mask])
        return kept_rows, kept_vals, state

    def _enumerate(self, adj, G, ranges, choice, lo: int, hi: int, k: int):
        """The candidates of prefix rows ``[lo, hi)`` on the host: (row
        index into the whole prefix, candidate value), generator group by
        generator group."""
        ch = choice[lo:hi]
        parts = []  # (row_idx, newcol) per generator group, in order
        for j, (start, deg) in enumerate(ranges):
            rows = np.nonzero(ch == j)[0] + lo
            if len(rows) == 0:
                continue
            row_idx, pos = expand_ragged(start[rows], deg[rows])
            parts.append((rows[row_idx], adj[j][3].edges[pos]))
        if G is not None:
            rows = np.nonzero(ch == len(ranges))[0] + lo
            if len(rows):
                parts.append((np.repeat(rows, len(G)), np.tile(G, len(rows))))
        row_idx = _concat([p[0] for p in parts], np.int64)
        newcol = _concat([p[1] for p in parts], np.int64)

        if self.part is not None and k == 0 and len(newcol):
            # distributed generic join: this slice keeps only its hash
            # partition of the first eliminated variable's candidates —
            # BEFORE the probes, so the fan-out divides the probe work
            S, kk = self.part
            from wukong_tpu.utils.mathutil import hash_mod

            pm = hash_mod(newcol.astype(np.int32), S) == kk
            row_idx, newcol = row_idx[pm], newcol[pm]
        return row_idx, newcol

    # ------------------------------------------------------------------
    def _device_level(self, adj, G, prefix: np.ndarray, q, k: int, tr):
        """The level's candidates made on the chip, from the prefix rows.

        ``_device_ranges`` ships the anchor columns and runs
        ``wk_level_ranges``: each adjacency's (start, degree) a row stays
        on the device, the host reads each row's generator and minimum
        degree. From them the host cuts runs of rows (``_row_chunks``) and
        counts each generator group's candidates; ``_probe_start`` makes
        one call of ``wk_level_probe`` a group (a slice of ``LEVEL_SLICE``
        in a level in runs), which expands the group's rows, probes every
        other constraint and compacts the survivors; ``_probe_finish``
        fetches them, call by call, while the chip runs the calls after. A
        level in runs dispatches run ``i + 1`` before it fetches run ``i``.
        The level's device arrays go when it ends. Raises on any device
        failure, before the prefix is touched. -> what ``_host_level``
        returns."""
        used = None if q is None else getattr(q, "_join_programs", None)
        if q is not None and used is None:
            used = q._join_programs = set()
        lvl = self._device_ranges(adj, G, prefix, tr, used)
        state = self._level_state("device")
        state["direct"], state["searched"] = lvl["direct"], lvl["searched"]
        kept_rows, kept_vals = [], []

        def finish(job):
            got = self._probe_finish(job, q, k)
            state["direct"] += job["direct"]
            state["searched"] += job["searched"]
            with span(tr, "wcoj.compact"):
                for rows, vals, count in got:
                    kept_rows.append(rows[:count])
                    kept_vals.append(vals[:count])

        choice, mins = lvl["choice_host"], lvl["mins"]
        with span(tr, "wcoj.enumerate"):
            chunks = _row_chunks(mins)
        whole = len(chunks) == 1
        pending = None
        for lo, hi in chunks:
            with span(tr, "wcoj.enumerate"):
                per = np.bincount(choice[lo:hi], weights=mins[lo:hi],
                                  minlength=lvl["generators"])
                groups = [(j, int(c)) for j, c in enumerate(per) if c]
            candidates = sum(c for _j, c in groups)
            state["candidates"] += candidates
            job = None
            if candidates:
                _M_DEVICE_CAND.observe(candidates)
                job = self._probe_start(lvl, groups, lo, hi, whole, tr)
                state["slots"] += job["slots"]
            if pending is not None:
                finish(pending)
            pending = job
        if pending is not None:
            finish(pending)
        for a in lvl["release"]:
            a.delete()
        return kept_rows, kept_vals, state

    def _device_ranges(self, adj, G, prefix: np.ndarray, tr,
                       used: set | None = None) -> dict:
        """Ship the prefix columns the level's adjacencies anchor on (int32,
        padded to the ``pad_pow2`` class of the rows with -1) and the list,
        run ``wk_level_ranges`` against the cached device tables, and fetch
        each row's generator (int8) and minimum degree. The ranges stay on
        the device for the level's calls; what ``_probe_start`` needs is in
        the returned dict. Counts the form of each adjacency's key lookup
        by ``direct_lookup_wins`` on the program's shapes. ``used`` gets
        the keys of the level's programs (``kernels.own_level_programs``).
        """
        import jax
        import jax.numpy as jnp

        n = len(prefix)
        rows = pad_pow2(n)
        with span(tr, "wcoj.probe.stage"):
            dev = [self.tables.device_tables(pid, d)
                   for (_c, pid, d, _s) in adj]
            at = sorted({c for c, *_x in adj})
            cols = prefix[:, at]
            if cols.size:
                # the prefix may hold ids a host-route level bound from
                # never-range-checked host tables: an unchecked int32 fill
                # would wrap ids past 2^31 and alias real keys (the
                # degrade-don't-truncate contract, like the tables)
                lo, hi = int(cols.min()), int(cols.max())
                if lo < -(1 << 31) or hi >= (1 << 31):
                    raise DeviceRangeError(
                        f"anchor values [{lo}, {hi}] exceed int32 — host "
                        "route required")
            anchors = np.full((len(at), rows), -1, dtype=np.int32)
            anchors[:, :n] = cols.T
            anchors = jnp.asarray(anchors)
            glob = jnp.zeros(1, dtype=jnp.int32) if G is None \
                else to_device_i32(G)
        fn = jit_level_ranges(tuple(t[4] for t in dev),
                              tuple(at.index(c) for c, *_x in adj),
                              G is not None, used)
        t0 = get_usec()
        with span(tr, "wcoj.probe.dispatch"):
            if tr is not None:
                tr.event("device.dispatch", kernel="wk_level_ranges")
            starts, degs, choice, mins = fn(
                anchors, 0 if G is None else len(G),
                *[a for t in dev for a in t[:2]])
        with span(tr, "wcoj.probe.sync"):
            choice_host, mins_host = jax.device_get((choice, mins))
        mins.delete()
        maybe_device_dispatch("wcoj.ranges", template=f"r{len(adj)}",
                              live=n, capacity=rows,
                              nbytes=anchors.nbytes + 5 * rows,
                              wall_us=get_usec() - t0)
        direct = sum(direct_lookup_wins(rows, int(t[0].shape[0]), t[4])
                     for t in dev)
        return {"dev": dev, "G": G, "glob": glob, "choice": choice,
                "starts": starts, "degs": degs,
                "list_bound": None if G is None else self._list_bound(G),
                "choice_host": choice_host[:n],
                "mins": mins_host[:n].astype(np.int64),
                "generators": len(adj) + (G is not None),
                "direct": int(direct), "searched": len(adj) - int(direct),
                "release": [anchors, glob, starts, degs, choice],
                "used": used}

    def _probe_start(self, lvl: dict, groups: list, lo: int, hi: int,
                     whole: bool, tr=None) -> dict:
        """Dispatch one run of prefix rows ``[lo, hi)``: a call of
        ``wk_level_probe`` a generator group ``(j, candidates)``, which
        expands the group's rows on the chip and keeps the candidates every
        constraint but the generator passes (its self-probe is true by
        construction): each other adjacency's edge run, spread by row from
        the level's ranges, and the list. -> the job ``_probe_finish``
        takes.

        ``whole`` (the level is one run: up to ``LEVEL_CHUNK_SLICES``
        slices of candidates, 2^24) makes a group ONE call at the
        ``pad_pow2`` class of its candidates; a level in runs cuts a group
        into slices of ``LEVEL_SLICE`` (``kernels.level_slices``). Nothing
        is fetched here. A group whose generator is its only constraint is
        made all the same and counted as its candidates' slots, as nothing
        probes it; the list's membership takes the form
        ``kernels.direct_lookup_wins`` picks from the call's shapes, and
        the job counts it by the same rule."""
        dev, G = lvl["dev"], lvl["G"]
        depths = tuple(t[3] for t in dev)
        edges = [t[2] for t in dev]
        # a run reads its own rows: from row0, rows_cap of the level's
        rows_cap = min(pad_pow2(hi - lo), int(lvl["choice"].shape[0]))
        row0 = min(lo, int(lvl["choice"].shape[0]) - rows_cap)
        calls = []  # one a call: what it gave, its live and padded slots
        direct = lookups = 0
        for j, n in groups:
            has_list = G is not None and j != len(dev)
            probed = len(dev) - (j < len(dev)) + has_list > 0
            for slo, shi, Cp in ([(0, n, pad_pow2(n))] if whole
                                 else level_slices(n)):
                fn = jit_level_probe(j, depths, has_list, lvl["list_bound"],
                                     Cp, rows_cap, lvl["used"])
                window = np.array([row0, lo, hi, slo], dtype=np.int32)
                t0 = get_usec()
                with span(tr, "wcoj.probe.dispatch"):
                    if tr is not None:
                        tr.event("device.dispatch", kernel="wk_level_probe")
                    out = fn(lvl["choice"], lvl["starts"], lvl["degs"],
                             window, lvl["glob"], *edges)
                if has_list:
                    lookups += 1
                    direct += lvl["list_bound"] is not None \
                        and direct_lookup_wins(Cp, len(G), lvl["list_bound"])
                calls.append({
                    "out": out, "live": shi - slo,
                    "slots": Cp if probed else shi - slo, "capacity": Cp,
                    "t0": t0,
                    "template": f"g{j}p" + "".join(map(str, depths))
                    + ("l" if has_list else ""),
                    # the window up, the survivors and their rows back
                    "nbytes": 12 + 8 * Cp + 4})
        return {"calls": calls, "tr": tr,
                "direct": int(direct), "searched": int(lookups - direct),
                "slots": sum(c["slots"] for c in calls)}

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

    def _probe_finish(self, job: dict, q=None, level: int = 0) -> list:
        """The survivors of what ``_probe_start`` dispatched, ``[(rows,
        values, count)]`` a call in order, fetched where they were not yet;
        each call charged to the device observatory."""
        import jax

        got = []
        with span(job["tr"], "wcoj.probe.sync"):
            for c in job["calls"]:
                rows, vals, count = jax.device_get(c["out"])
                got.append((rows, vals, int(count)))
                rec = maybe_device_dispatch(
                    "wcoj.probe", template=c["template"], live=c["live"],
                    capacity=c["capacity"], nbytes=c["nbytes"],
                    wall_us=get_usec() - c["t0"])
                if rec is not None and q is not None:
                    rec["step"] = int(level)
                    dsteps = getattr(q, "device_steps", None)
                    if dsteps is None:
                        dsteps = q.device_steps = []
                    dsteps.append(rec)
        return got

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
