"""Distributed query execution: shard_map chains with all-to-all row exchange.

This is the TPU-native replacement for the reference's distributed machinery:

- graph partitioned by hash(vid) % D over mesh devices (base_loader.hpp:172-173)
- one-sided RDMA reads + fork-join sub-queries (sparql.hpp:746-814,
  rmap.hpp) become a capacity-padded `lax.all_to_all` of binding-table rows
  keyed by the anchor column's owner, executed INSIDE one compiled program
- index-origin starts run on every shard over its local index slice
  (= dispatch to all servers x mt_factor, sparql.hpp:1064-1088)
- mid-chain type-membership expansion all-gathers rows and expands against
  each shard's local type index (= the reference's dispatch-to-all for
  `p == TYPE_ID && d == IN`, sparql.hpp:1139-1152)

The whole pattern chain for a query compiles to ONE jitted shard_map program
(cached per plan signature x capacity classes): zero mid-query host syncs, one
device_get at the end for row counts + overflow totals (+ gathered tables when
not blind). Capacity overflow anywhere (expansion or exchange) triggers a
host-side retry of the whole chain at exact capacities.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from wukong_tpu.config import Global
from wukong_tpu.engine import tpu_kernels as K
from wukong_tpu.obs.metrics import get_registry
from wukong_tpu.parallel.sharded_store import ShardedDeviceStore
from wukong_tpu.sparql.ir import NO_RESULT, PGType, SPARQLQuery
from wukong_tpu.types import IN, OUT, PREDICATE_ID, TYPE_ID, AttrType
from wukong_tpu.utils.errors import (
    BudgetExceeded,
    CapacityExceeded,
    ErrorCode,
    QueryTimeout,
    WukongError,
    assert_ec,
)

# what a chain's collectives carried, counted once its attempt came out
# sound: live rows that left their chip, and the slots shipped to other
# chips, padding included (``all_to_all`` ships ``exch_cap`` slots to each
# destination; ``all_gather`` the whole table to every other chip)
_M_XROWS = get_registry().counter(
    "wukong_dist_exchange_rows_total",
    "Live binding-table rows the sharded chain sent to another chip",
    labels=("collective",))
_M_XSLOTS = get_registry().counter(
    "wukong_dist_exchange_slots_total",
    "Row slots the sharded chain shipped to other chips, padding included",
    labels=("collective",))
_M_XBYTES = get_registry().counter(
    "wukong_dist_exchange_bytes_total",
    "Bytes of the live rows the sharded chain sent to another chip",
    labels=("collective",))
_M_RETRIES = get_registry().counter(
    "wukong_dist_capacity_retries_total",
    "Whole-chain reruns of the sharded chain at larger capacity classes")


@dataclass
class _Step:
    kind: str  # init_index | init_const | init_rows | expand
    #           | expand_type_all | expand_versatile | member
    pid: int = 0
    dir: int = 0
    col: int = -1  # anchor column
    vals_col: int = -1  # member: end column (-1 => const)
    const: int = 0  # member const / init const vid
    cap: int = 0  # output capacity class (expansion / exchange target)
    exch_cap: int = 0  # per-destination exchange capacity (0 = no exchange)
    new_col: bool = False
    width: int = 0  # init_rows: seed table width


@dataclass
class _Plan:
    steps: list = field(default_factory=list)
    width: int = 0
    v2c: dict = field(default_factory=dict)

    def signature(self):
        return tuple(
            (s.kind, s.pid, s.dir, s.col, s.vals_col, s.const, s.cap,
             s.exch_cap, s.width)
            for s in self.steps)


class DistEngine:
    """Executes device-supported SPARQL plans across a device mesh."""

    def __init__(self, stores: list, str_server=None, mesh=None, axis: str = "x"):
        from wukong_tpu.parallel.mesh import make_mesh

        self.mesh = mesh or make_mesh(len(stores))
        self.axis = axis
        self.D = len(stores)
        self.sstore = ShardedDeviceStore(stores, self.mesh, axis)
        self.str_server = str_server
        self.cap_min = Global.table_capacity_min
        self.cap_max = Global.table_capacity_max
        self._fn_cache: dict = {}
        # per-chain observability (bench --dist artifact detail): set by the
        # last successful _run_device_bgp — per-step row/exchange loads vs
        # their capacity classes, and how many whole-chain retries were paid
        self.last_chain_stats: dict | None = None
        self._last_plan: _Plan | None = None
        # one-shot dryrun hook: seed the NEXT chain's capacity overrides
        # (e.g. an undersized class) to exercise the overflow-retry path
        # deterministically; consumed and cleared by _run_device_bgp
        self.force_cap_override: dict | None = None
        # learned capacity classes per pattern-chain key: estimate-driven
        # first runs over-pad (the skew bound is conservative by design —
        # BENCH_DIST_r04 measured q1 shipping 335 MB of PADDED all-to-all
        # per chain against ~16x-smaller real peaks), so successful runs
        # record the EXACT classes and steady-state chains recompile once
        # at tight capacities; undersized learning self-corrects through
        # the normal overflow retry
        self._learned_caps: dict = {}

    # ------------------------------------------------------------------
    def execute(self, q: SPARQLQuery, from_proxy: bool = True) -> SPARQLQuery:
        from wukong_tpu.obs.trace import traced_execute

        # the ambient activation makes shard fetches / retries / breaker
        # trips land on this query's trace (see traced_execute)
        return traced_execute(
            q, "dist.execute", lambda: self._execute_impl(q, from_proxy),
            lambda: {"rows": q.result.nrows,
                     "status": q.result.status_code.name,
                     "complete": q.result.complete})

    def _execute_impl(self, q: SPARQLQuery,
                      from_proxy: bool = True) -> SPARQLQuery:
        if self.sstore.check_version():
            # compiled chains bake per-segment max_probe/depth — stale after
            # dynamic inserts (dynamic_gstore.hpp lease invalidation analogue);
            # learned capacity classes measured the old data; the in-place
            # engine's shard-segment/global-index memos point at old arrays
            self._fn_cache.clear()
            self._learned_caps.clear()
            self.__dict__.pop("_inplace_eng", None)
        # degraded stagings are never cached, so a query served entirely
        # from cache is complete by construction — judge incompleteness only
        # by fetch failures during THIS query, not a prior query's outage
        self.sstore.degraded_shards.clear()
        try:
            self._execute_sm(q, from_proxy)
        except (QueryTimeout, BudgetExceeded) as e:
            from wukong_tpu.runtime.resilience import mark_partial

            mark_partial(q, e)
        except WukongError as e:
            q.result.status_code = e.code
        if self.sstore.degraded_shards and q.result.status_code in (
                ErrorCode.SUCCESS, ErrorCode.QUERY_TIMEOUT,
                ErrorCode.BUDGET_EXCEEDED):
            # a down shard's partition contributed nothing to this chain:
            # the reply is well-formed but incomplete — tag it so clients
            # can distinguish "empty" from "missing a shard" (no crash)
            q.result.complete = False
            for s in sorted(self.sstore.degraded_shards):
                tag = f"shard:{s}"
                if tag not in q.result.dropped_patterns:
                    q.result.dropped_patterns.append(tag)
        return q

    def _execute_sm(self, q: SPARQLQuery, from_proxy: bool) -> None:
        """The distributed state machine: PATTERN -> UNION -> OPTIONAL ->
        FILTER -> FINAL (sparql.hpp:1564-1673). BGPs run as compiled
        shard_map chains; UNION branches and OPTIONAL groups run as seeded
        distributed children; FILTER/FINAL run host-side on the gathered
        table (they touch strings and projections, not the graph)."""
        if q.planner_empty and Global.enable_empty_shortcircuit:
            # planner-proved empty: no sharded chain, no collectives
            self._host().short_circuit_empty(q)
            if from_proxy:
                self._host()._final_process(q)
            return
        # silent-mode parity (reference Global::silent works for ANY shape —
        # it executes fully and simply never ships the table, query.hpp
        # shrink 619-630): shapes whose children need the gathered table
        # run non-blind internally and drop the table at reply time
        blind_deferred = bool(
            q.result.blind and (q.pattern_group.filters
                                or q.pattern_group.unions
                                or q.pattern_group.optional))
        if blind_deferred:
            q.result.blind = False
        if q.has_pattern and not q.done_patterns():
            self._execute_bgp(q)
        if q.pattern_group.unions and not q.union_done:
            self._execute_unions_dist(q)
        while q.optional_step < len(q.pattern_group.optional):
            self._execute_optional_dist(q)
        if q.pattern_group.filters or (from_proxy and q.orders):
            assert_ec(self.str_server is not None, ErrorCode.UNKNOWN_FILTER,
                      "FILTER/ORDER BY needs a string server")
        if q.pattern_group.filters:
            self._host()._execute_filters(q)
        if from_proxy:
            self._host()._final_process(q)
        if blind_deferred:
            # drop the table at reply time; the count survives (shrink)
            res = q.result
            res.blind = True
            nrows = res.nrows
            res.table = np.empty((0, res.col_num), dtype=np.int64)
            res.attr_table = np.empty((0, res.attr_col_num), np.float64)
            res.nrows = nrows

    def _host(self):
        from wukong_tpu.engine.cpu import CPUEngine

        if not hasattr(self, "_host_engine"):
            self._host_engine = CPUEngine(None, self.str_server)
        return self._host_engine

    def _attr_host(self):
        """Host engine over the sharded attribute segments: an attr lookup
        routes to the subject owner's partition — the reference executes attr
        patterns CPU-side too (gpu_engine.hpp:267-333 unsupported on GPU)."""
        from wukong_tpu.engine.cpu import CPUEngine

        if not hasattr(self, "_attr_engine"):
            self._attr_engine = CPUEngine(_ShardedAttrGraph(self.sstore.stores),
                                          self.str_server)
        return self._attr_engine

    # ------------------------------------------------------------------
    def _execute_bgp(self, q: SPARQLQuery) -> None:
        """Device-supported prefix as one distributed chain; a trailing run of
        attribute patterns executes host-side over the sharded attr stores."""
        pats = q.pattern_group.patterns
        split = q.pattern_step
        while split < len(pats) and \
                pats[split].pred_type == int(AttrType.SID_t):
            split += 1
        for pat in pats[split:]:  # the tail must be all-attr
            assert_ec(pat.pred_type != int(AttrType.SID_t),
                      ErrorCode.UNSUPPORTED_SHAPE,
                      "SID patterns after attr patterns are unsupported "
                      "in the distributed engine")
        first = pats[q.pattern_step] if split > q.pattern_step else None
        if first is not None \
                and self._try_inplace(q, n_steps=split - q.pattern_step):
            first = None  # the whole SID prefix ran in place
        if first is not None and q.result.col_num == 0 \
                and first.predicate < 0 and first.subject > 0:
            # versatile const start (CONST ?p ?y / CONST1 ?p CONST2): the
            # const's combined adjacency is one CSR walk on its owner
            # partition — done host-side, the rest of the chain runs as a
            # seeded distributed child (like the single-chip engine)
            self._versatile_const_start(q, first)
        if split > q.pattern_step:
            seed = None
            if q.result.col_num > 0:  # seeded child (UNION branch on a table)
                seed = (q.result.table, dict(q.result.v2c_map))
            tr = getattr(q, "trace", None)
            if tr is None:
                self._run_device_bgp(q, n_steps=split - q.pattern_step,
                                     seed=seed)
            else:
                sp = tr.start_span("dist.chain",
                                   steps=split - q.pattern_step,
                                   rows_in=q.result.nrows)
                try:
                    self._run_device_bgp(q, n_steps=split - q.pattern_step,
                                         seed=seed)
                finally:
                    st = getattr(self, "last_chain_stats", None) or {}
                    tr.end_span(sp, rows_out=q.result.nrows,
                                **{k: st[k] for k in
                                   ("mode", "retries", "exchanges",
                                    "exchange_rows", "exchange_slots",
                                    "rows_max_shard", "rows_mean_shard")
                                   if k in st})
        while not q.done_patterns():  # attr tail (or attr-only query)
            self._attr_host()._execute_one_pattern(q)

    def _try_inplace(self, q: SPARQLQuery, n_steps: int) -> bool:
        """Owner-routed in-place fast path for small-table chains (reference
        need_fork_join, sparql.hpp:802-814; proxy owner routing,
        proxy.hpp:201-219): light queries run the whole SID prefix host-side
        against the federated partition view — zero collectives, zero
        compiles — and retreat to the collective chain the moment the live
        table outgrows Global.dist_inplace_rows. Returns True when the
        prefix completed in place (pattern_step advanced past it)."""
        if not Global.enable_dist_inplace or n_steps <= 0:
            return False
        from wukong_tpu.parallel.inplace import InplaceOverflow

        thr = max(int(Global.dist_inplace_rows), 1)
        pats = q.pattern_group.patterns[q.pattern_step:q.pattern_step
                                        + n_steps]
        first = pats[0]
        eng = self._inplace_engine()
        if q.result.col_num == 0:
            # fresh starts: const-anchored only — index origins scan whole
            # index lists (the heavies) and belong to the sharded chain
            if first.subject <= 0 or _is_index_pattern(first):
                return False
            if first.predicate > 0:
                # exact first fan-out, one owner CSR lookup — the entry
                # check (the reference sizes the same decision on fetch
                # length vs global_rdma_threshold)
                fan = len(eng.g.get_triples(
                    first.subject, first.predicate, first.direction))
                if fan > thr:
                    return False
            # versatile starts (p < 0): no cheap exact bound; the dynamic
            # abort below still caps the walk
        elif q.result.nrows > thr:
            return False  # seeded (UNION/OPTIONAL) child with a big table
        import copy

        snap_step = q.pattern_step
        snap_res = copy.deepcopy(q.result)
        target = q.pattern_step + n_steps
        from wukong_tpu.runtime.resilience import charge_query, check_query

        tr = getattr(q, "trace", None)
        sp = (tr.start_span("dist.inplace", steps=n_steps,
                            rows_in=q.result.nrows)
              if tr is not None else None)
        try:
            while q.pattern_step < target:
                check_query(q, f"dist.inplace step {q.pattern_step}")
                eng._execute_one_pattern(q)
                charge_query(q, q.result.nrows,
                             f"dist.inplace step {q.pattern_step - 1}")
                if q.result.nrows > thr:
                    raise InplaceOverflow()
        except InplaceOverflow:
            q.pattern_step = snap_step
            q.result = snap_res
            if sp is not None:  # aborted to the collective chain
                tr.end_span(sp, ok=False, overflow=True)
            return False
        except BaseException:
            if sp is not None:
                tr.end_span(sp, ok=False, raised=True)
            raise
        if sp is not None:
            tr.end_span(sp, ok=True, rows_out=int(q.result.nrows))
        if q.result.blind and q.done_patterns():
            # blind parity with the collective chain (which never gathers
            # the table): count survives, rows are dropped. A pending attr
            # tail keeps the table — it still anchors the attr kernels.
            res = q.result
            nrows = res.nrows
            res.table = np.empty((0, res.col_num), dtype=np.int64)
            res.nrows = nrows
        self.last_chain_stats = {"mode": "inplace", "retries": 0,
                                 "exchanges": 0, "steps": n_steps,
                                 "rows": int(q.result.nrows)}
        self._last_plan = None  # bytes_model: no collective chain to model
        return True

    def _inplace_engine(self):
        from wukong_tpu.parallel.inplace import InplaceEngine

        if not hasattr(self, "_inplace_eng"):
            self._inplace_eng = InplaceEngine(self.sstore.stores,
                                              self.str_server)
        return self._inplace_eng

    def _versatile_const_start(self, q: SPARQLQuery, pat) -> None:
        """Delegate to a CPU engine over the const's owner partition — the
        owner holds the full combined adjacency (vertices are placed by
        hash on both subject and object), and the CPU kernels carry the
        exact const_unknown_* semantics (incl. start_from_index rejection
        of malformed tpid starts)."""
        from wukong_tpu.engine.cpu import CPUEngine
        from wukong_tpu.utils.mathutil import hash_mod

        owner = int(hash_mod(int(np.int32(pat.subject)), self.D))
        if not hasattr(self, "_owner_hosts"):
            self._owner_hosts: dict = {}
        if owner not in self._owner_hosts:
            self._owner_hosts[owner] = CPUEngine(self.sstore.stores[owner],
                                                 self.str_server)
        self._owner_hosts[owner]._execute_one_pattern(q)

    def _execute_unions_dist(self, q: SPARQLQuery) -> None:
        """Each UNION branch is a distributed child seeded with the parent's
        result table (query.hpp:702-711 inherit_union); children recurse
        through the full state machine, so nested UNION/OPTIONAL work."""
        from wukong_tpu.sparql.ir import Result

        assert_ec(q.result.attr_col_num == 0, ErrorCode.UNSUPPORT_UNION)
        q.union_done = True
        merged = None
        host = self._host()
        for sub_pg in q.pattern_group.unions:
            child = SPARQLQuery()
            child.pqid = q.qid
            child.pg_type = PGType.UNION
            child.pattern_group = sub_pg
            child.deadline = q.deadline  # children share the parent's budget
            # children rebind result state rather than mutate it, so the
            # parent table is shared by reference (no deepcopy of rows)
            child.result = Result(q.result.nvars)
            child.result.v2c_map = dict(q.result.v2c_map)
            child.result.col_num = q.result.col_num
            child.result.table = q.result.table
            child.result.nrows = q.result.nrows
            child.result.blind = False
            self._execute_sm(child, from_proxy=False)
            if child.result.status_code != ErrorCode.SUCCESS:
                raise WukongError(child.result.status_code,
                                  "union child failed")
            merged = host._merge_union(merged, child.result, q.result.nvars)
        q.result.v2c_map = merged.v2c_map
        q.result.col_num = merged.col_num
        q.result.set_table(merged.table)

    def _execute_optional_dist(self, q: SPARQLQuery) -> None:
        """OPTIONAL as a dedup-seeded distributed child + host left join
        (the shared engine-agnostic formulation, engine/optional_join.py)."""
        from wukong_tpu.engine.optional_join import execute_optional_leftjoin

        execute_optional_leftjoin(
            q, self._host(),
            run_child=lambda c: self._execute_sm(c, from_proxy=False),
            str_server=self.str_server)

    # ------------------------------------------------------------------
    def load_cap_memo(self, path: str) -> None:
        """Load learned capacity classes persisted by a previous process.
        A cold process then traces ONE program per chain at the exact
        classes (whose XLA compilation the persistent cache already holds)
        instead of estimate-class + overflow-retry + tight-class recompile
        — the dominant share of BENCH_DIST_r04's 4.5-9.7 s first_us
        (round-4 verdict Weak #3)."""
        import json as _json

        try:
            with open(path) as f:
                for ent in _json.load(f):
                    key = tuple(tuple(p) for p in ent["pats"])
                    caps = {}
                    for ck, v in ent["caps"].items():
                        kind, i = ck.split(":")
                        caps[(kind, int(i))] = int(v)
                    self._learned_caps.setdefault(key, caps)
        except FileNotFoundError:
            pass
        except Exception as e:
            from wukong_tpu.utils.logger import log_warn

            log_warn(f"dist cap memo load failed: {e}")

    def save_cap_memo(self, path: str) -> None:
        import json as _json
        import os as _os

        try:
            data = [{"pats": [list(p) for p in key],
                     "caps": {f"{k}:{i}": int(v)
                              for (k, i), v in caps.items()}}
                    for key, caps in self._learned_caps.items()]
            tmp = path + ".tmp"
            _os.makedirs(_os.path.dirname(path) or ".", exist_ok=True)
            with open(tmp, "w") as f:
                _json.dump(data, f)
            _os.replace(tmp, path)
        except Exception as e:
            from wukong_tpu.utils.logger import log_warn

            log_warn(f"dist cap memo save failed: {e}")

    def _run_device_bgp(self, q: SPARQLQuery, n_steps: int, seed=None) -> None:
        pats_key = tuple(
            (p.subject, p.predicate, int(p.direction), p.object)
            for p in q.pattern_group.patterns[
                q.pattern_step:q.pattern_step + n_steps])
        # learned caps apply only to unseeded chains, symmetric with the
        # write below: a seeded plan prepends init_rows (shifting every
        # step index) and carries a different parent table's cardinalities
        cap_override: dict = (dict(self._learned_caps.get(pats_key, {}))
                              if seed is None else {})
        if self.force_cap_override:
            cap_override.update(self.force_cap_override)
        self.force_cap_override = None
        from wukong_tpu.obs.trace import trace_event
        from wukong_tpu.runtime import faults
        from wukong_tpu.runtime.resilience import (
            charge_query,
            check_query,
            retry_call,
        )

        seed_cache: dict = {}  # seed shards are retry-invariant; transfer once
        for _attempt in range(8):
            check_query(q, f"dist.chain attempt {_attempt}")
            plan = self._build_plan(q, cap_override, n_steps, seed)
            fn, args = self._get_fn(plan, seed, seed_cache)

            def _dispatch():
                # transient dispatch failures (device hiccup, injected
                # chaos) retry with backoff; inputs are immutable so a
                # re-dispatch is safe. Routed through the transport seam:
                # the mesh is process-local on every backend we have, so
                # both transports execute in place, but the dispatch path
                # shares the fetch path's boundary object by contract.
                faults.site("dist.chain_dispatch")
                return self.sstore.transport.dispatch(fn, *args)

            out = retry_call(_dispatch, site="dist.chain_dispatch",
                             retry_on=(faults.TransientFault,),
                             deadline=getattr(q, "deadline", None))

            if q.result.blind:
                ns, totals = _gather_host((out["n"], out["totals"]))
                tables = None
            else:
                tables, ns, totals = _gather_host(
                    (out["table"], out["n"], out["totals"]))
            # [D, 4 * nsteps]: rows, exchange loads, rows sent, slots sent
            totals = np.asarray(totals)
            S = len(plan.steps)
            over = False
            for i, s in enumerate(plan.steps):
                t = int(totals[:, i].max())
                if t > s.cap:
                    if t > self.cap_max:
                        raise CapacityExceeded(
                            f"intermediate result ({t:,} rows/shard) exceeds "
                            f"table_capacity_max ({self.cap_max:,})")
                    cap_override[("cap", i)] = K.next_capacity(
                        t, self.cap_min, self.cap_max)
                    trace_event("capacity.retry", site="dist.chain", step=i,
                                cap_from=s.cap,
                                cap_to=cap_override[("cap", i)])
                    over = True
                if s.exch_cap:
                    em = int(totals[:, S + i].max())
                    if em > s.exch_cap:
                        if em > self.cap_max:
                            raise CapacityExceeded(
                                f"exchange destination load ({em:,} rows) "
                                f"exceeds table_capacity_max ({self.cap_max:,})")
                        cap_override[("exch", i)] = K.next_capacity(
                            em, self.cap_min, self.cap_max)
                        trace_event("capacity.retry", site="dist.exchange",
                                    step=i, cap_from=s.exch_cap,
                                    cap_to=cap_override[("exch", i)])
                        over = True
            if not over:
                break
        else:
            raise WukongError(ErrorCode.UNKNOWN_PATTERN,
                              "distributed capacity retry limit exceeded")

        # chain observability for the bench artifact (round-4 verdict #3:
        # the 42x cpu-mesh number needs per-step evidence, not a single
        # end-to-end time): per step, the peak per-shard row load and peak
        # per-destination exchange load against their capacity classes
        S = len(plan.steps)
        step_stats = []
        # collective -> [live rows, slots, live bytes] sent to other chips
        moved = {"all_to_all": [0, 0, 0], "all_gather": [0, 0, 0]}
        for i, (s, w_in) in enumerate(zip(plan.steps, _widths_in(plan))):
            st = {"kind": s.kind, "cap": s.cap,
                  "rows_peak_shard": int(totals[:, i].max()),
                  "rows_all_shards": int(totals[:, i].sum())}
            if s.exch_cap:
                st["exch_cap"] = s.exch_cap
                st["exch_peak_dest"] = int(totals[:, S + i].max())
            if s.exch_cap or s.kind == "expand_type_all":
                got = moved["all_to_all" if s.exch_cap else "all_gather"]
                rows = int(totals[:, 2 * S + i].sum())
                got[0] += rows
                got[1] += int(totals[:, 3 * S + i].sum())
                got[2] += rows * w_in * 4  # int32 ids
            step_stats.append(st)
        for coll, (rows, slots, nbytes) in moved.items():
            if slots:
                _M_XROWS.labels(collective=coll).inc(rows)
                _M_XSLOTS.labels(collective=coll).inc(slots)
                _M_XBYTES.labels(collective=coll).inc(nbytes)
        if _attempt:
            _M_RETRIES.inc(_attempt)
        ns = np.asarray(ns).reshape(-1)
        self.last_chain_stats = {
            "retries": int(_attempt),
            "exchanges": sum(1 for s in plan.steps if s.exch_cap),
            "exchange_rows": sum(m[0] for m in moved.values()),
            "exchange_slots": sum(m[1] for m in moved.values()),
            "rows_max_shard": int(ns.max()),
            "rows_mean_shard": float(ns.mean()),
            "steps": step_stats}
        self._last_plan = plan
        # learn EXACT classes for the next run of this chain (tighter
        # where the estimate over-padded, already-exact where it retried)
        learned = {}
        for i, s in enumerate(plan.steps):
            learned[("cap", i)] = K.next_capacity(
                max(int(totals[:, i].max()), 1), self.cap_min, self.cap_max)
            if s.exch_cap:
                learned[("exch", i)] = K.next_capacity(
                    max(int(totals[:, S + i].max()), 1),
                    self.cap_min, self.cap_max)
        if len(self._learned_caps) > 1024:
            self._learned_caps.clear()
        if seed is None:
            # seeded children sharing a pats key can carry very different
            # parent tables; learning from one would mis-size the next
            # (the retry would self-correct, but at a recompile per flip)
            self._learned_caps[pats_key] = learned

        n_total = int(np.sum(ns))
        charge_query(q, n_total, "dist.chain")
        res = q.result
        res.v2c_map = dict(plan.v2c)
        res.col_num = plan.width
        if q.result.blind:
            res.nrows = n_total
        else:
            parts = []
            for d in range(self.D):
                parts.append(np.asarray(tables[d][:, : int(ns[d])]).T)
            tab = (np.concatenate(parts) if parts
                   else np.empty((0, plan.width), np.int64))
            # device tables are int32; BLANK_ID must round-trip to its
            # uint32 host value (types.py BLANK_ID_I32)
            res.set_table(tab.astype(np.int64) & 0xFFFFFFFF
                          if tab.dtype == np.int32 else tab.astype(np.int64))
        q.pattern_step += n_steps

    # ------------------------------------------------------------------
    def bytes_model(self) -> dict | None:
        """Host-side traffic model of the LAST executed chain (the dist
        bench's roofline fields, round-4 verdict #4): staged segment arrays
        read, sharded table state written at the capacity classes, and —
        the number the 42x diagnosis needs — the capacity-PADDED collective
        traffic (all_to_all ships [D, W, exch_cap] per shard regardless of
        real row counts; expand_type_all allgathers the whole table). Each
        array counted once; a lower bound on real traffic per executed
        chain."""
        plan = self._last_plan
        if plan is None:
            return None
        W = 4  # int32 device arrays
        D = self.D
        seg_b = tab_b = exch_b = 0
        cap_prev = 0
        widths = _widths_in(plan)
        for i, s in enumerate(plan.steps):
            w_in, width = widths[i], widths[i + 1]
            if s.kind == "init_rows":
                cap_prev = s.cap
                tab_b += W * D * width * s.cap
                continue
            if s.kind == "init_index":
                idx = self.sstore.index_list(s.pid, s.dir)
                seg_b += int(idx.edges.size) * W
                cap_prev = s.cap
                tab_b += W * D * s.cap
                continue
            if s.kind == "init_const":
                seg = self.sstore.segment(s.pid, s.dir)
                seg_b += int(seg.nbytes) if seg is not None else 0
                cap_prev = s.cap
                tab_b += W * D * s.cap
                continue
            if s.exch_cap:
                exch_b += W * D * D * w_in * s.exch_cap
            if s.kind == "expand_type_all":
                # allgather replication of the whole table to every shard
                exch_b += W * D * D * w_in * cap_prev
            if s.kind == "member_index":
                idx = self.sstore.index_list(s.pid, s.dir)
                seg_b += int(idx.edges.size) * W
            elif s.kind in ("expand_versatile", "expand_versatile_const"):
                vseg = self.sstore.versatile_segment(s.dir)
                seg_b += int(vseg.nbytes) if vseg is not None else 0
            else:
                seg = self.sstore.segment(s.pid, s.dir)
                seg_b += int(seg.nbytes) if seg is not None else 0
            tab_b += W * D * (w_in * cap_prev + width * s.cap)
            cap_prev = s.cap
        return {"segment_bytes": int(seg_b), "table_bytes": int(tab_b),
                "exchange_bytes": int(exch_b),
                "total_bytes": int(seg_b + tab_b + exch_b)}

    # ------------------------------------------------------------------
    # plan building (host): pattern list -> step descriptors with capacities
    # ------------------------------------------------------------------
    def _build_plan(self, q: SPARQLQuery, cap_override: dict,
                    n_steps: int | None = None, seed=None) -> _Plan:
        plan = _Plan()
        v2c: dict[int, int] = {}
        width = 0
        aligned_col = None  # column rows are currently partitioned by
        est_rows = 1
        # upper bound on how many rows can share one value per column —
        # exchanges route equal values to one destination, so the hot-dest
        # load is bounded by est/D + the anchor column's multiplicity (the
        # University0-hub skew the reference absorbs via work stealing,
        # engine.hpp:186-207). Index/const starts yield unique values
        # (bound 1); expansions multiply every column's bound by the
        # segment's max degree, and the new column's bound is the REVERSE
        # segment's max degree times the anchor's. Unknown columns (seeds)
        # stay untracked -> the generic 4x-slack estimate.
        col_mult: dict[int, int] = {}
        MULT_CAP = 1 << 31

        # ``est_rows``: the rows a shard is expected to hold at a step, from
        # average degrees; each class is sized at twice it (one class of
        # headroom, as the single-chip engine's EST_SAFETY), the headroom
        # taken once a step and never compounded along the chain
        def cap_for(i, est):
            return cap_override.get(("cap", i)) or K.next_capacity(
                max(int(est) * 2, self.cap_min), self.cap_min, self.cap_max)

        def exch_cap_for(i, col):
            got = cap_override.get(("exch", i))
            if got:
                return got
            padded = est_rows * 2
            base = max(padded // self.D * 4, self.cap_min)
            hot = col_mult.get(col)
            if hot is not None:
                base = max(base, min(int(hot), int(padded))
                           + padded // self.D * 2)
            return K.next_capacity(min(base, self.cap_max),
                                   self.cap_min, self.cap_max)

        patterns = q.pattern_group.patterns[
            q.pattern_step:(None if n_steps is None
                            else q.pattern_step + n_steps)]
        if seed is not None:
            seed_table, seed_v2c = seed
            v2c.update(seed_v2c)
            width = seed_table.shape[1]
            first = patterns[0]
            if first.subject < 0:
                anchor = v2c.get(first.subject, NO_RESULT)
            else:
                # index membership or c2k on a bound (seeded) object column
                anchor = v2c.get(first.object, NO_RESULT)
            assert_ec(anchor != NO_RESULT,
                      ErrorCode.UNSUPPORTED_SHAPE,
                      "seeded distributed chains must start from a pattern "
                      "anchored on a seeded column")
            est_rows = max(len(seed_table) // self.D, 1)
            plan.steps.append(_Step(
                kind="init_rows", col=anchor, width=width,
                cap=self._seed_cap(seed_table, anchor)))
            aligned_col = anchor  # seed rows are sharded by the anchor owner
        for pat in patterns:
            i = len(plan.steps)  # step index (seeded chains prepend init_rows)
            s, p, d, o = pat.subject, pat.predicate, pat.direction, pat.object
            assert_ec(pat.pred_type == int(AttrType.SID_t),
                      ErrorCode.UNSUPPORTED_SHAPE,
                      "attr patterns are host-side in the distributed engine")
            if p < 0:
                # VERSATILE known_unknown_unknown (?x ?p ?y, x bound) and
                # known_unknown_const (?x ?p CONST): each shard expands
                # against its combined adjacency; a const object folds to an
                # equality filter inside the same program (beyond the
                # reference — its accelerator refuses every versatile
                # shape). A bound predicate or bound object stays host-side
                # (the CPU engine rejects those too).
                col = v2c.get(s, NO_RESULT) if s < 0 else NO_RESULT
                assert_ec(width > 0 and col != NO_RESULT and p not in v2c
                          and (o > 0 or o not in v2c),
                          ErrorCode.UNSUPPORTED_SHAPE,
                          "distributed versatile supports ?x ?p ?y / "
                          "?x ?p CONST with x bound and p fresh")
                exch_cap = 0
                if aligned_col != col:
                    exch_cap = exch_cap_for(i, col)
                vseg = self.sstore.versatile_segment(d)
                avg = vseg.avg_deg if vseg else 0.0
                est_rows = int(max(est_rows * max(avg, 0.1), 1))
                kind = "expand_versatile" if o < 0 else "expand_versatile_const"
                plan.steps.append(_Step(
                    kind=kind, pid=0, dir=d, col=col,
                    const=(o if o > 0 else 0),
                    cap=min(cap_for(i, est_rows), self.cap_max),
                    exch_cap=exch_cap, new_col=True))
                fwd_max = vseg.max_deg if vseg else 1
                for c in list(col_mult):
                    col_mult[c] = min(col_mult[c] * fwd_max, MULT_CAP)
                # the fresh columns' multiplicity bounds are unknown
                # (reverse combined degrees aren't tracked) — leave untracked
                v2c[p] = width
                width += 1
                if o < 0:
                    v2c[o] = width
                    width += 1
                aligned_col = col
                continue
            if i == 0 and seed is None and q.pattern_step == 0 \
                    and pat is patterns[0] and q.start_from_index():
                idx = self.sstore.index_list(s, d)
                est_rows = max(idx.total // self.D, 1)
                step = _Step(kind="init_index", pid=s, dir=d,
                             cap=cap_for(i, est_rows))
                v2c[o] = 0
                width = 1
                col_mult[0] = 1  # index members are globally unique
                aligned_col = 0  # index lists are owner-local by construction
                plan.steps.append(step)
                continue
            if width > 0 and _is_index_pattern(pat):
                # mid-chain index membership (index_to_known,
                # sparql.hpp:138-163): keep rows whose bound object is in
                # the owner shard's local index list
                ocol = v2c.get(o, NO_RESULT)
                assert_ec(ocol != NO_RESULT, ErrorCode.VERTEX_INVALID,
                          "index pattern needs a bound object mid-chain")
                exch_cap = 0
                if aligned_col != ocol:
                    exch_cap = exch_cap_for(i, ocol)
                self.sstore.index_list(s, d)  # ensure staged
                plan.steps.append(_Step(
                    kind="member_index", pid=s, dir=d, col=ocol,
                    cap=cap_for(i, est_rows), exch_cap=exch_cap))
                aligned_col = ocol
                continue
            if width == 0:
                assert_ec(s > 0, ErrorCode.FIRST_PATTERN_ERROR)
                seg = self.sstore.segment(p, d)
                est_rows = int(max(seg.avg_deg if seg else 1, 1))
                step = _Step(kind="init_const", pid=p, dir=d, const=s,
                             cap=cap_for(i, est_rows))
                v2c[o] = 0
                width = 1
                col_mult[0] = 1  # one const's neighbor list: unique values
                aligned_col = None  # rows sit on the const's owner, not col 0's
                plan.steps.append(step)
                continue

            if s > 0:
                # const_to_known mid-chain (sparql.hpp:138-163's c2k): the
                # membership "bound ?o in adj(const, p, d)" is exactly
                # "const in adj(?o, p, flip(d))" — a member step against the
                # reverse segment anchored on the bound object column
                ocol = v2c.get(o, NO_RESULT) if o < 0 else NO_RESULT
                assert_ec(ocol != NO_RESULT, ErrorCode.UNSUPPORTED_SHAPE,
                          "const subject mid-chain needs a bound object")
                fd = OUT if d == IN else IN
                exch_cap = 0
                if aligned_col != ocol:
                    exch_cap = exch_cap_for(i, ocol)
                self.sstore.segment(p, fd)  # ensure staged
                plan.steps.append(_Step(
                    kind="member", pid=p, dir=fd, col=ocol, vals_col=-1,
                    const=s, cap=cap_for(i, est_rows), exch_cap=exch_cap))
                aligned_col = ocol
                continue
            col = v2c.get(s, NO_RESULT)
            assert_ec(col != NO_RESULT, ErrorCode.UNSUPPORTED_SHAPE,
                      "distributed steps must anchor on a KNOWN subject")
            o_col = v2c.get(o, NO_RESULT) if o < 0 else NO_RESULT
            o_known = o < 0 and o_col != NO_RESULT

            type_all = (p == TYPE_ID and d == IN and o < 0 and not o_known)
            exch_cap = 0
            if not type_all and aligned_col != col:
                exch_cap = exch_cap_for(i, col)

            seg = self.sstore.segment(p, d)
            avg = seg.avg_deg if seg else 0.0
            if o < 0 and not o_known:  # expansion
                est_rows = int(max(est_rows * max(avg, 0.1), 1))
                kind = "expand_type_all" if type_all else "expand"
                step = _Step(kind=kind, pid=p, dir=d, col=col,
                             cap=min(cap_for(i, est_rows), self.cap_max),
                             exch_cap=exch_cap, new_col=True)
                if type_all:
                    col_mult.clear()  # allgather replication: bounds unknown
                else:
                    fwd_max = seg.max_deg if seg else 1
                    # host metadata only — staging the reverse segment to
                    # device for one scalar would waste HBM
                    rev_max = self.sstore.host_max_deg(p, OUT if d == IN else IN)
                    anchor_mult = col_mult.get(col)
                    for c in list(col_mult):
                        col_mult[c] = min(col_mult[c] * fwd_max, MULT_CAP)
                    if anchor_mult is not None:
                        col_mult[width] = min(anchor_mult * rev_max, MULT_CAP)
                v2c[o] = width
                width += 1
                aligned_col = width - 1 if type_all else col
            else:  # member filter
                step = _Step(kind="member", pid=p, dir=d, col=col,
                             vals_col=(o_col if o_known else -1),
                             const=(0 if o_known else o),
                             cap=cap_for(i, est_rows), exch_cap=exch_cap)
                aligned_col = col
            plan.steps.append(step)

        plan.width = width
        plan.v2c = v2c
        return plan

    # ------------------------------------------------------------------
    # compiled chain per plan signature
    # ------------------------------------------------------------------
    def _get_fn(self, plan: _Plan, seed=None, seed_cache: dict | None = None):
        # gather the device arrays each step needs (also the call args);
        # per-step (max_probe, max_deg_log2) join the cache key because the
        # compiled chain bakes them in as constants — a restaged segment
        # (dynamic insert) must never reuse a chain with smaller bounds
        bounds = []
        args = []
        for s in plan.steps:
            if s.kind == "init_rows":
                key = (s.col, s.cap)
                if seed_cache is None:
                    args.append(self._shard_seed(seed[0], s.col, s.cap))
                elif key not in seed_cache:
                    seed_cache[key] = self._shard_seed(seed[0], s.col, s.cap)
                    args.append(seed_cache[key])
                else:
                    args.append(seed_cache[key])
                bounds.append((0, 0))
            elif s.kind in ("init_index", "member_index"):
                idx = self.sstore.index_list(s.pid, s.dir)
                args.append((idx.edges, self._real_lens_arr(idx)))
                bounds.append((0, 0))
            elif s.kind in ("expand_versatile", "expand_versatile_const"):
                vseg = self.sstore.versatile_segment(s.dir)
                if vseg is None:
                    args.append(None)
                    bounds.append((0, 0))
                else:
                    args.append((vseg.bkey, vseg.bstart, vseg.bdeg,
                                 vseg.edges, vseg.edges2) + _fp_args(vseg))
                    bounds.append((vseg.max_probe, vseg.max_deg_log2,
                                   _fp_dup(vseg), vseg.key_shift))
            else:
                seg = self.sstore.segment(s.pid, s.dir)
                if seg is None:
                    args.append(None)
                    bounds.append((0, 0))
                else:
                    args.append((seg.bkey, seg.bstart, seg.bdeg, seg.edges)
                                + _fp_args(seg))
                    bounds.append((seg.max_probe, seg.max_deg_log2,
                                   _fp_dup(seg), seg.key_shift))
        sig = (plan.signature(), tuple(bounds))
        if sig in self._fn_cache:
            return self._fn_cache[sig], self._flatten_args(args)
        fn = self._compile(plan, args)
        self._fn_cache[sig] = fn
        return fn, self._flatten_args(args)

    def _real_lens_arr(self, idx):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(idx.real_lens.astype(np.int32).reshape(-1, 1),
                              NamedSharding(self.mesh, P(self.axis, None)))

    def _seed_cap(self, seed_table: np.ndarray, anchor: int) -> int:
        """Exact per-shard capacity for a seed table (host knows the counts)."""
        from wukong_tpu.utils.mathutil import hash_mod

        if len(seed_table) == 0:
            return self.cap_min
        dest = hash_mod(seed_table[:, anchor].astype(np.int32), self.D)
        peak = int(np.bincount(dest, minlength=self.D).max())
        return K.next_capacity(max(peak, 1), self.cap_min, self.cap_max)

    def _shard_seed(self, seed_table: np.ndarray, anchor: int, cap: int):
        """[N, W] host rows -> ([D, W, cap] int32 sharded, [D, 1] counts).

        Rows go to hash(anchor)%D — computed on the int32 view so host
        sharding matches the device-side `table[col] % D` exchange owner
        (BLANK_ID wraps to -1 on both sides)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        W = seed_table.shape[1]
        t32 = seed_table.astype(np.int32)  # ids < 2^31; BLANK wraps to -1
        from wukong_tpu.utils.mathutil import hash_mod

        dest = hash_mod(t32[:, anchor], self.D)
        out = np.zeros((self.D, W, cap), dtype=np.int32)
        counts = np.zeros((self.D, 1), dtype=np.int32)
        for d in range(self.D):
            rows = t32[dest == d]
            counts[d, 0] = len(rows)
            out[d, :, : len(rows)] = rows.T
        sharding = NamedSharding(self.mesh, P(self.axis, None, None))
        return (jax.device_put(out, sharding),
                jax.device_put(counts,
                               NamedSharding(self.mesh, P(self.axis, None))))

    @staticmethod
    def _flatten_args(args):
        flat = []
        for a in args:
            if a is not None:
                flat.extend(a)
        return flat

    def _compile(self, plan: _Plan, args_template):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        D = self.D
        axis = self.axis
        steps = [s for s in plan.steps]
        # arg layout mirrors _flatten_args
        arg_specs = []
        for a in args_template:
            if a is not None:
                arg_specs.extend([P(axis, *([None] * (x.ndim - 1))) for x in a])

        probes = {}
        depths = {}
        fps = {}  # the fingerprint probe's candidates a bucket; 0: plain
        shifts = {}  # the low key bits a table's home buckets ignore
        for i, s in enumerate(steps):
            if s.kind in ("expand_versatile", "expand_versatile_const"):
                # the combined segment's OWN probe bound — segment(pid=0)
                # would resolve to nothing and silently bake max_probe=1,
                # truncating probes on any hash-skewed versatile table
                vseg = self.sstore.versatile_segment(s.dir)
                probes[i] = vseg.max_probe if vseg else 1
                depths[i] = vseg.max_deg_log2 if vseg else 1
                fps[i] = _fp_dup(vseg)
                shifts[i] = vseg.key_shift if vseg else 0
            elif s.kind not in ("init_index", "init_rows", "member_index"):
                seg = self.sstore.segment(s.pid, s.dir)
                probes[i] = seg.max_probe if seg else 1
                depths[i] = seg.max_deg_log2 if seg else 1
                fps[i] = _fp_dup(seg)
                shifts[i] = seg.key_shift if seg else 0

        def probe_kw(i, fp):
            """How step ``i`` probes its table: its bounds, its key shift
            and, where staged, its fingerprint words."""
            kw = {"max_probe": probes[i], "key_shift": shifts[i]}
            if fp:
                kw.update(fpw0=fp[0], fpw1=fp[1], fp_dup=fps[i])
            return kw

        def wk_dist_chain(*flat):
            # unflatten per-step args (squeeze the leading shard axis)
            per_step = []
            it = iter(flat)
            for a in args_template:
                if a is None:
                    per_step.append(None)
                else:
                    per_step.append(tuple(next(it)[0] for _ in a))

            table = None
            n = jnp.int32(0)
            totals = [jnp.int32(0)] * len(steps)
            exch_totals = [jnp.int32(0)] * len(steps)
            # per step: live rows sent to other chips, slots shipped there
            sent = [jnp.int32(0)] * len(steps)
            slots = [jnp.int32(0)] * len(steps)

            for i, s in enumerate(steps):
                if s.kind == "init_rows":
                    table, counts = per_step[i]
                    n = counts[0]
                    totals[i] = n
                    continue
                if s.kind == "init_index":
                    edges, lens = per_step[i]
                    table, n = K.wk_walk_init_from_list.__wrapped__(
                        edges, lens[0], s.cap)
                    totals[i] = lens[0]
                    continue
                if s.kind == "init_const":
                    arrs = per_step[i]
                    const_tab = jnp.full((1, 1), np.int32(s.const), jnp.int32)
                    if arrs is None:
                        table = jnp.zeros((1, s.cap), jnp.int32)
                        n = jnp.int32(0)
                        continue
                    bkey, bstart, bdeg, edges, *fp = arrs
                    table, n, tot = K.wk_walk_expand.__wrapped__(
                        const_tab, jnp.int32(1), bkey, bstart, bdeg, edges,
                        col=0, cap_out=s.cap, **probe_kw(i, fp))
                    table = table[1:, :]  # drop the const row ([W, C] layout)
                    totals[i] = tot
                    continue

                if s.exch_cap:
                    table, n, em, tot_recv, sent[i] = _exchange(
                        table, n, s.col, s.exch_cap, s.cap, D, axis)
                    exch_totals[i] = em
                    totals[i] = jnp.maximum(totals[i], tot_recv)
                    slots[i] = jnp.int32((D - 1) * s.exch_cap)

                if s.kind == "member_index":
                    edges_i, lens = per_step[i]
                    keep = K.member_mask_list(
                        table, n, s.col, edges_i, lens[0])
                    table, n = K.wk_walk_compact.__wrapped__(table, keep)
                    continue

                arrs = per_step[i]
                if s.kind in ("expand_versatile", "expand_versatile_const"):
                    fold = s.kind == "expand_versatile_const"
                    if arrs is None:
                        table = jnp.concatenate(
                            [table,
                             jnp.zeros((1 if fold else 2, table.shape[1]),
                                       jnp.int32)],
                            axis=0)
                        n = jnp.int32(0)
                        continue
                    bkey, bstart, bdeg, edges, edges2, *fp = arrs
                    table, n, tot = K.wk_walk_expand2.__wrapped__(
                        table, n, bkey, bstart, bdeg, edges2, edges,
                        col=s.col, cap_out=s.cap, **probe_kw(i, fp))
                    totals[i] = jnp.maximum(totals[i], tot)
                    if fold:
                        # known_unknown_const: keep value == const rows,
                        # drop the value row — the surviving table binds
                        # only the predicate column
                        keep = (jnp.arange(s.cap, dtype=jnp.int32) < n) \
                            & (table[-1] == jnp.int32(s.const))
                        table, n = K.wk_walk_compact.__wrapped__(table, keep)
                        table = table[:-1]
                elif s.kind in ("expand", "expand_type_all"):
                    if s.kind == "expand_type_all":
                        sent[i] = n * (D - 1)
                        slots[i] = jnp.int32((D - 1) * table.shape[1])
                        table, n = _allgather_rows(table, n, D, axis)
                    if arrs is None:
                        table = jnp.concatenate(
                            [table, jnp.zeros((1, table.shape[1]), jnp.int32)],
                            axis=0)
                        n = jnp.int32(0)
                        continue
                    bkey, bstart, bdeg, edges, *fp = arrs
                    table, n, tot = K.wk_walk_expand.__wrapped__(
                        table, n, bkey, bstart, bdeg, edges, col=s.col,
                        cap_out=s.cap, **probe_kw(i, fp))
                    totals[i] = jnp.maximum(totals[i], tot)
                elif s.kind == "member":
                    if arrs is None:
                        keep = jnp.zeros(table.shape[1], bool)
                    else:
                        bkey, bstart, bdeg, edges, *fp = arrs
                        if s.vals_col >= 0:
                            vals = table[s.vals_col]
                        else:
                            vals = jnp.full(table.shape[1], np.int32(s.const))
                        keep = K.wk_walk_member_mask_known.__wrapped__(
                            table, n, vals, bkey, bstart, bdeg, edges,
                            col=s.col, depth=depths[i], **probe_kw(i, fp))
                    table, n = K.wk_walk_compact.__wrapped__(table, keep)

            return {
                "table": table[None],
                "n": n[None],
                "totals": jnp.stack(totals + exch_totals + sent
                                    + slots)[None],
            }

        out_specs = {"table": P(axis), "n": P(axis), "totals": P(axis)}
        return jax.jit(shard_map(wk_dist_chain, mesh=self.mesh,
                                 in_specs=tuple(arg_specs),
                                 out_specs=out_specs, check_vma=False))


def _fp_dup(seg) -> int:
    """How many candidate lanes the fingerprint probe verifies a bucket for
    ``seg`` (``tpu_kernels._hash_find_fp``), or 0 for the plain probe: where
    no fingerprints are staged or ``enable_fp_probe`` is off. The plain
    probe gathers three lanes-wide windows a round, some five times the
    fingerprint probe's elements."""
    if seg is None or seg.fpw0 is None \
            or not Global.enable_fp_probe:
        return 0
    return int(seg.max_fp_dup)


def _fp_args(seg) -> tuple:
    return (seg.fpw0, seg.fpw1) if _fp_dup(seg) else ()


def _widths_in(plan: _Plan) -> list[int]:
    """The binding table's width as each step of ``plan`` receives it, and
    last as the chain leaves it."""
    out, width = [], 0
    for s in plan.steps:
        out.append(width)
        if s.kind == "init_rows":
            width = s.width
        elif s.kind in ("init_index", "init_const"):
            width = 1
        elif s.new_col:
            width += 2 if s.kind == "expand_versatile" else 1
    return out + [width]


def _gather_host(tree):
    """Bring chain outputs to host. Single-process: plain device_get.
    Multi-process (jax.distributed, the reference's mpiexec contract,
    wukong.cpp:102-104): outputs are sharded across processes and
    device_get would raise on non-addressable shards — every process
    allgathers instead, so all controllers see identical totals/tables
    and take identical retry/assembly decisions (SPMD discipline)."""
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(tree, tiled=True)
    return jax.device_get(tree)


def _is_index_pattern(pat) -> bool:
    """Type/predicate index pattern: tpid subject under rdf:type or
    __PREDICATE__ with a variable object."""
    from wukong_tpu.types import is_tpid

    return (pat.subject > 0 and is_tpid(pat.subject)
            and pat.predicate in (PREDICATE_ID, TYPE_ID) and pat.object < 0)


class _ShardedAttrGraph:
    """Attribute lookups routed to the subject owner's partition — the same
    hash_mod placement build_partition uses for attr segments."""

    def __init__(self, stores: list):
        self.stores = stores
        self.D = len(stores)

    def get_attr(self, vid: int, aid: int, d: int = OUT):
        from wukong_tpu.utils.mathutil import hash_mod

        return self.stores[int(hash_mod(int(vid), self.D))].get_attr(
            vid, aid, d)


# ---------------------------------------------------------------------------
# collective building blocks (inside shard_map)
# ---------------------------------------------------------------------------


def _exchange(table, n, col, exch_cap: int, cap_new: int, D: int, axis: str):
    """Repartition rows to hash owners of `col` — the fork-join replacement.

    table: [W, C]. Per-destination capacity-padded all_to_all: send buffer
    [D, W, exch_cap]; per-dest row counts ride along so receivers compact
    exactly. A row's slot in its destination's block is its rank among the
    live rows bound there, a running count a destination: no sort. Returns
    (table [W, cap_new], n, max_dest_count, total_received, live rows sent
    to other chips).
    """
    import jax
    import jax.numpy as jnp

    W, C = table.shape
    live = jnp.arange(C, dtype=jnp.int32) < n
    dest = jnp.where(live, table[col] % D, D)
    to = dest[None, :] == jnp.arange(D, dtype=jnp.int32)[:, None]  # [D, C]
    rank = jnp.cumsum(to, axis=1, dtype=jnp.int32)
    counts = rank[:, -1]
    within = jnp.sum(jnp.where(to, rank, 0), axis=0) - 1
    slot = jnp.where(live & (within < exch_cap), dest * exch_cap + within,
                     D * exch_cap)
    send = jnp.zeros((W, D * exch_cap), jnp.int32).at[:, slot].set(
        table, mode="drop")
    send = send.reshape(W, D, exch_cap).transpose(1, 0, 2)  # [D, W, exch_cap]
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    rcounts = jax.lax.all_to_all(counts.reshape(D, 1), axis, 0, 0,
                                 tiled=False).reshape(D)
    cumr = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(rcounts)[:-1].astype(jnp.int32)])
    flat = recv.transpose(1, 0, 2).reshape(W, D * exch_cap)
    r_in_blk = jnp.tile(jnp.arange(exch_cap, dtype=jnp.int32), D)
    blk = jnp.repeat(jnp.arange(D, dtype=jnp.int32), exch_cap)
    valid = r_in_blk < jnp.minimum(rcounts, exch_cap)[blk]
    pos = jnp.where(valid, cumr[blk] + r_in_blk, cap_new)
    out = jnp.zeros((W, cap_new), jnp.int32).at[:, pos].set(flat, mode="drop")
    tot_recv = rcounts.sum().astype(jnp.int32)
    new_n = jnp.minimum(tot_recv, cap_new)
    sent = counts.sum() - counts[jax.lax.axis_index(axis)]
    return out, new_n, counts.max(), tot_recv, sent


def _allgather_rows(table, n, D: int, axis: str):
    """Replicate all live rows to every shard (dispatch-to-all for type steps).

    table: [W, C] -> [W, D*C]."""
    import jax
    import jax.numpy as jnp

    W, C = table.shape
    gat = jax.lax.all_gather(table, axis)  # [D, W, C]
    ns = jax.lax.all_gather(n, axis)  # [D]
    flat = gat.transpose(1, 0, 2).reshape(W, D * C)
    blk = jnp.repeat(jnp.arange(D, dtype=jnp.int32), C)
    r_in = jnp.tile(jnp.arange(C, dtype=jnp.int32), D)
    valid = r_in < ns[blk]
    cumn = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(ns)[:-1].astype(jnp.int32)])
    pos = jnp.where(valid, cumn[blk] + r_in, D * C)
    out = jnp.zeros((W, D * C), jnp.int32).at[:, pos].set(flat, mode="drop")
    return out, ns.sum().astype(jnp.int32)
