"""TPU accelerator engine — device-resident binding tables over staged CSR segments.

The analogue of the reference's GPU engine (core/gpu/gpu_engine.hpp +
gpu_engine_cuda.hpp): the binding table stays in device memory across pattern
steps (the dual-rbuf analogue — XLA owns the buffers), each step runs one of the
jitted kernels in tpu_kernels.py against segments staged by DeviceStore, and the
result is copied host-side only at the end (D2H only on the last pattern,
gpu_engine_cuda.hpp:189-196).

Scope EXCEEDS the reference's accelerator support matrix
(gpu_engine.hpp:267-333): index/const starts, known_to_unknown/known/const,
and every VERSATILE shape with an unbound predicate — known_unknown_unknown
and known_unknown_const via the combined-adjacency segment + expand2,
const_unknown_unknown / const_unknown_const via a host CSR init (the
reference refuses every versatile shape on GPU) — run on device; attribute
patterns, bound-predicate versatiles, OPTIONAL, and UNION fall back to the
CPU oracle kernels via a host sync — graceful degradation, not refusal.

Execution discipline: a host<->device sync is taken to cost far more than a
dispatch, which pipelines asynchronously (the ~70 ms per sync this design was
first sized against came from an earlier installation; not measured on the
attached chip). The driver therefore NEVER reads device values mid-query: output
capacities are *estimated* from host CSR metadata (segment average degree),
per-step true totals ride along as device scalars, and ONE device_get at the
end fetches table + row count + totals together. If any step overflowed its
capacity class, the whole chain re-runs with exact capacities (inputs are
immutable, so the retry is safe and rows are never lost).
"""

from __future__ import annotations

import numpy as np

from wukong_tpu.config import Global
from wukong_tpu.engine import tpu_kernels as K
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.device_store import DeviceStore
from wukong_tpu.obs.device import maybe_device_dispatch
from wukong_tpu.obs.trace import span, traced_execute, traced_step
from wukong_tpu.utils.timer import get_usec
from wukong_tpu.sparql.ir import NO_RESULT, Pattern, PGType, SPARQLQuery
from wukong_tpu.types import (
    IN,
    NORMAL_ID_START,
    OUT,
    PREDICATE_ID,
    TYPE_ID,
    AttrType,
)
from wukong_tpu.utils.errors import (
    BudgetExceeded,
    CapacityExceeded,
    ErrorCode,
    QueryTimeout,
    WukongError,
    assert_ec,
)

CONST_VAR, KNOWN_VAR, UNKNOWN_VAR = 0, 1, 2


class TPUEngine:
    """Executes one SPARQL query with device-resident pattern matching."""

    def __init__(self, gstore, str_server=None, device=None,
                 budget_bytes: int | None = None, stats=None):
        self.g = gstore
        self.str_server = str_server
        self.stats = stats  # optional planner Stats for capacity estimation
        if budget_bytes is None:
            # leave headroom for chain buffers: the segment cache gets the
            # configured share of HBM (gpu_kvcache analogue, Global config)
            budget_bytes = Global.tpu_mem_cache_gb << 30
        self.dstore = DeviceStore(gstore, budget_bytes=budget_bytes, device=device)
        self.cpu = CPUEngine(gstore, str_server)
        self.cap_min = Global.table_capacity_min
        self.cap_max = Global.table_capacity_max
        from wukong_tpu.utils.lru import LRUCache

        self._est_planner = None  # lazy Planner over self.stats
        # pattern-tuple -> {step: rows}; bounded LRU (a hot mixed workload
        # used to lose EVERY estimate at the old clear-at-4096 threshold)
        self._est_cache = LRUCache(4096)
        # chain shape -> {step: capacity class}: the classes a retry had to
        # grow to, so that the next draw of the template starts there
        self._cap_memo = LRUCache(4096)
        from wukong_tpu.engine.tpu_merge import MergeExecutor

        self.merge = MergeExecutor(self)  # sort-merge batch chains (v2)

    # estimate safety factor: one capacity class of headroom. Kernels pay for
    # CAPACITY, not live rows (a 2x over-provision doubles every gather), so
    # tight classes + overflow-retry beat compounding safety margins.
    EST_SAFETY = 2.0

    def _chain_estimates(self, patterns) -> dict[int, float]:
        """Per-step row estimates {step: rows} from the planner's joint
        type-table walk (optimizer.estimate_chain); empty when stats are
        absent or the chain shape defeats estimation. Memoized per pattern
        list — the emulator re-dispatches the same template thousands of
        times."""
        if self.stats is None:
            return {}
        key = tuple((p.subject, p.predicate, int(p.direction), p.object)
                    for p in patterns)
        cached = self._est_cache.get(key)
        if cached is not None:
            return cached
        if self._est_planner is None:
            from wukong_tpu.planner.optimizer import Planner

            self._est_planner = Planner(self.stats)
        try:
            ests = self._est_planner.estimate_chain(list(patterns))
        except Exception:
            ests = None
        out = ({} if ests is None
               else {k: max(float(e), 1.0) for k, e in enumerate(ests)})
        self._est_cache.put(key, out)
        return out

    def _const_start_sizing(self, q: SPARQLQuery, step_est: dict) -> dict:
        """A chain that starts from a constant is sized for the heaviest
        constant its start segment holds, not for the one drawn: degrees are
        skewed, an estimate from the average overflows on a heavy draw, and
        a class met for the first time is a program compiled inside a
        request. The later steps' estimates scale with the start's, so a
        template's capacity classes are its own, whatever constant comes."""
        pat = q.get_pattern(q.pattern_step)
        if q.pattern_step != 0 or q.start_from_index() or pat.predicate <= 0 \
                or pat.subject <= 0:
            return step_est
        heavy = self.g.max_degree(pat.predicate, pat.direction)
        mean = step_est.get(0)
        if not mean or heavy <= mean:
            return step_est
        return {k: v * (heavy / mean) for k, v in step_est.items()}

    def _sized_as(self, q: SPARQLQuery) -> list:
        """The patterns a chain is sized by: its own, with every type (of an
        index start, of a ``?x rdf:type T`` filter) replaced by its heaviest
        peer (``GStore.heaviest_peer_type``): a template that draws its
        type (WatDiv's S3 and S5: one of 15 product categories) then runs
        every draw at one set of capacity classes, and a type met for the
        first time compiles nothing."""
        peer = self.g.heaviest_peer_type
        out = []
        for i, p in enumerate(q.pattern_group.patterns):
            s, o = p.subject, p.object
            if p.predicate == TYPE_ID and i == 0 and q.start_from_index():
                s = peer(s)
            elif p.predicate == TYPE_ID and 0 < o < NORMAL_ID_START:
                o = peer(o)
            out.append(p if (s, o) == (p.subject, p.object) else
                       Pattern(s, p.predicate, p.direction, o, p.pred_type))
        return out

    @staticmethod
    def _chain_shape(patterns) -> tuple:
        """The plan with its vertex constants left out: what draws of one
        template share."""
        def elem(v):
            return 0 if v >= NORMAL_ID_START else int(v)

        return tuple((elem(p.subject), int(p.predicate), int(p.direction),
                      elem(p.object)) for p in patterns)

    # ------------------------------------------------------------------
    def execute(self, q: SPARQLQuery, from_proxy: bool = True) -> SPARQLQuery:
        return traced_execute(
            q, "tpu.execute", lambda: self._execute_impl(q, from_proxy),
            lambda: {"rows": q.result.nrows,
                     "status": q.result.status_code.name})

    def _execute_impl(self, q: SPARQLQuery,
                      from_proxy: bool = True) -> SPARQLQuery:
        try:
            if q.planner_empty and Global.enable_empty_shortcircuit:
                # planner-proved empty (planner.hpp:1505-1509): no device
                # work at all — the chain would stage segments and compile
                # only to produce zero rows
                self.cpu.short_circuit_empty(q)
                if from_proxy:
                    self.cpu._final_process(q)
                return q
            if getattr(q, "knn", None) is not None:
                # the hybrid seed/rank stages are host work either way
                # (vector/knn.py routes device scans itself), so the device
                # chain borrows the CPU engine's composition seams
                self.cpu._knn_pre(q)
            if q.has_pattern and not q.done_patterns():
                self._run_pattern_chain(q)
            if q.pattern_group.unions and not q.union_done:
                # children route back through THIS engine, so a branch BGP
                # rides the device chain (seeded upload init) when supported
                self.cpu._execute_unions(
                    q, child_exec=lambda c: self.execute(c, from_proxy=False))
            if q.pattern_group.optional:
                from wukong_tpu.engine.optional_join import (
                    execute_optional_leftjoin,
                )

                while q.optional_step < len(q.pattern_group.optional):
                    group = q.pattern_group.optional[q.optional_step]
                    shares = any(
                        v < 0 and q.result.var2col(v) != NO_RESULT
                        for p in group.patterns
                        for v in (p.subject, p.object))
                    # a parent-bound PREDICATE var has no seeded-child
                    # kernel (the child would re-solve it unconstrained) —
                    # the in-place host formulation handles that shape
                    pred_bound = any(
                        p.predicate < 0
                        and q.result.var2col(p.predicate) != NO_RESULT
                        for p in group.patterns)
                    if q.result.attr_col_num == 0 and shares \
                            and not pred_bound:
                        # dedup-seeded child + host left join: the group's
                        # BGP rides the device chain (seeded upload init)
                        execute_optional_leftjoin(
                            q, self.cpu,
                            run_child=lambda c: self.execute(
                                c, from_proxy=False),
                            str_server=self.str_server)
                    else:
                        # no shared binding (e.g. optional-only queries) or
                        # attr columns: the in-place host formulation
                        self.cpu._execute_optional(q)
            with span(getattr(q, "trace", None), "tpu.finalize"):
                if q.pattern_group.filters:
                    self.cpu._execute_filters(q)
                if getattr(q, "knn", None) is not None:
                    self.cpu._knn_post(q)
                if from_proxy:
                    self.cpu._final_process(q)
        except (QueryTimeout, BudgetExceeded) as e:
            from wukong_tpu.runtime.resilience import mark_partial

            mark_partial(q, e)
        except WukongError as e:
            q.result.status_code = e.code
        return q

    # ------------------------------------------------------------------
    # chain planning + execution with deferred overflow handling
    # ------------------------------------------------------------------
    def _run_pattern_chain(self, q: SPARQLQuery) -> None:
        # device prefix: the longest run of device-supported steps
        device_steps = 0
        flipped: set = set()
        probe = _MetaResult(q.result)
        for i in range(q.pattern_step, len(q.pattern_group.patterns)):
            pat = q.get_pattern(i)
            if not self._device_supported(q, pat, probe, i == q.pattern_step):
                break
            if pat.subject > 0 and i > q.pattern_step:
                flipped.add(i)  # const_to_known reads the reversed segment
            probe.bind(pat)
            device_steps += 1

        tr = getattr(q, "trace", None)
        if device_steps:
            # pin this query's segments for the chain's lifetime (the
            # GPUCache conflict-aware eviction analogue, gpu_cache.hpp).
            # A versatile CONST start is answered by one host CSR walk —
            # staging the whole-graph combined segment for it would be the
            # largest staging in the system for a one-lookup step, so it is
            # excluded (like the index-origin start below).
            first = q.get_pattern(q.pattern_step)
            vlo = q.pattern_step
            if q.result.col_num == 0 and first.predicate < 0 \
                    and first.subject > 0:
                vlo = q.pattern_step + 1
            def seg_pat(i):
                pat = q.get_pattern(i)
                return _flipped(pat, probe) if i in flipped else pat

            pins = [(seg_pat(i).predicate, seg_pat(i).direction)
                    for i in range(q.pattern_step, q.pattern_step + device_steps)
                    if q.get_pattern(i).predicate > 0]
            pins += [("vpv", int(q.get_pattern(i).direction))
                     for i in range(vlo, q.pattern_step + device_steps)
                     if q.get_pattern(i).predicate < 0]
            with span(tr, "tpu.stage"):
                self.dstore.pin(pins)
                if Global.gpu_enable_pipeline:
                    # stage every chain segment up front: device_put
                    # dispatches asynchronously, so the H2D transfers overlap
                    # the first steps' compute (gpu_engine_cuda.hpp:143-150's
                    # second-stream prefetch, collapsed into the async
                    # dispatch queue). An index-origin START consumes an
                    # index list, not a segment — staging its (TYPE_ID, dir)
                    # segment would build the whole type CSR for nothing, so
                    # it is skipped.
                    lo = max(q.pattern_step, vlo)
                    if lo == 0 and q.start_from_index() \
                            and _is_index_start(q.get_pattern(0)):
                        lo = 1
                    self.dstore.prefetch(
                        seg_pat(i) for i in
                        range(lo, q.pattern_step + device_steps))
            try:
                self._run_chain_pinned(q, device_steps)
            finally:
                self.dstore.unpin(pins)
        # host fallback for any remaining steps
        while not q.done_patterns():
            traced_step(tr, q, "tpu.host_step",
                        lambda: self.cpu._execute_one_pattern(q))

    def _run_chain_pinned(self, q: SPARQLQuery, device_steps: int) -> None:
        # blind queries with nothing after the device chain only need the
        # row count — skip the table transfer entirely (the reference's
        # silent mode never ships result tables, proxy.hpp blind)
        blind_ok = (q.result.blind
                    and device_steps + q.pattern_step
                    == len(q.pattern_group.patterns)
                    and not q.pattern_group.unions
                    and not q.pattern_group.optional
                    and not q.pattern_group.filters)
        sized = self._sized_as(q) if q.pattern_step == 0 else None
        shape = self._chain_shape(sized) if sized else None
        cap_override: dict[int, int] = dict(
            self._cap_memo.get(shape) or {}) if shape else {}
        step_est = (self._const_start_sizing(q, self._chain_estimates(sized))
                    if sized else {})
        # chain-level span: per-BGP-step work is fused into one compiled
        # dispatch here, so the trace carries steps + kernel-dispatch count
        # (attempts x steps) + rows out at chain granularity; each attempt
        # is a tpu.dispatch / tpu.sync pair inside it
        tr = getattr(q, "trace", None)
        with span(tr, "tpu.chain", steps=device_steps,
                  rows_in=q.result.nrows, attempts=0) as sp:
            try:
                grown = self._chain_attempts(q, device_steps, cap_override,
                                             step_est, blind_ok, tr, sp)
                if grown and shape:
                    # classes only grow: a lighter draw's retry takes none
                    # back from a heavier one's
                    kept = self._cap_memo.get(shape) or {}
                    self._cap_memo.put(shape, {
                        st: max(c, kept.get(st, 0))
                        for st, c in {**kept, **cap_override}.items()})
            finally:
                if sp is not None:
                    sp.attrs.update(
                        dispatches=sp.attrs["attempts"] * device_steps,
                        rows_out=q.result.nrows)

    def _chain_attempts(self, q: SPARQLQuery, device_steps: int,
                        cap_override: dict, step_est: dict,
                        blind_ok: bool, tr, chain_span) -> bool:
        """-> whether a step overflowed its class and the chain ran again
        (traced: one ``capacity.retry`` event a step that grew)."""
        from wukong_tpu.runtime.resilience import charge_query, check_query

        grown = False
        # every attempt puts right at least the first step that overflowed,
        # so a chain of k steps is sound after k + 1 at most; eight was the
        # limit whatever the length, and LSQB's q3 (16 steps whose later
        # estimates are under one row) met it with steps still to grow
        for _attempt in range(max(8, device_steps + 2)):
            if chain_span is not None:
                chain_span.attrs["attempts"] = _attempt + 1
            check_query(q, f"tpu.chain attempt {_attempt}")
            t0 = get_usec()
            with span(tr, "tpu.dispatch"):
                state = self._dispatch_chain(q, device_steps, cap_override,
                                             step_est)
            with span(tr, "tpu.sync"):
                host_table, n, totals = state.sync(blind=blind_ok)
            moved = 4 * (1 + len(totals))  # the ride-along scalars
            if not blind_ok and hasattr(host_table, "nbytes"):
                moved += int(host_table.nbytes)
            _charge_chain(q, "tpu.chain", totals, get_usec() - t0, moved)
            over = [s for s, t, c in totals if t > c]
            if not over:
                if grown:
                    # what is remembered for the template's next draw is
                    # what the data asked for, not what the retries guessed
                    for s, t, _c in totals:
                        if s in cap_override:
                            cap_override[s] = K.next_capacity(
                                int(t), self.cap_min, self.cap_max)
                break
            # steps after the first overflow counted over a cut table: their
            # totals are too low by about the share that was cut, so they
            # grow by it too, or a chain of k steps needs k attempts
            first = min(over)
            lost = max(t / c for s, t, c in totals if s == first)
            for s, t, c in totals:
                if s > first and t <= c:
                    t = min(int(max(t, 1) * lost), self.cap_max)
                if t > c:
                    if t > self.cap_max:
                        # CapacityExceeded (not a query bug): the proxy
                        # degrades to the CPU engine, which has no capacity
                        # classes and can materialize the oversized table
                        raise CapacityExceeded(
                            f"intermediate result ({t:,} rows) exceeds "
                            f"table_capacity_max ({self.cap_max:,})")
                    cap_override[s] = K.next_capacity(int(t), self.cap_min,
                                                      self.cap_max)
                    grown = True
                    if tr is not None:
                        tr.event("capacity.retry", site="tpu.chain", step=s,
                                 cap_from=c, cap_to=cap_override[s])
        else:
            raise WukongError(ErrorCode.UNKNOWN_PATTERN,
                              "capacity retry limit exceeded")
        charge_query(q, int(n), "tpu.chain")
        res = q.result
        if blind_ok:
            res.nrows = n
        else:
            res.set_table(host_table[:n].astype(np.int64))
        for var, col in state.new_cols:
            res.add_var2col(var, col)
        res.col_num = state.width
        q.pattern_step += device_steps
        if device_steps and q.get_pattern(q.pattern_step - 1) is not None:
            q.local_var = state.local_var
        return grown

    def _dispatch_chain(self, q: SPARQLQuery, device_steps: int,
                        cap_override: dict,
                        step_est: dict | None = None) -> "_ChainState":
        import jax.numpy as jnp

        state = _ChainState(q.result)
        state.step_est = step_est or {}
        for k in range(device_steps):
            step = q.pattern_step + k
            pat = q.get_pattern(step)
            self._dispatch_one(q, pat, step, state, cap_override)
        return state

    # ------------------------------------------------------------------
    def _dispatch_one(self, q: SPARQLQuery, pat, step: int, state: "_ChainState",
                      cap_override: dict, anchor_col: int | None = None) -> None:
        import jax.numpy as jnp

        start, pid, d, end = pat.subject, pat.predicate, pat.direction, pat.object
        # traced, every call of a jitted kernel below is one device.dispatch
        # event
        tr = getattr(q, "trace", None)

        if state.table is None and state.width > 0:
            # seeded chain (UNION child over the parent's binding table):
            # upload the host table once, then dispatch this pattern as a
            # normal anchored step. Upload capacity is exact (row count is
            # known), so it never participates in the overflow retry.
            # Parent tables at union time carry no BLANKs (optionals run
            # after unions in the state machine), so int32 is lossless.
            host_t = q.result.table
            n0 = len(host_t)
            assert_ec(n0 <= self.cap_max, ErrorCode.UNKNOWN_PATTERN,
                      f"seed table ({n0:,} rows) exceeds "
                      f"table_capacity_max ({self.cap_max:,})")
            cap = K.next_capacity(max(n0, 1), self.cap_min, self.cap_max)
            pad = np.zeros((state.width, cap), dtype=np.int32)
            if host_t.size:
                pad[:, :n0] = host_t.T
            state.table = jnp.asarray(pad)
            state.n = jnp.int32(n0)
            state.est_rows = max(n0, 1)

        if state.table is None:
            if q.start_from_index() and step == q.pattern_step == 0 \
                    and _is_index_start(pat):
                edges, real = self.dstore.index_list(start, d)
                heavy = real
                if q.mt_factor > 1:
                    lo, hi = _mt_slice(real, q.mt_factor, q.mt_tid)
                    edges, real = edges[lo:hi], hi - lo
                    heavy = real
                elif pid == TYPE_ID:  # the class of the heaviest peer: _sized_as
                    heavy = max(real, len(self.g.get_index(
                        self.g.heaviest_peer_type(start), d)))
                cap = max(cap_override.get(step, 0),
                          K.next_capacity(heavy, self.cap_min, self.cap_max))
                if tr is not None:
                    tr.event("device.dispatch", kernel="init_from_list")
                table, nn = K.wk_walk_init_from_list(edges, jnp.int32(real),
                                                     cap)
                state.begin(table, nn, end, est_rows=heavy)
                state.local_var = end
                return
            if pid < 0:
                # versatile const start (CONST ?p ?y / CONST1 ?p CONST2,
                # sparql.hpp:246-290's const_unknown_* — the reference GPU
                # engine refuses these): the const's combined adjacency is
                # one host CSR lookup, so the table is built host-side and
                # the device chain continues from it
                assert_ec(q.result.col_num == 0 and state.width == 0,
                          ErrorCode.FIRST_PATTERN_ERROR)
                prs, vls = [], []
                for p in self.g.get_triples(start, PREDICATE_ID, d):
                    nb = self.g.get_triples(start, int(p), d)
                    prs.extend([int(p)] * len(nb))
                    vls.extend(int(v) for v in nb)
                prs = np.asarray(prs, dtype=np.int64)
                vls = np.asarray(vls, dtype=np.int64)
                if end > 0:  # const object: keep matching pairs, bind p only
                    sel = vls == end
                    cols_data, bind = [prs[sel]], [pid]
                else:
                    cols_data, bind = [prs, vls], [pid, end]
                real = len(cols_data[0])
                assert_ec(real <= self.cap_max, ErrorCode.UNKNOWN_PATTERN,
                          f"versatile const start ({real:,} pairs) exceeds "
                          f"table_capacity_max ({self.cap_max:,})")
                cap = cap_override.get(step) or K.next_capacity(
                    max(real, 1), self.cap_min, self.cap_max)
                pad = np.zeros((len(cols_data), cap), dtype=np.int32)
                for r, cd in enumerate(cols_data):
                    pad[r, :real] = cd
                state.table = jnp.asarray(pad)
                state.n = jnp.int32(real)
                for v in bind:
                    state.cols[v] = state.width
                    state.new_cols.append((v, state.width))
                    state.width += 1
                state.est_rows = max(real, 1)
                return
            # const_to_unknown start
            assert_ec(q.result.col_num == 0 and state.width == 0,
                      ErrorCode.FIRST_PATTERN_ERROR)
            vids = np.asarray(self.g.get_triples(start, pid, d), dtype=np.int64)
            # the class of the segment's heaviest constant: _const_start_sizing
            heavy = max(len(vids), self.g.max_degree(pid, d))
            cap = max(cap_override.get(step, 0),
                      K.next_capacity(heavy, self.cap_min, self.cap_max))
            pad = np.zeros((1, cap), dtype=np.int32)  # [width=1, capacity]
            pad[0, : len(vids)] = vids
            state.begin(jnp.asarray(pad), jnp.int32(len(vids)), end,
                        est_rows=heavy)
            return

        if start > 0 and anchor_col is None:  # const_to_known: see _flipped
            pat = _flipped(pat, state)
            assert_ec(pat is not None, ErrorCode.VERTEX_INVALID)
            start, d, end = pat.subject, pat.direction, pat.object
        col = anchor_col if anchor_col is not None else state.col_of(start)
        assert_ec(col is not None, ErrorCode.VERTEX_INVALID)
        if pid < 0:  # versatile known_unknown_* via expand2
            vseg = self.dstore.versatile_segment(d)
            if vseg is None:
                state.append_empty_col(pid)
                if end < 0:
                    state.append_empty_col(end)
                return
            fan = max(1.0, vseg.num_edges / max(vseg.num_keys, 1)) * 2
            est = min(int(state.est_rows * fan) or 1, self.cap_max)
            cap_out = max(cap_override.get(step, 0), K.next_capacity(
                max(est, self.cap_min), self.cap_min, self.cap_max))
            fd = self._fp_dup(vseg)
            if tr is not None:
                tr.event("device.dispatch", kernel="expand2")
            out, nn, total = K.wk_walk_expand2(
                state.table, state.n, vseg.bkey, vseg.bstart, vseg.bdeg,
                vseg.edges2, vseg.edges, col=col, cap_out=cap_out,
                max_probe=vseg.max_probe,
                fpw0=vseg.fpw0 if fd else None,
                fpw1=vseg.fpw1 if fd else None, fp_dup=fd)
            if end > 0:
                # known_unknown_const (?x ?p CONST, sparql.hpp:651-699):
                # filter the expanded pairs to value == const inside the
                # same program, then drop the value row — the surviving
                # table binds only the predicate column (CPU layout parity)
                state.totals.append((step, total, cap_out))
                if tr is not None:
                    tr.event("device.dispatch", kernel="compact")
                keep = (jnp.arange(cap_out, dtype=jnp.int32) < nn) \
                    & (out[-1] == jnp.int32(end))
                out, nn = K.wk_walk_compact(out, keep)
                state.table = out[:-1]
                state.n = nn
                state.cols[pid] = state.width
                state.new_cols.append((pid, state.width))
                state.width += 1
                # the fold only shrinks the expansion, so the expand estimate
                # is a safe (over-)estimate for downstream capacity sizing
                state.est_rows = max(min(est, cap_out), 1)
                return
            state.advance_expand2(out, nn, pid, end, total, cap_out, step,
                                  est_rows=min(est, cap_out))
            return
        seg = self.dstore.segment(pid, d)
        e_col = state.col_of(end) if end < 0 else None
        e_known = end < 0 and e_col is not None

        if end < 0 and not e_known:  # known_to_unknown
            if seg is None:
                state.append_empty_col(end)
                return
            est = self._estimate_rows(state, pat, seg, step=step)
            cap_out = max(cap_override.get(step, 0), K.next_capacity(
                max(est, self.cap_min), self.cap_min, self.cap_max))
            fd = self._fp_dup(seg)
            if tr is not None:
                tr.event("device.dispatch", kernel="expand")
            out, nn, total = K.wk_walk_expand(
                state.table, state.n, seg.bkey, seg.bstart, seg.bdeg,
                seg.edges, col=col, cap_out=cap_out,
                max_probe=seg.max_probe,
                fpw0=seg.fpw0 if fd else None,
                fpw1=seg.fpw1 if fd else None, fp_dup=fd)
            state.advance_expand(out, nn, end, total, cap_out, step,
                                 est_rows=min(est, cap_out))
        else:  # known_to_known / known_to_const
            if seg is None:
                keep = jnp.zeros(state.table.shape[1], dtype=bool)
            else:
                if e_known:
                    vals = state.table[e_col]
                else:
                    vals = jnp.full(state.table.shape[1], np.int32(end))
                fd = self._fp_dup(seg)
                if tr is not None:
                    tr.event("device.dispatch", kernel="member_mask_known")
                keep = K.wk_walk_member_mask_known(
                    state.table, state.n, vals, seg.bkey, seg.bstart,
                    seg.bdeg, seg.edges, col=col, max_probe=seg.max_probe,
                    depth=seg.max_deg_log2,
                    fpw0=seg.fpw0 if fd else None,
                    fpw1=seg.fpw1 if fd else None, fp_dup=fd)
            C = state.table.shape[1]
            se = state.step_est.get(step)
            cap_new = cap_override.get(step)
            if se is not None:
                cap_new = max(cap_new or 0, K.next_capacity(
                    max(int(se * self.EST_SAFETY * state.skew), self.cap_min),
                    self.cap_min, self.cap_max))
            if cap_new is not None and cap_new < C:
                # estimate-driven shrink: totals ride-along so an
                # underestimate retries the chain, never drops rows
                if tr is not None:
                    tr.event("device.dispatch", kernel="compact_to")
                out, nn, total = K.wk_walk_compact_to(state.table, keep,
                                                      cap_new)
                state.advance_filter(out, nn)
                state.totals.append((step, total, cap_new))
            else:
                if tr is not None:
                    tr.event("device.dispatch", kernel="compact")
                out, nn = K.wk_walk_compact(state.table, keep)
                state.advance_filter(out, nn)

    # ------------------------------------------------------------------
    # batched execution: one compiled chain answers B template instances
    # (the emulator's TPU win — batch=1024 queries of one template compile to
    # one program; SURVEY §7.6)
    # ------------------------------------------------------------------
    def execute_batch(self, q: SPARQLQuery, consts: np.ndarray) -> np.ndarray:
        """Run a planned const-start query for B different start constants.

        The binding table carries a qid column; all steps run once for the
        whole batch; returns per-query result row counts (blind semantics).
        """
        import jax
        import jax.numpy as jnp

        pats = q.pattern_group.patterns
        self._check_batch_const(q)
        B = len(consts)
        if q.planner_empty and Global.enable_empty_shortcircuit:
            return np.zeros(B, dtype=np.int64)
        if Global.enable_merge_join and self.merge.supports(q):
            return self.merge.run_batch_const(q, consts)

        def make_init(state: "_ChainState", cap_override: dict) -> int:
            # init: [2, cap] — row 0 qid, row 1 the per-instance start constant
            cap0 = K.next_capacity(B, self.cap_min)
            init = np.zeros((2, cap0), dtype=np.int32)  # [width, capacity]
            init[0, :B] = np.arange(B)
            init[1, :B] = consts
            state.table = jnp.asarray(init)
            state.n = jnp.int32(B)
            state.width = 2
            state.cols[pats[0].subject] = 1  # start consts act as a known col
            state.est_rows = B
            return 0  # dispatch every pattern (the const col pre-binds step 0)

        return self._run_batch_chain(q, B, make_init, est_mult=float(B))

    def _check_batch_const(self, q: SPARQLQuery) -> None:
        """Shared validation for the const-batch entry points: every step
        must be device-supported (the start constant column counts as known
        for steps that re-anchor on it — the reference plans such shapes as
        known_to_*)."""
        pats = q.pattern_group.patterns
        assert_ec(len(pats) > 0 and pats[0].subject > 0,
                  ErrorCode.UNKNOWN_PLAN, "batch execution needs a const start")
        probe = _MetaResult(q.result)
        probe.cols[pats[0].subject] = 1
        probe.width = 2
        for k, pat in enumerate(pats):
            assert_ec(pat.pred_type == int(AttrType.SID_t) and pat.predicate >= 0,
                      ErrorCode.UNKNOWN_PATTERN,
                      "batch steps must have const SID predicates")
            if k > 0:
                assert_ec(probe.col_of(pat.subject) is not None,
                          ErrorCode.UNKNOWN_PATTERN,
                          "batch steps must anchor on a bound column")
            probe.bind(pat)

    def execute_batch_many(self, q: SPARQLQuery, consts_list: list) -> list:
        """K const-batches with as few device syncs as the active path
        allows (the emulator's in-flight window). Applies the same guards
        as execute_batch: planner-proved-empty classes answer instantly,
        the merge path dispatches all K batches back-to-back and syncs
        ONCE (run_batch_const_many), anything else degrades to a per-batch
        loop — callers never need routing knowledge."""
        self._check_batch_const(q)
        if q.planner_empty and Global.enable_empty_shortcircuit:
            return [np.zeros(len(c), dtype=np.int64) for c in consts_list]
        if Global.enable_merge_join and self.merge.supports(q):
            return self.merge.run_batch_const_many(q, consts_list)
        return [self.execute_batch(q, c) for c in consts_list]

    def execute_batch_mixed(self, jobs: list) -> list:
        """One device flight across MULTIPLE const-start templates (the
        cross-class window): jobs = [(query, consts), ...]. Planner-empty
        jobs answer instantly; merge-supported jobs share ONE sync via
        run_batch_const_mixed; the rest degrade to per-job execute_batch.
        Returns per-job count arrays in input order."""
        out: list = [None] * len(jobs)
        mixed = []
        for i, (q, consts) in enumerate(jobs):
            self._check_batch_const(q)
            if q.planner_empty and Global.enable_empty_shortcircuit:
                out[i] = np.zeros(len(consts), dtype=np.int64)
            elif Global.enable_merge_join and self.merge.supports(q):
                mixed.append(i)
            else:
                out[i] = self.execute_batch(q, consts)
        if mixed:
            res = self.merge.run_batch_const_mixed([jobs[i] for i in mixed])
            for i, r in zip(mixed, res):
                out[i] = r
        return out

    def execute_batch_index(self, q: SPARQLQuery, B: int,
                            slice_mode: bool = False) -> np.ndarray:
        """Batched execution of an index-origin (heavy) query.

        replicate mode: B independent full instances — the qid dimension
        amortizes the end-of-chain device sync across B queries (the
        reference's 'at batch' heavy throughput). slice mode: the index scan
        is split into B contiguous slices (qid = slice), the single-chip
        analogue of fanning a heavy query out to num_servers x mt_factor
        engines (sparql.hpp:98-108, 1064-1088); per-qid counts sum to the
        query total. Returns per-qid result row counts (blind semantics).

        ``q.mt_factor > 1`` pre-slices the index list to this copy's mt
        range before batching (the heavy-lane split: runtime/batcher.py
        fans one dispatch out as mt_factor carrier copies across pool
        engines; per-part counts sum to the full query's total).
        """
        import jax.numpy as jnp

        pats = q.pattern_group.patterns
        self._check_batch_index(q)
        if q.planner_empty and Global.enable_empty_shortcircuit:
            return np.zeros(B, dtype=np.int64)
        if Global.enable_merge_join and self.merge.supports(q) \
                and q.mt_factor <= 1 and not slice_mode:
            # merge only for REPLICATE mode (B independent instances — the
            # emulator's heavy-throughput shape, where the shared sort
            # amortizes over B copies). Slice mode runs the chain once at
            # 1/B granularity: the direct path is ~5x cheaper for it
            # (measured on this container: 60ms merge vs 12ms direct for a
            # 3-hop 16k-row scan), and mt-sliced split carriers need the
            # direct path's index pre-slicing anyway.
            return self.merge.run_batch_index(q, B, slice_mode)
        edges, real = self.dstore.index_list(pats[0].subject, pats[0].direction)
        if q.mt_factor > 1:
            lo, hi = _mt_slice(real, q.mt_factor, q.mt_tid)
            edges, real = edges[lo:hi], hi - lo
        total0 = real if slice_mode else real * B
        assert_ec(total0 <= self.cap_max, ErrorCode.UNKNOWN_PATTERN,
                  f"batch-index start ({total0:,} rows) exceeds "
                  f"table_capacity_max ({self.cap_max:,})")

        def make_init(state: "_ChainState", cap_override: dict) -> int:
            # total0 <= cap_max was asserted above, so cap0 always suffices
            # (the init step does not participate in the overflow-retry loop)
            cap0 = K.next_capacity(
                max(total0, 1), self.cap_min, self.cap_max)
            state.table, state.n = K.wk_walk_init_batch_index(
                edges, jnp.int32(real), B=B, cap=cap0, slice_mode=slice_mode)
            state.width = 2
            state.cols[pats[0].object] = 1
            state.est_rows = max(total0, 1)
            return 1  # pattern 0 is consumed by the init

        return self._run_batch_chain(q, B, make_init,
                                     est_mult=1.0 if slice_mode else float(B))

    def _check_batch_index(self, q: SPARQLQuery) -> None:
        """Shared validation for the index-origin batch entry points."""
        pats = q.pattern_group.patterns
        assert_ec(len(pats) > 0 and q.start_from_index()
                  and _is_index_start(pats[0]) and pats[0].object < 0,
                  ErrorCode.UNKNOWN_PLAN,
                  "batch-index execution needs an index-origin start")
        probe = _MetaResult(q.result)
        probe.cols[pats[0].object] = 1
        probe.width = 2
        for k, pat in enumerate(pats):
            assert_ec(pat.pred_type == int(AttrType.SID_t) and pat.predicate >= 0,
                      ErrorCode.UNKNOWN_PATTERN,
                      "batch steps must have const SID predicates")
            if k > 0:
                assert_ec(probe.col_of(pat.subject) is not None,
                          ErrorCode.UNKNOWN_PATTERN,
                          "batch steps must anchor on a bound column")
                probe.bind(pat)

    def execute_batch_index_many(self, q: SPARQLQuery, B: int,
                                 K_batches: int) -> list:
        """K replicate-mode heavy batches with as few device syncs as the
        active path allows (the heavy-class in-flight window) — same guard
        structure as execute_batch_many."""
        self._check_batch_index(q)
        if q.planner_empty and Global.enable_empty_shortcircuit:
            return [np.zeros(B, dtype=np.int64) for _ in range(K_batches)]
        if Global.enable_merge_join and self.merge.supports(q):
            return self.merge.run_batch_index_many(q, B, K_batches)
        return [self.execute_batch_index(q, B) for _ in range(K_batches)]

    def _run_batch_chain(self, q: SPARQLQuery, B: int, make_init,
                         est_mult: float = 1.0) -> np.ndarray:
        import jax

        from wukong_tpu.runtime.resilience import check_query

        pats = q.pattern_group.patterns
        step_est = {k: e * est_mult
                    for k, e in self._chain_estimates(pats).items()}
        pins = [(p.predicate, p.direction) for p in pats if p.predicate > 0]
        self.dstore.pin(pins)
        if Global.gpu_enable_pipeline:
            # skip an index-origin start — it consumes an index list
            skip0 = q.start_from_index() and _is_index_start(pats[0])
            self.dstore.prefetch(pats[1:] if skip0 else pats)
        try:
            cap_override: dict[int, int] = {}
            for _attempt in range(8):
                # fused heavy dispatches carry the group deadline
                # (runtime/batcher.py): abort between capacity attempts
                # instead of burning retries past the wall clock
                check_query(q, f"tpu.batch_chain attempt {_attempt}")
                state = _ChainState(q.result)
                state.step_est = step_est
                first = make_init(state, cap_override)
                for k in range(first, len(pats)):
                    pat = q.get_pattern(k)
                    anchor = state.col_of(pat.subject)
                    self._dispatch_one(q, pat, k, state, cap_override,
                                       anchor_col=anchor)
                t0 = get_usec()
                counts = _qid_counts(state.table, state.n, B)
                payload = (counts, [t for (_, t, _) in state.totals])
                host_counts, totals = jax.device_get(payload)
                _charge_chain(
                    q, "tpu.batch_chain",
                    [(s, int(t), c)
                     for (s, _, c), t in zip(state.totals, totals)],
                    get_usec() - t0,
                    4 * (B + len(totals)))
                over = False
                for (s, _, c), t in zip(state.totals, totals):
                    if int(t) > c:
                        if int(t) > self.cap_max:
                            raise WukongError(
                                ErrorCode.UNKNOWN_PATTERN,
                                f"batch intermediate ({int(t):,} rows) exceeds "
                                f"table_capacity_max ({self.cap_max:,})")
                        cap_override[s] = K.next_capacity(int(t), self.cap_min,
                                                          self.cap_max)
                        over = True
                if not over:
                    return np.asarray(host_counts)
            raise WukongError(ErrorCode.UNKNOWN_PATTERN,
                              "batch capacity retry limit exceeded")
        finally:
            self.dstore.unpin(pins)

    def suggest_index_batch(self, q: SPARQLQuery, cap: int = 1024) -> int:
        """Largest power-of-two B (<= cap) whose replicated batch is estimated
        to fit the capacity ceiling at every chain step."""
        pats = q.pattern_group.patterns
        if not pats or not q.start_from_index():
            return 1
        ests = self._chain_estimates(pats)
        if ests:
            peak = max(max(ests.values()),
                       len(self.g.get_index(pats[0].subject,
                                            pats[0].direction)), 1)
        else:
            peak = est = max(len(self.g.get_index(pats[0].subject,
                                                  pats[0].direction)), 1)
            bound = {pats[0].object}
            for pat in pats[1:]:
                if pat.object < 0 and pat.object not in bound \
                        and pat.subject in bound:
                    # a genuine expansion; member/k2k steps only shrink
                    est = int(est * self._fanout(pat)) or 1
                    peak = max(peak, est)
                    bound.add(pat.object)
        B = 1
        while B < cap and 2 * B * peak * self.EST_SAFETY <= self.cap_max:
            B *= 2
        return B

    def _fanout(self, pat, seg=None) -> float:
        """Per-row expansion factor estimate — the single source for both
        capacity estimation (_estimate_rows) and batch sizing, so the two
        can never drift. Stats-based when available (pred edges / anchor
        population, x1.5 safety), else segment average degree x2."""
        if self.stats is not None:
            pe = self.stats.pred_edges.get(pat.predicate)
            if pe:
                anchors = (self.stats.distinct_subj if pat.direction == OUT
                           else self.stats.distinct_obj
                           ).get(pat.predicate, 0) or 1
                return pe / anchors * 1.5
        if seg is not None:
            return max(1.0, seg.num_edges / max(seg.num_keys, 1)) * 2
        host = self.g.segments.get((pat.predicate, pat.direction))
        if host is None:
            return 1.0
        return max(1.0, host.num_edges / max(len(host.keys), 1)) * 2

    # ------------------------------------------------------------------
    def _estimate_rows(self, state, pat, seg, step=None) -> int:
        """Expected output rows of an expansion step.

        Prefers the planner's joint-type-table per-step estimate
        (state.step_est) with EST_SAFETY headroom; falls back to the shared
        _fanout estimate. A wrong estimate costs one chain retry, never
        correctness. Never under the longest edge list of the segment:
        one row of the frontier may be its heaviest key (a chain that goes
        constant -> hub -> expansion meets the skew at its second step).
        Where that floor lifts a step, the steps after it are lifted by the
        same factor (``state.skew``): they expand what the heavy key
        brought, not what the average key would have (WatDiv's L5: city ->
        country -> everyone of that nationality -> their job titles)."""
        se = state.step_est.get(step) if step is not None else None
        heavy = self.g.max_degree(pat.predicate, pat.direction)
        if se is not None:
            mean = max(se * state.skew, 1.0)
            if heavy > mean:  # the headroom is for the mean, not the skew
                state.skew *= heavy / mean
            est = int(mean * self.EST_SAFETY)
        else:
            est = int(state.est_rows * self._fanout(pat, seg))
        return max(min(max(est, heavy), self.cap_max), 1)

    @staticmethod
    def _fp_dup(seg) -> int:
        """Static fp-probe selector for this segment, or 0 (= classic
        probe). max_fp_dup is data-derived, so it is quantized to {2, 4, 8}
        to bound jit-cache fragmentation — rounding UP is safe (extra
        verification candidates, never a false negative)."""
        if seg.fpw0 is None or not getattr(Global, "enable_fp_probe", True):
            return 0
        d = seg.max_fp_dup
        return 2 if d <= 2 else (4 if d <= 4 else 8)

    # ------------------------------------------------------------------
    def _device_supported(self, q: SPARQLQuery, pat, probe, is_first: bool) -> bool:
        if q.pg_type == PGType.OPTIONAL:
            return False
        if pat.pred_type != int(AttrType.SID_t):
            return False
        if pat.predicate < 0:
            # VERSATILE shapes (beyond the reference, whose GPU engine
            # refuses all of them — gpu_engine.hpp:267-333):
            #   known_unknown_unknown  (?x ?p ?y, x bound)  expand2
            #   known_unknown_const   (?x ?p CONST, x bound) expand2 + filter
            #   const_unknown_unknown (CONST ?p ?y, start)   host CSR init
            #   const_unknown_const   (CONST1 ?p CONST2)     host CSR init
            # A bound predicate var stays on the host path (the CPU engine
            # rejects it too — there is no such reference kernel).
            if not Global.enable_versatile \
                    or probe.col_of(pat.predicate) is not None:
                return False
            if is_first and probe.width == 0:
                return pat.subject > 0  # const versatile start
            if not (pat.subject < 0
                    and probe.col_of(pat.subject) is not None):
                return False
            if pat.object < 0:
                return probe.col_of(pat.object) is None
            return True  # const object: expand2 + equality fold
        if is_first and q.pattern_step == 0 and q.start_from_index():
            # index_to_known is host-only (like the reference GPU engine),
            # and a seeded (width > 0) table cannot consume an index start —
            # the host kernel raises FIRST_PATTERN_ERROR (CPU parity)
            return probe.width == 0 and probe.col_of(pat.object) is None
        s_known = pat.subject > 0 or probe.col_of(pat.subject) is not None
        if is_first and probe.width == 0:
            return pat.subject > 0  # const start
        if pat.subject > 0:
            # const_to_known mid-chain (a plan that starts from one constant
            # and comes back to another): the membership test of the known
            # end against the constant, over the reversed segment
            return _flipped(pat, probe) is not None
        return s_known


def _flipped(pat, cols):
    """``(CONST p ?known)`` as ``(?known p^-1 CONST)``, or None where the
    object is not a bound variable or the predicate has no reversed
    segment (``rdf:type``: the object side stores no type triples)."""
    if pat.object >= 0 or cols.col_of(pat.object) is None \
            or pat.predicate in (PREDICATE_ID, TYPE_ID):
        return None
    return Pattern(pat.object, pat.predicate,
                   OUT if int(pat.direction) == IN else IN, pat.subject,
                   pat.pred_type)


def _is_index_start(pat) -> bool:
    return pat.predicate in (PREDICATE_ID, TYPE_ID)


def _mt_slice(total: int, mt_factor: int, mt_tid: int):
    mt = mt_tid % mt_factor
    length = total // mt_factor
    lo = mt * length
    hi = (mt + 1) * length if mt != mt_factor - 1 else total
    return lo, hi


class _MetaResult:
    """Host-side shadow of column bindings for chain planning (no device data)."""

    def __init__(self, res):
        self.cols = dict(res.v2c_map)
        self.width = res.col_num

    def col_of(self, var: int):
        c = self.cols.get(var)
        return c if c is not None and c != NO_RESULT else None

    def bind(self, pat) -> None:
        if self.width == 0:
            if pat.predicate < 0:  # versatile const start: pid col first
                self.cols[pat.predicate] = 0
                self.width = 1
                if pat.object < 0:
                    self.cols[pat.object] = 1
                    self.width = 2
                return
            self.cols[pat.object], self.width = 0, 1
            return
        if pat.predicate < 0 and self.col_of(pat.predicate) is None:
            # versatile expand2 binds the predicate var first (pid column
            # precedes the value column, matching the CPU kernel's order)
            self.cols[pat.predicate] = self.width
            self.width += 1
        if pat.object < 0 and self.col_of(pat.object) is None:
            self.cols[pat.object] = self.width
            self.width += 1


class _ChainState:
    """Device table + host-side column metadata + deferred overflow scalars."""

    def __init__(self, res):
        self.table = None
        self.n = None
        self.width = res.col_num
        self.cols = dict(res.v2c_map)
        self.new_cols: list = []
        self.totals: list = []  # (step, device_total, cap)
        self.est_rows = 1
        self.step_est: dict = {}  # {step: planner row estimate}
        self.skew = 1.0  # what heavy keys lifted the steps so far by
        self.local_var = 0

    def col_of(self, var: int):
        c = self.cols.get(var)
        return c if c is not None and c != NO_RESULT else None

    def begin(self, table, n, end_var: int, est_rows: int) -> None:
        self.table = table
        self.n = n
        self.width = 1
        self.cols[end_var] = 0
        self.new_cols.append((end_var, 0))
        self.est_rows = max(est_rows, 1)

    def advance_expand(self, table, n, end_var: int, total, cap: int, step: int,
                       est_rows: int) -> None:
        self.table = table
        self.n = n
        self.cols[end_var] = self.width
        self.new_cols.append((end_var, self.width))
        self.width += 1
        self.totals.append((step, total, cap))
        self.est_rows = max(est_rows, 1)

    def advance_expand2(self, table, n, pred_var: int, end_var: int, total,
                        cap: int, step: int, est_rows: int) -> None:
        """Versatile expand: binds the predicate column then the value."""
        self.table = table
        self.n = n
        for var in (pred_var, end_var):
            self.cols[var] = self.width
            self.new_cols.append((var, self.width))
            self.width += 1
        self.totals.append((step, total, cap))
        self.est_rows = max(est_rows, 1)

    def advance_filter(self, table, n) -> None:
        self.table = table
        self.n = n

    def append_empty_col(self, end_var: int) -> None:
        """Expansion over a missing segment: zero matches, one new column."""
        import jax.numpy as jnp

        self.table = jnp.concatenate(
            [self.table, jnp.zeros((1, self.table.shape[1]), jnp.int32)], axis=0)
        self.n = jnp.int32(0)
        self.cols[end_var] = self.width
        self.new_cols.append((end_var, self.width))
        self.width += 1

    def sync(self, blind: bool = False):
        """The single D2H sync: table, row count and all step totals together.

        blind=True transfers only scalars (row count + per-step totals) — the
        table stays on device, matching the reference's silent mode where
        result tables are never shipped to the proxy.
        """
        import jax

        scalars = [t for (_, t, _) in self.totals]
        if blind:
            n, totals = jax.device_get((self.n, scalars))
            host_table = np.empty((0, self.width), dtype=np.int32)
        else:
            host_table, n, totals = jax.device_get((self.table, self.n, scalars))
            host_table = np.ascontiguousarray(np.asarray(host_table).T)
        return (host_table, int(n),
                [(s, int(t), c) for (s, _, c), t in zip(self.totals, totals)])


def _charge_chain(q: SPARQLQuery, site: str, totals: list,
                  wall_us: int, moved: int) -> None:
    """Charge one chain sync on the device observatory: one dispatch
    record per fused step from the ride-along totals ``(step, total,
    cap)``, with the attempt's dispatch-to-sync wall split evenly across
    steps (the driver syncs ONCE per chain, so per-step device time is
    not separately observable) and the D2H payload charged to the first
    step. Records land on ``q.device_steps`` for EXPLAIN ANALYZE's
    device table."""
    if not totals or not Global.enable_device_obs:
        return
    per_us = int(wall_us) // len(totals)
    for i, (s, t, c) in enumerate(totals):
        rec = maybe_device_dispatch(
            site, template=f"d{len(totals)}", live=min(int(t), int(c)),
            capacity=int(c), wall_us=per_us,
            nbytes=moved if i == 0 else 0)
        if rec is None:
            return
        rec["step"] = int(s)
        dev = getattr(q, "device_steps", None)
        if dev is None:
            dev = q.device_steps = []
        dev.append(rec)


_qid_counts_jit = None


def _qid_counts(table, n, B: int):
    """Per-query row counts from the qid column (device-side bincount).

    The jitted kernel is module-global (cache keyed on shapes + static B), so
    repeated batch dispatches in the emulator loop never retrace."""
    global _qid_counts_jit
    if _qid_counts_jit is None:
        import functools

        import jax
        import jax.numpy as jnp

        def wk_walk_qid_counts(table, n, B: int):
            C = table.shape[1]
            live = jnp.arange(C, dtype=jnp.int32) < n
            qid = jnp.where(live, table[0], B)
            return jnp.bincount(qid, length=B + 1)[:B]

        _qid_counts_jit = functools.partial(
            jax.jit, static_argnames=("B",))(wk_walk_qid_counts)
    return _qid_counts_jit(table, n, B=B)
