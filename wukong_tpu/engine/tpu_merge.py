"""Sort-merge batch executor — the v2 device chain for batched queries.

Replaces the hash-probe + eager-table pipeline (tpu.py `_dispatch_one`) for
`execute_batch` / `execute_batch_index` with the gather-free kernels in
tpu_kernels.py (merge_expand / merge_member_*): on this TPU a variadic sort
costs 2-3 ns/elem while ANY gather — random or sorted — costs ~9.5, so joins
are restructured around sorting, and binding tables are never materialized
wide. The chain keeps, per expansion level, only (vals, parent): `vals` is
the new column in the current row space, `parent` maps each row to its
producer one level down (the reference's result_table regrow —
query.hpp:536-558 — priced lazily). A column is materialized only when a
later step anchors on it, at one sorted gather per intervening level;
membership filters fold into the NEXT expand's degree vector instead of
paying a compaction (rows die by never expanding), unless the planner
estimate says the survivor set is small enough that shrinking the capacity
class wins.

Scope: the same shapes the batch paths accepted before (const SID
predicates, const- or index-origin starts, known anchors). Everything else
stays on the v1/host paths. Capacity overflow handling is unchanged: true
totals ride along as device scalars, ONE device_get at the end, retry with
exact classes — plus a per-(query, B) capacity memo so the retry cost is
paid once per process, not once per call (the emulator and bench re-run the
same template thousands of times).

Reference anchors: gpu_engine_cuda.hpp:112-197 (the probe pipeline this
replaces), sparql.hpp:98-108 + 1064-1088 (index slicing the batch dimension
subsumes), proxy.hpp:477-525 (the batched emulator workload this serves).
"""

from __future__ import annotations

import numpy as np

from wukong_tpu.config import Global
from wukong_tpu.engine import tpu_kernels as K
from wukong_tpu.obs.device import maybe_device_dispatch
from wukong_tpu.sparql.ir import SPARQLQuery
from wukong_tpu.types import IN, OUT, PREDICATE_ID, TYPE_ID
from wukong_tpu.utils.errors import ErrorCode, WukongError, assert_ec
from wukong_tpu.utils.timer import get_usec


def _charge_merge(site: str, totals, device_totals, wall_us: int,
                  q=None) -> None:
    """Charge one merge-chain sync on the device observatory from the
    ride-along ``(step, _, cap)`` triples + their fetched device totals,
    splitting the dispatch-to-sync wall evenly (ONE device_get covers
    the whole chain). With ``q`` the records also land on
    ``q.device_steps`` for EXPLAIN ANALYZE."""
    if not totals or not Global.enable_device_obs:
        return
    per_us = int(wall_us) // len(totals)
    for (s, _, c), t in zip(totals, device_totals):
        rec = maybe_device_dispatch(
            site, template=f"d{len(totals)}", live=min(int(t), int(c)),
            capacity=int(c), wall_us=per_us)
        if rec is None:
            return
        rec["step"] = int(s)
        if q is not None:
            dev = getattr(q, "device_steps", None)
            if dev is None:
                dev = q.device_steps = []
            dev.append(rec)


class _Level:
    """One expansion level: new column values + parent map into the level
    below (parent is None at the root)."""

    __slots__ = ("var", "vals", "parent")

    def __init__(self, var, vals, parent):
        self.var = var
        self.vals = vals
        self.parent = parent


class _MergeState:
    """Chain state: levels + deferred filter mask + overflow totals."""

    def __init__(self):
        self.levels: list[_Level] = []
        self.n = None  # device scalar live rows at current level
        self.live = None  # deferred-filter mask at current level (or None)
        self.totals: list = []  # (step, device_total, cap)
        self.var_level: dict[int, int] = {}  # var -> level index
        self.est_rows = 1.0  # host-side live-row estimate (NOT capacity)

    @property
    def cap(self) -> int:
        return int(self.levels[-1].vals.shape[0])

    def live_mask(self):
        import jax.numpy as jnp

        if self.live is None:
            return jnp.ones(self.cap, dtype=bool)
        return self.live

    def materialize(self, var: int):
        """Column of `var` in the current row space: walk parent maps down to
        its level (one sorted gather per hop)."""
        lv = self.var_level[var]
        top = len(self.levels) - 1
        if lv == top:
            return self.levels[top].vals
        idx = self.levels[top].parent
        for k in range(top - 1, lv, -1):
            idx = K.wk_walk_merge_gather_col(self.levels[k].parent, idx)
        return K.wk_walk_merge_gather_col(self.levels[lv].vals, idx)

    def pos0(self):
        """Space-0 position of every current row (for qid recovery). The
        root level's parent is normally None (identity) but becomes a real
        map into the original space after a root compact."""
        import jax.numpy as jnp

        top = len(self.levels) - 1
        idx = None
        for k in range(top, -1, -1):
            p = self.levels[k].parent
            if p is None:
                continue
            idx = p if idx is None else K.wk_walk_merge_gather_col(p, idx)
        if idx is None:
            return jnp.arange(self.cap, dtype=jnp.int32)
        return idx


class MergeExecutor:
    """Batched blind execution over merge kernels. Owned by TPUEngine."""

    def __init__(self, engine):
        self.eng = engine  # TPUEngine: dstore, g, stats, cap bounds
        self._cap_memo: dict = {}  # (patterns key, B, mode) -> {step: cap}
        self.total_retries = 0  # cumulative overflow-retry chains this
        # process — the at-scale artifact's capacity-behavior evidence
        # expand dispatches by the emitter chosen (chip_smoke.py reads it:
        # the dispatch sites do not say which kernel a chain step ran)
        self.emit_counts = {"probe": 0, "stream": 0, "merge": 0}

    # ------------------------------------------------------------------
    def load_cap_memo(self, path: str) -> None:
        """Seed the capacity memo from a JSON file written by a previous
        process: the bench measures each query in its own subprocess, and
        without this every process pays one overflow-retry chain (which a
        best-of-3 then wrongly includes as steady-state latency)."""
        import ast
        import json as _json

        try:
            with open(path) as f:
                raw = _json.load(f)
            for k, caps in raw.items():
                self._cap_memo[ast.literal_eval(k)] = {
                    int(s): int(c) for s, c in caps.items()}
        except FileNotFoundError:
            pass
        except Exception:
            pass  # a corrupt memo only costs the retry it would have saved

    def save_cap_memo(self, path: str) -> None:
        import json as _json
        import os as _os

        try:
            merged = {}
            if _os.path.exists(path):
                with open(path) as f:
                    merged = _json.load(f)
            merged.update({repr(k): v for k, v in self._cap_memo.items()})
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                _json.dump(merged, f)
            _os.replace(tmp, path)
        except Exception:
            pass

    # ------------------------------------------------------------------
    def supports(self, q: SPARQLQuery) -> bool:
        """Merge scope == the batch paths' validated shapes; VERSATILE
        (predicate vars) and attr patterns are out (host handles them)."""
        return all(p.predicate >= 0 for p in q.pattern_group.patterns)

    @staticmethod
    def _key(pats, B: int, mode: str):
        return (tuple((p.subject, p.predicate, int(p.direction), p.object)
                      for p in pats), B, mode)

    # ------------------------------------------------------------------
    def run_batch_index(self, q: SPARQLQuery, B: int,
                        slice_mode: bool) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        eng = self.eng
        pats = q.pattern_group.patterns
        edges, real = eng.dstore.index_list(pats[0].subject,
                                            pats[0].direction)
        if slice_mode:
            r = max((real + B - 1) // B, 1)
            total0 = real
        else:
            r = max(real, 1)
            total0 = real * B
        assert_ec(total0 <= eng.cap_max, ErrorCode.UNKNOWN_PATTERN,
                  f"batch-index start ({total0:,} rows) exceeds "
                  f"table_capacity_max ({eng.cap_max:,})")

        def init(state: _MergeState):
            self._init_index(state, pats, edges, real, B, slice_mode, total0)
            return 1

        counts = self._run(q, pats, init, B, r, slice_mode,
                           mode="slice" if slice_mode else "rep")
        return counts

    def _init_index(self, state: "_MergeState", pats, edges, real, B: int,
                    slice_mode: bool, total0: int) -> None:
        import jax.numpy as jnp

        eng = self.eng
        cap0 = K.next_capacity(max(total0, 1), eng.cap_min, eng.cap_max)
        if slice_mode:
            vals, n = K.wk_walk_init_from_list(edges, jnp.int32(real), cap0)
        else:
            tab, n = K.wk_walk_init_batch_index(
                edges, jnp.int32(real), B=B, cap=cap0, slice_mode=False)
            vals = tab[1:2]
        state.levels.append(_Level(pats[0].object, vals[0], None))
        state.var_level[pats[0].object] = 0
        state.n = n
        state.est_rows = max(total0, 1)

    def run_batch_const(self, q: SPARQLQuery,
                        consts: np.ndarray) -> np.ndarray:
        pats = q.pattern_group.patterns
        B = len(consts)

        def init(state: _MergeState):
            self._init_const(state, pats, consts)
            return 0  # start consts pre-bind step 0's subject only

        return self._run(q, pats, init, B, 1, False, mode="const")

    def run_batch_index_many(self, q: SPARQLQuery, B: int,
                             K_batches: int) -> list:
        """Dispatch K replicate-mode index batches back-to-back and sync
        ONCE — the heavy-class in-flight window. Each batch is an
        independent chain at the same learned capacities, so throughput
        scales with K without growing any chain's capacity class. Batches
        that still overflow re-run individually (slow path)."""
        eng = self.eng
        pats = q.pattern_group.patterns
        edges, real = eng.dstore.index_list(pats[0].subject,
                                            pats[0].direction)
        total0 = real * B
        assert_ec(total0 <= eng.cap_max, ErrorCode.UNKNOWN_PATTERN,
                  f"batch-index start ({total0:,} rows) exceeds "
                  f"table_capacity_max ({eng.cap_max:,})")

        def dispatch_one(_spec, folds):
            cap_override = dict(
                self._cap_memo.get(self._key(pats, B, "rep"), {}))
            state = _MergeState()
            self._init_index(state, pats, edges, real, B, False, total0)
            for k, pat, _kind, fold in self.classify(
                    pats, folds, index_mode=True):
                self._dispatch(q, pat, k, state, cap_override, {}, fold)
            counts = K.wk_walk_merge_qid_counts(
                state.pos0(), state.n, state.live_mask(), B=B,
                r=max(real, 1), slice_mode=False)
            return counts, state.totals

        return self._run_many(pats, True, list(range(K_batches)),
                              dispatch_one,
                              lambda _spec: self.run_batch_index(q, B, False))

    # ------------------------------------------------------------------
    def run_batch_const_many(self, q: SPARQLQuery,
                             consts_list: list) -> list:
        """Dispatch K const-batches back-to-back and sync ONCE — the
        open-loop emulator's in-flight window (proxy.hpp:477-525) on a
        device: the fixed cost of the sync amortizes over every batch in
        the window. Requires learned capacities (a prior run_batch_const);
        batches that still overflow re-run individually."""
        pats = q.pattern_group.patterns

        def dispatch_one(consts, folds):
            B = len(consts)
            cap_override = dict(
                self._cap_memo.get(self._key(pats, B, "const"), {}))
            state = _MergeState()
            self._init_const(state, pats, consts)
            for k, pat, _kind, fold in self.classify(
                    pats, folds, index_mode=False):
                self._dispatch(q, pat, k, state, cap_override, {}, fold)
            counts = K.wk_walk_merge_qid_counts(
                state.pos0(), state.n, state.live_mask(), B=B, r=1,
                slice_mode=False)
            return counts, state.totals

        return self._run_many(pats, False, consts_list, dispatch_one,
                              lambda consts: self.run_batch_const(q, consts))

    def run_batch_const_mixed(self, jobs: list) -> list:
        """ONE device flight spanning MULTIPLE const-start templates — the
        cross-CLASS in-flight window (proxy.hpp:477-525's open loop
        interleaves classes freely; per-class windows left sync
        amortization on the table whenever the mix rotates templates).
        Segments shared between templates are pinned/staged once. Requires
        learned capacities per (query, B) — batches that still overflow
        re-run individually through run_batch_const."""
        per = []
        pin_set = []
        for q, consts in jobs:
            pats = q.pattern_group.patterns
            folds = self._plan_folds(pats, index_mode=False)
            pin_set.extend(self._chain_pins(pats, folds, index_mode=False))
            per.append((q, consts, pats, folds))

        def mk_thunk(q, consts, pats, folds):
            def thunk():
                cap_override = dict(self._cap_memo.get(
                    self._key(pats, len(consts), "const"), {}))
                state = _MergeState()
                self._init_const(state, pats, consts)
                for k, pat, _kind, fold in self.classify(
                        pats, folds, index_mode=False):
                    self._dispatch(q, pat, k, state, cap_override, {}, fold)
                counts = K.wk_walk_merge_qid_counts(
                    state.pos0(), state.n, state.live_mask(),
                    B=len(consts), r=1, slice_mode=False)
                return counts, state.totals
            return thunk

        return self._flight(
            pin_set,
            [mk_thunk(*p) for p in per],
            [lambda q=q, c=c: self.run_batch_const(q, c)
             for (q, c, _p, _f) in per])

    def _flight(self, pin_set, thunks, slows) -> list:
        """THE single in-flight-window protocol: pin, dispatch every chain
        back-to-back, device_get the whole flight in ONE sync, redo
        overflowing entries via their slow thunk (which retries internally
        and re-learns capacities for later windows)."""
        import jax

        eng = self.eng
        eng.dstore.pin(pin_set)
        t0 = get_usec()
        try:
            flight = [t() for t in thunks]
            payload = [(c, [t for (_, t, _) in tot]) for c, tot in flight]
            host = jax.device_get(payload)
        finally:
            eng.dstore.unpin(pin_set)
        wall = get_usec() - t0
        out = []
        for (slow, (host_counts, totals), (_, tot)) in zip(
                slows, host, flight):
            _charge_merge("tpu.merge.flight", tot, totals,
                          wall // max(len(flight), 1))
            if any(int(t) > c for (_, _, c), t in zip(tot, totals)):
                out.append(slow())
            else:
                out.append(np.asarray(host_counts))
        return out

    def _run_many(self, pats, index_mode: bool, specs: list, dispatch_one,
                  slow_one) -> list:
        """Single-template in-flight window over the shared _flight
        protocol: one pin set, one folds plan, K batches of one chain."""
        folds = self._plan_folds(pats, index_mode=index_mode)
        pins = self._chain_pins(pats, folds, index_mode=index_mode)
        return self._flight(
            pins,
            [lambda spec=spec: dispatch_one(spec, folds) for spec in specs],
            [lambda spec=spec: slow_one(spec) for spec in specs])

    def _init_const(self, state: "_MergeState", pats, consts) -> None:
        import jax.numpy as jnp

        eng = self.eng
        B = len(consts)
        cap0 = K.next_capacity(B, eng.cap_min)
        pad = np.zeros(cap0, dtype=np.int32)
        pad[:B] = consts
        state.levels.append(_Level(pats[0].subject, jnp.asarray(pad), None))
        state.var_level[pats[0].subject] = 0
        state.n = jnp.int32(B)
        state.est_rows = B

    # ------------------------------------------------------------------
    def _run(self, q, pats, init, B: int, r: int, slice_mode: bool,
             mode: str) -> np.ndarray:
        import jax

        eng = self.eng
        memo_key = self._key(pats, B, mode)
        cap_override = dict(self._cap_memo.get(memo_key, {}))
        step_est = {k: e * (1.0 if mode == "slice" else float(B))
                    for k, e in eng._chain_estimates(pats).items()}
        folds = self._plan_folds(pats, index_mode=(mode != "const"))
        pins = self._chain_pins(pats, folds, index_mode=(mode != "const"))
        eng.dstore.pin(pins)
        try:
            for _attempt in range(8):
                t0 = get_usec()
                state = _MergeState()
                first = init(state)
                assert first == (1 if mode != "const" else 0)
                for k, pat, _kind, fold in self.classify(
                        pats, folds, index_mode=(mode != "const")):
                    self._dispatch(q, pat, k, state, cap_override,
                                   step_est, fold)
                counts = K.wk_walk_merge_qid_counts(
                    state.pos0(), state.n, state.live_mask(), B=B, r=r,
                    slice_mode=slice_mode)
                payload = (counts, [t for (_, t, _) in state.totals])
                host_counts, totals = jax.device_get(payload)
                _charge_merge("tpu.merge", state.totals, totals,
                              get_usec() - t0, q=q)
                over = False
                for (s, _, c), t in zip(state.totals, totals):
                    exact = K.next_capacity(int(t), eng.cap_min, eng.cap_max)
                    if int(t) > c:
                        if int(t) > eng.cap_max:
                            raise WukongError(
                                ErrorCode.UNKNOWN_PATTERN,
                                f"batch intermediate ({int(t):,} rows) "
                                f"exceeds capacity ({eng.cap_max:,})")
                        cap_override[s] = exact
                        over = True
                    else:
                        # learn downward too: the next call starts tight
                        cap_override.setdefault(s, exact)
                if not over:
                    if len(self._cap_memo) > 4096:  # bound BEFORE storing:
                        self._cap_memo.clear()  # never wipe the fresh entry
                    self._cap_memo[memo_key] = dict(cap_override)
                    return np.asarray(host_counts)
                self.total_retries += 1  # one re-run of the whole chain
            raise WukongError(ErrorCode.UNKNOWN_PATTERN,
                              "batch capacity retry limit exceeded")
        finally:
            eng.dstore.unpin(pins)

    @staticmethod
    def classify(pats, folds, index_mode: bool):
        """THE single classification of a planned chain's executable steps:
        yields (step, pat, kind, fold) for every non-folded step, kind in
        {"expand", "k2k", "k2c"}, walking the bound set exactly the way the
        executor binds it. Pins and both dispatch loops derive from this one
        walk — the three hand-maintained copies it replaces could silently
        drift (advisor r2 #2's root cause)."""
        if not pats:
            return
        vars_bound = {pats[0].object if index_mode else pats[0].subject}
        # index mode: init consumes pattern 0; const mode: step 0 runs as a
        # real expand below
        first = 1 if index_mode else 0
        skip = folds.get("skip", ())
        for k in range(first, len(pats)):
            pat = pats[k]
            end = pat.object
            if k in skip:
                # _plan_folds only folds k2c steps (const objects); a folded
                # var-object step would silently diverge from the executor's
                # binding order — fail loudly if that invariant ever breaks
                assert end > 0, "folded step must be a k2c (const object)"
                continue
            if end < 0 and end not in vars_bound:
                vars_bound.add(end)
                yield k, pat, "expand", folds.get(k)
            elif end < 0:
                yield k, pat, "k2k", None
            else:
                yield k, pat, "k2c", None

    # frontier-vs-segment lookup dispatch: merge_lookup re-sorts the WHOLE
    # key array per call (O((S+C) log), ~150 ms/step for a 1024-row light
    # frontier against a 2^26-key LUBM-2560 segment), the bucket probe pays
    # ~max_probe row-contiguous gathers over the frontier only. Probe wins
    # when the frontier is far smaller than the key set; 16x keeps the
    # decision on the sort side near the crossover (sort 2.2-3.1 ns/elem,
    # gather ~9.5 ns/elem, ~150 ms/step: an earlier installation's figures,
    # not measured on the attached chip — ROADMAP S4 re-derives them).
    PROBE_LOOKUP_FACTOR = 16

    def _lookup_factor(self) -> int:
        """Backend-aware crossover: the sort-vs-gather economics INVERT
        across backends (an earlier installation's micro, not measured on
        the attached chip, ROADMAP S4: a TPU sorts cheaper than it
        gathers, a CPU the other way round), so the probe arm wins ~8x
        earlier on the CPU backend. Forced settings
        (factor 0 / huge in tests) scale through unchanged."""
        f = self.PROBE_LOOKUP_FACTOR
        if getattr(self.eng.dstore.device, "platform", "cpu") != "tpu":
            f = f // 8
        return f

    def _probe_lookup_wins(self, cap_in: int, pid: int, d: int) -> bool:
        """STATIC per capacity class (host metadata only — deciding must
        never stage a segment). Consumed by _dispatch (live capacity) and
        bytes_model (walked capacity); pins cover both outcomes, so a
        learning-phase flip can't leave the staged form unprotected."""
        return (self.eng.dstore.host_num_keys(pid, d)
                >= cap_in * self._lookup_factor())

    def _probe_member_wins(self, cap_in: int, pid: int, d: int) -> bool:
        """Membership twin of _probe_lookup_wins: merge_member_pairs sorts
        the per-EDGE pair arrays, so the dispatch scalar is the edge
        count."""
        return (self.eng.dstore.host_num_edges(pid, d)
                >= cap_in * self._lookup_factor())

    def _walk_caps(self, pats, folds, index_mode: bool, B: int, mode: str):
        """THE shared chain walk with capacity evolution: yields
        (step, pat, kind, fold, cap_in, cap_out) mirroring _dispatch's
        transitions exactly (same _expand_est/_expand_cap/_member_cap
        helpers, memo-first). cap_out == cap_in for non-compacting steps."""
        eng = self.eng
        memo = self._cap_memo.get(self._key(pats, B, mode), {})
        step_est = {k: e * (1.0 if mode == "slice" else float(B))
                    for k, e in eng._chain_estimates(pats).items()}
        if index_mode:
            p0 = pats[0]
            real = len(eng.g.get_index(p0.subject, p0.direction))
            total0 = real if mode == "slice" else real * B
            cap = K.next_capacity(max(total0, 1), eng.cap_min, eng.cap_max)
            est_rows = float(max(total0, 1))
        else:
            cap = K.next_capacity(B, eng.cap_min)
            est_rows = float(B)
        for k, pat, kind, fold in self.classify(pats, folds, index_mode):
            if kind == "expand":
                est = self._expand_est(pat, k, fold, step_est, est_rows)
                cap_out = self._expand_cap(k, est, memo)
                est_rows = max(min(est, cap_out), 1.0)
                yield k, pat, kind, fold, cap, cap_out
                cap = cap_out
            else:
                cap_new = self._member_cap(k, step_est, memo)
                if cap_new is not None and cap_new < cap:
                    yield k, pat, kind, fold, cap, cap_new
                    cap = cap_new
                    est_rows = max(min(est_rows, cap_new), 1.0)
                else:
                    yield k, pat, kind, fold, cap, cap

    @classmethod
    def _chain_pins(cls, pats, folds, index_mode: bool) -> list:
        """The DeviceStore keys the planned chain may stage, so pins protect
        what actually runs: folded expands use ("mrgf"/"segf", pid, d, fkey)
        filtered segments and k2c membership uses ("rev", ...) const lists —
        pinning only ("mrg", ...) left those evictable under budget
        pressure, forcing a host rebuild + device_put on every call (advisor
        r2 #2). Expands pin BOTH the merge form and the bucket form: the
        sort-vs-probe decision runs on the LIVE capacity class inside
        _dispatch (which can shift across overflow retries and ragged
        window batches), and pinning an unstaged key costs nothing — only
        whichever form the chain stages is actually held."""
        from wukong_tpu.engine.device_store import fold_key

        pins = []
        seen = set()

        def add(key):
            if key not in seen:
                seen.add(key)
                pins.append(key)

        for _k, pat, kind, fold in cls.classify(pats, folds, index_mode):
            pid, d, end = int(pat.predicate), int(pat.direction), pat.object
            if kind == "expand":
                if fold is not None:
                    fkey = fold_key(fold[0])
                    add(("mrgf", pid, d, fkey))
                    add(("segf", pid, d, fkey))
                else:
                    add(("mrg", pid, d))
                    add((pid, d))
            elif kind == "k2k":
                add(("mrg", pid, d))
                add((pid, d))  # bucket twin for the probe-member arm
            else:
                add(("rev", pid, d, int(end)))
        return pins

    @staticmethod
    def _plan_folds(pats, index_mode: bool = True) -> dict:
        """Fold k2c membership steps into their producing expand: a run of
        `(?v, fp, fd, const)` membership steps immediately following the
        expand that binds ?v becomes edge pre-filtering of that expand's
        segment (DeviceStore.filtered_merge_segment — the type-centric
        pruning of planner.hpp applied at execution time; conjunctive
        semantics make the early filter exact). Returns
        {expand_step: ([(fp, fd, fconst), ...], last_folded_step),
         "skip": {folded steps}}.
        """
        folds: dict = {}
        skip: set = set()
        bound: set = set()
        if pats:
            bound.add(pats[0].subject)
            # index mode: init consumes pattern 0 and pre-binds its object
            # (a step-0 fold would never execute). const mode: step 0 runs
            # as a real expand, so its object must stay foldable.
            if index_mode and pats[0].object < 0:
                bound.add(pats[0].object)
        for k, pat in enumerate(pats):
            is_expand = (pat.predicate >= 0 and pat.object < 0
                         and pat.object not in bound)
            if pat.object < 0:
                bound.add(pat.object)
            if not is_expand:
                continue
            v = pat.object
            fl = []
            last = k
            consec = True
            for j in range(k + 1, len(pats)):
                nxt = pats[j]
                if (nxt.subject == v and nxt.predicate >= 0
                        and nxt.object > 0 and j not in skip):
                    # conjunctive semantics: ANY later k2c on v folds into
                    # the producing expand; only a CONSECUTIVE run's last
                    # step keeps a meaningful post-filter row estimate
                    fl.append((nxt.predicate, int(nxt.direction),
                               nxt.object))
                    skip.add(j)
                    if consec:
                        last = j
                else:
                    consec = False
            if fl:
                folds[k] = (fl, last)
        folds["skip"] = skip
        return folds

    # ------------------------------------------------------------------
    # THE single capacity-transition policy: _dispatch (what the executor
    # allocates) and bytes_model (what the bench artifact reports) both
    # consume these three helpers — a second hand-maintained copy of the
    # memo-or-estimate rule would silently desynchronize the published
    # roofline bytes from the real allocation (the classify() lesson).
    def _expand_est(self, pat, step: int, fold, step_est: dict,
                    est_rows: float) -> float:
        """Live-row estimate for an expand step: the planner's (post-fold)
        step estimate when present, else fanout-propagated."""
        est = step_est.get(fold[1] if fold is not None else step)
        if est is None:
            est = est_rows * self.eng._fanout(pat)
        return est

    def _expand_cap(self, step: int, est: float, cap_override: dict) -> int:
        """Output capacity class of an expand: learned/memoized first, else
        safety-margined estimate."""
        eng = self.eng
        return cap_override.get(step) or K.next_capacity(
            max(int(min(est * eng.EST_SAFETY, eng.cap_max)), eng.cap_min),
            eng.cap_min, eng.cap_max)

    def _member_cap(self, step: int, step_est: dict,
                    cap_override: dict) -> int | None:
        """Post-membership compaction capacity (None = defer the filter)."""
        eng = self.eng
        cap_new = cap_override.get(step)
        if cap_new is None:
            se = step_est.get(step)
            if se is not None:
                cap_new = K.next_capacity(
                    max(int(se * eng.EST_SAFETY), eng.cap_min),
                    eng.cap_min, eng.cap_max)
        return cap_new

    # ------------------------------------------------------------------
    def _dispatch(self, q, pat, step: int, state: _MergeState,
                  cap_override: dict, step_est: dict,
                  fold_filters: list | None = None) -> None:
        import jax.numpy as jnp

        eng = self.eng
        start, pid, d, end = (pat.subject, pat.predicate, pat.direction,
                              pat.object)
        anchor = start if start in state.var_level else None
        assert_ec(anchor is not None or start > 0,
                  ErrorCode.VERTEX_INVALID)
        if anchor is None:
            # const subject mid-chain can't happen: batch validation anchors
            # every step on a bound column (execute_batch probe)
            raise WukongError(ErrorCode.UNKNOWN_PATTERN,
                              "merge chain step lacks a bound anchor")
        cur = state.materialize(anchor)

        e_known = end < 0 and end in state.var_level
        if end < 0 and not e_known:  # expand
            # sort-vs-probe lookup dispatch on the LIVE frontier capacity
            # (matches _walk_caps' cap_in when learning is settled)
            use_probe = self._probe_lookup_wins(state.cap, pid, d)
            if use_probe:
                seg = (eng.dstore.filtered_segment(pid, d, fold_filters[0])
                       if fold_filters is not None
                       else eng.dstore.segment(pid, d))
            elif fold_filters is not None:
                seg = eng.dstore.filtered_merge_segment(pid, d,
                                                        fold_filters[0])
            else:
                seg = eng.dstore.merge_segment(pid, d)
            if seg is None or seg.num_edges == 0:
                state.levels.append(_Level(
                    end, jnp.zeros(state.cap, jnp.int32),
                    jnp.zeros(state.cap, jnp.int32)))
                state.var_level[end] = len(state.levels) - 1
                state.n = jnp.int32(0)
                state.live = None
                return
            # folded filters make the POST-filter estimate (the last folded
            # step's) the right capacity driver; live-row estimate, never
            # capacity (capacity compounds geometrically and would inflate
            # every later sort)
            est = self._expand_est(pat, step, fold_filters, step_est,
                                   state.est_rows)
            cap_out = self._expand_cap(step, est, cap_override)
            state.est_rows = max(min(est, cap_out), 1.0)
            from wukong_tpu.engine import tpu_stream

            if use_probe:
                from wukong_tpu.engine.tpu import TPUEngine

                self.emit_counts["probe"] += 1
                fd = TPUEngine._fp_dup(seg)
                vals, parent, n, total = K.wk_walk_merge_probe_expand(
                    seg.bkey, seg.bstart, seg.bdeg, seg.edges, cur,
                    state.n, state.live_mask(), cap_out=cap_out,
                    max_probe=seg.max_probe,
                    fpw0=seg.fpw0 if fd else None,
                    fpw1=seg.fpw1 if fd else None, fp_dup=fd)
            elif tpu_stream.want_stream(est, int(seg.edges.shape[0]),
                                        cap_out):
                # dense expansion: stream the edge array through VMEM
                # (~3 ns/edge) instead of the per-output scatter+gather
                # (~25 ns/out); duplicate-anchor frontiers stream through
                # the m-hot arm up to multiplicity MDUP, beyond that a
                # device-side lax.cond falls back to the XLA emit
                self.emit_counts["stream"] += 1
                vals, parent, n, total = tpu_stream.wk_walk_merge_stream_expand(
                    seg.skey, seg.sstart, seg.sdeg, seg.edges, cur, state.n,
                    state.live_mask(), cap_out=cap_out,
                    interpret=tpu_stream.FORCE_INTERPRET,
                    mhot=tpu_stream.mhot_enabled(),
                    mdup=tpu_stream.stream_mdup())
            else:
                self.emit_counts["merge"] += 1
                vals, parent, n, total = K.wk_walk_merge_expand(
                    seg.skey, seg.sstart, seg.sdeg, seg.edges, cur, state.n,
                    state.live_mask(), cap_out=cap_out)
            state.levels.append(_Level(end, vals, parent))
            state.var_level[end] = len(state.levels) - 1
            state.n = n
            state.live = None  # filters before this step are consumed
            state.totals.append((step, total, cap_out))
            return

        # membership: known_to_const / known_to_known — each with its own
        # small-frontier arm (merge_member_* re-sorts the whole relation
        # per call; probe/binary-search touches O(frontier) instead)
        if e_known:
            if self._probe_member_wins(state.cap, pid, d):
                seg = eng.dstore.segment(pid, d)
                if seg is None:
                    keep = jnp.zeros(state.cap, dtype=bool)
                else:
                    from wukong_tpu.engine.tpu import TPUEngine

                    vals = state.materialize(end)
                    fd = TPUEngine._fp_dup(seg)
                    keep = K.wk_walk_member_mask_known(
                        cur[None, :], state.n, vals, seg.bkey, seg.bstart,
                        seg.bdeg, seg.edges, col=0,
                        max_probe=seg.max_probe, depth=seg.max_deg_log2,
                        fpw0=seg.fpw0 if fd else None,
                        fpw1=seg.fpw1 if fd else None,
                        fp_dup=fd) & state.live_mask()
            else:
                seg = eng.dstore.merge_segment(pid, d)
                if seg is None:
                    keep = jnp.zeros(state.cap, dtype=bool)
                else:
                    vals = state.materialize(end)
                    keep = K.wk_walk_merge_member_pairs(
                        seg.ekey, seg.edges, jnp.int32(seg.num_edges),
                        cur, vals, state.n, state.live_mask())
        else:
            rev, real = eng.dstore.const_list(pid, d, end)
            if real >= state.cap * self._lookup_factor():
                keep = K.wk_walk_merge_member_binsearch(
                    rev, jnp.int32(real), cur, state.n, state.live_mask())
            else:
                keep = K.wk_walk_merge_member_list(
                    rev, jnp.int32(real), cur, state.n, state.live_mask())
        cap_new = self._member_cap(step, step_est, cap_override)
        if cap_new is not None and cap_new < state.cap:
            top = state.levels[-1]
            vals, parent, n, total = K.wk_walk_merge_compact(
                top.vals, top.parent if top.parent is not None
                else jnp.arange(state.cap, dtype=jnp.int32),
                keep, state.n, cap_new)
            state.levels[-1] = _Level(top.var, vals, parent)
            state.n = n
            state.live = None
            state.totals.append((step, total, cap_new))
            state.est_rows = max(min(state.est_rows, cap_new), 1.0)
        else:
            state.live = keep  # defer: fold into the next expand's degrees

    # ------------------------------------------------------------------
    def _probe_rounds(self, pid: int, d: int) -> int:
        """The probe kernels' ACTUAL static probe bound for this segment —
        from the staged device segment when present (it is, for any chain
        just measured: _dispatch staged it), a conservative 2 otherwise.
        bytes_model uses this instead of a fixed worst-case constant so the
        model's lower-bound guarantee holds (round-4 advisor)."""
        seg = self.eng.dstore._cache.get((int(pid), int(d)))
        return int(seg.max_probe) if seg is not None else 2

    def _member_depth(self, pid: int, d: int) -> int:
        """The probe-member kernel's static binary-search depth
        (member_mask_known's `depth` arg = seg.max_deg_log2); host-CSR
        max-degree bit_length as fallback when the segment is unstaged."""
        dstore = self.eng.dstore
        seg = dstore._cache.get((int(pid), int(d)))
        if seg is not None:
            return int(seg.max_deg_log2)
        csr = dstore._host_csr(pid, d)
        if csr is None:
            return 1
        _keys, offs, _edges = csr
        import numpy as _np

        md = int(_np.max(offs[1:] - offs[:-1])) if len(offs) > 1 else 1
        return max(md.bit_length(), 1)

    def bytes_model(self, q, B: int, mode: str) -> dict | None:
        """Host-side HBM-traffic model of the planned batch chain — the
        roofline half of the bench artifact. Walks `classify` exactly as the
        executors do and sums, per step, the segment arrays streamed plus
        the binding-table state read/written, at the LEARNED capacity
        classes (the memo written by the preceding run; estimate-driven
        classes where no memo exists — same rule as `_dispatch`). Staged
        device segments are sized from the DeviceStore cache when present
        (what the chain actually streamed, filtered folds included);
        evicted entries fall back to host CSR sizes. Each array is counted
        ONCE per step — no sort-pass or materialize-walk multipliers — so
        achieved-GB/s derived from this model is a LOWER bound on real
        traffic. The reference reports raw latencies with no such model
        (docs/performance/*.md); the 8x target needs the "is this near HBM
        peak?" judgment, hence this accounting.

        Returns {"segment_bytes", "table_bytes", "total_bytes"} or None for
        chains the merge path does not own.
        """
        eng = self.eng
        pats = q.pattern_group.patterns
        if not pats or not self.supports(q):
            return None
        index_mode = mode != "const"
        folds = self._plan_folds(pats, index_mode=index_mode)
        W = 4  # every staged array is int32

        def seg_arrays(key, pid, d):
            """(num_keys_padded, num_edges_padded) of a merge segment —
            staged sizes when cached, host CSR lengths as fallback. An
            EVICTED filtered-fold segment sizes as (0, 0): the unfiltered
            CSR would overstate what the run streamed and break the
            model's lower-bound guarantee."""
            seg = eng.dstore._cache.get(key)
            if seg is not None:
                return int(seg.skey.size), int(seg.edges.size)
            if key[0] == "mrgf":
                return 0, 0
            csr = eng.dstore._host_csr(pid, d)
            if csr is None:
                return 0, 0
            keys, _offs, edges = csr
            return len(keys), len(edges)

        def list_bytes(key, host_len_fn):
            ent = eng.dstore._index_cache.get(key)
            if ent is not None:
                return int(ent[0].size) * W
            return host_len_fn() * W

        seg_b = 0
        tab_b = 0
        if index_mode:
            p0 = pats[0]
            real = len(eng.g.get_index(p0.subject, p0.direction))
            total0 = real if mode == "slice" else real * B
            cap0 = K.next_capacity(max(total0, 1), eng.cap_min, eng.cap_max)
            seg_b += list_bytes(("idx", int(p0.subject), int(p0.direction)),
                                lambda: real)
            tab_b += W * cap0  # init writes the root level
        else:
            tab_b += W * K.next_capacity(B, eng.cap_min)
        from wukong_tpu.engine.device_store import fold_key

        for k, pat, kind, fold, cap, cap_out in self._walk_caps(
                pats, folds, index_mode, B, mode):
            pid, d, end = int(pat.predicate), int(pat.direction), pat.object
            if kind == "expand":
                if self._probe_lookup_wins(cap, pid, d):
                    # bucket probe: max_probe bucket rows (3 arrays) per
                    # frontier row + one gather per emitted edge — the whole
                    # point of the probe path is NOT streaming the segment
                    seg_b += W * (3 * self._probe_rounds(pid, d) * cap
                                  + cap_out)
                else:
                    # the merge and stream expands read skey+sstart+sdeg+
                    # edges (ekey stays untouched on the expand path)
                    if fold is not None:
                        nk, ne = seg_arrays(
                            ("mrgf", pid, d, fold_key(fold[0])), pid, d)
                    else:
                        nk, ne = seg_arrays(("mrg", pid, d), pid, d)
                    seg_b += W * (3 * nk + ne)
                # read the anchor column, write (vals, parent)
                tab_b += W * (cap + 2 * cap_out)
                continue
            if kind == "k2k":
                if self._probe_member_wins(cap, pid, d):
                    # bucket probe + per-row binary search: max_probe bucket
                    # rows (3 arrays) + depth edge gathers per frontier row —
                    # the ACTUAL static depths the kernel compiles with
                    # (member_mask_known's max_probe/depth args), not
                    # worst-case constants, so the model stays a lower bound
                    # (round-4 advisor)
                    seg_b += W * cap * (3 * self._probe_rounds(pid, d)
                                        + self._member_depth(pid, d))
                else:
                    # merge_member_pairs reads only the (ekey, edges) pair
                    # arrays
                    _nk, ne = seg_arrays(("mrg", pid, d), pid, d)
                    seg_b += W * 2 * ne
                tab_b += W * 2 * cap + cap  # two columns read + bool mask
            else:  # k2c
                key = ("rev", pid, d, int(end))
                ent = eng.dstore._index_cache.get(key)
                # REAL length decides, exactly as _dispatch does (the
                # staged array is pow2-padded; deciding on the pad would
                # flip the modeled branch with cache state)
                real = (int(ent[1]) if ent is not None else len(
                    eng.dstore._const_members(pid, d, end)))
                if real >= cap * self._lookup_factor():
                    # binary-search gathers at the kernel's actual depth:
                    # log2 of the padded list length it searches over
                    pad = int(ent[0].size) if ent is not None else real
                    seg_b += W * cap * max(int(pad).bit_length(), 1)
                else:
                    seg_b += list_bytes(key, lambda: real)
                tab_b += W * cap + cap  # one column read + bool mask
            if cap_out < cap:
                tab_b += W * 2 * cap_out  # compact writes (vals, parent)
        return {"segment_bytes": int(seg_b), "table_bytes": int(tab_b),
                "total_bytes": int(seg_b + tab_b)}
