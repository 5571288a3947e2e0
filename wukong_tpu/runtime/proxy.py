"""Proxy: the client-facing frontend (reference: core/proxy.hpp).

Glues parser -> planner -> engine and implements the reference's query modes:
- run_single_query: parse, optimize (or apply a user plan), execute with
  repeats, record latency, print/dump results (proxy.hpp:298-385)
- run_query_emu: open-loop throughput emulator over template mixes with
  candidate filling (proxy.hpp:69-129, 391-545) — see emulator.py
- dynamic_load_data / gstore_check passthroughs (proxy.hpp:548-597)
- streaming verbs (no reference analogue — Wukong+S): stream_register /
  stream_unregister / stream_poll for standing queries, stream_feed for
  epoch commits (see wukong_tpu/stream/)
"""

from __future__ import annotations

import threading
import time

import numpy as np

from wukong_tpu.analysis.lockdep import make_lock
from wukong_tpu.config import Global
from wukong_tpu.join.kernels import disown_level_programs, own_level_programs
from wukong_tpu.obs import (
    activate,
    get_recorder,
    get_registry,
    maybe_device_trace,
    maybe_start_metrics_http,
    maybe_start_trace,
)
from wukong_tpu.obs.device import note_feedback
from wukong_tpu.obs.reuse import maybe_observe_reuse
from wukong_tpu.obs.slo import get_overload, get_slo, tenant_label
from wukong_tpu.obs.trace import span
from wukong_tpu.runtime.admission import maybe_admission
from wukong_tpu.planner.heuristic import heuristic_plan
from wukong_tpu.planner.plan_file import set_plan
from wukong_tpu.runtime.batcher import (
    _M_PARSE_CACHE,
    _M_PLAN_CACHE,
    PlanCache,
    QueryBatcher,
    snapshot_patterns,
    template_signature,
)
from wukong_tpu.runtime.monitor import Monitor
from wukong_tpu.runtime.resilience import Deadline
from wukong_tpu.sparql.ir import SPARQLQuery, SPARQLTemplate
from wukong_tpu.sparql.parser import Parser
from wukong_tpu.types import IN, OUT, TYPE_ID, is_tpid
from wukong_tpu.utils.errors import ErrorCode, WukongError
from wukong_tpu.utils.logger import log_error, log_info
from wukong_tpu.utils.lru import LRUCache
from wukong_tpu.utils.timer import get_usec


# ceiling on how long a serving thread waits for a coalesced dispatch to
# settle (the stream lane's STREAM_WAIT_TIMEOUT_S analogue) — a wedged
# batcher surfaces as an error, never as a hung client
BATCH_WAIT_TIMEOUT_S = 600.0


def _batch_wait_timeout(q) -> float:
    dl = getattr(q, "deadline", None)
    if dl is not None:
        rem = dl.remaining_s()
        if rem is not None:
            return min(rem + 60.0, BATCH_WAIT_TIMEOUT_S)
    return BATCH_WAIT_TIMEOUT_S


class Proxy:
    def __init__(self, gstore, str_server, cpu_engine=None, tpu_engine=None,
                 dist_engine=None, planner=None):
        self.g = gstore
        self.str_server = str_server
        self.cpu = cpu_engine
        self.tpu = tpu_engine
        self.dist = dist_engine
        self.planner = planner  # cost-based optimizer (optional)
        self.monitor = Monitor()
        # observability: flight recorder ring + process metrics registry
        # (console verbs `trace` / `metrics` read these back)
        self.recorder = get_recorder()
        self.metrics = get_registry()
        self._m_queries = self.metrics.counter(
            "wukong_queries_total", "Proxy queries by reply status and tenant",
            labels=("status", "tenant"))
        self._m_lane = self.metrics.counter(
            "wukong_lane_routed_total",
            "Plan-time light/heavy lane routing decisions", labels=("lane",))
        # tensor-join strategy routing (wukong_tpu/join/): per-query
        # strategy decisions and wcoj-to-walk degradations
        self._m_join = self.metrics.counter(
            "wukong_join_queries_total",
            "Plan-time execution-strategy decisions", labels=("strategy",))
        self._m_join_fallback = self.metrics.counter(
            "wukong_join_fallback_total",
            "WCOJ executions degraded to the walk", labels=("reason",))
        self._m_join_demoted = self.metrics.counter(
            "wukong_join_demotions_total",
            "Templates demoted wcoj->walk by measured-blowup feedback")
        # device-route plumbing (join_device knob): plan-time host/device
        # decisions and the measured-candidate demotions back to host
        self._m_join_route = self.metrics.counter(
            "wukong_join_route_total",
            "Plan-time wcoj level-route decisions", labels=("route",))
        self._m_route_demoted = self.metrics.counter(
            "wukong_join_route_demotions_total",
            "Templates demoted device->host by measured-candidate feedback")
        # compiled-template routing (engine/template_compile.py): plan-
        # time route decisions and compiled executions degraded to the
        # host walk (the demotion latch itself counts inside the engine)
        self._m_template_route = self.metrics.counter(
            "wukong_template_route_total",
            "Plan-time compiled-template route decisions",
            labels=("route",))
        self._m_template_fallback = self.metrics.counter(
            "wukong_template_fallback_total",
            "Compiled-template executions degraded to the host walk",
            labels=("reason",))
        # hybrid graph+vector serving (wukong_tpu/vector/): per-mode knn
        # query counts, plan-time scan-route decisions, and the measured
        # demotions back to the host kernels (the JOIN_ROUTES posture)
        self._m_vec_queries = self.metrics.counter(
            "wukong_vector_queries_total",
            "knn() queries by composition mode", labels=("mode",))
        self._m_vec_route = self.metrics.counter(
            "wukong_vector_route_total",
            "Plan-time knn scan route decisions", labels=("route",))
        self._m_vec_demoted = self.metrics.counter(
            "wukong_vector_route_demotions_total",
            "knn templates demoted device->host by measured feedback")
        self._wcoj = None  # guarded by: _batcher_init_lock
        self._wcoj_dist = None  # guarded by: _batcher_init_lock
        self._template = None  # guarded by: _batcher_init_lock
        self._pool = None
        self._stream = None
        # serving fast path: parse cache (query text -> parsed query) and
        # plan cache (template signature + store version -> plan recipe);
        # the batcher itself starts lazily on the first batched dispatch
        self._parse_cache = LRUCache(Global.parse_cache_size)
        self._plan_cache = PlanCache(Global.plan_cache_size)
        self._batcher: QueryBatcher | None = None  # guarded by: _batcher_init_lock
        self._batcher_init_lock = make_lock("proxy.batcher_init")
        # fault tolerance: the recovery manager (checkpoint/restore + shard
        # healing) starts lazily; its background threads launch here only
        # when the knobs ask for them (zero-cost when off)
        self._recovery = None  # guarded by: _recovery_init_lock
        self._recovery_init_lock = make_lock("proxy.recovery_init")
        if (Global.checkpoint_interval_s > 0 and Global.checkpoint_dir) or (
                dist_engine is not None and Global.replication_factor > 1):
            self.recovery().start()
        # metrics scrape endpoint (metrics_port knob; no-op when 0/off)
        maybe_start_metrics_http()
        # the placement observatory: the metrics time-series sampler
        # (enable_tsdb; trend windows for /history and the advisor) and —
        # with a sharded store — the observe-only placement advisor
        # (placement_interval_s > 0 runs its loop; 0 = on-demand /plan)
        from wukong_tpu.obs.placement import maybe_start_advisor
        from wukong_tpu.obs.tsdb import maybe_start_tsdb

        maybe_start_tsdb()
        sstore = getattr(dist_engine, "sstore", None)
        if sstore is not None:
            # the migration actuator (runtime/migration.py) attaches
            # either way (the `migrate` verb works on demand); when its
            # loop runs (migration_enable + placement_interval_s) it
            # sweeps the advisor itself, so the observe-only loop is
            # skipped — one sweeper, not two
            from wukong_tpu.runtime.migration import maybe_start_migration

            if maybe_start_migration(sstore, owner=self) is None:
                maybe_start_advisor(sstore)
            # /healthz readiness probe: degraded or failover shards mean
            # the process serves, but not at full strength. The probe
            # holds the store through a weakref: the registry is
            # process-global, so a strong capture would keep a retired
            # world's degraded set driving readiness (503 under
            # health_ready_503) long after the store that owned it died
            import weakref

            from wukong_tpu.obs.httpd import register_health_source

            ss_ref = weakref.ref(sstore)

            def _shard_probe():
                ss = ss_ref()
                if ss is None or not (ss.degraded_shards
                                      or ss.failover_shards):
                    return None
                return {"degraded": sorted(ss.degraded_shards),
                        "failover": sorted(ss.failover_shards)}

            register_health_source("shards", _shard_probe)
        # surface the sharded store's per-shard breaker in the rolling
        # throughput report (resilience observability, PR 1 follow-up)
        breaker = getattr(getattr(dist_engine, "sstore", None), "breaker", None)
        if breaker is not None:
            self.monitor.attach_breaker("dist.shard", breaker)
        # the materialized-view serving plane (wukong_tpu/serve/): bind
        # the result cache + view registry to THIS proxy's host
        # partition — a re-attach (new world in-process) purges entries
        # and drops old-world view registrations wholesale
        from wukong_tpu.serve import get_serve

        get_serve().attach(self.g, self.str_server)

    def engine_pool(self):
        """Lazily-started host engine pool (N CPU engines with stealing and
        adaptive snooze — wukong.cpp:202-225 spawns these at boot; here the
        first concurrent workload starts them)."""
        if self._pool is None:
            from wukong_tpu.runtime.scheduler import EnginePool

            self._pool = EnginePool(
                make_engine=lambda tid: self._new_host_engine())
            self._pool.start()
        return self._pool

    # ------------------------------------------------------------------
    def _parse_text(self, text: str) -> SPARQLQuery:
        """Parse with the bounded-LRU parse cache: repeated query texts
        skip the parser entirely. Entries are pickled blobs — loads() is
        several times cheaper than deepcopy on the serving fast path, and
        every hit gets a pristine query (no execution-state leaks)."""
        import pickle

        blob = self._parse_cache.get(text)
        if blob is not None:
            _M_PARSE_CACHE.labels(result="hit").inc()
            q = pickle.loads(blob)
            q._qtext = text  # view promotion re-registers from the text
            return q
        _M_PARSE_CACHE.labels(result="miss").inc()
        q = Parser(self.str_server).parse(text)
        q._qtext = text
        try:
            self._parse_cache.put(
                text, pickle.dumps(q, protocol=pickle.HIGHEST_PROTOCOL))
        except Exception:  # unpicklable artifact: skip caching, stay correct
            _M_PARSE_CACHE.labels(result="uncacheable").inc()
        return q

    def _plan_version(self):
        """The plan-cache version key: the store version (dynamic inserts /
        stream commits bump it) + whether the cost planner is active."""
        return (getattr(self.g, "version", 0),
                self.planner is not None and Global.enable_planner)

    def _plan(self, q: SPARQLQuery, plan_text: str | None = None) -> None:
        if plan_text is not None:
            if Global.enable_planner:
                log_info("user plan ignored: planner is enabled (config)")
            elif not set_plan(q.pattern_group, plan_text):
                raise WukongError(ErrorCode.UNKNOWN_PLAN, "bad plan file")
            else:
                return
        if getattr(getattr(q, "knn", None), "mode", "") == "rank_then_pattern":
            # a seeded chain executes in TEXTUAL order outward from the
            # knn seeds: a planner reorder would re-root the chain away
            # from the seeded variable and flip the query's semantics
            q._tsig = template_signature(q)
            q._rver = self._plan_version()[0]
            return
        # plan cache: same template signature + same store version replays
        # the recorded plan recipe (dynamic inserts / stream commits bump
        # the version, so stale plans never apply)
        sig = template_signature(q)
        # stashed for the reply-side reuse observatory: classify() reuses
        # the plan-time signature instead of re-walking the patterns
        # (the largest single component of the per-reply hook cost), and
        # the shadow key must carry the version the read EXECUTES under —
        # a write committing between plan and reply would otherwise file
        # the key under the new version and credit hits a real cache
        # could not have served
        q._tsig = sig
        version = self._plan_version()
        q._rver = version[0]
        # plans are kept by the template's family (_template_family): a
        # template that draws its type shares one join order, so its
        # draws run one set of programs
        fam = self._template_family(sig)
        if sig is None:
            # unions/optionals/empty groups plan recursively — shapes the
            # recipe cache (and the item-7 result cache) cannot key
            _M_PLAN_CACHE.labels(result="uncacheable").inc()
        elif self._plan_cache.lookup(q, fam, version):
            return
        parsed = snapshot_patterns(q) if sig is not None else None
        if self.planner is not None and Global.enable_planner:
            if self.planner.generate_plan(q):
                if sig is not None:
                    self._plan_cache.record(parsed, q, fam, version)
                return
        heuristic_plan(q)
        if sig is not None:
            self._plan_cache.record(parsed, q, fam, version)

    def _engine_for(self, q: SPARQLQuery, device: str | None):
        """The engine a request is served by: the pinned ``device``, else
        the sharded engine where the proxy holds one (the deployment is
        sharded), else the single-partition engines as configured."""
        if device == "dist" or (device is None and self.dist is not None):
            return self.dist or self.cpu
        return self._host_engine(device)

    def _host_engine(self, device: str | None = None):
        """The engine that answers without the sharded one: the device
        engine where ``tpu`` is pinned, or unpinned on a single-partition
        proxy while it is on; else the CPU engine, which on a sharded
        deployment walks every shard in place."""
        if device == "tpu" or (device is None and self.dist is None
                               and Global.enable_tpu and self.tpu):
            return self.tpu or self.cpu
        return self.cpu

    def _whole_graph(self):
        """The graph a host-side read sees whole: the proxy's partition,
        or where that is one shard of a sharded deployment, the host
        engine's view of every shard."""
        if self.dist is not None and self.cpu is not None:
            return self.cpu.g
        return self.g

    def _new_host_engine(self):
        """A host engine over the whole graph, for the engine pool."""
        from wukong_tpu.engine.cpu import CPUEngine
        from wukong_tpu.parallel.inplace import InplaceEngine

        if isinstance(self.cpu, InplaceEngine):  # the shards, in place
            return InplaceEngine(self.cpu.g.stores, self.str_server)
        return CPUEngine(self.g, self.str_server)

    # ------------------------------------------------------------------
    def run_single_query(self, text: str, repeats: int = 1,
                         plan_text: str | None = None, mt_factor: int = 1,
                         device: str | None = None, blind: bool | None = None,
                         print_results: int = 0,
                         tenant: str = "default") -> SPARQLQuery:
        """sparql -f <file> [-n repeats] [-p plan] [-m mt] [-N] [-v N] [-t tenant] (console.hpp:141-153)."""
        if mt_factor > 1:
            # the reference fans an index scan out to mt_factor threads and
            # merges replies (sparql.hpp:1064-1088). The single-driver engines
            # here scan the whole index vectorized in one kernel, and the
            # distributed engine shards scans per partition — so -m is a no-op
            # rather than a partial-result slice.
            log_info("-m (mt_factor) is vectorized away on this engine; "
                     "running the full index scan")

        if repeats < 1:
            # validate BEFORE admission: a raise past _admit would leak
            # the tenant's in-flight slot (note_done never runs)
            raise WukongError(ErrorCode.SYNTAX_ERROR, "repeats must be >= 1")
        # per-query trace context, created at receipt (sampled; None when
        # tracing is off — every downstream hook then degrades to a getattr)
        trace = maybe_start_trace(kind="query", text=text)
        t0_us = get_usec()
        # tenant admission: bounded label + overload-bus in-flight/arrival
        # note (obs/slo.py; one knob check when accounting is off)
        ten = self._admit(tenant)
        if trace is not None:
            trace.tenant = ten

        adm_d = None

        def prepare():
            qq = self._prepare(text, trace, blind, plan_text, ten)
            if adm_d is not None:
                adm_d.apply(qq)
            return qq

        q = None
        total_us = 0
        # activate the trace on the proxy thread too (parse/plan/fallback
        # decisions), and scope the JAX device profiler around the traced
        # execution when WUKONG_XPROF_DIR asks for an XProf capture
        try:
            adm_d = self._consult_admission(ten)
            with activate(trace), maybe_device_trace():
                q, total_us = self._run_repeats(prepare, repeats, device,
                                                trace)
        except Exception as e:
            # a parse/plan failure raises before any reply exists — it must
            # still reach the reply-side observability (a syntax-error storm
            # is an operational signal, not a silent gap)
            code = e.code if isinstance(e, WukongError) else "ERROR"
            self._m_queries.labels(
                status=code.name if isinstance(code, ErrorCode)
                else str(code), tenant=ten).inc()
            if trace is not None:
                self.recorder.on_complete(trace, code)
            self._observe_slo(ten, get_usec() - t0_us, ok=False,
                              status=code, trace=trace)
            raise
        # reply-side observability: the finished trace enters the flight
        # recorder (auto-dumping on timeout/budget/shard failures), and the
        # reply status lands on the metrics registry
        status = q.result.status_code
        self._m_queries.labels(status=status.name, tenant=ten).inc()
        if trace is not None:
            self.recorder.on_complete(trace, status)
            self._attribute(trace, q, text)
            log_info(f"trace {trace.trace_id} (qid {trace.qid}) recorded: "
                     f"{len(trace.spans)} spans, {trace.dur_us:,}us")
        # SLO accounting after the trace is finished/recorded: a burn
        # dump must serialize a completed trace, not a RUNNING one
        self._observe_slo(ten, get_usec() - t0_us,
                          ok=status == ErrorCode.SUCCESS, status=status,
                          trace=trace)
        self._note_admission_reply(ten, q)
        # serving-cache observatory (obs/reuse.py): template popularity +
        # the observe-only shadow-cache probe, charged at the reply point
        # against the store version the read executed under
        self._observe_reuse(q, ten, text)
        if q.result.status_code != ErrorCode.SUCCESS:
            if not q.result.complete:
                # structured partial reply, not a crash: the rows produced
                # before the deadline/budget expiry are still in the table
                log_error(
                    f"query degraded: {q.result.status_code.name} — partial "
                    f"result ({q.result.nrows} rows, "
                    f"{len(q.result.dropped_patterns)} pattern(s) dropped)")
            else:
                log_error(f"query failed: {q.result.status_code.name}")
            return q
        log_info(f"(last) result rows: {q.result.nrows}, "
                 f"avg latency: {total_us / repeats:,.0f} usec ({repeats} runs)")
        if print_results and not q.result.blind:
            self.print_result(q, min(print_results, q.result.nrows))
        return q

    def _prepare(self, text: str, trace, blind, plan_text,
                 ten: str) -> SPARQLQuery:
        """Parse and plan one text under the ``proxy.parse`` and
        ``proxy.plan`` spans; the query carries the trace from here."""
        with span(trace, "proxy.parse"):
            qq = self._parse_text(text)
        if trace is not None:
            qq.trace = trace
            qq.qid = trace.qid
        with span(trace, "proxy.plan"):
            self._plan_prepared(qq, blind, plan_text, tenant=ten)
        return qq

    def _run_repeats(self, prepare, repeats: int, device, trace):
        """The repeat/fallback execution loop (shape + capacity
        degradation); returns (last query, total execution usec). Each
        execution, the fallbacks' too, is one ``proxy.execute`` span."""
        q = None
        total_us = 0
        for i in range(repeats):
            q = prepare()
            eng = self._engine_for(q, device)
            t0 = get_usec()
            with span(trace, "proxy.execute"):
                self._serve_execute(q, eng, pinned=device is not None)
            total_us += get_usec() - t0
            if (q.result.status_code == ErrorCode.UNSUPPORTED_SHAPE
                    and eng is self.dist):
                # the distributed engine rejects some shapes up front
                # (UNION/OPTIONAL/versatile) — fall back to the
                # configured host engine. Capacity-exhaustion failures
                # keep their error status (falling back would
                # materialize the oversized table on one host).
                log_info("distributed engine rejected the plan shape; "
                         "falling back to the host engine")
                host = self._host_engine()
                if host is None:
                    break  # no host engine: keep the error status
                if trace is not None:
                    trace.event("proxy.fallback", reason="shape",
                                to="host")
                q = prepare()
                t0 = get_usec()
                with span(trace, "proxy.execute"):
                    host.execute(q)
                total_us += get_usec() - t0
            elif (q.result.status_code == ErrorCode.CAPACITY_EXCEEDED
                  and eng is self.tpu and self.cpu is not None):
                # graceful degradation: the device capacity ceiling is a
                # TPU constraint, not a query property — the CPU engine
                # has no capacity classes, so re-run host-side (the
                # resilience analogue of the GPU->CPU spill in
                # WCOJ-on-GPU engines)
                log_info("device capacity exceeded; degrading to the "
                         "CPU engine")
                if trace is not None:
                    trace.event("proxy.fallback", reason="capacity",
                                to="cpu")
                q = prepare()
                t0 = get_usec()
                with span(trace, "proxy.execute"):
                    self.cpu.execute(q)
                total_us += get_usec() - t0
            if q.result.status_code in (ErrorCode.QUERY_TIMEOUT,
                                        ErrorCode.BUDGET_EXCEEDED):
                break  # deadline/budget spent: repeats are pointless
        return q, total_us

    def _admit(self, tenant) -> str:
        """Tenant admission: the bounded metric-label form of the tenant
        id, plus the overload bus's in-flight/arrival note. With
        accounting off this is one knob check and the raw id."""
        if not Global.enable_tenant_accounting:
            return str(tenant) if tenant else "default"
        ten = tenant_label(tenant)
        get_overload().note_admit(ten)
        return ten

    def _consult_admission(self, ten: str, cached: bool = False):
        """The admission control plane's consult point, AFTER ``_admit``
        (so the in-flight signal includes the query under decision) and
        inside the caller's reply-accounting try (a rejection releases
        the in-flight slot through ``_observe_slo``). One knob check
        when the plane is off. Rung-1 defers sleep HERE on the serving
        thread (past the batch window, draining congestion); rung-3
        raises the structured CAPACITY_EXCEEDED rejection; the returned
        Decision stamps a rung-2 partial budget onto the prepared
        query."""
        adm = maybe_admission()
        if adm is None:
            return None
        d = adm.admit(ten, cached=cached)
        if d.action == "reject":
            raise WukongError(
                ErrorCode.CAPACITY_EXCEEDED,
                f"admission shed: tenant {ten!r} ({d.reason or 'overload'})"
                f" — retry after {d.retry_after_s:.1f}s")
        if d.action == "defer" and d.wait_s > 0:
            time.sleep(min(d.wait_s, 5.0))
        return d

    def _note_admission_reply(self, ten: str, q) -> None:
        """Reply-side aggregate-row accounting for the row-budget quota
        (one knob check when the plane is off)."""
        adm = maybe_admission()
        if adm is not None:
            adm.note_reply(ten, int(getattr(q.result, "nrows", 0)))

    def _observe_slo(self, tenant: str, dur_us: int, ok: bool, status,
                     trace) -> None:
        """Reply-side SLO accounting (the LatencyAttributor observation
        point): release the in-flight slot, count reply-side sheds, and
        fold the reply into the tenant's SLO window — the burn-rate
        sentinel fires from here. One knob check when accounting is off."""
        if not Global.enable_tenant_accounting:
            return
        sig = get_overload()
        sig.note_done(tenant)
        if status == ErrorCode.QUERY_TIMEOUT:
            sig.note_shed("reply_timeout", tenant)
        elif status == ErrorCode.BUDGET_EXCEEDED:
            sig.note_shed("reply_budget", tenant)
        get_slo().observe(tenant, int(dur_us), ok, trace=trace)

    def _observe_reuse(self, q, tenant: str, text: str) -> None:
        """Reply-side reuse-observatory hook: the shadow key carries the
        PLAN-time store version (``_rver``, stashed where the plan cache
        read it), so a write landing between plan and reply cannot file
        the key under a version the read never saw. Queries that skipped
        the plan path (user plan files) fall back to the current
        version. With the real cache on, the shadow's verdict for this
        reply is compared against the real probe's (stamped on the query
        in ``_serve_execute``) — a disagreement on the same key counts
        toward ``wukong_cache_divergence_total``."""
        shadow_hit = maybe_observe_reuse(
            q, tenant,
            q.__dict__.get("_rver", getattr(self.g, "version", 0)),
            text=text)
        if Global.enable_result_cache:
            from wukong_tpu.serve.result_cache import note_shadow_outcome

            note_shadow_outcome(q, shadow_hit)

    def _plan_prepared(self, qq: SPARQLQuery, blind, plan_text,
                       tenant: str = "default") -> None:
        """Shared prepare tail: tenant stamp, blind mode, resilience
        knobs, planning, plan-time lane routing."""
        qq.tenant = tenant
        qq.mt_factor = 1
        qq.result.blind = Global.silent if blind is None else blind
        # per-query deadline + work budget from the resilience knobs
        # (query_deadline_ms / query_budget_rows; None when both off)
        qq.deadline = Deadline.from_config()
        self._plan(qq, plan_text)
        if getattr(qq, "knn", None) is not None:
            self._prepare_knn(qq)
        qq.lane = self.classify_lane(qq)
        self._m_lane.labels(lane=qq.lane).inc()
        qq.join_strategy = self.classify_join_strategy(qq)
        self._m_join.labels(strategy=qq.join_strategy).inc()
        if qq.join_strategy == "wcoj":
            qq.join_route = self.classify_join_route(qq)
            self._m_join_route.labels(route=qq.join_route).inc()
        elif getattr(qq, "knn", None) is None and self.dist is None:
            # walk-strategy shapes may compile the WHOLE plan into one
            # fused device program (engine/template_compile.py); a sharded
            # deployment's chain is the sharded engine's own program
            qq.template_route = self.classify_template_route(qq)
            self._m_template_route.labels(route=qq.template_route).inc()

    # ------------------------------------------------------------------
    # hybrid graph+vector routing (wukong_tpu/vector/)
    # ------------------------------------------------------------------
    def _prepare_knn(self, q: SPARQLQuery) -> None:
        """Plan-time knn stamps: refuse when the subsystem is off (the
        actuator posture — never silently degrade a vector query to a
        graph query), classify the composition mode and scan route, and
        flag wide scans so lane routing sends them down the heavy lane."""
        from wukong_tpu.vector import knn as vknn

        if not Global.enable_vectors:
            raise WukongError(ErrorCode.ATTR_DISABLE,
                              "knn() requires enable_vectors")
        q.knn_mode = vknn.classify_knn_mode(q)
        self._m_vec_queries.labels(mode=q.knn_mode).inc()
        vs = getattr(self.g, "vstore", None)
        n = int(vs.live_count()) if vs is not None else 0
        # EXPLAIN inputs (obs/profile.py): scan size = every live
        # embedding, scan bytes = the float32 block the kernel reads
        q._knn_live = n
        q._knn_dim = int(vs.dim) if vs is not None else 0
        # a wide scan-side composition (pure scan / rank-then-pattern)
        # is heavy-lane work: slice-range split across the engine pool
        q._knn_wide = (q.knn_mode != "pattern_then_rank"
                       and n >= max(int(Global.knn_split_threshold), 1))
        q.knn_route = self.classify_knn_route(q, n)
        self._m_vec_route.labels(route=q.knn_route).inc()

    def classify_knn_route(self, q: SPARQLQuery, live: int) -> str:
        """Plan-time host/device route for the knn scan, memoized per
        template signature + store version under ``knn_device auto``
        (vector upserts bump the store version, so the volume-driven
        decision re-arms on every embedding mutation). Overwritten by
        ``_record_knn_feedback`` when the device path failed."""
        knob = str(Global.knn_device).strip().lower()
        if knob in ("host", "device"):
            return knob
        thr = max(int(Global.knn_split_threshold), 1)

        def compute() -> str:
            # device when the scan volume amortizes the dispatch: the
            # split threshold doubles as the auto-device floor (both mark
            # "wide enough that per-dispatch overhead stops mattering")
            return "device" if live >= thr else "host"

        sig = template_signature(q)
        if sig is None:
            return compute()  # pure scans: unmemoized, computed per query
        return self._plan_cache.aux("knn_route", sig,
                                    self._knn_route_memo_key(), compute)

    def _knn_route_memo_key(self):
        return (*self._plan_version(), "auto",
                int(Global.knn_split_threshold))

    def _record_knn_feedback(self, q: SPARQLQuery) -> None:
        """Measured-feedback demotion for the knn device route: the
        engine/slice fallback latched a device failure onto the query
        (``knn_demoted``) — under ``knn_device auto``, demote the
        template's memoized route to host so same-template queries stop
        re-paying the failed device attempt. A store mutation or knob
        flip re-arms the volume-driven decision."""
        if getattr(q, "knn", None) is None:
            return
        demoted = getattr(q, "knn_demoted", None)
        if demoted is None:
            return
        if str(Global.knn_device).strip().lower() == "auto":
            sig = template_signature(q)
            if sig is not None:
                self._plan_cache.put_aux("knn_route", sig,
                                         self._knn_route_memo_key(), "host")
        self._m_vec_demoted.inc()
        note_feedback("knn", "demote_host")
        log_info(f"knn device route: demoted to host ({demoted})")

    def _maybe_presolve_knn(self, q: SPARQLQuery) -> None:
        """Wide scan-side knn: run the slice-range split across the
        engine pool's heavy lane HERE (the proxy owns the pool), stamping
        the ranked seeds onto the query so the engine's ``_knn_pre``
        consumes them instead of scanning inline. Any fan-out failure
        falls back to the engine's single-threaded scan — degraded, never
        broken."""
        if (getattr(q, "knn", None) is None
                or not getattr(q, "_knn_wide", False)
                or getattr(q, "knn_seeds", None) is not None):
            return
        vs = getattr(self.g, "vstore", None)
        if vs is None:
            return  # the engine raises the structured error
        from wukong_tpu.vector import knn as vknn

        try:
            anchor = vknn.resolve_anchor(vs, q.knn)
        except WukongError:
            return  # the engine surfaces it with proper status plumbing
        metric = q.knn.metric or Global.knn_metric
        thr = max(int(Global.knn_split_threshold), 1)
        n = int(vs.live_count())
        parts = max(min(n // thr + 1, 8), 1)
        if parts <= 1:
            return
        # the heavy-split decision: this scan fans out across the pool
        note_feedback("knn", "heavy_split")
        try:
            seeds, _scores, demoted = vknn.sliced_topk(
                self.engine_pool(), vs, anchor, q.knn.k, metric,
                getattr(q, "knn_route", "host"), parts)
        except Exception as e:
            log_info(f"knn sliced scan failed ({type(e).__name__}); "
                     "the engine scans inline")
            return
        q.knn_seeds = seeds
        if demoted:
            q.knn_demoted = demoted

    # ------------------------------------------------------------------
    # tensor-join strategy routing (wukong_tpu/join/)
    # ------------------------------------------------------------------
    def classify_join_strategy(self, q: SPARQLQuery) -> str:
        """Plan-time walk/wcoj strategy for a PLANNED query, memoized per
        template signature + store version through the plan cache (the
        ``lane`` pattern). The mutable knobs join the memo key so a
        runtime ``join_strategy``/``wcoj_ratio`` change applies
        immediately instead of serving stale decisions."""
        pg = q.pattern_group
        if (pg.unions or pg.optional or q.planner_empty
                or not pg.patterns
                or getattr(q, "knn", None) is not None
                or self.dist is not None):
            # knn composition lives in the walk engine's pre/post hooks;
            # the tensor-join executors have no vector seam; and a sharded
            # deployment serves through its sharded chain, which closes a
            # cycle with a membership step on the owner's shard
            return "walk"
        knob = str(Global.join_strategy).strip().lower()
        if knob == "walk":
            return "walk"
        if self.planner is None or not Global.enable_planner:
            # no cost model: only the forced knob may route wcoj
            if knob != "wcoj":
                return "walk"
            from wukong_tpu.join.qgraph import analyze

            return "wcoj" if analyze(pg.patterns).supported else "walk"
        sig = template_signature(q)
        pats = list(pg.patterns)
        key_extra = (knob, int(Global.wcoj_ratio),
                     int(Global.wcoj_min_rows))
        return self._plan_cache.aux(
            "strategy", sig, (*self._plan_version(), *key_extra),
            lambda: self.planner.choose_strategy(pats))

    def classify_join_route(self, q: SPARQLQuery) -> str:
        """Plan-time host/device level route for a wcoj-routed query,
        memoized per template signature + store version like the strategy
        decision (the knobs join the key so a runtime flip applies
        immediately). Overwritten by ``_record_route_feedback`` when the
        measured candidate volume says the estimate over-predicted."""
        knob = str(Global.join_device).strip().lower()
        if knob in ("host", "device"):
            return "device" if knob == "device" else "host"
        if self.planner is None or not Global.enable_planner:
            return "host"  # no cost model to amortize the dispatch against
        sig = template_signature(q)
        pats = list(q.pattern_group.patterns)
        key_extra = (knob, int(Global.join_device_min_candidates))
        return self._plan_cache.aux(
            "route", sig, (*self._plan_version(), *key_extra),
            lambda: self.planner.choose_join_route(pats))

    def _route_memo_key(self):
        return (*self._plan_version(), "auto",
                int(Global.join_device_min_candidates))

    def _record_route_feedback(self, q: SPARQLQuery) -> None:
        """Device-route feedback (the PR 10 measured-blowup pattern, one
        layer down): after a successful wcoj execution that ROUTED device
        under ``join_device auto``, compare the MEASURED candidate volume
        (summed per-level candidates from ``q.join_stats``) against the
        dispatch-amortization threshold and demote the memoized route to
        host when the estimate over-predicted — the padded dispatches
        were pure overhead on a chain this small. The memo key mirrors
        ``classify_join_route``'s exactly, so the demotion takes effect
        on the very next same-template query, and a knob flip or store
        mutation re-arms the estimate-driven decision."""
        stats = getattr(q, "join_stats", None)
        if (not stats or q.result.status_code != ErrorCode.SUCCESS
                or getattr(q, "join_route", "host") != "device"
                or str(Global.join_device).strip().lower() != "auto"
                or self.planner is None or not Global.enable_planner):
            return
        sig = template_signature(q)
        if sig is None:
            return
        if getattr(q, "_join_device_broken", False):
            # the executor latched host mid-query (DeviceRangeError, a
            # kernel bug, ...): a deterministic failure would re-pay the
            # failed device attempt on every same-template query — demote
            # the memo; a store mutation or knob flip re-arms the attempt
            self._plan_cache.put_aux("route", sig, self._route_memo_key(),
                                     "host")
            self._m_route_demoted.inc()
            disown_level_programs(sig)
            note_feedback("join_route", "latched_host")
            log_info("wcoj device route: template demoted to host "
                     "(device path failed and latched host)")
            return
        measured = sum(int(lv.get("candidates", 0)) for lv in stats)
        if measured < max(int(Global.join_device_min_candidates), 1):
            self._plan_cache.put_aux("route", sig, self._route_memo_key(),
                                     "host")
            self._m_route_demoted.inc()
            disown_level_programs(sig)
            note_feedback("join_route", "demote_host")
            log_info(f"wcoj device route: template demoted to host "
                     f"(measured candidates {measured:,} < "
                     f"join_device_min_candidates "
                     f"{Global.join_device_min_candidates:,})")

    # ------------------------------------------------------------------
    # whole-plan compiled-template routing (engine/template_compile.py)
    # ------------------------------------------------------------------
    def classify_template_route(self, q: SPARQLQuery) -> str:
        """Plan-time host/device route for a walk-strategy query through
        the whole-plan compiled engine. Only the rule's INPUTS are
        memoized per template signature + store version (the planner's
        per-step estimates, the ``lane`` pattern; the capacity classes of
        the plan's program, by the template engine) — the route itself is
        chosen live by ``choose_template_route`` so the per-template
        demotion latch applies on the very next query, not at the next
        memo invalidation."""
        from wukong_tpu.engine.template_compile import \
            choose_template_route

        # the PRE-PLAN signature (stamped in _plan): the demotion latch
        # keys on q._tsig at failure time, and the planner has reordered
        # the patterns by now — recomputing here would never match it
        sig = getattr(q, "_tsig", None)
        if sig is None:
            sig = template_signature(q)
        if sig is None:
            return "host"  # recursive shapes: no template to compile
        est = None
        if self.planner is not None and Global.enable_planner:
            pats = list(q.pattern_group.patterns)

            def compute():
                try:
                    return self.planner.estimate_chain(pats)
                except Exception:
                    return None

            steps = self._plan_cache.aux("template_est", sig,
                                         self._plan_version(), compute)
            # the program's capacity classes start from the steps' own
            # estimates; the route is gated on their peak
            q._template_est_steps = steps
            est = int(max(steps)) if steps else None
        q._template_est_rows = est
        caps = None
        if self.tpu is not None and Global.enable_tpu \
                and not Global.enable_batching:
            # the walk would be the device engine, a jitted call a step:
            # a plan whose program is small takes the program for the
            # calls it saves. (A NumPy walk makes no calls, and the
            # batcher already answers many replies with one.)
            caps = self.template_engine().plan_caps(q)
        q._template_plan_caps = caps
        return choose_template_route(self._template_family(sig), est,
                                     getattr(self.g, "version", 0), caps)

    def _template_family(self, sig):
        """``sig`` with each type constant replaced by its heaviest peer
        (``GStore.heaviest_peer_type``): what the plan cache, the compiled
        route and its demotion latch go by. A template that draws its type
        from a class of classes (WatDiv's S3 and S5: one of 15 product
        categories) is then planned, routed and demoted once, not once a
        type: a type first met later runs the join order, and so the
        programs, of the first, and builds no template program of its own
        after the demotion. (``q._tsig`` keeps the type: it keys replies.)"""
        peer = getattr(self.g, "heaviest_peer_type", None)
        if sig is None or peer is None:
            return sig

        def typed(row):
            o = row[3]
            return row[1] == TYPE_ID and isinstance(o, tuple) and o[0] == "k"

        # a type named twice stays itself: a recipe cannot tell its two
        # places apart (build_plan_recipe pins it)
        once = {o for o in (r[3] for r in sig if typed(r))
                if sum(1 for r in sig if typed(r) and r[3] == o) == 1}
        return tuple(
            (s, p, d, ("k", peer(o[1])), t) if o in once and p == TYPE_ID
            else (s, p, d, o, t) for (s, p, d, o, t) in sig)

    def template_engine(self):
        """Lazily-built whole-plan compiled engine over the host
        partition (its staged device operands are cached per store
        version through the shared JoinTableCache discipline, so
        dynamic inserts and stream commits self-invalidate)."""
        if self._template is None:  # unguarded: double-checked fast path, as wcoj()
            with self._batcher_init_lock:
                if self._template is None:
                    from wukong_tpu.engine.template_compile import \
                        TemplateCompiledEngine

                    self._template = TemplateCompiledEngine(
                        self.g, self.str_server)
        return self._template  # unguarded: write-once reference, non-None past init

    def _record_template_feedback(self, q: SPARQLQuery) -> None:
        """Measured feedback for the compiled-template route: after a
        successful compiled execution under ``template_device auto``, a
        measured live-row count below ``template_min_rows`` out of a
        program with a capacity class at or over it means the estimate
        over-predicted and the program pushed padding through every step
        — latch the template back to the walk, which compacts between
        steps (a store mutation re-arms the estimate-driven decision). A
        program whose every class is under ``template_min_rows`` is kept
        whatever its reply holds: it is there for the calls it saves."""
        if str(Global.template_device).strip().lower() != "auto":
            return
        recs = [r for r in (getattr(q, "device_steps", None) or [])
                if r.get("site") == "template.plan"]
        if not recs:
            return
        live = int(recs[-1].get("live", 0))
        floor = max(int(Global.template_min_rows), 1)
        caps = getattr(q, "_template_caps", None)  # the classes it ran at
        if live < floor and not (caps and max(caps) < floor):
            from wukong_tpu.engine.template_compile import latch_demotion

            latch_demotion(
                self._template_family(getattr(q, "_tsig", None)),
                "small_measured", getattr(self.g, "version", 0))
            self.template_engine().drop_template(getattr(q, "_tsig", None))
            tr = getattr(q, "trace", None)
            if tr is not None:
                tr.event("proxy.route", route="template", demoted="walk",
                         why=f"live rows {live} < template_min_rows")
            log_info(f"compiled template demoted to the host walk "
                     f"(measured live rows {live:,} < template_min_rows "
                     f"{Global.template_min_rows:,})")

    @staticmethod
    def _own_join_programs(q: SPARQLQuery) -> None:
        """The join's level programs ``q`` ran belong to its template
        until a demotion takes the template off the join's device route
        (``disown_level_programs``): their text is resident while they are
        cached, and a LUBM heavy's first request runs the route once."""
        used = getattr(q, "_join_programs", None)
        sig = template_signature(q) if used else None
        if sig is not None:
            own_level_programs(used, sig)

    def _record_wcoj_feedback(self, q: SPARQLQuery) -> None:
        """WCOJ auto-routing feedback (PR 9 headroom): after a successful
        wcoj execution, record the MEASURED materialized-prefix blowup
        (peak per-level ``rows_out`` over the final fragment) from
        ``q.join_stats`` into the plan cache, and demote the template's
        memoized ``auto`` strategy to the walk when wcoj did NOT deliver
        its premise — intermediates bounded near the fragment. ``auto``
        routes wcoj on the ESTIMATED walk blowup, which over-predicts on
        the small WatDiv cyclic shapes: when the join's
        own materialized rows still blow past ``wcoj_ratio`` x final, it
        is doing walk-like materialization PLUS per-level intersection
        overhead, and the walk's simpler kernels win. Measured on the
        cyclic suite: winners keep the prefix at ~1.0x final (triangle
        1.0 / diamond 1.0) while the losers materialize 18-55x (clique4
        18.5 / w_tri_likes 27 / w_tri_follows 55). The closing-level
        CANDIDATE count is deliberately excluded — bounding candidates
        while materializing few rows is exactly the leapfrog win, and a
        candidate-based rule would demote the triangle's 14.8x speedup
        (candidates/final = 2.9 there). The memo key mirrors
        ``classify_join_strategy``'s exactly, so the demotion takes
        effect on the very next same-template query, and a knob flip or
        store mutation re-arms the estimate-driven decision."""
        stats = getattr(q, "join_stats", None)
        if (not stats or q.result.status_code != ErrorCode.SUCCESS
                or str(Global.join_strategy).strip().lower() != "auto"
                or self.planner is None or not Global.enable_planner):
            return
        sig = template_signature(q)
        if sig is None:
            return
        final = max(int(stats[-1]["rows_out"]), 1)
        peak = max(int(lv["rows_out"]) for lv in stats)
        measured = peak / final
        key = (*self._plan_version(), "auto", int(Global.wcoj_ratio),
               int(Global.wcoj_min_rows))
        self._plan_cache.put_aux("wcoj_measured", sig, key,
                                 round(measured, 2))
        # STRICTLY above the ratio: a prefix that stays at ~final rows
        # measures exactly 1.0, and a forced wcoj_ratio of 1 must not
        # demote the shapes wcoj is winning on
        if measured > max(float(Global.wcoj_ratio), 1.0):
            self._plan_cache.put_aux("strategy", sig, key, "walk")
            self._m_join_demoted.inc()
            disown_level_programs(sig)
            note_feedback("strategy", "demote_walk")
            tr = getattr(q, "trace", None)
            if tr is not None:
                tr.event("proxy.route", route="wcoj", demoted="walk",
                         why=f"prefix blowup {measured:.1f} > wcoj_ratio")
            log_info(f"wcoj auto-routing: template demoted to the walk "
                     f"(measured prefix blowup {measured:.1f}x > "
                     f"wcoj_ratio {Global.wcoj_ratio} — wcoj did not keep "
                     "intermediates near the fragment)")

    def wcoj(self):
        """Lazily-built WCOJ executor over the host partition (its sorted
        edge tables are cached per store version, so dynamic inserts and
        stream commits self-invalidate like the plan cache)."""
        if self._wcoj is None:  # unguarded: double-checked fast path — an atomic reference read; construction is serialized below
            with self._batcher_init_lock:
                if self._wcoj is None:
                    from wukong_tpu.join.wcoj import WCOJExecutor

                    self._wcoj = WCOJExecutor(
                        self.g, self.str_server,
                        stats=getattr(self.planner, "stats", None))
        return self._wcoj  # unguarded: write-once reference, non-None past init

    def wcoj_dist(self):
        """Lazily-built DISTRIBUTED WCOJ executor over the sharded
        store's host partitions: hash-partitions the first eliminated
        variable and fans the per-partition joins out on the heavy lane
        (join/dist.py), so a cyclic query on a sharded store no longer
        funnels through one engine. The pool resolves lazily — slices run
        inline until the host engine pool exists."""
        if self._wcoj_dist is None:  # unguarded: double-checked fast path, as wcoj()
            with self._batcher_init_lock:
                if self._wcoj_dist is None:
                    from wukong_tpu.join.dist import DistributedWCOJExecutor

                    self._wcoj_dist = DistributedWCOJExecutor(
                        self.dist.sstore.stores, self.str_server,
                        stats=getattr(self.planner, "stats", None),
                        pool=lambda: self._pool)
        return self._wcoj_dist  # unguarded: write-once reference, non-None past init

    # ------------------------------------------------------------------
    # heavy-lane routing (runtime/batcher.py heavy path)
    # ------------------------------------------------------------------
    def classify_lane(self, q: SPARQLQuery) -> str:
        """Plan-time light/heavy routing: index-origin starts are heavy
        (wide-table scans — the Wukong+G CPU-vs-GPU split); other shapes
        are heavy when the optimizer's ``estimate_chain`` peak reaches
        ``heavy_rows_threshold``. Memoized per template signature + store
        version through the plan cache, so the estimate walk runs once per
        template, not per query."""
        if getattr(q, "_knn_wide", False):
            # a wide knn scan is index-origin-shaped work: a full-store
            # pass, slice-range split across the pool (the PR 8 split)
            return "heavy"
        try:
            if q.start_from_index():
                return "heavy"
        except WukongError:
            return "light"
        if self.planner is None or not Global.enable_planner:
            return "light"
        sig = template_signature(q)
        if sig is None:
            return "light"  # recursive shapes: unestimated, route light
        pats = list(q.pattern_group.patterns)

        threshold = max(int(Global.heavy_rows_threshold), 1)

        def compute() -> str:
            try:
                ests = self.planner.estimate_chain(pats)
            except Exception:
                ests = None
            return "heavy" if ests and max(ests) >= threshold else "light"

        # the threshold is runtime-mutable: it joins the memo key so a
        # knob change takes effect immediately instead of serving stale
        # decisions until the next store-version bump
        return self._plan_cache.aux(
            "lane", sig, (*self._plan_version(), threshold), compute)

    def heavy_index_batch(self, q: SPARQLQuery) -> int:
        """Plan-cache-backed device slice count for an index-origin query:
        ``suggest_index_batch`` memoized on template signature + store
        version and capped by ``heavy_batch_max`` (the emulator's old
        per-query-object ``_heavy_b`` hack, now a shared plan fact)."""
        if self.tpu is None:
            return 1
        cap = max(int(Global.heavy_batch_max), 1)
        sig = template_signature(q)
        # cap in the memo key: heavy_batch_max is runtime-mutable (e.g.
        # shrunk after a device OOM) and must apply to already-seen
        # templates immediately
        return int(self._plan_cache.aux(
            "heavy_b", sig, (*self._plan_version(), cap),
            lambda: max(min(self.tpu.suggest_index_batch(q, cap=cap), cap),
                        1)))

    # ------------------------------------------------------------------
    # serving-path micro-batching (runtime/batcher.py)
    # ------------------------------------------------------------------
    def batcher(self) -> "QueryBatcher":
        """Lazily-started request coalescer. Groups ride the engine pool's
        batch lane when the pool is running, else they run inline on the
        batcher's flusher thread."""
        if self._batcher is None:  # unguarded: double-checked fast path — an atomic reference read; construction is serialized below
            with self._batcher_init_lock:  # concurrent first dispatches
                if self._batcher is None:  # must share ONE coalescer
                    cpu = self.cpu or (self.tpu.cpu
                                       if self.tpu is not None else None)
                    self._batcher = QueryBatcher(
                        cpu, self.tpu, pool=lambda: self._pool,
                        suggest_heavy_b=self.heavy_index_batch)
        return self._batcher  # unguarded: write-once reference, non-None past init

    @staticmethod
    def _note_route(q: SPARQLQuery, route: str) -> None:
        """Traced: one ``proxy.route`` event naming the route that answered
        (``wcoj``, ``template``, ``walk`` or ``dist``: the sharded engine's
        chain), what the plan-time choices
        were and, for a template program, ``why``: which half of the rule
        sent the reply to it (``small_classes`` or ``estimate``; ``knob``
        where ``template_device`` forced it). A demotion decided on this
        reply adds an event of its own with the reason."""
        tr = getattr(q, "trace", None)
        if tr is None:
            return
        attrs = {}
        if route == "template":
            from wukong_tpu.engine.template_compile import route_why

            attrs["why"] = route_why(
                getattr(q, "_template_est_rows", None),
                getattr(q, "_template_plan_caps", None)) or "knob"
        tr.event("proxy.route", route=route,
                 strategy=getattr(q, "join_strategy", "walk"),
                 template_route=getattr(q, "template_route", "host"),
                 **attrs)

    def _serve_execute(self, q: SPARQLQuery, eng,
                       pinned: bool = False) -> SPARQLQuery:
        """One serving-path dispatch: with ``enable_batching`` on,
        compatible queries coalesce into fused device dispatches; the
        default (off) and every bypass go straight to the engine — the
        single allowlisted direct-dispatch site for interactive queries.
        ``pinned`` (an explicit device= request) always bypasses: the
        batcher picks its own engine, which would silently override the
        caller's pin. A query the planner routed ``wcoj`` executes on the
        tensor-join engine first — any join-phase failure (unsupported
        residue, injected ``join.materialize`` fault, a bug) degrades to
        the walk below with the query untouched, never to an error.

        With ``enable_result_cache`` on (wukong_tpu/serve/), the dispatch
        is fronted by the version-keyed result cache: a hit installs the
        cached reply and skips execution entirely; a miss may elect this
        thread the key's request-collapsing leader, whose settlement (in
        the ``finally``) fills the cache and wakes the followers —
        whichever execution path below produced the reply."""
        from wukong_tpu.runtime import faults

        # the serving-boundary fault site: SLO-plane chaos scenarios
        # (Emulator.run_tenants) inject client-visible failures here so
        # per-tenant error budgets burn through the real reply path —
        # BEFORE the cache probe, so cached traffic burns budgets too
        faults.site("proxy.serve")
        lease = None
        if Global.enable_result_cache:
            from wukong_tpu.serve import get_serve

            served, lease = get_serve().cache.acquire(q)
            if served:
                return q
        try:
            if getattr(q, "join_strategy", "walk") == "wcoj" and not pinned:
                try:
                    # a sharded store routes the DISTRIBUTED join (heavy-
                    # lane fan-out over the partitions); any failure on
                    # either executor degrades to the matching walk below
                    if eng is self.dist and self.dist is not None:
                        self.wcoj_dist().try_execute(q)
                    else:
                        self.wcoj().try_execute(q)
                    self._note_route(q, "wcoj")
                    self._own_join_programs(q)
                    self._record_wcoj_feedback(q)
                    self._record_route_feedback(q)
                    return q
                except Exception as e:
                    reason = (e.code.name if isinstance(e, WukongError)
                              else type(e).__name__)
                    self._m_join_fallback.labels(reason=reason).inc()
                    tr = getattr(q, "trace", None)
                    if tr is not None:
                        tr.event("join.fallback", reason=reason)
                    log_info(f"wcoj degraded to the walk ({reason})")
            if getattr(q, "template_route", "host") == "device" \
                    and not pinned and eng is not self.dist \
                    and getattr(q, "knn", None) is None:
                # whole-plan compiled execution: one fused XLA dispatch
                # serves the query byte-identically, or the plan shape
                # is refused (False) and the walk below owns it; any
                # compile/dispatch FAILURE latches a per-template
                # demotion so same-template queries stop re-paying the
                # failed device attempt until a store mutation re-arms
                try:
                    if self.template_engine().try_execute(q):
                        self._note_route(q, "template")
                        self._record_template_feedback(q)
                        return q
                except Exception as e:
                    from wukong_tpu.engine.template_compile import \
                        latch_demotion

                    reason = (e.code.name if isinstance(e, WukongError)
                              else type(e).__name__)
                    latch_demotion(
                        self._template_family(getattr(q, "_tsig", None)),
                        reason, getattr(self.g, "version", 0))
                    self._m_template_fallback.labels(reason=reason).inc()
                    tr = getattr(q, "trace", None)
                    if tr is not None:
                        tr.event("template.fallback", reason=reason)
                    log_info(f"compiled template degraded to the walk "
                             f"({reason})")
            if Global.enable_batching and not pinned and eng is not None \
                    and eng is not self.dist \
                    and getattr(q, "knn", None) is None:
                # knn queries bypass the coalescer: their scan dispatch
                # is the batch (one fused matmul over the whole store)
                pend = self.batcher().offer(q)
                if pend is not None:
                    timeout = _batch_wait_timeout(q)
                    try:
                        pend.wait(timeout)
                    except TimeoutError:
                        # a wedged batcher must not hang the serving
                        # thread forever (the stream lane bounds its wait
                        # the same way) — surface the failure instead
                        log_error(f"batched dispatch not settled in "
                                  f"{timeout:.0f}s; batcher wedged?")
                        raise
                    return q
            if getattr(q, "knn", None) is not None:
                self._maybe_presolve_knn(q)
            eng.execute(q)  # batcher bypass: direct dispatch
            self._note_route(q, "dist" if eng is self.dist else "walk")
            self._record_knn_feedback(q)
            return q
        finally:
            if lease is not None:
                # leader settlement: fill on SUCCESS+admission, and wake
                # the followers either way (a failed leader must never
                # strand its collapsed waiters)
                lease.settle(q)

    def serve_query(self, text: str, blind: bool | None = None,
                    device: str | None = None,
                    tenant: str = "default") -> SPARQLQuery:
        """The lean serving entry (no repeats, no result printing): parse
        (cached) -> plan (cached) -> batched or direct execution, with the
        same shape/capacity fallbacks as run_single_query. This is the
        path live traffic takes; run_single_query is the console surface.
        ``tenant`` is the caller's identity — stamped on the query, the
        trace, and every reply-side metric (bounded to ``max_tenants``
        label values), and fed to the SLO tracker at reply.

        With ``enable_result_cache`` on, a repeated text whose key is
        resident at the current store version serves on the zero-parse
        fast path: the text resolves straight to its cache key (learned
        at fill time), skipping parse + plan entirely — the reply-side
        accounting (tenant admission, SLO, reuse observatory, the
        ``proxy.serve`` fault site) still runs in full."""
        if Global.enable_result_cache and device is None \
                and not Global.enable_tracing:
            q = self._serve_fast_hit(text, blind, tenant)
            if q is not None:
                return q
        trace = maybe_start_trace(kind="query", text=text)
        t0_us = get_usec()
        ten = self._admit(tenant)
        if trace is not None:
            trace.tenant = ten

        adm_d = None

        def prepare():
            qq = self._prepare(text, trace, blind, None, ten)
            if adm_d is not None:
                adm_d.apply(qq)
            return qq

        try:
            adm_d = self._consult_admission(ten)
            with activate(trace):
                q, _us = self._run_repeats(prepare, 1, device, trace)
        except Exception as e:
            code = e.code if isinstance(e, WukongError) else "ERROR"
            self._m_queries.labels(
                status=code.name if isinstance(code, ErrorCode)
                else str(code), tenant=ten).inc()
            if trace is not None:
                self.recorder.on_complete(trace, code)
            self._observe_slo(ten, get_usec() - t0_us, ok=False,
                              status=code, trace=trace)
            raise
        status = q.result.status_code
        self._m_queries.labels(status=status.name, tenant=ten).inc()
        # the reply-side accounting that does not read the trace runs while
        # the trace is open, so its span closes before the recorder has it
        with span(trace, "proxy.reply"):
            self._note_admission_reply(ten, q)
            self._observe_reuse(q, ten, text)
        if trace is not None:
            # finishes the trace: nothing writes to it from here on
            self.recorder.on_complete(trace, status)
            self._attribute(trace, q, text)
        # SLO accounting after the trace is finished/recorded (burn
        # dumps serialize a completed trace)
        self._observe_slo(ten, get_usec() - t0_us,
                          ok=status == ErrorCode.SUCCESS, status=status,
                          trace=trace)
        return q

    def _serve_fast_hit(self, text: str, blind, tenant: str):
        """The zero-parse cached-serving path: resolve the text to its
        cache key via the fill-time memo and, on a fresh-version hit,
        reply from the cached entry without parsing or planning. Returns
        None on any miss — the caller falls through to the full path
        (which probes the same key again, with collapsing). Skipped
        under tracing (a traced reply keeps its parse/plan spans) and
        for pinned-device requests."""
        from wukong_tpu.serve import get_serve

        eff_blind = Global.silent if blind is None else bool(blind)
        rc = get_serve().cache
        found = rc.fast_probe(text, eff_blind,
                              int(getattr(self.g, "version", 0)))
        if found is None:
            return None
        key, ent = found
        t0_us = get_usec()
        ten = self._admit(tenant)
        try:
            from wukong_tpu.runtime import faults

            # cached hits consume no engine capacity: only the q/s +
            # in-flight quotas apply (cached=True skips the ladder)
            self._consult_admission(ten, cached=True)
            # chaos parity: cached traffic crosses the same serving
            # boundary (and burns the same SLO budgets) as executed
            # traffic
            faults.site("proxy.serve")
        except Exception as e:
            code = e.code if isinstance(e, WukongError) else "ERROR"
            self._m_queries.labels(
                status=code.name if isinstance(code, ErrorCode)
                else str(code), tenant=ten).inc()
            self._observe_slo(ten, get_usec() - t0_us, ok=False,
                              status=code, trace=None)
            raise
        q = rc.build_reply(key, ent)
        q.tenant = ten
        self._m_queries.labels(status="SUCCESS", tenant=ten).inc()
        self._observe_slo(ten, get_usec() - t0_us, ok=True,
                          status=ErrorCode.SUCCESS, trace=None)
        self._observe_reuse(q, ten, text)
        return q

    # ------------------------------------------------------------------
    # introspection (obs/profile.py): EXPLAIN / EXPLAIN ANALYZE + the
    # latency-attribution regression sentinel
    # ------------------------------------------------------------------
    def explain_query(self, text: str, analyze: bool = False,
                      device: str | None = None,
                      plan_text: str | None = None) -> dict:
        """EXPLAIN: parse + plan and render the plan tree with the
        planner's per-step cost/cardinality estimates. EXPLAIN ANALYZE:
        additionally execute under a forced trace and join actual per-step
        rows/wall-time/fetches against the estimates, plus the end-to-end
        latency decomposition. Returns structured JSON; ``rendered`` holds
        the table (console verbs ``explain`` / ``analyze``)."""
        from wukong_tpu.obs.profile import explain_query

        return explain_query(self, text, analyze=analyze, device=device,
                             plan_text=plan_text)

    def _attribute(self, trace, q: SPARQLQuery, text: str) -> None:
        """Reply-side latency attribution: fold the finished trace into
        its template's rolling baseline; the sentinel auto-dumps the trace
        on a regression. One knob check when attribution is off."""
        if not Global.enable_attribution:
            return
        from wukong_tpu.obs.profile import get_attributor, template_key

        verdict = get_attributor().observe(
            trace, template_key(q, text),
            example=" ".join(text.split())[:120])
        if verdict is not None:
            log_error(
                f"latency regression ({verdict['reason']}): template "
                f"{verdict['template']} {verdict['total_us']:,}us vs "
                f"baseline p95 {verdict['baseline_p95_us']:,}us, worst "
                f"component {verdict['component']} "
                f"{verdict['share_drift_pts']:+.1f}pts — trace "
                f"{trace.trace_id} dumped")

    def print_result(self, q: SPARQLQuery, rows: int) -> None:
        """Render rows through the string server (proxy.hpp:247-294)."""
        for i in range(rows):
            vals = []
            for v in q.result.required_vars:
                col = q.result.v2c_map.get(v)
                if col is None:
                    vals.append("?")
                    continue
                vid = int(q.result.table[i, col])
                vals.append(self.str_server.id2str(vid)
                            if self.str_server.exist_id(vid) else str(vid))
            log_info(f"  {i + 1}: " + "\t".join(vals))

    # ------------------------------------------------------------------
    def fill_template(self, tmpl: SPARQLTemplate) -> None:
        """Collect candidate constants per %placeholder by running the
        type/predicate index (proxy.hpp:69-129)."""
        g = self._whole_graph()
        tmpl.candidates = []
        for tid, (pi, fld) in zip(tmpl.ptypes, tmpl.pos):
            if tid == "fromPredicate":
                # %<fromPredicate> (proxy.hpp:76-99): candidates are the
                # pattern's predicate index — subject slots draw its
                # subjects (IN side), object slots its objects (OUT side)
                pat = tmpl.query.pattern_group.patterns[pi]
                d = IN if fld == "subject" else OUT
                cands = np.asarray(g.get_index(pat.predicate, d))
                if len(cands) == 0:
                    raise WukongError(
                        ErrorCode.UNKNOWN_SUB,
                        f"no candidates for predicate {pat.predicate}")
                tmpl.candidates.append(cands)
                continue
            if not is_tpid(tid):
                raise WukongError(ErrorCode.SYNTAX_ERROR,
                                  f"placeholder type {tid} is not an index id")
            cands = np.asarray(g.get_index(tid, IN))
            if len(cands) == 0:
                raise WukongError(ErrorCode.UNKNOWN_SUB,
                                  f"no instances for placeholder type {tid}")
            tmpl.candidates.append(cands)

    # ------------------------------------------------------------------
    def dynamic_load_data(self, dirname: str, check_dup: bool = False) -> None:
        """`load -d <dir> [-c]` (proxy.hpp:548 -> RDFEngine -> DynamicLoader).

        -c (check_dup) opts into duplicate dropping, like the reference's
        dedup-on-insert option. Inserts reach the host store AND every
        distributed shard (their version bump restages device caches).
        """
        from wukong_tpu.loader.hdfs import resolve_dataset_dir
        from wukong_tpu.store.dynamic import load_dir_into

        dirname = resolve_dataset_dir(dirname)  # hdfs:// paths stage locally
        n = load_dir_into(self._insert_targets(), dirname, dedup=check_dup)
        if self.dist is not None and self.dist.sstore.check_version():
            # compiled chains bake per-segment probe/depth bounds
            self._fn_cache_clear()
        # plan recipes are version-keyed (stale ones can never apply), but
        # an insert obsoletes every cached plan's cost basis — free them
        self._plan_cache.clear()
        log_info(f"dynamic load: {n:,} new subject-side edges from {dirname}")

    # ------------------------------------------------------------------
    # streaming verbs (Wukong+S surface; wukong_tpu/stream/)
    # ------------------------------------------------------------------
    def stream_context(self, use_pool: bool = False):
        """Lazily-assembled StreamContext over this proxy's store(s).

        Inserts reach the host store and every distributed shard (like
        `load -d`); delta evaluation runs on the host partition. With
        use_pool the delta queries ride the engine pool's stream lane,
        interleaving with one-shot queries. The flag only matters on first
        call — the context is built once.
        """
        if self._stream is None:
            from wukong_tpu.stream import StreamContext

            self._stream = StreamContext(
                self._insert_targets(), self.str_server,
                pool=self.engine_pool() if use_pool else None,
                monitor=self.monitor)
        return self._stream

    def _insert_targets(self) -> list:
        """Every store online inserts must reach: the host partition first,
        then the distributed shards (the `load -d` fan-out), then any
        shard replicas — a mirror that missed a write would serve stale
        data on failover."""
        targets = [self.g]
        if self.dist is not None:
            targets += [g for g in self.dist.sstore.stores if g is not self.g]
            targets += self.dist.sstore.replica_stores()
        return targets

    def _checkpoint_targets(self) -> list:
        """The checkpointed primaries (no replicas: they are re-cloned
        from the restored primaries, not persisted twice)."""
        targets = [self.g]
        if self.dist is not None:
            targets += [g for g in self.dist.sstore.stores if g is not self.g]
        return targets

    # ------------------------------------------------------------------
    # fault tolerance (runtime/recovery.py)
    # ------------------------------------------------------------------
    def recovery(self):
        """Lazily-assembled RecoveryManager over this proxy's stores,
        stream context, and sharded store."""
        if self._recovery is None:  # unguarded: double-checked fast path — an atomic reference read; construction is serialized below
            with self._recovery_init_lock:
                if self._recovery is None:
                    from wukong_tpu.runtime.recovery import RecoveryManager

                    self._recovery = RecoveryManager(
                        self._checkpoint_targets,  # live view across heals
                        stream=self.stream_context(),
                        sstore=getattr(self.dist, "sstore", None),
                        pool=lambda: self._pool,
                        on_change=self._on_store_change)
        return self._recovery  # unguarded: write-once reference, non-None past init

    def _on_store_change(self) -> None:
        """Restore/rebuild invalidation: exactly the dynamic-insert
        contract — compiled chains and cached plans must re-derive."""
        if self.dist is not None and self.dist.sstore.check_version():
            self._fn_cache_clear()
        self._plan_cache.clear()

    def checkpoint(self) -> str:
        """Console `checkpoint` verb: write one atomic checkpoint bundle
        (partitions + stream registry) and truncate the covered WAL."""
        return self.recovery().checkpoint()

    def recover(self) -> dict:
        """Console `recover` verb: restore the newest checkpoint and
        replay the WAL tail (boot-time crash recovery)."""
        return self.recovery().recover()

    def stream_register(self, text: str, window=None, base_triples=None,
                        callback=None) -> int:
        """Register a standing SPARQL query; returns its stream qid.
        ``callback`` is the push-mode sink: invoked per committed
        ResultDelta next to the pull poll() surface (exceptions contained
        and surfaced as the stream-callback-error metric)."""
        return self.stream_context().register(text, window=window,
                                              base_triples=base_triples,
                                              callback=callback)

    def stream_unregister(self, qid: int) -> None:
        self.stream_context().unregister(qid)

    def stream_poll(self, qid: int, since_epoch: int = -1) -> list:
        """Read a standing query's append-only result deltas."""
        return self.stream_context().poll(qid, since_epoch)

    def stream_prune(self, qid: int, upto_epoch: int) -> int:
        """Free a standing query's consumed sink history behind a cursor."""
        return self.stream_context().prune(qid, upto_epoch)

    def stream_feed(self, triples, ts=None):
        """Commit one triple batch as the next stream epoch; standing
        queries are incrementally evaluated before this returns. Device
        caches restage lazily via the store version bump, and compiled
        distributed chains are re-specialized like dynamic_load_data."""
        rec = self.stream_context().feed(triples, ts=ts)
        if self.dist is not None and self.dist.sstore.check_version():
            self._fn_cache_clear()
        self._plan_cache.clear()  # stream commit: same contract as load -d
        return rec

    def _fn_cache_clear(self) -> None:
        cache = getattr(self.dist, "_fn_cache", None)
        if cache is not None:
            cache.clear()

    def gstore_check(self, index_check: bool = True, normal_check: bool = True) -> int:
        from wukong_tpu.store.checker import check_partition

        errors = check_partition(self.g, index_check, normal_check)
        for e in errors[:20]:
            log_error(f"gsck: {e}")
        log_info(f"gsck: {'PASS' if not errors else f'{len(errors)} violations'}")
        return len(errors)
