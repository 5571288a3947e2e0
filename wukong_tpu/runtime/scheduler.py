"""Host engine pool: per-engine run queues with work stealing.

The reference runs N engine threads per server, each with a private queue,
priority handling for fork-join sub-queries, work stealing from neighbors
("work obliger", pair or ring patterns per Global::stealing_pattern), and an
adaptive busy-poll/snooze loop (core/engine/engine.hpp:78-219). This module
reproduces that runtime structure for the host-side engines: inter-query
parallelism across a thread pool (numpy/JAX release the GIL on the heavy ops),
deque-based queues stolen from the back, and the same pair/ring neighbor
selection.

Beyond the reference: a shared low-priority *stream lane*
(``submit(q, lane="stream")``) for standing-query delta work
(stream/continuous.py). Engines drain it only after their own queue and
their steal targets are empty, so interactive one-shot queries always go
first and continuous evaluation soaks up the idle capacity — under the same
per-query deadline/budget machinery (expired stream items are shed from the
queue exactly like interactive ones). Stream-lane completions are reserved
for wait() and never returned by poll(), so an open-loop poll() consumer
(the emulator) can share the pool with the stream context without racing
its completions; the one-consumer discipline (wait XOR poll) still applies
among default-lane users.
"""

from __future__ import annotations

import collections
import threading
import time

from wukong_tpu.analysis.lockdep import make_lock
from wukong_tpu.config import Global
from wukong_tpu.obs.metrics import get_registry
from wukong_tpu.obs.slo import maybe_note_queue_delay, maybe_note_shed
from wukong_tpu.utils.timer import get_usec

# pool-level observability: submissions/sheds/respawns push counters; queue
# depth is a pull gauge registered per pool (the hot loop never updates it)
_M_SUBMITTED = get_registry().counter(
    "wukong_pool_submitted_total", "Queries submitted to the engine pool",
    labels=("lane",))
_M_SHED = get_registry().counter(
    "wukong_pool_shed_total",
    "Queries shed from the queue with an expired deadline")
_M_RESPAWNS = get_registry().counter(
    "wukong_pool_engine_respawns_total", "Engine-thread crash respawns")

# one registry-level queue-depth gauge summed over every LIVE pool (weakly
# referenced: a stopped, dropped pool reads as gone, never as stale depth)
import weakref  # noqa: E402

_POOLS: "weakref.WeakSet" = weakref.WeakSet()


def _queue_depth() -> int:
    return sum(sum(len(dq) for dq in p.queues) + len(p.stream_queue)
               + len(p.batch_queue) + len(p.heavy_queue)
               + len(p.heavy_slices) + len(p.rebuild_queue)
               + (len(f) if (f := p._fair) is not None else 0)
               for p in list(_POOLS))


get_registry().gauge(
    "wukong_pool_queue_depth",
    "Queries waiting in pool queues (incl. stream/batch/heavy/rebuild lanes)"
).set_function(_queue_depth)


def _lane_depth_series() -> dict:
    """Per-lane queue depth across every live pool — the /top lane view's
    pull source (depth by lane, not just the total)."""
    acc = {"default": 0, "batch": 0, "heavy": 0, "stream": 0, "rebuild": 0}
    for p in list(_POOLS):
        acc["default"] += sum(len(dq) for dq in p.queues)
        acc["batch"] += len(p.batch_queue)
        acc["heavy"] += len(p.heavy_queue) + len(p.heavy_slices)
        acc["stream"] += len(p.stream_queue)
        acc["rebuild"] += len(p.rebuild_queue)
        f = p._fair  # the DRR sub-lane exists only once admission armed
        if f is not None:
            acc["fair"] = acc.get("fair", 0) + len(f)
    return {(k,): v for k, v in acc.items()}


get_registry().gauge(
    "wukong_pool_lane_depth", "Queries waiting per pool lane",
    labels=("lane",)).set_function(_lane_depth_series)


def _pool_utilization() -> float:
    """Busy fraction of live engines across every live pool — an
    ADMISSION_INPUTS signal (obs/slo.py) for item 4's admission control."""
    busy = alive = 0
    for p in list(_POOLS):
        for t in range(p.n):
            if not p._dead[t]:  # unguarded: report-only snapshot, like health()
                alive += 1
                if p._busy_since[t]:
                    busy += 1
    return busy / alive if alive else 0.0


get_registry().gauge(
    "wukong_pool_utilization",
    "Busy fraction of live pool engines").set_function(_pool_utilization)


def dead_engine_count() -> int:
    """Engines declared dead (respawn budget exhausted) across every live
    pool — a /healthz readiness input (obs/httpd.py health_report)."""
    return sum(1 for p in list(_POOLS) for t in range(p.n)
               if p._dead[t])  # unguarded: report-only snapshot, like health()


def _live_engine_count() -> int:
    """Engines NOT declared dead across every live pool — the admission
    plane's derived in-flight capacity base (runtime/admission.py
    ``_inflight_cap``: structural config, not a telemetry signal)."""
    return sum(1 for p in list(_POOLS) for t in range(p.n)
               if not p._dead[t])  # unguarded: report-only snapshot, like health()


class EnginePool:
    # engine-thread crashes (outside the per-query try) respawn up to this
    # many times per tid; past it the engine is declared dead, its queue is
    # redistributed, and routing skips it. The reference has NO failure
    # handling at all (wukong.cpp:252 TODO; a dead pthread strands its ring).
    MAX_RESPAWNS = 3

    # idle relax bounds (ROADMAP follow-up i): the reference busy-polls
    # 10 -> 80us (engine.hpp:120-150), which keeps every idle engine waking
    # 12.5k times/s — on this 2-core container a 4-engine idle pool burned
    # a full core (each timed-semaphore wake costs ~170-500us of CPU here)
    # and doubled co-located serve_query p50 (617us -> 1,230us). The
    # semaphore acquire IS the wake-on-submit event (a submit releases a
    # permit and wakes one sleeper immediately), so a deep cap costs
    # nothing in pickup latency on the submit path; it only bounds the
    # poll cadence for work that arrives via stealing races (an item
    # stranded in a busy non-neighbor's queue).
    IDLE_SNOOZE_MIN_US = 10
    IDLE_SNOOZE_MAX_US = 20000

    def __init__(self, num_engines: int | None = None, make_engine=None):
        """make_engine(tid) -> object with .execute(query) (one per thread,
        mirroring per-thread SPARQLEngine instances)."""
        self.n = num_engines or Global.num_engines
        # per-engine run queues, each guarded by the matching element of
        # `locks` (declared in analysis/guarded.py GUARDED_BY_REGISTRY —
        # per-element guards have no single annotation line)
        self.queues = [collections.deque() for _ in range(self.n)]
        self.locks = [make_lock("pool.queue") for _ in range(self.n)]
        self._make_engine = make_engine
        self._threads: list[threading.Thread | None] = [None] * self.n  # lock-free: start/stop/respawn are operator-or-dying-thread only
        self._stop = threading.Event()
        self._pending = threading.Semaphore(0)
        self._results: dict[int, object] = {}  # guarded by: _results_lock
        self._results_lock = make_lock("pool.results")
        self._next_qid = 0  # guarded by: _results_lock
        self._done = {}  # guarded by: _results_lock
        # finished qids (poll() feed); append-before-set protocol relies
        # on CPython deque append/popleft atomicity
        self._completed = collections.deque()  # lock-free: atomic deque ops, see _fail()
        self._respawns = [0] * self.n  # lock-free: per-tid slot, single writer (the engine thread / its respawner)
        self._dead = [False] * self.n  # guarded by: _route_lock
        # serializes dead-state transitions against routing: submit's
        # dead-check + enqueue must not interleave with declare-dead's
        # drain, or a query lands in a queue nobody will ever pop
        self._route_lock = make_lock("pool.route")
        self._busy_since = [0] * self.n  # lock-free: per-tid slot, single writer; health() reads a snapshot
        self._inflight: list = [None] * self.n  # lock-free: per-tid slot, single writer (engine thread; death handler runs after it stopped)
        # stream lane: shared low-priority queue for standing-query work
        self.stream_queue = collections.deque()  # guarded by: _stream_lock
        self._stream_lock = make_lock("pool.stream")
        # batch lane: coalesced serving-path groups (runtime/batcher.py).
        # A group is ONE item — work stealing cannot split it — popped
        # right after the engine's own queue (batched queries are
        # interactive traffic, unlike the stream lane's background work).
        # Groups deliver results through their members' futures, so items
        # here are fire-and-forget for the pool's result bookkeeping.
        self.batch_queue = collections.deque()  # guarded by: _batch_lock
        self._batch_lock = make_lock("pool.batch")
        # heavy lane: fused index-origin dispatches + their split slices
        # (runtime/batcher.py HeavyGroup/_HeavySlice), same fire-and-forget
        # contract as the batch lane but WEIGHTED: at most
        # ceil(n * heavy_lane_pct / 100) engines (min 1) execute heavy
        # items concurrently, so a heavy flood can never occupy every
        # engine — interactive light traffic always keeps capacity.
        self.heavy_queue = collections.deque()  # guarded by: _heavy_lock
        # split-slice continuations in their own deque: they are
        # cap-exempt (their group already holds a slot) and exist only
        # during an active split, so the pop path stays O(1) instead of
        # scanning the group queue for them
        self.heavy_slices = collections.deque()  # guarded by: _heavy_lock
        self._heavy_lock = make_lock("pool.heavy")
        self._heavy_inflight = 0  # guarded by: _heavy_lock
        # rebuild lane: background shard-rebuild jobs (runtime/recovery.py
        # RebuildJob), drained only when every other lane is empty —
        # healing soaks idle capacity, never displaces serving traffic.
        # Items share the batch lane's fire-and-forget contract
        # (run(engine) + fail_all(exc)).
        self.rebuild_queue = collections.deque()  # guarded by: _rebuild_lock
        self._rebuild_lock = make_lock("pool.rebuild")
        # stream-lane qids are reserved for wait(): poll() skips them, so
        # an open-loop poll() consumer (the emulator) sharing this pool
        # can't steal the stream context's completions
        self._stream_qids: set = set()  # guarded by: _results_lock
        # weighted-fair sub-lane (runtime/admission.py FairQueue): created
        # lazily on the first admission-armed submission so the off-knob
        # pop path pays one attribute read, nothing else
        self._fair = None  # guarded by: _route_lock
        # heavy-lane slots currently held per tenant — the per-tenant
        # weighted cap (admission heavy_cap_for) counts against this
        self._heavy_by_tenant: dict = {}  # guarded by: _heavy_lock
        _POOLS.add(self)  # feeds the wukong_pool_queue_depth gauge

    # ------------------------------------------------------------------
    def start(self) -> None:
        for tid in range(self.n):
            self._spawn(tid)

    def _spawn(self, tid: int) -> None:
        t = threading.Thread(target=self._run_engine, args=(tid,),
                             daemon=True, name=f"engine-{tid}")
        t.start()
        self._threads[tid] = t

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            if t is not None:
                self._pending.release()
        for t in self._threads:
            if t is not None:
                t.join(timeout=5)
        self._threads = [None] * self.n

    # ------------------------------------------------------------------
    # failure detection / recovery (beyond the reference: its engine
    # pthreads have no supervision — wukong.cpp:245-252)
    # ------------------------------------------------------------------
    def health(self) -> dict:
        """Per-engine liveness snapshot: alive flag, respawn count, and how
        long the current query has been executing (0 = idle). A stuck
        engine shows a growing busy_us — report-only (Python threads cannot
        be preempted safely); dead engines are routed around."""
        now = get_usec()
        return {
            tid: {"alive": not self._dead[tid],  # unguarded: report-only snapshot; a stale bool here only ages the health report by one call
                  "respawns": self._respawns[tid],
                  "busy_us": (now - b) if (b := self._busy_since[tid]) else 0}
            for tid in range(self.n)}

    def _fail(self, qid: int, exc: Exception) -> None:
        """Deliver an error result, honoring the append-before-set protocol
        (one place: wait()/poll() race discipline lives here only)."""
        with self._results_lock:
            self._results[qid] = exc
            ev = self._done[qid]
        self._completed.append(qid)
        ev.set()

    @staticmethod
    def _stamp_enqueue(query, lane: str) -> None:
        """Queue-delay accounting for the overload signal bus (obs/slo.py):
        submit stamps the enqueue clock, the popping engine charges the
        per-lane delay EWMA. One knob check when accounting is off;
        ``__slots__`` items (split slices) skip silently."""
        if not Global.enable_tenant_accounting:
            return
        try:
            query._slo_enq_us = get_usec()
            query._slo_lane = lane
        except AttributeError:
            pass

    @staticmethod
    def _charge_queue_delay(query) -> None:
        enq = getattr(query, "_slo_enq_us", None)
        if enq is not None:
            query._slo_enq_us = None
            maybe_note_queue_delay(getattr(query, "_slo_lane", "default"),
                                   get_usec() - enq)

    @staticmethod
    def _end_queue_span(query, **attrs) -> None:
        """Close a traced query's pool.queue span. Every exit from the
        queue — popped by an engine, shed, or failed without ever being
        popped (dead pool, stranded redistribution) — must end it, or the
        open span keeps accruing time and swallows later trace events."""
        qs = getattr(query, "_obs_queue_span", None)
        if qs is not None:
            query.trace.end_span(qs, **attrs)
            query._obs_queue_span = None

    def _on_engine_death(self, tid: int, exc: BaseException) -> None:
        from wukong_tpu.utils.logger import log_error, log_warn

        # the in-flight query (if any) likely triggered the crash: fail it
        # rather than retry it into every engine, and never strand its waiter
        self._busy_since[tid] = 0
        item = self._inflight[tid]
        self._inflight[tid] = None
        if item is not None:
            qid, _q = item
            if qid is None:  # batch-lane group: settle its member futures
                self._heavy_done(_q)  # a heavy slot died with the thread
                fail = getattr(_q, "fail_all", None)
                if fail is not None:
                    fail(RuntimeError(
                        f"engine-{tid} crashed executing a fused batch: "
                        f"{exc!r}"))
            else:
                self._fail(qid, RuntimeError(
                    f"engine-{tid} crashed executing query {qid}: {exc!r}"))
        self._respawns[tid] += 1
        _M_RESPAWNS.inc()
        if self._respawns[tid] <= self.MAX_RESPAWNS and not self._stop.is_set():
            log_warn(f"engine-{tid} died ({exc!r}); respawning "
                     f"({self._respawns[tid]}/{self.MAX_RESPAWNS})")
            self._spawn(tid)  # its queue is intact; the new thread drains it
            return
        # crash loop: declare dead, push queued work to the neighbors so
        # nothing strands, and stop routing here (submit skips dead tids).
        # _route_lock makes the drain atomic against concurrent submits and
        # other deaths — nothing can enqueue into the drained queue after.
        log_error(f"engine-{tid} dead after {self._respawns[tid]} crashes; "
                  "redistributing its queue")
        with self._route_lock:
            self._dead[tid] = True
            with self.locks[tid]:
                stranded = list(self.queues[tid])
                self.queues[tid].clear()
            live = [t for t in range(self.n) if not self._dead[t]]
            for k, item in enumerate(stranded):
                if not live:  # whole pool dead: fail queries, don't hang
                    self._end_queue_span(item[1], dead_pool=True)
                    self._fail(item[0], RuntimeError("engine pool dead"))
                    continue
                dst = live[k % len(live)]
                with self.locks[dst]:
                    self.queues[dst].append(item)
                self._pending.release()
            if not live:  # nobody left to drain the stream lane either
                # ...starting with the fair sub-lane: pop until dry (the
                # DRR order is irrelevant now, every item fails the same)
                f = self._fair
                while f is not None:
                    it = f.pop()
                    if it is None:
                        break
                    self._end_queue_span(it[1], dead_pool=True)
                    self._fail(it[0], RuntimeError("engine pool dead"))
                with self._stream_lock:
                    stream_stranded = list(self.stream_queue)
                    self.stream_queue.clear()
                for item in stream_stranded:
                    self._end_queue_span(item[1], dead_pool=True)
                    self._fail(item[0], RuntimeError("engine pool dead"))
                # ...or the batch lane: settle fused groups' member futures
                with self._batch_lock:
                    batch_stranded = list(self.batch_queue)
                    self.batch_queue.clear()
                for _qid, group in batch_stranded:
                    fail = getattr(group, "fail_all", None)
                    if fail is not None:
                        fail(RuntimeError("engine pool dead"))
                # ...or the heavy lane: groups and split slices alike
                with self._heavy_lock:
                    heavy_stranded = (list(self.heavy_queue)
                                      + list(self.heavy_slices))
                    self.heavy_queue.clear()
                    self.heavy_slices.clear()
                for _qid, item2 in heavy_stranded:
                    fail = getattr(item2, "fail_all", None)
                    if fail is not None:
                        fail(RuntimeError("engine pool dead"))
                # ...or the rebuild lane: same fire-and-forget settlement
                with self._rebuild_lock:
                    rebuild_stranded = list(self.rebuild_queue)
                    self.rebuild_queue.clear()
                for _qid, job in rebuild_stranded:
                    fail = getattr(job, "fail_all", None)
                    if fail is not None:
                        fail(RuntimeError("engine pool dead"))

    # ------------------------------------------------------------------
    def submit(self, query, tid: int | None = None,
               lane: str | None = None) -> int:
        """Enqueue a query; returns a handle. tid routes like the reference's
        proxy dst engine choice (round-robin default, proxy.hpp:143-160).

        lane="stream" bypasses per-engine routing into the shared
        low-priority stream queue: any engine drains it, but only after its
        own queue and its steal targets are empty (standing-query work never
        displaces interactive queries).

        lane="batch" enqueues a coalesced FusedGroup (runtime/batcher.py)
        as ONE indivisible item; the group delivers results through its
        members' futures, so no pool-side result entry is created (returns
        -1). A dead pool fails the group immediately via fail_all.

        lane="heavy" enqueues a fused heavy dispatch (HeavyGroup) or one of
        its split slices with the batch lane's fire-and-forget contract,
        drained under the weighted heavy_lane_pct concurrency cap so heavy
        work never starves interactive traffic.

        lane="rebuild" enqueues a background shard-rebuild job
        (runtime/recovery.py RebuildJob) with the same fire-and-forget
        contract, drained only when every other lane is empty."""
        if lane in ("batch", "heavy", "rebuild"):
            _M_SUBMITTED.labels(lane=lane).inc()
            lock = {"batch": self._batch_lock, "heavy": self._heavy_lock,
                    "rebuild": self._rebuild_lock}[lane]
            if lane == "heavy" and getattr(query, "heavy_continuation",
                                           False):
                queue = self.heavy_slices  # unguarded: binds the deque reference only (immutable attr); mutated below under `lock`
            else:
                queue = {"batch": self.batch_queue,  # unguarded: reference binding only, as above
                         "heavy": self.heavy_queue,  # unguarded: reference binding only, as above
                         "rebuild": self.rebuild_queue}[lane]  # unguarded: reference binding only, as above
            self._stamp_enqueue(query, lane)
            with self._route_lock:
                if all(self._dead[k] for k in range(self.n)):
                    fail = getattr(query, "fail_all", None)
                    if fail is not None:
                        fail(RuntimeError("engine pool dead"))
                    return -1
                with lock:
                    queue.append((None, query))
            self._pending.release()
            return -1
        with self._results_lock:
            qid = self._next_qid
            self._next_qid += 1
            self._done[qid] = threading.Event()
        _M_SUBMITTED.labels(lane=lane or "default").inc()
        # traced queries get a queue span opened here and closed by the
        # engine thread that pops them (cross-thread end is supported)
        tr = getattr(query, "trace", None)
        if tr is not None:
            query._obs_queue_span = tr.start_span(
                "pool.queue", qid=qid, lane=lane or "default")
        self._stamp_enqueue(query, lane or "default")
        if lane == "stream":
            if Global.enable_admission and getattr(query, "owner_tenant",
                                                   None):
                # priority inheritance: a standing query's maintenance
                # work rides the fair sub-lane at its OWNER's weight
                # instead of the last-priority stream lane
                return self._submit_fair(qid, query, stream=True)
            with self._results_lock:
                self._stream_qids.add(qid)
            with self._route_lock:
                if all(self._dead[k] for k in range(self.n)):
                    self._end_queue_span(query, dead_pool=True)
                    self._fail(qid, RuntimeError("engine pool dead"))
                    return qid
                with self._stream_lock:
                    self.stream_queue.append((qid, query))
            self._pending.release()
            return qid
        if tid is None and Global.enable_admission:
            # default-lane traffic with no routing pin rides the DRR fair
            # sub-lane: per-tenant sub-queues drained by weight, so a
            # bulk flood cannot monopolize the interactive engines
            return self._submit_fair(qid, query)
        t = qid % self.n if tid is None else tid % self.n
        with self._route_lock:  # atomic dead-check + enqueue vs declare-dead
            if self._dead[t]:  # route around dead engines
                live = [k for k in range(self.n) if not self._dead[k]]
                if not live:
                    self._end_queue_span(query, dead_pool=True)
                    self._fail(qid, RuntimeError("engine pool dead"))
                    return qid
                t = live[qid % len(live)]
            with self.locks[t]:
                self.queues[t].append((qid, query))
        self._pending.release()
        return qid

    def _submit_fair(self, qid: int, query, stream: bool = False) -> int:
        """Enqueue into the weighted-fair sub-lane (admission armed).

        The tenant is the EFFECTIVE one (``owner_tenant`` wins — priority
        inheritance for standing-query maintenance) and the DRR weight is
        resolved HERE, by the caller, from the lock-free quota map:
        FairQueue never calls out under ``admission.queue``, keeping that
        lock a lockdep leaf."""
        from wukong_tpu.runtime.admission import (FairQueue,
                                                  effective_tenant,
                                                  get_admission)

        ten = effective_tenant(query)
        w = get_admission().weight(ten)
        if stream:
            with self._results_lock:
                self._stream_qids.add(qid)
        with self._route_lock:  # atomic dead-check + enqueue, as above
            if all(self._dead[k] for k in range(self.n)):
                self._end_queue_span(query, dead_pool=True)
                self._fail(qid, RuntimeError("engine pool dead"))
                return qid
            f = self._fair
            if f is None:
                f = self._fair = FairQueue()
            f.push(ten, (qid, query), weight=w)
        self._pending.release()
        return qid

    def wait(self, qid: int, timeout: float | None = None):
        """Returns the engine's result, or raises TimeoutError (the result
        stays claimable by a later wait — no stranded entries)."""
        # capture the event under the lock: the bare `self._done[qid]`
        # read raced concurrent dict mutation (found by the guarded-by
        # analysis gate when _done was annotated)
        with self._results_lock:
            ev = self._done[qid]
        if not ev.wait(timeout):
            raise TimeoutError(f"query {qid} still running")
        with self._results_lock:
            self._done.pop(qid, None)
            self._stream_qids.discard(qid)
            try:
                self._completed.remove(qid)
            except ValueError:
                pass
            return self._results.pop(qid, None)

    def poll(self) -> list:
        """Drain finished queries as (qid, result) pairs — the open-loop
        receive side (proxy.hpp tryrecv_reply analogue). A pool user should
        consume completions via EITHER wait() or poll(), not both."""
        out = []
        while True:
            try:
                qid = self._completed.popleft()
            except IndexError:
                break
            with self._results_lock:
                if qid not in self._done:  # already consumed via wait()
                    continue
                if qid in self._stream_qids:
                    # stream-lane completions belong to the stream
                    # context's wait() — leave them claimable
                    continue
                self._done.pop(qid)
                out.append((qid, self._results.pop(qid, None)))
        return out

    # ------------------------------------------------------------------
    def alive_count(self) -> int:
        """Engines not declared dead (the heavy split fan-out bound)."""
        return sum(1 for t in range(self.n) if not self._dead[t])  # unguarded: report-only snapshot, like health()

    def _heavy_cap(self) -> int:
        """Max engines concurrently executing heavy-lane items."""
        return max((self.n * max(int(Global.heavy_lane_pct), 0)) // 100, 1)

    def _heavy_done(self, query) -> None:
        """Release the weighted heavy slot an engine-loop pop took. Keyed
        on the item's lane tag: only slot-counted heavy pops incremented
        (cap-exempt slice continuations did not take one)."""
        if getattr(query, "lane", None) != "heavy" \
                or getattr(query, "heavy_continuation", False):
            return
        ten = getattr(query, "_adm_heavy_ten", None)
        with self._heavy_lock:
            self._heavy_inflight = max(self._heavy_inflight - 1, 0)
            if ten is not None:
                query._adm_heavy_ten = None
                left = self._heavy_by_tenant.get(ten, 1) - 1
                if left <= 0:
                    self._heavy_by_tenant.pop(ten, None)
                else:
                    self._heavy_by_tenant[ten] = left

    def _heavy_pick_locked(self) -> int:  # caller holds: _heavy_lock
        """Index of the first heavy-queue group whose tenant is under its
        weighted per-tenant slot share, or -1 when every queued tenant is
        at cap (caller holds ``_heavy_lock``). ``heavy_cap_for`` is a pure
        function of the lock-free quota map — no lock is taken under the
        heavy lock, so ``pool.heavy`` ordering is unchanged."""
        if not Global.enable_admission:
            return 0 if self.heavy_queue else -1
        from wukong_tpu.runtime.admission import get_admission

        adm = get_admission()
        cap = self._heavy_cap()
        for i, (_qid, g) in enumerate(self.heavy_queue):
            ten = getattr(g, "tenant", None)
            if ten is None:
                return i  # untagged groups predate admission: no cap
            if (self._heavy_by_tenant.get(ten, 0)
                    < adm.heavy_cap_for(ten, cap, self._heavy_by_tenant)):
                return i
        return -1

    # ------------------------------------------------------------------
    def _neighbors(self, tid: int) -> list[int]:
        """Stealing pattern (engine.hpp:186-207): 0=pair, 1=ring."""
        if self.n <= 1:
            return []
        if Global.stealing_pattern == 1:  # ring: next engine
            return [(tid + 1) % self.n]
        return [tid ^ 1] if (tid ^ 1) < self.n else []  # pair

    def _pop_work(self, tid: int):
        # own queue first (front)
        with self.locks[tid]:
            if self.queues[tid]:
                return self.queues[tid].popleft()
        # batch lane next: coalesced groups are interactive traffic, popped
        # whole (a group is one item — stealing can never split it)
        with self._batch_lock:
            if self.batch_queue:
                return self.batch_queue.popleft()
        # weighted-fair sub-lane (admission armed): one DRR pop serves the
        # per-tenant sub-queues by weight — still interactive priority,
        # ahead of stealing (a fair item has no owner engine to steal from)
        f = self._fair  # unguarded: reads the set-once published reference
        if f is not None:
            item = f.pop()
            if item is not None:
                return item
        # steal from neighbors (back — leave the owner its freshest work)
        for nb in self._neighbors(tid):
            with self.locks[nb]:
                if self.queues[nb]:
                    return self.queues[nb].pop()
        # heavy lane after every interactive source, under the weighted
        # concurrency cap: fused index-origin dispatches soak the engines
        # light traffic is not using, never all of them. Split SLICES are
        # cap-exempt continuations — their group already holds a slot, and
        # capping them would stall its gather barrier behind itself.
        with self._heavy_lock:
            if self.heavy_slices:
                return self.heavy_slices.popleft()
            if self.heavy_queue and self._heavy_inflight < self._heavy_cap():
                i = self._heavy_pick_locked()
                if i >= 0:
                    item = self.heavy_queue[i]
                    del self.heavy_queue[i]
                    self._heavy_inflight += 1
                    ten = getattr(item[1], "tenant", None)
                    if ten is not None and Global.enable_admission:
                        # stamp the counted tenant on the group so
                        # _heavy_done releases the SAME slot even if the
                        # knob or quota map changes mid-flight
                        try:
                            item[1]._adm_heavy_ten = ten
                            self._heavy_by_tenant[ten] = (
                                self._heavy_by_tenant.get(ten, 0) + 1)
                        except AttributeError:
                            pass  # __slots__ item: skip tenant accounting
                    return item
        # stream lane next-to-last: standing-query work fills idle capacity
        with self._stream_lock:
            if self.stream_queue:
                return self.stream_queue.popleft()
        # rebuild lane last: background shard healing is fully deferrable —
        # failover keeps results complete while the rebuild waits
        with self._rebuild_lock:
            if self.rebuild_queue:
                return self.rebuild_queue.popleft()
        return None

    def _run_engine(self, tid: int) -> None:
        try:
            self._engine_loop(tid)
        except BaseException as e:  # thread death (not per-query errors)
            if not self._stop.is_set():
                self._on_engine_death(tid, e)

    def _engine_loop(self, tid: int) -> None:
        from wukong_tpu.runtime.bind import get_binder

        get_binder().bind_thread(tid)  # no-op unless core binding is enabled
        engine = self._make_engine(tid)
        snooze_us = self.IDLE_SNOOZE_MIN_US
        while not self._stop.is_set():
            item = self._pop_work(tid)
            if item is None:
                # capped exponential idle backoff with wake-on-submit: the
                # semaphore wakes a sleeper the moment anything is
                # submitted, so deep relax costs no submit-path latency;
                # the doubling only thins the *poll* cadence (10us ->
                # IDLE_SNOOZE_MAX_US) so an idle pool no longer starves
                # co-located fused dispatches
                got = self._pending.acquire(timeout=snooze_us / 1e6)
                snooze_us = (self.IDLE_SNOOZE_MIN_US if got
                             else min(snooze_us * 2, self.IDLE_SNOOZE_MAX_US))
                continue
            qid, query = item
            self._inflight[tid] = item
            self._busy_since[tid] = get_usec()
            self._charge_queue_delay(query)  # overload bus: per-lane EWMA
            if qid is None:  # batch/heavy lanes: fire-and-forget items
                try:
                    from wukong_tpu.runtime import faults

                    faults.site("pool.execute", shard=tid)
                    query.run(engine)
                except Exception as e:
                    # run() settles its members on internal errors; this
                    # catches the re-raise (and fault injection) so the
                    # engine thread survives — fail_all is idempotent
                    fail = getattr(query, "fail_all", None)
                    if fail is not None:
                        fail(e)
                self._heavy_done(query)  # release the weighted heavy slot
                self._busy_since[tid] = 0
                self._inflight[tid] = None
                self._respawns[tid] = 0
                continue
            # close the queue span opened at submit (the wait IS the span)
            self._end_queue_span(query, engine=tid)
            try:
                # a query whose deadline expired while queued fails fast
                # with a structured QueryTimeout instead of occupying the
                # engine (the resilience layer's load-shedding path); the
                # pool keeps serving — nothing wedges
                dl = getattr(query, "deadline", None)
                if dl is not None and dl.expired():
                    from wukong_tpu.utils.errors import QueryTimeout

                    _M_SHED.inc()
                    maybe_note_shed("queue_deadline",
                                    getattr(query, "tenant", "default"))
                    raise QueryTimeout(
                        f"deadline expired in engine-{tid} queue")
                from wukong_tpu.runtime import faults

                faults.site("pool.execute", shard=tid)
                out = engine.execute(query)
            except Exception as e:  # engine errors become the reply
                out = e
            # cleared HERE, not in a finally: a thread-killing exception
            # must leave the in-flight marker for _on_engine_death to fail
            # the query instead of stranding its waiter
            self._busy_since[tid] = 0
            self._inflight[tid] = None
            # a served query proves the engine healthy: reset the crash
            # budget so isolated poison queries spread over time never
            # accumulate into a permanent declare-dead
            self._respawns[tid] = 0
            with self._results_lock:
                self._results[qid] = out
                ev = self._done[qid]  # capture: a racing poll() may pop it
            # append BEFORE set(): a wait()er woken by set() must find the
            # qid already in _completed so its remove() never races the append
            self._completed.append(qid)
            ev.set()
