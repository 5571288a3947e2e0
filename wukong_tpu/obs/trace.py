"""Per-query trace context: trace id + span stack, propagated end to end.

SURVEY §5 notes the reference has "no pervasive tracing framework" — its
only timing is the proxy-side Monitor's latency records. This module is the
structured replacement: a :class:`QueryTrace` is created at proxy receipt
(sampled via the ``enable_tracing`` / ``trace_sample_every`` knobs), carried
on the query object (``q.trace``, next to ``q.deadline``), and *activated*
as a thread-ambient context while an engine executes it, so deep layers
that never see the query (shard fetches, retry/backoff, circuit breakers,
fault injection) can attach spans and events to the right trace without
plumbing it through every signature.

Granularity contract: spans are opened per STEP (BGP step, chain dispatch,
shard fetch, stream epoch phase), never per row — with tracing off, every
hook is a single ``getattr``/``None`` check, so the hot path stays flat.

One clock with the device trace: while a :class:`QueryTrace` is live,
every span opened through :func:`span` (and ``traced_execute`` /
``traced_step``) also enters ``jax.profiler.TraceAnnotation("wk:" + name)``,
so the program's spans land on the host plane of whatever profile is being
captured, on the profiler's clock. The program starts no profiler session
for this (``device_trace`` in obs/export.py scopes one on request).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
from collections import defaultdict

from wukong_tpu.analysis.lockdep import declare_leaf, make_lock
from wukong_tpu.config import Global
from wukong_tpu.utils.timer import get_usec

# span-stack locks only ever guard list/dict appends — innermost by
# construction (the resilience layer already fires its trace hooks outside
# the breaker lock; lockdep now proves that stays true)
declare_leaf("trace.spans")

_tls = threading.local()
_trace_seq = itertools.count(1)
# one sampling sequence PER KIND: a burst of stream epochs must not skew
# the 1-in-N sampling of interactive queries (and vice versa)
_sample_seqs: dict[str, itertools.count] = {}


class Span:
    """One timed operation inside a trace. ``end()`` is idempotent and may
    run on a different thread than ``start`` (queue spans end in the engine
    thread that popped the query).

    ``index`` is the span's place in ``QueryTrace.spans`` and ``parent`` the
    index of the span that caused it (the innermost span open on the same
    thread at start; -1 at trace level)."""

    __slots__ = ("name", "t0_us", "t1_us", "attrs", "events", "depth", "tid",
                 "index", "parent")

    def __init__(self, name: str, attrs: dict, depth: int, tid: int,
                 index: int = -1, parent: int = -1):
        self.name = name
        self.t0_us = get_usec()
        self.t1_us: int | None = None
        self.attrs = attrs
        self.events: list[tuple[int, str, dict]] = []
        self.depth = depth
        self.tid = tid
        self.index = index
        self.parent = parent

    def event(self, name: str, **attrs) -> None:
        self.events.append((get_usec(), name, attrs))

    def end(self, **attrs) -> None:
        if attrs:
            self.attrs.update(attrs)
        if self.t1_us is None:
            self.t1_us = get_usec()

    @property
    def dur_us(self) -> int:
        return (self.t1_us if self.t1_us is not None else get_usec()) - self.t0_us

    def to_dict(self) -> dict:
        return {"name": self.name, "t0_us": self.t0_us,
                "dur_us": self.dur_us, "depth": self.depth,
                "parent": self.parent, "tid": self.tid,
                "attrs": dict(self.attrs),
                "events": [{"t_us": t, "name": n, "attrs": a}
                           for t, n, a in self.events]}


class QueryTrace:
    """Trace id + per-thread span stacks for one query (or stream epoch).

    Spans append under a lock: the proxy thread, the engine-pool thread
    executing the query, and (in principle) fetch helpers may all write.
    """

    def __init__(self, kind: str = "query", qid: int | None = None,
                 text: str | None = None, tenant: str = "default"):
        n = next(_trace_seq)
        self.trace_id = f"{kind[0]}{n:06d}"
        self.kind = kind
        self.qid = n if qid is None else qid
        self.text = text
        # tenant identity (obs/slo.py): the proxy stamps the bounded
        # label at admission so every recorded/dumped trace is
        # attributable to a tenant without replaying it
        self.tenant = tenant
        self.t0_us = get_usec()
        self.t1_us: int | None = None
        self.status = "RUNNING"
        self.spans: list[Span] = []  # guarded by: _lock
        self._lock = make_lock("trace.spans")
        self._stacks: dict[int, list[Span]] = defaultdict(list)  # guarded by: _lock

    # ------------------------------------------------------------------
    def start_span(self, name: str, **attrs) -> Span:
        tid = threading.get_ident()
        with self._lock:
            stack = self._stacks[tid]
            sp = Span(name, attrs, depth=len(stack), tid=tid,
                      index=len(self.spans),
                      parent=stack[-1].index if stack else -1)
            stack.append(sp)
            self.spans.append(sp)
        return sp

    def end_span(self, sp: Span, **attrs) -> None:
        sp.end(**attrs)
        with self._lock:
            # pop from whichever thread-stack holds it (cross-thread ends)
            for stack in self._stacks.values():
                if sp in stack:
                    stack.remove(sp)
                    break

    def span(self, name: str, **attrs):
        """``with trace.span(name) as sp``: :func:`span` on a trace that is
        known to be there."""
        return _SpanScope(self, name, attrs)

    def event(self, name: str, **attrs) -> None:
        """Attach to the current thread's innermost open span, falling back
        to a zero-length synthetic span at trace level."""
        tid = threading.get_ident()
        with self._lock:
            stack = self._stacks.get(tid)
            if stack:
                stack[-1].events.append((get_usec(), name, attrs))
                return
            sp = Span(name, attrs, depth=0, tid=tid, index=len(self.spans))
            sp.t1_us = sp.t0_us
            self.spans.append(sp)

    def finish(self, status: str = "SUCCESS") -> None:
        if self.t1_us is None:
            self.t1_us = get_usec()
            self.status = status

    # ------------------------------------------------------------------
    @property
    def dur_us(self) -> int:
        return (self.t1_us if self.t1_us is not None else get_usec()) - self.t0_us

    def event_names(self) -> list[str]:
        return [n for sp in self.spans for (_t, n, _a) in sp.events]  # unguarded: reporting surface on finished traces

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "kind": self.kind, "qid": self.qid,
                "tenant": self.tenant,
                "status": self.status, "t0_us": self.t0_us,
                "dur_us": self.dur_us,
                **({"text": self.text} if self.text else {}),
                "spans": [sp.to_dict() for sp in self.spans]}  # unguarded: reporting surface on finished traces


# ---------------------------------------------------------------------------
# ambient (thread-local) current trace
# ---------------------------------------------------------------------------

def current() -> QueryTrace | None:
    """The trace active on this thread, or None (the deep-layer hook)."""
    return getattr(_tls, "trace", None)


@contextlib.contextmanager
def activate(trace: QueryTrace | None):
    """Make ``trace`` this thread's ambient trace for the block. Engines
    activate ``q.trace`` around execution so shard fetches / retries /
    breakers / fault sites attach to it without seeing the query."""
    prev = getattr(_tls, "trace", None)
    _tls.trace = trace
    try:
        yield trace
    finally:
        _tls.trace = prev


def trace_event(name: str, **attrs) -> None:
    """Record an event on the ambient trace; no-op (one getattr) without one."""
    tr = getattr(_tls, "trace", None)
    if tr is not None:
        tr.event(name, **attrs)


def maybe_start_trace(kind: str = "query", qid: int | None = None,
                      text: str | None = None) -> QueryTrace | None:
    """A new QueryTrace per the ``enable_tracing`` + ``trace_sample_every``
    knobs, or None (the zero-overhead default)."""
    if not Global.enable_tracing:
        return None
    n = max(int(Global.trace_sample_every), 1)
    if n > 1:
        seq = _sample_seqs.get(kind)
        if seq is None:
            seq = _sample_seqs.setdefault(kind, itertools.count())
        if next(seq) % n:
            return None
    return QueryTrace(kind=kind, qid=qid, text=text)


# ---------------------------------------------------------------------------
# instrumentation helpers (one definition; proxy and engines share them)
# ---------------------------------------------------------------------------

_BARE = contextlib.nullcontext()  # what span() hands out with tracing off
_annotation = None  # jax.profiler.TraceAnnotation, imported on first use


def _annotate(name: str):
    """``TraceAnnotation("wk:" + name)``: the span on the profiler's clock,
    next to the device's operations, where a profile is being captured."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation("wk:" + name)


class _SpanScope:
    """``with span(tr, name) as sp``: one span of ``tr`` on this thread and
    its ``wk:`` annotation."""

    __slots__ = ("tr", "name", "attrs", "sp", "note")

    def __init__(self, tr: QueryTrace, name: str, attrs: dict):
        self.tr, self.name, self.attrs = tr, name, attrs

    def __enter__(self) -> Span:
        self.note = _annotate(self.name)
        self.note.__enter__()
        self.sp = self.tr.start_span(self.name, **self.attrs)
        return self.sp

    def __exit__(self, *exc):
        self.tr.end_span(self.sp)
        return self.note.__exit__(*exc)


def span(tr: QueryTrace | None, name: str, **attrs):
    """The context manager every layer boundary opens its span through.
    ``tr is None`` (tracing off, or the query not sampled) hands back one
    shared no-op whose ``as`` value is None, so the body runs bare; a caller
    that closes the span with attributes guards on that None."""
    if tr is None:
        return _BARE
    return _SpanScope(tr, name, attrs)


def traced_execute(q, span_name: str, body, end_attrs=None):
    """Engine execute() wrapper: activate ``q.trace`` thread-ambiently and
    span the whole execution. The untraced path is ONE getattr then
    ``body()`` — the obs hot-path contract. ``end_attrs()`` (optional)
    supplies the span's closing attributes after body ran."""
    tr = getattr(q, "trace", None)
    if tr is None:
        return body()
    with activate(tr), _SpanScope(tr, span_name, {}) as sp:
        try:
            return body()
        finally:
            if end_attrs is not None:
                sp.attrs.update(end_attrs())


def traced_step(tr, q, span_name: str, fn) -> None:
    """One BGP-step span with rows in/out (step granularity, zero per-row
    work); ``tr is None`` runs ``fn()`` bare."""
    if tr is None:
        fn()
        return
    rows_in = q.result.nrows
    with _SpanScope(tr, span_name, {"step": q.pattern_step,
                                    "pattern": repr(q.get_pattern())}) as sp:
        try:
            fn()
        finally:
            sp.attrs.update(rows_in=rows_in, rows_out=q.result.nrows)
