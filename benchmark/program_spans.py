"""What the readers of the program's own tracing share.

A traced run switches ``Global.enable_tracing`` on, so every reply carries the
``QueryTrace`` the program made of it. ``driver.serve`` copies it into
``Reply.spans``, a list of ``(name, depth, t0_us, dur_us)`` in the order the
spans were opened (one thread a reply: a span's children are the deeper spans
that follow it), and ``Reply.events``, the names of the trace's events
(``device.dispatch``, ...). While a trace is live the program
also enters ``jax.profiler.TraceAnnotation("wk:" + name)`` around each span,
so the profile the harness records holds the same spans on the device
trace's clock; ``annotations`` reads them from the ``.xplane.pb`` that
``xplane.find_trace`` finds.

A program without these spans (a parent commit) gives replies with no
``proxy.execute`` span and a profile with no ``wk:`` annotation: ``traced``
is then empty, ``annotations`` too, and every reader returns ``None``.
"""

from __future__ import annotations

import functools
import os

from benchmark import xplane

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
WK = "wk:"
SERVED = "proxy.execute"  # every reply of a program with the spans has one


def traced(run, kind: str) -> list:
    """The window's good replies of one kind that carry the program's
    ``serve_query`` spans."""
    return [r for r in run.replies
            if r.ok and r.req.kind == kind and r.spans
            and any(s[0] == SERVED for s in r.spans)]


def matches(name: str, names=(), suffixes=()) -> bool:
    return name in names or name.endswith(tuple(suffixes))


def span_ms(reply, names=(), suffixes=()) -> float:
    """Summed duration of the reply's spans with one of ``names`` or ending
    in one of ``suffixes`` (``".sync"`` takes ``tpu.sync`` and
    ``template.sync``), in ms."""
    return sum(s[3] for s in reply.spans
               if matches(s[0], names, suffixes)) / 1e3


def span_count(reply, names=(), suffixes=()) -> int:
    return sum(1 for s in reply.spans if matches(s[0], names, suffixes))


def event_count(reply, name: str) -> int:
    return sum(1 for e in reply.events if e == name)


def self_ms(spans) -> dict[str, float]:
    """Span name -> ms of its own: a span's duration less what its children
    cover. ``spans`` is one reply's list, in opening order."""
    out: dict[str, float] = {}
    for i, (name, depth, _t0, dur) in enumerate(spans):
        for child in spans[i + 1:]:
            if child[1] <= depth:
                break
            if child[1] == depth + 1:
                dur -= child[3]
        out[name] = out.get(name, 0.0) + max(dur, 0) / 1e3
    return out


# ---------------------------------------------------------------------------
# the wk: annotations of the recorded profile
# ---------------------------------------------------------------------------

def trace_dir(run) -> str:
    """Where ``run.py`` writes the cell's profile."""
    return os.path.join(OUT, "trace", run.cell.name)


@functools.lru_cache(maxsize=2)
def _read_annotations(path: str, _mtime: float) -> tuple:
    from jax.profiler import ProfileData

    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(WK):
                    found.append((e.name[len(WK):], e.start_ns,
                                  e.start_ns + e.duration_ns))
    return tuple(found)


def annotations(run) -> tuple:
    """``(span name, start_ns, end_ns)`` of every ``wk:`` annotation in the
    run's profile, on the trace's clock; empty where there is no profile or
    the program wrote none."""
    try:
        path = xplane.find_trace(trace_dir(run))
    except FileNotFoundError:
        return ()
    return _read_annotations(path, os.path.getmtime(path))


def intersect(a, b) -> list:
    """The overlap of two sorted, merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_shares(run) -> dict[str, float] | None:
    """The device-idle time of the traced window split three ways, in % of
    it: ``sync`` while some thread is inside a ``*.sync`` span; ``dispatch``,
    of the rest, while some thread is inside a ``*.stage`` or ``*.dispatch``
    span; ``outside``, the rest (parse, plan, the reply side, client code,
    waiting for the interpreter). They sum to 100."""
    t = run.trace
    notes = annotations(run) if t else ()
    if not notes:
        return None
    lo, hi = t["window_ns"]
    idle = xplane.gaps(xplane.clip(t["busy_intervals_ns"], lo, hi), lo, hi)
    idle_ns = xplane.total(idle)
    if idle_ns <= 0:
        return None

    def inside(suffixes):
        return xplane.union((a, b) for n, a, b in notes
                            if n.endswith(suffixes))

    sync = intersect(idle, inside((".sync",)))
    rest = intersect(idle, xplane.gaps(sync, lo, hi))
    dispatch = intersect(rest, inside((".stage", ".dispatch")))
    sync_ns, dispatch_ns = xplane.total(sync), xplane.total(dispatch)
    return {"sync": 100.0 * sync_ns / idle_ns,
            "dispatch": 100.0 * dispatch_ns / idle_ns,
            "outside": 100.0 * (idle_ns - sync_ns - dispatch_ns) / idle_ns}
