"""The measured window: closed-loop clients on ``Proxy.serve_query``.

A copy, cut down, of ``Emulator.run_serving`` (``runtime/emulator.py``): the
clients take their requests from one seeded queue instead of drawing their
own, replies keep their rows for the check, and a reply's time runs from the
send to the reply's table on the host."""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from benchmark.stats import ReplyLog

_tls = threading.local()


class FrontSpans:
    """Spans of the benchmark's own around the proxy's parse and plan calls
    (``Proxy.serve_query`` records none): seconds per reply, kept per client
    thread. Installed in the traced run only."""

    def __init__(self, proxy):
        for name in ("_parse_text", "_plan_prepared"):
            setattr(proxy, name, self._timed(getattr(proxy, name)))

    @staticmethod
    def _timed(fn):
        def wrapper(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                _tls.front_s = getattr(_tls, "front_s", 0.0) \
                    + time.perf_counter() - t0
        return wrapper


class Reply:
    __slots__ = ("req", "t_send", "t_done", "ok", "status", "table", "cols",
                 "route", "device_broken", "spans", "events", "error",
                 "front_ms")

    def __init__(self, req):
        self.req = req
        self.table = self.cols = self.error = self.spans = self.route = None
        self.events, self.device_broken, self.front_ms = (), False, 0.0

    def rows(self) -> np.ndarray:
        """The reply's table over the SELECT variables, in SELECT order."""
        return np.asarray(self.table)[:, self.cols].astype(np.int64)


def route_of(q) -> str:
    """Which of the proxy's routes answered, from what the reply carries
    with tracing off."""
    if getattr(q, "planner_empty", False):
        return "planner-empty"
    if getattr(q, "_template_compiled", False):
        return "template.plan"
    levels = getattr(q, "join_stats", None)
    if levels:
        on_dev = any(lv.get("route") == "device" for lv in levels)
        return "wcoj:device" if on_dev else "wcoj:host"
    return "walk"


def serve(proxy, req, annotate: bool = False) -> Reply:
    """One request through the entry live traffic takes, timed."""
    r = Reply(req)
    note = contextlib.nullcontext()
    if annotate:
        import jax.profiler

        note = jax.profiler.TraceAnnotation(f"serve:{req.cls}")
    q = None
    _tls.front_s = 0.0
    r.t_send = time.perf_counter()
    try:
        with note:
            q = proxy.serve_query(req.text, blind=False)
            r.table = np.asarray(q.result.table)  # on the host, or fetched now
    except Exception as e:  # a refused or crashed request is a failed one
        r.error = f"{type(e).__name__}: {e}"[:300]
    r.t_done = time.perf_counter()
    r.front_ms = _tls.front_s * 1e3
    if q is None or r.error:
        r.ok, r.status = False, r.error or "no reply"
        return r
    res = q.result
    r.status = getattr(res.status_code, "name", str(res.status_code))
    r.ok = int(res.status_code) == 0 and bool(getattr(res, "complete", True))
    if r.ok:
        r.cols = [res.v2c_map[v] for v in res.required_vars]
    r.route = route_of(q)
    r.device_broken = bool(getattr(q, "_join_device_broken", False))
    tr = getattr(q, "trace", None)
    if tr is not None:
        r.spans = [(sp.name, sp.depth, sp.t0_us, sp.dur_us) for sp in tr.spans]
        r.events = tuple(tr.event_names())
    return r


def run_window(proxy, traffic, seconds: float, annotate: bool = False,
               on_open=None):
    """Drive the queue for ``seconds``; the window then closes as the mix
    says (reply in flight, or block in hand). -> (ReplyLog, [Reply])."""
    replies: list[list[Reply]] = [[] for _ in range(traffic.clients)]
    gate = threading.Event()
    t_open = [0.0]

    def client(k: int) -> None:
        gate.wait()
        deadline = t_open[0] + seconds
        while True:
            req = traffic.take(closing=time.perf_counter() >= deadline)
            if req is None:
                return
            replies[k].append(serve(proxy, req, annotate))

    threads = [threading.Thread(target=client, args=(k,),
                                name=f"bench-client-{k}")
               for k in range(traffic.clients)]
    for t in threads:
        t.start()
    t_open[0] = time.perf_counter()
    gate.set()
    if on_open is not None:
        on_open(t_open[0])
    for t in threads:
        t.join()
    log = ReplyLog(t_open[0])
    flat = sorted((r for rs in replies for r in rs), key=lambda r: r.req.idx)
    for r in flat:
        log.add(r.req.cls, r.req.kind, r.t_send, r.t_done, r.ok)
    return log, flat
