"""Trace exporters: Chrome trace-event JSON (Perfetto) + JAX device profiler.

``chrome_trace_events`` flattens QueryTraces into the Chrome trace-event
format (``chrome://tracing`` / https://ui.perfetto.dev): spans become
complete ("X") events, span events become instants ("i"), one virtual
thread row per (trace, real thread) so concurrent queries don't interleave
on one track. ``write_chrome_trace`` wraps that in the JSON envelope.

``device_trace`` scopes the JAX profiler around a block — the
XProf/TensorBoard view of the device side of a traced query, with the
trace's spans beside it as ``wk:`` annotations. ``maybe_device_trace``
gates it on the ``xprof_dir`` config knob (env form ``WUKONG_XPROF_DIR``)
so the proxy/emulator wire it unconditionally at zero default cost.
"""

from __future__ import annotations

import contextlib
import json
import os


@contextlib.contextmanager
def device_trace(logdir: str):
    """Capture a JAX profiler trace of everything inside the block."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def maybe_device_trace():
    """``device_trace`` when a capture dir is configured — the
    ``xprof_dir`` knob first, then the ``WUKONG_XPROF_DIR`` env form —
    else a nullcontext, so callers wrap hot paths unconditionally and
    EXPLAIN ANALYZE can point operators at a capture without env
    plumbing."""
    try:
        from wukong_tpu.config import Global

        logdir = str(Global.xprof_dir) or None
    except Exception:
        logdir = None
    logdir = logdir or os.environ.get("WUKONG_XPROF_DIR")
    return device_trace(logdir) if logdir else contextlib.nullcontext()


# ---------------------------------------------------------------------------
# Chrome trace-event JSON
# ---------------------------------------------------------------------------

def chrome_trace_events(traces) -> list[dict]:
    """Flatten traces into Chrome trace-event dicts (ts/dur in usec)."""
    events: list[dict] = []
    tid_map: dict[tuple, int] = {}

    def vtid(trace, real_tid) -> int:
        key = (trace.trace_id, real_tid)
        if key not in tid_map:
            tid_map[key] = len(tid_map) + 1
            events.append({
                "name": "thread_name", "ph": "M", "pid": 0,
                "tid": tid_map[key],
                "args": {"name": f"{trace.trace_id} "
                                 f"[{trace.kind} qid={trace.qid}]"}})
        return tid_map[key]

    for tr in traces:
        for sp in tr.spans:
            t = vtid(tr, sp.tid)
            events.append({
                "name": sp.name, "cat": tr.kind, "ph": "X",
                "ts": sp.t0_us, "dur": max(sp.dur_us, 1), "pid": 0, "tid": t,
                "args": {**sp.attrs, "trace_id": tr.trace_id,
                         "parent": sp.parent}})
            for (ts, name, attrs) in sp.events:
                events.append({
                    "name": name, "cat": tr.kind, "ph": "i", "s": "t",
                    "ts": ts, "pid": 0, "tid": t,
                    "args": {**attrs, "trace_id": tr.trace_id}})
    return events


def write_chrome_trace(path: str, traces) -> str:
    """Write traces as a Perfetto-loadable JSON file; returns the path."""
    payload = {"traceEvents": chrome_trace_events(traces),
               "displayTimeUnit": "ms"}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path
