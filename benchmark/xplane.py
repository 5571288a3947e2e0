"""From a profiler trace (``.xplane.pb``) to device-busy time, the device
operations that took most of it and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. A device is a plane
whose name starts with ``/device:``; its busy time is the union of the
intervals of the events on its operation line (``XLA Ops`` where the plane has
one, else every line), cut to the traced window. The window is the
``bench.window`` annotation the harness wraps around the traced seconds; the
``serve:<class>`` annotations its clients wrap around each request are on the
same clock and label the idle gaps."""

from __future__ import annotations

import glob
import os
from collections import Counter, defaultdict

WINDOW = "bench.window"
SERVE = "serve:"
OP_LINES = ("XLA Ops",)
NAME_MAX = 96  # an operation's name in the breakdown: the head of its HLO text


def find_trace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def gaps(merged, lo, hi):
    """The complement of merged intervals inside [lo, hi)."""
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append([at, a])
        at = max(at, b)
    if hi > at:
        out.append([at, hi])
    return out


def read(path: str) -> dict:
    """A trace as plain lists: device name -> [(name, start_ns, end_ns)] of
    its operation line (every line, where it has none of ``OP_LINES``), and
    the host annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    window, serves = None, []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name in OP_LINES] or lines
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for ln in ops for e in ln.events]
            if evs:
                devices[plane.name] = evs
            continue
        for line in lines:
            for e in line.events:
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(SERVE):
                    serves.append((e.name[len(SERVE):], e.start_ns,
                                   e.start_ns + e.duration_ns))
    return {"devices": devices, "window": window, "serves": serves}


def _label(serves, a: float, b: float) -> str:
    """What the clients were in the middle of during an idle gap."""
    if not serves:
        return "unattributed"
    mid = (a + b) / 2
    inflight = Counter(c for c, s, e in serves if s <= mid < e)
    if not inflight:
        return "no request in flight"
    cls, n = inflight.most_common(1)[0]
    others = sum(inflight.values()) - n
    return f"serve:{cls} x{n}" + (f" +{others} others" if others else "")


def reduce(path: str, top: int = 10) -> dict:
    """-> window_s, busy_s (mean over devices), per-device busy, the ``top``
    operations by device seconds, the ``top`` idle gaps with labels, and the
    merged busy intervals of the first device with the serve annotations
    (for readers that cut busy time to some requests)."""
    t = read(path)
    if not t["devices"]:
        raise ValueError(f"{path}: no device plane in the trace")
    if t["window"] is None:
        evs = [e for evs in t["devices"].values() for e in evs]
        t["window"] = (min(e[1] for e in evs), max(e[2] for e in evs))
    lo, hi = t["window"]
    per_dev, op_s = {}, defaultdict(float)
    first = None
    for name in sorted(t["devices"]):
        evs = t["devices"][name]
        merged = clip(union((a, b) for _n, a, b in evs), lo, hi)
        per_dev[name] = total(merged) / 1e9
        if first is None:
            first = merged
        for n, a, b in evs:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_s[n] += d / 1e9
    busy = sum(per_dev.values()) / len(per_dev)
    idle = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "per_device_busy_s": per_dev,
        "device_ops": [[n[:NAME_MAX], s] for n, s in sorted(
            op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(t["serves"], a, b), (b - a) / 1e9]
                      for a, b in idle],
        "busy_intervals_ns": first,
        "serves": t["serves"],
        "window_ns": (lo, hi),
        "n_device_events": sum(len(evs) for evs in t["devices"].values()),
    }


def busy_within(reduced: dict, spans) -> float:
    """Device-busy seconds of the first device inside the union of ``spans``
    ((start_ns, end_ns) on the trace clock)."""
    s = 0.0
    for a, b in union(spans):
        s += total(clip(reduced["busy_intervals_ns"], a, b))
    return s / 1e9
