"""Device-busy seconds by program, from the profile the harness records.

Every jitted function of the program is named ``wk_<route>_<kernel>``, so the
module XLA compiles it to is ``jit_wk_<route>_<kernel>`` (a template program
``jit_wk_template_<label>``, the join's level probe ``jit_wk_level_probe``).
On the TPU the device plane's ``XLA Modules`` line holds one event a run of a
module (``jit_wk_walk_expand(<fingerprint>)``) and its ``XLA Ops`` line the
operations: an operation belongs to the run that holds its start. On the CPU
backend the operations are events of a host plane, each with an
``hlo_module`` stat. A module's busy time is the union of its operations'
intervals inside the ``bench.window`` annotation, so the modules of one
device sum to ``xplane.reduce``'s ``busy_s``.

A program that names nothing (a parent commit before PR 36) gives modules
named by JAX (``jit_run``, ``jit_expand``): ``share_pct`` and ``busy_ms`` then
find none of the names a reader counts and return ``None``.
"""

from __future__ import annotations

import bisect
import functools
import os
from collections import defaultdict

from benchmark import xplane
from benchmark.program_spans import trace_dir

MODULES = "XLA Modules"
NAMED = "jit_wk_"


def _module(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def _device_ops(plane) -> dict[str, list] | None:
    """Module -> its operations' intervals on one device plane; ``None``
    where the plane has no modules line."""
    lines = {ln.name: ln for ln in plane.lines}
    if MODULES not in lines:
        return None
    runs = sorted((e.start_ns, e.start_ns + e.duration_ns, _module(e.name))
                  for e in lines[MODULES].events)
    starts = [r[0] for r in runs]
    out = defaultdict(list)
    for name in xplane.OP_LINES:
        for e in lines[name].events if name in lines else ():
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns < runs[i][1]:
                out[runs[i][2]].append((e.start_ns, e.start_ns + e.duration_ns))
    return out


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime: float) -> tuple:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    devices = [ops for p in planes if p.name.startswith("/device:")
               for ops in [_device_ops(p)] if ops]
    window, host = None, defaultdict(list)
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == xplane.WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif not devices:  # the CPU backend: operations on the host
                    stats = dict(e.stats)
                    if "hlo_op" in stats and "hlo_module" in stats:
                        host[str(stats["hlo_module"])].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    lo, hi = window or (float("-inf"), float("inf"))
    busy = defaultdict(float)
    for ops in devices or [host]:
        for module, intervals in ops.items():
            ns = xplane.total(xplane.clip(xplane.union(intervals), lo, hi))
            if ns > 0:
                busy[module] += ns / 1e9 / max(len(devices), 1)
    return tuple(sorted(busy.items()))


def busy_s(run) -> dict[str, float]:
    """Module -> device-busy seconds inside the traced window (the mean over
    devices); empty without a traced run or a profile."""
    if not run.trace:
        return {}
    try:
        path = xplane.find_trace(trace_dir(run))
    except FileNotFoundError:
        return {}
    return dict(_read(path, os.path.getmtime(path)))


def share_pct(run, prefix: str) -> float | None:
    """% of the window's device-busy seconds in modules whose name starts
    with ``prefix``; ``None`` where the window holds none of them."""
    by = busy_s(run)
    part = [s for m, s in by.items() if m.startswith(prefix)]
    if not part:
        return None
    return 100.0 * sum(part) / sum(by.values())


def window_replies(run) -> float:
    """Replies' worth of requests inside the traced window: each request's
    share of its time that lies inside it, summed."""
    lo, hi = run.trace["window_ns"]
    return sum((min(b, hi) - max(a, lo)) / (b - a)
               for _cls, a, b in run.trace["serves"]
               if b > a and min(b, hi) > max(a, lo))


def busy_ms_per_reply(run, module: str) -> float | None:
    """Device-busy ms of one module over the window's replies' worth."""
    by = busy_s(run)
    replies = window_replies(run) if module in by else 0.0
    return 1e3 * by[module] / replies if replies > 0 else None
