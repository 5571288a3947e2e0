"""Share of the device-idle time of the traced window during which some
thread is inside a ``wk:wcoj.enumerate`` or ``wk:wcoj.compact`` annotation
(%): the chip waits for the join's host work, the candidates made and the
survivors kept. ``None`` where the profile holds no ``wcoj.compact`` (a
program before PR 36 would count the enumeration alone)."""
from benchmark import xplane
from benchmark.program_spans import annotations, intersect

HOST_JOIN = ("wcoj.enumerate", "wcoj.compact")


def read(run):
    t = run.trace
    notes = annotations(run) if t else ()
    if not any(n == "wcoj.compact" for n, _a, _b in notes):
        return None
    lo, hi = t["window_ns"]
    idle = xplane.gaps(xplane.clip(t["busy_intervals_ns"], lo, hi), lo, hi)
    idle_ns = xplane.total(idle)
    if idle_ns <= 0:
        return None
    host = xplane.union((a, b) for n, a, b in notes if n in HOST_JOIN)
    return 100.0 * xplane.total(intersect(idle, host)) / idle_ns
