"""Share of the join's levels (%) whose probe ran on the device: of the
``join.level`` events of the traced replies, those whose ``wcoj.level`` span
holds a ``wcoj.probe.stage`` span (an event keeps its name only, so the
route is read off the span). A level under ``join_device_min_candidates``
keeps the host kernels."""
from benchmark.program_spans import event_count, traced
from benchmark.wcoj_levels import level_routes


def read(run):
    levels = on_device = 0
    for r in traced(run, "heavy"):
        n, dev = level_routes(r)
        levels += event_count(r, "join.level") or n
        on_device += dev
    return 100.0 * on_device / levels if levels else None
