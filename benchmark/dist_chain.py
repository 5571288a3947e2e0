"""What the readers of the sharded cell share.

A reply the sharded engine answered holds a ``dist.execute`` span (the
engine) with a ``dist.chain`` span inside it (its one program,
``wk_dist_chain``), and a ``capacity.retry`` event each time the chain ran
again at larger classes. ``Reply.events`` keeps an event's name, not its
attributes, so what the chain's collectives carried is read from the
program's registry: ``wukong_dist_exchange_rows_total``,
``wukong_dist_exchange_slots_total`` and ``wukong_dist_exchange_bytes_total``
(by collective: the live rows that left their chip, the slots shipped to
other chips with their padding, the live rows' bytes), which count every
chain the process ran, the warm-up's too, beside ``wukong_queries_total``,
every reply the proxy gave. No constant is drawn in the cell that reads them
and every reply there is the sharded engine's, so a reply of the warm-up
moves what a reply of the window does.

From the profile: each device's busy intervals (its ``XLA Ops``, as
``xplane.reduce`` reads them) and those of its collective operations, the
operations whose HLO is an all-to-all, all-gather, collective-permute,
all-reduce or reduce-scatter, on that line or, from start to done, on the
``Async XLA Ops`` line; inside the traced window.

A program without these counters or spans, or a run without a profile,
gives nothing to read: every function here returns ``None`` or nothing and
the reader leaves its metric out."""

from __future__ import annotations

import functools
import os
import re

from benchmark import xplane
from benchmark.program_spans import trace_dir

# Cloud TPU v5e: 1,600 Gbit/s of chip-to-chip interconnect a chip (Google
# Cloud documentation, "TPU v5e" system architecture)
ICI_BYTES_PER_S = 1600e9 / 8
COLLECTIVE = re.compile(r"\b(all-to-all|all-gather|collective-permute|"
                        r"all-reduce|reduce-scatter)(-start|-done)?\(")


def registry_totals() -> dict | None:
    """Rows, slots and bytes the chains' collectives carried, and the
    replies the proxy gave; ``None`` where no chain moved a row."""
    try:
        from wukong_tpu.obs.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()

    def total(name, **labels):
        return sum(float(s.get("value", 0))
                   for s in (snap.get(name) or {}).get("series", [])
                   if all(s.get("labels", {}).get(k) == v
                          for k, v in labels.items()))

    out = {"rows": total("wukong_dist_exchange_rows_total"),
           "slots": total("wukong_dist_exchange_slots_total"),
           "bytes": total("wukong_dist_exchange_bytes_total"),
           "replies": total("wukong_queries_total", status="SUCCESS")}
    return out if out["slots"] > 0 and out["replies"] > 0 else None


ASYNC_LINES = ("Async XLA Ops",)


@functools.lru_cache(maxsize=2)
def _intervals(path: str, _mtime: float) -> tuple:
    from jax.profiler import ProfileData

    window, devices = None, []
    for plane in ProfileData.from_file(path).planes:
        lines = {ln.name: ln for ln in plane.lines}
        if not plane.name.startswith("/device:"):
            for line in lines.values():
                for e in line.events:
                    if e.name == xplane.WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
            continue
        ops = [lines[n] for n in xplane.OP_LINES if n in lines] \
            or list(lines.values())
        busy = [(e.start_ns, e.start_ns + e.duration_ns)
                for ln in ops for e in ln.events]
        if not busy:
            continue
        # an asynchronous collective's transfer is on its own line, from its
        # start to its done
        coll = [(e.start_ns, e.start_ns + e.duration_ns)
                for ln in ops + [lines[n] for n in ASYNC_LINES if n in lines]
                for e in ln.events if COLLECTIVE.search(e.name)]
        devices.append((plane.name, busy, coll))
    if not devices:
        return ()
    lo, hi = window or (min(a for _n, b, _c in devices for a, _e in b),
                        max(e for _n, b, _c in devices for _a, e in b))
    return tuple((xplane.clip(xplane.union(busy), lo, hi),
                  xplane.clip(xplane.union(coll), lo, hi))
                 for _name, busy, coll in sorted(devices))


def device_intervals(run) -> tuple:
    """Per device: (busy intervals, collective intervals), merged and cut
    to the traced window, in ns on the trace's clock; empty without a
    profile."""
    if not run.trace:
        return ()
    try:
        path = xplane.find_trace(trace_dir(run))
    except FileNotFoundError:
        return ()
    return _intervals(path, os.path.getmtime(path))


def device_peak_bytes(run) -> list[int]:
    """``memory_stats()["peak_bytes_in_use"]`` of each of the cell's chips,
    read in the process that served the window; empty where the backend
    keeps no such statistics."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:run.cell.chips]]
    return [int(s["peak_bytes_in_use"]) for s in stats
            if s.get("peak_bytes_in_use")]
