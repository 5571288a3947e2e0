"""What the readers of the worst-case-optimal join's levels share.

A traced reply the ``wcoj`` route answered holds a ``wcoj.execute`` span, a
``wcoj.level`` span a level with ``wcoj.enumerate`` (the host's candidate
enumeration) inside it and, where the level was probed on the device,
``wcoj.probe.stage``, ``wcoj.probe.dispatch`` and ``wcoj.probe.sync``; and a
``join.level`` event a level. ``Reply.events`` keeps an event's name, not its
attributes, so what a level enumerated and probed is read from the program's
registry: ``wukong_join_level_candidates_total{route}`` and
``wukong_join_level_slots_total{route}``, which count every level the
process ran, the warm-up's too, beside ``wukong_join_queries_total
{strategy="wcoj"}``, the requests planned for the route. No constant is
drawn in the cell that reads them, so a reply of the warm-up enumerates what
a reply of the window does.

A program without these spans and counters gives nothing to read: every
function here returns ``None`` or 0 and the reader leaves its metric out."""

from __future__ import annotations

LEVEL, PROBED = "wcoj.level", "wcoj.probe.stage"


def level_routes(reply) -> tuple[int, int]:
    """-> (levels of the reply, those of them on the device route): a
    ``wcoj.level`` span that holds a ``wcoj.probe.stage`` span (a level whose
    one constraint is its own generator stages and dispatches nothing more)."""
    levels = on_device = 0
    depth_of_level = None
    seen = False
    for name, depth, _t0, _dur in reply.spans:
        if name == LEVEL:
            levels += 1
            depth_of_level, seen = depth, False
        elif depth_of_level is not None and depth <= depth_of_level:
            depth_of_level = None
        elif name == PROBED and depth_of_level is not None and not seen:
            on_device += 1
            seen = True
    return levels, on_device


def registry_totals() -> dict | None:
    """Candidates and slots of every level the process ran, and the
    requests planned for the route; ``None`` where the program has no such
    counters or no level ran."""
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

    out = {"candidates": total("wukong_join_level_candidates_total"),
           "slots": total("wukong_join_level_slots_total"),
           "replies": total("wukong_join_queries_total", strategy="wcoj")}
    return out if out["candidates"] > 0 and out["replies"] > 0 else None
