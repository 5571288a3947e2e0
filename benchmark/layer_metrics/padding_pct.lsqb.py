"""Slots the levels probed over the candidates they enumerated, less one
(%): what padding to capacity classes costs the device route
(``wukong_join_level_slots_total`` over
``wukong_join_level_candidates_total``; a host level probes its candidates
as they are and adds none)."""
from benchmark.wcoj_levels import registry_totals


def read(run):
    t = registry_totals()
    return 100.0 * (t["slots"] / t["candidates"] - 1.0) if t else None
