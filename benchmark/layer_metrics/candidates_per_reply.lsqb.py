"""Candidates the join's levels enumerated, a reply of the route: the
registry's ``wukong_join_level_candidates_total`` over
``wukong_join_queries_total{strategy="wcoj"}`` (every level the process
ran, the warm-up's too: no constant is drawn, so every reply of a class
enumerates the same)."""
from benchmark.wcoj_levels import registry_totals


def read(run):
    t = registry_totals()
    return t["candidates"] / t["replies"] if t else None
