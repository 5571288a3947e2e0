"""Share of the row slots shipped to other chips that carried no row (%):
100 x (1 - live rows / slots), from ``wukong_dist_exchange_rows_total`` and
``wukong_dist_exchange_slots_total``. An all-to-all ships its per-destination
capacity class whatever is live; what padding costs the exchange."""
from benchmark.dist_chain import registry_totals


def read(run):
    t = registry_totals()
    return 100.0 * (1.0 - t["rows"] / t["slots"]) if t else None
