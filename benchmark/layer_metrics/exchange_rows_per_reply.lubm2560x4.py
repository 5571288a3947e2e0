"""Live binding-table rows a reply's sharded chain sent to another chip:
the registry's ``wukong_dist_exchange_rows_total`` over the replies the
proxy gave (``benchmark/dist_chain.py``)."""
from benchmark.dist_chain import registry_totals


def read(run):
    t = registry_totals()
    return t["rows"] / t["replies"] if t else None
