"""Share of the HBM roofline of the four chips over the replies of the traced
window over the sharded cell's LUBM: the bytes those replies cannot do without
(``benchmark/bytes_model.py``, the same count as ``bytes_roofline_pct.lubm2560``
whatever implements it) over four chips' peak bytes a second, divided by the
mean of the chips' busy seconds inside those requests. Only requests that lie
wholly inside the traced window count."""
from benchmark import xplane
from benchmark.bytes_model import query_bytes
from benchmark.dist_chain import device_intervals


def read(run):
    t = run.trace
    devs = device_intervals(run)
    if not t or not t["serves"] or not devs:
        return None
    lo, hi = t["window_ns"]
    texts = {r.req.cls: (r.req.text, len(r.table)) for r in run.replies
             if r.ok and r.req.kind == "heavy"}
    spans, total_bytes = [], 0
    for cls, a, b in t["serves"]:
        if cls in texts and a >= lo and b <= hi:
            spans.append((a, b))
            total_bytes += query_bytes(run.ref, *texts[cls])
    inside = xplane.union(spans)
    busy_ns = sum(xplane.total(xplane.clip(busy, a, b))
                  for busy, _c in devs for a, b in inside)
    if not spans or busy_ns <= 0:
        return None
    # (bytes / (chips x peak)) / (busy seconds summed / chips)
    return 100.0 * (total_bytes / run.peaks["hbm_bytes_per_s"]) \
        / (busy_ns / 1e9)
