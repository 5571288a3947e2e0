"""Share of the HBM roofline over the heavy replies of the traced window at LUBM-2560:
the bytes those replies cannot do without (``benchmark/bytes_model.py``) over
the device's peak bytes a second, divided by the device-busy seconds inside
those requests. One figure for whole replies; only requests that lie wholly
inside the traced window count."""
from benchmark import xplane
from benchmark.bytes_model import query_bytes


def read(run):
    t = run.trace
    if not t or not t["serves"]:
        return None
    lo, hi = t["window_ns"]
    texts = {r.req.cls: (r.req.text, len(r.table)) for r in run.replies
             if r.ok and r.req.kind == "heavy"}
    spans, total_bytes = [], 0
    for cls, a, b in t["serves"]:
        if cls in texts and a >= lo and b <= hi:
            spans.append((a, b))
            total_bytes += query_bytes(run.ref, *texts[cls])
    busy = xplane.busy_within(t, spans)
    if not spans or busy <= 0:
        return None
    return 100.0 * (total_bytes / run.peaks["hbm_bytes_per_s"]) / busy
