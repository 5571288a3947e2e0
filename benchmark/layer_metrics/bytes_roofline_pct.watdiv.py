"""Share of the HBM roofline over the replies of the traced window: the
bytes those replies cannot do without (``benchmark/bytes_model.py``, from
the data's own shapes) over the device's peak bytes a second, divided by the
device-busy seconds inside those requests. Only requests that lie wholly
inside the traced window count; a request's bytes are its class's mean over
the window's replies (within a class the drawn constant moves only the
constant's own index list)."""
from benchmark import xplane
from benchmark.bytes_model import query_bytes
from benchmark.stats import mean


def read(run):
    t = run.trace
    if not t or not t["serves"]:
        return None
    lo, hi = t["window_ns"]
    per_text: dict[str, int] = {}
    by_class: dict[str, list] = {}
    for r in run.replies:
        if not r.ok:
            continue
        if r.req.text not in per_text:
            per_text[r.req.text] = query_bytes(run.ref, r.req.text,
                                               len(r.table))
        by_class.setdefault(r.req.cls, []).append(per_text[r.req.text])
    class_bytes = {c: mean(v) for c, v in by_class.items()}
    spans, total_bytes = [], 0.0
    for cls, a, b in t["serves"]:
        if cls in class_bytes and a >= lo and b <= hi:
            spans.append((a, b))
            total_bytes += class_bytes[cls]
    busy = xplane.busy_within(t, spans)
    if not spans or busy <= 0:
        return None
    return 100.0 * (total_bytes / run.peaks["hbm_bytes_per_s"]) / busy
