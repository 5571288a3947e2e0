"""Share of the HBM roofline over the replies of the traced window: the
bytes q2's and q3's replies cannot do without over the device's peak bytes a
second, divided by the device-busy seconds inside those requests. A request
counts where at least nine tenths of it lie inside the traced window, with
that share of its bytes and the device-busy seconds of that part: the one
client sends its first request while the profiler is still starting, so the
request that fills the window begins some milliseconds before it, and a
window of 30 s holds no other whole.

The bytes (``reply_bytes``): each sorted table a pattern names read once
(a predicate's edges two ids an edge, a class's list one id a member:
``benchmark/bytes_model.py``, from the plain reference's own counts), each
reply row written on the device and read to the host once, and each
candidate of the join's levels with one anchor once, two ids a candidate
(the registry's candidates a reply, ``benchmark/wcoj_levels.py``: the mean
over both patterns, so the share is of a whole cycle). A share of the level
probe alone waits for stable kernel names in ``benchmark/xplane.py``."""
from benchmark import xplane
from benchmark.bytes_model import ID_BYTES, query_bytes
from benchmark.wcoj_levels import registry_totals


INSIDE = 0.9  # the least share of a request inside the traced window


def reply_bytes(ref, text: str, reply_rows: int, candidates: float) -> float:
    return query_bytes(ref, text, reply_rows) + 2 * ID_BYTES * candidates


def read(run):
    t = run.trace
    if not t or not t["serves"]:
        return None
    totals = registry_totals()
    candidates = totals["candidates"] / totals["replies"] if totals else 0.0
    lo, hi = t["window_ns"]
    texts = {r.req.cls: (r.req.text, len(r.table)) for r in run.replies
             if r.ok and r.req.kind == "heavy"}
    spans, total_bytes = [], 0.0
    for cls, a, b in t["serves"]:
        inside = (min(b, hi) - max(a, lo)) / (b - a) if b > a else 0.0
        if cls in texts and inside >= INSIDE:
            spans.append((max(a, lo), min(b, hi)))
            total_bytes += inside * reply_bytes(run.ref, *texts[cls],
                                                candidates)
    busy = xplane.busy_within(t, spans)
    if not spans or busy <= 0:
        return None
    return 100.0 * (total_bytes / run.peaks["hbm_bytes_per_s"]) / busy
