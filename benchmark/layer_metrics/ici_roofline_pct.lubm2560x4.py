"""Share of the chip-to-chip roofline over the collectives of the traced
window: the live rows' bytes the chains sent to other chips over the four
chips' ICI peak (``dist_chain.ICI_BYTES_PER_S``, 1,600 Gbit/s a chip), divided
by the mean of the chips' seconds in collective operations. The bytes are the
registry's ``wukong_dist_exchange_bytes_total`` a reply, times the replies'
worth of requests inside the window."""
from benchmark import xplane
from benchmark.device_programs import window_replies
from benchmark.dist_chain import ICI_BYTES_PER_S, device_intervals, \
    registry_totals


def read(run):
    t = registry_totals()
    devs = device_intervals(run)
    if not t or not devs or not run.trace.get("serves"):
        return None
    coll_ns = sum(xplane.total(c) for _b, c in devs)
    if coll_ns <= 0:
        return None
    sent = t["bytes"] / t["replies"] * window_replies(run)
    # (bytes / (chips x peak)) / (collective seconds summed / chips)
    return 100.0 * (sent / ICI_BYTES_PER_S) / (coll_ns / 1e9)
