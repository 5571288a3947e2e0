"""Median reply time of ``lubm_q1`` at LUBM-2560 (ms), send to the reply's
table on the host; the walk over the staged segments. Set beside four times the same
query's median in ``lubm640-heavy``: what does not scale linearly shows in
the ratio."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(cls="lubm_q1"), 50)
