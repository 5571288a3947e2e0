"""Median reply time of ``lubm_q2`` at LUBM-2560 (ms), send to the reply's
table on the host; a whole-plan template program. Set beside four times the same
query's median in ``lubm640-heavy``: what does not scale linearly shows in
the ratio."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(cls="lubm_q2"), 50)
