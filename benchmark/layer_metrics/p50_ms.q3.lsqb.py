"""Median reply time of LSQB's q3 (ms), send to the reply's table on the
host: every ordered triangle of acquaintances who live in one country; its
widest level holds 1.6 x 10^7 candidates at scale factor 3 (8 x 10^7 at 10)."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(cls="lsqb_q3"), 50)
