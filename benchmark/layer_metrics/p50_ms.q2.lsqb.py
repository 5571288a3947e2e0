"""Median reply time of LSQB's q2 (ms), send to the reply's table on the
host: every comment that answers a post directly and whose author knows the
post's, 4.0 M rows at scale factor 3 (some 10^7 at 10)."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(cls="lsqb_q2"), 50)
