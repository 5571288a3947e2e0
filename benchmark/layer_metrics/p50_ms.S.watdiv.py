"""Median reply time of WatDiv's star templates (S1-...) in the
``watdiv_basic`` queue (ms), send to the reply's table on the host."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(kind="S"), 50)
