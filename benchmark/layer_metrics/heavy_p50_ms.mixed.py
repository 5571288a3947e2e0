"""Median reply time of the heavy replies in the mixed queue (ms)."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(kind="heavy"), 50)
