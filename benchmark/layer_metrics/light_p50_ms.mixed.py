"""Median reply time of the light replies in the mixed queue (ms): what a
light waits behind the heavies. Not an end-to-end metric of that cell: it
reads in two modes, by the order of the queue (PERF.md)."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(kind="light"), 50)
