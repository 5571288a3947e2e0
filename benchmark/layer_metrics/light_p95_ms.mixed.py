"""95th percentile of the light replies' time in the mixed queue (ms)."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(kind="light"), 95)
