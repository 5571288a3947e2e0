"""95th percentile over all light replies of the window, send to the
reply's table on the host."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(kind="light"), 95)
