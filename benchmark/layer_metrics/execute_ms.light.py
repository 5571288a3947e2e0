"""Mean over the light replies of the traced window of the time outside the
parse and plan spans: route choice, device engine, fetch (ms)."""
from benchmark.spans import execute_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(execute_ms(r) for r in traced(run, "light"))
