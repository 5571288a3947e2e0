"""Mean of the ``proxy.parse`` + ``proxy.plan`` spans over the light replies
of the traced window (ms)."""
from benchmark.spans import front_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(front_ms(r) for r in traced(run, "light"))
