"""Mean per light reply of the ``*.sync`` spans (ms): the blocking fetch of
the chain's table, row count and totals. Wall time: with 16 clients it holds
the device's work and the wait for the interpreter after it."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(span_ms(r, suffixes=(".sync",)) for r in traced(run, "light"))
