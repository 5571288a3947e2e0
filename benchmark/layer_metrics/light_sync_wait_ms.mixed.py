"""Median over the light replies of the mixed queue of their summed
``*.sync`` spans (ms): what a light waits for the device behind the heavies."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import percentile


def read(run):
    return percentile([span_ms(r, suffixes=(".sync",))
                       for r in traced(run, "light")], 50)
