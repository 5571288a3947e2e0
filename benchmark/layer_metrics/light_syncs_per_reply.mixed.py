"""Mean number of ``*.sync`` spans per light reply of the mixed queue: a
capacity retry is one more round trip behind whatever the device is running."""
from benchmark.program_spans import span_count, traced
from benchmark.stats import mean


def read(run):
    return mean(span_count(r, suffixes=(".sync",))
                for r in traced(run, "light"))
