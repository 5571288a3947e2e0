"""Mean number of ``*.sync`` spans per light reply: blocking round trips to
the device, one per attempt of a chain or of a template program."""
from benchmark.program_spans import span_count, traced
from benchmark.stats import mean


def read(run):
    return mean(span_count(r, suffixes=(".sync",))
                for r in traced(run, "light"))
