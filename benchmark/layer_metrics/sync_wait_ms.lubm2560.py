"""Mean per heavy reply of the ``*.sync`` spans (ms) at LUBM-2560: the
blocking fetch at the end of each attempt, which is where a heavy program's
device time shows on the host."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(span_ms(r, suffixes=(".sync",)) for r in traced(run, "heavy"))
