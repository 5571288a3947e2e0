"""Mean number of ``capacity.retry`` events per traced reply: the walk or a
template program run again at a larger capacity class. Should read 0 once
the warm-up has seen the template."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "capacity.retry")
                for kind in "LSFC" for r in traced(run, kind))
