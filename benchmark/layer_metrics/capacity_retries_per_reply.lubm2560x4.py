"""Mean number of ``capacity.retry`` events per traced reply of the sharded
chain: the whole chain run again at a larger class, of a step's rows or of an
exchange's destination. Should read 0 once the warm-up has seen the three
queries (the chain then starts at the classes it measured)."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "capacity.retry") for r in traced(run, "heavy"))
