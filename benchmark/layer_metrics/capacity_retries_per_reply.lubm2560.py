"""Mean number of ``capacity.retry`` events per traced heavy reply at
LUBM-2560: the walk or a template program run again at a larger capacity
class (an estimate that met its class, or ``table_capacity_max``). Should
read 0 once the warm-up has seen the three queries."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "capacity.retry") for r in traced(run, "heavy"))
