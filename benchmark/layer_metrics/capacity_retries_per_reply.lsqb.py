"""Mean number of ``capacity.retry`` events per traced reply of LSQB's two
patterns: the walk or a template program run again at a larger capacity
class. The join's levels have no such retry (a level's slots follow its
candidates), so this reads 0 unless a reply left the route."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "capacity.retry") for r in traced(run, "heavy"))
