"""Mean number of ``device.dispatch`` events per light reply: calls of a
jitted function made for the query."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "device.dispatch")
                for r in traced(run, "light"))
