"""Mean number of ``device.dispatch`` events (calls of a jitted function
made for the query) per reply of WatDiv's linear, star and snowflake
templates: the chains of 2-9 steps a light reply is made of."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "device.dispatch")
                for kind in "LSF" for r in traced(run, kind))
