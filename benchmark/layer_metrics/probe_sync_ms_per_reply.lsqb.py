"""Mean per traced reply of the ``wcoj.probe.sync`` spans (ms): the waits
for a level's masks to come back from the device."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean

SPAN = ("wcoj.probe.sync",)


def read(run):
    return mean(span_ms(r, names=SPAN) for r in traced(run, "heavy"))
