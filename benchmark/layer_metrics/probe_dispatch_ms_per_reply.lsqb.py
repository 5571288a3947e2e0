"""Mean per traced reply of the ``wcoj.probe.dispatch`` spans (ms): the
calls that hand a level's padded candidate tensors to the jitted probe."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean

SPAN = ("wcoj.probe.dispatch",)


def read(run):
    return mean(span_ms(r, names=SPAN) for r in traced(run, "heavy"))
