"""Mean per traced reply of the ``wcoj.enumerate`` spans (ms): the host's
candidate enumeration (NumPy: the choice of a generator a row, the ragged
expansion, the gather of the candidates)."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean

SPAN = ("wcoj.enumerate",)


def read(run):
    return mean(span_ms(r, names=SPAN) for r in traced(run, "heavy"))
