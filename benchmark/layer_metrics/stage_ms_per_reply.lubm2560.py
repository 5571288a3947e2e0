"""Mean per heavy reply of the ``*.stage`` spans (ms) at LUBM-2560: host
time spent pinning, looking programs up and, where the byte budget evicted
one since its last use, putting a segment back on the device."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(span_ms(r, suffixes=(".stage",)) for r in traced(run, "heavy"))
