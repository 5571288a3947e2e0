"""Mean per light reply of the time the host spends getting work to the
device (ms): the ``*.stage`` spans (pinning, program lookup, staging on a
miss) and the ``*.dispatch`` spans (the calls of jitted functions)."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(span_ms(r, suffixes=(".stage", ".dispatch"))
                for r in traced(run, "light"))
