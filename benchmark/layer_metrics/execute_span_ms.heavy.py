"""Mean over the heavy replies of the traced window of the program's
``proxy.execute`` span (ms): the twin, from inside, of ``execute_ms.heavy``."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(span_ms(r, names=("proxy.execute",))
                for r in traced(run, "heavy"))
