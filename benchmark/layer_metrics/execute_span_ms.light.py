"""Mean over the light replies of the traced window of the program's
``proxy.execute`` span (ms): route choice and the engine behind it, without
the reply side and without the recorder's work on the finished trace."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(span_ms(r, names=("proxy.execute",))
                for r in traced(run, "light"))
