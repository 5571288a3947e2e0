"""Mean over the light replies of the traced window of the program's own
``proxy.parse`` + ``proxy.plan`` spans (ms): the twin, from inside, of
``parse_plan_ms.light``."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(span_ms(r, names=("proxy.parse", "proxy.plan"))
                for r in traced(run, "light"))
