"""Mean per traced reply of the ``wcoj.compact`` spans (ms): the host's
keep of each run's surviving candidates and the level's concatenation into
the next prefix (NumPy: ``row_idx[mask]``, ``column_stack``). ``None`` where
no reply has the span (a program before PR 36)."""
from benchmark.program_spans import span_count, span_ms, traced
from benchmark.stats import mean

SPAN = ("wcoj.compact",)


def read(run):
    replies = traced(run, "heavy")
    if not any(span_count(r, names=SPAN) for r in replies):
        return None
    return mean(span_ms(r, names=SPAN) for r in replies)
