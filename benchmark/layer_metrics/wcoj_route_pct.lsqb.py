"""Share of the traced replies (%) that the worst-case-optimal join route
answered: those that hold a ``wcoj.execute`` span. The route is the
program's own choice (``planner/optimizer.py:choose_strategy`` under
``join_strategy auto``, less what ``Proxy._record_wcoj_feedback`` demoted)."""
from benchmark.program_spans import span_count, traced


def read(run):
    replies = traced(run, "heavy")
    if not replies:
        return None
    hit = sum(1 for r in replies if span_count(r, names=("wcoj.execute",)))
    return 100.0 * hit / len(replies)
