"""Share of the traced replies (%) that the sharded engine answered: those
that hold a ``dist.execute`` span. A proxy that holds the sharded engine
serves every request through it (``Proxy._engine_for``); a reply that fell
back to another engine holds that engine's span instead."""
from benchmark.program_spans import span_count, traced


def read(run):
    replies = traced(run, "heavy")
    if not replies:
        return None
    hit = sum(1 for r in replies if span_count(r, names=("dist.execute",)))
    return 100.0 * hit / len(replies)
