"""Share of the traced replies (%) that a whole-plan template program
answered: those that hold a ``template.execute`` span."""
from benchmark.program_spans import span_count, traced


def read(run):
    replies = [r for kind in "LSFC" for r in traced(run, kind)]
    if not replies:
        return None
    hit = sum(1 for r in replies if span_count(r, names=("template.execute",)))
    return 100.0 * hit / len(replies)
