"""The rest of the device-idle time of the traced window (%): no thread is
inside a sync, stage or dispatch span. Parse, plan, the reply side, client
code, waiting for the interpreter. With ``idle_while_sync_pct`` and
``idle_while_dispatch_pct`` it sums to 100."""
from benchmark.program_spans import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["outside"]
