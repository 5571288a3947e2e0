"""Share of the device-idle time of the traced window during which some
thread is inside a ``wk:*.sync`` annotation (%): the device has nothing to
run while a reply waits for it, or for the interpreter, in its fetch."""
from benchmark.program_spans import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["sync"]
