"""Share of the device-idle time of the traced window, outside every
``wk:*.sync``, during which some thread is inside ``wk:*.stage`` or
``wk:*.dispatch`` (%): the host is getting work to the device."""
from benchmark.program_spans import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["dispatch"]
