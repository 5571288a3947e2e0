"""% of the traced window's device-busy seconds in modules the program
named (``jit_wk_*``): how much of the device's time a program-level
breakdown can put down to a route. The rest is JAX's own modules, such as
an eager ``jnp`` operation in host code."""
from benchmark.device_programs import NAMED, share_pct


def read(run):
    return share_pct(run, NAMED)
