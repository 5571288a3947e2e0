"""% of the traced window's device-busy seconds in the walk's programs
(``jit_wk_walk_*``: the TPU engine's chain and its merge executor)."""
from benchmark.device_programs import share_pct


def read(run):
    return share_pct(run, "jit_wk_walk_")
