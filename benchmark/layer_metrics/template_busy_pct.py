"""% of the traced window's device-busy seconds in the whole-plan template
programs (``jit_wk_template_<label>``, one a template family)."""
from benchmark.device_programs import share_pct


def read(run):
    return share_pct(run, "jit_wk_template_")
