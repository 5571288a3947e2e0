"""Device-busy ms of the join's level probe (``jit_wk_level_probe``) per
reply of the traced window (each request counted by the share of it inside
the window): what the chip spends on a reply's probes, beside
``probe_sync_ms_per_reply.lsqb``, the host's wait for them."""
from benchmark.device_programs import busy_ms_per_reply


def read(run):
    return busy_ms_per_reply(run, "jit_wk_level_probe")
