"""``memory_stats()["peak_bytes_in_use"]`` of the serving device after the
window, in GiB: what the staged segments of LUBM-2560, the template
programs' tables and the largest reply in flight take together."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2 ** 30
