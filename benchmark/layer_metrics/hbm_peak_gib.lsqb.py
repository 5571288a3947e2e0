"""``memory_stats()["peak_bytes_in_use"]`` of the serving device after the
window, in GiB: the int32 copies of the sorted tables the levels probe, the
candidate tensors of the slices in flight and their masks."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2 ** 30
