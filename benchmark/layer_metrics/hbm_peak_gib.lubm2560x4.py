"""The largest ``memory_stats()["peak_bytes_in_use"]`` of the four chips
after the window, in GiB: a shard's staged segments, padded to the largest
shard's, and the chain's tables in flight. The fullest chip sets what the
deployment can hold."""
from benchmark.dist_chain import device_peak_bytes


def read(run):
    peaks = device_peak_bytes(run)
    return max(peaks) / 2 ** 30 if peaks else None
