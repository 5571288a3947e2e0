"""Mean number of ``device.stage`` events per traced heavy reply at
LUBM-2560: segments put on the device inside a request. Over 0 after the
warm-up only where the store's byte budget sent one round the LRU."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "device.stage") for r in traced(run, "heavy"))
