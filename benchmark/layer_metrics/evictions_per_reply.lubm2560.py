"""Mean number of ``device.evict`` events per traced heavy reply at
LUBM-2560: segments ``DeviceStore`` took off the device inside a request,
for its byte budget or at an unpin. Each answers a ``device.stage``."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "device.evict") for r in traced(run, "heavy"))
