"""Mean number of ``device.stage`` events per traced reply of LSQB's two
patterns: sorted tables ``JoinTableCache`` put on the device, or segments
``DeviceStore`` staged, inside a request. Should read 0 once the warm-up
has seen both."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "device.stage") for r in traced(run, "heavy"))
