"""Mean number of ``device.stage`` events per traced reply: segments
``DeviceStore`` put on the device inside a request. Should read 0 once the
warm-up has touched every predicate of the mix."""
from benchmark.program_spans import event_count, traced
from benchmark.stats import mean


def read(run):
    return mean(event_count(r, "device.stage")
                for kind in "LSFC" for r in traced(run, kind))
