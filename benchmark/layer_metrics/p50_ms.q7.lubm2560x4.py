"""Median reply time of ``lubm_q7`` over the sharded cell's LUBM (1280
universities) on four chips (ms), send to the reply's table on the host; the
sharded chain over the four shards. Set beside ``p50_ms.q7.lubm2560``, the
same query over twice the universities on one chip."""
from benchmark.stats import percentile


def read(run):
    return percentile(run.log.latencies_ms(cls="lubm_q7"), 50)
